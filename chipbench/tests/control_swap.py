"""The control of the cell whose operation is a hot-swap: something is
altered underneath the program or the check, and the last line's ``correct``
has to come out false.

    python3 chipbench/tests/control_swap.py --break live --workload shard-swap --seed 7 --seconds 1 --trace 0

runs the whole cell with the break in place from the window's first swap on
(set-up and the warm-up are sound) and prints the usual last line:

  live     one bit of one word of the LIVE generation's buffer differs,
           inside a run that the swap copies HBM -> HBM (the embedding):
           the program's own gate must refuse the flip, so the swap FAILS
           (``failed`` 1), the old generation stays live and the window ends
  store    one bit of one byte of the base version differs in this host's
           store, inside a chunk the delta reuses: the resolver's digest
           catches it during the copy, fetches the chunk again and counts
           ``corrupt_base``; the swap succeeds, and the check objects to a
           fetched count, and a ranged task, that the reference never has
  version  every swap installs the routed experts of the generation before
           it: tensors of the wrong version are what is served, and what
           the check reads
  torn     one of the reader's notes pairs the new generation's frozen
           tensor with the old generation's expert
"""

from __future__ import annotations

import contextlib
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE), os.path.dirname(os.path.dirname(HERE))]


@contextlib.contextmanager
def broken(how: str):
    from dragonfly2_tpu.ops import hbm_sink
    from drivers import closed_loop_swap as driver

    sound = (driver.window, driver.download_delta,
             hbm_sink.DoubleBuffer.flip, driver.Reader.note)
    state = {"cell": None, "last": None, "forged": False}

    async def window(cell, seconds, traced):
        state["cell"] = cell
        if how == "store":
            store = cell.fabric.daemon.task_manager.storage \
                .find_completed_task(cell.tasks[cell.version])
            at = cell.objects.data_start \
                + cell.objects.spans[driver.FROZEN][1] // 2
            with open(store._data_path, "r+b") as f:
                f.seek(at)
                byte = f.read(1)
                f.seek(at)
                f.write(bytes([byte[0] ^ 0x10]))
        return await sound[0](cell, seconds, traced)

    async def download_delta(daemon, url, *, hot, **rest):
        if how == "live" and state["cell"] is not None:
            generation, words, tensors = hot.snapshot()
            objects = state["cell"].objects
            at = (objects.data_start
                  + objects.spans[driver.FROZEN][1] // 2) // 4
            hot._state = (generation, words.at[at].set(words[at] ^ 0x10),
                          tensors)
        return await sound[1](daemon, url, hot=hot, **rest)

    def flip(self, buffer, tensors):
        if how == "version" and state["cell"] is not None:
            tensors = {**tensors, **{
                name: old for name, old in self.tensors().items()
                if ".mlp.experts." in name}}
        return sound[2](self, buffer, tensors)

    def note(self, snapshot):
        seen = sound[3](self, snapshot)
        last, state["last"] = state["last"], seen
        if (how == "torn" and state["cell"] is not None and last is not None
                and last[0] != seen[0] and not state["forged"]):
            state["forged"] = True
            return seen[0], seen[1], last[2]
        return seen

    driver.window, driver.download_delta = window, download_delta
    hbm_sink.DoubleBuffer.flip, driver.Reader.note = flip, note
    try:
        yield
    finally:
        driver.window, driver.download_delta = sound[:2]
        hbm_sink.DoubleBuffer.flip, driver.Reader.note = sound[2:]


def main(argv: list[str]) -> int:
    import run

    how = argv[argv.index("--break") + 1]
    rest = [a for i, a in enumerate(argv)
            if a != "--break" and (i == 0 or argv[i - 1] != "--break")]
    with broken(how):
        return run.main(rest)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
