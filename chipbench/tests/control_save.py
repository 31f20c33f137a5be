"""The control of the cell whose operation is a save and a resume: something
is altered underneath the program or the check, and the run has to say so.

    python3 chipbench/tests/control_save.py --break flip --workload ckpt-save-resume --seed 7 --seconds 1 --trace 0

runs the whole cell with the break in place from the window's first
operation on (set-up and the warm-up are sound) and prints the usual last
line:

  flip     one bit of one byte of the replica's stored file differs, altered
           after the ack and after the resume that read it, before the
           benchmark hashes the file: every operation finishes, and the
           check objects to a replica that is not the reference writer's
           (``correct`` false, ``failed`` 0)
  replica  the second host's copy is deleted together with this host's,
           before the resume: no holder is left, the P2P-only resume has
           nowhere to go back to, the operation FAILS and is counted
           (``failed`` 1 and more, ``correct`` false)
"""

from __future__ import annotations

import contextlib
import glob
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE), os.path.dirname(os.path.dirname(HERE))]


@contextlib.contextmanager
def broken(how: str):
    from drivers import closed_loop_save as driver

    sound = (driver.window, driver.read_replica, driver.lose_host_copy)
    state = {"on": False}

    async def window(cell, seconds, traced):
        state["on"] = True
        return await sound[0](cell, seconds, traced)

    def read_replica(cell, task_id):
        if how == "flip" and state["on"]:
            path, = glob.glob(os.path.join(cell.replica.home, "**", task_id,
                                           "data"), recursive=True)
            with open(path, "r+b") as f:
                f.seek(os.path.getsize(path) // 2)
                byte = f.read(1)
                f.seek(-1, os.SEEK_CUR)
                f.write(bytes([byte[0] ^ 0x10]))
        return sound[1](cell, task_id)

    async def lose_host_copy(cell, task_id):
        await sound[2](cell, task_id)
        if how == "replica" and state["on"]:
            reply = await cell.replica.call("Daemon.DeleteTask",
                                            {"task_id": task_id})
            print(f"[control] host 1's copy deleted too: {reply}", flush=True)

    driver.window, driver.read_replica = window, read_replica
    driver.lose_host_copy = lose_host_copy
    try:
        yield
    finally:
        driver.window, driver.read_replica, driver.lose_host_copy = sound


def main(argv: list[str]) -> int:
    import run

    how = argv[argv.index("--break") + 1]
    rest = [a for i, a in enumerate(argv)
            if a != "--break" and (i == 0 or argv[i - 1] != "--break")]
    with broken(how):
        return run.main(rest)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
