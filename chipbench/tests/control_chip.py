"""The second control of a cell that places an object on several chips: ONE
chip's copy is altered after the program's own per-chip verification has
passed, which only the benchmark's comparison on every chip can catch.

    python3 chipbench/tests/control_chip.py --chip 2 --workload shard-reland-4chip --seed 7 --seconds 1 --trace 0

runs the whole cell with the break in place and prints the usual last line,
whose ``correct`` must be false. ``control.py --break flip`` breaks the
landing on chip 0 and so all four copies; this one leaves chip 0 and the
other chips sound and flips one bit of one word on chip ``--chip`` alone, in
what ``as_words()`` hands out and every view is cut from.
"""

from __future__ import annotations

import contextlib
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE), os.path.dirname(os.path.dirname(HERE))]


@contextlib.contextmanager
def broken(chip: int):
    """Alter the copy on the mesh's ``chip``-th device of every placement
    whole-on-every-chip, after the program has verified it."""
    import jax
    import numpy as np

    from dragonfly2_tpu.ops.hbm_sink import HBMSink

    sound = HBMSink.replicate

    def replicate(self, mesh, axis_name: str = "d"):
        received = sound(self, mesh, axis_name)
        if not received:
            return received
        words = self._assembled
        victim = list(mesh.devices.flat)[chip]
        at = (self.total_pieces // 2) * self.piece_words + self.piece_words // 3
        copies = [s.data if s.device != victim
                  else s.data.at[at].set(s.data[at] ^ np.uint32(0x10))
                  for s in words.addressable_shards]
        self._assembled = jax.make_array_from_single_device_arrays(
            words.shape, words.sharding, copies)
        return received

    HBMSink.replicate = replicate
    try:
        yield
    finally:
        HBMSink.replicate = sound


def main(argv: list[str]) -> int:
    import run

    chip = int(argv[argv.index("--chip") + 1])
    rest = [a for i, a in enumerate(argv)
            if a != "--chip" and (i == 0 or argv[i - 1] != "--chip")]
    with broken(chip):
        return run.main(rest)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
