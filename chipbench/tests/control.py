"""The control and the broken timed path: a run whose landing is altered
underneath the program, which ``correct`` has to catch.

    python3 chipbench/tests/control.py --break flip --workload shard-cold --seed 7 --seconds 1 --trace 0

runs a whole cell (on the chip, at the cell's own size) with the break in
place and prints the usual last line, whose ``correct`` must be false.
tests/test_control.py does the same at the rehearsal's size on the CPU.

The configurations state no numeric precision; what they guarantee is that
the bytes in HBM are the origin's. The control breaks that guarantee in the
smallest way, and both breaks are made where the answer is produced, before
the program takes its own checksums, so the program's verification passes
and only the benchmark's comparison with the generator can object:

  flip  one bit of one byte of one piece differs
  zero  the last piece is left out (zeros land in its place)
"""

from __future__ import annotations

import contextlib
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE), os.path.dirname(os.path.dirname(HERE))]


@contextlib.contextmanager
def broken(how: str):
    """Alter what the sink lands, for every task, while the block runs."""
    from dragonfly2_tpu.ops.hbm_sink import HBMSink

    sound = HBMSink.land_piece

    def land_piece(self, piece_num: int, data: bytes) -> None:
        if how == "flip" and piece_num == self.total_pieces // 2:
            data = bytearray(data)
            data[len(data) // 3] ^= 0x10
            data = bytes(data)
        elif how == "zero" and piece_num == self.total_pieces - 1:
            data = bytes(len(data))
        return sound(self, piece_num, data)

    HBMSink.land_piece = land_piece
    try:
        yield
    finally:
        HBMSink.land_piece = sound


def main(argv: list[str]) -> int:
    import run

    how = argv[argv.index("--break") + 1]
    rest = [a for i, a in enumerate(argv)
            if a != "--break" and (i == 0 or argv[i - 1] != "--break")]
    with broken(how):
        return run.main(rest)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
