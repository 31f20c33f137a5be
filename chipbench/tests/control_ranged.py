"""The control of a cell whose operation is a rank's ranged pull: the result
is altered underneath the program, which ``correct`` has to catch.

    python3 chipbench/tests/control_ranged.py --break flip --workload rank-cold --seed 7 --seconds 1 --trace 0

runs the whole cell with the break in place and prints the usual last line,
whose ``correct`` must be false. Both breaks leave the program's own
verification passing, so only the benchmark's comparison with the
generator can object:

  flip   one bit of one byte of ONE ranged task of every operation differs
         (the first span's to reach its middle piece; the header's task,
         the first sink of a call, stays sound), altered where the sink
         takes the piece, before the program's checksums
  stray  the result also holds one tensor of another rank (a copy of one of
         the rank's own under the other's name): ``only_selected`` broken
"""

from __future__ import annotations

import contextlib
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE), os.path.dirname(os.path.dirname(HERE))]


@contextlib.contextmanager
def broken(how: str):
    """Alter what every ``download_sharded`` returns while the block runs."""
    from dragonfly2_tpu.client import device as device_lib
    from dragonfly2_tpu.ops.hbm_sink import HBMSink

    sound_pull, sound_land = device_lib.download_sharded, HBMSink.land_piece
    armed = [False]
    header = [None]     # the first sink to land since the call: the header's

    def land_piece(self, piece_num: int, data: bytes) -> None:
        if armed[0] and header[0] is None:
            header[0] = id(self)
        if armed[0] and id(self) != header[0] \
                and piece_num == self.total_pieces // 2:
            armed[0] = False
            data = bytearray(data)
            data[len(data) // 3] ^= 0x10
            data = bytes(data)
        return sound_land(self, piece_num, data)

    async def download_sharded(*args, **kwargs):
        armed[0], header[0] = how == "flip", None
        tensors = await sound_pull(*args, **kwargs)
        if how == "stray":
            own = next(n for n in tensors if ".mlp.experts.0." in n)
            stray = own.replace(".mlp.experts.0.", ".mlp.experts.63.")
            tensors[stray] = tensors[own]
        return tensors

    device_lib.download_sharded, HBMSink.land_piece = download_sharded, land_piece
    try:
        yield
    finally:
        device_lib.download_sharded, HBMSink.land_piece = sound_pull, sound_land


def main(argv: list[str]) -> int:
    import run

    how = argv[argv.index("--break") + 1]
    rest = [a for i, a in enumerate(argv)
            if a != "--break" and (i == 0 or argv[i - 1] != "--break")]
    with broken(how):
        return run.main(rest)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
