"""The control of the cell whose operation is a batch of the dataset feed: the
result is altered underneath the program, which ``correct`` has to catch.

    python3 chipbench/tests/control_feed.py --break flip --workload feed-records --seed 7 --seconds 1 --trace 0

runs the whole cell with the break in place and prints the usual last line,
whose ``correct`` must be false, with no operation failed. Every break leaves
the program's own verification passing (or, the last, the program going on as
it is built to), so only the benchmark's comparison with the reference can
object:

  flip   one bit of one byte of ONE record of every batch differs, altered
         where the feed's sink takes the record, before the program's
         checksums (set-up's shard pulls land in no sink)
  swap   the first two (shard, key) of every batch change places; the rows
         stay
  numpy  the device path fails at the first batch, and the feed goes on with
         NumPy batches, as it is built to: not on the device
"""

from __future__ import annotations

import contextlib
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE), os.path.dirname(os.path.dirname(HERE))]


@contextlib.contextmanager
def broken(how: str):
    """Alter what every batch of a ``DeviceFeed`` holds while the block
    runs."""
    from dragonfly2_tpu.dataset.device_feed import DeviceFeed
    from dragonfly2_tpu.ops.hbm_sink import HBMSink

    sound = HBMSink.land_piece, DeviceFeed._land, DeviceFeed._land_hbm

    def land_piece(self, piece_num: int, data: bytes) -> None:
        if how == "flip" and piece_num == self.total_pieces // 2:
            data = bytearray(data)
            data[len(data) // 3] ^= 0x10
            data = bytes(data)
        return sound[0](self, piece_num, data)

    def _land(self, keys, shards, *rest):
        if how == "swap" and len(keys) > 1:
            keys[:2], shards[:2] = keys[1::-1], shards[1::-1]
        return sound[1](self, keys, shards, *rest)

    def _land_hbm(self, records):
        if how == "numpy":
            raise RuntimeError("control: the device path is broken")
        return sound[2](self, records)

    HBMSink.land_piece = land_piece
    DeviceFeed._land, DeviceFeed._land_hbm = _land, _land_hbm
    try:
        yield
    finally:
        HBMSink.land_piece = sound[0]
        DeviceFeed._land, DeviceFeed._land_hbm = sound[1:]


def main(argv: list[str]) -> int:
    import run

    how = argv[argv.index("--break") + 1]
    rest = [a for i, a in enumerate(argv)
            if a != "--break" and (i == 0 or argv[i - 1] != "--break")]
    with broken(how):
        return run.main(rest)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
