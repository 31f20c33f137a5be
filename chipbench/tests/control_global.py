"""The controls of a cell whose operation is a host's ``download_global``: the
result is altered underneath the program, which ``correct`` has to catch.

    python3 chipbench/tests/control_global.py --break flip --workload host-reland-ep4 --seed 7 --seconds 1 --trace 0

runs the whole cell with the break in place and prints the usual last line,
whose ``correct`` must be false. Every break leaves the program's own
verification passing, so only the benchmark's comparison with the reference
can object:

  flip      one bit of one byte of ONE ranged task of every operation differs
            (the first sink after the header's to reach its middle piece),
            altered where the sink takes the piece, before the program's
            checksums
  copy      chip 2's copy of what every chip keeps differs in one bit of one
            word, altered after the program's per-chip verification has
            passed; chip 0's and the others' are sound
  misplace  one expert's tensor comes back on another chip than its rank's
            (a copy by ``jax.device_put``, bit for bit): ``placement_exact``
            broken
"""

from __future__ import annotations

import contextlib
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE), os.path.dirname(os.path.dirname(HERE))]


@contextlib.contextmanager
def broken(how: str):
    """Alter what every ``download_global`` returns while the block runs."""
    import jax
    import numpy as np

    from dragonfly2_tpu.client import device as device_lib
    from dragonfly2_tpu.ops.hbm_sink import HBMSink

    sound_pull = device_lib.download_global
    sound_land, sound_fan = HBMSink.land_piece, HBMSink.replicate
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

    def replicate(self, mesh, axis_name: str = "d"):
        received = sound_fan(self, mesh, axis_name)
        devices = list(mesh.devices.flat)
        if not received or how != "copy" or len(devices) < 3:
            return received
        words = self._assembled
        at = min(self.content_length // 8, self.padded_words - 1)
        copies = [s.data if s.device != devices[2]
                  else s.data.at[at].set(s.data[at] ^ np.uint32(0x10))
                  for s in words.addressable_shards]
        self._assembled = jax.make_array_from_single_device_arrays(
            words.shape, words.sharding, copies)
        return received

    async def download_global(daemon, url, shardings, **kwargs):
        armed[0], header[0] = how == "flip", None
        tensors = await sound_pull(daemon, url, shardings, **kwargs)
        if how == "misplace":
            name = next(n for n in tensors if ".mlp.experts.20." in n)
            wrong = next(d for d in jax.devices()
                         if d not in tensors[name].devices())
            tensors[name] = jax.device_put(tensors[name], wrong)
        return tensors

    device_lib.download_global = download_global
    HBMSink.land_piece, HBMSink.replicate = land_piece, replicate
    try:
        yield
    finally:
        device_lib.download_global = sound_pull
        HBMSink.land_piece, HBMSink.replicate = sound_land, sound_fan


def main(argv: list[str]) -> int:
    import run

    how = argv[argv.index("--break") + 1]
    rest = [a for i, a in enumerate(argv)
            if a != "--break" and (i == 0 or argv[i - 1] != "--break")]
    with broken(how):
        return run.main(rest)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
