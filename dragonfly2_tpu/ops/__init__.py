"""TPU compute ops: HBM piece sink + on-device checksums (JAX)."""

from dragonfly2_tpu.ops.checksum import checksum_numpy
from dragonfly2_tpu.ops.hbm_sink import HBMSink

__all__ = ["HBMSink", "checksum_numpy"]
