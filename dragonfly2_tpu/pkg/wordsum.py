"""The host's half of the piece checksum (sum32, xor32), on numpy alone.

The definition and the device's half are ops/checksum.py's; this half lives
here so that a process that must never import jax (a seed peer, a daemon
without a device sink: ``cli.main.assert_no_jax``) can take the sums of the
bytes it commits (delta/resolver.py's piece jobs).
"""

from __future__ import annotations

import numpy as np


def checksum_numpy(data) -> tuple[int, int]:
    """Host-side reference: (sum32, xor32) of any bytes-like, its last word
    padded with zeros (the few bytes of a tail are copied, never the data).
    The sum is taken at the word's own width: a uint32 reduction wraps mod
    2^32, which IS sum32's definition, and it runs at the xor's speed,
    where a sum widened to uint64 goes through numpy's buffered cast at a
    third of it (PERF.md section 5, "The passes, alone")."""
    whole = len(data) - len(data) % 4
    words = np.frombuffer(data, dtype="<u4", count=whole // 4)
    s = int(np.add.reduce(words, dtype=np.uint32))
    x = int(np.bitwise_xor.reduce(words))
    if whole < len(data):
        last = int.from_bytes(bytes(data[whole:]), "little")
        s, x = (s + last) & 0xFFFFFFFF, x ^ last
    return s, x
