"""The plain reference of the deployment ``moonlight-shard-8hosts``: "every
host ends with the generator's bytes; the origin sends each byte about once;
nobody goes back to the source".

Independent of the code under test: nothing here imports ``dragonfly2_tpu``.
The object is the benchmark generator's bytes; what a host must hold is
those bytes (their sha256, their per-piece (sum32, xor32) by NumPy), what
host 0 must hold on its device is each tensor as ``numpy.frombuffer`` reads
it at the safetensors header's offsets, and what the origin may have served
for the whole operation is the content once, with a tenth to spare.
"""

from __future__ import annotations

import hashlib
import json
import struct

import numpy as np

# safetensors dtype -> the NumPy type of the same bytes (bfloat16 has none:
# its bits are compared as uint16).
NUMPY_BITS = {"BF16": "<u2", "F16": "<u2", "F32": "<u4", "I32": "<u4",
              "U16": "<u2", "U8": "u1", "I8": "u1", "I64": "<u8"}
ORIGIN_AMPLIFICATION_MAX = 1.1


def sha256(content: bytes) -> str:
    return "sha256:" + hashlib.sha256(content).hexdigest()


def piece_checksums(content: bytes, piece_bytes: int) -> np.ndarray:
    """(pieces, 2) uint32: each piece's little-endian uint32 words summed
    (wrapping) and xor-ed, a short last word padded with zeros."""
    rows = []
    for at in range(0, len(content), piece_bytes):
        raw = content[at:at + piece_bytes]
        words = np.frombuffer(raw + b"\0" * (-len(raw) % 4), "<u4")
        rows.append((int(words.sum(dtype=np.uint64)) & 0xFFFFFFFF,
                     int(np.bitwise_xor.reduce(words))))
    return np.asarray(rows, np.uint64).astype(np.uint32)


def tensors(content: bytes) -> dict:
    """{name: (dtype, shape, the tensor's bits as a NumPy array)}."""
    (header_len,) = struct.unpack("<Q", content[:8])
    header = json.loads(content[8:8 + header_len])
    data = memoryview(content)[8 + header_len:]
    out = {}
    for name, meta in header.items():
        if name == "__metadata__":
            continue
        begin, end = meta["data_offsets"]
        bits = np.frombuffer(data[begin:end], NUMPY_BITS[meta["dtype"]])
        out[name] = (meta["dtype"], tuple(meta["shape"]),
                     bits.reshape(meta["shape"]))
    return out


def origin_bounds(content: bytes) -> tuple[int, int]:
    """The bytes the origin serves for one operation of any number of
    hosts: at least the content, at most 1.1 times it."""
    return len(content), int(ORIGIN_AMPLIFICATION_MAX * len(content))
