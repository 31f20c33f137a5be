"""What "whole on every chip" has to mean, in plain numpy: from an object's
bytes, the piece size of its landing and the number of chips, the words
every chip must hold, every piece's (sum32, xor32), and every tensor of a
safetensors object by ``np.frombuffer`` at the header's own offsets.

It imports nothing of the program (no ``ops/``, no jax): the tests in
``test_mesh_placement.py`` hold the program to it.
"""

from __future__ import annotations

import json

import numpy as np

_NUMPY = {"F32": "<f4", "F16": "<f2", "BF16": "<u2", "I32": "<i4",
          "I16": "<i2", "I8": "i1", "U8": "u1", "U16": "<u2", "U32": "<u4"}


def piece_words(piece_bytes: int) -> int:
    """A piece as whole 32-bit words (a single-piece landing's piece is its
    content, which need not be whole words)."""
    return -(-piece_bytes // 4)


def words_on_every_chip(content: bytes, piece_bytes: int,
                        chips: int) -> list[np.ndarray]:
    """One uint32 array a chip: the content as little-endian words, then
    zeros up to whole pieces. Every chip holds the same."""
    pieces = max(1, -(-len(content) // piece_bytes))
    total = pieces * piece_words(piece_bytes)
    raw = np.zeros(4 * total, np.uint8)
    raw[:len(content)] = np.frombuffer(content, np.uint8)
    words = raw.view("<u4")
    return [words.copy() for _ in range(chips)]


def piece_checksums(content: bytes, piece_bytes: int) -> np.ndarray:
    """(pieces, 2) uint32: the sum mod 2**32 and the xor of each piece's
    words, the zero tail of the last piece included (it changes neither)."""
    words = words_on_every_chip(content, piece_bytes, 1)[0]
    rows = words.reshape(-1, piece_words(piece_bytes)).astype(np.uint64)
    sums = (rows.sum(axis=1) & 0xFFFFFFFF).astype(np.uint32)
    xors = np.bitwise_xor.reduce(rows, axis=1).astype(np.uint32)
    return np.stack([sums, xors], axis=1)


def tensors(content: bytes) -> dict[str, np.ndarray]:
    """Every tensor of a safetensors object, as the format defines it: an
    8-byte little-endian header length, the JSON header, and each tensor's
    bytes at ``data_offsets`` after the header. BF16 comes back as its
    uint16 bit patterns (numpy has no bfloat16)."""
    n = int.from_bytes(content[:8], "little")
    header = json.loads(content[8:8 + n])
    out = {}
    for name, meta in header.items():
        if name == "__metadata__":
            continue
        begin, end = meta["data_offsets"]
        dtype = np.dtype(_NUMPY[meta["dtype"]])
        out[name] = np.frombuffer(
            content, dtype, count=(end - begin) // dtype.itemsize,
            offset=8 + n + begin).reshape(meta["shape"])
    return out
