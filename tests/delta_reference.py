"""What "version v+1 of a checkpoint as a delta against version v" has to
mean, in plain Python and numpy: the content-defined chunks of a version, as
``delta/chunker.py`` documents the algorithm, and from two versions' chunks
what a delta fetches and what it reuses.

The gear hash of the 32 bytes ending at byte i is ``sum(gear[b[i-j]] << j
for j in range(32)) mod 2**32`` with ``gear[x]`` the first four bytes
(little-endian) of sha256(bytes([x])); byte i may end a chunk where the
hash's top ``mask_bits`` bits are zero; from a chunk's start the first such
end at least ``min_size`` on closes it if it is at most ``max_size`` on, else
the chunk is cut at ``max_size``; what is left at the end is the last chunk.
A chunk of the new version is reused where the base holds a chunk of the
same sha256 and length, anywhere; every other chunk is fetched, and fetched
chunks that touch are one span.

It also makes the versions the tests swap through: a safetensors file of an
embedding and a layer's experts, each version the one before with a seeded
choice of experts drawn again and nothing else touched.

It imports nothing of the program (no ``delta/``, no ``ops/``) and no jax.
The benchmark has its copy with its generator
(``chipbench/objects/safetensors_shard_versions.py``); the tests in
``test_delta_reference.py`` hold the program to this one.
"""

from __future__ import annotations

import hashlib
import json
import struct

import numpy as np

WINDOW = 32
GEAR = np.array([int.from_bytes(hashlib.sha256(bytes([x])).digest()[:4],
                                "little") for x in range(256)], np.uint32)
_BLOCK = 1 << 20


def cut_candidates(data: np.ndarray, mask_bits: int) -> np.ndarray:
    """Where a chunk may end (exclusive offsets, ascending)."""
    shift = np.uint32(32 - mask_bits)
    found = []
    for lo in range(0, data.size, _BLOCK):
        start = max(0, lo - (WINDOW - 1))
        h = GEAR[data[start:lo + _BLOCK]]
        for span in (1, 2, 4, 8, 16):
            h[span:] += h[:-span] << np.uint32(span)
        found.append(lo + 1 + np.flatnonzero((h[lo - start:] >> shift) == 0))
    return np.concatenate(found) if found else np.zeros((0,), np.int64)


def chunk_ends(candidates: np.ndarray, total: int, min_size: int,
               max_size: int) -> list[int]:
    ends: list[int] = []
    start = 0
    while True:
        i = int(np.searchsorted(candidates, start + min_size))
        if i < len(candidates) and candidates[i] - start <= max_size:
            cut = int(candidates[i])
        elif total - start >= max_size:
            cut = start + max_size
        else:
            break
        ends.append(cut)
        start = cut
    if start < total:
        ends.append(total)
    return ends


def chunks_of(content: bytes, mask_bits: int, min_size: int,
              max_size: int) -> list[tuple[int, int, str]]:
    """(offset, length, sha256 hex) of every chunk of ``content``."""
    data = np.frombuffer(content, np.uint8)
    ends = chunk_ends(cut_candidates(data, mask_bits), data.size, min_size,
                      max_size)
    return [(s, e - s, hashlib.sha256(content[s:e]).hexdigest())
            for s, e in zip([0] + ends[:-1], ends)]


def delta_plan(new: list, base: list) -> dict:
    """What a delta of ``new`` against ``base`` (both ``chunks_of``) moves."""
    held = {(digest, length) for _, length, digest in base}
    spans: list[list[int]] = []
    fetched = reused = 0
    for offset, length, digest in new:
        if (digest, length) in held:
            reused += length
            continue
        fetched += length
        if spans and spans[-1][1] == offset:
            spans[-1][1] = offset + length
        else:
            spans.append([offset, offset + length])
    return {"chunks": len(new), "fetched_bytes": fetched,
            "reused_bytes": reused, "spans": [(s, e) for s, e in spans]}


# -- the versions ----------------------------------------------------------

def _bf16_bits(rng: np.random.Generator, shape) -> np.ndarray:
    """Random finite normal bfloat16 values, as their uint16 bits (a TPU
    canonicalises NaNs and flushes denormals; the CPU keeps them, and the
    tests run on both)."""
    return ((rng.integers(0, 1 << 16, shape, dtype=np.uint16)
             & np.uint16(0x8FFF)) | np.uint16(0x3000))


def version_tensors(seed: int, version: int, *, experts: int = 16,
                    redrawn: int = 2) -> dict[str, np.ndarray]:
    """name -> uint16 bits (BF16) or float32 values of ``version``: an
    embedding, a float32 bias and ``experts`` experts of three matrices;
    version v+1 draws ``redrawn`` of the experts again."""
    def epoch(expert: int) -> int:
        for v in range(version, 0, -1):
            if expert in np.random.default_rng([seed, 77, v]).choice(
                    experts, redrawn, replace=False):
                return v
        return 0

    def draw(*key) -> np.random.Generator:
        return np.random.default_rng([seed, *key])

    out = {"model.embed_tokens.weight": _bf16_bits(draw(1), (1024, 128)),
           "model.layers.1.mlp.gate.e_score_correction_bias":
               draw(2).random(experts, dtype=np.float32)}
    for e in range(experts):
        for m, (name, shape) in enumerate((("down_proj", (128, 96)),
                                           ("gate_proj", (96, 128)),
                                           ("up_proj", (96, 128)))):
            out[f"model.layers.1.mlp.experts.{e}.{name}.weight"] = \
                _bf16_bits(draw(3, e, m, epoch(e)), shape)
    return dict(sorted(out.items()))


def safetensors_file(tensors: dict[str, np.ndarray]) -> bytes:
    """The tensors as a safetensors file whose data starts 2 bytes into a
    word (uint16 arrays are BF16 bits)."""
    header, blobs, at = {}, [], 0
    for name, array in tensors.items():
        raw = array.tobytes()
        header[name] = {"dtype": "BF16" if array.dtype == np.uint16
                        else "F32", "shape": list(array.shape),
                        "data_offsets": [at, at + len(raw)]}
        blobs.append(raw)
        at += len(raw)
    head = json.dumps(header, separators=(",", ":")).encode()
    head += b" " * ((2 - (8 + len(head))) % 4)
    return struct.pack("<Q", len(head)) + head + b"".join(blobs)
