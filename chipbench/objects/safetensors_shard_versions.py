"""Object kind ``safetensors_shard_versions``: a chain of versions of the
shard of ``safetensors_shard``, as expert-specialised fine-tuning publishes
them, and the plain reference of what a delta between two of them is.

Object ``index`` is VERSION ``index``. Version 0 is ``safetensors_shard``'s
file for the seed, byte for byte. Version v+1 is version v with
``versions.experts_a_version`` of the layer's routed experts drawn again (a
seeded choice without replacement inside a version; an expert is its
gate_proj, up_proj and down_proj): every other tensor, the header and every
offset stay as they were, byte for byte. A tensor's bytes are a pure function
of (seed, tensor index, the last version that drew it), so the origin child
and the checking process make the same bytes without sharing any, and the
tensors that versions share are made once a process and held once.

The reference, written out plainly from the documented algorithm and from
the generator alone:

  * a version's tensors: ``numpy.frombuffer`` of the generator's bytes;
  * the content-defined chunks of a version: the gear hash of the 32 bytes
    ending at byte i is ``sum(gear[b[i-j]] << j for j in range(32)) mod
    2**32`` with ``gear[x]`` the first four bytes (little-endian) of
    sha256(bytes([x])); byte i may end a chunk where the hash's top
    ``mask_bits`` bits are zero; from a chunk's start the first such end at
    least ``min_size`` on closes it if it is at most ``max_size`` on, else
    the chunk is cut at ``max_size``; what is left at the end is the last
    chunk;
  * ``expected_plan(v)``: the chunks of version v+1, which of them version v
    holds (same sha256, same length, anywhere), hence the bytes a delta
    fetches and reuses and the spans it fetches (fetched chunks that touch
    are one span).

Nothing here imports the program or jax; the origin child loads it too.
"""

from __future__ import annotations

import concurrent.futures
import hashlib
import threading

import numpy as np

from objects import safetensors_shard
from objects.safetensors_shard import JAX_DTYPE, LAYER, NUMPY_VIEW

WINDOW = 32
GEAR = np.array([int.from_bytes(hashlib.sha256(bytes([x])).digest()[:4],
                                "little") for x in range(256)], np.uint32)
_BLOCK = 1 << 20      # bytes hashed at a time: the hashes stay in the cache
_THREADS = 8


def cut_candidates(data: np.ndarray, mask_bits: int) -> np.ndarray:
    """Where a chunk may end (exclusive offsets, ascending): i + 1 for every
    byte i of ``data`` whose gear hash has its top ``mask_bits`` bits zero."""
    shift = np.uint32(32 - mask_bits)

    def one(lo: int) -> np.ndarray:
        start = max(0, lo - (WINDOW - 1))
        h = GEAR[data[start:lo + _BLOCK]]
        # h[i] = sum over j < 2**k of gear[b[i-j]] << j, k doubling.
        for span in (1, 2, 4, 8, 16):
            h[span:] += h[:-span] << np.uint32(span)
        return lo + 1 + np.flatnonzero((h[lo - start:] >> shift) == 0)

    with concurrent.futures.ThreadPoolExecutor(_THREADS) as pool:
        found = list(pool.map(one, range(0, data.size, _BLOCK)))
    return np.concatenate(found) if found else np.zeros((0,), np.int64)


def chunk_ends(candidates: np.ndarray, total: int, min_size: int,
               max_size: int) -> list[int]:
    """The chunks' ends, the last one ``total``."""
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


def chunks_of(data: np.ndarray, cdc: dict) -> list[tuple[int, int, str]]:
    """(offset, length, sha256 hex) of every chunk of ``data``."""
    ends = chunk_ends(cut_candidates(data, int(cdc["mask_bits"])), data.size,
                      int(cdc["min_size"]), int(cdc["max_size"]))
    starts = [0] + ends[:-1]
    with concurrent.futures.ThreadPoolExecutor(_THREADS) as pool:
        digests = list(pool.map(
            lambda se: hashlib.sha256(data[se[0]:se[1]]).hexdigest(),
            zip(starts, ends)))
    return [(s, e - s, d) for s, e, d in zip(starts, ends, digests)]


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
            "reused_bytes": reused,
            "spans": [(s, e) for s, e in spans]}


def item_checksums(raw: np.ndarray, dtype: str) -> tuple[int, int]:
    """(sum mod 2**32, xor) of a tensor's items as unsigned integers of the
    item's width, little-endian: what the check takes of every tensor on the
    device."""
    items = raw.view(NUMPY_VIEW[dtype])
    return (int(np.sum(items, dtype=np.uint64) & 0xFFFFFFFF),
            int(np.bitwise_xor.reduce(items)))


class Objects(safetensors_shard.Objects):
    """The versions of one shard for a configuration and seed."""

    distinct = True
    KEPT = 24       # versions behind the newest whose own tensors are held

    def __init__(self, config: dict, seed: int):
        super().__init__(config, seed)
        self.cdc = config["versions"]["cdc"]
        self.experts_a_version = int(config["versions"]["experts_a_version"])
        self._index = {name: i for i, (name, _, _) in enumerate(self.tensors)}
        self._drawn: dict[int, tuple[int, ...]] = {}
        self._made: dict[tuple[int, int], concurrent.futures.Future] = {}
        self._lock = threading.Lock()
        self._chunks: dict[int, list] = {}
        self._sums: dict[tuple[int, int], tuple[int, int]] = {}

    # -- the generator -----------------------------------------------------

    def drawn(self, version: int) -> tuple[int, ...]:
        """The routed experts that ``version`` (1 on) draws again."""
        if version not in self._drawn:
            routed = self.widths["n_routed_experts"]
            self._drawn[version] = tuple(sorted(
                np.random.default_rng([self.seed, 0xE5F7, version]).choice(
                    routed, self.experts_a_version, replace=False).tolist()))
        return self._drawn[version]

    def epoch(self, name: str, version: int) -> int:
        """The last version up to ``version`` that drew ``name``'s bytes."""
        prefix = LAYER + "mlp.experts."
        if not name.startswith(prefix):
            return 0
        expert = int(name[len(prefix):].split(".", 1)[0])
        for v in range(version, 0, -1):
            if expert in self.drawn(v):
                return v
        return 0

    def changed(self, version: int) -> list[str]:
        """The tensors that differ between ``version - 1`` and ``version``."""
        return [name for name, _, _ in self.tensors
                if version and self.epoch(name, version) == version]

    def _draw(self, name: str, epoch: int) -> np.ndarray:
        if not epoch:
            return super().tensor_bytes(name)
        index, dtype = self._index[name], self.tensors[self._index[name]][1]
        begin, end = self.spans[name]
        words = np.random.PCG64([self.seed, index, epoch]).random_raw(
            (end - begin + 7) // 8).view(np.uint32)
        if dtype == "BF16":
            words &= np.uint32(0x8FFF8FFF)
            words |= np.uint32(0x30003000)
        return words.view(np.uint8)[: end - begin]

    def tensor_bytes(self, name: str, version: int = 0) -> np.ndarray:
        """uint8 array of ``name``'s bytes in ``version``, made once a
        process for all the versions that share them."""
        key = (self._index[name], self.epoch(name, version))
        with self._lock:
            made = self._made.get(key)
            mine = made is None
            if mine:
                made = self._made[key] = concurrent.futures.Future()
                for old in [k for k in self._made
                            if 0 < k[1] < version - self.KEPT]:
                    del self._made[old]
        if mine:
            try:
                made.set_result(self._draw(name, key[1]))
            except BaseException as e:
                made.set_exception(e)
        return made.result()

    def segments(self, index: int = 0):
        yield np.frombuffer(self.head, np.uint8)
        with concurrent.futures.ThreadPoolExecutor(4) as pool:
            yield from pool.map(lambda name: self.tensor_bytes(name, index),
                                [name for name, _, _ in self.tensors])

    def content(self, index: int = 0) -> np.ndarray:
        return np.concatenate(list(self.segments(index)))

    # -- the reference -----------------------------------------------------

    def expected(self, name: str, rows: slice | None,
                 version: int = 0) -> np.ndarray:
        """``numpy.frombuffer`` of the generator's bytes of that version, as
        rows of bytes."""
        _, dtype, shape = self.tensors[self._index[name]]
        want = np.frombuffer(self.tensor_bytes(name, version),
                             NUMPY_VIEW[dtype]).reshape(shape).view(
            np.uint8).reshape(shape[0], -1)
        return want if rows is None else want[rows]

    def expected_checksums(self, version: int) -> dict[str, tuple[int, int]]:
        """name -> ``item_checksums`` of the tensor in ``version``."""
        out = {}
        for name, dtype, _ in self.tensors:
            key = (self._index[name], self.epoch(name, version))
            if key not in self._sums:
                self._sums[key] = item_checksums(
                    self.tensor_bytes(name, version), dtype)
            out[name] = self._sums[key]
        return out

    def chunks(self, version: int) -> list[tuple[int, int, str]]:
        if version not in self._chunks:
            self._chunks[version] = chunks_of(self.content(version), self.cdc)
        return self._chunks[version]

    def expected_plan(self, version: int) -> dict:
        """The delta that takes a host from ``version`` to ``version + 1``."""
        return delta_plan(self.chunks(version + 1), self.chunks(version))

    def matches(self, item, version: int = 0) -> bool:
        name, rows, meta, got = item
        if not name:
            return got == sorted(n for n, _, _ in self.tensors)
        _, dtype, shape = self.tensors[self._index[name]]
        return (meta == (JAX_DTYPE[dtype], shape, 1)
                and np.array_equal(got, self.expected(name, rows, version)))
