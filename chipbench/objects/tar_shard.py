"""Object kind ``tar_shard``: a stream of distinct webdataset tar shards.

Shard ``i`` is a valid ustar archive of exactly ``shard_bytes``: samples of
``<key>.jpg``, ``<key>.txt`` and ``<key>.json`` with heavy-tailed jpg sizes,
then two zero blocks. Its bytes are a pure function of (seed, i). The member
layout is drawn once per seed (keys are unique within a shard, as webdataset
needs; the first member's name carries the shard's index), so that making a
shard is two NumPy passes over its bytes and no Python loop: the payload
is one pool of random words per seed, rotated and xor-ed per shard, the
padding zeroed by one mask, and the precomputed header blocks written over
it by rows. The origin serves several shards a second; a generator that
held the GIL would set the pace of the cell. Nothing here imports the
program or jax.
"""

from __future__ import annotations

import numpy as np

BLOCK = 512


def _header(name: str, size: int) -> bytes:
    """One ustar header block for a regular file."""
    h = bytearray(BLOCK)
    h[0:len(name)] = name.encode()
    h[100:108] = b"0000644\0"
    h[108:116] = b"0000000\0"
    h[116:124] = b"0000000\0"
    h[124:136] = b"%011o\0" % size
    h[136:148] = b"%011o\0" % 0          # mtime
    h[148:156] = b" " * 8                # checksum field while summing
    h[156:157] = b"0"
    h[257:263] = b"ustar\0"
    h[263:265] = b"00"
    h[148:156] = b"%06o\0 " % sum(h)
    return bytes(h)


def _padded(size: int) -> int:
    return (size + BLOCK - 1) // BLOCK * BLOCK


class Objects:
    """Shard ``index`` of a configuration and seed; every index differs."""

    typed = False
    distinct = True

    def __init__(self, config: dict, seed: int):
        self.seed = seed
        self.o = config["object"]
        self.length = int(self.o["shard_bytes"])
        if self.length % BLOCK:
            raise ValueError("shard_bytes must be a multiple of 512")
        self.pool = np.random.PCG64([seed, 0x7a7]).random_raw(
            self.length // 8).view(np.uint32)
        self.layout = self._layout()
        # 0xFF over payload, 0 over each member's padding and the closing
        # zero blocks; and the block number of every header.
        self.keep = np.full(self.length, 0xFF, np.uint8)
        blocks, at = [], 0
        for _, size in self.layout:
            blocks.append(at // BLOCK)
            at += BLOCK
            self.keep[at + size:at + _padded(size)] = 0
            at += _padded(size)
        if at + 2 * BLOCK != self.length:
            raise AssertionError(f"layout ends at {at}")
        self.keep[at:] = 0
        self.header_blocks = np.asarray(blocks)
        self.headers = np.frombuffer(b"".join(
            _header(name, size) for name, size in self.layout),
            np.uint8).reshape(-1, BLOCK)

    def size(self, index: int = 0) -> int:
        return self.length

    def _layout(self) -> list[tuple[str, int]]:
        """(name, size) of every member, filling the shard exactly."""
        rng = np.random.default_rng([self.seed, 1])
        o = self.o
        left = self.length - 2 * BLOCK
        out: list[tuple[str, int]] = []
        n = 0
        while True:
            key = f"{n:09d}"
            jpg = int(np.clip(rng.lognormal(np.log(o["jpg_median_bytes"]),
                                            o["jpg_sigma"]),
                              o["jpg_min_bytes"], o["jpg_max_bytes"]))
            txt = int(rng.integers(16, 257))
            meta = int(rng.integers(300, 901))
            small = 2 * BLOCK + _padded(txt) + _padded(meta)
            need = BLOCK + _padded(jpg) + small
            # The sample after this one must still fit with a jpg of one
            # block; else this jpg takes all that is left.
            last = left - need < 2 * BLOCK + small
            if last:
                jpg = left - small - BLOCK
            out += [(key + ".jpg", jpg), (key + ".txt", txt),
                    (key + ".json", meta)]
            if last:
                return out
            left -= need
            n += 1

    def members(self, index: int) -> list[tuple[str, int]]:
        first = (f"shard{index:06d}-" + self.layout[0][0], self.layout[0][1])
        return [first] + self.layout[1:]

    def content(self, index: int) -> np.ndarray:
        """The whole shard as one uint8 array."""
        key = np.random.default_rng([self.seed, index, 2]).integers(
            0, 1 << 32, size=2, dtype=np.uint32)
        # The pool rotated by a whole number of words and xor-ed with a
        # word, written in one pass.
        turn = int(key[0] % self.pool.size)
        words = np.empty_like(self.pool)
        np.bitwise_xor(self.pool[turn:], key[1], out=words[:words.size - turn])
        np.bitwise_xor(self.pool[:turn], key[1], out=words[words.size - turn:])
        out = words.view(np.uint8)
        out &= self.keep
        out.reshape(-1, BLOCK)[self.header_blocks] = self.headers
        out[:BLOCK] = np.frombuffer(_header(*self.members(index)[0]),
                                    np.uint8)
        return out

    def segments(self, index: int):
        yield self.content(index)
