"""Object kind ``tar_shard_feed``: the shards of ``tar_shard`` as ONE host's
input pipeline reads them, sample by sample, and the plain reference of what
that pipeline has to deliver.

The shards are ``tar_shard``'s, byte for byte, but for two header blocks a
shard: there the first sample's jpg alone carries the shard's index in its
name, which leaves its txt and json under another key, a sample with no jpg.
Here all three members of the first sample carry it
(``shard000003-000000000.jpg|txt|json``), so every sample of every shard has
its jpg, as webdataset's grouping by key needs. Keys repeat from shard to
shard (the layout is drawn once a seed); a sample is named by (shard, key).

The reference is what the README documents of ``dataset.PodShardedLoader`` and
``dataset.device_feed.DeviceFeed``, written out plainly from the generator
alone: where a member's bytes lie follows from tar arithmetic (a 512-byte
header, the data, padding to the next block) over ``members(i)``, never from
the program's index; the epoch order is: shuffle the shards, shuffle inside
each, flatten, stride by host, interleave over ``interleave`` open shards, all
from ``random.Random("dfdataset:<seed>:<epoch>")``; batch ``k`` is the
``batch_size`` samples from ``k * batch_size`` on, each one's jpg bytes in a
row of ``record_bytes``, zeros after them, the epoch's last batch short.

Nothing here imports the program or jax; the origin child loads it too.
"""

from __future__ import annotations

import collections
import random

import numpy as np

from objects import tar_shard

BLOCK = tar_shard.BLOCK


def data_offsets(members) -> list[tuple[str, int, int]]:
    """(name, offset of the first data byte, size) of every member of an
    archive of plain ustar members written one after the other."""
    out, at = [], 0
    for name, size in members:
        out.append((name, at + BLOCK, size))
        at += BLOCK + (size + BLOCK - 1) // BLOCK * BLOCK
    return out


def samples_of(members) -> list[tuple[str, dict]]:
    """[(key, {extension: (data offset, size)})] in the archive's order:
    webdataset's grouping, the key a member's name up to the first dot of
    its last path component, the extension all that follows."""
    found: dict[str, dict] = {}
    for name, offset, size in data_offsets(members):
        slash = name.rfind("/") + 1
        stem, _, ext = name[slash:].partition(".")
        found.setdefault(name[:slash] + stem, {}).setdefault(
            ext, (offset, size))
    return list(found.items())


def interleaved(items: list, open_shards: int) -> list:
    """``items`` ((shard, sample) in order) dealt round-robin from up to
    ``open_shards`` shards at a time: shards open in the order they first
    appear, one that runs out makes room for the next."""
    if open_shards <= 1:
        return list(items)
    waiting: list[list] = []
    by_shard: dict[int, list] = {}
    for item in items:
        if item[0] not in by_shard:
            by_shard[item[0]] = []
            waiting.append(by_shard[item[0]])
        by_shard[item[0]].append(item)
    out, open_now = [], []
    while waiting or open_now:
        while waiting and len(open_now) < open_shards:
            open_now.append(waiting.pop(0))
        queue = open_now.pop(0)
        out.append(queue.pop(0))
        if queue:
            open_now.append(queue)
    return out


def epoch_plan(counts: list[int], seed: int, epoch: int, num_hosts: int,
               host_id: int, open_shards: int) -> list[tuple[int, int]]:
    """One host's (shard, sample) order of an epoch, as documented."""
    rng = random.Random(f"dfdataset:{seed}:{epoch}")
    shards = list(range(len(counts)))
    rng.shuffle(shards)
    flat = []
    for shard in shards:
        inside = list(range(counts[shard]))
        rng.shuffle(inside)
        flat += [(shard, sample) for sample in inside]
    return interleaved(flat[host_id::num_hosts], open_shards)


def record_checksums(rows: np.ndarray) -> np.ndarray:
    """(records, 2) uint32: (sum32, xor32) of each row's bytes as
    little-endian uint32 words."""
    words = np.ascontiguousarray(rows).view("<u4")
    return np.stack([
        (np.sum(words, axis=1, dtype=np.uint64) & 0xFFFFFFFF)
        .astype(np.uint32),
        np.bitwise_xor.reduce(words, axis=1)], axis=1)


class Objects(tar_shard.Objects):
    """The configuration's ``feed.shards`` shards of a seed, and the batches
    its host must be handed."""

    KEPT = 5    # shards' bytes held at once: an interleave of 4 and one more

    def __init__(self, config: dict, seed: int):
        super().__init__(config, seed)
        self.feed = config["feed"]
        self.shards = int(self.feed["shards"])
        self.ext = self.feed["ext"]
        self.record_bytes = int(self.feed["record_bytes"])
        self.batch_size = int(self.feed["batch_size"])
        self._contents: "collections.OrderedDict[int, np.ndarray]" = \
            collections.OrderedDict()
        self._plans: dict[int, list] = {}
        # Every shard's samples lie where shard 0's do; the first one's key
        # alone differs.
        self._samples = samples_of(self.members(0))
        longest = max(parts[self.ext][1] for _, parts in self._samples)
        if longest > self.record_bytes:
            raise ValueError(
                f"seed {seed}: a {self.ext} of {longest} bytes in records of "
                f"{self.record_bytes} (the shard's last one takes what is "
                "left of the shard)")

    # -- the generator -----------------------------------------------------

    def members(self, index: int) -> list[tuple[str, int]]:
        lead = f"shard{index:06d}-"
        return [(lead + name, size) for name, size in self.layout[:3]] \
            + self.layout[3:]

    def content(self, index: int) -> np.ndarray:
        out = super().content(index)
        for member in (1, 2):
            at = int(self.header_blocks[member]) * BLOCK
            out[at:at + BLOCK] = np.frombuffer(
                tar_shard._header(*self.members(index)[member]), np.uint8)
        return out

    # -- the reference -----------------------------------------------------

    def samples_a_shard(self) -> int:
        return len(self._samples)

    def key(self, shard: int, sample: int) -> str:
        key = self._samples[sample][0]
        return key if sample else f"shard{shard:06d}-" + key.split("-", 1)[1]

    def plan(self, epoch: int) -> list[tuple[int, int]]:
        if epoch not in self._plans:
            self._plans[epoch] = epoch_plan(
                [len(self._samples)] * self.shards, self.seed, epoch,
                int(self.feed["num_hosts"]), int(self.feed["host_id"]),
                int(self.feed["interleave"]))
        return self._plans[epoch]

    def batches_an_epoch(self) -> int:
        return -(-len(self.plan(0)) // self.batch_size)

    def planned(self, epoch: int, k: int) -> list[tuple[int, int]]:
        return self.plan(epoch)[k * self.batch_size:(k + 1) * self.batch_size]

    def expected_keys(self, epoch: int, k: int) -> list[tuple[int, str]]:
        """(shard, key) of batch ``k``'s records, in order."""
        return [(shard, self.key(shard, sample))
                for shard, sample in self.planned(epoch, k)]

    def _content(self, shard: int) -> np.ndarray:
        if shard in self._contents:
            self._contents.move_to_end(shard)
        else:
            self._contents[shard] = self.content(shard)
            while len(self._contents) > self.KEPT:
                self._contents.popitem(last=False)
        return self._contents[shard]

    def expected_batch(self, epoch: int, k: int) -> np.ndarray:
        """Batch ``k`` of ``epoch``: (records, record_bytes) uint8."""
        items = self.planned(epoch, k)
        rows = np.zeros((len(items), self.record_bytes), np.uint8)
        for row, (shard, sample) in zip(rows, items):
            offset, size = self._samples[sample][1][self.ext]
            row[:size] = self._content(shard)[offset:offset + size]
        return rows

    def payload_bytes(self, epoch: int, k: int) -> int:
        return sum(self._samples[sample][1][self.ext][1]
                   for _, sample in self.planned(epoch, k))
