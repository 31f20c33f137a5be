"""What "webdataset shards read sample by sample into record batches" has to
mean, in plain Python and numpy: from each shard's bytes and its list of
members alone (name and size, in the archive's order), where every sample's
members lie (tar arithmetic: a 512-byte header, the data, padding to the next
block), the order one host of a pod reads an epoch in, as the README documents
it of ``dataset.PodShardedLoader`` (shuffle the shards, shuffle inside each,
flatten, stride by host, interleave over the open shards, all from
``random.Random("dfdataset:<seed>:<epoch>")``), and batch ``k`` of
``dataset.device_feed.DeviceFeed``: the planned samples' bytes of one
extension, a row each, zeros after them, the last batch short.

It imports nothing of the program (no ``dataset/``, no ``ops/``) and no jax.
The benchmark has its copy with its generator
(``chipbench/objects/tar_shard_feed.py``); the tests in
``test_feed_reference.py`` hold the program to this one.
"""

from __future__ import annotations

import random

import numpy as np

BLOCK = 512


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


def batches(contents: list[bytes], members: list[list], plan: list,
            batch_size: int, record_bytes: int, ext: str) -> list[tuple]:
    """[(keys as (shard, key), rows as (records, record_bytes) uint8)] of a
    host's epoch ``plan`` over shards given by their bytes and members."""
    samples = [samples_of(m) for m in members]
    out = []
    for at in range(0, len(plan), batch_size):
        items = plan[at:at + batch_size]
        rows = np.zeros((len(items), record_bytes), np.uint8)
        keys = []
        for row, (shard, sample) in zip(rows, items):
            key, parts = samples[shard][sample]
            offset, size = parts[ext]
            row[:size] = np.frombuffer(contents[shard], np.uint8,
                                       size, offset)
            keys.append((shard, key))
        out.append((keys, rows))
    return out
