"""The dataset plane on an embedded daemon against the plain reference beside
this file (``feed_reference.py``): ``PodShardedLoader.over_daemon`` on a real
TaskManager (shards named by URL, indexed by streaming each once through a
whole-file task, every sample a read of its span out of that store, no task
made for it) feeding ``DeviceFeed(force_hbm=True)`` (the one ``HBMSink``, on
the CPU backend), held to the reference's keys, order and every byte, the
short last batch included, for one host and for each host of two; and what
the plane stamps on its flight ring while it does so.
"""

from __future__ import annotations

import io
import random
import tarfile
import types

import numpy as np
import pytest

from dragonfly2_tpu.dataset import LoaderOptions, PodShardedLoader
from dragonfly2_tpu.dataset.device_feed import DeviceFeed
from dragonfly2_tpu.dataset.shard_reader import RANGE_READS
from dragonfly2_tpu.pkg import flight
from dragonfly2_tpu.pkg.testing import start_range_origin
from dragonfly2_tpu.source.client import default_registry
from tests import feed_reference as ref

SHARDS, SAMPLES, RECORD, BATCH, SEED = 3, 13, 1536, 8, 5


def make_shard(shard: int) -> tuple[bytes, list]:
    """A webdataset shard of jpg/txt/json samples with jpgs of 40..1500
    bytes, as plain ustar members, and its (name, size) list."""
    rng = random.Random(f"shard:{shard}")
    buf, members = io.BytesIO(), []
    with tarfile.open(fileobj=buf, mode="w",
                      format=tarfile.USTAR_FORMAT) as tar:
        for i in range(SAMPLES + shard):       # shards of unequal length
            for ext, size in (("jpg", rng.randrange(40, 1500)),
                              ("txt", rng.randrange(1, 60)),
                              ("json", rng.randrange(100, 300))):
                name = f"s{shard}/{i:05d}.{ext}"
                info = tarfile.TarInfo(name)
                info.size = size
                tar.addfile(info, io.BytesIO(rng.randbytes(size)))
                members.append((name, size))
    return buf.getvalue(), members


@pytest.fixture(scope="module")
def landed(tmp_path_factory):
    """One run of the plane: every host's batches fetched back, for one host
    of one and for both hosts of two, beside what the reference needs."""
    import asyncio

    from dragonfly2_tpu.daemon.peer.piece_manager import (
        PieceManager,
        PieceManagerOption,
    )
    from dragonfly2_tpu.daemon.peer.task_manager import TaskManager
    from dragonfly2_tpu.storage import StorageManager, StorageOption

    shards = [make_shard(s) for s in range(SHARDS)]
    out = types.SimpleNamespace(shards=shards, runs={}, events={},
                                origin_bytes={}, tasks={}, reads={})
    out.local_reads_before = RANGE_READS.labels("local")._value.get()

    async def run():
        origins = [await start_range_origin(content) for content, _ in shards]
        urls = [url for _, url, _ in origins]
        try:
            for hosts in (1, 2):
                for host in range(hosts):
                    storage = StorageManager(StorageOption(data_dir=str(
                        tmp_path_factory.mktemp(f"h{host}of{hosts}"))))
                    tm = TaskManager(storage, PieceManager(
                        PieceManagerOption(concurrency=2)))
                    loader = PodShardedLoader.over_daemon(
                        tm, urls, tag=f"feed-test-{host}of{hosts}",
                        options=LoaderOptions(
                            seed=SEED, num_hosts=hosts, host_id=host,
                            interleave=2, readahead=4))
                    await loader.prepare()
                    served = sum(stats["bytes"] for _, _, stats in origins)
                    feed = DeviceFeed("jpg", RECORD, BATCH, pad=True,
                                      force_hbm=True, flight=loader.flight)
                    got = []
                    async for batch in feed.batches(loader.epoch(0)):
                        assert batch.on_device
                        got.append((
                            [(urls.index(s), k) for s, k in
                             zip(batch.shards, batch.keys)],
                            np.asarray(batch.array)))
                    out.runs[hosts, host] = got
                    out.events[hosts, host] = [
                        (flight.EVENT_NAMES[code], piece, aux, note)
                        for _, code, piece, aux, note
                        in loader.flight.events()]
                    out.origin_bytes[hosts, host] = sum(
                        stats["bytes"] for _, _, stats in origins) - served
                    out.tasks[hosts, host] = [
                        t.metadata.content_length for t in storage.tasks()]
                    out.reads[hosts, host] = [
                        r.fetcher.stats for r in loader.readers]
                    out.plan = loader.plan(0) if hosts == 1 else out.plan
                    storage.close()
        finally:
            await default_registry().close_all()
            for runner, _, _ in origins:
                await runner.cleanup()

    asyncio.run(run())
    return out


def reference(landed, hosts: int, host: int):
    counts = [len(ref.samples_of(m)) for _, m in landed.shards]
    plan = ref.epoch_plan(counts, SEED, 0, hosts, host, 2)
    return ref.batches([c for c, _ in landed.shards],
                       [m for _, m in landed.shards], plan, BATCH, RECORD,
                       "jpg")


HOSTS = [(1, 0), (2, 0), (2, 1)]


@pytest.mark.parametrize("hosts, host", HOSTS)
def test_keys_come_in_the_references_order(landed, hosts, host):
    got, want = landed.runs[hosts, host], reference(landed, hosts, host)
    assert [keys for keys, _ in got] == [keys for keys, _ in want]


@pytest.mark.parametrize("hosts, host", HOSTS)
def test_every_row_is_the_references_bytes_and_zeros_after(landed, hosts,
                                                           host):
    got, want = landed.runs[hosts, host], reference(landed, hosts, host)
    assert len(got) == len(want)
    for (_, rows), (_, expected) in zip(got, want):
        assert rows.dtype == np.uint8 and rows.shape == expected.shape
        np.testing.assert_array_equal(rows, expected)


@pytest.mark.parametrize("hosts, host", HOSTS)
def test_the_last_batch_is_short(landed, hosts, host):
    total = sum(SAMPLES + s for s in range(SHARDS))
    mine = len(range(host, total, hosts))
    sizes = [len(keys) for keys, _ in landed.runs[hosts, host]]
    assert sizes == [BATCH] * (mine // BATCH) + ([mine % BATCH]
                                                 if mine % BATCH else [])
    assert mine % BATCH      # the sizes above were chosen so that it is


def test_two_hosts_together_are_the_epoch_exactly_once(landed):
    one = [k for keys, _ in landed.runs[1, 0] for k in keys]
    two = [k for h in (0, 1) for keys, _ in landed.runs[2, h] for k in keys]
    assert len(set(one)) == len(one) == sum(SAMPLES + s
                                            for s in range(SHARDS))
    assert sorted(two) == sorted(one)


def test_the_loaders_plan_names_the_references_samples(landed):
    samples = [ref.samples_of(m) for _, m in landed.shards]
    want = [samples[s][k][0] for s, k in ref.epoch_plan(
        [len(s) for s in samples], SEED, 0, 1, 0, 2)]
    assert [key for _, key in landed.plan] == want


@pytest.mark.parametrize("hosts, host", HOSTS)
def test_a_shard_is_pulled_once_and_a_sample_is_a_read_of_this_store(
        landed, hosts, host):
    """prepare() streamed each shard whole through one task; after it the
    origin served nothing more and no task was made: the only tasks in the
    store are the shards', every sample's one span (its three members
    coalesce) was read out of this host's store, and counted so."""
    assert landed.origin_bytes[hosts, host] == 0
    assert sorted(landed.tasks[hosts, host]) == sorted(
        len(content) for content, _ in landed.shards)
    samples = sum(len(k) for k, _ in landed.runs[hosts, host])
    reads = [e for e in landed.events[hosts, host] if e[0] == "feed_sample"]
    assert len(reads) == samples
    assert sorted(piece for _, piece, _, _ in reads) == list(range(samples))
    for _, _, aux, note in reads:
        fields = dict(part.split("=") for part in note.split())
        assert fields["src"] == "local" and fields["tasks"] == "0"
        assert float(fields["move"]) == 0 <= float(fields["read"]) <= aux
        assert 0 < int(fields["bytes"]) < 4096
    stats = landed.reads[hosts, host]
    assert sum(s["local"] for s in stats) == samples
    assert all(s["cold"] == s["reuse"] == 0 for s in stats)


def test_the_counter_of_span_reads_gains_local_by_every_sample(landed):
    samples = sum(len(k) for run in landed.runs.values() for k, _ in run)
    assert RANGE_READS.labels("local")._value.get() \
        - landed.local_reads_before >= samples


@pytest.mark.parametrize("hosts, host", HOSTS)
def test_the_ring_carries_every_batch_and_its_sinks_steps(landed, hosts,
                                                          host):
    events = landed.events[hosts, host]
    sizes = [len(keys) for keys, _ in landed.runs[hosts, host]]
    batches = [e for e in events if e[0] == "feed_batch"]
    waits = [e for e in events if e[0] == "feed_wait"]
    assert [piece for _, piece, _, _ in batches] == list(range(len(sizes)))
    assert [(piece, int(note)) for _, piece, _, note in waits] == list(
        enumerate(sizes))
    padded = RECORD + (-RECORD) % 4
    for (_, k, aux, note), n, (_, rows) in zip(
            batches, sizes, reference(landed, hosts, host)):
        fields = dict(part.split("=") for part in note.split())
        assert fields["path"] == "hbm" and int(fields["n"]) == n
        # What was landed and put is the feed's one geometry, BATCH rows,
        # the short last batch's too: its rows past n are empty records.
        assert int(fields["rows"]) == BATCH
        assert int(fields["put"]) == padded * BATCH
        steps = [float(fields[s]) for s in ("stage", "verify", "view")]
        assert all(s >= 0 for s in steps) and sum(steps) <= aux + 1e-6
        # The batch's own sink stamped its steps under the batch's number:
        # a stage and a checksum a row, a put a stack, one assembly.
        mine = [e for e in events if e[3].split(" ")[0] == f"batch={k}"]
        names = [e[0] for e in mine]
        assert names.count("sink_checksum") == BATCH
        assert names.count("sink_put") == -(-BATCH // 64)
        assert names.count("sink_assemble") == 1
        assert sorted(p for name, p, _, _ in mine
                      if name == "sink_checksum") == list(range(BATCH))


def test_payload_in_the_ring_is_the_records_bytes_before_padding(landed):
    samples = [ref.samples_of(m) for _, m in landed.shards]
    batches = [e for e in landed.events[1, 0] if e[0] == "feed_batch"]
    for (_, _, _, note), (keys, _) in zip(batches, landed.runs[1, 0]):
        by_key = [dict(samples[s])[k]["jpg"][1] for s, k in keys]
        assert f"payload={sum(by_key)} " in note


def test_the_counters_gain_the_device_side(landed):
    from dragonfly2_tpu.dataset.shard_reader import DATASET_BYTES

    rows = sum(rows.size for run in landed.runs.values() for _, rows in run)
    payload = sum(int(np.count_nonzero(rows)) for run in landed.runs.values()
                  for _, rows in run)
    assert DATASET_BYTES.labels("device")._value.get() >= rows
    assert DATASET_BYTES.labels("fetched")._value.get() \
        >= DATASET_BYTES.labels("yielded")._value.get() > payload


@pytest.mark.parametrize("asked", [True, False], ids=["told_its_device",
                                                      "not_told"])
def test_a_fall_to_numpy_is_said_once_and_loudly_where_a_device_was_asked(
        asked, monkeypatch):
    """The device path fails at the second batch: host batches from there on
    (the input pipeline outlives a sink hiccup), one line for the feed's life
    and not one a batch, an error where the caller had named its device and a
    warning where it had not, the cause counted and kept on the feed."""
    import asyncio

    from dragonfly2_tpu.dataset import device_feed

    said = {"error": [], "warning": []}
    monkeypatch.setattr(device_feed.log, "error",
                        lambda msg, **kw: said["error"].append(kw))
    monkeypatch.setattr(device_feed.log, "warning",
                        lambda msg, **kw: said["warning"].append(kw))
    sound = DeviceFeed._land_hbm

    def land_hbm(self, records):
        if self.batch_no >= 1:
            raise MemoryError("out of HBM")
        return sound(self, records)

    monkeypatch.setattr(DeviceFeed, "_land_hbm", land_hbm)
    counted = device_feed.DEVICE_FALLBACKS.labels("MemoryError")
    before = counted._value.get()

    async def samples():
        for i in range(7):
            yield {"__key__": f"k{i}", "__shard__": "s", "jpg": bytes([i]) * 8}

    async def run():
        feed = DeviceFeed("jpg", 8, 2, force_hbm=asked)
        feed.use_hbm = True       # the CPU backend takes the sink's path
        return feed, [b async for b in feed.batches(samples())]

    feed, batches = asyncio.run(run())
    assert [b.on_device for b in batches] == [True, False, False, False]
    assert [len(b.keys) for b in batches] == [2, 2, 2, 1]
    assert bytes(np.asarray(batches[2].array)[1]) == bytes([5]) * 8
    assert feed.fell_back == "MemoryError" and not feed.use_hbm
    assert counted._value.get() == before + 1
    loud, quiet = ("error", "warning") if asked else ("warning", "error")
    assert len(said[loud]) == 1 and said[quiet] == []
    assert said[loud][0]["cause"] == "MemoryError"
    assert said[loud][0]["batch"] == 1
    assert said[loud][0]["device_asked"] is asked


def test_a_shard_cut_short_fails_the_index_and_not_a_later_sample(tmp_path):
    """The embedded-daemon form of the one-pass build keeps the indexer's
    typed truncation: an origin that serves a shard cut mid-member fails
    prepare(), and no loader is left that would yield partial samples."""
    import asyncio

    from dragonfly2_tpu.daemon.peer.piece_manager import (
        PieceManager,
        PieceManagerOption,
    )
    from dragonfly2_tpu.daemon.peer.task_manager import TaskManager
    from dragonfly2_tpu.dataset import TruncatedShardError
    from dragonfly2_tpu.storage import StorageManager, StorageOption

    content, _ = make_shard(0)

    async def run():
        runner, url, _ = await start_range_origin(content[:len(content) // 2
                                                          + 100])
        storage = StorageManager(StorageOption(data_dir=str(tmp_path)))
        tm = TaskManager(storage, PieceManager(PieceManagerOption()))
        loader = PodShardedLoader.over_daemon(tm, [url], tag="cut")
        try:
            with pytest.raises(TruncatedShardError):
                await loader.prepare()
            assert loader.readers is None
        finally:
            await default_registry().close_all()
            await runner.cleanup()
            storage.close()

    asyncio.run(run())
