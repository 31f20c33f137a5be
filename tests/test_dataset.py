"""Dataset plane end-to-end: pod-sharded loader, sample-ranged P2P reads,
device feed, and metrics exposure.

Runs against the in-process gateway fixture (pkg/testing) — a REAL
TaskManager behind the object gateway — so the assertions about task
ranges and reuse are about the actual P2P machinery, not mocks.
"""

from __future__ import annotations

import io
import os
import random
import tarfile

import aiohttp
import pytest

from dragonfly2_tpu.client.dfstore import Dfstore
from dragonfly2_tpu.daemon.peer import task_manager as task_manager_module
from dragonfly2_tpu.daemon.peer.task_manager import FileTaskRequest
from dragonfly2_tpu.dataset import (
    DaemonRangeFetcher,
    LoaderOptions,
    PodShardedLoader,
    ShardReader,
    epoch_order,
    host_partition,
    index_tar_bytes,
    interleave_shards,
    plan_host_epoch,
)
from dragonfly2_tpu.dataset.shard_reader import RANGE_READS
from dragonfly2_tpu.dataset.tar_index import fetch_or_build_index, index_object_key
from dragonfly2_tpu.pkg import idgen, metrics
from dragonfly2_tpu.pkg.testing import start_gateway_fixture, start_range_origin
from dragonfly2_tpu.proto.common import UrlMeta
from dragonfly2_tpu.source.client import default_registry
from dragonfly2_tpu.storage.local_store import TaskStoreMetadata

from tests.test_stream_proxy import make_task_manager


def make_shard(shard_no: int, n_samples: int, payload_base: int = 64) -> bytes:
    """A webdataset shard: numbered (jpg, cls) samples, deterministic
    payloads so content assertions are exact."""
    buf = io.BytesIO()
    with tarfile.open(fileobj=buf, mode="w") as tar:
        for i in range(n_samples):
            payload = bytes([(shard_no * 31 + i) % 256]) * (payload_base + i)
            info = tarfile.TarInfo(name=f"{shard_no:03d}/{i:05d}.jpg")
            info.size = len(payload)
            tar.addfile(info, io.BytesIO(payload))
            label = str((shard_no + i) % 10).encode()
            info = tarfile.TarInfo(name=f"{shard_no:03d}/{i:05d}.cls")
            info.size = len(label)
            tar.addfile(info, io.BytesIO(label))
    return buf.getvalue()


def expected_payload(shard_no: int, i: int, payload_base: int = 64) -> bytes:
    return bytes([(shard_no * 31 + i) % 256]) * (payload_base + i)


async def put_shards(store: Dfstore, bucket: str, n_shards: int,
                     n_samples: int) -> dict[str, bytes]:
    await store.create_bucket(bucket)
    shards = {}
    for s in range(n_shards):
        key = f"train-{s:05d}.tar"
        data = make_shard(s, n_samples)
        await store.put_object(bucket, key, data, mode="write_back")
        shards[key] = data
    return shards


# -- pure planning contract --------------------------------------------------

def test_exactly_once_partition_and_reproducibility():
    counts = [17, 3, 0, 25, 8]
    total = sum(counts)
    for num_hosts in (1, 2, 4, 7):
        flat = epoch_order(counts, seed=5, epoch=2)
        assert len(flat) == total
        union: list = []
        for h in range(num_hosts):
            opts = LoaderOptions(seed=5, num_hosts=num_hosts, host_id=h,
                                 interleave=3)
            mine = plan_host_epoch(counts, opts, epoch=2)
            # interleave permutes but never changes membership
            assert sorted(mine) == sorted(
                host_partition(flat, num_hosts, h))
            union.extend(mine)
        assert sorted(union) == sorted(
            (si, ki) for si, n in enumerate(counts) for ki in range(n))
    # Same (seed, epoch) → identical; different epoch/seed → different.
    a = epoch_order(counts, seed=5, epoch=2)
    assert a == epoch_order(counts, seed=5, epoch=2)
    assert a != epoch_order(counts, seed=5, epoch=3)
    assert a != epoch_order(counts, seed=6, epoch=2)


def test_interleave_round_robins_across_k_shards():
    items = [(0, i) for i in range(4)] + [(1, i) for i in range(4)] \
        + [(2, i) for i in range(2)]
    out = interleave_shards(items, 2)
    assert sorted(out) == sorted(items)
    # First four picks alternate between the first two open shards.
    assert [si for si, _ in out[:4]] == [0, 1, 0, 1]
    assert interleave_shards(items, 1) == items


def test_loader_options_validation():
    from dragonfly2_tpu.dataset import LoaderError

    with pytest.raises(LoaderError):
        LoaderOptions(num_hosts=0)
    with pytest.raises(LoaderError):
        LoaderOptions(num_hosts=2, host_id=2)


# -- end-to-end over the gateway ---------------------------------------------

def test_loader_smoke_over_gateway(run_async, tmp_path):
    """Tier-1 smoke: 2 tiny shards, indexes built by streaming, a full
    single-host epoch yields every sample exactly once with exact
    payloads, and a second pass with the same seed repeats the order."""

    async def run():
        fx = await start_gateway_fixture(tmp_path)
        store = Dfstore(fx.endpoint)
        try:
            await put_shards(store, "wds", 2, 6)
            loader = PodShardedLoader(
                store, "wds", ["train-00000.tar", "train-00001.tar"],
                options=LoaderOptions(seed=11, interleave=2, readahead=4))
            await loader.prepare()
            assert loader.num_samples == 12

            got = [s async for s in loader.epoch(0)]
            assert len(got) == 12
            keys = [s["__key__"] for s in got]
            assert sorted(keys) == sorted(
                f"{sh:03d}/{i:05d}" for sh in range(2) for i in range(6))
            for s in got:
                sh, i = int(s["__key__"][:3]), int(s["__key__"][4:])
                assert s["jpg"] == expected_payload(sh, i)
                assert s["cls"] == str((sh + i) % 10).encode()
                assert s["__shard__"] == f"train-{sh:05d}.tar"
            assert keys == [s["__key__"] async for s in loader.epoch(0)]
            # The published index is now a cached P2P object.
            fresh = PodShardedLoader(
                store, "wds", ["train-00000.tar"],
                options=LoaderOptions(seed=1))
            await fresh.prepare()
            assert fresh.indexes[0].num_samples == 6
        finally:
            await store.close()
            await fx.aclose()

    run_async(run())


def test_cold_read_is_ranged_and_warm_read_reuses(run_async, tmp_path):
    """Acceptance: a cold sample read creates ranged tasks covering ONLY
    that sample's member spans (never a whole-shard task); re-reading
    the same sample rides completed-task reuse (local piece store)."""

    async def run():
        fx = await start_gateway_fixture(tmp_path)
        store = Dfstore(fx.endpoint)
        try:
            shards = await put_shards(store, "wds", 1, 8)
            key = "train-00000.tar"
            shard_size = len(shards[key])
            # Index computed locally and published — the shard itself is
            # never streamed, so every shard fetch below is sample-driven.
            idx = index_tar_bytes(shards[key], key)
            await store.put_object("wds", index_object_key(key),
                                   idx.to_json_bytes(), mode="write_back")
            loader = PodShardedLoader(
                store, "wds", [key],
                options=LoaderOptions(seed=3, readahead=2))
            await loader.prepare()
            reader = loader.readers[0]
            sample = loader.indexes[0].samples[5]
            spans = reader.sample_spans(sample)
            out = await reader.read_sample(sample)
            assert out["jpg"] == expected_payload(0, 5)

            shard_url = fx.object_url("wds", key)
            shard_tasks = [t.metadata for t in fx.tm.storage.tasks()
                           if t.metadata.url == shard_url]
            assert shard_tasks, "no daemon tasks for the shard"
            # Every task over the shard is a ranged one, sized exactly as
            # the sample's coalesced spans — the whole shard never moved.
            span_lengths = sorted(e - s for s, e in spans)
            assert sorted(t.content_length for t in shard_tasks) \
                == span_lengths
            assert all(t.content_length < shard_size for t in shard_tasks)
            assert reader.fetcher.stats == {"cold": len(spans), "reuse": 0}

            # Warm: identical spans hit the completed ranged task.
            out2 = await reader.read_sample(sample)
            assert out2["jpg"] == out["jpg"]
            assert reader.fetcher.stats["reuse"] == len(spans)
            assert len([t for t in fx.tm.storage.tasks()
                        if t.metadata.url == shard_url]) == len(shard_tasks)
        finally:
            await store.close()
            await fx.aclose()

    run_async(run())


def test_daemon_fetcher_matches_gateway(run_async, tmp_path):
    """On a store that holds no parent of the shard, the embedded-daemon
    fetcher (ranged FileTasks straight on the TaskManager) produces
    identical sample bytes and dedupes with the gateway's ranged tasks
    (same tag → same task identity)."""

    async def run():
        fx = await start_gateway_fixture(tmp_path)
        store = Dfstore(fx.endpoint)
        try:
            shards = await put_shards(store, "wds", 1, 4)
            key = "train-00000.tar"
            idx = index_tar_bytes(shards[key], key)
            reader = ShardReader(
                DaemonRangeFetcher(fx.tm, fx.object_url("wds", key),
                                   tag="wds"),
                idx)
            sample = idx.samples[2]
            assert not [t for t in fx.tm.storage.tasks()
                        if t.metadata.content_length == len(shards[key])]
            out = await reader.read_sample(sample)
            assert out["jpg"] == expected_payload(0, 2)
            assert reader.fetcher.stats == {"local": 0, "cold": 1,
                                            "reuse": 0}
            n_tasks = len(fx.tm.storage.tasks())
            # Same span over the gateway: byte-identical task id → reuse,
            # no new task store.
            _, data = await store.read_object_range(
                "wds", key, *reader.sample_spans(sample)[0])
            assert len(fx.tm.storage.tasks()) == n_tasks
            assert out["cls"] in data
        finally:
            await store.close()
            await fx.aclose()

    run_async(run())


# -- a span this host's store covers is read from it, not made a task --------

MB = 1 << 20
CONTENT = random.Random(47).randbytes(9 * MB)    # pieces of 4, 4 and 1 MiB
SPAN = (4 * MB - 700, 4 * MB + 900)              # across pieces 0 and 1
TAG = "held"


async def pull_whole(tm, url: str, tag: str = TAG):
    """The shard whole in ``tm``'s store under the id that every ranged
    read of (url, tag) names as its parent, as ``prepare()`` leaves it."""
    async for p in tm.start_file_task(FileTaskRequest(
            url=url, output="", meta=UrlMeta(tag=tag))):
        assert p.state != "failed", p.error
    return tm.storage.find_completed_task(p.task_id)


def ranged_task_id(url: str, start: int, end: int, tag: str = TAG) -> str:
    """The id the fabric dedupes a span's task by, reckoned beside the
    fetcher: it must not move with where the bytes were found."""
    return idgen.task_id_v1(url, tag=tag,
                            range_header=f"bytes={start}-{end - 1}")


async def fetch(tm, url: str, span=SPAN, tag: str = TAG):
    fetcher = DaemonRangeFetcher(tm, url, tag=tag)
    buf = memoryview(bytearray(span[1] - span[0]))
    got = await fetcher.fetch_into(*span, buf)
    return fetcher, got, bytes(buf)


def local_reads() -> float:
    return RANGE_READS.labels("local")._value.get()


def test_a_span_the_store_covers_is_read_from_the_pinned_parent_as_no_task(
        run_async, tmp_path):
    """The whole shard is in this store: a span across two of its pieces
    comes out of it byte for byte what the ranged task gives a host without
    the shard, with the parent pinned while the read runs and let go after,
    no task made for it, nothing asked of the origin, and counted."""

    async def run():
        runner, url, served = await start_range_origin(CONTENT)
        holder = make_task_manager(tmp_path / "a")
        other = make_task_manager(tmp_path / "b")
        try:
            parent = await pull_whole(holder, url)
            assert parent.metadata.piece_size == 4 * MB
            pinned = []
            sound = parent.read_into

            def read_into(offset, length, buf, at=0):
                pinned.append(parent.pinned)
                return sound(offset, length, buf, at)

            parent.read_into = read_into
            before = served["bytes"], local_reads(), len(holder.flight.summary())
            fetcher, (src, move, read_ms), got = await fetch(holder, url)
            assert (src, move) == ("local", 0.0) and read_ms >= 0
            assert got == CONTENT[SPAN[0]:SPAN[1]]
            assert pinned == [True] and not parent.pinned
            assert [t.metadata.task_id for t in holder.storage.tasks()] \
                == [parent.metadata.task_id]
            assert (served["bytes"], local_reads() - 1,
                    len(holder.flight.summary())) == before
            assert fetcher.stats == {"local": 1, "cold": 0, "reuse": 0}
            # The host without the shard: ONE ranged task under the id
            # every host names the span by, the same bytes.
            fetcher, (src, _, _), through_task = await fetch(other, url)
            assert src == "origin" and through_task == got
            assert [t.metadata.task_id for t in other.storage.tasks()] \
                == [ranged_task_id(url, *SPAN)]
            assert fetcher.stats == {"local": 0, "cold": 1, "reuse": 0}
        finally:
            await default_registry().close_all()
            await runner.cleanup()
            holder.storage.close()
            other.storage.close()

    run_async(run())


async def parent_absent(tm, url):
    return SPAN


async def parent_of_another_tag(tm, url):
    await pull_whole(tm, url, tag="another")
    return SPAN


async def partial_parent_missing_a_piece_of_the_span(tm, url):
    """Piece 0 of three is here: a span inside it is read from it, the span
    that runs on into piece 1 is not covered."""
    store = tm.storage.register_task(TaskStoreMetadata(
        task_id=idgen.parent_task_id_v1(url, tag=TAG), url=url, tag=TAG))
    store.update_task(content_length=len(CONTENT), piece_size=4 * MB,
                      total_piece_count=3)
    store.write_piece(0, CONTENT[:4 * MB])
    inside = (MB, MB + 5000)
    _, (src, _, _), got = await fetch(tm, url, inside)
    assert src == "local" and got == CONTENT[inside[0]:inside[1]]
    return SPAN


async def parent_evicted_between_two_reads(tm, url):
    parent = await pull_whole(tm, url)
    _, (src, _, _), _ = await fetch(tm, url)
    assert src == "local"
    tm.storage.delete_task(parent.metadata.task_id)
    return SPAN


async def parent_cut_short_under_its_metadata(tm, url):
    """``read_into`` raises StorageError (EOF inside the span): the read
    says no, the task's own import fails the same way and falls to the
    origin, as it did before the short cut."""
    parent = await pull_whole(tm, url)
    os.truncate(parent.data_path, SPAN[0] + 100)
    return SPAN


@pytest.mark.parametrize("arrange", [
    parent_absent, parent_of_another_tag,
    partial_parent_missing_a_piece_of_the_span,
    parent_evicted_between_two_reads, parent_cut_short_under_its_metadata],
    ids=lambda f: f.__name__)
def test_the_gate_says_no_and_the_ranged_task_runs(run_async, tmp_path,
                                                   monkeypatch, arrange):
    """Where this store cannot give the span, the span is ONE ranged task
    under the unchanged id, its bytes exact; a parent that cannot be read is
    warned of once, however many spans ask."""
    warned = []
    monkeypatch.setattr(
        task_manager_module.log, "warning",
        lambda msg, **kw: warned.append(msg))

    async def run():
        runner, url, served = await start_range_origin(CONTENT)
        tm = make_task_manager(tmp_path / "host")
        try:
            span = await arrange(tm, url)
            before = served["bytes"], local_reads()
            held = {t.metadata.task_id for t in tm.storage.tasks()}
            fetcher, (src, _, _), got = await fetch(tm, url, span)
            assert src == "origin" and got == CONTENT[span[0]:span[1]]
            assert fetcher.stats == {"local": 0, "cold": 1, "reuse": 0}
            assert {t.metadata.task_id for t in tm.storage.tasks()} - held \
                == {ranged_task_id(url, *span)}
            # The span and the probe's byte, not the shard.
            assert 0 <= served["bytes"] - before[0] - (span[1] - span[0]) <= 8
            assert local_reads() == before[1]
            again = (span[0] + 16, span[1])
            _, (src, _, _), got = await fetch(tm, url, again)
            assert src == "origin" and got == CONTENT[again[0]:again[1]]
        finally:
            await default_registry().close_all()
            await runner.cleanup()
            tm.storage.close()

    run_async(run())
    unreadable = [m for m in warned if m.startswith("local range read failed")]
    assert len(unreadable) == (
        arrange is parent_cut_short_under_its_metadata)


def test_under_a_scheduler_a_host_that_holds_the_shard_asks_nobody(
        run_async, tmp_path):
    """Origin, scheduler, seed and a peer that pulled the shard whole: a
    sample's span on the peer makes no register (the scheduler's tables stand
    still), no task in the seed and none in the peer; the same span under a
    tag the peer holds no shard of is still one ranged task from the seed,
    under the unchanged id."""
    import tests.test_p2p_e2e as e2e

    async def run():
        runner, url, served = await start_range_origin(CONTENT)
        sched = await e2e.start_scheduler()
        daemons = []
        try:
            daemons.append(seed := await e2e.start_daemon(
                tmp_path, "seed", sched.port(), seed=True))
            daemons.append(peer := await e2e.start_daemon(
                tmp_path, "peer", sched.port()))
            await pull_whole(peer.task_manager, url)

            def tables():
                return (len(sched.service.tasks.all()),
                        len(sched.service.peers.all()),
                        len(seed.storage.tasks()), len(peer.storage.tasks()),
                        served["bytes"])

            before = tables()
            fetcher, (src, _, _), got = await fetch(peer.task_manager, url)
            assert src == "local" and got == CONTENT[SPAN[0]:SPAN[1]]
            assert tables() == before
            assert fetcher.stats == {"local": 1, "cold": 0, "reuse": 0}

            fetcher, (src, _, _), got = await fetch(peer.task_manager, url,
                                                    tag="not-held")
            assert src == "peer" and got == CONTENT[SPAN[0]:SPAN[1]]
            assert fetcher.stats == {"local": 0, "cold": 1, "reuse": 0}
            task_id = ranged_task_id(url, *SPAN, tag="not-held")
            assert sched.service.tasks.load(task_id) is not None
            for d in daemons:
                assert d.storage.find_completed_task(task_id) is not None
            after = tables()
            assert (after[0] - before[0], after[2] - before[2],
                    after[3] - before[3]) == (1, 1, 1)
            assert after[1] - before[1] == 2      # the peer's and the seed's
        finally:
            for d in daemons:
                await d.stop()
            await sched.stop()
            await runner.cleanup()

    run_async(run(), timeout=120)


@pytest.mark.slow
def test_multihost_exactly_once_e2e(run_async, tmp_path):
    """4 simulated hosts over one gateway: the union of their epochs
    covers every sample exactly once, each host is reproducible, and
    epoch 1 reshuffles."""

    async def run():
        fx = await start_gateway_fixture(tmp_path)
        store = Dfstore(fx.endpoint)
        try:
            await put_shards(store, "wds", 3, 5)
            keys = [f"train-{s:05d}.tar" for s in range(3)]
            all_keys = {f"{sh:03d}/{i:05d}"
                        for sh in range(3) for i in range(5)}
            per_host: list[list[str]] = []
            for h in range(4):
                loader = PodShardedLoader(
                    store, "wds", keys,
                    options=LoaderOptions(seed=42, num_hosts=4, host_id=h,
                                          interleave=2, readahead=3))
                await loader.prepare()
                got = [s["__key__"] async for s in loader.epoch(0)]
                assert got == [k for _, k in loader.plan(0)]
                per_host.append(got)
            union = [k for host in per_host for k in host]
            assert len(union) == len(all_keys)
            assert set(union) == all_keys
            # Reproducible per host; epoch advance reshuffles.
            re0 = PodShardedLoader(
                store, "wds", keys,
                options=LoaderOptions(seed=42, num_hosts=4, host_id=0,
                                      interleave=2))
            await re0.prepare()
            assert [s["__key__"] async for s in re0.epoch(0)] == per_host[0]
            assert [s["__key__"] async for s in re0.epoch(1)] != per_host[0]
        finally:
            await store.close()
            await fx.aclose()

    run_async(run())


# -- device feed -------------------------------------------------------------

async def _as_aiter(items):
    for it in items:
        yield it


def test_device_feed_numpy_fallback(run_async):
    import numpy as np

    from dragonfly2_tpu.dataset.device_feed import DeviceFeed, DeviceFeedError

    samples = [{"__key__": f"k{i}", "jpg": bytes([i]) * 10} for i in range(5)]

    async def run():
        feed = DeviceFeed("jpg", record_bytes=10, batch_size=2)
        batches = [b async for b in feed.batches(_as_aiter(samples))]
        assert [len(b.keys) for b in batches] == [2, 2, 1]
        assert all(not b.on_device for b in batches)
        np.testing.assert_array_equal(
            np.asarray(batches[0].array),
            np.stack([np.full(10, 0, np.uint8), np.full(10, 1, np.uint8)]))
        # drop_last drops the ragged tail.
        feed2 = DeviceFeed("jpg", record_bytes=10, batch_size=2,
                           drop_last=True)
        assert len([b async for b in feed2.batches(_as_aiter(samples))]) == 2
        # Oversize and (unpadded) undersize records are typed errors.
        bad = [{"__key__": "b", "jpg": b"x" * 11}]
        with pytest.raises(DeviceFeedError):
            async for _ in DeviceFeed("jpg", 10, 1).batches(_as_aiter(bad)):
                pass
        short = [{"__key__": "s", "jpg": b"x" * 3}]
        with pytest.raises(DeviceFeedError):
            async for _ in DeviceFeed("jpg", 10, 1).batches(_as_aiter(short)):
                pass
        padded = [b async for b in DeviceFeed(
            "jpg", 10, 1, pad=True).batches(_as_aiter(short))]
        assert bytes(padded[0].array[0]) == b"x" * 3 + b"\0" * 7

    run_async(run())


def test_device_feed_hbm_path(run_async):
    """force_hbm exercises the HBMSink landing (piece-per-record with
    on-device verification) on the CPU backend."""
    import numpy as np

    from dragonfly2_tpu.dataset.device_feed import DeviceFeed

    samples = [{"__key__": f"k{i}", "jpg": bytes([7 + i]) * 13}
               for i in range(4)]

    async def run():
        feed = DeviceFeed("jpg", record_bytes=13, batch_size=3,
                          force_hbm=True)
        batches = [b async for b in feed.batches(_as_aiter(samples))]
        assert [len(b.keys) for b in batches] == [3, 1]
        assert all(b.on_device for b in batches)
        arr = np.asarray(batches[0].array)
        assert arr.shape == (3, 13)
        np.testing.assert_array_equal(
            arr, np.stack([np.full(13, 7 + i, np.uint8) for i in range(3)]))
        np.testing.assert_array_equal(
            np.asarray(batches[1].array),
            np.full((1, 13), 10, np.uint8))

    run_async(run())


# -- metrics exposure --------------------------------------------------------

def test_loader_metrics_exported(run_async, tmp_path):
    """The dataset plane's metrics are visible on a pkg/metrics_server
    scrape after a loader run (the test_tracing-style liveness check)."""
    from dragonfly2_tpu.pkg.metrics_server import MetricsServer

    async def run():
        fx = await start_gateway_fixture(tmp_path)
        store = Dfstore(fx.endpoint)
        srv = MetricsServer()
        await srv.serve("127.0.0.1", 0)
        try:
            await put_shards(store, "wds", 1, 4)
            loader = PodShardedLoader(
                store, "wds", ["train-00000.tar"],
                options=LoaderOptions(seed=2, readahead=2))
            await loader.prepare()
            from dragonfly2_tpu.dataset.device_feed import DeviceFeed

            feed = DeviceFeed("cls", record_bytes=1, batch_size=2)
            n = 0
            async for batch in feed.batches(loader.epoch(0)):
                n += len(batch.keys)
            assert n == 4

            async with aiohttp.ClientSession() as http:
                async with http.get(
                        f"http://127.0.0.1:{srv.port}/metrics") as resp:
                    assert resp.status == 200
                    text = await resp.text()
            for name in (
                    "dragonfly_tpu_dataset_samples_total",
                    "dragonfly_tpu_dataset_readahead_depth",
                    "dragonfly_tpu_dataset_epochs_total",
                    'dragonfly_tpu_dataset_index_total{result="built"}',
                    'dragonfly_tpu_dataset_range_reads_total{result="cold"}',
                    'dragonfly_tpu_dataset_device_batches_total{path=',
            ):
                assert name in text, f"{name} missing from scrape"
            by_dir = metrics.parse_labeled_samples(
                text, "dragonfly_tpu_dataset_bytes_total", "direction")
            assert by_dir.get("fetched", 0) >= by_dir.get("yielded", 0) > 0
        finally:
            await store.close()
            await srv.close()
            await fx.aclose()

    run_async(run())
