"""Checkpoint-delta plane: chunker, manifests, resolver, hot-swap.

The acceptance story (ISSUE 10): a host with version N landed receives
version N+1 by copying unchanged chunks locally (digest-verified during
the copy) and fetching ONLY changed chunks as ranged P2P tasks — reused
spans never appear on the wire, a corrupt base chunk is transparently
re-fetched, the result is a byte-identical normal completed task served
to peers, and the device flip is atomic (a reader thread observes only
complete old-or-new tensor sets).
"""

from __future__ import annotations

import hashlib
import json
import os
import random
import struct

import numpy as np
import pytest

from dragonfly2_tpu.delta.chunker import CDCParams, GearChunker, chunk_bytes
from dragonfly2_tpu.delta.manifest import (
    DeltaManifest,
    ManifestError,
    build_manifest,
)
from dragonfly2_tpu.delta.resolver import plan_delta

# Small-content chunking geometry for tests: the default 1 MiB targets
# would make an 8 MiB "checkpoint" a handful of chunks.
P = CDCParams(mask_bits=14, min_size=4 << 10, max_size=64 << 10)


def scattered_mutation(data: bytes, frac: float = 0.01, sites: int = 4,
                       seed: int = 5) -> bytes:
    """The realistic edit pattern: ``sites`` scattered small updates
    totalling ``frac`` of the bytes (not one contiguous blob)."""
    rng = random.Random(seed)
    out = bytearray(data)
    per = max(1, int(len(data) * frac / sites))
    for i in range(sites):
        at = rng.randrange(0, len(data) - per)
        out[at:at + per] = bytes(rng.getrandbits(8) for _ in range(per))
    return bytes(out)


# ------------------------------------------------------------------ #
# Chunker
# ------------------------------------------------------------------ #

class TestChunker:
    def test_tiling_and_bounds(self):
        data = os.urandom(1 << 20)
        chunks = chunk_bytes(data, P)
        assert chunks[0].offset == 0
        for a, b in zip(chunks, chunks[1:]):
            assert b.offset == a.end
        assert chunks[-1].end == len(data)
        for c in chunks[:-1]:
            assert P.min_size <= c.length <= P.max_size
        assert chunks[-1].length <= P.max_size
        for c in chunks:
            assert c.sha256 == hashlib.sha256(
                data[c.offset:c.end]).hexdigest()

    def test_feed_split_independence(self):
        data = os.urandom(600_000)
        want = chunk_bytes(data, P)
        for seed in (1, 2):
            rng = random.Random(seed)
            ch = GearChunker(P)
            i = 0
            while i < len(data):
                step = rng.randrange(1, 50_000)
                ch.feed(data[i:i + step])
                i += step
            ch.finish()
            assert ch.chunks == want
        # Degenerate: byte-at-a-time.
        small = data[:30_000]
        ch = GearChunker(P)
        for b in small:
            ch.feed(bytes([b]))
        ch.finish()
        assert ch.chunks == chunk_bytes(small, P)

    def test_shift_resistance(self):
        """An insertion re-chunks only its neighborhood: almost every
        chunk digest survives — the property dedup is built on."""
        data = os.urandom(1 << 20)
        one = {c.sha256 for c in chunk_bytes(data, P)}
        mutated = data[:400_000] + os.urandom(64) + data[400_000:]
        two = {c.sha256 for c in chunk_bytes(mutated, P)}
        assert len(one & two) >= 0.85 * len(one)

    def test_empty_and_tiny_content(self):
        assert chunk_bytes(b"", P) == []
        tiny = chunk_bytes(b"abc", P)
        assert len(tiny) == 1 and tiny[0].length == 3

    def test_forced_cut_at_max(self):
        # All-zero content has no natural boundaries: every chunk but
        # the tail must be exactly max_size.
        data = b"\0" * (P.max_size * 3 + 100)
        chunks = chunk_bytes(data, P)
        assert [c.length for c in chunks[:-1]] == [P.max_size] * 3

    def test_feed_after_finish_refused(self):
        ch = GearChunker(P)
        ch.finish()
        with pytest.raises(RuntimeError):
            ch.feed(b"x")


# ------------------------------------------------------------------ #
# Manifest
# ------------------------------------------------------------------ #

class TestManifest:
    def test_roundtrip(self):
        data = os.urandom(300_000)
        m = build_manifest(data, "v1", P)
        m2 = DeltaManifest.from_json_bytes(m.to_json_bytes())
        assert m2.chunks == m.chunks
        assert m2.params == P
        assert m2.content_length == len(data)

    def test_corrupt_rejected(self):
        with pytest.raises(ManifestError):
            DeltaManifest.from_json_bytes(b"not json")
        m = build_manifest(os.urandom(100_000), "v1", P)
        doc = json.loads(m.to_json_bytes())
        doc["chunks"][0][1] += 1          # breaks tiling
        with pytest.raises(ManifestError):
            DeltaManifest.from_json_bytes(json.dumps(doc).encode())
        doc = json.loads(m.to_json_bytes())
        doc["v"] = 99
        with pytest.raises(ManifestError):
            DeltaManifest.from_json_bytes(json.dumps(doc).encode())

    def test_plan_partition(self):
        # Seeded bytes: the share reused depends on where the four edits
        # fall among chunks of 4-64 KiB, and over os.urandom's bytes it
        # read 0.78-0.90, under the bound below in one run of sixty.
        data = random.Random(10).randbytes(1 << 20)
        mutated = scattered_mutation(data)
        base = build_manifest(data, "v1", P)
        new = build_manifest(mutated, "v2", P)
        plan = plan_delta(new, base)
        # Exact accounting: every new chunk in exactly one class.
        assert plan.reused_bytes + plan.fetched_bytes == len(mutated)
        assert plan.fetched, "a mutation must dirty at least one chunk"
        assert plan.reused_bytes > 0.8 * len(mutated)
        # Identical content -> all reused; disjoint -> all fetched.
        same = plan_delta(base, base)
        assert same.fetched == [] and same.reused_bytes == len(data)
        other = build_manifest(os.urandom(1 << 20), "v3", P)
        assert plan_delta(other, base).reused == []

    def test_plan_rejects_mismatched_params(self):
        base = build_manifest(b"x" * 100_000, "v1", P)
        new = build_manifest(b"x" * 100_000, "v2",
                             CDCParams(mask_bits=10, min_size=1024,
                                       max_size=8192))
        with pytest.raises(ManifestError):
            plan_delta(new, base)

    def test_fetch_spans_merge_only_adjacent(self):
        # Reused gap between two fetched chunks must NOT ride along.
        data = os.urandom(1 << 20)
        mutated = scattered_mutation(data, sites=3)
        plan = plan_delta(build_manifest(mutated, "v2", P),
                          build_manifest(data, "v1", P))
        spans = plan.fetch_spans()
        assert sum(e - s for s, e in spans) == plan.fetched_bytes
        for (s0, e0), (s1, e1) in zip(spans, spans[1:]):
            assert s1 > e0     # strictly disjoint, gaps stay local


def test_fetch_or_build_manifest_gateway_lifecycle(run_async, tmp_path):
    """The .dfidx pattern on the gateway surface: first call streams the
    object through the chunker and publishes `.dfdelta/<key>.json`;
    the second call hits the cache; replacing the object in place
    (size change) rebuilds."""
    from dragonfly2_tpu.client.dfstore import Dfstore
    from dragonfly2_tpu.delta.manifest import (
        fetch_or_build_manifest,
        manifest_object_key,
    )
    from dragonfly2_tpu.pkg.testing import start_gateway_fixture

    data = os.urandom(400_000)

    async def body():
        fx = await start_gateway_fixture(tmp_path)
        store = Dfstore(fx.endpoint)
        try:
            await store.create_bucket("ckpt")
            await store.put_object("ckpt", "shard-0", data)
            m1 = await fetch_or_build_manifest(store, "ckpt", "shard-0",
                                               params=P)
            assert m1.content_length == len(data)
            assert await store.is_object_exist(
                "ckpt", manifest_object_key("shard-0"))
            m2 = await fetch_or_build_manifest(store, "ckpt", "shard-0",
                                               params=P)
            assert m2.chunks == m1.chunks
            # Replace the object in place (write_back so the backend
            # sees it synchronously).
            await store.put_object("ckpt", "shard-0", data + b"xx",
                                   mode="write_back")
        finally:
            await store.close()
            await fx.aclose()

        # A FRESH daemon (the gateway's whole-object stream task caches
        # the old bytes until its TTL on the original) now sees the
        # cached manifest as stale by size and rebuilds it.
        fx2 = await start_gateway_fixture(tmp_path / "g2")
        store2 = Dfstore(fx2.endpoint)
        try:
            import shutil

            shutil.copytree(str(tmp_path / "buckets"),
                            str(tmp_path / "g2" / "buckets"),
                            dirs_exist_ok=True)
            m3 = await fetch_or_build_manifest(store2, "ckpt", "shard-0",
                                               params=P)
            assert m3.content_length == len(data) + 2
            assert m3.chunks[0] == m1.chunks[0]   # shared prefix chunks
        finally:
            await store2.close()
            await fx2.aclose()

    run_async(body(), timeout=60)


# ------------------------------------------------------------------ #
# Device span helper satellites (client/device.py, daemon-free)
# ------------------------------------------------------------------ #

class TestDeviceSpanHelpers:
    def test_coalesce_spans(self):
        from dragonfly2_tpu.client.device import coalesce_spans

        # Out-of-order, overlapping, adjacent and disjoint inputs.
        spans = [(50, 60), (0, 10), (10, 20), (18, 30), (40, 45)]
        assert coalesce_spans(spans) == [(0, 30), (40, 45), (50, 60)]
        assert coalesce_spans([]) == []
        assert coalesce_spans([(5, 9)]) == [(5, 9)]

    def test_covering_span(self):
        from dragonfly2_tpu.client.device import covering_span
        from dragonfly2_tpu.ops.safetensors import SafetensorsError

        cov = [(0, 100), (200, 300)]
        assert covering_span(cov, 10, 90) == (0, 100)
        assert covering_span(cov, 200, 300) == (200, 300)
        with pytest.raises(SafetensorsError):
            covering_span(cov, 90, 110)      # straddles a hole
        with pytest.raises(SafetensorsError):
            covering_span([], 0, 1)

    def test_validated_span_edges(self):
        from dragonfly2_tpu.client.device import _validated_span
        from dragonfly2_tpu.ops.safetensors import SafetensorsError

        assert _validated_span("t", {"data_offsets": [0, 8]}, 100) == (100, 108)
        assert _validated_span("t", {"data_offsets": [5, 5]}, 10) == (15, 15)
        for bad in (None, {"data_offsets": [8, 0]},      # inverted
                    {"data_offsets": [-1, 4]},           # negative
                    {"data_offsets": [0]},               # wrong arity
                    {"data_offsets": [0.0, 4]},          # float
                    {"data_offsets": [False, True]},     # bools
                    {}):                                 # missing
            with pytest.raises(SafetensorsError):
                _validated_span("t", bad, 0)


# ------------------------------------------------------------------ #
# Double-buffer flip atomicity
# ------------------------------------------------------------------ #

def _make_safetensors(tensors: dict) -> bytes:
    header, blobs, off = {}, [], 0
    for name, arr in tensors.items():
        raw = arr.tobytes()
        header[name] = {"dtype": "F32", "shape": list(arr.shape),
                        "data_offsets": [off, off + len(raw)]}
        blobs.append(raw)
        off += len(raw)
    hj = json.dumps(header).encode()
    return struct.pack("<Q", len(hj)) + hj + b"".join(blobs)


class TestDoubleBuffer:
    def test_flip_atomicity_under_reader_thread(self):
        """A reader hammering snapshot() during flips sees only complete
        generations: every tensor in a snapshot carries the same version
        sentinel, never a mix."""
        import threading

        import jax.numpy as jnp

        from dragonfly2_tpu.ops import safetensors as st
        from dragonfly2_tpu.ops.hbm_sink import DoubleBuffer

        def gen_views(version: float):
            tensors = {f"t{i}": np.full((16,), version, np.float32)
                       for i in range(4)}
            content = _make_safetensors(tensors)
            words = jnp.asarray(np.frombuffer(
                content + bytes(-len(content) % 4), "<u4"))
            header, ds = st.parse_header(content)
            return words, st.tensor_views(words, header, ds,
                                          total=len(content))

        hot = DoubleBuffer()
        hot.flip(*gen_views(1.0))
        bad: list = []
        stop = threading.Event()

        def reader():
            while not stop.is_set():
                gen, _buf, views = hot.snapshot()
                vals = {float(np.asarray(v)[0]) for v in views.values()}
                if len(vals) != 1:
                    bad.append((gen, vals))

        threads = [threading.Thread(target=reader) for _ in range(2)]
        for t in threads:
            t.start()
        try:
            for version in range(2, 12):
                hot.flip(*gen_views(float(version)))
        finally:
            stop.set()
            for t in threads:
                t.join()
        assert not bad, f"mixed-generation snapshots observed: {bad[:3]}"
        assert hot.generation == 11

    def test_assemble_and_verify(self):
        import jax
        import jax.numpy as jnp

        from dragonfly2_tpu.ops.checksum import checksum_numpy
        from dragonfly2_tpu.ops.hbm_sink import (
            assemble_swap_words,
            plan_swap,
            stage_swap,
            verify_words_against_host,
        )

        old = os.urandom(4096)
        fetched = os.urandom(512)
        live = jnp.asarray(np.frombuffer(old, "<u4"))
        # New layout: old[0:1024] + fetched + old[1536:4096], the runs cut
        # inside a word on either side of the fetched one.
        want = old[:1022] + fetched + old[1534:]
        runs = [[0, 0, 1022, True], [1022, 1022, 512, False],
                [1534, 1534, 2562, True]]
        plan = plan_swap(runs, len(want), 1024, live.shape[0])
        # Whole words of the reused runs never leave the device; the words
        # the runs' edges cut are staged with the fetched ones.
        assert plan.runs == 2 and plan.reused_bytes == 1020 + 2560
        device = jax.devices()[0]

        def read_into(start, length, buf):
            buf[:length] = want[start:start + length]

        words = assemble_swap_words(
            live, plan, stage_swap(plan, read_into, device), device)
        assert np.asarray(words).tobytes() == want
        checks = {0: checksum_numpy(want[:2048]),
                  1: checksum_numpy(want[2048:])}
        verify_words_against_host(words, 2048, checks)
        # A flipped byte must be caught, naming the piece.
        corrupt = bytearray(want)
        corrupt[100] ^= 0xFF
        bad = jnp.asarray(np.frombuffer(bytes(corrupt), "<u4"))
        with pytest.raises(ValueError, match="piece 0"):
            verify_words_against_host(bad, 2048, checks)


# ------------------------------------------------------------------ #
# Real-process e2e: delta transfer + accounting + corrupt base +
# device hot-swap
# ------------------------------------------------------------------ #

async def _two_blob_origin(v1: bytes, v2: bytes):
    """Origin serving /v1 and /v2 with single-range 206 support and
    per-blob served-byte accounting."""
    from aiohttp import web

    from dragonfly2_tpu.pkg.piece import Range

    stats = {"v1": 0, "v2": 0}

    def handler(name: str, content: bytes):
        async def blob(request):
            hdr = request.headers.get("Range")
            if hdr:
                r = Range.parse_http(hdr, len(content))
                data = content[r.start:r.start + r.length]
                stats[name] += len(data)
                return web.Response(status=206, body=data, headers={
                    "Content-Range":
                        f"bytes {r.start}-{r.start + len(data) - 1}"
                        f"/{len(content)}",
                    "Accept-Ranges": "bytes"})
            stats[name] += len(content)
            return web.Response(body=content,
                                headers={"Accept-Ranges": "bytes"})
        return blob

    app = web.Application()
    app.router.add_get("/v1", handler("v1", v1))
    app.router.add_get("/v2", handler("v2", v2))
    runner = web.AppRunner(app, access_log=None)
    await runner.setup()
    site = web.TCPSite(runner, "127.0.0.1", 0)
    await site.start()
    port = site._server.sockets[0].getsockname()[1]
    return runner, f"http://127.0.0.1:{port}", stats


async def _drain_task(tm, req, base: str = ""):
    final = None
    it = (tm.start_delta_task(req, base) if base
          else tm.start_file_task(req))
    async for p in it:
        if p.state == "failed":
            from dragonfly2_tpu.pkg.errors import DfError

            raise DfError.from_wire(p.error or {})
        if p.state == "done":
            final = p
    assert final is not None
    return final


def _file_req(url: str, digest: str = "", output: str = ""):
    from dragonfly2_tpu.daemon.peer.task_manager import FileTaskRequest
    from dragonfly2_tpu.proto.common import UrlMeta

    return FileTaskRequest(url=url, output=output,
                           meta=UrlMeta(digest=digest))


def test_delta_e2e_reuse_accounting_and_corrupt_base(run_async, tmp_path):
    """Host with landed version N receives N+1 via delta: reused spans
    never cross the wire (origin byte accounting + metric), accounting
    sums exactly to the content length, the result is byte-identical and
    announced (served to a third peer), and a corrupt base chunk is
    detected during the local copy and transparently re-fetched."""
    from tests import test_p2p_e2e as e2e
    from dragonfly2_tpu.delta.resolver import publish_manifest_for
    from dragonfly2_tpu.pkg import metrics as metrics_lib
    from dragonfly2_tpu.delta import resolver as resolver_mod

    content = os.urandom(6 << 20)
    mutated = scattered_mutation(content, frac=0.01, sites=3)
    sha1 = "sha256:" + hashlib.sha256(content).hexdigest()
    sha2 = "sha256:" + hashlib.sha256(mutated).hexdigest()

    async def body():
        origin, base_url, stats = await _two_blob_origin(content, mutated)
        sched = await e2e.start_scheduler()
        daemons = []
        try:
            seed = await e2e.start_daemon(tmp_path, "seed", sched.port(),
                                         seed=True)
            peer = await e2e.start_daemon(tmp_path, "peer", sched.port())
            daemons += [seed, peer]
            url1, url2 = f"{base_url}/v1", f"{base_url}/v2"

            # Seed lands both versions and publishes their manifests.
            r1 = await _drain_task(seed.task_manager, _file_req(url1, sha1))
            r2 = await _drain_task(seed.task_manager, _file_req(url2, sha2))
            assert await publish_manifest_for(
                seed.task_manager, r1.task_id, params=P) is not None
            assert await publish_manifest_for(
                seed.task_manager, r2.task_id, params=P) is not None

            # Peer lands version N via P2P.
            p1 = await _drain_task(peer.task_manager, _file_req(url1, sha1))
            v2_origin_before = stats["v2"]

            # Version N+1 arrives as a delta.
            before = resolver_mod.DELTA_BYTES.labels("reused")._value.get()
            p2 = await _drain_task(peer.task_manager,
                                   _file_req(url2, sha2), base=p1.task_id)
            st = peer.task_manager.delta_stats[p2.task_id]
            # Exact accounting: every byte booked exactly once.
            assert st["reused_bytes"] + st["fetched_bytes"] == len(mutated)
            assert st["corrupt_base"] == 0
            # The point of the plane: a 1% scattered mutation moves a
            # small fraction of the bytes.
            assert st["fetched_bytes"] < 0.2 * len(mutated), st
            assert st["reused_bytes"] > 0.8 * len(mutated), st
            # Reused spans never on the wire: origin served ONLY the
            # fetched spans for v2 during the delta (the seed already
            # held v2, so v2 origin traffic here is the peer's ranged
            # back-sources), plus the source client's 1-byte length
            # probe per ranged task.
            assert stats["v2"] - v2_origin_before <= \
                st["fetched_bytes"] + 1024
            # Metric agrees with per-task stats.
            after = resolver_mod.DELTA_BYTES.labels("reused")._value.get()
            assert after - before == st["reused_bytes"]

            # Byte-identical result, served to peers: verify the store.
            store = peer.task_manager.storage.find_completed_task(
                p2.task_id)
            assert store is not None and store.metadata.digest == sha2
            got = bytearray()
            with store:
                for rec in store.get_pieces():
                    got += store.read_piece(rec.num)
            assert bytes(got) == mutated

            # --- corrupt base: a second host with a silently-corrupted
            # copy of v1 still lands v2 byte-identical, re-fetching the
            # poisoned chunks.
            peer2 = await e2e.start_daemon(tmp_path, "peer2", sched.port())
            daemons.append(peer2)
            q1 = await _drain_task(peer2.task_manager, _file_req(url1, sha1))
            base_store = peer2.task_manager.storage.find_completed_task(
                q1.task_id)
            # Flip bytes on disk AFTER landing (bitrot under the task).
            with open(base_store.data_path, "r+b") as f:
                f.seek(100_000)
                f.write(b"\xde\xad\xbe\xef" * 8)
            q2 = await _drain_task(peer2.task_manager,
                                   _file_req(url2, sha2), base=q1.task_id)
            st2 = peer2.task_manager.delta_stats[q2.task_id]
            assert st2["corrupt_base"] >= 1
            assert st2["reused_bytes"] + st2["fetched_bytes"] == len(mutated)
            store2 = peer2.task_manager.storage.find_completed_task(
                q2.task_id)
            assert store2.metadata.digest == sha2
        finally:
            for d in daemons:
                await d.stop()
            await sched.stop()
            await origin.cleanup()

    run_async(body(), timeout=120)


def test_delta_flight_events_attribute_phases(run_async, tmp_path):
    """The flight recorder books delta local copies as store time and
    span pulls as dcn time; the phase partition stays wall-time-exact
    and dfget --explain's renderer shows the delta events."""
    from tests import test_p2p_e2e as e2e
    from dragonfly2_tpu.delta.resolver import publish_manifest_for
    from dragonfly2_tpu.pkg import flight as flightlib

    content = os.urandom(2 << 20)
    mutated = scattered_mutation(content, frac=0.02, sites=2)
    sha1 = "sha256:" + hashlib.sha256(content).hexdigest()
    sha2 = "sha256:" + hashlib.sha256(mutated).hexdigest()

    async def body():
        origin, base_url, _stats = await _two_blob_origin(content, mutated)
        sched = await e2e.start_scheduler()
        daemons = []
        try:
            seed = await e2e.start_daemon(tmp_path, "seedf", sched.port(),
                                         seed=True)
            peer = await e2e.start_daemon(tmp_path, "peerf", sched.port())
            daemons += [seed, peer]
            # Per-daemon recorders: both embedded daemons share the
            # process-global recorder by default, and the seed's finished
            # flight for the same task id would clip the peer's timeline.
            seed.task_manager.flight = flightlib.FlightRecorder()
            peer.task_manager.flight = flightlib.FlightRecorder()
            r1 = await _drain_task(seed.task_manager,
                                   _file_req(f"{base_url}/v1", sha1))
            r2 = await _drain_task(seed.task_manager,
                                   _file_req(f"{base_url}/v2", sha2))
            await publish_manifest_for(seed.task_manager, r1.task_id,
                                       params=P)
            await publish_manifest_for(seed.task_manager, r2.task_id,
                                       params=P)
            p1 = await _drain_task(peer.task_manager,
                                   _file_req(f"{base_url}/v1", sha1))
            p2 = await _drain_task(peer.task_manager,
                                   _file_req(f"{base_url}/v2", sha2),
                                   base=p1.task_id)
            tf = peer.task_manager.flight.get(p2.task_id)
            assert tf is not None
            report = flightlib.analyze(tf)
            counts = report["event_counts"]
            assert counts.get("delta_reuse", 0) >= 1
            assert counts.get("delta_fetch", 0) >= 1
            # store phase (local copies) present; partition exact.
            assert report["phases"]["store"] > 0
            total = sum(report["phases"].values()) + report["other_s"]
            assert total == pytest.approx(report["wall_s"], rel=0.05)
            text = flightlib.render_waterfall(report)
            assert "store" in text
        finally:
            for d in daemons:
                await d.stop()
            await sched.stop()
            await origin.cleanup()

    run_async(body(), timeout=120)


def test_download_delta_device_hotswap_e2e(run_async, tmp_path):
    """The full device chain: version N lands in HBM via the fabric,
    version N+1 arrives as a delta, reused chunks are copied
    device-side out of the live buffer, the assembled spare verifies
    on-device, and the DoubleBuffer flip atomically exposes the new
    tensors."""
    from tests import test_p2p_e2e as e2e
    from tests.test_device_sink import _start_sink_daemon
    from dragonfly2_tpu.client import device as device_lib
    from dragonfly2_tpu.delta.resolver import publish_manifest_for
    from dragonfly2_tpu.ops.hbm_sink import DoubleBuffer

    rng = np.random.RandomState(3)
    tensors_v1 = {
        "w1": rng.randn(256, 256).astype(np.float32),
        "w2": rng.randn(256, 128).astype(np.float32),
        "bias": rng.randn(512).astype(np.float32),
    }
    # Version 2: scattered update — one tensor tweaked, others identical.
    tensors_v2 = {k: v.copy() for k, v in tensors_v1.items()}
    tensors_v2["bias"][7] += 1.0
    tensors_v2["w2"][3, :8] *= 1.5
    v1 = _make_safetensors(tensors_v1)
    v2 = _make_safetensors(tensors_v2)
    assert len(v1) == len(v2)
    sha1 = "sha256:" + hashlib.sha256(v1).hexdigest()
    sha2 = "sha256:" + hashlib.sha256(v2).hexdigest()
    params = CDCParams(mask_bits=12, min_size=2 << 10, max_size=32 << 10)

    async def body():
        origin, base_url, _stats = await _two_blob_origin(v1, v2)
        sched = await e2e.start_scheduler()
        daemons = []
        try:
            seed = await e2e.start_daemon(tmp_path, "seedd", sched.port(),
                                         seed=True)
            pod = await _start_sink_daemon(tmp_path, "pod", sched.port())
            daemons += [seed, pod]
            r1 = await _drain_task(seed.task_manager,
                                   _file_req(f"{base_url}/v1", sha1))
            r2 = await _drain_task(seed.task_manager,
                                   _file_req(f"{base_url}/v2", sha2))
            await publish_manifest_for(seed.task_manager, r1.task_id,
                                       params=params)
            await publish_manifest_for(seed.task_manager, r2.task_id,
                                       params=params)

            # Serve version N from HBM.
            result = await device_lib.download_to_device(
                pod, f"{base_url}/v1", digest=sha1)
            hot = DoubleBuffer()
            hot.flip(result.as_words(), result.load_safetensors())
            assert hot.generation == 1
            np.testing.assert_array_equal(
                np.asarray(hot.tensors()["bias"]), tensors_v1["bias"])

            # Hot-swap to version N+1.
            swap = await device_lib.download_delta(
                pod, f"{base_url}/v2", base=result.task_id, hot=hot,
                digest=sha2)
            assert swap.flipped and hot.generation == 2
            assert swap.on_device
            # Device-side reuse actually happened: most of the content
            # moved HBM->HBM, not host->device.
            assert swap.reused_device_bytes > 0.5 * len(v2)
            assert swap.reused_device_bytes + swap.staged_bytes == len(v2)
            # Wire-side delta accounting recorded too.
            assert swap.stats and \
                swap.stats["reused_bytes"] + swap.stats["fetched_bytes"] \
                == len(v2)
            for name, want in tensors_v2.items():
                np.testing.assert_array_equal(
                    np.asarray(hot.tensors()[name]), want, err_msg=name)
        finally:
            for d in daemons:
                await d.stop()
            await sched.stop()
            await origin.cleanup()

    run_async(body(), timeout=120)


def test_example_checkpoint_hotswap_smoke():
    """The end-to-end example runs on CPU (JAX_PLATFORMS=cpu) and
    reports a successful flip."""
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, os.path.join(repo, "examples",
                                      "checkpoint_hotswap.py")],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert "flipped to generation 2" in proc.stdout, proc.stdout
