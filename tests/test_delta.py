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

import asyncio
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


# ------------------------------------------------------------------ #
# The landing as piece jobs (delta/resolver.py _assemble): one
# TaskManager with no scheduler, version N imported, version N+1 behind an
# origin, the target's pieces far smaller than a real landing's so a dozen
# jobs run side by side
# ------------------------------------------------------------------ #

JOB_PARAMS = CDCParams(mask_bits=12, min_size=2 << 10, max_size=32 << 10)
JOB_PIECE = 64 << 10


def _manifest_of(content: bytes, params: CDCParams) -> DeltaManifest:
    ch = GearChunker(params)
    ch.feed(content)
    ch.finish()
    return DeltaManifest(name="t", content_length=len(content),
                         chunks=ch.chunks, params=ch.params)


class _JobRig:
    """Version N landed, version N+1 planned, the target store registered
    with ``JOB_PIECE`` pieces; ``land`` drives ``_run_delta`` itself."""

    def __init__(self, tm, origin, url, v1, v2, base_store):
        from dragonfly2_tpu.delta.resolver import plan_delta

        self.tm, self.origin, self.v2 = tm, origin, v2
        self.base_store = base_store
        self.new_m = _manifest_of(v2, JOB_PARAMS)
        self.plan = plan_delta(self.new_m, _manifest_of(v1, JOB_PARAMS))
        self.req = _file_req(
            url, "sha256:" + hashlib.sha256(v2).hexdigest())
        self.task_id = self.req.task_id()
        from dragonfly2_tpu.storage.local_store import TaskStoreMetadata
        self.store = tm.storage.register_task(TaskStoreMetadata(
            task_id=self.task_id, piece_size=JOB_PIECE))
        self.pieces = -(-len(v2) // JOB_PIECE)

    def landing(self):
        from dragonfly2_tpu.delta import resolver

        return resolver._run_delta(self.tm, self.req, self.task_id,
                                   self.base_store, self.new_m, self.plan, 4)

    async def land(self):
        final = None
        async for p in self.landing():
            assert p.state != "failed", p.error
            final = p
        assert final is not None and final.state == "done"
        return self.tm.delta_stats[self.task_id]

    def landed(self) -> bytes:
        got = bytearray()
        for rec in self.store.get_pieces():
            got += self.store.read_piece(rec.num)
        return bytes(got)

    def events(self, name: str) -> list:
        from dragonfly2_tpu.pkg import flight as flightlib

        return [e for e in self.tm.flight.get(self.task_id).events()
                if flightlib.EVENT_NAMES.get(e[1]) == name]


def _with_job_rig(run_async, tmp_path, body, v1: bytes, v2: bytes):
    from dragonfly2_tpu.daemon.peer.piece_manager import PieceManager
    from dragonfly2_tpu.daemon.peer.task_manager import TaskManager
    from dragonfly2_tpu.pkg import flight as flightlib
    from dragonfly2_tpu.source import default_registry
    from dragonfly2_tpu.storage import StorageManager, StorageOption

    async def run():
        origin, base_url, _stats = await _two_blob_origin(v1, v2)
        storage = StorageManager(StorageOption(
            data_dir=str(tmp_path / "data")))
        try:
            tm = TaskManager(storage, PieceManager())
            tm.flight = flightlib.FlightRecorder()
            path = tmp_path / "v1.bin"
            path.write_bytes(v1)
            base = await tm.import_task(str(path), _file_req("probe://v1"))
            rig = _JobRig(tm, origin, f"{base_url}/v2", v1, v2,
                          storage.find_completed_task(base["task_id"]))
            await body(rig)
        finally:
            await default_registry().close_all()
            storage.close()
            await origin.cleanup()

    run_async(run(), timeout=120)


def _pieces_counted() -> dict:
    from dragonfly2_tpu.delta import resolver

    return {how: resolver.DELTA_PIECES.labels(how)._value.get()
            for how in ("built", "resumed")}


def _job_versions(n: int = 1 << 20):
    content = os.urandom(n)
    return content, scattered_mutation(content, frac=0.02, sites=3)


def test_piece_jobs_cut_every_chunk_to_one_owner():
    """The cut alone: every chunk of the new manifest is owned by exactly
    one job (the piece it starts in), lies whole inside the buffer span of
    every job whose piece it overlaps, and no span is wider than the piece
    and two chunks."""
    from dragonfly2_tpu.delta.resolver import _piece_jobs, plan_delta

    v1, v2 = _job_versions()
    new_m = _manifest_of(v2, JOB_PARAMS)
    plan = plan_delta(new_m, _manifest_of(v1, JOB_PARAMS))
    jobs = _piece_jobs(new_m, plan, JOB_PIECE)
    assert [j.num for j in jobs] == list(range(-(-len(v2) // JOB_PIECE)))
    owners: dict = {}
    straddlers = 0
    for j in jobs:
        chunks = sorted([c for c, _ in j.reused] + [c for c, _ in j.fetched],
                        key=lambda c: c.offset)
        # The job's chunks tile its buffer span and cover its piece.
        assert chunks[0].offset == j.lo <= j.start
        assert chunks[-1].end == j.hi >= j.end
        assert all(a.end == b.offset for a, b in zip(chunks, chunks[1:]))
        assert j.hi - j.lo <= JOB_PIECE + 2 * JOB_PARAMS.max_size
        for c, span in j.fetched:
            assert span in plan.fetch_spans() and span[0] <= c.offset \
                and c.end <= span[1]
        for c in chunks:
            straddlers += not j.owns(c)
            if j.owns(c):
                assert c.offset not in owners
                owners[c.offset] = j.num
    assert sorted(owners) == [c.offset for c in new_m.chunks]
    assert straddlers > 0


def test_delta_jobs_straddling_chunks_booked_once(run_async, tmp_path):
    """With pieces of 64 KiB and chunks of 2-32 KiB nearly every piece
    boundary cuts a chunk: each such chunk is written by two jobs and
    booked by one, both pieces come out right, and the accounting sums to
    the content exactly."""
    v1, v2 = _job_versions()

    async def body(rig):
        before = _pieces_counted()
        st = await rig.land()
        assert st["chunks_reused"] + st["chunks_fetched"] \
            == rig.new_m.num_chunks
        assert st["reused_bytes"] == rig.plan.reused_bytes
        assert st["fetched_bytes"] == rig.plan.fetched_bytes
        assert st["corrupt_base"] == 0
        assert rig.landed() == v2
        after = _pieces_counted()
        assert after["built"] - before["built"] == rig.pieces
        assert after["resumed"] == before["resumed"]
        # One delta_reuse span a job that reused anything, stamped with the
        # job's piece; their notes are the bytes booked.
        spans = rig.events("delta_reuse")
        assert 1 <= len(spans) <= rig.pieces
        assert sum(int(e[4]) for e in spans) == rig.plan.reused_bytes
        assert sum(int(e[4]) for e in rig.events("delta_fetch")) \
            == rig.plan.fetched_bytes

    _with_job_rig(run_async, tmp_path, body, v1, v2)


def test_delta_jobs_commit_out_of_order_under_the_prefix_hasher(
        run_async, tmp_path, monkeypatch):
    """Piece 0 is held back on its thread until later pieces have
    committed: the whole-object sha256 still comes from the prefix hasher
    (``verified``'s note ``prefix``: the frontier piece from memory, the
    others read back), and the landing is byte-equal to the new version."""
    import time as time_mod

    from dragonfly2_tpu.delta import resolver

    v1, v2 = _job_versions()
    build = resolver._build_piece
    order = []

    def held_back(job, buf, views, base_store, store):
        if job.num == 0:
            deadline = time_mod.monotonic() + 10.0
            while (len(store.metadata.pieces) < 3
                   and time_mod.monotonic() < deadline):
                time_mod.sleep(0.005)
        out = build(job, buf, views, base_store, store)
        order.append(job.num)
        return out

    monkeypatch.setattr(resolver, "_build_piece", held_back)

    async def body(rig):
        st = await rig.land()
        assert order.index(0) >= 3, order
        assert st["reused_bytes"] + st["fetched_bytes"] == len(v2)
        (verified,) = rig.events("verified")
        assert verified[4] == "prefix"
        (start,) = rig.events("verify_start")
        assert 0 <= start[2] <= rig.pieces
        assert rig.store.metadata.digest == rig.req.meta.digest
        assert rig.store.metadata.done
        assert rig.landed() == v2

    _with_job_rig(run_async, tmp_path, body, v1, v2)


def test_delta_jobs_corrupt_base_chunk_refetched_among_neighbours(
        run_async, tmp_path):
    """One base chunk rots on disk under the landed task. The job that
    meets it hands it back, it is re-fetched as its own ranged task and
    verified again, counted once (though two jobs may write part of it),
    and every other reused chunk is still reused."""
    v1 = os.urandom(1 << 20)
    v2 = bytearray(v1)
    v2[900_000:910_000] = os.urandom(10_000)     # far from the rot
    v2 = bytes(v2)

    async def body(rig):
        rot = next(b for c, b in rig.plan.reused
                   if b.offset <= 300_000 < b.end)
        with open(rig.base_store.data_path, "r+b") as f:
            f.seek(300_000)
            f.write(bytes(x ^ 0xFF for x in v1[300_000:300_016]))
        st = await rig.land()
        assert st["corrupt_base"] == 1
        assert st["fetched_bytes"] == rig.plan.fetched_bytes + rot.length
        assert st["reused_bytes"] == rig.plan.reused_bytes - rot.length
        assert st["chunks_reused"] == len(rig.plan.reused) - 1
        assert rig.landed() == v2
        assert rig.store.metadata.digest == rig.req.meta.digest

    _with_job_rig(run_async, tmp_path, body, v1, v2)


def test_delta_jobs_client_gone_then_resumed(run_async, tmp_path,
                                             monkeypatch):
    """A client that goes away after its first frame leaves verified pieces,
    a terminal state for waiters, both stores unpinned and NO job still on
    a thread; the retry skips the pieces the store has (their jobs are not
    run) and its accounting still sums to the content."""
    import threading
    import time as time_mod

    from dragonfly2_tpu.delta import resolver

    v1, v2 = _job_versions(2 << 20)
    build = resolver._build_piece
    lock = threading.Lock()
    running = [0]
    built = []

    def slow(job, buf, views, base_store, store):
        with lock:
            running[0] += 1
        try:
            time_mod.sleep(0.03)
            return build(job, buf, views, base_store, store)
        finally:
            with lock:
                running[0] -= 1
                built.append(job.num)

    monkeypatch.setattr(resolver, "_build_piece", slow)

    async def body(rig):
        landing = rig.landing()
        async for p in landing:
            assert p.state == "running" and p.piece_count >= 1
            break
        await landing.aclose()
        # Nothing of the landing is left: no job in its thread, no pin, a
        # terminal state.
        assert running[0] == 0
        assert not rig.store.pinned and not rig.base_store.pinned
        assert not rig.tm.is_task_running(rig.task_id)
        assert rig.tm.flight.get(rig.task_id).state == "failed"
        had = sorted(rig.store.metadata.pieces)
        assert 1 <= len(had) < rig.pieces
        assert sorted(built) == had
        await asyncio.sleep(0.1)
        assert sorted(rig.store.metadata.pieces) == had   # nobody writes on
        for num in had:
            assert rig.store.read_piece(num) \
                == v2[num * JOB_PIECE:(num + 1) * JOB_PIECE]

        before = _pieces_counted()
        del built[:]
        st = await rig.land()
        after = _pieces_counted()
        assert sorted(built) == [n for n in range(rig.pieces)
                                 if n not in had]
        assert after["resumed"] - before["resumed"] == len(had)
        assert after["built"] - before["built"] == rig.pieces - len(had)
        assert st["reused_bytes"] + st["fetched_bytes"] == len(v2)
        assert st["chunks_reused"] + st["chunks_fetched"] \
            == rig.new_m.num_chunks
        assert rig.landed() == v2
        assert rig.store.metadata.digest == rig.req.meta.digest

    _with_job_rig(run_async, tmp_path, body, v1, v2)


def test_flight_analyze_folds_delta_spans_side_by_side():
    """Piece jobs stamp ``delta_reuse`` spans that lie side by side: the
    ``store`` phase is their union, never more seconds than the task
    took, though their ``aux`` sums to several times that."""
    import time as time_mod

    from dragonfly2_tpu.pkg import flight as flightlib

    tf = flightlib.TaskFlight("side-by-side")
    time_mod.sleep(0.06)
    now = time_mod.perf_counter()
    for k in range(6):
        tf.record_at(now - 0.001 * k, flightlib.EV_DELTA_REUSE, k, 50.0,
                     "1")
    tf.finish("done")
    report = flightlib.analyze(tf)
    assert report["event_counts"]["delta_reuse"] == 6
    wall = report["wall_s"]
    assert 0.045 <= report["phases"]["store"] <= wall < 6 * 0.050
    total = sum(report["phases"].values()) + report["other_s"]
    assert total == pytest.approx(wall, rel=0.01)


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


def test_delta_probe_rehearsal(tmp_path):
    """``benchmarks/delta_probe.py`` (the probe ``_JOBS_IN_FLIGHT`` rests
    on) runs whole at a tiny size: every landing through ``_run_delta``
    verified by its own digest, a row a number of jobs in flight."""
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, os.path.join(repo, "benchmarks", "delta_probe.py"),
         "--pieces", "3", "--piece-bytes", str(4 << 20), "--repeats", "1",
         "--in-flight", "1,4", "--out", str(tmp_path / "probe.json")],
        capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-4000:]
    with open(tmp_path / "probe.json") as f:
        rows = json.load(f)["rows"]
    assert [r["in_flight"] for r in rows] == [1, 4]
    assert all(r["how"] == ["prefix"] for r in rows)
