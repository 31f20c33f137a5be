"""P2P integration: scheduler + seed + N peer daemons on localhost.

BASELINE config #2 analog (8-peer fan-out, origin fetched ~once) — the
hermetic multi-process harness from SURVEY.md §4 realized in-process: one
origin, one scheduler, one seed daemon, N peer daemons, all on one loop.
"""

import asyncio
import hashlib
import random

import pytest
from aiohttp import web

from dragonfly2_tpu.client import dfget as dfget_lib
from dragonfly2_tpu.daemon.config import DaemonConfig
from dragonfly2_tpu.daemon.daemon import Daemon
from dragonfly2_tpu.pkg.piece import Range
from dragonfly2_tpu.scheduler.config import SchedulerConfig
from dragonfly2_tpu.scheduler.server import SchedulerServer

CONTENT = bytes(random.Random(99).randbytes(10 * 1024 * 1024))
SHA = "sha256:" + hashlib.sha256(CONTENT).hexdigest()


async def start_origin():
    stats = {"blob_streams": 0, "blob_bytes": 0}

    async def blob(request: web.Request) -> web.StreamResponse:
        stats["blob_streams"] += 1
        rng = request.headers.get("Range")
        if rng:
            r = Range.parse_http(rng, len(CONTENT))
            data = CONTENT[r.start : r.start + r.length]
            stats["blob_bytes"] += len(data)
            return web.Response(
                status=206, body=data,
                headers={
                    "Content-Range": f"bytes {r.start}-{r.start + r.length - 1}/{len(CONTENT)}",
                    "Accept-Ranges": "bytes",
                })
        stats["blob_bytes"] += len(CONTENT)
        return web.Response(body=CONTENT, headers={"Accept-Ranges": "bytes"})

    app = web.Application()
    app.router.add_get("/blob", blob)
    runner = web.AppRunner(app, access_log=None)
    await runner.setup()
    site = web.TCPSite(runner, "127.0.0.1", 0)
    await site.start()
    return runner, site._server.sockets[0].getsockname()[1], stats


async def start_scheduler() -> SchedulerServer:
    cfg = SchedulerConfig()
    cfg.server.port = 0
    cfg.scheduling.retry_interval = 0.05   # fast tests
    cfg.scheduling.no_source_patience = 0.5
    cfg.gc.interval = 3600
    server = SchedulerServer(cfg)
    await server.start()
    return server


def daemon_config(tmp_path, name: str, scheduler_port: int, *, seed=False) -> DaemonConfig:
    cfg = DaemonConfig()
    cfg.work_home = str(tmp_path / name)
    cfg.__post_init__()
    cfg.host.hostname = name
    cfg.host.ip = "127.0.0.1"
    cfg.scheduler.addrs = [f"127.0.0.1:{scheduler_port}"]
    cfg.seed_peer = seed
    cfg.gc_interval = 3600
    cfg.download.piece_concurrency = 1          # deterministic origin counting
    cfg.download.concurrent_min_length = 1 << 40
    return cfg


async def start_daemon(tmp_path, name, scheduler_port, *, seed=False) -> Daemon:
    d = Daemon(daemon_config(tmp_path, name, scheduler_port, seed=seed))
    await d.start()
    return d


async def dfget_via(daemon: Daemon, url: str, out: str, digest: str = SHA,
                    *, allow_source_fallback: bool = False,
                    timeout: float = 60.0) -> dict:
    from dragonfly2_tpu.proto.common import UrlMeta

    return await dfget_lib.download(
        dfget_lib.DfgetConfig(
            url=url, output=out,
            daemon_sock=daemon.config.unix_sock,
            meta=UrlMeta(digest=digest),
            allow_source_fallback=allow_source_fallback,
            timeout=timeout,
        ))


class TestP2PFanout:
    def test_seed_plus_peers_single_origin_fetch(self, run_async, tmp_path):
        """8 peers + 1 seed: origin serves ~one content copy; every peer's
        output sha-verifies; peers report from_p2p."""

        async def body():
            origin, oport, stats = await start_origin()
            sched = await start_scheduler()
            url = f"http://127.0.0.1:{oport}/blob"
            daemons = []
            try:
                seed = await start_daemon(tmp_path, "seed", sched.port(), seed=True)
                daemons.append(seed)
                peers = []
                for i in range(8):
                    d = await start_daemon(tmp_path, f"peer{i}", sched.port())
                    daemons.append(d)
                    peers.append(d)

                results = await asyncio.gather(*[
                    dfget_via(d, url, str(tmp_path / f"out{i}.bin"))
                    for i, d in enumerate(peers)
                ])
                for i, r in enumerate(results):
                    assert r["state"] == "done"
                    data = (tmp_path / f"out{i}.bin").read_bytes()
                    assert hashlib.sha256(data).hexdigest() == SHA.split(":")[1]
                # Origin economy: one probe + one content stream (seed only).
                assert stats["blob_streams"] <= 3, stats
                assert stats["blob_bytes"] <= len(CONTENT) + (1 << 20), stats
                # At least some peers rode P2P (the rest may have deduped
                # onto a running conductor of the same daemon — not here,
                # every daemon is distinct, so all should be P2P).
                assert all(r["from_p2p"] for r in results), results
            finally:
                for d in daemons:
                    await d.stop()
                await sched.stop()
                await origin.cleanup()

        run_async(body(), timeout=120)

    def test_first_peer_back_source_without_seed(self, run_async, tmp_path):
        """No seed daemon: first peer falls back to origin, second peer
        pulls pieces from the first over P2P."""

        async def body():
            origin, oport, stats = await start_origin()
            sched = await start_scheduler()
            sched.config.seed_peer_enabled = False
            url = f"http://127.0.0.1:{oport}/blob"
            daemons = []
            try:
                d1 = await start_daemon(tmp_path, "p1", sched.port())
                d2 = await start_daemon(tmp_path, "p2", sched.port())
                daemons += [d1, d2]
                r1 = await dfget_via(d1, url, str(tmp_path / "o1.bin"))
                assert r1["state"] == "done"
                streams_after_first = stats["blob_streams"]

                r2 = await dfget_via(d2, url, str(tmp_path / "o2.bin"))
                assert r2["state"] == "done"
                assert r2["from_p2p"]
                assert (tmp_path / "o2.bin").read_bytes() == CONTENT
                # Second download never touched origin.
                assert stats["blob_streams"] == streams_after_first
            finally:
                for d in daemons:
                    await d.stop()
                await sched.stop()
                await origin.cleanup()

        run_async(body(), timeout=60)

    def test_seed_reannounce_serves_after_scheduler_restart(self, run_async, tmp_path):
        """Scheduler restarts (loses all state); seed re-announce path lets a
        new peer still fetch via P2P without a fresh origin fetch."""

        async def body():
            origin, oport, stats = await start_origin()
            sched = await start_scheduler()
            url = f"http://127.0.0.1:{oport}/blob"
            daemons = []
            try:
                seed = await start_daemon(tmp_path, "seed", sched.port(), seed=True)
                daemons.append(seed)
                d1 = await start_daemon(tmp_path, "p1", sched.port())
                daemons.append(d1)
                await dfget_via(d1, url, str(tmp_path / "o1.bin"))
                bytes_after = stats["blob_bytes"]

                # Scheduler dies and comes back empty on the same port.
                port = sched.port()
                await sched.stop()
                cfg = SchedulerConfig()
                cfg.server.port = port
                cfg.scheduling.retry_interval = 0.05
                cfg.gc.interval = 3600
                sched2 = SchedulerServer(cfg)
                await sched2.start()
                # Daemons re-announce their host records.
                for d in daemons:
                    await d.announcer.announce_once()

                d2 = await start_daemon(tmp_path, "p2", sched2.port())
                daemons.append(d2)
                r = await dfget_via(d2, url, str(tmp_path / "o2.bin"))
                assert r["state"] == "done"
                assert (tmp_path / "o2.bin").read_bytes() == CONTENT
                # Origin payload untouched: seed re-announced local pieces.
                assert stats["blob_bytes"] == bytes_after, stats
                await sched2.stop()
            finally:
                for d in daemons:
                    await d.stop()
                await origin.cleanup()

        run_async(body(), timeout=60)


def test_broker_no_channel_leak(run_async, tmp_path):
    from dragonfly2_tpu.daemon.peer.broker import PieceBroker, PieceEvent

    async def body():
        b = PieceBroker()
        for i in range(100):
            b.publish(f"task{i}", PieceEvent([1]))
        assert len(b._tasks) == 0  # no subscribers → no channels
        q = b.subscribe("t")
        b.publish("t", PieceEvent([1]))
        assert (await q.get()).piece_nums == [1]
        b.unsubscribe("t", q)
        assert len(b._tasks) == 0

    run_async(body())


def test_dispatcher_peek_does_not_reserve():
    from dragonfly2_tpu.daemon.peer.piece_dispatcher import PieceDispatcher

    d = PieceDispatcher()
    d.total_piece_count = 2
    d.piece_size = 4
    d.content_length = 8
    d.upsert_parent("p1", "127.0.0.1", 9000)
    d.on_parent_pieces("p1", [0, 1])
    assert d.has_assignable()
    assert d.has_assignable()  # peek twice, nothing reserved
    a1 = d.try_get()
    a2 = d.try_get()
    assert {a1.piece_num, a2.piece_num} == {0, 1}  # both still assignable


def test_seed_death_mid_transfer_peers_recover(run_async, tmp_path):
    """Resilience: the seed daemon dies while peers are mid-download. Peers
    must still finish sha-exact — rescheduling onto each other for pieces
    already spread, and a bounded back-to-source for the remainder (the
    reference e2e counts pod restarts for the same reason)."""

    async def body():
        origin, oport, stats = await start_origin()
        sched = await start_scheduler()
        url = f"http://127.0.0.1:{oport}/blob"
        daemons = []
        try:
            # Rate-limit the seed's serving (this also selects the
            # limiter-honoring aiohttp upload path over the native server)
            # so the kill deterministically lands mid-transfer.
            seed_cfg = daemon_config(tmp_path, "seed", sched.port(), seed=True)
            seed_cfg.upload.rate_limit = 4 * 1024 * 1024
            seed = Daemon(seed_cfg)
            await seed.start()
            daemons.append(seed)  # killer() stops it; stop() is idempotent
            daemons.append(p1 := await start_daemon(tmp_path, "p1", sched.port()))
            daemons.append(p2 := await start_daemon(tmp_path, "p2", sched.port()))

            async def killer():
                # Wait until at least one peer has a piece, then kill.
                for _ in range(200):
                    for d in (p1, p2):
                        for s in d.storage.tasks():
                            if s.metadata.pieces:
                                await seed.stop()
                                return
                    await asyncio.sleep(0.02)
                await seed.stop()  # nothing landed; kill anyway

            kill_task = asyncio.ensure_future(killer())
            try:
                results = await asyncio.gather(
                    dfget_via(p1, url, str(tmp_path / "k1.bin")),
                    dfget_via(p2, url, str(tmp_path / "k2.bin")))
                await kill_task
            finally:
                kill_task.cancel()
            for i, r in enumerate(results):
                assert r["state"] == "done", r
                got = (tmp_path / f"k{i + 1}.bin").read_bytes()
                assert hashlib.sha256(got).hexdigest() == SHA.split(":")[1]
            # Recovery is allowed to re-touch origin, but boundedly: the
            # seed's partial fetch plus at most one remainder per peer
            # (BOTH peers may legitimately demote if they stall at the
            # same instant — the scheduler allows it).
            assert stats["blob_bytes"] <= 3 * len(CONTENT) + (1 << 20), stats
        finally:
            for d in daemons:
                await d.stop()
            await sched.stop()
            await origin.cleanup()

    run_async(body(), timeout=120)


def test_scheduler_death_mid_transfer_download_still_lands(run_async, tmp_path):
    """Resilience: the scheduler dies while a peer is mid-download. The
    user-visible guarantee: with source fallback permitted, the download
    still lands sha-exact (conductor-level back-source demotion or the
    client library's daemon-side fallback — either path is acceptable;
    losing the download is not)."""

    async def body():
        origin, oport, stats = await start_origin()
        sched = await start_scheduler()
        url = f"http://127.0.0.1:{oport}/blob"
        daemons = []
        try:
            seed_cfg = daemon_config(tmp_path, "seed", sched.port(), seed=True)
            seed_cfg.upload.rate_limit = 4 * 1024 * 1024  # slow serving
            seed = Daemon(seed_cfg)
            await seed.start()
            daemons.append(seed)
            daemons.append(p1 := await start_daemon(tmp_path, "p1",
                                                    sched.port()))

            async def killer():
                for _ in range(200):
                    for s in p1.storage.tasks():
                        if s.metadata.pieces:
                            await sched.stop()
                            return
                    await asyncio.sleep(0.02)
                await sched.stop()

            kill_task = asyncio.ensure_future(killer())
            result = await dfget_via(p1, url, str(tmp_path / "s1.bin"),
                                     allow_source_fallback=True, timeout=90.0)
            # Await the killer: a silently-failed kill would leave the
            # scheduler alive and this test would stop testing anything.
            await kill_task
            assert result["state"] == "done", result
            got = (tmp_path / "s1.bin").read_bytes()
            assert hashlib.sha256(got).hexdigest() == SHA.split(":")[1]
        finally:
            for d in daemons:
                await d.stop()
            try:
                await sched.stop()
            except Exception:
                pass
            await origin.cleanup()

    run_async(body(), timeout=120)


def test_dead_scheduler_at_register_degrades_to_back_source(run_async, tmp_path):
    """Scheduler unreachable at registration: the DAEMON demotes to
    back-to-source (reference behavior) instead of failing the task — no
    client-side source fallback needed, and the piece store is populated
    for reuse."""

    async def body():
        origin, oport, stats = await start_origin()
        url = f"http://127.0.0.1:{oport}/blob"
        # Point the daemon at a port nothing listens on.
        d = None
        try:
            cfg = daemon_config(tmp_path, "p1", scheduler_port=1)
            d = Daemon(cfg)
            await d.start()
            r = await dfget_via(d, url, str(tmp_path / "o.bin"))
            assert r["state"] == "done", r
            assert not r["from_p2p"]
            assert (tmp_path / "o.bin").read_bytes() == CONTENT
            # The store is populated and reusable.
            r2 = await dfget_via(d, url, str(tmp_path / "o2.bin"))
            assert r2["from_reuse"], r2
        finally:
            if d is not None:
                await d.stop()
            await origin.cleanup()

    run_async(body(), timeout=60)


def test_certified_digests_provenance():
    """certified_digest_maps returns only DONE parents' own maps — a
    corrupt still-downloading parent's announced digests must not be
    certified by an honest parent's completion."""
    from dragonfly2_tpu.daemon.peer.piece_dispatcher import PieceDispatcher

    d = PieceDispatcher()
    d.upsert_parent("corrupt", "10.0.0.1", 1)
    d.upsert_parent("honest", "10.0.0.2", 1)
    d.on_parent_pieces("corrupt", [0, 1],
                       digests={0: "crc32c:bad00000", 1: "crc32c:bad00001"})
    assert d.certified_digest_maps() == []        # nobody done yet
    d.on_parent_pieces("honest", [0, 1],
                       digests={0: "crc32c:00000aaa", 1: "crc32c:00000bbb"})
    d.note_parent_done("honest")
    assert d.certified_digest_maps() == [
        {0: "crc32c:00000aaa", 1: "crc32c:00000bbb"}]
    # The merged view (scheduling convenience) may hold the corrupt
    # values, but certification never reads it.
    assert d.piece_digests[0] in ("crc32c:bad00000", "crc32c:00000aaa")
    # certified_digest_maps exposes EVERY done parent's map so the store
    # can pick the one that verifies — done-ness alone does not elect one.
    d.note_parent_done("corrupt")
    maps = d.certified_digest_maps()
    assert {0: "crc32c:00000aaa", 1: "crc32c:00000bbb"} in maps
    assert {0: "crc32c:bad00000", 1: "crc32c:bad00001"} in maps


class _CertStubStore:
    """Minimal store for _await_certification unit tests: a pluggable
    certifies predicate plus the REAL apply_certification (one scan-and-
    install implementation, not a test copy)."""

    from dragonfly2_tpu.storage.local_store import LocalTaskStore as _LTS
    apply_certification = _LTS.apply_certification

    def __init__(self, content_length: int, pieces_verified: bool, certifies):
        import types

        self.metadata = types.SimpleNamespace(content_length=content_length)
        self._pieces_verified = pieces_verified
        self._certifies = certifies
        self.certified_digests = None

    def pieces_verified_against_digests(self):
        return self._pieces_verified

    def certifies(self, m):
        return bool(m) and self._certifies(m)


def _await_cert_conductor(content_length: int, meta: dict, *,
                          pieces_verified: bool = True, certifies=None):
    """Minimal conductor for _await_certification unit tests: the method
    touches only meta, content_range, the stub store and the dispatcher."""
    from dragonfly2_tpu.daemon.peer.conductor import PeerTaskConductor

    c = PeerTaskConductor(
        task_id="t", peer_id="p", url="http://x/",
        store=_CertStubStore(content_length, pieces_verified,
                             certifies or (lambda m: True)),
        scheduler_client=None, piece_manager=None, host_info={}, meta=meta)
    return c


class TestAwaitCertification:
    """Cold-race closer: a child that completes moments before its
    certifying parent waits (bounded by the estimated re-hash cost) for
    the parent's done instead of paying a redundant whole-content hash."""

    def test_catches_a_late_done(self, run_async):
        async def body():
            # 512 MiB -> ~1.07s bound; the done at 0.03s must end the
            # wait far earlier (generous slack for loaded runners).
            c = _await_cert_conductor(512 << 20, {"digest": "sha256:x"})
            c.dispatcher.upsert_parent("seed", "10.0.0.1", 1)
            digests = {0: "crc32c:0000000a"}

            async def late_done():
                await asyncio.sleep(0.03)
                c.dispatcher.on_parent_pieces("seed", [0], digests=digests)
                c.dispatcher.note_parent_done("seed")

            t = asyncio.ensure_future(late_done())
            t0 = asyncio.get_running_loop().time()
            assert await c._await_certification() is True
            elapsed = asyncio.get_running_loop().time() - t0
            await t
            assert c.store.certified_digests == digests
            assert elapsed < 0.5, "wait must end at the done, not the bound"

        run_async(body(), timeout=10)

    def test_corrupt_early_done_does_not_eat_the_budget(self, run_async):
        async def body():
            # Corrupt parent done at t=0 (its map doesn't certify); honest
            # parent's done lands mid-wait — the wait must ride past the
            # corrupt map and return the honest one.
            honest = {0: "crc32c:0000000a"}
            corrupt = {0: "crc32c:deadbeef"}
            c = _await_cert_conductor(
                512 << 20, {"digest": "sha256:x"},
                certifies=lambda m: m == honest)
            c.dispatcher.upsert_parent("bad", "10.0.0.1", 1)
            c.dispatcher.upsert_parent("good", "10.0.0.2", 1)
            c.dispatcher.on_parent_pieces("bad", [0], digests=corrupt)
            c.dispatcher.note_parent_done("bad")

            async def honest_done():
                await asyncio.sleep(0.03)
                c.dispatcher.on_parent_pieces("good", [0], digests=honest)
                c.dispatcher.note_parent_done("good")

            t = asyncio.ensure_future(honest_done())
            assert await c._await_certification() is True
            await t
            assert c.store.certified_digests == honest

        run_async(body(), timeout=10)

    def test_bound_formula_stays_near_break_even(self):
        from dragonfly2_tpu.daemon.peer.conductor import PeerTaskConductor

        bound = PeerTaskConductor._cert_wait_bound
        assert bound(1 << 20) < 0.06        # tiny: epsilon + ~2 ms hash
        assert 0.15 < bound(64 << 20) < 0.25
        assert bound(8 << 30) == 3.0        # capped
        # Monotonic in content: never cheaper to wait longer for less.
        assert bound(1 << 20) < bound(64 << 20) <= bound(8 << 30)

    def test_bound_is_the_estimated_rehash_cost(self, run_async):
        async def body():
            # 64 MiB -> 0.05 + 2 * 0.067 = ~0.18s bound. The lower bound
            # proves the wait ran its budget; the upper is loose slack.
            c = _await_cert_conductor(64 << 20, {"digest": "sha256:x"})
            c.dispatcher.upsert_parent("seed", "10.0.0.1", 1)  # never done
            t0 = asyncio.get_running_loop().time()
            assert await c._await_certification() is False
            elapsed = asyncio.get_running_loop().time() - t0
            assert 0.15 <= elapsed < 1.5, elapsed

        run_async(body(), timeout=10)

    def test_unverified_piece_makes_the_wait_futile(self, run_async):
        async def body():
            # A piece landed without a verified-against digest: no
            # certified map can engage the skip, so no wait at all.
            c = _await_cert_conductor(512 << 20, {"digest": "sha256:x"},
                                      pieces_verified=False)
            c.dispatcher.upsert_parent("seed", "10.0.0.1", 1)
            t0 = asyncio.get_running_loop().time()
            assert await c._await_certification() is False
            assert asyncio.get_running_loop().time() - t0 < 0.05

        run_async(body(), timeout=10)

    def test_scheduler_demotion_ends_the_wait(self, run_async):
        async def body():
            # A need_back_source push blocks every parent via drop_parent:
            # the waiter must wake immediately, not sleep out the bound.
            c = _await_cert_conductor(8 << 30, {"digest": "sha256:x"})
            c.dispatcher.upsert_parent("a", "10.0.0.1", 1)
            c.dispatcher.upsert_parent("b", "10.0.0.2", 1)

            async def demote():
                await asyncio.sleep(0.03)
                for pid in list(c.dispatcher.parents):
                    c.dispatcher.drop_parent(pid)

            t = asyncio.ensure_future(demote())
            t0 = asyncio.get_running_loop().time()
            assert await c._await_certification() is False
            elapsed = asyncio.get_running_loop().time() - t0
            await t
            assert elapsed < 1.0, elapsed

        run_async(body(), timeout=10)

    def test_no_rehash_pending_no_wait(self, run_async):
        async def body():
            c = _await_cert_conductor(64 << 20, {})  # no whole-content digest
            c.dispatcher.upsert_parent("seed", "10.0.0.1", 1)
            t0 = asyncio.get_running_loop().time()
            assert await c._await_certification() is False
            assert asyncio.get_running_loop().time() - t0 < 0.05

        run_async(body(), timeout=10)

    def test_last_certifier_dropping_ends_the_wait(self, run_async):
        async def body():
            # 8 GiB -> bound clamps to 3s; the drop must end the wait early.
            c = _await_cert_conductor(8 << 30, {"digest": "sha256:x"})
            c.dispatcher.upsert_parent("seed", "10.0.0.1", 1)

            async def drop():
                await asyncio.sleep(0.03)
                c.dispatcher.drop_parent("seed")

            t = asyncio.ensure_future(drop())
            t0 = asyncio.get_running_loop().time()
            assert await c._await_certification() is False
            elapsed = asyncio.get_running_loop().time() - t0
            await t
            assert elapsed < 1.0, elapsed

        run_async(body(), timeout=10)

    @pytest.mark.parametrize("ending", ["certified", "timeout",
                                        "no_certifier", "unverifiable",
                                        "digest_does_not_apply"])
    def test_every_ending_stamps_one_cert_wait(self, run_async, ending):
        """However the stay in _await_certification ends it leaves ONE
        ``cert_wait`` on the task's flight (aux = its ms, piece = digest
        maps tried, note = the ending) and its seconds on the counter; a
        task whose completion digest does not apply leaves nothing."""
        from dragonfly2_tpu.daemon.peer.conductor import CERT_WAIT_SECONDS
        from dragonfly2_tpu.pkg import flight

        async def body():
            applies = ending != "digest_does_not_apply"
            c = _await_cert_conductor(
                1 << 20, {"digest": "sha256:x"} if applies else {},
                pieces_verified=ending != "unverifiable")
            c.flight = c.dispatcher.flight = flight.TaskFlight("cert-wait")
            if ending != "no_certifier":
                c.dispatcher.upsert_parent("seed", "10.0.0.1", 1)
            if ending == "certified":
                c.dispatcher.on_parent_pieces(
                    "seed", [0], digests={0: "crc32c:0000000a"})
                c.dispatcher.note_parent_done("seed")
            before = {how: CERT_WAIT_SECONDS.labels(how)._value.get()
                      for how in ("certified", "timeout", "no_certifier",
                                  "unverifiable")}
            assert await c._await_certification() is (ending == "certified")
            stamped = [(piece, aux, note)
                       for _, code, piece, aux, note in c.flight.events()
                       if code == flight.EV_CERT_WAIT]
            moved = {how: CERT_WAIT_SECONDS.labels(how)._value.get() - was
                     for how, was in before.items()}
            if not applies:
                assert stamped == [] and not any(moved.values())
                return
            (tried, ms, note), = stamped
            assert note == ending
            assert tried == (1 if ending == "certified" else 0)
            # 1 MiB: the bound is 52 ms, and only the timeout sits it out.
            assert (40.0 <= ms < 1500.0) if ending == "timeout" else ms < 40.0
            assert moved.pop(ending) == pytest.approx(ms / 1000.0)
            assert not any(moved.values())

        run_async(body(), timeout=10)


def test_ranged_task_seed_trigger_fetches_the_slice(run_async, tmp_path):
    """A ranged dfget through a scheduler with a live seed: the triggered
    seed must fetch exactly the slice under the ranged task id (the range
    rides announce open body -> scheduler Task -> trigger spec), and the
    client's output must be the byte-exact slice — not the whole object."""

    async def body():
        origin, oport, stats = await start_origin()
        sched = await start_scheduler()
        url = f"http://127.0.0.1:{oport}/blob"
        daemons = []
        try:
            daemons.append(seed := await start_daemon(
                tmp_path, "seed", sched.port(), seed=True))
            daemons.append(p1 := await start_daemon(
                tmp_path, "p1", sched.port()))

            from dragonfly2_tpu.proto.common import UrlMeta

            start, length = 2 * 1024 * 1024, 1024 * 1024
            out = str(tmp_path / "slice.bin")
            r = await dfget_lib.download(dfget_lib.DfgetConfig(
                url=url, output=out, daemon_sock=p1.config.unix_sock,
                meta=UrlMeta(range=f"{start}-{start + length - 1}"),
                allow_source_fallback=False, timeout=60.0))
            assert r["state"] == "done", r
            got = open(out, "rb").read()
            assert got == CONTENT[start:start + length]

            # The seed holds the SLICE under the ranged id: content_length
            # is the range length, bytes are the slice.
            slices = [s for d in daemons for s in d.storage.tasks()
                      if s.metadata.content_length == length
                      and s.metadata.done]
            assert slices, "no daemon holds the completed ranged task"
            for s in slices:
                data = b"".join(s.read_piece(n)
                                for n in sorted(s.metadata.pieces))
                assert data == CONTENT[start:start + length]
            # Origin served the slice (possibly via the seed), never the
            # whole object for this request.
            assert stats["blob_bytes"] <= 2 * length, stats
        finally:
            for d in daemons:
                await d.stop()
            await sched.stop()
            await origin.cleanup()

    run_async(body(), timeout=120)


def test_cold_race_child_waits_for_seed_certification(run_async, tmp_path,
                                                      monkeypatch):
    """Cold fan-out race: the child's last piece lands BEFORE the seed's
    completion gate (whole-content validation) passes — the profile's
    whole_content_digest_validation cost. The child must wait (bounded)
    for the seed's done instead of paying its own O(content) re-hash, so
    N children × content hashing collapses into the seed's single
    validation (conductor._await_certification)."""
    import time as _time

    from dragonfly2_tpu.daemon.peer.conductor import PeerTaskConductor
    from dragonfly2_tpu.storage.local_store import LocalTaskStore

    calls: list[str] = []
    real = LocalTaskStore.validate_digest

    def spy(self, expected=""):
        calls.append(self.dir)
        if "/seed/" in self.dir:
            _time.sleep(0.02)  # widen the race: the child completes first
        return real(self, expected)

    monkeypatch.setattr(LocalTaskStore, "validate_digest", spy)
    # Decouple the pass margin from CONTENT's size: the 10 MiB bound
    # (~71 ms) is thinner than spy-sleep + sha256 + propagation on a
    # loaded runner. The test exercises the WAKE-ON-DONE mechanism, not
    # the budget arithmetic (test_bound_formula_stays_near_break_even
    # covers that), so give the wait generous room.
    monkeypatch.setattr(PeerTaskConductor, "_cert_wait_bound",
                        staticmethod(lambda content_length: 2.0))

    async def body():
        origin, oport, stats = await start_origin()
        sched = await start_scheduler()
        url = f"http://127.0.0.1:{oport}/blob"
        daemons = []
        try:
            daemons.append(seed := await start_daemon(
                tmp_path, "seed", sched.port(), seed=True))
            daemons.append(p1 := await start_daemon(
                tmp_path, "p1", sched.port()))
            seed_task = asyncio.ensure_future(
                dfget_via(seed, url, str(tmp_path / "s.bin")))
            # The child joins once the seed is a viable parent (has landed
            # its first piece) and then trails it piece by piece.
            for _ in range(500):
                if any(s.metadata.pieces for s in seed.storage.tasks()):
                    break
                await asyncio.sleep(0.01)
            r1 = await dfget_via(p1, url, str(tmp_path / "c.bin"))
            rs = await seed_task
            assert r1["state"] == "done", r1
            assert rs["state"] == "done", rs
            assert open(tmp_path / "c.bin", "rb").read() == CONTENT
            assert stats["blob_streams"] >= 1
            assert [c for c in calls if "/seed/" in c], \
                "seed (trust anchor) must validate"
            assert not [c for c in calls if "/p1/" in c], \
                "child re-hashed despite the certification wait"
        finally:
            for d in daemons:
                await d.stop()
            await sched.stop()
            await origin.cleanup()

    run_async(body(), timeout=120)


def test_warm_pull_skips_whole_content_rehash(run_async, tmp_path, monkeypatch):
    """A child pulling from a DONE (validated) seed must skip the
    O(content) completion re-hash: every piece verified against the
    seed's announced digests + the seed's certified map. The seed itself
    (trust anchor) must still validate."""
    from dragonfly2_tpu.storage.local_store import LocalTaskStore

    calls: list[str] = []
    real = LocalTaskStore.validate_digest

    def spy(self, expected=""):
        calls.append(self.dir)
        return real(self, expected)

    monkeypatch.setattr(LocalTaskStore, "validate_digest", spy)

    async def body():
        origin, oport, stats = await start_origin()
        sched = await start_scheduler()
        url = f"http://127.0.0.1:{oport}/blob"
        daemons = []
        try:
            daemons.append(seed := await start_daemon(
                tmp_path, "seed", sched.port(), seed=True))
            daemons.append(p1 := await start_daemon(
                tmp_path, "p1", sched.port()))
            # Warm the seed: completes + VALIDATES (the anchor).
            r = await dfget_via(seed, url, str(tmp_path / "w0.bin"))
            assert r["state"] == "done", r
            seed_validations = [c for c in calls if "/seed/" in c]
            assert seed_validations, "seed (anchor) must validate"

            from dragonfly2_tpu.daemon.peer.task_manager import (
                COMPLETION_REHASH,
            )
            skipped_before = COMPLETION_REHASH.labels("skipped")._value.get()
            hashed_before = COMPLETION_REHASH.labels("hashed")._value.get()

            # Child pulls from the done seed: pure P2P, skip engaged.
            r = await dfget_via(p1, url, str(tmp_path / "w1.bin"))
            assert r["state"] == "done", r
            import hashlib as _h
            got = open(tmp_path / "w1.bin", "rb").read()
            assert "sha256:" + _h.sha256(got).hexdigest() == SHA
            p1_validations = [c for c in calls if "/p1/" in c]
            assert not p1_validations, \
                f"child re-hashed despite certified chain: {p1_validations}"
            # The child's store still records the verified digest.
            stores = [s for s in p1.storage.tasks() if s.metadata.done]
            assert stores and stores[0].metadata.digest == SHA
            # The decision is operator-visible: exactly one skip counted
            # for this pull, and the hashed branch did not move (deltas
            # against the pre-pull snapshot — the counter is process-
            # global across the suite).
            assert COMPLETION_REHASH.labels("skipped")._value.get() \
                == skipped_before + 1
            assert COMPLETION_REHASH.labels("hashed")._value.get() \
                == hashed_before
        finally:
            for d in daemons:
                await d.stop()
            await sched.stop()
            await origin.cleanup()

    run_async(body(), timeout=120)


# --------------------------------------------------------------------- #
# The parent's spans on the sync stream (pkg/flight SpanRelay -> the
# child's parent_source_first_byte / parent_verified)
# --------------------------------------------------------------------- #

NEW_EVENTS = ("source_first_byte", "cert_wait", "parent_done",
              "parent_source_first_byte", "parent_verified")


def _named(tf, *names):
    """(piece, aux, note) of the flight's events of these names."""
    from dragonfly2_tpu.pkg import flight

    return [(piece, aux, note) for _, code, piece, aux, note in tf.events()
            if flight.EVENT_NAMES[code] in names]


@pytest.mark.parametrize("wire", ["relayed", "parent_sends_none",
                                  "child_ignores_the_field"])
def test_cold_pull_carries_the_seeds_spans(run_async, tmp_path, monkeypatch,
                                           wire):
    """A child trailing a seed that is still pulling from the origin: each
    origin request's first byte and the seed's whole-object verify reach
    the child's flight ONCE each, on the announcement of the piece and on
    ``done``; the tail of the pull is booked as ``verify``. A parent that
    sends no ``spans`` and a child that never reads the field pull just as
    well. Afterwards a re-land on the child, and a pull by a second child
    from the seed now complete, stamp no parent span."""
    import time as _time

    from dragonfly2_tpu.daemon.peer.conductor import PeerTaskConductor
    from dragonfly2_tpu.daemon.peer.piece_dispatcher import PieceDispatcher
    from dragonfly2_tpu.pkg import flight
    from dragonfly2_tpu.storage.local_store import LocalTaskStore

    real = LocalTaskStore.validate_digest

    def slow_seed(self, expected=""):
        if "/seed/" in self.dir:
            _time.sleep(0.05)     # the child's last piece lands first
        return real(self, expected)

    monkeypatch.setattr(LocalTaskStore, "validate_digest", slow_seed)
    monkeypatch.setattr(PeerTaskConductor, "_cert_wait_bound",
                        staticmethod(lambda content_length: 5.0))
    if wire == "parent_sends_none":
        monkeypatch.setattr(flight.SpanRelay, "take", lambda self: [])
    elif wire == "child_ignores_the_field":
        monkeypatch.setattr(PieceDispatcher, "note_parent_spans",
                            lambda self, spans: None)

    async def body():
        origin, oport, stats = await start_origin()
        sched = await start_scheduler()
        url = f"http://127.0.0.1:{oport}/blob"
        daemons = []
        try:
            daemons.append(seed := await start_daemon(
                tmp_path, "seed", sched.port(), seed=True))
            daemons.append(p1 := await start_daemon(
                tmp_path, "p1", sched.port()))
            daemons.append(p2 := await start_daemon(
                tmp_path, "p2", sched.port()))
            # The seed keeps the process's recorder (its source client
            # stamps there); each child gets a ring of its own.
            p1.task_manager.flight = flight.FlightRecorder()
            p2.task_manager.flight = flight.FlightRecorder()
            seed_task = asyncio.ensure_future(
                dfget_via(seed, url, str(tmp_path / "s.bin")))
            for _ in range(500):
                if any(s.metadata.pieces for s in seed.storage.tasks()):
                    break
                await asyncio.sleep(0.01)
            r1 = await dfget_via(p1, url, str(tmp_path / "c.bin"))
            assert (await seed_task)["state"] == "done"
            assert r1["state"] == "done", r1
            assert open(tmp_path / "c.bin", "rb").read() == CONTENT
            task_id = r1["task_id"]

            seed_tf = flight.get(task_id)
            child_tf = p1.task_manager.flight.get(task_id)
            sent = _named(seed_tf, "source_first_byte")
            assert sent and all(piece == 0 and ms > 0 for piece, ms, _ in sent)
            (frontier, behind, _), = _named(seed_tf, "verify_start")
            total = len(seed.storage.try_get(task_id).metadata.pieces)
            assert 0 <= frontier <= total and behind == total - frontier
            (read_back, verify_ms, how), = _named(seed_tf, "verified")
            assert how == "prefix" and verify_ms >= 50.0
            assert 0 <= read_back <= total
            assert not _named(seed_tf, "cert_wait", "parent_done",
                              "parent_source_first_byte", "parent_verified")

            (tried, waited_ms, note), = _named(child_tf, "cert_wait")
            assert note == "certified" and tried >= 1
            assert _named(child_tf, "parent_done") == [(total, 0.0, "")]
            got = _named(child_tf, "parent_source_first_byte",
                         "parent_verified")
            report = flight.analyze(child_tf)
            text = flight.render_waterfall(report)
            if wire == "relayed":
                # Each of the seed's spans once, with the seed's own ms.
                assert sorted(got) == sorted(
                    [(piece, round(ms, 3), "") for piece, ms, _ in sent]
                    + [(int(behind), round(verify_ms, 3), "")])
                assert report["parent"]["verified_ms"] == \
                    round(verify_ms, 3)
                assert f"seed verify {round(verify_ms, 3):.1f} ms" in text
                assert "origin first byte" in text
            else:
                assert got == []
                assert "seed verify" not in text
            # (the report rounds seconds to the microsecond)
            assert report["phases"]["verify"] >= waited_ms / 1000.0 - 1e-5
            assert report["parent"]["cert_wait_ms"] == round(waited_ms, 3)
            assert (f"cert_wait={round(waited_ms, 3):.1f} ms (certified)"
                    in text)

            # A re-land on the child: served from its store, nothing new.
            counts = flight.analyze(child_tf)["event_counts"]
            r = await dfget_via(p1, url, str(tmp_path / "again.bin"))
            assert r["state"] == "done" and r["from_reuse"], r
            after = flight.analyze(child_tf)["event_counts"]
            assert {n: after.get(n, 0) for n in NEW_EVENTS} == \
                {n: counts.get(n, 0) for n in NEW_EVENTS}

            # A second child, the seed complete: its wait ends at once on
            # the snapshot's done, and a parent nobody waited for sends
            # no span.
            r2 = await dfget_via(p2, url, str(tmp_path / "late.bin"))
            assert r2["state"] == "done", r2
            late_tf = p2.task_manager.flight.get(task_id)
            (_, _, note), = _named(late_tf, "cert_wait")
            assert note == "certified"
            assert not _named(late_tf, "parent_source_first_byte",
                              "parent_verified")
        finally:
            for d in daemons:
                await d.stop()
            await sched.stop()
            await origin.cleanup()

    run_async(body(), timeout=120)


def test_sync_stream_tolerates_absent_unknown_and_malformed_spans(run_async):
    """The child's side alone, against a parent that speaks the wire by
    hand: a message with ``spans``, one without the field, one with a field
    and span names this child has never heard of and entries of other
    shapes. Every well-formed span of a known name becomes ONE event; the
    rest is passed over and the stream lives on."""
    from dragonfly2_tpu.daemon.peer.piece_dispatcher import PieceDispatcher
    from dragonfly2_tpu.daemon.peer.synchronizer import PieceTaskSynchronizer
    from dragonfly2_tpu.pkg import flight
    from dragonfly2_tpu.pkg.types import NetAddr
    from dragonfly2_tpu.rpc import Server

    async def body():
        base = {"total_piece_count": 3, "content_length": 3 << 20,
                "piece_size": 1 << 20, "digests": {}}

        async def handler(stream, ctx):
            await stream.send({**base, "pieces": [0], "done": False,
                               "spans": [["source_first_byte", 12.5, 0]]})
            await stream.send({**base, "pieces": [1], "done": False})
            await stream.send({
                **base, "pieces": [2], "done": True, "sparks": {"x": 1},
                "spans": [["verified", 80.25, 2], ["hashed", 1.0, 0],
                          ["verified", "soon", 0], 7, ["verified"], None,
                          [["verified"], 1.0, 0]]})

        server = Server("test.parent.spans")
        server.register_stream("Peer.SyncPieceTasks", handler)
        await server.serve(NetAddr.tcp("127.0.0.1", 0))
        try:
            tf = flight.TaskFlight("t-spans")
            dispatcher = PieceDispatcher(flight=tf)
            sync = PieceTaskSynchronizer("t-spans", "child", dispatcher)
            dispatcher.upsert_parent("parent-1", "127.0.0.1", 9000)
            task = asyncio.ensure_future(
                sync._sync_one("parent-1", "127.0.0.1", server.port()))
            sync._tasks["parent-1"] = task
            await asyncio.wait_for(task, 10)
            p = dispatcher.parents["parent-1"]
            assert p.pieces == {0, 1, 2} and not p.blocked
            assert "parent-1" in dispatcher.done_parents
            assert [(flight.EVENT_NAMES[code], piece, aux)
                    for _, code, piece, aux, _ in tf.events()
                    if code != flight.EV_PARENT_PIECES] == [
                ("parent_source_first_byte", 0, 12.5),
                ("parent_verified", 2, 80.25),
                ("parent_done", 3, 0.0)]
            await sync.close()
        finally:
            await server.close()

    run_async(body(), timeout=30)
