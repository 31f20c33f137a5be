"""Persistent cache tasks: durable records, replica management, RPC family.

Reference: scheduler/resource/persistentcache (Redis-backed durability) +
service_v2.go:1580-1895 (UploadPersistentCacheTask* family). Durability here
is sqlite: records survive a scheduler restart, replicas are re-established
when hosts leave, TTL-expired tasks are deleted everywhere.
"""

from __future__ import annotations

import asyncio
import os

import pytest

from dragonfly2_tpu.client import dfcache
from dragonfly2_tpu.scheduler.config import SchedulerConfig
from dragonfly2_tpu.scheduler.resource.persistentcache import (
    PersistentCacheResource,
    STATE_SUCCEEDED,
)
from dragonfly2_tpu.scheduler.server import SchedulerServer

from tests.test_p2p_e2e import start_daemon


async def _wait(predicate, timeout: float = 40.0):
    deadline = asyncio.get_running_loop().time() + timeout
    while asyncio.get_running_loop().time() < deadline:
        if predicate():
            return True
        await asyncio.sleep(0.05)
    return False


def _sched_config(tmp_path) -> SchedulerConfig:
    cfg = SchedulerConfig()
    cfg.server.port = 0
    cfg.scheduling.retry_interval = 0.05
    cfg.scheduling.no_source_patience = 0.5
    cfg.gc.interval = 3600
    cfg.persistent_cache_db = str(tmp_path / "pc.sqlite")
    return cfg


# -- resource unit ----------------------------------------------------------

def test_resource_survives_reopen(tmp_path):
    path = str(tmp_path / "pc.sqlite")
    r = PersistentCacheResource(path)
    r.upsert_task("t1", url="dfcache://x", replica_count=3, state="succeeded")
    r.upsert_peer("p1", "t1", "h1", state=STATE_SUCCEEDED)
    r.upsert_host("h1", hostname="a", ip="1.2.3.4", port=9)
    r.close()

    r2 = PersistentCacheResource(path)
    task = r2.get_task("t1")
    assert task["replica_count"] == 3 and task["url"] == "dfcache://x"
    assert r2.replica_count("t1") == 1
    assert r2.get_host("h1")["ip"] == "1.2.3.4"
    r2.close()


def test_resource_host_departure_and_ttl(tmp_path):
    r = PersistentCacheResource(":memory:")
    r.upsert_task("t1", replica_count=2, ttl=0.001)
    r.upsert_peer("p1", "t1", "h1", state=STATE_SUCCEEDED)
    r.upsert_peer("p2", "t1", "h2", state=STATE_SUCCEEDED)
    assert r.replica_count("t1") == 2
    assert r.delete_peers_of_host("h1") == ["t1"]
    assert r.replica_count("t1") == 1
    import time

    time.sleep(0.01)
    assert [t["task_id"] for t in r.expired_tasks()] == ["t1"]
    r.close()


# -- end-to-end: import → auto-replication → restart → delete ---------------

def test_persistent_import_replicates_and_survives_restart(run_async, tmp_path):
    async def run():
        cfg = _sched_config(tmp_path)
        sched = SchedulerServer(cfg)
        await sched.start()
        d_a = await start_daemon(tmp_path, "pc-a", sched.port())
        d_b = await start_daemon(tmp_path, "pc-b", sched.port())
        sched2 = None
        try:
            payload = os.urandom(1024 * 1024)
            src = tmp_path / "data.bin"
            src.write_bytes(payload)
            # Both daemons must be announced before replication fans out.
            assert await _wait(lambda: len(sched.service.hosts.all()) >= 2)

            cfg_a = dfcache.DfcacheConfig(
                daemon_sock=d_a.config.unix_sock, cache_id="pc-entry")
            result = await dfcache.import_file(
                cfg_a, str(src), persistent=True, replica_count=2)
            task_id = result["task_id"]

            # The scheduler recorded the task and fired replication at B.
            wire = sched.service.persistent.task_wire(task_id)
            assert wire is not None and wire["replica_count"] == 2
            assert await _wait(
                lambda: sched.service.persistent.replica_count(task_id) >= 2)
            # B actually holds the bytes now.
            store_b = d_b.task_manager.storage.try_get(task_id)
            assert store_b is not None and store_b.metadata.done

            # Restart the scheduler with the same sqlite: state survives.
            await sched.stop()
            sched2 = SchedulerServer(cfg)
            await sched2.start()
            wire2 = sched2.service.persistent.task_wire(task_id)
            assert wire2 is not None
            assert wire2["current_replicas"] == 2

            # Delete fans Peer.DeleteTask to the recorded holders.
            resp = await sched2.service.delete_persistent_cache_task(
                {"task_id": task_id}, None)
            assert resp["ok"], resp
            assert await _wait(
                lambda: d_b.task_manager.storage.try_get(task_id) is None)
            assert d_a.task_manager.storage.try_get(task_id) is None
            assert sched2.service.persistent.get_task(task_id) is None
        finally:
            await d_a.stop()
            await d_b.stop()
            if sched2 is not None:
                await sched2.stop()
            else:
                await sched.stop()

    run_async(run())


def test_replicas_restored_when_host_leaves(run_async, tmp_path):
    async def run():
        cfg = _sched_config(tmp_path)
        sched = SchedulerServer(cfg)
        await sched.start()
        d_a = await start_daemon(tmp_path, "rep-a", sched.port())
        d_b = await start_daemon(tmp_path, "rep-b", sched.port())
        d_c = await start_daemon(tmp_path, "rep-c", sched.port())
        try:
            payload = os.urandom(512 * 1024)
            src = tmp_path / "d.bin"
            src.write_bytes(payload)
            assert await _wait(lambda: len(sched.service.hosts.all()) >= 3)

            cfg_a = dfcache.DfcacheConfig(
                daemon_sock=d_a.config.unix_sock, cache_id="rep-entry")
            result = await dfcache.import_file(
                cfg_a, str(src), persistent=True, replica_count=2)
            task_id = result["task_id"]
            assert await _wait(
                lambda: sched.service.persistent.replica_count(task_id) >= 2)
            holders = {p["host_id"] for p in
                       sched.service.persistent.peers_of(task_id)}
            # Kill a replica host (not the uploader): leave_host must
            # re-replicate onto the remaining free host.
            victim = next(h for h in holders
                          if h != sched.service.persistent.peers_of(
                              task_id)[0]["host_id"])
            replica_daemon = {d.config.host.hostname: d
                             for d in (d_a, d_b, d_c)}
            await sched.service.leave_host({"id": victim}, None)
            assert await _wait(
                lambda: sched.service.persistent.replica_count(task_id) >= 2)
            new_holders = {p["host_id"] for p in
                           sched.service.persistent.peers_of(task_id)}
            assert victim not in new_holders
        finally:
            await d_a.stop()
            await d_b.stop()
            await d_c.stop()
            await sched.stop()

    run_async(run())


def test_task_retrievable_after_replica_host_killed(run_async, tmp_path):
    """The VERDICT r04 item-6 'done' bar: import with --replica-count 2,
    HARD-KILL one replica's daemon (no goodbye — its announcer is torn
    off before stop so no LeaveHost is ever sent, the failure-detection
    analog of a SIGKILL), and a THIRD host must still export the exact
    bytes over P2P from the surviving replica — replication repair is
    stubbed out until after the export so the survivor cannot be
    pre-warmed by the repair racing the pull. Then the repair path is
    restored and the GC top-up re-establishes the count without the dead
    host. Reference capability: service_v2.go:1726-1895 +
    persistentcache host GC."""

    async def run():
        cfg = _sched_config(tmp_path)
        sched = SchedulerServer(cfg)
        await sched.start()
        d_a = await start_daemon(tmp_path, "kill-a", sched.port())
        d_b = await start_daemon(tmp_path, "kill-b", sched.port())
        d_c = await start_daemon(tmp_path, "kill-c", sched.port())
        alive = [d_a, d_b, d_c]
        try:
            payload = os.urandom(768 * 1024)
            src = tmp_path / "k.bin"
            src.write_bytes(payload)
            assert await _wait(lambda: len(sched.service.hosts.all()) >= 3)

            cfg_a = dfcache.DfcacheConfig(
                daemon_sock=d_a.config.unix_sock, cache_id="kill-entry")
            result = await dfcache.import_file(
                cfg_a, str(src), persistent=True, replica_count=2)
            task_id = result["task_id"]
            assert await _wait(
                lambda: sched.service.persistent.replica_count(task_id) >= 2)

            # The uploader is d_a by construction; the victim is the
            # OTHER holder (replication placed it on b or c).
            uploader_host = d_a._host_wire()["id"]
            holders = {p["host_id"] for p in
                       sched.service.persistent.peers_of(task_id)}
            assert uploader_host in holders
            victim_host = next(h for h in holders if h != uploader_host)
            by_host = {d._host_wire()["id"]: d for d in (d_a, d_b, d_c)}
            victim = by_host[victim_host]
            alive.remove(victim)
            # Hard kill: no announcer → no LeaveHost goodbye; the
            # scheduler still lists the host until failure detection
            # (modeled by the explicit leave below) reaps it.
            victim.announcer = None
            await victim.stop()
            assert any(h.id == victim_host
                       for h in sched.service.hosts.all())

            # Stub replication repair so the upcoming leave cannot
            # pre-warm the survivor before the export exercises P2P.
            real_trigger = sched.service.seed_clients.trigger_download_task

            async def no_repair(host, spec):
                return False

            sched.service.seed_clients.trigger_download_task = no_repair
            resp = await sched.service.leave_host({"id": victim_host}, None)
            assert resp.get("ok"), resp

            # The survivor that never held the entry exports it: bytes
            # must arrive exactly, pulled over P2P from the live replica.
            survivor = next(d for d in alive
                            if d._host_wire()["id"] not in holders)
            assert survivor.task_manager.storage.try_get(task_id) is None
            out = tmp_path / "exported.bin"
            cfg_s = dfcache.DfcacheConfig(
                daemon_sock=survivor.config.unix_sock, cache_id="kill-entry")
            await dfcache.export_file(cfg_s, str(out))
            assert out.read_bytes() == payload

            # Restore repair; the GC top-up re-establishes the count
            # without ever handing out the dead host.
            sched.service.seed_clients.trigger_download_task = real_trigger
            sched.service.gc()
            assert await _wait(
                lambda: sched.service.persistent.replica_count(task_id) >= 2)
            assert victim_host not in {
                p["host_id"]
                for p in sched.service.persistent.peers_of(task_id)}
        finally:
            for d in alive:
                await d.stop()
            await sched.stop()

    run_async(run(), timeout=120)


def test_gc_repairs_under_replication(run_async):
    """A replication trigger whose download failed leaves the task under-
    replicated with no retry scheduled; the GC pass must re-check succeeded
    tasks and top them up (ADVICE round 1, service.py _ensure_replicas)."""
    from dragonfly2_tpu.scheduler.service import SchedulerService
    from dragonfly2_tpu.scheduler.resource import Host

    async def run():
        svc = SchedulerService()
        svc.persistent.upsert_task(
            "t-under", url="dfcache://x", replica_count=2, state="succeeded",
            tag="", application="", digest="")
        svc.persistent.upsert_peer("p1", "t-under", "h1", state="succeeded")
        svc.hosts.store(Host("h2", ip="10.0.0.2", port=8000, upload_port=9000))

        fired = []

        async def fake_trigger(host, spec):
            fired.append((host.id, spec["task_id"]))
            return True

        svc.seed_clients.trigger_download_task = fake_trigger
        svc.gc()
        await asyncio.sleep(0.1)  # let the spawned repair run
        assert fired == [("h2", "t-under")]

        # At quota: no repair fires.
        svc.persistent.upsert_peer("p2", "t-under", "h2", state="succeeded")
        fired.clear()
        svc.gc()
        await asyncio.sleep(0.1)
        assert fired == []

    run_async(run())


# -- replication begun at Started (the scheduler alone) ----------------------

GEOMETRY = {"content_length": 3 * 4096 - 7, "piece_size": 4096,
            "total_piece_count": 3}
UPLOADS = {
    # case: (what Started carries beside the task, replicas asked at Started,
    #        replicas asked at Finished)
    "no geometry, two replicas": ({"replica_count": 2}, 0, 1),
    "geometry, one replica": ({"replica_count": 1, **GEOMETRY}, 0, 0),
    "geometry, two replicas": ({"replica_count": 2, **GEOMETRY}, 1, 0),
}


def _alone():
    """A scheduler service, hosts a (slice s), b (slice t) and c (slice s)
    announced, its triggers recorded and answered ok."""
    from dragonfly2_tpu.scheduler.resource import Host
    from dragonfly2_tpu.scheduler.service import SchedulerService

    svc = SchedulerService()
    for i, (name, tpu_slice) in enumerate(
            (("a", "s"), ("b", "t"), ("c", "s"))):
        svc.hosts.store(Host(name, ip=f"10.0.0.{i + 1}", port=8000 + i,
                             upload_port=9000 + i, tpu_slice=tpu_slice))
    fired = []

    async def trigger(host, spec):
        fired.append((host.id, dict(spec)))
        return True

    svc.seed_clients.trigger_download_task = trigger
    return svc, fired


def _upload(extra: dict) -> dict:
    return {"task_id": "t-up", "peer_id": "p-a", "url": "dfcache://up",
            "tag": "", "application": "", "digest": "",
            "host": {"id": "a", "ip": "10.0.0.1", "port": 8000,
                     "upload_port": 9000, "tpu_slice": "s"}, **extra}


def _triggered(at: str) -> float:
    from dragonfly2_tpu.scheduler import service

    return service.PERSISTENT_REPLICAS_TRIGGERED.labels(at)._value.get()


@pytest.mark.parametrize("case", list(UPLOADS))
def test_replicas_are_asked_at_started_only_for_what_started_carries(
        run_async, case):
    """``Started`` with the task's geometry and more than one replica fires
    the triggers then, once; without either it fires none before
    ``Finished``, as it always has."""
    from dragonfly2_tpu.scheduler.resource.peer import PeerState

    extra, at_started, at_finished = UPLOADS[case]

    async def run():
        svc, fired = _alone()
        before = {at: _triggered(at) for at in ("started", "finished")}
        await svc.upload_persistent_cache_task_started(_upload(extra), None)
        await asyncio.sleep(0.05)   # the triggers run behind the answer
        assert len(fired) == at_started
        peer = svc.peers.load("p-a")
        if "piece_size" in extra:
            # The uploader is a parent from now, pieceless: a child of
            # another host is handed it.
            assert peer.fsm.current == PeerState.BACK_TO_SOURCE
            assert (peer.task.content_length, peer.task.total_piece_count) \
                == (GEOMETRY["content_length"], 3)
            assert peer.task.back_to_source_peers == set()
        else:
            assert peer is None
        if at_started:
            (host, spec), = fired
            # The host of the other slice, told where the digest comes from.
            assert host == "b" and spec["digest"] == ""
            assert spec["digest_from_parent"] == "sha256"
            assert (spec["seed"], spec["disable_back_source"]) == (False, True)
            assert svc._replicas_asked["t-up"] == {"b"}
        fired.clear()
        await svc.upload_persistent_cache_task_finished(
            _upload({"digest": "sha256:" + "ab" * 32, **GEOMETRY}), None)
        await asyncio.sleep(0.05)
        assert len(fired) == at_finished
        if at_finished:
            (host, spec), = fired
            assert host == "b" and spec["digest"] == "sha256:" + "ab" * 32
            assert "digest_from_parent" not in spec
        assert _triggered("started") - before["started"] == at_started
        assert _triggered("finished") - before["finished"] == at_finished

    run_async(run())


@pytest.mark.parametrize("early", [False, True])
def test_the_top_up_after_a_host_left_is_what_it_was(run_async, early):
    """``leave_host`` restores the count elsewhere with the spec a replica
    made after the import gets, whether or not the task's first replica was
    asked for at ``Started``."""
    async def run():
        svc, fired = _alone()
        extra = {"replica_count": 2, **(GEOMETRY if early else {})}
        await svc.upload_persistent_cache_task_started(_upload(extra), None)
        await asyncio.sleep(0.05)
        await svc.upload_persistent_cache_task_finished(
            _upload({"digest": "sha256:" + "cd" * 32, **GEOMETRY}), None)
        await asyncio.sleep(0.05)
        assert [host for host, _ in fired] == ["b"]
        svc.persistent.upsert_peer("p-b", "t-up", "b", state=STATE_SUCCEEDED)
        svc.gc()    # an answered upload's asked hosts are forgotten
        assert "t-up" not in svc._replicas_asked
        fired.clear()
        await svc.leave_host({"id": "b"}, None)
        await asyncio.sleep(0.05)
        (host, spec), = fired
        assert host == "c" and spec["digest"] == "sha256:" + "cd" * 32
        assert "digest_from_parent" not in spec

    run_async(run())


def test_a_replica_that_failed_before_finished_is_asked_for_again(run_async):
    """A host asked at ``Started`` whose pull failed is no longer counted:
    ``Finished`` asks once more; and a failed upload deletes the task on the
    hosts it had asked."""
    async def run():
        svc, fired = _alone()
        deleted = []

        async def delete(host, task_id):
            deleted.append(host.id)
            return True

        svc.seed_clients.delete_task = delete
        await svc.upload_persistent_cache_task_started(
            _upload({"replica_count": 2, **GEOMETRY}), None)
        await asyncio.sleep(0.05)
        assert [host for host, _ in fired] == ["b"]
        # b's pull registers and fails.
        _, task, replica = svc._resolve(
            {"task_id": "t-up", "peer_id": "p-b",
             "host": {"id": "b", "ip": "10.0.0.2", "port": 8001}})
        replica.fsm.event("register_normal")
        svc._handle_download_failed({}, task, replica)
        assert svc._replicas_asked["t-up"] == set()
        await svc.upload_persistent_cache_task_finished(
            _upload({"digest": "sha256:" + "ef" * 32, **GEOMETRY}), None)
        await asyncio.sleep(0.05)
        assert [host for host, _ in fired] == ["b", "b"]
        assert fired[1][1]["digest"] == "sha256:" + "ef" * 32
        # The upload is reported failed: b, a peer of the task, is told to
        # delete it; the uploader's host is not (its daemon did that).
        await svc.upload_persistent_cache_task_failed(
            _upload({"unreplicated": True}), None)
        await asyncio.sleep(0.05)
        assert deleted == ["b"]
        assert svc.persistent.get_task("t-up")["state"] == "failed"
        assert "t-up" not in svc._replicas_asked

    run_async(run())
