"""``save_from_device`` and the P2P-only resume held to the plain reference
of ``save_reference.py``, at a small size on the CPU, on a real fabric in
one process: a scheduler, the saver (a daemon with the device sink), a host
of another slice, and where a case says so a slice-mate and a seed peer.
"""

from __future__ import annotations

import asyncio
import hashlib
import time

import numpy as np
import pytest

from dragonfly2_tpu.daemon.config import DaemonConfig
from dragonfly2_tpu.daemon.daemon import Daemon
from dragonfly2_tpu.pkg import flight as flightlib
from dragonfly2_tpu.pkg.errors import DfError
from dragonfly2_tpu.pkg.types import NetAddr
from dragonfly2_tpu.rpc import Client
from tests import save_reference as ref
from tests.test_p2p_e2e import start_scheduler

WIDTHS = {"hidden_size": 128, "num_attention_heads": 2,
          "qk_nope_head_dim": 32, "qk_rope_head_dim": 16, "v_head_dim": 32,
          "kv_lora_rank": 64, "moe_intermediate_size": 96,
          "n_routed_experts": 2, "n_routed_experts_published": 8,
          "n_shared_experts": 2, "rank": 1}
SEED = 54


def host(tmp_path, name: str, sched_port: int, *, tpu_slice: str = "",
         sink: bool = False, seed: bool = False) -> Daemon:
    cfg = DaemonConfig(work_home=str(tmp_path / name))
    cfg.host.ip = "127.0.0.1"
    cfg.host.hostname = name
    cfg.host.tpu_slice = tpu_slice
    cfg.scheduler.addrs = [f"127.0.0.1:{sched_port}"]
    cfg.gc_interval = 3600
    cfg.seed_peer = seed
    cfg.tpu_sink.enabled = sink
    daemon = Daemon(cfg)
    daemon.task_manager.flight = daemon.upload.flight = \
        flightlib.FlightRecorder()
    return daemon


def on_device(tensors: dict) -> dict:
    """The reference's tensors as jax arrays of their own dtype."""
    import jax.numpy as jnp

    dtypes = {"F32": np.float32, "BF16": jnp.bfloat16, "U8": np.uint8,
              "I8": np.int8}
    return {name: jnp.asarray(np.frombuffer(bytes(raw), dtypes[dtype])
                              .reshape(shape))
            for name, (dtype, shape, raw) in tensors.items()}


def stored(daemon: Daemon, task_id: str) -> bytes | None:
    store = daemon.task_manager.storage.find_completed_task(task_id)
    if store is None:
        return None
    with open(store.data_path, "rb") as f:
        return f.read(store.metadata.content_length)


def same_bits(tensors: dict, want: dict) -> bool:
    return set(tensors) == set(want) and all(
        tuple(tensors[n].shape) == tuple(want[n][1])
        and np.asarray(tensors[n]).tobytes() == bytes(want[n][2])
        for n in want)


class Fabric:
    """A scheduler, the saver ``h0`` of slice ``a`` and the hosts a case
    names; ``others``: name -> the keywords of ``host``."""

    def __init__(self, tmp_path, others: dict):
        self.tmp_path, self.others = tmp_path, others
        self.daemons: dict[str, Daemon] = {}

    async def __aenter__(self):
        self.sched = await start_scheduler()
        specs = {"h0": {"tpu_slice": "a", "sink": True}, **self.others}
        for name, kw in specs.items():
            self.daemons[name] = host(self.tmp_path, name, self.sched.port(),
                                      **kw)
            await self.daemons[name].start()
        for _ in range(400):
            if len(self.sched.service.hosts.all()) >= len(specs):
                break
            await asyncio.sleep(0.02)
        return self

    async def __aexit__(self, *exc):
        for daemon in self.daemons.values():
            await daemon.stop()
        await self.sched.stop()

    def host_id(self, name: str) -> str:
        return self.daemons[name]._host_wire()["id"]

    async def delete_on(self, name: str, task_id: str) -> dict:
        cli = Client(NetAddr.unix(self.daemons[name].config.unix_sock))
        try:
            return await cli.call("Daemon.DeleteTask", {"task_id": task_id},
                                  timeout=10.0)
        finally:
            await cli.close()


OTHER = {"h1": {"tpu_slice": "b"}}


def small_pieces(monkeypatch, size: int = 256 * 1024) -> None:
    """Pieces of 256 KiB: the rank's state of ``WIDTHS`` is 11 of them, in
    three groups."""
    from dragonfly2_tpu.pkg import piece

    monkeypatch.setattr(piece, "compute_piece_size", lambda length: size)


def slowed_fetch(monkeypatch, seconds: float = 0.1) -> None:
    """Every group's copy out of the device takes ``seconds`` longer."""
    from dragonfly2_tpu.ops import hbm_source

    fetch = hbm_source.Snapshot.fetch

    def slow(self, first, count):
        time.sleep(seconds)
        return fetch(self, first, count)

    monkeypatch.setattr(hbm_source.Snapshot, "fetch", slow)


def events_of(daemon: Daemon, task_id: str, name: str) -> list:
    """(end s, piece, aux, note) of the task's ``name`` events."""
    daemon.task_manager.flight.sync()   # the native server's sends
    tf = daemon.task_manager.flight.get(task_id)
    return [(t, piece, aux, note)
            for t, code, piece, aux, note in (tf.events() if tf else ())
            if flightlib.EVENT_NAMES[code] == name]


def served_pieces(daemon: Daemon, task_id: str) -> int:
    return len(events_of(daemon, task_id, "upload_serve"))


def triggered(at: str) -> float:
    from dragonfly2_tpu.scheduler import service

    return service.PERSISTENT_REPLICAS_TRIGGERED.labels(at)._value.get()


async def stat_done(daemon: Daemon, task_id: str) -> bool:
    """What the scheduler asks a holder: ``Peer.StatTask`` says done."""
    cli = Client(NetAddr.tcp("127.0.0.1", daemon._peer_port))
    try:
        return bool((await cli.call("Peer.StatTask", {"task_id": task_id},
                                    timeout=10.0))["done"])
    except DfError:
        return False
    finally:
        await cli.close()

# (a) What is stored is the writer's file, header included: the state of a
# rank, and tensors that begin and end inside words.
ODD = {
    "bf16 and float32": {"w": ("BF16", (4, 6)), "m": ("F32", (4, 6))},
    "a 1-byte tensor": {"w": ("BF16", (8, 2)), "mask": ("U8", (5,)),
                        "sign": ("I8", (2, 3))},
    "an odd-length tensor": {"a": ("BF16", (3, 5)), "b": ("BF16", (7,)),
                             "c": ("BF16", (2, 2)), "m": ("F32", (3,))},
    "an empty tensor": {"e": ("F32", (0, 4)), "w": ("BF16", (2, 2))},
}


def odd_tensors(case: str) -> dict:
    return {name: (dtype, shape, ref.tensor_bytes(SEED, 0, name, dtype, shape))
            for name, (dtype, shape) in ODD[case].items()}


@pytest.mark.parametrize("case", ["a rank's state", *ODD])
def test_the_stored_bytes_are_the_writers(run_async, tmp_path, case):
    from dragonfly2_tpu.client.device import save_from_device

    async def body():
        tensors = (ref.state(SEED, 3, WIDTHS) if case == "a rank's state"
                   else odd_tensors(case))
        content = ref.write(tensors, {"step": 3})
        async with Fabric(tmp_path, OTHER) as fab:
            save = await save_from_device(
                fab.daemons["h0"], on_device(tensors), "step-3",
                metadata={"step": 3})
            ack = await save.acked()
            stat = await fab.sched.service.stat_persistent_cache_task(
                {"task_id": ack.task_id}, None)
            assert ref.not_acknowledged(
                ack.holders, stat, fab.host_id("h0"), 2,
                {fab.host_id(n): stored(d, ack.task_id)
                 for n, d in fab.daemons.items()}, content) == []
            assert stored(fab.daemons["h0"], ack.task_id) == content
            assert (ack.content_length, ack.digest) == (
                len(content), "sha256:" + hashlib.sha256(content).hexdigest())
            # Every stage left its span on the task's flight, and the
            # piece's sums are the ones the device took.
            tf = fab.daemons["h0"].task_manager.flight.get(ack.task_id)
            names = {flightlib.EVENT_NAMES[code]
                     for _, code, _, _, _ in tf.events()}
            assert names >= {"save_snapshot", "save_pack", "save_d2h",
                             "save_commit", "save_digest", "save_replicated"}
            store = fab.daemons["h0"].task_manager.storage \
                .find_completed_task(ack.task_id)
            assert store.word_sums() == {
                0: ref.word_checksums(content)}
    run_async(body())


# (b) Save, the saver's copy deleted, the resume from the other holder alone.
@pytest.mark.parametrize("through", ["download_to_device", "download_sharded"])
def test_the_resume_is_bit_identical_and_p2p_only(run_async, tmp_path,
                                                  through):
    from dragonfly2_tpu.client import device

    async def body():
        tensors = ref.state(SEED, 7, WIDTHS)
        async with Fabric(tmp_path, OTHER) as fab:
            saver = fab.daemons["h0"]
            ack = await (await device.save_from_device(
                saver, on_device(tensors), "step-7")).acked()
            assert (await fab.delete_on("h0", ack.task_id))["ok"]
            assert stored(saver, ack.task_id) is None
            url = device.CACHE_SCHEME + "step-7"
            if through == "download_to_device":
                result = await device.download_to_device(saver, url)
                assert (result.from_p2p, result.from_reuse) == (True, False)
                assert result.task_id == ack.task_id
                got = result.load_safetensors()
            else:
                got = await device.download_sharded(
                    saver, url, prefix_guess=4096, coalesce_gap=0,
                    selector=lambda name, meta: "experts.3." not in name)
                tensors = {n: t for n, t in tensors.items()
                           if "experts.3." not in n}
                assert len(got.tasks) > 2
                assert all(t.from_p2p and not t.from_reuse
                           for t in got.tasks)
            assert same_bits(got, tensors)
    run_async(body(), timeout=120)


def test_a_cache_entry_nobody_holds_fails_with_the_schedulers_code(
        run_async, tmp_path):
    from dragonfly2_tpu.client import device

    async def body():
        async with Fabric(tmp_path, OTHER) as fab:
            with pytest.raises(DfError) as failed:
                await device.download_to_device(
                    fab.daemons["h0"], device.CACHE_SCHEME + "never-saved")
            assert "back-to-source" not in str(failed.value).lower() \
                or "disabled" in str(failed.value)
    run_async(body())


# (c) No second host that can take the copy: no ack, and the task failed.
@pytest.mark.parametrize("how", ["stopped", "absent"])
def test_no_ack_without_the_second_copy(run_async, tmp_path, how):
    from dragonfly2_tpu.client.device import save_from_device

    async def body():
        async with Fabric(tmp_path, OTHER if how == "stopped" else {}) as fab:
            if how == "stopped":
                # No goodbye: the scheduler still lists the host.
                fab.daemons["h1"].announcer = None
                await fab.daemons.pop("h1").stop()
            save = await save_from_device(
                fab.daemons["h0"], on_device(ref.state(SEED, 1, WIDTHS)),
                "step-1", ack_timeout=1.5)
            with pytest.raises(DfError, match="not replicated"):
                await save.acked()
            task = fab.sched.service.persistent.get_task(save.task_id)
            assert task["state"] == "failed"
    run_async(body())


# (d) What is stored is what was in HBM: a byte that changes between the
# device and write_piece fails the save; in a late group of a save of many
# pieces that is with the replica already pulling, and it goes with the save.
@pytest.mark.parametrize("where", ["the one piece", "a late piece"])
def test_a_byte_flipped_after_the_copy_fails_the_save(run_async, tmp_path,
                                                      monkeypatch, where):
    from dragonfly2_tpu.client.device import save_from_device
    from dragonfly2_tpu.ops import hbm_source

    fetch = hbm_source.Snapshot.fetch
    late = where == "a late piece"
    if late:
        small_pieces(monkeypatch)

    def flipped(self, first, count):
        view = fetch(self, first, count)
        if late:
            time.sleep(0.1)
            if first < 8:
                return view
        raw = np.frombuffer(view, np.uint8).copy()
        raw[len(raw) // 2] ^= 0x40
        return memoryview(raw)

    async def body():
        async with Fabric(tmp_path, OTHER) as fab:
            monkeypatch.setattr(hbm_source.Snapshot, "fetch", flipped)
            save = await save_from_device(
                fab.daemons["h0"], on_device(ref.state(SEED, 2, WIDTHS)),
                "step-2")
            with pytest.raises(DfError, match=r"\(sum, xor\)"):
                await save.acked()
            h1 = fab.daemons["h1"].task_manager
            if late:
                # The replica had pieces when the save failed ...
                assert served_pieces(fab.daemons["h0"], save.task_id) >= 4
            # ... and ends with its parent: nothing of it stays as a task.
            for _ in range(400):
                if not h1.is_task_running(save.task_id):
                    break
                await asyncio.sleep(0.05)
            assert not h1.is_task_running(save.task_id)
            for name in ("h0", "h1"):
                assert stored(fab.daemons[name], save.task_id) is None
                assert not await stat_done(fab.daemons[name], save.task_id)
            gone = h1.storage.try_get(save.task_id)
            assert gone is None or gone.metadata.invalid
            task = fab.sched.service.persistent.get_task(save.task_id)
            assert task["state"] == "failed"
            assert save.task_id not in fab.sched.service._replicas_asked
    run_async(body(), timeout=60)


# (e) The handle is the snapshot: what the caller does to its tensors after
# it has no part in what is stored.
@pytest.mark.parametrize("how", ["donated", "deleted"])
def test_the_stored_bytes_are_the_snapshots(run_async, tmp_path, how):
    import jax

    from dragonfly2_tpu.client.device import save_from_device

    async def body():
        tensors = ref.state(SEED, 5, WIDTHS)
        content = ref.write(tensors)
        live = on_device(tensors)
        async with Fabric(tmp_path, OTHER) as fab:
            save = await save_from_device(fab.daemons["h0"], live, "step-5")
            if how == "donated":
                step = jax.jit(lambda t: {n: x * 0 for n, x in t.items()},
                               donate_argnums=0)
                live = jax.block_until_ready(step(live))
            else:
                for x in live.values():
                    x.delete()
            ack = await save.acked()
            for name in ("h0", "h1"):
                assert stored(fab.daemons[name], ack.task_id) == content
    run_async(body())


# (f) Which host gets the copy is a rule: a host of another slice before a
# slice-mate, a seed peer last, whatever the order they announced in.
PLACEMENTS = {
    "another slice before a slice-mate and a seed": (
        {"mate": {"tpu_slice": "a"}, "seed": {"seed": True},
         "far": {"tpu_slice": "b"}}, "far"),
    "an unlabelled host before a slice-mate": (
        {"mate": {"tpu_slice": "a"}, "plain": {}}, "plain"),
    "a slice-mate before a seed": (
        {"seed": {"seed": True}, "mate": {"tpu_slice": "a"}}, "mate"),
    "a seed where there is no other host": ({"seed": {"seed": True}}, "seed"),
}


@pytest.mark.parametrize("case", list(PLACEMENTS))
def test_the_replica_goes_where_the_rule_says(run_async, tmp_path, case):
    from dragonfly2_tpu.client.device import save_from_device

    others, want = PLACEMENTS[case]

    async def body():
        async with Fabric(tmp_path, others) as fab:
            ack = await (await save_from_device(
                fab.daemons["h0"], on_device(odd_tensors("bf16 and float32")),
                "placed")).acked()
            assert ack.holders == [fab.host_id("h0"), fab.host_id(want)]
            for name, daemon in fab.daemons.items():
                assert (stored(daemon, ack.task_id) is not None) == (
                    name in ("h0", want))
    run_async(body())


def test_the_awaited_form_leaves_dfcache_import_as_it_was(run_async, tmp_path):
    """``dfcache import --persistent`` is answered before replication."""
    from dragonfly2_tpu.client import dfcache

    async def body():
        async with Fabric(tmp_path, OTHER) as fab:
            src = tmp_path / "file.bin"
            src.write_bytes(b"x" * 70000)
            real = fab.sched.service.seed_clients.trigger_download_task
            released = asyncio.Event()

            async def held(host, spec):
                await released.wait()
                return await real(host, spec)

            fab.sched.service.seed_clients.trigger_download_task = held
            result = await dfcache.import_file(
                dfcache.DfcacheConfig(
                    daemon_sock=fab.daemons["h0"].config.unix_sock,
                    cache_id="a-file"),
                str(src), persistent=True, replica_count=2)
            assert fab.sched.service.persistent.replica_count(
                result["task_id"]) == 1
            released.set()
            for _ in range(400):
                if fab.sched.service.persistent.replica_count(
                        result["task_id"]) == 2:
                    break
                await asyncio.sleep(0.02)
            assert stored(fab.daemons["h1"], result["task_id"]) == b"x" * 70000
    run_async(body())


# (g) The replica is pulled while the save is committed: asked at Started,
# fed as pieces commit, and held to the checks a replica made after the
# import is held to.
def test_the_replica_is_pulled_while_the_save_is_committed(run_async,
                                                           tmp_path,
                                                           monkeypatch):
    from dragonfly2_tpu.client.device import save_from_device

    small_pieces(monkeypatch)
    slowed_fetch(monkeypatch)

    async def body():
        tensors = ref.state(SEED, 9, WIDTHS)
        content = ref.write(tensors)
        async with Fabric(tmp_path, OTHER) as fab:
            before = {at: triggered(at) for at in ("started", "finished")}
            save = await save_from_device(fab.daemons["h0"],
                                          on_device(tensors), "step-9")
            ack = await save.acked()
            assert ack.pieces == 11
            h0, h1 = fab.daemons["h0"], fab.daemons["h1"]
            # Host 1 asked for its first piece before the saver committed
            # its last (one flight, one clock).
            serves = events_of(h0, ack.task_id, "upload_serve")
            commits = events_of(h0, ack.task_id, "save_commit")
            assert len(commits) == 11
            assert min(t - aux / 1000.0 for t, _, aux, _ in serves) < \
                max(t for t, _, _, _ in commits)
            # The point that says so: most pieces were out before Finished.
            (_, ahead, first_ms, sent), = events_of(h0, ack.task_id,
                                                    "save_replica_ahead")
            assert 8 <= ahead <= 11 and first_ms > 0
            assert int(sent) <= len(content)
            # The ack is what it was: holders in order, the writer's digest
            # and length, the scheduler agreeing, every stored file the
            # writer's.
            assert ack.holders == [fab.host_id("h0"), fab.host_id("h1")]
            assert (ack.content_length, ack.digest) == (
                len(content), "sha256:" + hashlib.sha256(content).hexdigest())
            stat = await fab.sched.service.stat_persistent_cache_task(
                {"task_id": ack.task_id}, None)
            assert ref.not_acknowledged(
                ack.holders, stat, fab.host_id("h0"), 2,
                {fab.host_id(n): stored(d, ack.task_id)
                 for n, d in fab.daemons.items()}, content) == []
            assert await stat_done(h1, ack.task_id)
            # Host 1 pulled one copy, all of it from the saver, and hashed
            # what it stored itself.
            (_, _, _, note), = events_of(h1, ack.task_id, "task_sources")
            assert flightlib.parse_sources_note(note) == {
                "seed_bytes": 0, "peer_bytes": len(content),
                "origin_bytes": 0}
            (_, _, _, how), = events_of(h1, ack.task_id, "verified")
            assert how in ("prefix", "rehash")
            # Asked once, at Started.
            assert triggered("started") - before["started"] == 1
            assert triggered("finished") - before["finished"] == 0
            assert ack.task_id not in fab.sched.service._replicas_asked
    run_async(body(), timeout=60)


# (h) A byte of the replica's store that changes between a piece's landing
# and the completion: the replica's own sha256 is not the saver's, it never
# says done, and there is no ack.
def test_no_ack_for_a_replica_whose_stored_bytes_changed(run_async, tmp_path,
                                                         monkeypatch):
    from dragonfly2_tpu.client.device import save_from_device
    from dragonfly2_tpu.daemon.peer.task_manager import TaskManager
    from dragonfly2_tpu.storage.local_store import LocalTaskStore

    small_pieces(monkeypatch)
    slowed_fetch(monkeypatch, 0.05)
    # The hash at the completion and not behind the pieces, so that the
    # byte changes before it is hashed whichever thread is faster.
    monkeypatch.setattr(LocalTaskStore, "start_prefix_hasher",
                        lambda self, expected: None)
    finalize = TaskManager._finalize_content_digest

    async def body():
        async with Fabric(tmp_path, OTHER) as fab:
            h1 = fab.daemons["h1"]

            async def flipped_first(self, req, store):
                if self is h1.task_manager:
                    with open(store.data_path, "r+b") as f:
                        f.seek(300_000)
                        byte = f.read(1)
                        f.seek(300_000)
                        f.write(bytes([byte[0] ^ 0x01]))
                await finalize(self, req, store)

            monkeypatch.setattr(TaskManager, "_finalize_content_digest",
                                flipped_first)
            save = await save_from_device(
                fab.daemons["h0"], on_device(ref.state(SEED, 4, WIDTHS)),
                "step-4", ack_timeout=2.0)
            with pytest.raises(DfError, match="not replicated") as refused:
                await save.acked()
            from dragonfly2_tpu.pkg.errors import Code
            assert refused.value.code == Code.SchedError
            assert not await stat_done(h1, save.task_id)
            assert stored(h1, save.task_id) is None
            task = fab.sched.service.persistent.get_task(save.task_id)
            assert task["state"] == "failed"
    run_async(body(), timeout=60)


# (i) The replica's done stands behind its OWN sha256: a saver that says
# another digest with its done fails the replica, which never reads done
# under either digest.
def test_a_wrong_digest_in_the_savers_done_fails_the_replica(run_async,
                                                             tmp_path,
                                                             monkeypatch):
    from dragonfly2_tpu.client.device import save_from_device

    small_pieces(monkeypatch)
    slowed_fetch(monkeypatch, 0.05)

    async def body():
        async with Fabric(tmp_path, OTHER) as fab:
            h0, h1 = fab.daemons["h0"], fab.daemons["h1"]
            publish = h0.task_manager.broker.publish

            def lying(task_id, event):
                if event.content_digest:
                    event.content_digest = "sha256:" + "0" * 64
                publish(task_id, event)

            h0.task_manager.broker.publish = lying
            save = await save_from_device(
                h0, on_device(ref.state(SEED, 6, WIDTHS)), "step-6",
                ack_timeout=2.0)
            with pytest.raises(DfError, match="not replicated"):
                await save.acked()
            failed = events_of(h1, save.task_id, "task_failed")
            assert failed and "digest mismatch" in failed[0][3]
            assert not await stat_done(h1, save.task_id)
            assert stored(h1, save.task_id) is None
    run_async(body(), timeout=60)


# (j) ``import_pieces`` alone: a subscriber of the broker that joins while
# the import runs is given the snapshot's pieces, then each later piece
# once, then done with the content's digest.
def test_an_import_publishes_its_pieces_as_they_commit(run_async, tmp_path):
    from dragonfly2_tpu.daemon.peer.task_manager import FileTaskRequest
    from dragonfly2_tpu.pkg.wordsum import checksum_numpy
    from dragonfly2_tpu.proto.common import UrlMeta

    piece_size, pieces = 4096, 12
    content = np.random.default_rng(SEED).integers(
        0, 256, piece_size * pieces - 100, np.uint8).tobytes()

    class Source:
        content_length, sums = len(content), None

        def __init__(self):
            self.piece_size = piece_size

        def fetch(self, first, count):
            time.sleep(0.05)
            return memoryview(content)[first * piece_size:
                                       (first + count) * piece_size]

    async def body():
        async with Fabric(tmp_path, {}) as fab:
            tm = fab.daemons["h0"].task_manager
            req = FileTaskRequest(url="dfcache://published", output="",
                                  meta=UrlMeta())
            task_id = req.task_id()
            importing = asyncio.ensure_future(tm.import_source(
                Source(), req, replica_count=1, wait_replicas_s=0.0))
            # Join once some pieces are committed and others are not.
            for _ in range(400):
                store = tm.storage.try_get(task_id)
                if store is not None and len(store.metadata.pieces) >= 4:
                    break
                await asyncio.sleep(0.005)
            assert tm.is_task_running(task_id)
            snapshot = fab.daemons["h0"].rpc._piece_snapshot(task_id)
            q = tm.broker.subscribe(task_id)
            assert not snapshot["done"] and 4 <= len(snapshot["pieces"]) < 12
            assert "content_digest" not in snapshot
            result = await importing
            seen, digests, end = list(snapshot["pieces"]), {}, None
            while end is None:
                event = q.get_nowait()
                seen += event.piece_nums
                digests.update(event.digests)
                if event.done:
                    end = event
            assert sorted(seen) == list(range(pieces))
            assert len(seen) == pieces and q.empty() and not end.failed
            store = tm.storage.find_completed_task(task_id)
            assert all(digests[n] == store.metadata.pieces[n].digest
                       for n in digests)
            want = "sha256:" + hashlib.sha256(content).hexdigest()
            assert end.content_digest == result["digest"] == want
            assert (end.total_piece_count, end.content_length) == (
                pieces, len(content))
            assert not tm.is_task_running(task_id)
            # Who comes after the end reads the digest off the snapshot.
            after = fab.daemons["h0"].rpc._piece_snapshot(task_id)
            assert after["done"] and after["content_digest"] == want
            assert checksum_numpy(content[:piece_size]) == \
                store.word_sums()[0]
    run_async(body(), timeout=60)


# (k) The benchmark's reader of the point: the served pieces over the
# operation's, per operation; nothing from a program that stamps none.
AHEAD = {
    "60 of 71 pieces out before Finished": (
        [[("save_snapshot", 71), ("save_replica_ahead", 60)]], 100 * 60 / 71),
    "the median over the operations": (
        [[("save_snapshot", 10), ("save_replica_ahead", n)]
         for n in (10, 0, 8)], 80.0),
    "a replica pulled after the import": (
        [[("save_snapshot", 71), ("save_replica_ahead", 0)]], 0.0),
    "a program without the point": ([[("save_snapshot", 71)]], None),
    "an operation that is no save": ([[("landed", 3)]], None),
}


@pytest.mark.parametrize("case", list(AHEAD))
def test_the_benchmark_reads_the_share_of_pieces_served_ahead(monkeypatch,
                                                               case):
    import importlib.util
    import os
    import sys
    import types

    # Loaded from its source under the names it imports, which leave
    # ``sys.modules`` again with the test (``tests/test_sink_spans.py``
    # ``load_reader``: chipbench/ never lands on ``sys.path``).
    layers = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "chipbench", "layers")

    def load(name: str):
        spec = importlib.util.spec_from_file_location(
            "layers." + name, os.path.join(layers, name + ".py"))
        module = importlib.util.module_from_spec(spec)
        monkeypatch.setitem(sys.modules, "layers." + name, module)
        spec.loader.exec_module(module)
        return module

    package = types.ModuleType("layers")
    monkeypatch.setitem(sys.modules, "layers", package)
    package.save_events = load("save_events")
    read = load("save_replica_ahead_pct").read
    flights, want = AHEAD[case]
    run = types.SimpleNamespace(ops=[
        types.SimpleNamespace(flight=[(100.0 + i, name, piece, 0.0)
                                      for i, (name, piece) in enumerate(f)])
        for f in flights])
    got = read(run)
    assert got is None if want is None else got == pytest.approx(want)
