"""``save_from_device`` and the P2P-only resume held to the plain reference
of ``save_reference.py``, at a small size on the CPU, on a real fabric in
one process: a scheduler, the saver (a daemon with the device sink), a host
of another slice, and where a case says so a slice-mate and a seed peer.
"""

from __future__ import annotations

import asyncio
import hashlib

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
# device and write_piece fails the save.
def test_a_byte_flipped_after_the_copy_fails_the_save(run_async, tmp_path,
                                                      monkeypatch):
    from dragonfly2_tpu.client.device import save_from_device
    from dragonfly2_tpu.ops import hbm_source

    fetch = hbm_source.Snapshot.fetch

    def flipped(self, first, count):
        view = fetch(self, first, count)
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
            assert stored(fab.daemons["h0"], save.task_id) is None
            assert stored(fab.daemons["h1"], save.task_id) is None
            task = fab.sched.service.persistent.get_task(save.task_id)
            assert task["state"] == "failed"
    run_async(body())


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
