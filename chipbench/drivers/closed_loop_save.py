"""Traffic kind ``closed_loop_save``: ONE client, closed loop, no think time;
an operation is a checkpoint that leaves HBM through the fabric, the loss of
this host's copy, and the resume from the replica.

Parameters of a traffic file of this kind:
  clients             1: the rank is one process's
  mode                "save" (the harness wants the key; neither of its modes)
  warm_up_operations  operations before the window (the compiles)
  fetch_share         share of the window's operations whose every resumed
                      tensor is fetched back whole for the comparison, drawn
                      from the cell's seeded generator, the first and the
                      last always
  ack_timeout_s       what ``save_from_device`` waits for the replicas
  trace               how much of the window a traced run covers:
                      {"operations": n}

The layout (``warm_up``). Host 0 is the embedded daemon that holds the chip,
built again as the fabric builds it with the deployment's labels
(``deployment.slices[0]``) and its work home on the memory-backed scratch;
host 1 is a plain ``python -m dragonfly2_tpu.cli.main daemon`` child, as
``closed_loop_fanout`` starts its hosts (its ``Host`` and ``hosts_scratch``
are used as they stand), labelled ``deployment.slices[1]``: a host of another
slice than the saver's. The fabric's seed peer runs and holds nothing of this
cell; the origin child serves nothing.

Timed, one operation, from the call to the last verified tensor:
``save_from_device(daemon, state_n, cache_id, replicas=2)`` -> the handle
(the stall) -> ``acked()`` -> the host's copy lost (``lose_host_copy``:
``Daemon.DeleteTask`` over the embedded daemon's own socket, the live arrays
dropped) -> ``download_to_device(daemon, "dfcache://<cache_id>")``, P2P-only,
from host 1 -> ``load_safetensors`` -> every tensor ready. ``op.nbytes`` is
the file's content. Untimed, after: host 0's flight of the task (the save's
and the resume's events lie on one ring: one task id) and host 1's; the
program's counters; the scheduler's ``StatPersistentCacheTask``; the
benchmark's own sha256 of the file in host 1's store (``read_replica``); the
origin's ``/stats``; the benchmark's (sum, xor) of every resumed tensor,
taken on the device in plain jax.numpy; where the draw says so every tensor
fetched back whole; the entry of two steps ago deleted everywhere
(``Scheduler.DeletePersistentCacheTask``); the next step's state made from
the seed on the host and put on the chip.

The comparison is the driver's own (``check``, put in ``Cell.check``'s
place), against ``objects/train_state_rank.py`` alone: every guarantee of the
configuration, each beside its limit.

A program without ``save_from_device`` cannot run the cell: this module
refuses to load there, before the fabric's daemon starts.
"""

from __future__ import annotations

import asyncio
import functools
import glob
import hashlib
import os
import statistics
import time

import numpy as np

import harness
from dragonfly2_tpu.client import device as device_api
from fabric import LOOPBACK, wait_for

if not hasattr(device_api, "save_from_device"):
    raise RuntimeError(
        "closed_loop_save: this program has no way out of HBM "
        "(client.device has no save_from_device): the cell needs it")

from drivers.closed_loop_fanout import Host, hosts_scratch  # noqa: E402
from layers import sink_events  # noqa: E402

STAGES = ("save_snapshot", "save_pack", "save_d2h", "save_commit",
          "save_digest", "save_replicated")


@functools.lru_cache(maxsize=None)
def _items_checksum_program(dtype: str, shape: tuple):
    """The benchmark's own (sum mod 2**32, xor) of a tensor's items as
    unsigned integers of the item's width, on the device, in plain
    jax.numpy (int32 lanes wrap as uint32 does)."""
    import jax
    import jax.numpy as jnp

    unsigned = {2: jnp.uint16, 4: jnp.uint32}[jnp.dtype(dtype).itemsize]

    def chipbench_items_checksum(tensor):
        items = jax.lax.bitcast_convert_type(tensor, unsigned) \
            .astype(jnp.uint32)
        items = jax.lax.bitcast_convert_type(items, jnp.int32)
        return jnp.stack([
            jnp.sum(items, dtype=jnp.int32),
            jax.lax.reduce(items, jnp.int32(0), jax.lax.bitwise_xor,
                           tuple(range(items.ndim)))])

    return jax.jit(chipbench_items_checksum)


def counters() -> dict:
    """The program's counters this cell reads, as they stand."""
    from dragonfly2_tpu.ops import bitview, hbm_source

    out = {f"save_{kind}": hbm_source.SAVE_BYTES.labels(kind)._value.get()
           for kind in ("content", "d2h", "copied", "stored")}
    out.update({f"failed_{why}":
                hbm_source.SAVE_FAILURES.labels(why)._value.get()
                for why in ("mismatch", "replica", "error")})
    views = getattr(bitview, "VIEWS_BYTES", None)
    if views is not None:
        out.update({f"views_{form}": views.labels(form)._value.get()
                    for form in ("rows", "flat")})
    return out


# -- the two hosts ---------------------------------------------------------

def labels(cell, index: int) -> dict:
    deployment = cell.config["deployment"]
    return {"ip": LOOPBACK, "hostname": f"bench-host-{index}",
            "idc": deployment["idc"],
            "tpu_slice": deployment["slices"][index], "tpu_worker_index": 0}


async def start_hosts(cell) -> None:
    """Host 0 again, with the deployment's labels and its store on the
    scratch; host 1 as a child."""
    from dragonfly2_tpu.daemon.config import DaemonConfig
    from dragonfly2_tpu.daemon.daemon import Daemon
    from dragonfly2_tpu.pkg.types import NetAddr
    from dragonfly2_tpu.rpc import Client

    fabric = cell.fabric
    old, fabric.daemon = fabric.daemon, None
    await asyncio.wait_for(old.stop(), 30)
    sink = cell.config["deployment"]["sink"]
    cfg = DaemonConfig(work_home=os.path.join(cell.scratch, "h0"))
    for key, value in labels(cell, 0).items():
        setattr(cfg.host, key, value)
    cfg.scheduler.addrs = [f"{LOOPBACK}:{fabric.sched_port}"]
    cfg.tpu_sink.enabled = True
    cfg.tpu_sink.max_tasks = int(sink["max_tasks"])
    cfg.tpu_sink.batch_pieces = int(sink["batch_pieces"])
    fabric.daemon = Daemon(cfg)
    await asyncio.wait_for(fabric.daemon.start(), 60)

    host = cell.replica = Host(cell.scratch, 1)
    config = os.path.join(fabric.home, host.name + ".yaml")
    with open(config, "w") as f:
        f.write("host:\n" + "".join(
            f"  {key}: {value}\n" for key, value in labels(cell, 1).items()))
    fabric.spawn(host.name, [
        "-m", "dragonfly2_tpu.cli.main", "daemon", "--config", config,
        "--work-home", host.home,
        "--scheduler", f"{LOOPBACK}:{fabric.sched_port}"])
    await wait_for("host 1's daemon socket",
                   lambda: fabric.alive(host.name)
                   and os.path.exists(host.sock), 90)
    host.client = Client(NetAddr.unix(host.sock))
    await host.call("Daemon.Health", {})
    cell.scheduler = Client(NetAddr.tcp(LOOPBACK, fabric.sched_port))
    cell.own = Client(NetAddr.unix(fabric.daemon.config.unix_sock))
    cell.host_ids = [fabric.daemon._host_wire()["id"]]

    async def both_announced():
        reply = await cell.scheduler.call("Scheduler.ListHosts", {},
                                          timeout=10.0)
        return {h["id"] for h in reply["hosts"]}

    for _ in range(600):
        ids = await both_announced()
        other = [i for i in ids if i.startswith("bench-host-1")]
        if cell.host_ids[0] in ids and other:
            cell.host_ids.append(other[0])
            return
        await asyncio.sleep(0.05)
    raise RuntimeError("closed_loop_save: the two hosts never announced")


def put_state(cell, state: dict) -> dict:
    """The state's tensors on the chip, each in its own dtype."""
    import jax
    import jax.numpy as jnp

    dtypes = {"F32": np.float32, "BF16": jnp.bfloat16}
    dev = jax.local_devices()[0]
    live = {name: jax.device_put(
        np.frombuffer(raw, dtypes[dtype]).reshape(shape), dev)
        for name, (dtype, shape, raw) in state.items()}
    jax.block_until_ready(list(live.values()))
    return live


async def next_state(cell, made=None) -> None:
    """The next step's state made (``made``: the making already begun, on a
    thread beside the last operation's readings) and put, and what its save
    has to store reckoned beside the put (all untimed)."""
    step = cell.step = cell.step + 1
    t0 = time.perf_counter()
    cell.state = await (made or asyncio.to_thread(cell.objects.state, step))
    facts = asyncio.ensure_future(asyncio.to_thread(
        cell.objects.facts, step, cell.state))
    cell.live = await asyncio.to_thread(put_state, cell, cell.state)
    cell.facts_of_state = await facts
    cell.made_s = time.perf_counter() - t0


# -- the parts of an operation a control may break -------------------------

async def lose_host_copy(cell, task_id: str) -> None:
    """The "kill": this host's store entry and sinks of the task go, as a
    replacement VM has none; the daemon stays (the harness cannot kill the
    process that holds the chip)."""
    for _ in range(50):
        reply = await cell.own.call("Daemon.DeleteTask",
                                    {"task_id": task_id}, timeout=10.0)
        if reply.get("ok"):
            return
        await asyncio.sleep(0.02)
    raise RuntimeError(f"this host kept the task: {reply}")


def read_replica(cell, task_id: str) -> tuple[int, str]:
    """(length, sha256) of the task's file in host 1's store, by the
    benchmark's own reading."""
    found = glob.glob(os.path.join(cell.replica.home, "**", task_id, "data"),
                      recursive=True)
    if len(found) != 1:
        return -1, f"{len(found)} files"
    h = hashlib.sha256()
    length = 0
    with open(found[0], "rb") as f:
        while chunk := f.read(8 << 20):
            h.update(chunk)
            length += len(chunk)
    return length, "sha256:" + h.hexdigest()


async def read_tensors(cell, op, tensors: dict, whole: bool, saved: dict,
                       facts: dict) -> None:
    """The benchmark's readings of the resumed tensors against the state
    that was saved (``saved``, and ``facts`` of it): ``op.bad_sums`` of
    ``op.summed`` by the sums taken on the device, ``op.bad_whole`` of
    ``op.fetched_whole`` fetched back."""
    want = facts["checksums"]

    def sums() -> int:
        taken = {name: _items_checksum_program(str(t.dtype), tuple(t.shape))(t)
                 for name, t in tensors.items()}
        bad = set(taken) ^ set(want)
        for name in set(taken) & set(want):
            s, x = (int(v) for v in np.asarray(taken[name]).view(np.uint32))
            bad |= {name} if (s, x) != want[name] else set()
        return len(bad)

    def fetched() -> int:
        return sum(not cell.objects.matches(
            name, (str(t.dtype), tuple(t.shape)), np.asarray(t), saved)
            for name, t in tensors.items())

    op.summed = len(want)
    op.bad_sums = await asyncio.to_thread(sums)
    op.fetched_whole = len(tensors) if whole else 0
    op.bad_whole = await asyncio.to_thread(fetched) if whole else 0


async def operation(cell, number: int, *, warmup: bool = False,
                    closing=lambda: False) -> harness.Op:
    """Save, lose, resume: timed by the host clock from the call to the
    last verified tensor; after it the readings and the next state."""
    import jax
    from jax.profiler import TraceAnnotation

    objects, daemon = cell.objects, cell.fabric.daemon
    step = cell.step
    cache_id = objects.cache_id(step)
    op = harness.Op(number=number, client=0, object_index=step,
                    tag=cache_id, warmup=warmup, cold=True)
    op.made_s = cell.made_s
    stats = await asyncio.to_thread(cell.fabric.origin_json, "/stats")
    origin_before = sum(s["bytes"] for s in stats.values())
    before = counters()
    device = jax.local_devices()[0]
    result = tensors = None
    op.t0 = time.perf_counter()
    try:
        with TraceAnnotation(f"chipbench:op#{number}"):
            save = await device_api.save_from_device(
                daemon, cell.live, cache_id, replicas=2,
                metadata=objects.metadata(step),
                ack_timeout=float(cell.traffic["ack_timeout_s"]))
            op.task_id = save.task_id
            op.stall_s = time.perf_counter() - op.t0
            op.save_hbm = (device.memory_stats() or {}).get("bytes_in_use")
            ack = await save.acked()
            op.t_ack = time.perf_counter()
            cell.live = None
            await lose_host_copy(cell, ack.task_id)
            op.t_lost = time.perf_counter()
            result = await asyncio.wait_for(device_api.download_to_device(
                daemon, device_api.CACHE_SCHEME + cache_id), 600)
            jax.block_until_ready(result.as_words())
            v0 = time.perf_counter()
            with TraceAnnotation(f"chipbench:views#{number}"):
                tensors = result.load_safetensors()
                jax.block_until_ready(list(tensors.values()))
            op.views_span = (v0, time.perf_counter())
        op.t1 = time.perf_counter()
    except Exception as e:  # a failed operation is counted, not fatal
        op.t1 = time.perf_counter()
        op.error = f"{type(e).__name__}: {e}"[:500]
        harness.say(f"operation {number} failed: {op.error}")
    cell.ops.append(op)
    cell.live = None
    # The next step's state is made from here on, on threads of its own,
    # beside the readings; the state just saved stays for them.
    saved, want = cell.state, cell.facts_of_state
    made = asyncio.ensure_future(asyncio.to_thread(objects.state, step + 1))
    if not op.error:
        after = counters()
        op.counted = {k: after[k] - before[k] for k in after}
        op.nbytes = ack.content_length
        op.ack = {"holders": list(ack.holders), "digest": ack.digest,
                  "length": ack.content_length, "task_id": ack.task_id}
        op.resume = {"task_id": result.task_id, "from_p2p": result.from_p2p,
                     "from_reuse": result.from_reuse,
                     "length": result.content_length}
        cell.tasks[step] = ack.task_id
        cell._read_flight(op)
        try:
            raw = (await cell.replica.call(
                "Daemon.FlightReport", {"task_id": ack.task_id, "raw": True}))
            op.replica_sources = next(
                (note for _, name, _, _, note in raw["raw"]["events"]
                 if name == "task_sources"), "")
        except Exception as e:
            op.replica_sources = f"no flight: {e}"[:200]
        try:
            op.stat = await cell.scheduler.call(
                "Scheduler.StatPersistentCacheTask",
                {"task_id": ack.task_id}, timeout=10.0)
        except Exception as e:
            op.stat = {"state": f"no record: {e}"[:200], "peers": []}
        replica = asyncio.ensure_future(asyncio.to_thread(
            read_replica, cell, ack.task_id))
        stats = await asyncio.to_thread(cell.fabric.origin_json, "/stats")
        op.origin_bytes = sum(s["bytes"] for s in stats.values()) \
            - origin_before
        op.want = {k: want[k] for k in ("length", "digest")}
        always = number == 0 or closing()
        drawn = cell.rng.random() < float(cell.traffic["fetch_share"])
        del result
        await read_tensors(cell, op, tensors, always or drawn, saved, want)
        op.replica = await replica
    tensors = saved = None
    # The entry of two steps ago goes, from every holder the scheduler
    # knows; then the next step's state.
    gone = cell.tasks.pop(step - 2, None)
    if gone is not None:
        await cell.scheduler.call("Scheduler.DeletePersistentCacheTask",
                                  {"task_id": gone}, timeout=30.0)
    await next_state(cell, made)
    op.gap_s = time.perf_counter() - op.t1
    return op


async def warm_up(cell) -> None:
    """The two hosts, the first state, then the warm-up's operations.
    Anything that fails here ends the run."""
    cell.check = functools.partial(check, cell)
    cell.scratch = hosts_scratch(cell)
    cell.tasks = {}
    cell.step = -1
    cell.made_s = 0.0
    await start_hosts(cell)
    harness.say(f"set-up: hosts {cell.host_ids} (slices "
                f"{cell.config['deployment']['slices']}), stores under "
                f"{cell.scratch}")
    await next_state(cell)
    harness.say(f"set-up: a state of {len(cell.state)} tensors, "
                f"{cell.objects.size()} bytes as a file, made and put in "
                f"{cell.made_s:.1f}s")
    count = int(cell.traffic["warm_up_operations"])
    for n in range(count):
        op = await operation(cell, n - count, warmup=True)
        if op.error:
            raise RuntimeError("closed_loop_save: the warm-up's operation "
                               f"failed: {op.error}")
        harness.say(f"warm-up operation at step {op.object_index}: "
                    + describe([op]))


async def window(cell, seconds: float, traced: bool) -> tuple[float, float]:
    """As ``closed_loop.window``, one client. A failed operation is counted
    and the next one starts from the next state."""
    limit = cell.traffic.get("trace", {}) if traced else {}
    most = limit.get("operations")
    start = time.perf_counter()
    number = 0
    while time.perf_counter() - start < seconds and (
            most is None or number < most):
        await operation(
            cell, number,
            closing=lambda: time.perf_counter() - start >= seconds
            or (most is not None and number + 1 >= most))
        number += 1
    end = time.perf_counter()
    done = [op for op in cell.ops if not op.warmup and not op.error]
    if done:
        harness.say(describe(done))
    await cell.scheduler.close()
    await cell.own.close()
    return start, end


def describe(done) -> str:
    """Where an operation's time went, for a person: medians over the
    operations."""
    def ms(values) -> str:
        return f"{statistics.median(values) * 1e3:.0f}"

    def of(name: str) -> float:
        return statistics.median(sink_events.summed_ms(op, name) or 0.0
                                 for op in done)

    return (f"an operation, medians of {len(done)}: call->ready "
            f"{ms(op.seconds for op in done)} ms = stall "
            f"{ms(op.stall_s for op in done)} + to the ack "
            f"{ms(op.t_ack - op.t0 - op.stall_s for op in done)} + lost "
            f"{ms(op.t_lost - op.t_ack for op in done)} + resume "
            f"{ms(op.t1 - op.t_lost for op in done)}; summed span ms: "
            + ", ".join(f"{name} {of(name):.0f}" for name in (
                *STAGES, "sched_wait", "sink_finalize"))
            + f"; the next state made and put (untimed), s: "
            f"{[round(op.made_s, 1) for op in done]}")


async def check(cell) -> tuple[bool, list[str]]:
    """Every number compared, beside its limit; ``correct`` is all of them
    inside their limits."""
    from dragonfly2_tpu.pkg import flight as flightlib

    guarantees = cell.config["guarantees"]
    done = [op for op in cell.ops if not op.error]
    timed = [op for op in done if not op.warmup]
    lines: list[str] = []
    saver, other = cell.host_ids

    unacked = sum(op.ack["holders"] != [saver, other] for op in done)
    disagree = 0
    for op in done:
        # Its hosts: after the resume this host holds the task again, under
        # a second peer of its own.
        held = sorted({p["host_id"] for p in op.stat["peers"]
                       if p["state"] == "succeeded"})
        disagree += not (op.stat.get("state") == "succeeded"
                         and held == sorted(op.ack["holders"])
                         and op.stat.get("digest") == op.ack["digest"]
                         and op.stat.get("replica_count") == 2)
    lines.append(f"acks that do not name the saver and the host of the other "
                 f"slice, in that order: {unacked} of {len(done)} (limit 0); "
                 f"acks with which the scheduler's StatPersistentCacheTask "
                 f"disagrees (state, holders, digest, replica count): "
                 f"{disagree} (limit 0)")
    bad_ack = sum((op.ack["length"], op.ack["digest"])
                  != (op.want["length"], op.want["digest"]) for op in done)
    bad_replica = sum(op.replica != (op.want["length"], op.want["digest"])
                      for op in done)
    lines.append(f"acks whose length or sha256 is not the reference "
                 f"writer's: {bad_ack} of {len(done)} (limit 0); replicas "
                 f"whose stored file, hashed by the benchmark, is not the "
                 f"reference writer's by length and sha256: {bad_replica} "
                 f"of {len(done)} (limit 0)")

    off_path = sum(not (op.resume["from_p2p"] and not op.resume["from_reuse"]
                        and op.resume["task_id"] == op.ack["task_id"]
                        and op.resume["length"] == op.want["length"])
                   for op in done)
    to_source = sum(any(name == "back_source" for _, name, _, _ in op.flight)
                    for op in done)
    origin = sum(op.origin_bytes for op in done)
    lines.append(f"resumes off the path the cell names (from_p2p, not "
                 f"from_reuse, the saved task, its length): {off_path} of "
                 f"{len(done)} (limit 0); resumes that went back to a "
                 f"source: {to_source} (limit 0); bytes the origin served "
                 f"during the operations: {origin} (limit 0)")

    bad_sums = sum(op.bad_sums for op in done)
    lines.append(f"resumed tensors whose (sum, xor), taken on the device, "
                 f"differs from the saved state's: {bad_sums} of "
                 f"{sum(op.summed for op in done)} in {len(done)} resumes "
                 "(limit 0)")
    bad_whole = sum(op.bad_whole for op in done)
    whole = sum(op.fetched_whole for op in done)
    lines.append(f"resumed tensors fetched back whole that differ from the "
                 f"saved state's bytes, dtype or shape: {bad_whole} of "
                 f"{whole} (limit 0)")

    copies = []
    for op in done:
        moved = flightlib.parse_sources_note(op.replica_sources)
        copies.append((moved["peer_bytes"] + moved["seed_bytes"]
                       + moved["origin_bytes"]) / op.nbytes)
    limit = guarantees["copies_between_hosts_max"]
    lines.append(f"copies of the object that host 1 pulled for a save, by "
                 f"its own task's sources: least {min(copies, default=0):.4f}"
                 f", most {max(copies, default=0):.4f} (limits 1, {limit})")
    gate = sum(op.counted["failed_mismatch"] + op.counted["failed_replica"]
               + op.counted["failed_error"] for op in done)
    unbalanced = sum(op.counted["save_content"] != op.nbytes
                     or op.counted["save_stored"] != op.nbytes
                     or op.counted["save_d2h"] < op.nbytes for op in done)
    dark = sum(not {name for _, name, _, _ in op.flight}.issuperset(STAGES)
               for op in done)
    lines.append(f"saves the program counted as failed among the finished: "
                 f"{gate} (limit 0); saves whose device_save_bytes_total "
                 f"(content, stored, d2h) do not cover the file: "
                 f"{unbalanced} (limit 0); saves whose flight lacks one of "
                 f"{', '.join(STAGES)}: {dark} of {len(done)} (limit 0)")
    ok = (bool(timed) and unacked == 0 and disagree == 0 and bad_ack == 0
          and bad_replica == 0 and off_path == 0 and to_source == 0
          and origin == 0 and bad_sums == 0 and bad_whole == 0 and whole > 0
          and copies and 1.0 <= min(copies) and max(copies) <= limit
          and gate == 0 and unbalanced == 0 and dark == 0)
    return bool(ok), lines
