"""Traffic kind ``closed_loop_global``: one caller a client, each sending its
next operation when the last one has finished; an operation is ONE
``client.device.download_global`` of a whole checkpoint file under the sharding
map of the configuration's expert parallelism over the host's chips: expert
``e`` of every layer ``SingleDeviceSharding`` of local device ``e // held``,
every other tensor replicated over a mesh of the chips.

Parameters of a traffic file of this kind:
  clients   callers running side by side
  mode      "reland": set-up's one cold call leaves every ranged task in the
            peer's store, and every operation lands them again from there
  trace     how much of the window a traced run covers:
            {"operations": n} or {"seconds": s}
The chips and the header's guess are the configuration's (``deployment``).

An operation here is the driver's own, built from the harness's parts and
appended to ``cell.ops``, so that ``Cell.check`` runs unchanged. Timed:
request -> ``download_global(shardings, tag)`` -> every tensor ready on
every chip that holds it. ``op.nbytes`` is the file's tensors counted once.
Untimed: the flights of ALL the operation's ranged tasks (``op.ranged``, a row
a task with the chips its words lie on, and merged in ``op.flight``, as
``closed_loop_ranged`` keeps them); what the program's counters counted in
the operation (``op.counts``: bytes read from the store, bytes that reached a
chip from another chip); what each chip holds of the result
(``op.chip_resident``); the benchmark's own (sum32, xor32) of EVERY
addressable shard of every tensor, taken on the chip that holds it by
``closed_loop_ranged``'s program, in the order of (name, chip), which
``Cell.check``'s first line compares with what the objects module reckons with
NumPy from the reference's shards (``cell.facts``: a "piece" of that line is
here a shard); a seeded sample of shards fetched back from their chips, with
the set of names, every shard's chip against the layout, and the bytes the
store was read for; ``from_reuse`` true only where true of every ranged
task, ``from_p2p`` where true of any. After the window every chip's peak
bytes go to ``cell.chip_peaks``.

A program whose ``download_to_device`` cannot be told a chip lands every byte
on the daemon's one device and names no ranged task of a ``download_global``:
neither ``from_reuse`` of every task nor a task's chip can be read there.
This module refuses to load on it, before the fabric starts, and ``warm_up``
raises when an operation of its own fails (an out-of-memory included), so
such a run ends at once with no last line and not after a window of failed
operations.
"""

from __future__ import annotations

import asyncio
import inspect
import itertools
import time

import numpy as np

import harness
from dragonfly2_tpu.client import device as device_lib
from drivers.closed_loop_ranged import _tensor_checksum_program, read_flights

if "device" not in inspect.signature(
        device_lib.download_to_device).parameters:
    raise RuntimeError(
        "closed_loop_global: this program's download_to_device takes no "
        "device: every ranged task lands on the daemon's one chip, and its "
        "download_global names no task, so neither a task's chip nor its "
        "from_reuse can be read")


def chips_of(cell) -> list:
    """The configuration's chips: the first n of ``jax.devices()``."""
    import jax

    n = int(cell.config["deployment"]["expert_parallel"]["ranks"])
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(f"closed_loop_global: {n} chips on "
                           f"{len(devices)} device(s)")
    return devices[:n]


def shardings_of(cell, devices) -> dict:
    """name -> sharding, in the file's order: a routed expert's tensors on
    the one chip that keeps them, every other tensor on all of them."""
    from jax.sharding import (Mesh, NamedSharding, PartitionSpec,
                              SingleDeviceSharding)

    everywhere = NamedSharding(Mesh(np.array(devices), ("ep",)),
                               PartitionSpec())
    alone = [SingleDeviceSharding(d) for d in devices]
    return {name: everywhere if cell.objects.chip_of(name) is None
            else alone[cell.objects.chip_of(name)]
            for name, _, _ in cell.objects.tensors}


def indices_of(cell, shardings: dict, devices) -> dict:
    """name -> {chip: the index of the tensor that the chip's shard is}:
    what the plain reference is cut by."""
    chip = {d: i for i, d in enumerate(devices)}
    shape = {name: shape for name, _, shape in cell.objects.tensors}
    return {name: {chip[d]: index for d, index in
                   sharding.devices_indices_map(shape[name]).items()}
            for name, sharding in shardings.items()}


def checksums_of(tensors: dict, devices) -> np.ndarray:
    """(shards, 2) uint32 in the order of (name, chip): each addressable
    shard's checksum, computed on the chip that holds it."""
    import jax.numpy as jnp

    chip = {d: i for i, d in enumerate(devices)}
    rows: dict = {i: [] for i in range(len(devices))}
    order = []
    for name, t in sorted(tensors.items()):
        for s in sorted(t.addressable_shards, key=lambda s: chip[s.device]):
            i = chip[s.device]
            order.append((i, len(rows[i])))
            rows[i].append(_tensor_checksum_program(
                str(s.data.dtype), tuple(s.data.shape))(s.data))
    on_host = {i: np.asarray(jnp.stack(r)).view(np.uint32)
               for i, r in rows.items() if r}
    return np.stack([on_host[i][k] for i, k in order])


def counted() -> dict:
    """The program's counters this cell reads, as they stand (a program
    that this module loads on has both)."""
    from dragonfly2_tpu.daemon.peer import device_sink

    return {"store_bytes": device_sink.SINK_STORE_READ_BYTES._value.get(),
            "hop_bytes": sum(
                device_sink.SINK_HOP_BYTES.labels(how)._value.get()
                for how in ("fanout", "device_put"))}


async def operation(cell, plan, number: int, client: int, *,
                    warmup: bool = False, cold: bool = False) -> harness.Op:
    """One whole ``download_global``, timed by the host clock until every
    tensor is ready on every chip that holds it; then, untimed, the
    benchmark's readings."""
    import jax
    from jax.profiler import TraceAnnotation

    devices, shardings = plan
    tag = f"s{cell.seed}-reland"
    op = harness.Op(number=number, client=client, object_index=0, tag=tag,
                    warmup=warmup, cold=cold)
    tensors = None
    before = counted()
    op.t0 = time.perf_counter()
    try:
        with TraceAnnotation(f"chipbench:op#{number}"):
            tensors = await asyncio.wait_for(device_lib.download_global(
                cell.fabric.daemon, cell.fabric.url(0), shardings, tag=tag,
                prefix_guess=int(
                    cell.config["deployment"]["prefix_guess"])), 600)
            jax.block_until_ready(list(tensors.values()))
        op.t1 = time.perf_counter()
    except Exception as e:  # a failed operation is counted, not fatal
        op.t1 = time.perf_counter()
        op.error = f"{type(e).__name__}: {e}"[:500]
        harness.say(f"operation {number} failed: {op.error}")
    cell.ops.append(op)
    if op.error:
        return op
    op.counts = {k: v - before[k] for k, v in counted().items()}
    tasks = tensors.tasks
    op.nbytes = cell.objects.tensor_bytes_once()
    op.task_id = tasks[0].task_id
    if cold:
        op.from_p2p = all(t.from_p2p for t in tasks)
        op.from_reuse = any(t.from_reuse for t in tasks)
        cell.pulls[0] = cell.pulls.get(0, 0) + 1
    else:
        op.from_p2p = any(t.from_p2p for t in tasks)
        op.from_reuse = all(t.from_reuse for t in tasks)
    read_flights(cell, op, tasks)
    for row, task in zip(op.ranged, tasks):
        row["chips"] = list(task.chips)
    dispatched = [(t - aux / 1000.0) for t, name, _, aux in op.flight
                  if name == "shard_views"]
    if dispatched:
        op.views_span = (dispatched[0], op.t1)
    chip = {d: i for i, d in enumerate(devices)}
    op.chip_resident = [0] * len(devices)
    for t in tensors.values():
        for s in t.addressable_shards:
            op.chip_resident[chip[s.device]] += int(s.data.nbytes)
    if sorted(tensors) == sorted(shardings):
        op.fetched = await asyncio.to_thread(
            cell.objects.fetch, dict(tensors), cell.rng,
            cell.fetch_whole_first, chip)
        cell.fetch_whole_first = False
        op.fetched.append(("@store_bytes", None, None,
                           op.counts["store_bytes"]))
        op.device_checksums = await asyncio.to_thread(
            checksums_of, tensors, devices)
    else:
        # Not the file's names: the set alone is compared, and fails.
        op.fetched = [("", None, None, sorted(tensors))]
        op.device_checksums = np.zeros((0, 2), np.uint32)
    tensors = None
    op.gap_s = time.perf_counter() - op.t1
    return op


def plan_of(cell):
    devices = chips_of(cell)
    return devices, shardings_of(cell, devices)


async def warm_up(cell) -> None:
    """The objects module's facts of every chip's shards take the place of
    the origin's whole-object ones; then one cold call, which leaves every
    ranged task in the peer's store (set-up's race, as ``closed_loop.warm_up``
    has it, is marked), and one re-land of the cell's own geometries on every
    chip. An operation that fails here ends the run."""
    devices, shardings = plan = plan_of(cell)
    cell.facts[0] = await asyncio.to_thread(
        cell.objects.shard_facts, indices_of(cell, shardings, devices))
    for number, cold in ((-2, True), (-1, False)):
        op = await operation(cell, plan, number, 0, warmup=True, cold=cold)
        if op.error:
            raise RuntimeError("closed_loop_global: the warm-up's operation "
                               f"failed: {op.error}")
        op.raced = cold and not op.from_p2p
        by_chips: dict = {}
        for task in op.ranged:
            row = by_chips.setdefault(tuple(task["chips"]), [0, 0])
            row[0] += 1
            row[1] += task["end"] - task["start"]
        harness.say(
            f"warm-up {number} ({'cold' if cold else 're-land'}): "
            f"{len(op.ranged)} ranged tasks, the header's first; by the chips "
            "their words lie on, tasks and bytes: " + ", ".join(
                f"{chips} {n} {size}" for chips, (n, size)
                in sorted(by_chips.items()))
            + f"; resident by chip {op.chip_resident}; counted {op.counts}")


async def window(cell, seconds: float, traced: bool) -> tuple[float, float]:
    """As ``closed_loop.window``; then every chip's peak bytes."""
    plan = plan_of(cell)
    limit = cell.traffic.get("trace", {}) if traced else {}
    seconds = min(seconds, limit.get("seconds", seconds))
    most = limit.get("operations")
    numbers = itertools.count()
    start = time.perf_counter()

    async def client(c: int) -> None:
        while time.perf_counter() - start < seconds:
            n = next(numbers)
            if most is not None and n >= most:
                return
            await operation(cell, plan, n, c)

    await asyncio.gather(*(client(c)
                           for c in range(int(cell.traffic["clients"]))))
    end = time.perf_counter()
    done = [op for op in cell.ops if not op.warmup and not op.error]
    if done:
        sums: dict = {}
        for _, name, _, aux in done[-1].flight:
            if name.startswith("sink_") or name in ("admit_wait",
                                                    "device_pull"):
                sums[name] = sums.get(name, 0.0) + aux
        harness.say(f"operation {done[-1].number} of "
                    f"{done[-1].seconds * 1000.0:.0f} ms, summed ms of its "
                    f"{len(done[-1].ranged)} tasks' spans: " + ", ".join(
                        f"{name} {ms:.1f}" for name, ms in sums.items()))
    cell.chip_peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                       for d in plan[0]]
    harness.say(f"peak bytes in use by chip: {cell.chip_peaks}")
    return start, end
