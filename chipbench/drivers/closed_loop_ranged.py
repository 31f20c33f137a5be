"""Traffic kind ``closed_loop_ranged``: one caller a client, each sending its
next operation when the last one has finished; an operation is one
expert-parallel rank's pull of its own tensors of a checkpoint file through
``client.device.download_sharded``.

Parameters of a traffic file of this kind:
  clients   callers running side by side
  mode      "cold": every operation a fresh task set (new tag), pulled
            through the whole fabric and deleted from both stores after
  trace     how much of the window a traced run covers:
            {"operations": n} or {"seconds": s}
The rank, the coalescing gap and the header's guess are the configuration's
(``deployment``), handed to the call as they stand.

An operation here is the driver's own, built from the harness's parts and
appended to ``cell.ops``, so that ``Cell.check`` runs unchanged. Timed:
request -> ``download_sharded(selector=the rank's, tag=fresh)`` -> every
returned tensor ready. Untimed: the flights of ALL the operation's ranged
tasks (the header's too) merged into ``op.flight`` (a piece's number is
``1000 * k + piece`` for the k-th task, so that pieces of tasks that landed
side by side still pair) and kept apart in ``op.ranged`` for the readers of
the layer "ranged pull"; ``op.nbytes`` the bytes of the returned tensors;
the benchmark's own (sum32, xor32) of EVERY returned tensor, taken on the
device, which ``Cell.check``'s first line compares with what the objects
module reckons with NumPy from the generator's bytes (``cell.facts``: a
"piece" of that line is here a tensor); a seeded sample of whole tensors
fetched back, with the set of names; ``from_p2p`` / ``from_reuse`` true only
where true of every ranged task. ``objects.size()`` is the rank's bytes, so
the origin's line reads bytes served over bytes selected.

A program whose ``download_sharded`` says nothing of the tasks it made
cannot run the cell: this module refuses to load there, before the fabric
starts, and ``warm_up`` raises when its operation fails, so such a run ends
at once with no last line and not after a window of failed operations.
"""

from __future__ import annotations

import asyncio
import functools
import itertools
import time

import numpy as np

import harness
from dragonfly2_tpu.client import device as device_lib

if not hasattr(device_lib, "ShardedTensors"):
    raise RuntimeError(
        "closed_loop_ranged: this program's download_sharded returns a bare "
        "dict; it names no ranged task, so no flight, path or byte count "
        "of an operation can be read")


@functools.lru_cache(maxsize=None)
def _tensor_checksum_program(dtype: str, shape: tuple):
    """The benchmark's own (sum32, xor32) of one tensor's bytes as
    little-endian uint32 words, on the device, in plain jax.numpy. A
    16-bit tensor is read as its even and odd items, the low and high
    halves of each word; the sums wrap as uint32 does."""
    import jax
    import jax.numpy as jnp

    def xor_all(w):
        return jax.lax.reduce(w, w.dtype.type(0), jax.lax.bitwise_xor,
                              tuple(range(w.ndim))).astype(jnp.uint32)

    def chipbench_tensor_checksum(t):
        size = jnp.dtype(t.dtype).itemsize
        if size == 4:
            w = jax.lax.bitcast_convert_type(t, jnp.uint32)
            return jnp.stack([jnp.sum(w, dtype=jnp.uint32), xor_all(w)])
        if size != 2 or t.shape[-1] % 2:
            raise TypeError(f"no word checksum for {t.dtype}{t.shape}")
        # In the tensor's own shape: a reshape to pairs would give the
        # array a minor dimension of 2, which the TPU pads to 128 lanes.
        items = jax.lax.bitcast_convert_type(t, jnp.uint16)
        lo, hi = items[..., 0::2], items[..., 1::2]
        return jnp.stack([
            jnp.sum(lo, dtype=jnp.uint32)
            + (jnp.sum(hi, dtype=jnp.uint32) << 16),
            xor_all(lo) | (xor_all(hi) << 16)])

    return jax.jit(chipbench_tensor_checksum)


def checksums_of(tensors: dict) -> np.ndarray:
    """(tensors, 2) uint32, in the order of the names."""
    import jax.numpy as jnp

    rows = [_tensor_checksum_program(str(t.dtype), tuple(t.shape))(t)
            for _, t in sorted(tensors.items())]
    return np.asarray(jnp.stack(rows)).view(np.uint32)


def read_flights(cell, op, tasks) -> None:
    """The peer's flight events of every ranged task that fall inside the
    operation, on this process's perf_counter clock: apart in
    ``op.ranged`` (one row a task, in the result's order), merged by time
    in ``op.flight``."""
    from dragonfly2_tpu.pkg import flight as flightlib

    names = flightlib.EVENT_NAMES
    op.ranged, merged = [], []
    for k, task in enumerate(tasks):
        tf = cell.fabric.daemon.task_manager.flight.get(task.task_id)
        events = []
        if tf is not None:
            start = time.perf_counter() - (flightlib.anchored_wall()
                                           - tf.start_wall)
            events = [(start + t, names.get(code, str(code)), piece, aux)
                      for t, code, piece, aux, _ in tf.events()
                      if op.t0 <= start + t <= op.t1]
        op.ranged.append({"start": task.start, "end": task.end,
                          "task_id": task.task_id, "names": len(task.names),
                          "flight": events})
        merged += [(t, name, piece if piece < 0 else 1000 * k + piece, aux)
                   for t, name, piece, aux in events]
    op.flight = sorted(merged)


def describe(op) -> str:
    """One row a ranged task for a person, in the result's order: MB,
    then seconds after the request at which it was admitted (and the ms it
    had waited), registered, first requested a piece, landed its last,
    was done, and was verified on the device."""
    def at(flight, name, last=False):
        times = [t for t, event, _, _ in flight if event == name]
        return f"{(times[-1] if last else times[0]) - op.t0:.3f}" \
            if times else "-"

    rows = []
    for task in op.ranged:
        flight = task["flight"]
        waited = [aux for _, event, _, aux in flight if event == "admit_wait"]
        rows.append(
            f"{(task['end'] - task['start']) / 1e6:.1f}MB "
            f"{at(flight, 'admit_wait')}({sum(waited):.0f}ms) "
            f"{at(flight, 'register')} {at(flight, 'request')} "
            f"{at(flight, 'hbm_landed', True)} {at(flight, 'task_done', True)}"
            f" {at(flight, 'sink_finalize', True)}")
    return "; ".join(rows)


async def operation(cell, number: int, client: int, *,
                    warmup: bool = False) -> harness.Op:
    """One rank's whole pull, timed by the host clock until every tensor is
    ready; then, untimed, the benchmark's readings and the deletes."""
    import jax
    from jax.profiler import TraceAnnotation

    deployment = cell.config["deployment"]
    tag = f"s{cell.seed}-op{number}"
    op = harness.Op(number=number, client=client, object_index=0, tag=tag,
                    warmup=warmup, cold=True)
    tensors = None
    op.t0 = time.perf_counter()
    try:
        with TraceAnnotation(f"chipbench:op#{number}"):
            tensors = await asyncio.wait_for(device_lib.download_sharded(
                cell.fabric.daemon, cell.fabric.url(0),
                selector=cell.objects.selector(), tag=tag,
                coalesce_gap=int(deployment["coalesce_gap"]),
                prefix_guess=int(deployment["prefix_guess"])), 600)
            jax.block_until_ready(list(tensors.values()))
        op.t1 = time.perf_counter()
    except Exception as e:  # a failed operation is counted, not fatal
        op.t1 = time.perf_counter()
        op.error = f"{type(e).__name__}: {e}"[:500]
        harness.say(f"operation {number} failed: {op.error}")
    cell.ops.append(op)
    if op.error:
        return op
    tasks = tensors.tasks
    op.nbytes = sum(int(t.nbytes) for t in tensors.values())
    op.task_id = tasks[0].task_id
    op.from_p2p = all(t.from_p2p for t in tasks)
    op.from_reuse = all(t.from_reuse for t in tasks)
    cell.pulls[0] = cell.pulls.get(0, 0) + 1
    read_flights(cell, op, tasks)
    dispatched = [(t - aux / 1000.0) for t, name, _, aux in op.flight
                  if name == "shard_views"]
    if dispatched:
        # From the first view's dispatch inside the call to every tensor
        # ready: what the device's idle gaps are labelled with.
        op.views_span = (dispatched[0], op.t1)
    returned = sorted(tensors)
    if returned == sorted(cell.objects.selected()):
        op.fetched = await asyncio.to_thread(
            cell.objects.fetch, dict(tensors), cell.rng,
            cell.fetch_whole_first)
        cell.fetch_whole_first = False
    else:
        # Not the rank's names: the set alone is compared, and fails.
        op.fetched = [("", None, None, returned)]
    op.device_checksums = await asyncio.to_thread(checksums_of, tensors)
    tensors = None
    await asyncio.gather(*(cell.fabric.delete_everywhere(t.task_id)
                           for t in tasks))
    op.gap_s = time.perf_counter() - op.t1
    return op


async def warm_up(cell) -> None:
    """The objects module's facts of the rank's tensors take the place of
    the origin's whole-object ones; then untimed operations of the cell's
    own geometries until one went the cell's path (set-up's race, as
    ``closed_loop.warm_up`` has it). An operation that fails here ends the
    run."""
    cell.facts[0] = await asyncio.to_thread(cell.objects.rank_facts)
    for attempt in range(4):
        op = await operation(cell, -1 - attempt, 0, warmup=True)
        if op.error:
            raise RuntimeError("closed_loop_ranged: the warm-up's operation "
                               f"failed: {op.error}")
        harness.say(
            f"warm-up {-1 - attempt}: {len(op.ranged)} ranged tasks, the "
            f"header's first, of bytes "
            f"{[t['end'] - t['start'] for t in op.ranged]} with "
            f"{[t['names'] for t in op.ranged]} tensors cut from each; "
            f"{op.nbytes} bytes resident")
        if op.from_p2p:
            return
        op.raced = True


async def window(cell, seconds: float, traced: bool) -> tuple[float, float]:
    """As ``closed_loop.window``."""
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
            await operation(cell, n, c)

    await asyncio.gather(*(client(c)
                           for c in range(int(cell.traffic["clients"]))))
    end = time.perf_counter()
    done = [op for op in cell.ops if not op.warmup and not op.error]
    if done:
        harness.say(f"operation {done[-1].number}, a row a ranged task (MB "
                    "admitted(waited) register first-request last-landed "
                    "task_done verified, seconds after the request): "
                    + describe(done[-1]))
    return start, end
