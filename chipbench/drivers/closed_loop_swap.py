"""Traffic kind ``closed_loop_swap``: ONE client hot-swapping a serving
replica's shard from one version to the next, its next swap asked for when
the last one has flipped; an operation is one swap.

Parameters of a traffic file of this kind:
  clients        1: the replica is one process's
  mode           "swap" (the harness wants the key; neither of its modes)
  warm_up_swaps  swaps before the window (the compiles; the first one also
                 builds version 0's manifest, which no one published)
  fetch_share    share of the window's swaps whose every tensor is fetched
                 back whole for the comparison, drawn from the cell's seeded
                 generator, the first and the last always
  read_every_s   the reader thread's pause between two snapshots
  trace          how much of the window a traced run covers:
                 {"operations": n}

Set-up (``warm_up``): version 0 is pulled cold through
``download_to_device`` on the embedded daemon, its tensors loaded and
installed as generation 1 of an ``ops.hbm_sink.DoubleBuffer``; ONE reader
thread starts taking ``hot.snapshot()`` and noting (generation, the identity
of a frozen tensor, the identity of a routed expert's); then the warm-up's
swaps. Before every swap, untimed and never beside a timed one, the next
version is preheated: ``Daemon.Download`` with ``delta_base`` (what ``dfget
--delta-base`` sends) asked of the SEED peer's daemon over its socket, which
finds no manifest of it, pulls it whole from the origin and publishes its
manifest. Timed: ``download_delta(daemon, url(v+1), base=<task of v>,
hot=hot)`` on the embedded daemon, request -> flipped and every tensor of the
new generation ready. ``op.nbytes`` is the version's content. Untimed, after:
the delta task's flight; the program's counters; the benchmark's own (sum,
xor) of every tensor's items, taken on the device in plain jax.numpy; where
the draw says so every tensor fetched back whole and compared; the origin's
``/stats``; the ranged tasks the swap left in this host's store; then the
task of the version before the one just replaced is deleted from both stores.

The comparison is the driver's own (``check``, put in ``Cell.check``'s
place), against the objects module's reference alone
(``objects/safetensors_shard_versions.py``): every guarantee of the
configuration, each beside its limit.

A program without this PR's hot-swap plane cannot run the cell: this module
refuses to load there, before the fabric's daemon starts.
"""

from __future__ import annotations

import asyncio
import functools
import os
import statistics
import threading
import time

import numpy as np

import harness
from dragonfly2_tpu.ops import hbm_sink

if not hasattr(hbm_sink, "SWAP_ASSEMBLIES"):
    raise RuntimeError(
        "closed_loop_swap: this program's hot-swap assembles a new program "
        "a version (ops.hbm_sink has no device_swap_assemblies_total): the "
        "cell needs one program a geometry")

from dragonfly2_tpu.client.device import (  # noqa: E402
    download_delta,
    download_to_device,
)
from dragonfly2_tpu.delta import resolver  # noqa: E402
from layers import sink_events  # noqa: E402
from objects.safetensors_shard import LAYER  # noqa: E402

FROZEN = "model.embed_tokens.weight"


@functools.lru_cache(maxsize=None)
def _items_checksum_program(dtype: str, shape: tuple):
    """The benchmark's own (sum mod 2**32, xor) of a tensor's items as
    unsigned integers of the item's width, on the device, in plain
    jax.numpy (int32 lanes wrap as uint32 does)."""
    import jax
    import jax.numpy as jnp

    unsigned = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32}[
        jnp.dtype(dtype).itemsize]

    def chipbench_items_checksum(tensor):
        items = jax.lax.bitcast_convert_type(tensor, unsigned) \
            .astype(jnp.uint32)
        items = jax.lax.bitcast_convert_type(items, jnp.int32)
        return jnp.stack([
            jnp.sum(items, dtype=jnp.int32),
            jax.lax.reduce(items, jnp.int32(0), jax.lax.bitwise_xor,
                           tuple(range(items.ndim)))])

    return jax.jit(chipbench_items_checksum)


class Reader(threading.Thread):
    """The serving side: takes the live generation as a request would and
    notes what it saw. A run of equal notes is one entry with a count."""

    def __init__(self, hot, expert: str, pause_s: float):
        super().__init__(name="chipbench-swap-reader", daemon=True)
        self.hot = hot
        self.expert = expert
        self.pause_s = pause_s
        self.notes: list[list] = []      # [generation, frozen id, expert id, n]
        self.old_reads: list[tuple] = []  # (generation, bytes of its expert)
        self.failed = ""
        self._halt = threading.Event()

    def note(self, snapshot) -> tuple:
        generation, _, tensors = snapshot
        return generation, id(tensors[FROZEN]), id(tensors[self.expert])

    def run(self) -> None:
        held = None
        try:
            while not self._halt.is_set():
                snapshot = self.hot.snapshot()
                seen = self.note(snapshot)
                if self.notes and tuple(self.notes[-1][:3]) == seen:
                    self.notes[-1][3] += 1
                else:
                    self.notes.append([*seen, 1])
                if held is not None and held[0] != snapshot[0]:
                    # The generation it held is no longer the live one:
                    # its tensors must still read as they did.
                    self.old_reads.append((held[0], np.asarray(
                        held[2][self.expert][:1]).view(np.uint8).copy()))
                held = snapshot
                time.sleep(self.pause_s)
        except Exception as e:     # the check reports it
            self.failed = f"{type(e).__name__}: {e}"[:300]

    def stop(self) -> None:
        self._halt.set()
        self.join(10)


def counters() -> dict:
    """The program's counters this cell reads, as they stand."""
    return {
        **{f"swap_{how}": hbm_sink.SWAP_BYTES.labels(how)._value.get()
           for how in ("hbm_reused", "staged")},
        **{f"result_{how}": hbm_sink.SWAP_RESULTS.labels(how)._value.get()
           for how in ("flipped", "refused", "fallback")},
        **{f"assembly_{how}":
           hbm_sink.SWAP_ASSEMBLIES.labels(how)._value.get()
           for how in ("compiled", "cached")},
        **{f"delta_{kind}": resolver.DELTA_BYTES.labels(kind)._value.get()
           for kind in ("reused", "fetched")},
        "corrupt_base":
            resolver.DELTA_CHUNKS.labels("corrupt_base")._value.get()}


def request_for(cell, version: int, digest: str = "", span=None):
    """The task request the program makes for a version, or for one span of
    it as the delta plane's fetcher asks: its id is how the check finds the
    task in a store."""
    from dragonfly2_tpu.daemon.peer.task_manager import FileTaskRequest
    from dragonfly2_tpu.pkg.piece import Range
    from dragonfly2_tpu.proto.common import UrlMeta

    rng = Range.normalize_header(f"{span[0]}-{span[1] - 1}") if span else ""
    return FileTaskRequest(
        url=cell.fabric.url(version), output="",
        meta=UrlMeta(digest=digest, tag=cell.tag, range=rng))


async def preheat(cell, version: int, digest: str) -> float:
    """Version ``version`` whole on the seed peer, through the delta entry
    point (which publishes its manifest). Returns the seconds it took."""
    from dragonfly2_tpu.pkg.types import NetAddr
    from dragonfly2_tpu.proto.common import UrlMeta
    from dragonfly2_tpu.rpc import Client

    t0 = time.perf_counter()
    out = os.path.join(cell.fabric.seed_home, "out", f"v{version}")
    cli = Client(NetAddr.unix(cell.fabric.seed_sock))
    try:
        stream = await cli.open_stream("Daemon.Download", {
            "url": cell.fabric.url(version), "output": out,
            "meta": UrlMeta(digest=digest, tag=cell.tag).to_wire(),
            "delta_base": cell.tasks[version - 1]})
        final = None
        while True:
            msg = await stream.recv(timeout=600)
            if msg is None:
                break
            if msg.get("state") in ("done", "failed"):
                final = msg
        if final is None or final["state"] != "done":
            raise RuntimeError(f"closed_loop_swap: the preheat of version "
                               f"{version} on the seed ended {final}")
    finally:
        await cli.close()
    if os.path.exists(out):
        os.unlink(out)
    return time.perf_counter() - t0


async def read_generation(cell, op, tensors: dict, whole: bool) -> None:
    """The benchmark's readings of a generation's tensors, compared with the
    reference's version at once (the tensors go with the generation):
    ``op.bad_sums`` of ``op.summed`` by the checksums taken on the device,
    ``op.bad_whole`` of ``op.fetched_whole`` fetched back."""
    objects = cell.objects
    want = await asyncio.to_thread(objects.expected_checksums,
                                   op.object_index)

    def sums() -> int:
        taken = {name: _items_checksum_program(str(t.dtype), tuple(t.shape))(t)
                 for name, t in tensors.items()}
        bad = set(taken) ^ set(want)
        for name in set(taken) & set(want):
            s, x = (int(v) for v in np.asarray(taken[name]).view(np.uint32))
            bad |= {name} if (s, x) != want[name] else set()
        return len(bad)

    def fetched() -> int:
        bad = 0
        for name, t in tensors.items():
            meta = (str(t.dtype), tuple(t.shape), len(t.devices()))
            got = np.asarray(t)
            got = got.view(np.uint8).reshape(got.shape[0], -1)
            bad += not objects.matches((name, None, meta, got),
                                       op.object_index)
        return bad

    op.summed = len(want)
    op.bad_sums = await asyncio.to_thread(sums)
    op.fetched_whole = len(tensors) if whole else 0
    op.bad_whole = await asyncio.to_thread(fetched) if whole else 0


async def operation(cell, number: int, *, warmup: bool = False,
                    closing=lambda: False) -> harness.Op:
    """One swap to the next version, timed by the host clock until the new
    generation is live and ready; before it the preheat, after it the
    readings, both untimed."""
    import jax
    from jax.profiler import TraceAnnotation

    version = cell.version + 1
    facts = await cell.facts_for(version)
    preheat_s = await preheat(cell, version, facts["digest"])
    stats = await asyncio.to_thread(cell.fabric.origin_json, "/stats")
    op = harness.Op(number=number, client=0, object_index=version,
                    tag=cell.tag, warmup=warmup, cold=False)
    op.preheat_s = preheat_s
    op.origin_preheat = stats.get(str(version), {}).get("bytes", 0)
    before = counters()
    result = None
    op.t0 = time.perf_counter()
    try:
        with TraceAnnotation(f"chipbench:op#{number}"):
            result = await asyncio.wait_for(download_delta(
                cell.fabric.daemon, cell.fabric.url(version),
                base=cell.tasks[version - 1], hot=cell.hot,
                digest=facts["digest"], tag=cell.tag), 600)
            jax.block_until_ready(list(result.tensors.values()))
        op.t1 = time.perf_counter()
    except Exception as e:  # a failed operation is counted, not fatal
        op.t1 = time.perf_counter()
        op.error = f"{type(e).__name__}: {e}"[:500]
        harness.say(f"operation {number} failed: {op.error}")
    cell.ops.append(op)
    if op.error:
        return op
    after = counters()
    op.counted = {k: after[k] - before[k] for k in after}
    op.nbytes = result.content_length
    op.task_id = result.task_id
    op.swap = {"on_device": result.on_device, "flipped": result.flipped,
               "generation": result.generation,
               "hbm_reused": result.reused_device_bytes,
               "staged": result.staged_bytes, "stats": dict(result.stats)}
    cell.version = version
    cell.tasks[version] = result.task_id
    # What is served from now on: the DoubleBuffer's, not the call's.
    generation, _, tensors = cell.hot.snapshot()
    op.swap["live"] = generation
    cell.installed[generation] = (
        version, id(tensors[FROZEN]), id(tensors[cell.expert]))
    cell._read_flight(op)
    stats = await asyncio.to_thread(cell.fabric.origin_json, "/stats")
    op.origin_swap = stats.get(str(version), {}).get("bytes", 0) \
        - op.origin_preheat
    # What the swap left in this host's store of the new version's URL
    # beside the version itself: the ranged tasks of its fetched spans.
    storage = cell.fabric.daemon.task_manager.storage
    op.wire = [(s.metadata.task_id, s.metadata.content_length)
               for s in storage.tasks()
               if s.metadata.url == cell.fabric.url(version)
               and s.metadata.task_id != op.task_id]
    always = number == 0 or closing()
    drawn = cell.rng.random() < float(cell.traffic["fetch_share"])
    del result
    await read_generation(cell, op, tensors, always or drawn)
    del tensors
    # The version before the one just replaced goes, and the ranged tasks
    # of the swap that brought it.
    gone = version - 2
    if gone in cell.tasks:
        await cell.fabric.delete_everywhere(cell.tasks.pop(gone))
        for task_id, _ in cell.spans.pop(gone, ()):
            await asyncio.to_thread(storage.delete_task, task_id)
    cell.spans[version] = op.wire
    op.gap_s = time.perf_counter() - op.t1
    return op


async def warm_up(cell) -> None:
    """Version 0 cold into HBM and installed, the reader started, then the
    warm-up's swaps. Anything that fails here ends the run."""
    import jax

    objects = cell.objects
    cell.check = functools.partial(check, cell)
    cell.tag = f"s{cell.seed}-swap"
    cell.version = 0
    cell.tasks = {}
    cell.spans = {}
    cell.installed = {}
    # The sentinel of the reader: a routed expert that version 1 draws.
    cell.expert = (LAYER + f"mlp.experts.{objects.drawn(1)[0]}."
                   "gate_proj.weight")
    facts = await cell.facts_for(0)
    t0 = time.perf_counter()
    result = await asyncio.wait_for(download_to_device(
        cell.fabric.daemon, cell.fabric.url(0), digest=facts["digest"],
        tag=cell.tag), 600)
    words = result.as_words()
    tensors = result.load_safetensors()
    jax.block_until_ready(list(tensors.values()))
    cell.tasks[0] = result.task_id
    cell.hot = hbm_sink.DoubleBuffer()
    cell.hot.flip(words, tensors)
    cell.installed[1] = (0, id(tensors[FROZEN]), id(tensors[cell.expert]))
    harness.say(f"set-up: version 0 ({result.content_length} bytes, "
                f"{len(tensors)} tensors) pulled cold and installed as "
                f"generation 1 in {time.perf_counter() - t0:.1f}s "
                f"(from_p2p {result.from_p2p})")
    first = harness.Op(number=-100, client=0, object_index=0, tag=cell.tag)
    del result, words
    await read_generation(cell, first, tensors, True)
    del tensors
    cell.first = first
    cell.reader = Reader(cell.hot, cell.expert,
                         float(cell.traffic["read_every_s"]))
    cell.reader.start()
    swaps = int(cell.traffic["warm_up_swaps"])
    for n in range(swaps):
        op = await operation(cell, n - swaps, warmup=True)
        if op.error:
            raise RuntimeError("closed_loop_swap: the warm-up's swap "
                               f"failed: {op.error}")
        harness.say(f"warm-up swap to version {op.object_index}: preheat "
                    f"{op.preheat_s:.1f}s, swap {op.seconds:.2f}s, "
                    + describe([op]))


async def window(cell, seconds: float, traced: bool) -> tuple[float, float]:
    """As ``closed_loop.window``, one client; a swap that fails ends the
    window (the chain of versions is broken)."""
    limit = cell.traffic.get("trace", {}) if traced else {}
    most = limit.get("operations")
    start = time.perf_counter()
    number = 0
    while time.perf_counter() - start < seconds and (
            most is None or number < most):
        op = await operation(
            cell, number,
            closing=lambda: time.perf_counter() - start >= seconds
            or (most is not None and number + 1 >= most))
        number += 1
        if op.error:
            break
    end = time.perf_counter()
    cell.reader.stop()
    done = [op for op in cell.ops if not op.warmup and not op.error]
    if done:
        harness.say(f"preheats (untimed), s: "
                    f"{[round(op.preheat_s, 1) for op in done]}; "
                    + describe(done))
    return start, end


def describe(done) -> str:
    """Where a swap's time went, for a person: medians over the operations
    of the spans' summed ms."""
    def of(name: str) -> float:
        return statistics.median(sink_events.summed_ms(op, name) or 0.0
                                 for op in done)

    return (f"a swap, medians of {len(done)}: request->flipped "
            f"{statistics.median(op.seconds for op in done) * 1e3:.0f} ms; "
            "summed span ms: " + ", ".join(
                f"{name} {of(name):.0f}" for name in (
                    "swap_plan", "delta_reuse", "delta_fetch", "verified",
                    "admit_wait", "swap_stage", "swap_assemble",
                    "swap_verify", "swap_views"))
            + f"; staged {[op.swap['staged'] for op in done]}")


async def check(cell) -> tuple[bool, list[str]]:
    """Every number compared, beside its limit; ``correct`` is all of them
    inside their limits."""
    objects = cell.objects
    guarantees = cell.config["guarantees"]
    done = [op for op in cell.ops if not op.error]
    timed = [op for op in done if not op.warmup]
    read = [cell.first, *done]
    lines: list[str] = []

    bad_sums = sum(op.bad_sums for op in read)
    lines.append(f"tensors whose (sum, xor), taken on the device, differs "
                 f"from the reference's of that version: {bad_sums} of "
                 f"{sum(op.summed for op in read)} in {len(read)} "
                 "generations (limit 0)")
    bad_whole = sum(op.bad_whole for op in read)
    whole = sum(op.fetched_whole for op in read)
    lines.append(f"tensors fetched back whole that differ from "
                 f"numpy.frombuffer of the generator's bytes of that "
                 f"version: {bad_whole} of {whole} (limit 0)")

    drift = off_plan = corrupt = device_drift = 0
    worst_wire, stray, origin_swap = 0.0, 0, 0
    shares = []
    for op in done:
        plan = await asyncio.to_thread(objects.expected_plan,
                                       op.object_index - 1)
        stats = op.swap["stats"]
        drift += stats.get("reused_bytes", 0) \
            + stats.get("fetched_bytes", -1) != op.nbytes
        off_plan += (stats.get("fetched_bytes") != plan["fetched_bytes"]
                     or op.counted["delta_fetched"] != plan["fetched_bytes"]
                     or op.counted["delta_reused"] != plan["reused_bytes"])
        corrupt += op.counted["corrupt_base"]
        device_drift += (op.swap["hbm_reused"] + op.swap["staged"]
                         != op.nbytes
                         or op.counted["swap_hbm_reused"]
                         != op.swap["hbm_reused"]
                         or op.counted["swap_staged"] != op.swap["staged"])
        digest = (await cell.facts_for(op.object_index))["digest"]
        wanted = {request_for(cell, op.object_index, digest, span).task_id()
                  for span in plan["spans"]}
        stray += sum(task_id not in wanted for task_id, _ in op.wire)
        worst_wire = max(worst_wire, sum(n for _, n in op.wire)
                         / max(1, plan["fetched_bytes"]))
        origin_swap += op.origin_swap
        shares.append(op.origin_preheat / objects.size(op.object_index))
    lines.append(f"swaps whose reused + fetched differs from the content: "
                 f"{drift} of {len(done)} (limit 0); whose fetched or reused "
                 f"bytes (the task's own and peer_delta_bytes_total) differ "
                 f"from the reference's plan: {off_plan} (limit 0); base "
                 f"chunks re-fetched as corrupt: {corrupt} (limit 0)")
    lines.append(f"swaps whose hbm_reused + staged differs from the content "
                 f"or from device_swap_bytes_total: {device_drift} of "
                 f"{len(done)} (limit 0)")
    limit = guarantees["wire_amplification_max"]
    lines.append(f"bytes of the ranged tasks a swap left in this host's "
                 f"store over the reference's fetched bytes, worst swap: "
                 f"{worst_wire:.4f} (limit {limit}); ranged tasks that are "
                 f"no span of the reference's plan: {stray} (limit 0)")
    limit_o = guarantees["origin_amplification_max"]
    lines.append(f"origin bytes for a version's preheat over its length: "
                 f"least {min(shares, default=0):.4f}, most "
                 f"{max(shares, default=0):.4f} (limits 1, {limit_o}); origin "
                 f"bytes served during the swaps: {origin_swap} (limit 0)")

    reader = cell.reader
    torn = sum(1 for g, frozen, expert, _ in reader.notes
               if cell.installed.get(g, (None, None, None))[1:]
               != (frozen, expert))
    order = [g for g, _, _, _ in reader.notes]
    falls = sum(b < a for a, b in zip(order, order[1:]))
    stale = 0
    for generation, got in reader.old_reads:
        version = cell.installed[generation][0]
        stale += not np.array_equal(
            got, objects.expected(cell.expert, slice(0, 1), version))
    lines.append(f"snapshots the reader took that are no one complete "
                 f"generation: {torn} of "
                 f"{sum(n for _, _, _, n in reader.notes)} (limit 0); "
                 f"generation numbers that fell: {falls} (limit 0); "
                 f"generations seen {sorted(set(order))}; reads of a "
                 f"generation after it was replaced that differ from its "
                 f"version: {stale} of {len(reader.old_reads)} (limit 0); the "
                 f"reader failed: {reader.failed or 'never'}")

    off_device = sum(not (op.swap["on_device"] and op.swap["flipped"]
                          and op.swap["live"] == op.swap["generation"])
                     for op in done)
    results = {k: sum(op.counted[k] for op in done) for k in (
        "result_flipped", "result_refused", "result_fallback")}
    compiled = sum(op.counted["assembly_compiled"] for op in timed)
    steps = ("swap_plan", "delta_reuse", "delta_fetch", "swap_stage",
             "swap_assemble", "swap_verify", "swap_views", "swap_flip")
    dark = sum(not {name for _, name, _, _ in op.flight}.issuperset(steps)
               for op in done)
    lines.append(f"swaps whose flight lacks one of {', '.join(steps)}: "
                 f"{dark} of {len(done)} (limit 0)")
    lines.append(f"swaps that fell back to a host buffer or did not flip: "
                 f"{off_device} of {len(done)} (limit 0); device_swap_total "
                 f"{results}; assemblies compiled inside the window's "
                 f"operations: {compiled} (limit 0)")
    ok = (bool(timed) and bad_sums == 0 and bad_whole == 0 and whole > 0
          and drift == 0 and off_plan == 0 and corrupt == 0
          and device_drift == 0 and stray == 0 and worst_wire <= limit
          and shares and 1.0 <= min(shares) and max(shares) <= limit_o
          and origin_swap == 0 and torn == 0 and falls == 0 and stale == 0
          and not reader.failed and len(set(order)) > 1
          and dark == 0 and off_device == 0 and results["result_flipped"] == len(done)
          and compiled == 0)
    return bool(ok), lines
