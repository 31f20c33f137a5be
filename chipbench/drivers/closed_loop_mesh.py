"""Traffic kind ``closed_loop_mesh``: as ``closed_loop``, with every object
placed whole on every chip of a mesh. One caller a client, each sending its
next operation when the last one has finished.

Parameters of a traffic file of this kind:
  clients    callers running side by side
  mode       "cold" | "reland", as ``closed_loop`` reads them
  mesh       [n]: the first n of ``jax.devices()`` on one axis
  placement  "replicated": the object whole on every chip of the mesh
  trace      how much of the window a traced run covers:
             {"operations": n} or {"seconds": s}

An operation here is the driver's own, built from the harness's parts and
appended to ``cell.ops``, so that ``Cell.check`` runs unchanged: request ->
``download_to_device`` with the mesh and the placement -> the words ready ON
EVERY CHIP -> ``load_safetensors`` -> every tensor ready on every chip.
``nbytes`` is the content's length once: the rate is the shard's bytes over
the time until the last chip has them. The benchmark's own readings speak
for every chip: its per-piece checksums are taken on each chip's copy, and
``op.device_checksums`` is chip 0's only where all agree (a piece on which
the chips differ gets a value none of them reported); the seeded sample of tensors is fetched from the copy on chip
``number % n``, so a window samples every chip.

A program whose ``download_to_device`` knows no placement cannot run the
cell: this module refuses to load there, before the fabric starts, and
``warm_up`` raises when its operation fails, so such a run ends at once with
no last line and not after a window of failed operations.
"""

from __future__ import annotations

import asyncio
import inspect
import itertools
import time

import numpy as np

import harness
from dragonfly2_tpu.client.device import download_to_device

if "placement" not in inspect.signature(download_to_device).parameters:
    raise RuntimeError(
        "closed_loop_mesh: this program's download_to_device takes no "
        "placement; it cannot place an object whole on every chip")


def mesh_of(cell):
    """The traffic's mesh: the first n devices on one axis."""
    import jax
    from jax.sharding import Mesh

    n = int(np.prod(cell.traffic["mesh"]))
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(f"closed_loop_mesh: a mesh of {n} on "
                           f"{len(devices)} device(s)")
    return Mesh(np.array(devices[:n]), ("d",))


def on_chip(array, device):
    """The copy of a replicated ``array`` that ``device`` holds, as an
    array of that device alone (no copy is made)."""
    return next(s.data for s in array.addressable_shards
                if s.device == device)


def checksums_on_every_chip(words, piece_words: int, devices) -> np.ndarray:
    """The benchmark's per-piece (sum32, xor32), taken on each chip's copy
    by that chip, as ONE (pieces, 2) array that speaks for every chip:
    chip 0's where all chips agree; where they differ on a piece (whichever
    chip is the odd one, chip 0 included), a value that NO chip reported,
    so that the piece cannot equal the generator's; zeros where a chip
    holds no complete copy."""
    program = harness._checksum_program(piece_words)
    held = {s.device: s.data for s in words.addressable_shards}
    if any(d not in held or held[d].shape != words.shape for d in devices):
        return np.zeros((words.shape[0] // piece_words, 2), np.uint32)
    every = np.stack([np.asarray(program(held[d])).view(np.uint32)
                      for d in devices])
    out = every[0].copy()
    for piece in np.flatnonzero((every != every[0]).any(axis=(0, 2))):
        reported = {tuple(row) for row in every[:, piece].tolist()}
        while tuple(out[piece].tolist()) in reported:
            out[piece, 0] += np.uint32(1)
    return out


async def operation(cell, mesh, number: int, client: int, *,
                    warmup: bool = False, cold: bool | None = None,
                    index: int | None = None,
                    closing=lambda: False) -> harness.Op:
    """As ``Cell.operation``, placed on the mesh: timed by the host clock
    until every chip has the words and every tensor; then, untimed, the
    benchmark's readings on every chip and the clean-up the mode asks
    for."""
    import jax
    from jax.profiler import TraceAnnotation

    devices = list(mesh.devices.flat)
    if index is None:
        index = cell.claim_object(client)
    if cold is None:
        cold = cell.mode == "cold"
    fresh = cold and cell.mode == "cold"
    tag = f"s{cell.seed}-op{number}" if fresh else f"s{cell.seed}-reland"
    op = harness.Op(number=number, client=client, object_index=index,
                    tag=tag, warmup=warmup, cold=cold)
    digest = ""
    if cell.config["object"].get("digest"):
        digest = (await cell.facts_for(index))["digest"]
    url = cell.fabric.url(index)
    result = words = tensors = None
    op.t0 = time.perf_counter()
    try:
        with TraceAnnotation(f"chipbench:op#{number}"):
            result = await asyncio.wait_for(
                download_to_device(
                    cell.fabric.daemon, url, digest=digest, tag=tag,
                    mesh=mesh, placement=cell.traffic["placement"]), 600)
            # A replicated array is ready when every chip's copy is.
            words = jax.block_until_ready(result.as_words())
            if cell.objects.typed:
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
    if op.error:
        return op
    op.nbytes = result.content_length
    op.task_id = result.task_id
    op.from_p2p, op.from_reuse = result.from_p2p, result.from_reuse
    op.piece_bytes = result.sink.sink.piece_size
    piece_words = result.sink.sink.piece_words
    if cold:
        cell.pulls[index] = cell.pulls.get(index, 0) + 1
    cell._read_flight(op)
    del result
    # Untimed readings, in the order that keeps each device's peak the
    # program's: the sampled tensors (of one chip's copy) first, then the
    # tensors go, then the checksums over every chip's words, a piece at
    # a time.
    chip = devices[number % len(devices)]
    if tensors is not None:
        placed_on = {frozenset(t.devices()) for t in tensors.values()}
        if placed_on != {frozenset(devices)}:
            # A tensor that is not on every chip of the mesh fails the
            # comparison: the sample is left empty of everything but the
            # names, and "tensors > 0" with a mismatch of names catches it.
            op.fetched = [("", None, None, [
                f"not on every chip: {sorted(map(len, placed_on))}"])]
        else:
            # A warm-up samples every chip's copy, so that no chip meets
            # its first slice of a tensor inside the window.
            for device in (devices if warmup else [chip]):
                op.fetched += await asyncio.to_thread(
                    cell.objects.fetch,
                    {name: on_chip(t, device) for name, t in tensors.items()},
                    cell.rng, cell.fetch_whole_first and device == chip)
        cell.fetch_whole_first = False
        tensors = None
    op.device_checksums = await asyncio.to_thread(
        checksums_on_every_chip, words, piece_words, devices)
    if not cell.objects.typed:
        if warmup:
            op.kept_words = await asyncio.to_thread(
                np.asarray, on_chip(words, chip))
        elif closing():
            op.kept_words = on_chip(words, chip)
    del words
    if fresh:
        await cell.fabric.delete_everywhere(op.task_id)
    op.gap_s = time.perf_counter() - op.t1
    return op


async def warm_up(cell) -> None:
    """As ``closed_loop.warm_up``; an operation that fails here ends the
    run."""
    mesh = mesh_of(cell)

    async def one(*args, **kwargs) -> harness.Op:
        op = await operation(cell, mesh, *args, warmup=True, **kwargs)
        if op.error:
            raise RuntimeError("closed_loop_mesh: the warm-up's operation "
                               f"failed: {op.error}")
        return op

    if cell.mode == "reland":
        clients = int(cell.traffic["clients"])
        numbers = itertools.count(-2, -1)
        todo = list(range(cell.stored if cell.objects.distinct else 1))

        async def fill(c: int) -> None:
            while todo:
                op = await one(next(numbers), c, cold=True,
                               index=todo.pop(0))
                op.raced = not op.from_p2p

        await asyncio.gather(*(fill(c) for c in range(clients)))
        await one(-1, 0)
        return
    for attempt in range(4):
        op = await one(-1 - attempt, 0)
        if op.from_p2p:
            return
        op.raced = True


async def window(cell, seconds: float, traced: bool) -> tuple[float, float]:
    """As ``closed_loop.window``."""
    mesh = mesh_of(cell)
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
            await operation(
                cell, mesh, n, c,
                closing=lambda: time.perf_counter() - start >= seconds)

    await asyncio.gather(*(client(c)
                           for c in range(int(cell.traffic["clients"]))))
    return start, time.perf_counter()
