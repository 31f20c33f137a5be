"""ICI shard redistribution: mesh plans for intra-slice piece spread.

The fabric's TPU-side collective layer: one host's daemon lands checkpoint
bytes in its local devices' HBM; these plans spread/reshape them across the
slice over ICI using XLA collectives (all_gather / ppermute under
shard_map), never the NIC. Designed per the scaling-book recipe: pick a
mesh, annotate shardings, let XLA insert the collectives.

All plans are jit-compiled once per (mesh, shape) and work identically on a
virtual CPU mesh (tests / dryrun) and a real TPU slice.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dragonfly2_tpu.ops import bitview


def make_mesh(n_devices: int | None = None, axis_name: str = "d") -> Mesh:
    """1-D mesh over the slice's devices (the ICI ring)."""
    devices = jax.devices()
    if n_devices is not None:
        devices = devices[:n_devices]
    return Mesh(np.array(devices), (axis_name,))


def scatter_shards(mesh: Mesh, host_array: np.ndarray, axis_name: str = "d"):
    """Host buffer → device-sharded array: device i holds shard i. The entry
    point for fabric-landed bytes (leading dim must divide by mesh size)."""
    sharding = NamedSharding(mesh, P(axis_name))
    return jax.device_put(host_array, sharding)


def replicate_to_mesh(mesh: Mesh, host_array: np.ndarray):
    """Host buffer → replicated on every device of the mesh (XLA chooses
    one transfer + ICI broadcast on TPU)."""
    return jax.device_put(host_array, NamedSharding(mesh, P()))


@functools.partial(jax.jit, static_argnames=("axis_name", "mesh"))
def _all_gather_jit(x, *, mesh: Mesh, axis_name: str):
    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=P(axis_name), out_specs=P(),
        check_vma=False,
    )
    def gather(shard):
        return jax.lax.all_gather(shard, axis_name, axis=0, tiled=True)

    return gather(x)


def all_gather_shards(mesh: Mesh, sharded, axis_name: str = "d"):
    """Every device ends with the full content (one-shot XLA all-gather —
    on TPU this lowers to the bidirectional ICI ring). The way to every
    chip that a landing placed "whole on every chip" takes
    (``HBMSink.replicate``): of this module's ways and the runtime's own
    ``device_put`` to a replicated sharding it was the fastest on four chips
    of a v5e at a checkpoint shard's size (PERF.md section 6, PR 31)."""
    return _all_gather_jit(sharded, mesh=mesh, axis_name=axis_name)


@functools.partial(jax.jit, static_argnames=("axis_name", "mesh"))
def _ring_all_gather_jit(x, *, mesh: Mesh, axis_name: str):
    """Explicit ring all-gather via ppermute: N-1 neighbor hops, each step
    overlapping a send with local accumulation. The hand-rolled variant of
    all_gather_shards — useful when interleaving compute per hop (e.g.
    verifying piece checksums shard-by-shard as they arrive)."""
    n = mesh.shape[axis_name]

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=P(axis_name), out_specs=P(axis_name),
        check_vma=False,
    )
    def ring(shard):
        # shard: [chunk, ...] local block. Accumulate n blocks stacked on a
        # new leading axis, receiving the next block from the left neighbor
        # each step (lax.fori_loop keeps the graph compact for any n).
        axis_index = jax.lax.axis_index(axis_name)
        perm = [(i, (i + 1) % n) for i in range(n)]

        def body(i, carry):
            blocks, cur = carry
            blocks = jax.lax.dynamic_update_index_in_dim(
                blocks, cur, (axis_index - i) % n, axis=0)
            cur = jax.lax.ppermute(cur, axis_name, perm)
            return blocks, cur

        blocks0 = jnp.zeros((n,) + shard.shape, shard.dtype)
        blocks, _ = jax.lax.fori_loop(0, n, body, (blocks0, shard))
        # out_specs=P(axis_name) splits the leading axis back across devices,
        # but every device computed the full stack; reshape to [n*chunk,...]
        # and return the slice this device owns post-split.
        return blocks.reshape((-1,) + shard.shape[1:])

    return ring(x)


def ring_all_gather(mesh: Mesh, sharded, axis_name: str = "d"):
    """Ring all-gather returning a sharded stack: logically the full content
    everywhere (each device's output block is the full gather for its ring
    position). Primarily a building block / benchmark for ICI hop patterns;
    use all_gather_shards for the plain collective."""
    return _ring_all_gather_jit(sharded, mesh=mesh, axis_name=axis_name)


@functools.partial(jax.jit,
                   static_argnames=("axis_name", "mesh", "n_chunks"))
def _chunked_ring_all_gather_jit(x, *, mesh: Mesh, axis_name: str,
                                 n_chunks: int):
    """Chunked ring all-gather: the local shard splits into ``n_chunks``
    row slices, each gathered by its own N-1-hop ppermute ring. Chunking
    bounds per-hop message size (the ICI link pipelines hop h of chunk c
    against hop h-1 of chunk c+1 instead of serializing one shard-sized
    transfer per hop) and is the unit the striped broadcast overlaps with
    DCN landing (StripedBroadcast below). Output: the FULL content,
    replicated, rows in global order."""
    n = mesh.shape[axis_name]

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=P(axis_name), out_specs=P(),
        check_vma=False,
    )
    def gather(shard):
        axis_index = jax.lax.axis_index(axis_name)
        perm = [(i, (i + 1) % n) for i in range(n)]
        rows = shard.shape[0]
        bounds = [(rows * c // n_chunks, rows * (c + 1) // n_chunks)
                  for c in range(n_chunks)]
        outs = []
        for r0, r1 in bounds:
            if r1 <= r0:
                continue
            cur = jax.lax.slice_in_dim(shard, r0, r1, axis=0)

            def body(i, carry):
                blocks, c = carry
                blocks = jax.lax.dynamic_update_index_in_dim(
                    blocks, c, (axis_index - i) % n, axis=0)
                c = jax.lax.ppermute(c, axis_name, perm)
                return blocks, c

            blocks0 = jnp.zeros((n,) + cur.shape, shard.dtype)
            blocks, _ = jax.lax.fori_loop(0, n, body, (blocks0, cur))
            outs.append(blocks)
        full = (jnp.concatenate(outs, axis=1) if len(outs) > 1 else outs[0])
        # [n, rows, ...] -> [n*rows, ...]: device i's shard occupied global
        # rows [i*rows, (i+1)*rows), so the flatten restores global order.
        return full.reshape((-1,) + shard.shape[1:])

    return gather(x)


def chunked_ring_all_gather(mesh: Mesh, sharded, axis_name: str = "d",
                            n_chunks: int = 4):
    """Every device ends with the full content (replicated), gathered as
    ``n_chunks`` independent ppermute rings — the ICI leg of the striped
    slice broadcast. Identical result to all_gather_shards; the chunking
    exists for hop pipelining and DCN/ICI overlap."""
    n_chunks = max(1, int(n_chunks))
    return _chunked_ring_all_gather_jit(sharded, mesh=mesh,
                                        axis_name=axis_name,
                                        n_chunks=n_chunks)


class StripedBroadcast:
    """Pipelined striped broadcast driver: DCN landing overlapped with ICI
    spread.

    Each host of an S-host slice DCN-fetches 1/S of the content (its
    stripe); the fabric completes the copy. Per stripe chunk k the caller
    ``feed``s the freshly landed host bytes: feed scatters the chunk onto
    the mesh and DISPATCHES its ring all-gather without blocking — jax
    dispatch is async, so the ICI spread of chunk k runs while the daemon
    lands chunk k+1 from the network. ``result()`` materializes the
    replicated content with one blocking concatenate at the end.

    Feeding order is the content order: chunk rows concatenate in feed
    sequence. On the virtual CPU mesh (tests/dryrun) the same code path
    executes end to end, minus the chip."""

    def __init__(self, mesh: Mesh, axis_name: str = "d", n_chunks: int = 1):
        self.mesh = mesh
        self.axis_name = axis_name
        self.n_chunks = max(1, int(n_chunks))
        self._parts: list[tuple] = []   # (gathered jax.Array, valid_rows)

    def feed(self, host_chunk: np.ndarray) -> None:
        """Scatter one stripe chunk across the slice and dispatch its
        gather (non-blocking). The leading dim is padded up to a mesh
        multiple; result() trims the pad."""
        n = self.mesh.shape[self.axis_name]
        arr = np.asarray(host_chunk)
        rows = arr.shape[0]
        pad = (-rows) % n
        if pad:
            arr = np.concatenate(
                [arr, np.zeros((pad,) + arr.shape[1:], arr.dtype)])
        sharded = scatter_shards(self.mesh, arr, self.axis_name)
        gathered = _chunked_ring_all_gather_jit(
            sharded, mesh=self.mesh, axis_name=self.axis_name,
            n_chunks=self.n_chunks)
        self._parts.append((gathered, rows))

    def result(self):
        """Block for every dispatched gather and return the replicated
        content (device array, rows in feed order)."""
        if not self._parts:
            raise ValueError("StripedBroadcast.result() before any feed()")
        trimmed = [g[:rows] for g, rows in self._parts]
        out = (jnp.concatenate(trimmed, axis=0) if len(trimmed) > 1
               else trimmed[0])
        return jax.block_until_ready(out)


def bitcast_landed_bytes(buffer, dtype, shape):
    """Reinterpret fabric-landed uint8 HBM bytes as a checkpoint tensor
    without leaving the device (e.g. bf16 weights)."""
    return bitview.typed_view(buffer, 0, dtype, shape)
