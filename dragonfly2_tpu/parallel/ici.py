"""ICI shard redistribution: the mesh plan for intra-slice piece spread.

The fabric's TPU-side collective layer: one host's daemon lands checkpoint
bytes in its local devices' HBM; this plan spreads them across the slice
over ICI using XLA's collective (all_gather under shard_map), never the
NIC. Designed per the scaling-book recipe: pick a mesh, annotate shardings,
let XLA insert the collectives.

The plan is jit-compiled once per (mesh, shape) and works identically on a
virtual CPU mesh (tests / dryrun) and a real TPU slice.
"""

from __future__ import annotations

import functools

import jax
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


def make_mesh(n_devices: int | None = None, axis_name: str = "d") -> Mesh:
    """1-D mesh over the slice's devices (the ICI ring)."""
    devices = jax.devices()
    if n_devices is not None:
        devices = devices[:n_devices]
    return Mesh(np.array(devices), (axis_name,))


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
    (``HBMSink.replicate``): of two ppermute rings, since deleted, and the
    runtime's own ``device_put`` to a replicated sharding it was the fastest
    on four chips of a v5e at a checkpoint shard's size (PERF.md section 6,
    PR 31)."""
    return _all_gather_jit(sharded, mesh=mesh, axis_name=axis_name)
