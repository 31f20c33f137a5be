"""Checkpoint save / resume: this host's copy gone -> every tensor of the
resumed state typed and ready (ms): the P2P-only pull from the replica, its
landing and its views. Median per operation."""

from layers import save_events


def read(run):
    return save_events.median(
        (op.t1 - op.t_lost) * 1000.0 for op in run.ops
        if getattr(op, "t_lost", None))
