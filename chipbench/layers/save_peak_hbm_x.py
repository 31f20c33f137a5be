"""Checkpoint save / resume: bytes in use on the chip as the save's handle
comes back, the live state and its snapshot beside it, over the state's
bytes as a file (x), as the runtime counts them. Median per operation."""

from layers import save_events


def read(run):
    return save_events.median(
        op.save_hbm / op.nbytes for op in run.ops
        if getattr(op, "save_hbm", None) and op.nbytes)
