"""Checkpoint save / resume: ``load_safetensors`` of the resumed landing ->
every tensor ready (ms): 86 % of the bytes are 4-byte tensors, which the
flat path cuts. Median per operation."""

from layers import save_events


def read(run):
    return save_events.median(
        (op.views_span[1] - op.views_span[0]) * 1000.0 for op in run.ops
        if getattr(op, "t_lost", None) and op.views_span)
