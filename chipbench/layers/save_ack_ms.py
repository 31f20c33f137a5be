"""Checkpoint save / resume: ``save_from_device`` called -> the
acknowledgement (the end of the ``save_replicated`` span, ms after the
operation began). Median per operation."""

from layers import save_events, sink_events


def read(run):
    return save_events.median(
        None if (t := sink_events.first(op, "save_replicated")) is None
        else (t - op.t0) * 1000.0 for op in run.ops)
