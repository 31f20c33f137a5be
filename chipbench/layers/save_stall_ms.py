"""Checkpoint save / resume: the caller's stall, ``save_from_device`` called
-> its handle returned, the snapshot taken (``save_snapshot``, ms): what
blocks training (ByteCheckpoint's "checkpoint stall"). Median per operation."""

from layers import sink_events


def read(run):
    return sink_events.median_of_sums(run, "save_snapshot")
