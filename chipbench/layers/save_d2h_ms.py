"""Checkpoint save / resume: the device -> host copies of the words, a group of
pieces each (``save_d2h``, ms): the union of the spans, because the v5e's
trace has no transfer line. Median per operation."""

from layers import save_events


def read(run):
    return save_events.median_union_ms(run, "save_d2h")
