"""Checkpoint save / resume: the whole-content sha256 on the digest's thread,
following the pieces (``save_digest``, ms): the union of its spans, a group
each. Median per operation."""

from layers import save_events


def read(run):
    return save_events.median_union_ms(run, "save_digest")
