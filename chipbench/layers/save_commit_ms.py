"""Checkpoint save / resume: the piece commits on the worker threads, each its
host sums held equal to the device's, its digest and its write
(``save_commit``, ms): the union of the spans. Median per operation."""

from layers import save_events


def read(run):
    return save_events.median_union_ms(run, "save_commit")
