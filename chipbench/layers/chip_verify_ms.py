"""Collectives: the verification of every chip's copy after the fan-out
(``sink_verify_chips``: each chip's per-piece checksums of its own copy
dispatched -> all compared with the host's), summed per operation, median
(ms). A program that verifies nothing off the landing chip stamps none, and
this reads nothing."""

from layers import sink_events


def read(run):
    return sink_events.median_of_sums(run, "sink_verify_chips")
