"""Collectives: the fan-out of a landing placed whole on every chip of a
mesh (``sink_replicate``: the fan-out dispatched on the landing thread ->
every chip's copy ready, host clock), summed per operation, median (ms). A
program that places nothing on a mesh stamps none, and this reads
nothing."""

from layers import sink_events


def read(run):
    return sink_events.median_of_sums(run, "sink_replicate")
