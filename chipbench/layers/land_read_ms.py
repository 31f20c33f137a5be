"""Store read-back + host staging: the landing thread's time inside
``store.read_piece`` (``sink_read`` spans), summed per operation, median
per operation (ms)."""

from layers import sink_events


def read(run):
    return sink_events.median_of_sums(run, "sink_read")
