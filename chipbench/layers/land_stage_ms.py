"""Store read-back + host staging: the landing thread's time building a
batch on the host (``sink_stage`` spans in ``HBMSink.flush``: sort, the
zero-filled stack, the row copies, up to the ``device_put``), summed per
operation, median per operation (ms)."""

from layers import sink_events


def read(run):
    return sink_events.median_of_sums(run, "sink_stage")
