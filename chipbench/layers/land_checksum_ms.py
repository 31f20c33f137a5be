"""Store read-back + host staging: the landing thread's time in the host
checksum of each piece (``sink_checksum`` spans around ``checksum_numpy``
in ``HBMSink.land_piece``), summed per operation, median per operation
(ms)."""

from layers import sink_events


def read(run):
    return sink_events.median_of_sums(run, "sink_checksum")
