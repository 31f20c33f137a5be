"""Hot-swap: the completion digest's tail (``verified``, ms): the delta
task's ``verify_start`` -> ``verified``, what the whole-object sha256 of the
landing still had to do once its last piece was written (the prefix
hasher's drain, or a full re-hash); summed per operation, median."""

from layers import sink_events


def read(run):
    return sink_events.median_of_sums(run, "verified")
