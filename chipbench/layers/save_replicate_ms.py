"""Checkpoint save / resume: ``Finished`` sent -> the scheduler's answer that
the replica is made and verified (``save_replicated``, ms): the other host's
P2P pull of the whole file, its piece digests and its sha256. Median per
operation."""

from layers import sink_events


def read(run):
    return sink_events.median_of_sums(run, "save_replicated")
