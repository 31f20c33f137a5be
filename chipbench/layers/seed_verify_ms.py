"""Completion digest: the SEED's ``verify_start`` -> ``verified`` as the
seed itself timed it, carried to the peer by the sync stream's ``done`` and
stamped there as ``parent_verified`` (``aux`` = the seed's ms): the drain of
the seed's prefix hasher after its last piece, or its full re-hash. Per
operation, median (ms). Beside ``cert_wait_ms`` it says how much of the
peer's wait was the seed's verify and how much the ``done``'s way."""

from layers import sink_events


def read(run):
    return sink_events.median_of_sums(run, "parent_verified")
