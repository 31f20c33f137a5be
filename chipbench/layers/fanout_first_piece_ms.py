"""Fan-out between peers: time to the first piece, the median over the
hosts of the request (all ask as the operation starts) -> the host's first
``landed``; median per operation (ms). ``BASELINE.json``'s
time-to-first-piece."""

import statistics

from layers import fanout_events


def of_operation(op) -> float | None:
    hosts = fanout_events.flights(op)
    if hosts is None:
        return None
    firsts = [fanout_events.first(flight, "landed") for flight in hosts]
    if any(t is None for t in firsts):
        return None
    return statistics.median(t - op.t0 for t in firsts) * 1000.0


def read(run):
    return fanout_events.median_per_operation(run, of_operation)
