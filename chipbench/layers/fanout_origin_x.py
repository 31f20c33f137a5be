"""Fan-out between peers: the program's own count of the guarantee: the bytes
the seed took from the origin plus any host's back-to-source bytes
(``task_sources`` of all nine daemons), over the content ONCE; median per
operation (x). 1.0 is each byte from the origin once for all the hosts."""

from layers import fanout_events


def of_operation(op) -> float | None:
    hosts = fanout_events.flights(op)
    seed = fanout_events.flights(op, seed=True)
    if hosts is None or seed is None or not op.nbytes:
        return None
    took = [fanout_events.sources(flight) for flight in hosts + seed]
    if any(t is None for t in took):
        return None
    return sum(t["origin_bytes"] for t in took) / op.nbytes


def read(run):
    return fanout_events.median_per_operation(run, of_operation)
