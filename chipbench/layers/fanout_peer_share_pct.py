"""Fan-out between peers: of the bytes the hosts received, the share
that came from fellow hosts and not from the seed (or the origin), from every
host's ``task_sources``; median per operation (%). One host fed by the seed
and the others by their mates would read 87.5."""

from layers import fanout_events


def of_operation(op) -> float | None:
    hosts = fanout_events.flights(op)
    if hosts is None:
        return None
    took = [fanout_events.sources(flight) for flight in hosts]
    if any(t is None for t in took):
        return None
    total = sum(t["seed_bytes"] + t["peer_bytes"] + t["origin_bytes"]
                for t in took)
    return 100.0 * sum(t["peer_bytes"] for t in took) / total \
        if total else None


def read(run):
    return fanout_events.median_per_operation(run, of_operation)
