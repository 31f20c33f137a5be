"""Fan-out between peers: the last host's ``task_done`` less the first
host's; median per operation (ms). What a job behind a barrier loses to its
slowest host."""

from layers import fanout_events


def of_operation(op) -> float | None:
    hosts = fanout_events.flights(op)
    if hosts is None:
        return None
    done = [fanout_events.last(flight, "task_done") for flight in hosts]
    if any(t is None for t in done):
        return None
    return (max(done) - min(done)) * 1000.0


def read(run):
    return fanout_events.median_per_operation(run, of_operation)
