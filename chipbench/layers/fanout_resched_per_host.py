"""Fan-out between peers: how often the schedule changed under a host:
``reschedule`` (the host asked again, starved) plus ``sched_push`` (the
scheduler pushed parents mid-task) events over the hosts, a host;
median per operation."""

from layers import fanout_events


def of_operation(op) -> float | None:
    hosts = fanout_events.flights(op)
    if hosts is None:
        return None
    return sum(name in ("reschedule", "sched_push")
               for flight in hosts for _, name, *_ in flight) / len(hosts)


def read(run):
    return fanout_events.median_per_operation(run, of_operation)
