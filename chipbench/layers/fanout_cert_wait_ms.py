"""Fan-out between peers: the longest stay of any of the hosts in
``conductor._await_certification`` (a host's summed ``cert_wait``), where the
certifier may itself be a host that waits for the seed's ``done``; median per
operation (ms). A host that stamped none waited 0."""

from layers import fanout_events


def of_operation(op) -> float | None:
    hosts = fanout_events.flights(op)
    if hosts is None:
        return None
    return max(sum(aux for _, name, _, aux, _ in flight
                   if name == "cert_wait") for flight in hosts)


def read(run):
    return fanout_events.median_per_operation(run, of_operation)
