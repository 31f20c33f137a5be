"""Fan-out between peers: how long the SEED was sending pieces of the task to
the hosts: the union of its ``upload_serve`` spans (``t`` − ``aux`` ms, ``t``;
stamped at each send's end by the upload server, the native one too); median
per operation (ms)."""

import reduce_trace as trace
from layers import fanout_events


def of_operation(op) -> float | None:
    seed = fanout_events.flights(op, seed=True)
    if seed is None:
        return None
    sends = [(t - aux / 1000.0, t) for t, name, _, aux, _ in seed[0]
             if name == "upload_serve"]
    return trace.total(sends) * 1000.0 if sends else None


def read(run):
    return fanout_events.median_per_operation(run, of_operation)
