"""Scheduling: register -> first schedule, median per operation (ms),
from the peer's flight events."""

import spans


def read(run):
    waits = [spans.sched_wait(op) for op in run.ops]
    return spans.median_ms(w[0][1] - w[0][0] for w in waits if w)
