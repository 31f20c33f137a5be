"""Piece transfer: from the scheduler's answer (``scheduled``) to the
first announcement by a parent that it holds a piece (``parent_pieces``):
the seed's back-to-source start as the peer sees it, median per operation
(ms). What is left of the wait for the first piece, first
``parent_pieces`` -> first ``request``, is the peer's own dispatch: it is
printed, not reported."""

import statistics

from layers import sink_events


def read(run):
    waits, dispatch = [], []
    for op in run.ops:
        scheduled = sink_events.first(op, "scheduled")
        if scheduled is None:
            continue
        announced = sink_events.first(op, "parent_pieces", scheduled)
        if announced is None:
            continue
        waits.append((announced - scheduled) * 1000.0)
        request = sink_events.first(op, "request", announced)
        if request is not None:
            dispatch.append((request - announced) * 1000.0)
    if dispatch:
        print("[chipbench] first parent_pieces -> first request (the "
              "peer's own dispatch), ms by operation: "
              + ", ".join(f"{d:.1f}" for d in dispatch), flush=True)
    return statistics.median(waits) if waits else None
