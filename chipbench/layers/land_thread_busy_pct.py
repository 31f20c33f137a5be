"""Store read-back + host staging: the share (%) of the time between an
operation's first ``hbm_start`` and its last ``hbm_landed`` in which the
one landing thread was at work on a piece (``sink_land`` spans, clipped to
that interval), median per operation. Near 100: the thread, not the wire,
sets the pace; well under: pieces arrive more slowly than it lands them."""

import statistics

import reduce_trace as trace
from layers import sink_events


def read(run):
    shares = []
    for op in run.ops:
        start = sink_events.first(op, "hbm_start")
        ends = [t for t, event, _, _ in op.flight if event == "hbm_landed"]
        lands = [(t - aux / 1000.0, t) for t, event, _, aux in op.flight
                 if event == "sink_land"]
        if start is None or not ends or not lands or ends[-1] <= start:
            continue
        busy = trace.total(trace.clip(lands, [(start, ends[-1])]))
        shares.append(100.0 * busy / (ends[-1] - start))
    return statistics.median(shares) if shares else None
