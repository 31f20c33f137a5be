"""Piece transfer: the union of the request -> landed intervals of an
operation's pieces, median per operation (ms). On loopback: seed and peer
share the machine."""

import spans
import reduce_trace as trace


def read(run):
    per_op = [trace.total(spans.transfers(op)) for op in run.ops]
    return spans.median_ms(t for t in per_op if t > 0)
