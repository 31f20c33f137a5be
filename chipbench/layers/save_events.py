"""The save's spans and counters in an operation, shared by the readers of
the layer "checkpoint save / resume" beside this file (it reads no metric
itself).

``save_from_device`` stamps the persistent cache task's flight, each span
ONE event at its end with ``aux`` = its ms: ``save_pack``, ``save_snapshot``
(the call -> the handle returned), then behind the caller ``save_d2h`` (a
group of pieces), ``save_commit`` (a piece, on a worker thread),
``save_digest`` (a group, on the digest's thread), ``save_replicated``
(``Finished`` sent -> the scheduler's answer). The driver
(``drivers/closed_loop_save.py``) keeps beside them ``op.counted`` (the
program's counters over the operation), ``op.t_lost`` (the host's copy gone)
and ``op.save_hbm`` (bytes in use on the chip as the handle came back: the
state and its snapshot). A program older than those events stamps none, and
every reader then reads nothing.
"""

import statistics

PACK_PROGRAMS = ("_save_pack",)


def median(values):
    values = [v for v in values if v is not None]
    return statistics.median(values) if values else None


def union_ms(op, name: str):
    """The ms in which at least one ``name`` span of the operation ran."""
    import reduce_trace

    spans = [(t - aux / 1000.0, t) for t, event, _, aux in op.flight
             if event == name]
    return reduce_trace.total(spans) * 1000.0 if spans else None


def median_union_ms(run, name: str):
    return median(union_ms(op, name) for op in run.ops)


def pack_seconds_per_operation(run):
    """Summed device seconds of the programs that place the tensors in the
    file's words and checksum them, per operation, from the trace."""
    import reduce_trace

    if run.trace is None or not run.ops:
        return None
    seconds = reduce_trace.program_seconds(run.trace, PACK_PROGRAMS,
                                           run.windows)
    return seconds / len(run.ops) if seconds > 0 else None


def content_bytes(run) -> float:
    return sum(op.nbytes for op in run.ops) / len(run.ops)
