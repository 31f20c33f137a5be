"""The hot-swap's spans in an operation's flight events, shared by the
readers beside this file (it reads no metric itself).

``download_delta`` stamps the delta task's flight, each span ONE event at
its end with ``aux`` = its ms: ``swap_plan`` (twice: the resolver's manifests
and plan as the task starts, the device half's), the resolver's
``delta_reuse`` / ``delta_fetch`` (one a chunk), ``swap_stage``,
``swap_assemble``, ``swap_verify``, ``swap_views``, ``swap_flip``. A program
older than those events stamps none, and every reader then reads nothing.
A span's summed ms per operation is ``sink_events.median_of_sums``.
"""

import statistics

COPY_PROGRAM = "_swap_copy_jit"
VERIFY_PROGRAM = "_words_checksums_jit"


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


def program_seconds_per_operation(run, program: str):
    """Summed device seconds of ``program``'s runs inside the operations,
    per operation, from the profiler's trace."""
    import reduce_trace

    if run.trace is None or not run.ops:
        return None
    seconds = reduce_trace.program_seconds(run.trace, (program,), run.windows)
    return seconds / len(run.ops) if seconds > 0 else None


def content_bytes(run) -> float:
    return sum(op.nbytes for op in run.ops) / len(run.ops)
