"""The landing thread's spans in an operation's flight events, shared by
the readers beside this file (it reads no metric itself).

The program's device sink stamps each step of its one ``df-device-sink``
thread as ONE event at the step's end, ``aux`` = the step's duration in
ms: ``sink_land`` (a piece's work on the thread) > ``sink_read``,
``sink_checksum``, ``sink_stage``, ``sink_put``; ``sink_finalize`` > the
backfill's ``sink_land``s, ``sink_assemble`` > ``sink_compile``. A program
older than those events stamps none, and every reader then reads nothing.
"""

import statistics


def summed_ms(op, name: str) -> float | None:
    """The summed durations (ms) of the operation's ``name`` spans, or
    None where it stamped none."""
    found = [aux for _, event, _, aux in op.flight if event == name]
    return sum(found) if found else None


def median_of_sums(run, name: str) -> float | None:
    """Median per operation of the per-operation sum (ms)."""
    sums = [s for s in (summed_ms(op, name) for op in run.ops)
            if s is not None]
    return statistics.median(sums) if sums else None


def first(op, name: str, after: float = float("-inf")) -> float | None:
    """When the operation's first ``name`` event at or after ``after``
    fell (perf_counter seconds)."""
    return next((t for t, event, _, _ in op.flight
                 if event == name and t >= after), None)
