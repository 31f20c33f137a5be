"""The layer "ranged pull" in an operation's flight events, shared by the
``rank_*`` readers beside this file (it reads no metric itself).

An operation of the cell ``rank-cold`` is one ``download_sharded``: the
header's ranged task and one ranged task a coalesced span. Its driver
(``drivers/closed_loop_ranged.py``) keeps each task's flight events apart
in ``op.ranged`` (one ``{"flight": [(t, name, piece, aux)], ...}`` a task,
the header's first) beside the merged ``op.flight``. The program stamps
``admit_wait`` on every device pull's flight as the task starts (``aux`` =
ms it stood at the sink's admission), and ``shard_plan`` / ``shard_views``
on the header task's flight (``aux`` = ms; ``piece`` = ranged tasks planned
/ tensors returned). An operation of another driver has no ``op.ranged``,
and a program older than those events stamps none: every reader then reads
nothing.
"""

import statistics


def tasks(op) -> list:
    """The flights of the operation's ranged tasks, one list a task."""
    return [task["flight"] for task in getattr(op, "ranged", None) or []]


def median_per_operation(run, reading) -> float | None:
    """Median over the operations of ``reading(op)``, which is None where
    the operation has nothing to read."""
    values = [v for v in (reading(op) for op in run.ops) if v is not None]
    return statistics.median(values) if values else None


def summed_aux(op, name: str) -> float | None:
    """The summed ``aux`` of the ``name`` events over all the operation's
    ranged tasks, or None where none stamped one."""
    found = [aux for flight in tasks(op) for _, event, _, aux in flight
             if event == name]
    return sum(found) if found else None
