"""The layer "sharded landing" in an operation's readings, shared by the
``global_*`` readers beside this file (it reads no metric itself).

An operation of the cell ``host-reland-ep4`` is one ``download_global``: the
header's ranged task and one ranged task a run of neighbours that the same
chips want. Its driver (``drivers/closed_loop_global.py``) keeps each task's
flight events apart in ``op.ranged`` (``{"flight": [(t, name, piece, aux)],
"chips": [ids, the landing chip first], ...}`` a task, the header's first),
the program's counters over the operation in ``op.counts``, each chip's
resident bytes in ``op.chip_resident``, and every chip's peak bytes in
``run.cell.chip_peaks``. The program stamps ``shard_plan`` / ``shard_views``
on the header task's flight (``aux`` = ms; ``piece`` = ranged tasks planned /
tensors returned) and ``device_pull`` on every task's as its sink is in the
caller's hand, fanned out and verified where several chips want it (``aux`` =
ms since the task's admission; ``piece`` = the chip it landed on). An
operation of another driver has none of these, and a program older than the
events stamps none: every reader then reads nothing.
"""

import statistics

from layers import ranged_events

median_per_operation = ranged_events.median_per_operation
summed_aux = ranged_events.summed_aux

# The landing thread's spans of a task: its queue for the thread, the
# landing, the fan-out and the verification on every chip.
LANDING = ("sink_wait", "sink_finalize", "sink_replicate",
           "sink_verify_chips")


def rows(op) -> list:
    """The operation's ranged tasks that say which chips hold them."""
    return [task for task in getattr(op, "ranged", None) or []
            if task.get("chips")]


def pull(flight):
    """(start, end, ms) of a task's last ``device_pull``, or None."""
    found = [(t - aux / 1000.0, t, aux) for t, name, _, aux in flight
             if name == "device_pull"]
    return found[-1] if found else None


def fixed_ms(flight) -> float | None:
    """A task's time in ``download_to_device`` less the landing thread's
    spans that fall inside it (ms)."""
    span = pull(flight)
    if span is None:
        return None
    start, end, ms = span
    return ms - sum(aux for t, name, _, aux in flight
                    if name in LANDING and start <= t <= end + 1e-6)


def chip_done(op) -> dict:
    """chip -> when the last task whose words lie on it was in hand
    (perf_counter seconds)."""
    done: dict = {}
    for task in rows(op):
        span = pull(task["flight"])
        if span is not None:
            for chip in task["chips"]:
                done[chip] = max(done.get(chip, span[1]), span[1])
    return done


def median(values) -> float | None:
    values = [v for v in values if v is not None]
    return statistics.median(values) if values else None
