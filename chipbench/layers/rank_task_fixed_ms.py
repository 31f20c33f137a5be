"""Ranged pull: what a ranged task costs beside its bytes: the task's wall
time outside its pieces, ``register`` -> first ``request`` plus last
``hbm_landed`` -> ``task_done``, the median over an operation's ranged
tasks (the header's too), then the median per operation (ms). 26 tasks an
operation multiply it, three at a time."""

import statistics

from layers import ranged_events


def fixed_ms(flight) -> float | None:
    """One task's two stretches (ms), or None where an end is missing."""
    at = {}
    for t, name, _, _ in flight:
        if name in ("register", "request"):
            at.setdefault(name, t)        # the first
        elif name in ("hbm_landed", "task_done"):
            at[name] = t                  # the last
    if len(at) < 4 or at["request"] < at["register"] \
            or at["task_done"] < at["hbm_landed"]:
        return None
    return ((at["request"] - at["register"])
            + (at["task_done"] - at["hbm_landed"])) * 1000.0


def of_operation(op) -> float | None:
    costs = [c for c in map(fixed_ms, ranged_events.tasks(op))
             if c is not None]
    return statistics.median(costs) if costs else None


def read(run):
    return ranged_events.median_per_operation(run, of_operation)
