"""Ranged pull: the summed waits of an operation's ranged tasks at the
sink's admission (``admit_wait``, stamped on each task's flight as the task
starts, ``aux`` = ms since it asked): 25 tasks through ``max_tasks`` - 1
slots. The waits overlap, so the sum exceeds the operation; median per
operation (ms)."""

from layers import ranged_events


def read(run):
    return ranged_events.median_per_operation(
        run, lambda op: ranged_events.summed_aux(op, "admit_wait"))
