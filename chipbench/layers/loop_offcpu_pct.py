"""Event loop: the share (%) of the loop thread's turns spent off a core:
100 x (1 - cpu / busy) over the same ``loop_acct`` slices as ``loop_busy_ms``,
cpu being the thread's own CPU time (``time.thread_time_ns``) inside its
turns. A turn that holds the loop and is not on a core stands in a blocking
call, waits for the GIL or a page, or was descheduled. Nothing where the loop
ran no turn."""

from layers import loop_events


def read(run):
    slices = loop_events.slices(run)
    busy = sum(s[0] for s in slices or ())
    if busy <= 0:
        return None
    return 100.0 * (1.0 - sum(s[1] for s in slices) / 1000.0 / busy)
