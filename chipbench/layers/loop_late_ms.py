"""Event loop: the loop due and not running: the summed ``late`` ms of the
``loop_acct`` slices inside the union of the operations (a due timer waited
that long while the loop still sat in ``select``: no GIL or no core for its
thread; ``epoll``'s millisecond rounding is in it, under 1 ms a timer), over
the operations finished. A held loop is busy; a starved one is late."""

from layers import loop_events


def read(run):
    slices = loop_events.slices(run)
    if slices is None:
        return None
    return loop_events.per_operation(run, (s[2] for s in slices))
