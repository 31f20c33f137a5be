"""Event loop: the cyclic collector on the loop's thread: the summed ``gc`` ms
of the ``loop_acct`` slices inside the union of the operations (the pauses of
the collections that ran on that thread, by ``gc.callbacks``; part of the busy
time), over the operations finished."""

from layers import loop_events


def read(run):
    slices = loop_events.slices(run)
    if slices is None:
        return None
    return loop_events.per_operation(run, (s[3] for s in slices))
