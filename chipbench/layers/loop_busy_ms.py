"""Event loop: what an operation costs the peer's loop thread: the busy ms
(``select`` exit -> the next ``select`` entry, the ready handles of each turn)
of the ``loop_acct`` slices, each clipped to the union of the operations, over
the operations finished. Against an operation's length it says how much of it the
one thread that runs every coroutine was running Python (or held off the core
inside a turn), and how much it sat in ``select`` waiting for threads, the
wire or the device."""

from layers import loop_events


def read(run):
    slices = loop_events.slices(run)
    if slices is None:
        return None
    return loop_events.per_operation(run, (s[0] for s in slices))
