"""Store read-back + host staging: the share (%) of the window's busy time
in which the one landing thread was at work for ANY operation. The union,
over all the window's operations, of the on-thread intervals ``sink_land``
and ``sink_finalize`` (``t - aux / 1000`` to ``t``; a backfill's
``sink_land``s lie inside their ``sink_finalize``), clipped to the union
of the operations, over that union's length. Near 100: the one thread is
the limit, whatever each sink does; well under: the clients, the event
loop or the wire leave it idle. ``land_thread_busy_pct`` is one
operation's view between its first and last piece; this is the thread's,
over every task at once. Nothing where no operation stamped either span."""

import reduce_trace as trace

ON_THREAD = ("sink_land", "sink_finalize")


def read(run):
    at_work = [(t - aux / 1000.0, t) for op in run.ops
               for t, event, _, aux in op.flight if event in ON_THREAD]
    in_flight = [(op.t0, op.t1) for op in run.ops]
    seconds = trace.total(in_flight)
    if not at_work or seconds <= 0:
        return None
    return 100.0 * trace.total(trace.clip(at_work, in_flight)) / seconds
