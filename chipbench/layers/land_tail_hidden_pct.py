"""Store read-back + host staging: the share (%) of the finalizes' tails
that ran beside another sink's host pass. A finalize hands its sink over
with the ``device_put`` of its last stack; its tail (the wait for the
sink's puts, the assembly's dispatch, the fetched checksums, their
comparison) runs off the one landing thread and stamps ``sink_tail``: ONE
event as the tail ends, ``aux`` = the ms since the hand-over. Over all the
window's operations: the part of the summed ``sink_tail`` intervals
(``t - aux / 1000`` to ``t``) that lies inside the union of the window's
``sink_land`` intervals (the thread at a host pass, any task's), over the
summed tails, x 100. 0 where one client lands one sink (no other pass to
hide behind); near 100 where a successor always stands queued at the
thread. A program older than the event stamps none, and this reads
nothing."""

import reduce_trace as trace


def read(run):
    spans = {"sink_tail": [], "sink_land": []}
    for op in run.ops:
        for t, event, _, aux in op.flight:
            if event in spans:
                spans[event].append((t - aux / 1000.0, t))
    tails = spans["sink_tail"]
    summed = sum(end - start for start, end in tails)
    if summed <= 0:
        return None
    passes = trace.union(spans["sink_land"])
    hidden = sum(trace.total(trace.clip([tail], passes)) for tail in tails)
    return 100.0 * hidden / summed
