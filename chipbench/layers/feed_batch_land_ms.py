"""Dataset feed: a batch's landing, its first record staged (``feed_batch``'s
end less its ``aux``) -> the ``(batch, record_bytes)`` array ready on the chip
(the operation's end), the median per operation (ms): the records' copies
into the staging rows, their checksums, the puts, the assembly with its
verification and the byte view."""

from layers import feed_events


def read(run):
    return feed_events.median(
        (op.t1 - (t - aux / 1000.0)) * 1000.0
        for t, aux, _, op in feed_events.events(run, "feed_batch")
        if op.t0 <= t <= op.t1)
