"""Dataset feed: the share of the operations' time that the feed's consumer
side stood waiting for samples (``feed_wait``'s ``aux``, summed), the rest
being the batch's landing (%). Near 100: the reads set the pace, and the
device path is idle most of an operation."""

from layers import feed_events


def read(run):
    waited = [aux for _, aux, _, _ in feed_events.events(run, "feed_wait")]
    seconds = sum(op.t1 - op.t0 for op in run.ops)
    if not waited or seconds <= 0:
        return None
    return 100.0 * sum(waited) / 1000.0 / seconds
