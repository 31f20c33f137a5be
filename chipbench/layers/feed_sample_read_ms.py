"""Dataset feed: a sample's read, launched by the read-ahead -> its spans
resolved -> its ranged task(s) done -> the bytes in the pooled buffer
(``feed_sample``'s ``aux``), the median over the window's samples (ms).
``readahead`` of them are in flight at once, so a batch waits about
``batch_size / readahead`` of these."""

from layers import feed_events


def read(run):
    return feed_events.median(
        aux for _, aux, _, _ in feed_events.events(run, "feed_sample"))
