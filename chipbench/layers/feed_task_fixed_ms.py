"""Dataset feed: of a sample's read, what is not moving its bytes: the read
less what its task spent moving them (``move``: a local import's reads and
writes, a piece's transfer from a peer) and less the read from the task's
store into the pooled buffer (``read``); the median over the window's samples
(ms). What is left is a task's id, store, flight and conductor, its register
with the scheduler and the scheduler's answer, its metadata and its end."""

from layers import feed_events


def read(run):
    return feed_events.median(
        aux - float(f["move"]) - float(f["read"])
        for _, aux, f, _ in feed_events.events(run, "feed_sample")
        if "move" in f and "read" in f)
