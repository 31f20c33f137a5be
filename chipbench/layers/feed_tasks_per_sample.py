"""Dataset feed: ranged tasks a sample, as the program counted them
(``feed_sample``'s ``tasks``), over the window's samples. 1.0 where a
sample's members coalesce into one span."""

from layers import feed_events


def read(run):
    samples = feed_events.events(run, "feed_sample")
    tasks = feed_events.summed(run, "feed_sample", "tasks")
    return None if tasks is None else tasks / len(samples)
