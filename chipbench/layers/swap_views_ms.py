"""Hot-swap: the typed tensors cut from the new generation's words and ready
(``swap_views``, ms), median per operation."""

from layers import sink_events


def read(run):
    return sink_events.median_of_sums(run, "swap_views")
