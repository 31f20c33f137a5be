"""Hot-swap: the flip gate (``swap_verify``, ms): the host's sums of every
piece of the landing, the device's of every piece of the new words, the
comparison; median per operation."""

from layers import sink_events


def read(run):
    return sink_events.median_of_sums(run, "swap_verify")
