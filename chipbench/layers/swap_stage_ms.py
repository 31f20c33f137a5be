"""Hot-swap: the words no reused run holds read from the verified landing
into staging slabs and put on the device (``swap_stage``, ms), median per
operation."""

from layers import sink_events


def read(run):
    return sink_events.median_of_sums(run, "swap_stage")
