"""Hot-swap: the ms in which a reused chunk was being copied out of the base
store and hashed (the union of an operation's ``delta_reuse`` spans), median
per operation."""

from layers import swap_events


def read(run):
    return swap_events.median_union_ms(run, "delta_reuse")
