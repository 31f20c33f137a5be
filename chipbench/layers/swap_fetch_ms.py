"""Hot-swap: the ms in which the landing waited for a fetched span's ranged
task (the union of an operation's ``delta_fetch`` spans), median per
operation."""

from layers import swap_events


def read(run):
    return swap_events.median_union_ms(run, "delta_fetch")
