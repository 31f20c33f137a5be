"""Hot-swap: the bytes a swap fetched as ranged tasks over the version's
content (%), by the delta task's own accounting; what the versions differ in
is the floor, what the chunking adds around it the rest. Median per
operation."""

from layers import swap_events


def read(run):
    return swap_events.median(
        100.0 * op.swap["stats"]["fetched_bytes"] / op.nbytes
        for op in run.ops
        if getattr(op, "swap", None) and op.nbytes
        and "fetched_bytes" in op.swap["stats"])
