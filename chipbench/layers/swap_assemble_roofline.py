"""Hot-swap: the assembly against the memory roofline (%): the least time
the chip could take to make the new generation's words, over the device time
of the swap's copy program per operation. Memory-bound: a masked copy."""

from layers import swap_events


def least_bytes(content_bytes: float) -> float:
    """What any assembly must move in HBM: every content byte read once
    (from the live words or a staged slab) and written once. The program's
    form moves more (the new buffer is zeroed first, and a step reads the
    block it overwrites for the mask), which shows as a lower share."""
    return 2 * content_bytes


def read(run):
    took = swap_events.program_seconds_per_operation(
        run, swap_events.COPY_PROGRAM)
    if took is None:
        return None
    return 100.0 * least_bytes(swap_events.content_bytes(run)) \
        / run.peaks["hbm_bytes_per_s"] / took
