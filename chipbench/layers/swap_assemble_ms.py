"""Hot-swap: summed device time of the swap's copy program
(``_swap_copy_jit``: the live generation's reused words, then each staging
slab's) per operation (ms), from the profiler's trace."""

from layers import swap_events


def read(run):
    s = swap_events.program_seconds_per_operation(
        run, swap_events.COPY_PROGRAM)
    return None if s is None else s * 1000.0
