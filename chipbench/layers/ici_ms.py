"""Collectives: device time of the fan-out's programs per operation (ms),
from the profiler's trace, on the chip's plane where they took longest: the
sharded placement on the landing chip (jax's own ``_multi_slice``) and the
all-gather over the mesh (``parallel/ici.py`` ``_all_gather_jit``; the
other collectives of that module are listed so that a later choice among
them is still read). ``reduce_trace.program_seconds`` reads the first plane
only, so this walks ``chip_planes`` itself. A program that runs no such
program in the traced operations (one chip, or the parent of the cell)
leaves nothing to read."""

PROGRAMS = ("_all_gather_jit", "_chunked_ring_all_gather_jit",
            "_ring_all_gather_jit", "_multi_slice")


def seconds_per_operation(run):
    import reduce_trace as trace

    if run.trace is None or not run.ops:
        return None
    slowest = 0.0
    for plane in trace.chip_planes(run.trace):
        runs = [(s, s + d) for name, s, d in
                trace.events_on(run.trace, plane, trace.MODULE_LINES)
                if any(n in name for n in PROGRAMS)]
        slowest = max(slowest,
                      sum(e - s for s, e in trace.clip(runs, run.windows)))
    return slowest / len(run.ops) if slowest > 0 else None


def read(run):
    s = seconds_per_operation(run)
    return None if s is None else s * 1000.0
