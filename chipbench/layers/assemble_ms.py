"""Assemble + checksum: summed device time of the sink's assembly
programs per operation (ms), from the profiler's trace."""

PROGRAMS = ("_assemble_checksum_jit", "_gather_checksum_jit")


def seconds_per_operation(run):
    import reduce_trace as trace

    if run.trace is None or not run.ops:
        return None
    seconds = trace.program_seconds(run.trace, PROGRAMS, run.windows)
    return seconds / len(run.ops) if seconds > 0 else None


def read(run):
    s = seconds_per_operation(run)
    return None if s is None else s * 1000.0
