"""Dataset feed: a batch's device programs against the memory roofline (%):
the least time the chip could take to make the batch out of what was put on
it, over the device time of the feed's programs (the sink's assembly with its
checksums, the record view) per operation, from the profiler's trace.
Memory-bound: a checksum is one integer operation a word."""

from layers import feed_events

PROGRAMS = ("_assemble_checksum_jit", "_record_batch_jit")


def least_bytes(put_bytes: float, batch_bytes: float) -> float:
    """What any implementation must move in HBM to make the batch: the
    staged words, padding and all, read once, and the batch written once.
    The program's form moves more (the assembled words are written and read
    again before the byte view), which shows as a lower share."""
    return put_bytes + batch_bytes


def read(run):
    import reduce_trace as trace

    batches = feed_events.events(run, "feed_batch")
    put = feed_events.summed(run, "feed_batch", "put")
    if run.trace is None or not batches or not put:
        return None
    took = trace.program_seconds(run.trace, PROGRAMS, run.windows)
    if took <= 0:
        return None
    made = sum(op.shape[0] * op.shape[1] for _, _, _, op in batches)
    return 100.0 * least_bytes(put, made) \
        / run.peaks["hbm_bytes_per_s"] / took
