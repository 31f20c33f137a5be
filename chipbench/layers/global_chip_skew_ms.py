"""Sharded landing: the first chip complete -> the last chip complete: a chip
is complete when the last ranged task whose words lie on it is in the caller's
hand (``device_pull``), fanned out and verified where several chips want it;
median per operation (ms). One landing thread serves every chip: what it
lands last decides which chip waits."""

from layers import global_events


def of_operation(op):
    done = global_events.chip_done(op)
    if len(done) < 2:
        return None
    return (max(done.values()) - min(done.values())) * 1000.0


def read(run):
    return global_events.median_per_operation(run, of_operation)
