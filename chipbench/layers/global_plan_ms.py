"""Sharded landing: ``download_global`` called -> the header's ranged task
landed, the header parsed, every (tensor, chip) span planned and the spans
coalesced by destination (``shard_plan`` on the header task's flight, ``aux``
= ms), median per operation (ms)."""

from layers import global_events


def read(run):
    return global_events.median_per_operation(
        run, lambda op: global_events.summed_aux(op, "shard_plan")
        if global_events.rows(op) else None)
