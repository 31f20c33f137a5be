"""Sharded landing: the typed views of every landed range, cut on the chips
the range lies on, and the global arrays made of them (``shard_views`` on the
header task's flight, ``aux`` = ms, the host's; the device's part ends with
the operation), median per operation (ms)."""

from layers import global_events


def read(run):
    return global_events.median_per_operation(
        run, lambda op: global_events.summed_aux(op, "shard_views")
        if global_events.rows(op) else None)
