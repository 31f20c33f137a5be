"""Ranged pull: ``download_sharded`` called -> the header's ranged task
landed, the header parsed, the spans coalesced and planned (``shard_plan``
on the header task's flight, ``aux`` = ms), median per operation (ms)."""

from layers import ranged_events


def read(run):
    return ranged_events.median_per_operation(
        run, lambda op: ranged_events.summed_aux(op, "shard_plan"))
