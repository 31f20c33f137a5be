"""Ranged pull: the typed views of all of an operation's spans, validated
and dispatched span by span inside ``download_sharded`` (``shard_views`` on
the header task's flight, ``aux`` = ms, the host's; the device's part ends
with the operation), median per operation (ms)."""

from layers import ranged_events


def read(run):
    return ranged_events.median_per_operation(
        run, lambda op: ranged_events.summed_aux(op, "shard_views"))
