"""Assemble+checksum: ``DeviceSinkManager._finalize_sync`` on the landing
thread (``sink_finalize``: backfill of what did not stream in, the last
flush, the assembly with any compile, the verification), per operation,
median (ms). In a re-land it holds the whole landing."""

from layers import sink_events


def read(run):
    return sink_events.median_of_sums(run, "sink_finalize")
