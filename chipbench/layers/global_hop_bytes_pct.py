"""Sharded landing: bytes that reached their chip by a copy from another chip
(the program's ``device_sink_hop_bytes_total``: the fan-out of what several chips
want, whole padded pieces, and any ``device_put`` of a landed shard) over the
bytes resident on all chips at the operation's end (%), median per operation.
What all four chips of an expert-parallel host keep, three times, is the
floor; every byte through one landing chip is three quarters."""

from layers import global_events


def of_operation(op):
    hops = (getattr(op, "counts", None) or {}).get("hop_bytes")
    resident = sum(getattr(op, "chip_resident", None) or [])
    return 100.0 * hops / resident if hops is not None and resident else None


def read(run):
    return global_events.median_per_operation(run, of_operation)
