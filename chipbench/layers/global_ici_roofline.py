"""Sharded landing: the fan-out against the interconnect's roofline (%): the
least time a receiving chip could take, the bytes that hopped INTO it once at
the chip's ICI peak (``ici_roofline.least_seconds``, ``peaks_ici.json``), over
the time the fan-out's programs took on the slowest chip's plane
(``global_ici_ms``). Here a chip takes in what every chip keeps, not the
file: the operation's hop bytes over the chips that received a copy."""

import json
import os

from layers import ici_ms, ici_roofline

HERE = os.path.dirname(os.path.abspath(__file__))


def into_one_chip(run) -> float | None:
    """Bytes that reached ONE receiving chip from another, an operation."""
    rows = [(op.counts["hop_bytes"], len(op.chip_resident) - 1)
            for op in run.ops
            if (getattr(op, "counts", None) or {}).get("hop_bytes")
            and len(getattr(op, "chip_resident", None) or []) > 1]
    if not rows:
        return None
    return sum(hops / receivers for hops, receivers in rows) / len(rows)


def read(run):
    took = ici_ms.seconds_per_operation(run)
    moved = into_one_chip(run)
    if took is None or moved is None:
        return None
    with open(os.path.join(os.path.dirname(HERE), "peaks_ici.json")) as f:
        peaks = json.load(f)
    kind = getattr(run, "device_kind", None)
    if kind is None:
        import jax

        kind = jax.devices()[0].device_kind
    if kind not in peaks:
        return None
    return 100.0 * ici_roofline.least_seconds(
        moved, peaks[kind]["ici_bytes_per_s"]) / took
