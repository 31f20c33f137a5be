"""Checkpoint save / resume: share of the resumed tensors' bytes that the
``flat`` path of ``ops/bitview.py`` cut, the rest the rows kernel's (%), by
``device_views_bytes_total``: the evidence ROADMAP's S11 asks for. Median
per operation."""

from layers import save_events


def read(run):
    return save_events.median(
        100.0 * op.counted["views_flat"]
        / (op.counted["views_flat"] + op.counted["views_rows"])
        for op in run.ops
        if getattr(op, "counted", None) and "views_flat" in op.counted
        and op.counted["views_flat"] + op.counted["views_rows"] > 0)
