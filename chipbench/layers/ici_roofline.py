"""Collectives: the fan-out against the interconnect's roofline (%): the
least time a receiving chip could take, the content's bytes once into that
chip at the chip's ICI peak, over the time the fan-out's programs took on
the slowest chip's plane (``ici_ms``). The peak is ``peaks_ici.json``'s,
read at its loosest: all of a chip's interconnect as ingress of one chip.
A chip takes in less than the content where it holds a shard already, and
no link of a 2x2 carries the whole peak, so a share cannot pass 100."""

import importlib
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def least_seconds(content_bytes: float, ici_bytes_per_s: float) -> float:
    """Every byte of the content into one receiving chip, once."""
    return content_bytes / ici_bytes_per_s


def read(run):
    took = importlib.import_module("layers.ici_ms").seconds_per_operation(run)
    if took is None:
        return None
    with open(os.path.join(os.path.dirname(HERE), "peaks_ici.json")) as f:
        peaks = json.load(f)
    # The run says no device kind (``run.peaks`` is that kind's row of
    # peaks.json, which a PR may not edit): asked of jax, as run.py does.
    kind = getattr(run, "device_kind", None)
    if kind is None:
        import jax

        kind = jax.devices()[0].device_kind
    if kind not in peaks:
        return None
    content = sum(op.nbytes for op in run.ops) / len(run.ops)
    return 100.0 * least_seconds(content, peaks[kind]["ici_bytes_per_s"]) / took
