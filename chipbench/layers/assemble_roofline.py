"""Assemble + checksum against the memory roofline (%): the least time the
chip could take, one read and one write of the content at the peak HBM
rate, over the time the assembly programs took. Memory-bound: the sums and
xors are one integer operation per word."""

import importlib


def least_seconds(content_bytes: int, peaks: dict) -> float:
    """The algorithm's least traffic: every content byte read once and
    written once. The program's form moves more (PERF.md); that shows as a
    lower share, never as one above 100."""
    return 2 * content_bytes / peaks["hbm_bytes_per_s"]


def read(run):
    took = importlib.import_module("layers.assemble_ms") \
        .seconds_per_operation(run)
    if took is None:
        return None
    content = sum(op.nbytes for op in run.ops) / len(run.ops)
    return 100.0 * least_seconds(content, run.peaks) / took
