"""Typed views: the benchmark's own span around load_safetensors +
block_until_ready, median per operation (ms)."""

import spans


def read(run):
    return spans.median_ms(op.views_span[1] - op.views_span[0]
                           for op in run.ops if op.views_span)
