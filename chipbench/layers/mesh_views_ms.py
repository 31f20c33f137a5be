"""Collectives: typed views over words that lie on every chip of a mesh:
the benchmark's own span around ``load_safetensors`` + every tensor ready
on every chip, median per operation (ms). What ``views_ms`` is on one chip,
and read as it is; a metric of its own name because that metric's list of
cells is the benchmark's."""

from layers import views_ms


def read(run):
    return views_ms.read(run)
