"""Completion digest: the peer's stay in ``conductor._await_certification``
(``cert_wait``: ONE event as it returns, ``aux`` = its ms), between its last
piece and ``task_done``: the wait for the seed's ``done``, which the seed
says only once it has verified the whole object. Per operation, median
(ms). A task with no whole-object digest stamps none."""

from layers import sink_events


def read(run):
    return sink_events.median_of_sums(run, "cert_wait")
