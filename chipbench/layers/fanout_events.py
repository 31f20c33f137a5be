"""The layer "fan-out between peers" in the flights of ALL the daemons of an
operation, shared by the ``fanout_*`` readers beside this file (it reads no
metric itself).

An operation of the cell ``shard-cold-fanout`` is all the hosts of one slice
(eight) asking at once for one
fresh task. Its driver
(``drivers/closed_loop_fanout.py``) keeps every daemon's flight events for the
task apart in ``op.hosts``: a row a daemon, host 0 first, the seed last,
``{"host": index or "seed", "flight": [(t, name, piece, aux, note)] or None}``,
every ``t`` on the benchmark's perf_counter clock. Host 0's ring is read in
place; the others' come through ``Daemon.FlightReport`` with ``raw``. The
program stamps, beside what a cold pull always stamped: ``upload_serve`` at a
send's END on the daemon that served (``aux`` = ms of the send, ``piece``,
``note`` = "<bytes>" or "<bytes> wait=<ms>"), and ONE ``task_sources`` as a
conductor ends (``piece`` = distinct parents that served it a piece, ``aux`` =
bytes from parents that are not seeds, ``note`` = "seed=<bytes> peer=<bytes>
origin=<bytes>"). An operation of another driver has no ``op.hosts``; a
program older than the ``raw`` reply gives none of the other daemons' flights,
and one older than the events stamps none: every reader then reads nothing.
"""

from layers.ranged_events import median_per_operation  # noqa: F401


def flights(op, seed: bool = False):
    """The flights of the operation's hosts (or the seed's alone), or
    None where the operation has no such rows or ANY of them is missing:
    a metric over the hosts says nothing if a host is not in it."""
    rows = [row for row in getattr(op, "hosts", None) or []
            if (row["host"] == "seed") == seed]
    if not rows or any(row["flight"] is None for row in rows):
        return None
    return [row["flight"] for row in rows]


def sources(flight) -> dict | None:
    """A daemon's ``task_sources``: bytes by where they came from, and the
    parents that served any; None where it stamped none."""
    for _, name, piece, _, note in flight:
        if name == "task_sources":
            out = {"seed_bytes": 0, "peer_bytes": 0, "origin_bytes": 0,
                   "parents": piece}
            for part in note.split():
                key, _, value = part.partition("=")
                if key + "_bytes" in out and value.isdigit():
                    out[key + "_bytes"] = int(value)
            return out
    return None


def first(flight, name: str) -> float | None:
    return next((t for t, event, *_ in flight if event == name), None)


def last(flight, name: str) -> float | None:
    return next((t for t, event, *_ in reversed(flight) if event == name),
                None)
