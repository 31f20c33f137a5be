"""The layer "dataset feed" in an operation's flight events, shared by the
``feed_*`` readers beside this file (it reads no metric itself).

An operation of the cell ``feed-records`` is one batch of
``dataset.device_feed.DeviceFeed`` fed by ``dataset.PodShardedLoader``. Its
driver (``drivers/closed_loop_feed.py``) keeps in ``op.feed`` what the feed's
one flight ring gained since the operation before: ``(t, name, piece, aux,
note)``, where the program stamps ``feed_sample`` a sample's read (``aux`` =
ms launched -> the bytes in the pooled buffer; ``note`` = ``src=.. tasks=..
bytes=.. task=.. move=.. read=..``), ``feed_wait`` a batch (``aux`` = ms the
consumer side waited for its samples), ``feed_batch`` a batch (``aux`` = ms
first record staged -> ``as_record_batch`` dispatched; ``note`` = ``path=..
n=.. payload=.. put=.. stage=.. verify=.. view=..``) and the sink's
``sink_*`` steps with ``batch=<k>`` leading the note. The read-ahead runs
past a batch's end, so a sample's event may lie in the operation before its
batch's: the readers pool the window's. An operation of another driver has no
``op.feed``, and a program older than those events stamps none: every reader
then reads nothing.
"""

import statistics


def fields(note: str) -> dict:
    """``"a=1 b=x"`` -> ``{"a": "1", "b": "x"}``."""
    return dict(part.split("=", 1) for part in note.split() if "=" in part)


def events(run, name: str) -> list:
    """``(t, aux, fields of the note, op)`` of every ``name`` event of the
    window."""
    return [(t, aux, fields(note), op) for op in run.ops
            for t, event, _, aux, note in getattr(op, "feed", None) or []
            if event == name]


def median(values) -> float | None:
    values = [v for v in values if v is not None]
    return statistics.median(values) if values else None


def summed(run, name: str, field: str) -> float | None:
    """The summed ``field`` of the notes of the window's ``name`` events."""
    found = [float(f[field]) for _, _, f, _ in events(run, name)
             if field in f]
    return sum(found) if found else None
