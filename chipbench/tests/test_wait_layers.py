"""The readers of the wait for the landing thread, on hand-made flights:
each number is worked out by hand beside it. ``sink_wait`` is one event a
job, stamped as the job starts on the thread, aux = the ms it stood
queued; ``sink_land`` / ``sink_finalize`` are one event at the span's end,
aux = its ms."""

import importlib
import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE)]


def op(t0, t1, flight):
    return types.SimpleNamespace(t0=t0, t1=t1, flight=flight,
                                 views_span=None)


def reland(t0, submitted, started, ended, t1):
    """One re-land: a single finalize job that stood queued from
    ``submitted`` to ``started`` and held the thread until ``ended``, with
    a backfilled piece inside it."""
    return op(t0, t1, [
        (started, "sink_wait", 0, (started - submitted) * 1000.0),
        (started + 0.05, "sink_land", 0, 40.0),
        (ended, "sink_finalize", 1, (ended - started) * 1000.0)])


# Three clients, one thread: the landings follow each other, 0.2 s each,
# and each client asks again 0.1 s after its last ended.
#   client 0: 10.0-10.3, lands 10.1-10.3 (waited 0.0)
#   client 1: 10.0-10.5, lands 10.3-10.5 (waited 0.2)
#   client 2: 10.0-10.7, lands 10.5-10.7 (waited 0.4)
#   client 0 again: 10.4-10.9, lands 10.7-10.9 (waited 0.2)
THREE = [reland(10.0, 10.1, 10.1, 10.3, 10.3),
         reland(10.0, 10.1, 10.3, 10.5, 10.5),
         reland(10.0, 10.1, 10.5, 10.7, 10.7),
         reland(10.4, 10.5, 10.7, 10.9, 10.9)]

# One client: a landing of 0.5 s in an operation of 1.0 s, then the
# benchmark's own second between two operations, in which nothing is in
# flight and the thread's idleness does not count.
ONE = [reland(20.0, 20.1, 20.102, 20.602, 21.0),
       reland(22.0, 22.1, 22.104, 22.604, 23.0)]


def cold():
    """Two pieces stream in together: the second waits for the first. The
    finalize then waits for nothing."""
    return op(30.0, 32.0, [
        (31.000, "hbm_start", 0, 0.0), (31.000, "hbm_start", 1, 0.0),
        (31.001, "sink_wait", 0, 1.0),
        (31.101, "sink_land", 0, 100.0), (31.101, "hbm_landed", 0, 0.0),
        (31.101, "sink_wait", 1, 101.0),
        (31.201, "sink_land", 1, 100.0), (31.201, "hbm_landed", 1, 0.0),
        (31.300, "sink_wait", 0, 0.5),
        (31.500, "sink_finalize", 0, 200.0)])


def parent_op():
    """What the parent of this PR stamps: the thread's spans, no wait."""
    return op(40.0, 41.0, [(40.3, "sink_land", 0, 100.0),
                           (40.9, "sink_finalize", 1, 700.0)])


def older_program_op():
    """What a program older than every ``sink_*`` span stamps."""
    return op(0.0, 9.0, [(0.1, "register", -1, 0.0),
                         (1.5, "hbm_start", 0, 0.0),
                         (2.5, "hbm_landed", 0, 0.0)])


def read(name, ops):
    return importlib.import_module("layers." + name).read(
        types.SimpleNamespace(ops=ops))


@pytest.mark.parametrize("name, ops, want", [
    # Waits 0, 200, 400 and 200 ms: the median of the four.
    ("land_wait_ms", THREE, 200.0),
    # At work 10.1-10.9 without a gap, operations in flight 10.0-10.9.
    ("land_thread_util_pct", THREE, 100.0 * 0.8 / 0.9),
    ("land_wait_ms", ONE, 3.0),
    # 0.5 s at work in each 1.0 s operation; the second between them is
    # no operation's.
    ("land_thread_util_pct", ONE, 50.0),
    # A cold pull: 1 + 101 + 0.5 ms, the sum over its jobs.
    ("land_wait_ms", [cold()], 102.5),
    # At work 31.001-31.201 and 31.3-31.5 of the operation's 2 s.
    ("land_thread_util_pct", [cold()], 100.0 * 0.4 / 2.0),
    # The parent's spans give the thread's share, 40.2-40.9 of 1 s (the
    # backfilled piece lies inside the finalize and is counted once).
    ("land_thread_util_pct", [parent_op()], 70.0),
])
def test_reader_on_hand_made_flight(name, ops, want):
    got = read(name, ops)
    assert got is not None and got == pytest.approx(want, abs=1e-6)


def test_util_is_clipped_to_the_operations():
    """A span that began before its operation (the flight keeps events by
    their end) counts only from the operation's start."""
    early = op(50.0, 51.0, [(50.5, "sink_finalize", 0, 800.0)])
    assert read("land_thread_util_pct", [early]) == pytest.approx(50.0)


@pytest.mark.parametrize("name, ops", [
    # No sink_wait: the parent of this PR, and anything older.
    ("land_wait_ms", [parent_op()]),
    ("land_wait_ms", [older_program_op()]),
    ("land_wait_ms", []),
    # No on-thread span at all.
    ("land_thread_util_pct", [older_program_op()]),
    ("land_thread_util_pct", []),
])
def test_reader_reads_nothing_without_its_events(name, ops):
    assert read(name, ops) is None
