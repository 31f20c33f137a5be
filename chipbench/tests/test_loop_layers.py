"""The five readers of the layer "event loop" on a hand-made ring and three
overlapping operations, each number worked out by hand beside it. The ring is
a flight the process's recorder holds outside its tasks
(``runtime:loop:daemon``); a slice (``loop_acct``) is one event at its end,
aux = busy ms, piece = cpu us, note = ``late=.. gc=.. it=.. n=..``; a hold
(``loop_lag``) one event at its end, aux = SECONDS, note ``held ..`` or
``late``. An event is a span that ends where it is stamped, clipped to the
UNION of the operations: it counts by the share of the span inside."""

import importlib
import os
import sys
import time
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE), os.path.dirname(os.path.dirname(HERE))]

from dragonfly2_tpu.pkg import flight  # noqa: E402
from layers import loop_events  # noqa: E402

READERS = ("loop_busy_ms", "loop_offcpu_pct", "loop_hold_ms", "loop_late_ms",
           "loop_gc_ms")
ACCT = getattr(flight, "EV_LOOP_ACCT", None)


def near(value):
    # The ring's clock is put on the operations' through the anchored wall
    # clock: good to microseconds, so a clipped span is to a hundredth of a ms.
    return pytest.approx(value, abs=0.01)


def read(name, run):
    return importlib.import_module("layers." + name).read(run)


def run_of(base, *spans):
    return types.SimpleNamespace(ops=[
        types.SimpleNamespace(number=i, task_id=f"no-such-task-{i}",
                              t0=base + s, t1=base + e)
        for i, (s, e) in enumerate(spans)])


@pytest.fixture
def ring(monkeypatch):
    """An empty ring in the readers' way, and the perf_counter second its
    events and the operations count from."""
    tf = flight.TaskFlight(loop_events.RING, 64)
    monkeypatch.setattr(loop_events, "find_ring", lambda: tf)
    return tf, time.perf_counter()


def fill(tf, base):
    # Three clients' operations: 10.0-11.0, 10.5-11.5 and 11.2-12.0 overlap
    # into ONE stretch 10.0-12.0; a fourth stands alone, 13.0-13.5.
    for t, busy_ms, cpu_us, note in (
            (9.9, 5.0, 5000, "late=0.100 gc=0.000 it=10 n=12"),    # before
            (10.2, 6.0, 3000, "late=0.500 gc=1.000 it=20 n=64"),
            (10.7, 5.0, 5000, "late=0.000 gc=0.000 it=900 n=900"),
            (11.3, 40.0, 2000, "late=0.000 gc=2.500 it=1 n=1"),
            # An operation's last turn ends after its t1: 30 of its 40 ms
            # lie inside 10.0-12.0, so three quarters of it count.
            (12.01, 40.0, 8000, "late=0.000 gc=4.000 it=4 n=8"),
            (12.5, 7.0, 7000, "late=9.000 gc=9.000 it=3 n=3"),     # between
            (13.2, 9.0, 8000, "late=30.250 gc=0.000 it=2 n=2"),
            # No busy time, a late wake alone: a point, inside or not.
            (13.3, 0.0, 0, "late=25.000 gc=0.000 it=1 n=1")):
        tf.record_at(base + t, ACCT, cpu_us, busy_ms, note)
    for t, seconds, cpu_ms, note in (
            (11.3, 0.040, 2, "held n=1 gc=2.5 who=device_feed.py:_land:207"),
            (12.01, 0.040, 8, "held n=8 gc=4.0 who=hbm_sink.py:flush:300"),
            (12.5, 0.300, 1, "held n=1 gc=0.0 who=?"),             # between
            (13.2, 0.030, 0, "late")):
        tf.record_at(base + t, flight.EV_LOOP_LAG, cpu_ms, seconds, note)


@pytest.mark.skipif(ACCT is None, reason="a program older than the ring")
def test_the_readers_sum_over_the_union_per_operation(ring):
    tf, base = ring
    fill(tf, base)
    run = run_of(base, (10.0, 11.0), (10.5, 11.5), (11.2, 12.0), (13.0, 13.5))
    # Inside the union: the slices that ended at 10.2, 10.7, 11.3 (each ONCE,
    # though two operations were in flight), three quarters of the one that
    # ended at 12.01, and those at 13.2 and 13.3; four operations.
    # busy 6 + 5 + 40 + 30 + 9 + 0 = 90 ms -> 22.5 an operation
    assert read("loop_busy_ms", run) == near(22.5)
    # cpu 3 + 5 + 2 + 6 + 8 + 0 = 24 ms of the 90: 73.33 % off the core
    assert read("loop_offcpu_pct", run) == near(100 * (1 - 24 / 90))
    # the held holds inside: 40 ms and 30 of 40 ms -> 70 / 4; the late wake
    # at 13.2 is none
    assert read("loop_hold_ms", run) == near(17.5)
    # late 0.5 + 0 + 0 + 0 + 30.25 + 25 = 55.75 -> 13.9375
    assert read("loop_late_ms", run) == near(13.9375)
    # gc 1.0 + 0 + 2.5 + 3.0 + 0 + 0 = 6.5 -> 1.625
    assert read("loop_gc_ms", run) == near(1.625)
    # The log's lines: the turns (20 + 900 + 1 + 3 + 2 + 1 over the union's
    # 2.5 s), the holds by who, each with its operation and its offset.
    lines = loop_events.describe(run)
    assert lines[0].startswith("6 slices, 927 iterations (371 a second) of "
                               "974 handles in the 2.50 s")
    assert lines[1] == ("3 holds: device_feed.py:_land:207 x1 0.040 s, "
                        "hbm_sink.py:flush:300 x1 0.030 s, late x1 0.030 s")
    assert lines[2].startswith("hold of 0.0400 s, cpu 2 ms, held n=1 gc=2.5 "
                               "who=device_feed.py:_land:207; it ended 0.800 "
                               "s into operation 1 (1.000 s)")
    assert "who=hbm_sink.py:flush:300; it ended 0.810 s into operation 2" \
        in lines[3]
    assert "0.200 s into operation 3 (0.500 s)" in lines[4]
    assert lines[5].startswith("the operations' own flights: 0 rings, "
                               "events_dropped 0 in all")


@pytest.mark.skipif(ACCT is None, reason="a program older than the ring")
def test_one_operation_sees_only_its_own_stretch(ring):
    tf, base = ring
    fill(tf, base)
    # 12.4-12.6 holds the slice that ended at 12.5 whole (7 ms) and the last
    # 100 ms of the hold of 300 ms that ended with it.
    run = run_of(base, (12.4, 12.6))
    assert read("loop_busy_ms", run) == near(7.0)
    assert read("loop_offcpu_pct", run) == near(0.0)
    assert read("loop_hold_ms", run) == near(100.0)
    assert read("loop_late_ms", run) == near(9.0)
    assert read("loop_gc_ms", run) == near(9.0)


def test_an_empty_ring_reads_an_idle_loop(ring):
    # The ring is there and the loop ran nothing worth a slice: the sums are
    # 0, and a share of no busy time is nothing.
    _, base = ring
    run = run_of(base, (10.0, 11.0), (10.5, 11.5))
    for name in READERS:
        assert read(name, run) == (None if name == "loop_offcpu_pct" else 0.0)
    # No operation finished: nothing, and no division by zero.
    for name in READERS:
        assert read(name, run_of(base)) is None


def test_no_ring_reads_nothing(monkeypatch):
    # A program older than the account (``recorder().get`` finds no such
    # flight), or a process whose daemon never armed a probe.
    monkeypatch.setattr(loop_events, "RING", "runtime:loop:never-armed")
    assert loop_events.find_ring() is None
    run = run_of(time.perf_counter(), (10.0, 11.0))
    for name in READERS:
        assert read(name, run) is None
