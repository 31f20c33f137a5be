"""The readers of the landing thread's spans, on a hand-made operation:
each number is worked out by hand beside it. A span is one event at its
end whose aux is its duration in ms."""

import importlib
import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE)]


def cold_op(compile_ms=None):
    """Two pieces stream in, the second waits for the thread; finalize
    flushes, assembles (with a compile, if asked) and verifies."""
    flight = [
        (10.000, "register", -1, 0.0), (10.010, "scheduled", -1, 0.0),
        (11.510, "parent_pieces", 0, 1.0), (11.530, "request", 0, 0.0),
        (11.600, "parent_pieces", 1, 1.0), (11.610, "request", 1, 0.0),
        (12.000, "landed", 0, 470.0), (12.000, "hbm_start", 0, 0.0),
        (12.100, "landed", 1, 490.0), (12.100, "hbm_start", 1, 0.0),
        (12.040, "sink_read", 0, 40.0), (12.060, "sink_checksum", 0, 20.0),
        (12.300, "sink_land", 0, 300.0), (12.300, "hbm_landed", 0, 0.0),
        (12.350, "sink_read", 1, 50.0), (12.360, "sink_checksum", 1, 10.0),
        (12.700, "sink_land", 1, 400.0), (12.700, "hbm_landed", 1, 0.0),
        # 0.3 s with nothing to land, then a late third piece.
        (13.000, "hbm_start", 2, 0.0), (13.030, "sink_read", 2, 30.0),
        (13.045, "sink_checksum", 2, 15.0), (13.100, "sink_land", 2, 100.0),
        (13.100, "hbm_landed", 2, 0.0),
        (13.260, "sink_stage", 0, 60.0), (13.400, "sink_put", 0, 140.0),
    ]
    if compile_ms is not None:
        flight.append((13.400 + compile_ms / 1000.0 + 0.010, "sink_compile",
                       3, compile_ms))
    end = 13.400 + (compile_ms or 0.0) / 1000.0 + 0.100
    flight += [(end, "sink_assemble", 3, (end - 13.400) * 1000.0),
               (end + 0.020, "sink_finalize", 0, (end + 0.020 - 13.200)
                * 1000.0)]
    return types.SimpleNamespace(t0=10.0, t1=end + 0.5, flight=flight,
                                 views_span=None)


def reland_op():
    """Backfill of two pieces inside one finalize; no transfer events."""
    return types.SimpleNamespace(t0=50.0, t1=52.0, views_span=None, flight=[
        (50.100, "sink_read", 0, 90.0), (50.130, "sink_checksum", 0, 30.0),
        (50.140, "sink_land", 0, 135.0),
        (50.250, "sink_read", 1, 100.0), (50.290, "sink_checksum", 1, 40.0),
        (50.500, "sink_stage", 0, 200.0), (50.900, "sink_put", 0, 400.0),
        (50.910, "sink_land", 1, 770.0),
        (51.200, "sink_assemble", 1, 290.0),
        (51.210, "sink_finalize", 2, 1205.0)])


def old_program_op():
    """What a program older than the spans stamps."""
    return types.SimpleNamespace(t0=0.0, t1=9.0, views_span=None, flight=[
        (0.1, "register", -1, 0.0), (0.3, "scheduled", -1, 0.0),
        (0.4, "request", 0, 0.0), (1.4, "landed", 0, 0.0),
        (1.5, "hbm_start", 0, 0.0), (2.5, "hbm_landed", 0, 0.0)])


def read(name, ops):
    return importlib.import_module("layers." + name).read(
        types.SimpleNamespace(ops=ops))


COLD = [cold_op(), cold_op(compile_ms=1500.0), cold_op()]
RELAND = [reland_op(), reland_op()]


@pytest.mark.parametrize("name, ops, want", [
    ("land_read_ms", COLD, 40.0 + 50.0 + 30.0),
    ("land_checksum_ms", COLD, 20.0 + 10.0 + 15.0),
    ("land_stage_ms", COLD, 60.0),
    ("land_put_ms", COLD, 140.0),
    # Busy 12.0-12.7 and 13.0-13.1 of 12.0-13.1.
    ("land_thread_busy_pct", COLD, 100.0 * 0.8 / 1.1),
    # 13.2 -> 13.52 without a compile; the median of the three.
    ("finalize_ms", COLD, 320.0),
    # One operation of three compiled for 1.5 s: the mean.
    ("plan_compile_ms", COLD, 500.0),
    ("seed_start_ms", COLD, 1500.0),
    ("land_read_ms", RELAND, 190.0),
    ("land_checksum_ms", RELAND, 70.0),
    ("land_stage_ms", RELAND, 200.0),
    ("land_put_ms", RELAND, 400.0),
    ("finalize_ms", RELAND, 1205.0),
    # Assemblies ran and none compiled: a number, and it is nought.
    ("plan_compile_ms", RELAND, 0.0),
])
def test_reader_on_hand_made_flight(name, ops, want):
    got = read(name, ops)
    assert got is not None and got == pytest.approx(want, abs=1e-6)


NEW = ("land_read_ms", "land_checksum_ms", "land_stage_ms", "land_put_ms",
       "land_thread_busy_pct", "finalize_ms", "plan_compile_ms",
       "seed_start_ms")


@pytest.mark.parametrize("name", NEW)
def test_reader_reads_nothing_from_an_older_program(name):
    assert read(name, [old_program_op()]) is None
    assert read(name, []) is None


def test_seed_start_prints_the_peers_own_dispatch(capsys):
    read("seed_start_ms", COLD[:1])
    out = capsys.readouterr().out
    assert "first parent_pieces -> first request" in out and "20.0" in out
