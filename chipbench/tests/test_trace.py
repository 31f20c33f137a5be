"""The reduction from a trace to numbers, on plain intervals and on the
small trace recorded on the v5e that is kept beside this file."""

import json
import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE)]

import reduce_trace as rt  # noqa: E402
import spans  # noqa: E402


def toy_trace():
    ops = [["fusion.1", 1.0, 0.5], ["copy.2", 1.25, 0.5], ["fusion.1", 3.0, 1.0]]
    modules = [["jit__assemble_checksum_jit(123)", 1.0, 0.75],
               ["jit_chipbench_piece_checksums(9)", 3.0, 1.0]]
    return {"device": {"/device:TPU:0": {"XLA Ops": ops,
                                         "XLA Modules": modules},
                       "/device:TPU:0 SparseCore": {"XLA Ops": [["x", 0, 9]]}},
            "host": [["chipbench:op#0", 10.5, 4.0]]}


def test_union_clip_total():
    assert rt.union([(0, 1), (0.5, 2), (3, 4), (4, 4)]) == [(0, 2), (3, 4)]
    assert rt.total([(0, 1), (0.5, 2), (3, 4)]) == 3
    assert rt.clip([(0, 2), (3, 5)], [(1, 4)]) == [(1, 2), (3, 4)]


def test_busy_window_programs_and_offset():
    trace = toy_trace()
    assert rt.chip_planes(trace) == ["/device:TPU:0"]
    assert rt.clock_offset(trace, {"chipbench:op#0": 0.5}) == 10.0
    # The op at 3.0 ran inside the benchmark's own program: not counted.
    busy, window = rt.busy_and_window(trace, [(0.5, 3.5)])
    assert (busy, window) == (0.75, 3.0)
    assert rt.program_seconds(trace, ("_assemble_checksum_jit",),
                              [(0.5, 3.5)]) == 0.75
    assert rt.top_device_ops(trace, [(0.5, 3.5)])[0] == ["fusion.1", 0.5]


def test_idle_gaps_go_to_the_most_specific_label():
    gaps = dict(rt.idle_gaps_by_label(
        toy_trace(), [(0.0, 4.0)],
        [("inner", 0.5, 1.0), ("outer", 0.0, 2.5)]))
    # idle: 0-1 and 1.75-4; inner takes 0.5-1, outer 0-0.5 and 1.75-2.5.
    assert gaps == {"inner": 0.5, "outer": 1.25, "unlabelled": 1.5}


def test_spans_pair_the_raw_flight_events():
    op = types.SimpleNamespace(
        t0=0.0, t1=9.0, views_span=(7.0, 9.0), flight=[
            (0.1, "register", -1, 0.0), (0.3, "scheduled", -1, 0.0),
            (0.4, "request", 0, 0.0), (0.5, "request", 1, 0.0),
            (1.4, "landed", 0, 0.0), (1.5, "hbm_start", 0, 0.0),
            (2.0, "landed", 1, 0.0), (2.1, "hbm_start", 1, 0.0),
            (2.5, "hbm_landed", 0, 0.0), (3.0, "hbm_landed", 1, 0.0),
            (4.0, "landed", 2, 500.0)])
    assert spans.sched_wait(op) == [(0.1, 0.3)]
    assert rt.total(spans.transfers(op)) == pytest.approx(1.6 + 0.5)
    assert rt.total(spans.paired(op, "hbm_start", "hbm_landed")) == 1.5
    assert spans.labelled(op)[0][0].startswith("views")


RECORDED = os.path.join(HERE, "recorded_trace.json")


@pytest.mark.skipif(not os.path.exists(RECORDED), reason="no recorded trace")
def test_recorded_trace_reduces_to_its_recorded_numbers():
    with open(RECORDED) as f:
        rec = json.load(f)
    trace, windows, want = rec["trace"], rec["windows"], rec["expect"]
    busy, window = rt.busy_and_window(trace, windows)
    assert busy == pytest.approx(want["busy_s"], rel=1e-9)
    assert window == pytest.approx(want["window_s"], rel=1e-9)
    assert 0 < busy < window
    got = rt.program_seconds(trace, ("_assemble_checksum_jit",
                                     "_gather_checksum_jit"), windows)
    assert got == pytest.approx(want["assemble_s"], rel=1e-9) and got > 0
