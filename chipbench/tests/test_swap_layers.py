"""Two readers of the layer "hot-swap" on hand-made flights, each number
worked out by hand beside it. ``verified`` is one event at the completion
digest's end, aux = the ms since ``verify_start``; ``delta_reuse`` is one
event at a span's end, aux = the ms in which those bytes were read out of
the base store and verified (one a chunk from the chunk walk, one a piece
job since the landing is built a piece at a time, the jobs side by side)."""

import importlib
import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE)]


def op(flight):
    return types.SimpleNamespace(flight=flight)


def run_of(*flights):
    return types.SimpleNamespace(ops=[op(f) for f in flights])


def read(name, run):
    return importlib.import_module("layers." + name).read(run)


def test_digest_tail_sums_an_operations_verified_events():
    # Operation 0: a swap whose merged flight holds two verified events
    # (the delta task's and a span task's): 300 + 20 = 320 ms. Operation 1:
    # 500 ms. Operation 2: 100 ms. The median of 320, 500, 100 is 320.
    run = run_of(
        [(5.0, "verify_start", 40, 15.0), (5.3, "verified", 15, 300.0),
         (5.4, "verified", 0, 20.0), (5.5, "swap_verify", 55, 90.0)],
        [(8.0, "verify_start", 0, 55.0), (8.5, "verified", 55, 500.0)],
        [(9.0, "verified", 2, 100.0)])
    assert read("swap_digest_tail_ms", run) == pytest.approx(320.0)


def test_digest_tail_reads_nothing_from_an_older_program():
    # A program that stamps no verified event (or an operation that skipped
    # the digest: no digest asked for) reads None, and does not raise.
    run = run_of([(5.0, "delta_reuse", -1, 3.0), (5.5, "swap_verify", 55, 90.0)],
                 [])
    assert read("swap_digest_tail_ms", run) is None
    assert read("swap_digest_tail_ms", run_of()) is None


def test_reuse_copy_is_the_union_of_spans_side_by_side():
    # Four piece jobs side by side, 100 ms each, ending at 10.10, 10.11,
    # 10.12 and 10.13: they cover 10.00-10.13, 130 ms, where their sum is
    # 400. A fifth, alone, 10.50-10.55: 50 ms more. Union 180 ms.
    side_by_side = [(10.10 + 0.01 * k, "delta_reuse", k, 100.0)
                    for k in range(4)]
    alone = [(10.55, "delta_reuse", 4, 50.0)]
    # The chunk walk of an older program: three spans end to end, 10 ms
    # each: union = sum = 30 ms.
    walk = [(20.01 + 0.01 * k, "delta_reuse", -1, 10.0) for k in range(3)]
    assert read("swap_reuse_copy_ms", run_of(side_by_side + alone)) \
        == pytest.approx(180.0)
    assert read("swap_reuse_copy_ms", run_of(walk)) == pytest.approx(30.0)
    # Median per operation: of 180 and 30, 105.
    assert read("swap_reuse_copy_ms", run_of(side_by_side + alone, walk)) \
        == pytest.approx(105.0)
    assert read("swap_reuse_copy_ms", run_of([(1.0, "verified", 0, 5.0)])) \
        is None
