"""The reader ``swap_sums_carried_pct`` of the layer "hot-swap" on hand-made
operations, each number worked out by hand beside it (a file of its own: the
cases beside ``test_swap_layers.py``'s, which a PR that claims a gain may not
edit)."""

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


def swap_op(carried, pieces, nbytes=1843):
    """An operation as ``closed_loop_swap`` leaves it: ``swap["stats"]`` the
    program's ``HotSwapResult.stats`` copied whole, the ``swap_verify`` span's
    ``piece`` the pieces the gate compared."""
    stats = {"reused_bytes": 1700, "fetched_bytes": 143}
    if carried is not None:
        stats["host_sums_carried"] = carried
    flight = [(5.0, "verified", 0, 480.0)]
    if pieces is not None:
        flight.append((5.5, "swap_verify", pieces, 30.0))
    return types.SimpleNamespace(flight=flight, nbytes=nbytes,
                                 swap={"stats": stats})


def test_sums_carried_is_the_share_of_the_gates_pieces():
    # Every pair carried in each of three swaps of 55 pieces: 100 %.
    run = types.SimpleNamespace(ops=[swap_op(55, 55) for _ in range(3)])
    assert read("swap_sums_carried_pct", run) == pytest.approx(100.0)


def test_sums_carried_of_a_mixed_swap():
    # A swap resumed over 11 pieces the store had: 44 of 55 carried, 80 %.
    # Beside a whole one (100 %) and a walked one (0 %): the median is 80.
    run = types.SimpleNamespace(
        ops=[swap_op(44, 55), swap_op(55, 55), swap_op(0, 55)])
    assert read("swap_sums_carried_pct", run) == pytest.approx(80.0)
    assert read("swap_sums_carried_pct", types.SimpleNamespace(
        ops=[swap_op(44, 55)])) == pytest.approx(80.0)


def test_sums_carried_reads_nothing_from_an_older_program():
    # A program whose stats carry no such key (the parent), an operation
    # that is no swap, a swap whose gate never ran (no ``swap_verify``): None,
    # and no raise.
    older = types.SimpleNamespace(ops=[swap_op(None, 55), swap_op(None, 55)])
    assert read("swap_sums_carried_pct", older) is None
    assert read("swap_sums_carried_pct", run_of([(5.0, "verified", 0, 5.0)])) \
        is None
    assert read("swap_sums_carried_pct", types.SimpleNamespace(
        ops=[swap_op(55, None)])) is None
    assert read("swap_sums_carried_pct", run_of()) is None
    # The older operations do not dilute the ones that have the key.
    mixed = types.SimpleNamespace(ops=[swap_op(None, 55), swap_op(55, 55)])
    assert read("swap_sums_carried_pct", mixed) == pytest.approx(100.0)
