"""The four-chip cell's names and readers: every name of the cell leads to
a file, the readers of its layer are worked out by hand on a synthetic
``run`` (hand-made flights, a hand-made trace of four chip planes), they
read nothing from a program that places nothing on a mesh, and the
rehearsal of the cell on four virtual CPU devices runs the new driver end to
end, with ``correct`` true when nothing is broken and false under either
control."""

import importlib
import json
import os
import subprocess
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
sys.path[:0] = [BENCH]

MESH_LAYERS = ("replicate_ms", "chip_verify_ms", "ici_ms", "ici_roofline",
               "mesh_views_ms")
CONTENT = 1_843_431_563


def manifest() -> dict:
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def test_the_cells_names_resolve():
    m = manifest()
    cell = next(w for w in m["workloads"] if w["name"] == "shard-reland-4chip")
    assert cell["chips"] == 4 and cell["traffic"] == "reland-1client-4chip"
    config = next(c for c in m["configs"] if c["name"] == cell["config"])
    with open(os.path.join(REPO, config["file"])) as f:
        on_file = json.load(f)
    with open(os.path.join(BENCH, "configs",
                           "moonlight-shard-1p7g.json")) as f:
        one_chip = json.load(f)
    # The same object from the same generator: a seed gives the same bytes.
    assert on_file["object"] == one_chip["object"]
    assert {k: v for k, v in on_file.items() if isinstance(v, (int, float))
            } == {k: v for k, v in one_chip.items()
                  if isinstance(v, (int, float))}
    assert on_file["deployment"]["chips"] == 4
    assert "resident_on_every_chip" in on_file["guarantees"]
    with open(os.path.join(BENCH, "traffic",
                           cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    assert traffic["mesh"] == [4] and traffic["placement"] == "replicated"
    driver = importlib.import_module("drivers." + traffic["kind"])
    assert hasattr(driver, "warm_up") and hasattr(driver, "window")
    listed = {p["name"] for p in m["per_layer"]
              if "shard-reland-4chip" in p.get("workloads", [])}
    assert listed == set(MESH_LAYERS)
    for name in MESH_LAYERS:
        assert hasattr(importlib.import_module("layers." + name), "read")
    with open(os.path.join(BENCH, "peaks_ici.json")) as f:
        for kind, row in json.load(f).items():
            assert row["ici_bytes_per_s"] > 0 and row["source"], kind


def mesh_op(t0, fin, rep_ms, ver_ms, views_s, nbytes=CONTENT):
    """One operation placed on a mesh: a landing that ends at ``fin``, the
    fan-out, the verification, then the views."""
    rep_end = fin + rep_ms / 1000.0
    ver_end = rep_end + ver_ms / 1000.0
    return types.SimpleNamespace(
        t0=t0, t1=ver_end + views_s, nbytes=nbytes,
        views_span=(ver_end, ver_end + views_s),
        flight=[(fin, "sink_finalize", 0, (fin - t0) * 1000.0),
                (rep_end, "sink_replicate", 3, rep_ms),
                (ver_end, "sink_verify_chips", 4, ver_ms)])


def one_chip_op():
    """What a landing on one chip stamps (and the parent of the cell)."""
    return types.SimpleNamespace(
        t0=50.0, t1=50.6, nbytes=CONTENT, views_span=None,
        flight=[(50.45, "sink_finalize", 0, 450.0)])


OPS = [mesh_op(10.0, 10.45, 52.0, 4.0, 0.30),
       mesh_op(11.0, 11.44, 50.0, 5.0, 0.32),
       mesh_op(12.0, 12.46, 51.0, 4.5, 0.31)]

# Three operations, each with the sharded placement on chip 0 (6 ms) and
# the all-gather on all four, whose span on chip 0 is the longest: 44, 40
# and 42 ms there, 23-33 elsewhere. A run of the benchmark's own program
# and a run outside every operation count for nothing.
TRACE = {"host": [], "device": {
    "/device:TPU:0": {"XLA Modules": [
        ["jit__multi_slice(1)", 10.450, 0.006],
        ["jit__all_gather_jit(2)", 10.456, 0.044],
        ["jit__multi_slice(1)", 11.440, 0.006],
        ["jit__all_gather_jit(2)", 11.446, 0.040],
        ["jit__multi_slice(1)", 12.460, 0.006],
        ["jit__all_gather_jit(2)", 12.466, 0.042],
        ["jit_chipbench_piece_checksums(3)", 10.90, 0.004],
        ["jit__all_gather_jit(2)", 20.0, 0.5]]},
    "/device:TPU:1": {"XLA Modules": [
        ["jit__all_gather_jit(2)", 10.470, 0.023],
        ["jit__all_gather_jit(2)", 11.460, 0.023],
        ["jit__all_gather_jit(2)", 12.480, 0.023]]},
    "/device:TPU:2": {"XLA Modules": [
        ["jit__all_gather_jit(2)", 10.460, 0.033],
        ["jit__all_gather_jit(2)", 11.450, 0.033],
        ["jit__all_gather_jit(2)", 12.470, 0.033]]},
    "/device:TPU:3": {"XLA Modules": [
        ["jit__all_gather_jit(2)", 10.460, 0.033]]},
    "/device:TPU:0 SparseCore 0": {"XLA Modules": [
        ["jit__all_gather_jit(2)", 10.0, 0.9]]}}}


def run_of(ops, trace=None):
    return types.SimpleNamespace(
        ops=ops, trace=trace, windows=[(op.t0, op.t1) for op in ops],
        device_kind="TPU v5 lite")


def read(name, run):
    return importlib.import_module("layers." + name).read(run)


@pytest.mark.parametrize("name, want", [
    ("replicate_ms", 51.0),            # the median of 52, 50, 51
    ("chip_verify_ms", 4.5),           # of 4, 5, 4.5
    ("mesh_views_ms", 310.0),          # of 300, 320, 310
    # Chip 0's plane is the slowest: (6 + 44) + (6 + 40) + (6 + 42) ms
    # over three operations.
    ("ici_ms", 48.0),
    # 1,843,431,563 bytes at 200 GB/s are 9.217 ms.
    ("ici_roofline", 100.0 * (CONTENT / 200e9) / 0.048),
])
def test_reader_on_a_hand_made_run(name, want):
    got = read(name, run_of(OPS, TRACE))
    assert got is not None and got == pytest.approx(want, rel=1e-9)
    assert name != "ici_roofline" or got < 100.0


@pytest.mark.parametrize("name", MESH_LAYERS)
@pytest.mark.parametrize("what", ["one_chip", "no_operation"])
def test_reader_reads_nothing_where_nothing_was_placed_on_a_mesh(name, what):
    """One chip, or the parent of the cell: no event, no program, no span;
    the line leaves the metric out and nothing raises."""
    ops = [one_chip_op()] if what == "one_chip" else []
    trace = {"host": [], "device": {"/device:TPU:0": {"XLA Modules": [
        ["jit__assemble_checksum_jit(9)", 50.40, 0.011]]}}}
    assert read(name, run_of(ops, trace)) is None
    assert read(name, run_of(ops, None)) is None


def rehearse(script: str, *extra: str) -> dict:
    """One whole run of the cell's rehearsal in a process of its own, on
    four virtual CPU devices."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, script), *extra,
         "--manifest", os.path.join(BENCH, "rehearsal", "manifest-4chip.json"),
         "--workload", "tiny-shard-reland-4chip", "--seed", "2147484031",
         "--seconds", "1", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=600, cwd=REPO)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("script, extra, correct", [
    ("run.py", (), True),
    ("tests/control.py", ("--break", "flip"), False),
    ("tests/control_chip.py", ("--chip", "2"), False),
    ("tests/control_chip.py", ("--chip", "0"), False),
], ids=["sound", "landing_flipped", "chip_2_altered", "chip_0_altered"])
def test_the_rehearsal_on_four_devices_and_both_controls(script, extra,
                                                         correct):
    line = rehearse(script, *extra)
    assert line["failed"] == 0 and line["attempted"] >= 3
    assert line["correct"] is correct, line
    assert line["metrics"] == {} and line["device"] == {
        "platform": "cpu", "kind": "cpu", "count": 4,
        "memory_peak_bytes": None}
