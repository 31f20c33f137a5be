"""The readers of the layer "checkpoint save / resume" on hand-made
operations, each number worked out by hand beside it, and the cell's names:
every reader of the thirteen is a file beside ``save_events.py``, reads
nothing (and does not raise) from an operation of a program that stamps none
of the save's events, and the control's breaks are the ones its docstring
names."""

import importlib
import json
import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [BENCH]

METRICS = ["save_stall_ms", "save_pack_ms", "save_pack_roofline",
           "save_d2h_ms", "save_digest_ms", "save_commit_ms",
           "save_replicate_ms", "save_ack_ms", "save_host_copies_x",
           "save_peak_hbm_x", "resume_ms", "resume_views_ms",
           "resume_flat_view_pct"]
GB = 1_000_000_000


def read(name, run):
    return importlib.import_module("layers." + name).read(run)


def run_of(*ops, trace=None, windows=()):
    return types.SimpleNamespace(
        ops=list(ops), trace=trace, windows=list(windows),
        peaks={"hbm_bytes_per_s": 800e9})


def operation(t0=100.0):
    """One operation of 2 GB: the handle after 50 ms (the pack 40 of them);
    two groups copied side by side, 100.05-100.45 and 100.25-100.65 (600 ms
    covered, 800 summed); four piece commits of 200 ms, two by two, ending
    at 100.6, 100.6, 100.9, 100.9 (400 ms covered); the digest's thread two
    groups end to end, 300 ms each; Finished answered after 2.5 s, at 103.6;
    the copy lost at 103.7; views 105.0-105.2; ready at 105.2."""
    flight = [
        (t0 + 0.045, "save_pack", 237, 40.0),
        (t0 + 0.05, "save_snapshot", 60, 50.0),
        (t0 + 0.45, "save_d2h", 0, 400.0), (t0 + 0.65, "save_d2h", 4, 400.0),
        (t0 + 0.6, "save_commit", 0, 200.0), (t0 + 0.6, "save_commit", 1, 200.0),
        (t0 + 0.9, "save_commit", 4, 200.0), (t0 + 0.9, "save_commit", 5, 200.0),
        (t0 + 0.75, "save_digest", 0, 300.0), (t0 + 1.05, "save_digest", 4, 300.0),
        (t0 + 3.6, "save_replicated", 2, 2500.0),
        (t0 + 4.0, "landed", 0, 30.0)]
    return types.SimpleNamespace(
        t0=t0, t1=t0 + 5.2, nbytes=2 * GB, flight=flight, t_lost=t0 + 3.7,
        views_span=(t0 + 5.0, t0 + 5.2), save_hbm=4.5 * GB,
        counted={"save_content": 2 * GB, "save_d2h": 2.1 * GB,
                 "save_copied": 0, "save_stored": 2 * GB,
                 "views_rows": 0.25 * GB, "views_flat": 1.75 * GB})


def test_the_spans_and_counters_read_as_reckoned():
    run = run_of(operation())
    assert read("save_stall_ms", run) == pytest.approx(50.0)
    assert read("save_d2h_ms", run) == pytest.approx(600.0)
    assert read("save_commit_ms", run) == pytest.approx(400.0)
    assert read("save_digest_ms", run) == pytest.approx(600.0)
    assert read("save_replicate_ms", run) == pytest.approx(2500.0)
    assert read("save_ack_ms", run) == pytest.approx(3600.0)
    assert read("save_host_copies_x", run) == pytest.approx(2.05)
    assert read("save_peak_hbm_x", run) == pytest.approx(2.25)
    assert read("resume_ms", run) == pytest.approx(1500.0)
    assert read("resume_views_ms", run) == pytest.approx(200.0)
    assert read("resume_flat_view_pct", run) == pytest.approx(87.5)


def test_the_pack_is_read_from_the_trace_against_three_passes():
    # Two operations; the pack's programs ran 4 + 1 ms inside the first and
    # 5 + 2 ms inside the second, and once outside any operation (a warm-up:
    # left out): 12 ms over two operations, 6 ms each. Three passes over
    # 2 GB at 800 GB/s are 7.5 ms: 125 % of 6 (a share above 100 is the
    # reader's to show and the driver's to refuse, not to be clipped).
    modules = [["jit__save_pack_jit(1)", 100.01, 0.004],
               ["jit__save_pack_sums_jit(2)", 100.02, 0.001],
               ["jit__save_pack_jit(1)", 110.01, 0.005],
               ["jit__save_pack_sums_jit(2)", 110.02, 0.002],
               ["jit__save_pack_jit(1)", 90.0, 0.5],
               ["jit__assemble_checksum_jit(3)", 104.0, 0.02]]
    trace = {"device": {"/device:TPU:0": {"XLA Modules": modules}},
             "host": []}
    run = run_of(operation(100.0), operation(110.0), trace=trace,
                 windows=[(100.0, 105.2), (110.0, 115.2)])
    assert read("save_pack_ms", run) == pytest.approx(6.0)
    assert read("save_pack_roofline", run) == pytest.approx(125.0)


@pytest.mark.parametrize("name", METRICS)
def test_a_reader_reads_nothing_from_an_older_program(name):
    """An operation as the harness's own ``Op`` has it, with the flight of a
    plain landing: no save event, no counter of the driver's."""
    plain = types.SimpleNamespace(
        t0=1.0, t1=2.0, nbytes=GB, views_span=None,
        flight=[(1.5, "landed", 0, 30.0), (1.9, "verified", 2, 100.0)])
    assert read(name, run_of(plain)) is None
    assert read(name, run_of()) is None


def test_the_thirteen_are_the_manifests_and_the_cell_is_named():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        manifest = json.load(f)
    mine = [m for m in manifest["per_layer"]
            if m["layer"] == "checkpoint save / resume"]
    assert [m["name"] for m in mine] == METRICS
    assert all(m["workloads"] == ["ckpt-save-resume"]
               and m["moves"] == "resident_MBps" for m in mine)
    cell = next(w for w in manifest["workloads"]
                if w["name"] == "ckpt-save-resume")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "moonlight-trainstate-ep4", "save-resume-1client", 1)
    with open(os.path.join(BENCH, "rehearsal", "manifest-save.json")) as f:
        tiny = json.load(f)
    assert tiny["workloads"][0]["traffic"] == cell["traffic"]


def test_the_configuration_states_what_it_must():
    with open(os.path.join(BENCH, "configs",
                           "moonlight-trainstate-ep4.json")) as f:
        config = json.load(f)
    with open(os.path.join(BENCH, "configs", "moonlight-ep4-rank.json")) as f:
        published = json.load(f)
    # Moonlight's widths unchanged; what is cut is listed.
    differs = {k for k, v in published.items()
               if isinstance(v, (int, float, str, bool, type(None)))
               and k not in ("name", "source", "object_source")
               and config.get(k) != v}
    assert differs == {"num_hidden_layers"} and set(config["reduced"]) == {
        "num_hidden_layers", "n_routed_experts"}
    assert config["num_hidden_layers"] == 1
    assert config["deployment"]["expert_parallel"] == {
        "ranks": 4, "rank": 0, "n_routed_experts_published": 64}
    assert config["n_routed_experts"] == 16
    assert config["guarantees"]["copies_between_hosts_max"] == 1.05
    assert len(config["source"]) <= 200
    objects = importlib.import_module("objects.train_state_rank")
    state = objects.Objects(config, 1)
    floats = sum(n for (_, dtype, _), n in zip(
        state.table, (state.nbytes[t[0]] for t in state.table))
        if dtype == "F32")
    assert len(state.table) == 237 and 2.37e9 < state.length < 2.38e9
    assert 0.855 < floats / sum(state.nbytes.values()) < 0.86
