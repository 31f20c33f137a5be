"""The sharded-landing cell's names and readers: every name of the cell leads
to a file, the configuration is the published one with its depth alone
reduced and states the program's own defaults, the layout at published widths
is ISSUE 42's reckoning, the readers of the layer "sharded landing" are worked
out by hand on recorded events (a hop counted, a skew, a peak on chip 2), they
read nothing from an operation of another driver or from a program that
stamps none of their events, and the rehearsal of the cell on four virtual
CPU devices runs the new driver end to end, with ``correct`` true when nothing
is broken and false under each control."""

import importlib
import inspect
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

GLOBAL_LAYERS = ("global_plan_ms", "global_views_ms", "global_task_fixed_ms",
                 "global_hop_bytes_pct", "global_chip_skew_ms",
                 "global_peak_hbm_x", "global_ici_ms", "global_ici_roofline",
                 "global_assemble_roofline")
UNLISTED = ("assemble_ms", "device_idle_pct", "peak_hbm_x")


def manifest() -> dict:
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def config_of(path: str) -> dict:
    with open(os.path.join(REPO, path)) as f:
        return json.load(f)


def test_the_cells_names_resolve():
    m = manifest()
    cell = next(w for w in m["workloads"] if w["name"] == "host-reland-ep4")
    assert cell["chips"] == 4 and cell["traffic"] == "reland-1client-global"
    assert len(cell["why"]) <= 200
    entry = next(c for c in m["configs"] if c["name"] == cell["config"])
    assert entry["reduced"] == ["num_hidden_layers"]
    assert 0 < len(entry["source"]) <= 200
    assert sum(c["source"] == entry["source"] for c in m["configs"]) == 1
    on_file = config_of(entry["file"])
    published = config_of("chipbench/configs/moonlight-shard-1p7g.json")
    # Every number of the published config but the depth.
    assert {k: v for k, v in on_file.items()
            if isinstance(v, (int, float)) and k not in entry["reduced"]
            } == {k: v for k, v in published.items()
                  if isinstance(v, (int, float))
                  and k not in entry["reduced"]}
    assert list(on_file["reduced"]) == entry["reduced"]
    assert on_file["source"] == entry["source"]
    parallel = on_file["deployment"]["expert_parallel"]
    assert on_file["n_routed_experts"] == \
        parallel["n_routed_experts_published"] == 64
    assert parallel["ranks"] == on_file["deployment"]["chips"] == 4
    assert "digest" not in on_file["object"]
    assert {"piece_digest_chain", "piece_checksums_on_device",
            "bit_identical", "placement_exact", "resident_on_every_chip",
            "origin_amplification_max"} <= set(on_file["guarantees"])
    assert on_file["guarantees"]["origin_amplification_max"] == 1.1
    with open(os.path.join(BENCH, "traffic",
                           cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    assert (traffic["clients"], traffic["mode"]) == (1, "reland")
    assert traffic["trace"] == {"operations": 1}
    driver = importlib.import_module("drivers." + traffic["kind"])
    assert hasattr(driver, "warm_up") and hasattr(driver, "window")
    listed = [p for p in m["per_layer"]
              if "host-reland-ep4" in p.get("workloads", [])]
    assert [p["name"] for p in listed] == list(GLOBAL_LAYERS)
    for p in listed:
        assert p["layer"] == "sharded landing"
        assert p["moves"] == "resident_MBps"
        assert p["workloads"] == ["host-reland-ep4"]
        assert hasattr(importlib.import_module("layers." + p["name"]), "read")
    # The metrics with no list of cells are read in the new cell too.
    # ``assemble_roofline`` holds chip 0's assemblies against the whole
    # operation's bytes, four chips' here: it keeps to the cells it had
    # (the chip read 129.9 %), and ``global_assemble_roofline`` reads a chip
    # against its own bytes.
    assert {p["name"] for p in m["per_layer"] if "workloads" not in p} == set(
        UNLISTED)
    roofline = next(p for p in m["per_layer"]
                    if p["name"] == "assemble_roofline")
    assert roofline["workloads"] == [w["name"] for w in m["workloads"]
                                     if w["name"] != "host-reland-ep4"][:7]


def test_the_deployment_states_the_programs_own_defaults():
    from dragonfly2_tpu.client.device import download_global
    from dragonfly2_tpu.daemon.config import DaemonConfig

    deployment = config_of(
        "chipbench/configs/moonlight-ep4-host.json")["deployment"]
    defaults = inspect.signature(download_global).parameters
    assert deployment["prefix_guess"] == defaults["prefix_guess"].default
    sink = DaemonConfig(work_home="/nowhere").tpu_sink
    assert deployment["sink"] == {"max_tasks": sink.max_tasks,
                                  "batch_pieces": sink.batch_pieces}


def test_the_layout_at_published_widths_is_issue_42s_reckoning():
    """Reckoned from the header alone (no byte of a tensor is made): the
    file is moonlight-ep4-rank's, a chip's share is that cell's rank 0's."""
    from origin import load_objects

    host = load_objects(
        config_of("chipbench/configs/moonlight-ep4-host.json"), 7)
    rank = load_objects(
        config_of("chipbench/configs/moonlight-ep4-rank.json"), 7)
    assert host.head == rank.head and host.length == 4_678_884_738
    assert len(host.tensors) == 816 and host.chips == 4
    assert host.resident_bytes() == [1_356_895_232] * 4 == [rank.size()] * 4
    assert host.tensor_bytes_once() == host.length - host.data_start
    rest = sum(host.spans[n][1] - host.spans[n][0]
               for n, _, _ in host.tensors if host.chip_of(n) is None)
    assert rest == 249_598_976                     # 249.6 MB of the file
    assert round(100 * 3 * rest / sum(host.resident_bytes()), 1) == 13.8
    for chip in range(4):
        assert [n for n in host.selected() if chip in host.holders(n)] == \
            rank.selected(chip)
    assert host.size() == host.length


# -- the readers, on recorded events ---------------------------------------

def task(t, chips, *, wait, land, fan=0.0, verify=0.0, extra=5.0,
         size=100_000_000):
    """One ranged task's flight: admitted at ``t``, ``wait`` ms queued for
    the landing thread, ``land`` ms of finalize, then (where several chips
    want it) another 1 ms queued, ``fan`` ms of fan-out and ``verify`` ms of
    verification, and ``extra`` ms that no landing span covers."""
    at = t + (wait + land) / 1000.0
    rows = [(t, "admit_wait", -1, 0.0), (t + wait / 1000.0, "sink_wait", 0,
                                         wait),
            (at, "sink_finalize", 1, land)]
    inside = wait + land
    if fan:
        rows += [(at + 0.001, "sink_wait", 0, 1.0),
                 (at + 0.001 + fan / 1000.0, "sink_replicate", 3, fan),
                 (at + 0.001 + (fan + verify) / 1000.0, "sink_verify_chips",
                  4, verify)]
        at += 0.001 + (fan + verify) / 1000.0
        inside += 1.0 + fan + verify
    at += extra / 1000.0
    rows.append((at, "device_pull", chips[0], inside + extra))
    return {"flight": rows, "chips": list(chips), "start": 0,
            "end": size}, at


def global_op(t0, plan_ms, views_ms, spans, hop_bytes):
    """An operation: the header's task, then ``spans`` tasks given as
    (start ms after the plan, chips, wait, land, fan, verify, extra)."""
    header, _ = task(t0 + 0.001, (0,), wait=0.5, land=3.0, extra=2.0,
                     size=262_144)
    header["flight"].append((t0 + plan_ms / 1000.0, "shard_plan",
                             len(spans), plan_ms))
    ranged, last = [header], t0
    for start, chips, wait, land, fan, verify, extra in spans:
        row, done = task(t0 + (plan_ms + start) / 1000.0, chips, wait=wait,
                         land=land, fan=fan, verify=verify, extra=extra)
        ranged.append(row)
        last = max(last, done)
    end = last + views_ms / 1000.0
    header["flight"].append((end, "shard_views", 816, views_ms))
    return types.SimpleNamespace(
        t0=t0, t1=end + 0.02, nbytes=4_678_784_000, views_span=None,
        ranged=ranged, counts={"hop_bytes": hop_bytes,
                               "store_bytes": 4_678_884_738},
        chip_resident=[1_356_895_232] * 4,
        flight=sorted(e for row in ranged for e in
                      ((t, n, p, a) for t, n, p, a in row["flight"])))


# Fixed costs, ms: the header's 2 in every operation.
#   op A: 4, 6, 8 (fanned out)            -> 2, 4, 6, 8     median 5
#   op B: 10, 12                          -> 2, 10, 12      median 10
#   op C: 1, 1, 3, 3                      -> 2, 1, 1, 3, 3  median 2
# Chips complete (ms after the plan), and the skew between them:
#   op A: chip 1 at 0+2+20+4 = 26, chip 2 at 10+1+30+6 = 47, chips 0-3 at
#         50+3+40+1+12+4+8 = 118 -> every chip at 118 but for none: skew 0
#   op B: chip 0 at 0+5+25+10 = 40, chip 3 at 100+5+25+12 = 142  -> 102
#   op C: chip 0 at 34, chip 1 at 44, chip 2 at 56, chip 3 at 66  -> 32
OPS = [
    global_op(10.0, 100.0, 50.0, [
        (0.0, (1,), 2.0, 20.0, 0.0, 0.0, 4.0),
        (10.0, (2,), 1.0, 30.0, 0.0, 0.0, 6.0),
        (50.0, (0, 1, 2, 3), 3.0, 40.0, 12.0, 4.0, 8.0)], 750_000_000),
    global_op(20.0, 120.0, 70.0, [
        (0.0, (0,), 5.0, 25.0, 0.0, 0.0, 10.0),
        (100.0, (3,), 5.0, 25.0, 0.0, 0.0, 12.0)], 760_000_000),
    global_op(30.0, 110.0, 60.0, [
        (0.0, (0,), 3.0, 30.0, 0.0, 0.0, 1.0),
        (10.0, (1,), 3.0, 30.0, 0.0, 0.0, 1.0),
        (20.0, (2,), 3.0, 30.0, 0.0, 0.0, 3.0),
        (30.0, (3,), 3.0, 30.0, 0.0, 0.0, 3.0)], 4_070_000_000)]
# In op C the header's task is on chip 0 alone, and its own task ends later.
RESIDENT = 4 * 1_356_895_232


def trace_of(ops):
    """Four chips' planes: the fan-out's two programs in every operation,
    40 ms + 10 ms on chip 3's plane and half of that on the others'."""
    device = {}
    for chip in range(4):
        scale = 1.0 if chip == 3 else 0.5
        rows = []
        for op in ops:
            rows += [["jit__all_gather_jit(1)", op.t0 + 0.2, 0.040 * scale],
                     ["jit__multi_slice(2)", op.t0 + 0.15, 0.010 * scale],
                     ["jit__assemble_checksum_jit(3)", op.t0 + 0.1,
                      0.005 if chip else 0.002]]
        device[f"/device:TPU:{chip}"] = {"XLA Modules": rows, "XLA Ops": []}
    return {"device": device, "host": []}


def run_of(ops, peaks=(1.7e9, 1.6e9, 2.4e9, 1.5e9), traced=True):
    return types.SimpleNamespace(
        ops=ops, trace=trace_of(ops) if traced else None,
        peaks={"hbm_bytes_per_s": 819e9},
        windows=[(op.t0, op.t1) for op in ops], device_kind="TPU v5 lite",
        cell=types.SimpleNamespace(chip_peaks=list(peaks)))


def read(name, run):
    return importlib.import_module("layers." + name).read(run)


@pytest.mark.parametrize("name, want", [
    ("global_plan_ms", 110.0),             # the median of 100, 120, 110
    ("global_views_ms", 60.0),             # of 50, 70, 60
    ("global_task_fixed_ms", 5.0),         # of 5, 10, 2
    # a hop counted: 750, 760, 4,070 MB over 5,427.58 MB resident.
    ("global_hop_bytes_pct", 100 * 760_000_000 / RESIDENT),
    ("global_chip_skew_ms", 32.0),         # of 0, 102, 32
    ("global_peak_hbm_x", 2.4e9 / 1_356_895_232),   # a peak on chip 2
    ("global_ici_ms", 50.0),               # chip 3's plane, an operation
    # the bytes into one chip, (750 + 760 + 4,070) / 3 / 3 MB an operation,
    # at 200 GB/s, over those 50 ms
    ("global_ici_roofline",
     100 * (5_580_000_000 / 9 / 200e9) / 0.050),
    # the chips' planes hold 2 ms (chip 0) and 5 ms (chips 1-3) an operation;
    # of the slowest, the first: chip 1, on which 100 MB landed in ops A and
    # C and nothing in op B: 2 x (200 MB / 3) at 819 GB/s over 5 ms
    ("global_assemble_roofline",
     100 * (2 * 200_000_000 / 3 / 819e9) / 0.005),
])
def test_reader_on_recorded_events(name, want):
    got = read(name, run_of(OPS))
    assert got is not None and got == pytest.approx(want, rel=1e-9)


def test_a_task_fanned_out_completes_every_chip_it_lies_on():
    from layers import global_events

    done = global_events.chip_done(OPS[0])
    assert sorted(done) == [0, 1, 2, 3] and len(set(done.values())) == 1
    assert global_events.fixed_ms(OPS[0].ranged[3]["flight"]) == \
        pytest.approx(8.0)
    # A landing span of another pull of the same task, before this one's
    # admission, is no part of it.
    flight = [(1.0, "sink_finalize", 1, 30.0)] + OPS[1].ranged[1]["flight"]
    assert global_events.fixed_ms(flight) == pytest.approx(10.0)


@pytest.mark.parametrize("name", GLOBAL_LAYERS)
@pytest.mark.parametrize("what", ["another_driver", "older_program",
                                  "no_operation"])
def test_reader_reads_nothing_where_nothing_is_stamped(name, what):
    """An operation of ``Cell.operation`` (no ``op.ranged``, no counts), a
    program older than the events and counters (tasks that name no chip, no
    counter, no peak by chip, no fan-out in the trace), or no operation at
    all: the line leaves the metric out, and nothing raises."""
    ops = {"another_driver": [types.SimpleNamespace(
               t0=50.0, t1=50.6, nbytes=10, views_span=None,
               flight=[(50.1, "register", -1, 0.0),
                       (50.45, "sink_finalize", 0, 450.0)])],
           "older_program": [types.SimpleNamespace(
               t0=50.0, t1=50.6, nbytes=0, views_span=None, flight=[],
               counts={}, chip_resident=[],
               ranged=[{"flight": []}, {"flight": [
                   (50.2, "sink_finalize", 0, 100.0)]}])],
           "no_operation": []}[what]
    run = types.SimpleNamespace(
        ops=ops, trace={"device": {"/device:TPU:0": {
            "XLA Modules": [["jit__assemble_checksum_jit(3)", 50.1, 0.005]],
            "XLA Ops": []}}, "host": []},
        windows=[(op.t0, op.t1) for op in ops], device_kind="TPU v5 lite",
        peaks={"hbm_bytes_per_s": 819e9}, cell=types.SimpleNamespace())
    assert read(name, run) is None


# -- the rehearsal ---------------------------------------------------------

def rehearse(script: str, *extra: str) -> dict:
    """One whole run of the cell's rehearsal in a process of its own."""
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, script), *extra,
         "--manifest", os.path.join(BENCH, "rehearsal",
                                    "manifest-global.json"),
         "--workload", "tiny-host-reland-ep4", "--seed", "2147484042",
         "--seconds", "1", "--trace", "0"],
        env=dict(os.environ, JAX_PLATFORMS="cpu",
                 XLA_FLAGS="--xla_force_host_platform_device_count=4"),
        capture_output=True, text=True, timeout=600, cwd=REPO)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("script, extra, correct", [
    ("run.py", (), True),
    ("tests/control_global.py", ("--break", "flip"), False),
    ("tests/control_global.py", ("--break", "copy"), False),
    ("tests/control_global.py", ("--break", "misplace"), False),
], ids=["sound", "one_byte_of_one_span_flipped", "chip_2s_copy_of_the_rest",
        "an_expert_on_another_chip"])
def test_the_rehearsal_and_the_controls(script, extra, correct):
    line = rehearse(script, *extra)
    assert line["failed"] == 0 and line["attempted"] >= 3
    assert line["correct"] is correct, line
    assert line["metrics"] == {} and line["device"]["platform"] == "cpu"
    assert line["device"]["count"] == 4
