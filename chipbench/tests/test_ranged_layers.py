"""The ranged cell's names and readers: every name of the cell leads to a
file, the configuration states the program's own defaults and the plan that
ISSUE 34 reckoned, the five readers of the layer "ranged pull" are worked
out by hand on recorded flight events, they read nothing from an operation
of another driver or from a program that stamps none of their events, and
the rehearsal of the cell on the CPU runs the new driver end to end, with
``correct`` true when nothing is broken and false under either control."""

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

RANGED_LAYERS = ("rank_plan_ms", "rank_admit_wait_ms", "rank_task_fixed_ms",
                 "rank_registers_per_GB", "rank_views_ms")


def manifest() -> dict:
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def config_of(path: str) -> dict:
    with open(os.path.join(REPO, path)) as f:
        return json.load(f)


def test_the_cells_names_resolve():
    m = manifest()
    cell = next(w for w in m["workloads"] if w["name"] == "rank-cold")
    assert cell["chips"] == 1 and cell["traffic"] == "cold-1client-ranged"
    entry = next(c for c in m["configs"] if c["name"] == cell["config"])
    assert entry["reduced"] == ["num_hidden_layers", "n_routed_experts"]
    on_file = config_of(entry["file"])
    published = config_of("chipbench/configs/moonlight-shard-1p7g.json")
    # Every number of the published config but the two that are reduced.
    assert {k: v for k, v in on_file.items()
            if isinstance(v, (int, float)) and k not in entry["reduced"]
            } == {k: v for k, v in published.items()
                  if isinstance(v, (int, float))
                  and k not in entry["reduced"]}
    assert set(on_file["reduced"]) == set(entry["reduced"])
    parallel = on_file["deployment"]["expert_parallel"]
    assert on_file["n_routed_experts"] * parallel["ranks"] == \
        parallel["n_routed_experts_published"] == \
        published["n_routed_experts"]
    assert "digest" not in on_file["object"]
    assert {"only_selected", "bit_identical", "piece_digest_chain"} <= set(
        on_file["guarantees"])
    with open(os.path.join(BENCH, "traffic",
                           cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    assert (traffic["clients"], traffic["mode"]) == (1, "cold")
    assert traffic["trace"] == {"operations": 2}
    driver = importlib.import_module("drivers." + traffic["kind"])
    assert hasattr(driver, "warm_up") and hasattr(driver, "window")
    listed = {p["name"] for p in m["per_layer"]
              if "rank-cold" in p.get("workloads", [])}
    assert listed == set(RANGED_LAYERS)
    for name in RANGED_LAYERS:
        assert hasattr(importlib.import_module("layers." + name), "read")


def test_the_deployment_states_the_programs_own_defaults():
    from dragonfly2_tpu.client.device import download_sharded
    from dragonfly2_tpu.daemon.config import DaemonConfig

    deployment = config_of(
        "chipbench/configs/moonlight-ep4-rank.json")["deployment"]
    defaults = inspect.signature(download_sharded).parameters
    assert deployment["coalesce_gap"] == defaults["coalesce_gap"].default
    assert deployment["prefix_guess"] == defaults["prefix_guess"].default
    sink = DaemonConfig(work_home="/nowhere").tpu_sink
    assert deployment["sink"] == {"max_tasks": sink.max_tasks,
                                  "batch_pieces": sink.batch_pieces}


def test_the_plan_at_published_widths_is_issue_34s_table():
    """Reckoned from the header alone (no byte of a tensor is made)."""
    from origin import load_objects

    objects = load_objects(
        config_of("chipbench/configs/moonlight-ep4-rank.json"), 7)
    assert len(objects.tensors) == 4 * 204
    assert objects.length == 4_678_884_738
    plans = [objects.plan(rank) for rank in range(4)]
    assert [len(objects.selected(r)) for r in range(4)] == [240] * 4
    assert objects.size() == 1_356_895_232       # 29 % of the file
    assert [len(spans) for _, spans in plans] == [25, 16, 12, 16]
    inside, spans = plans[0]
    assert inside == ["model.layers.1.input_layernorm.weight"]
    sizes = sorted(end - start for start, end, _ in spans)
    expert = 3 * 5_767_168
    assert sizes[:20] == [expert] * 20            # one expert alone, 17.3 MB
    assert [round(s / 1e6, 1) for s in sizes[20:]] == [
        114.3, 138.4, 252.7, 252.7, 252.7]
    assert sum(sizes) + 4096 == objects.size()    # no gap is bridged
    # The four shares partition the routed experts and agree on the rest.
    names = [set(objects.selected(r)) for r in range(4)]
    shared = set.intersection(*names)
    assert all(".mlp.experts." not in n for n in shared)
    assert set.union(*names) == {n for n, _, _ in objects.tensors}
    assert sum(len(n - shared) for n in names) + len(shared) == 4 * 204


# -- the readers, on recorded flight events --------------------------------

def task(t, *, admit, first_request, pieces, tail, register_again=False):
    """One ranged task's flight: admitted at ``t`` after ``admit`` ms,
    registered, first request ``first_request`` ms later, ``pieces`` pieces
    10 ms apart, ``task_done`` ``tail`` ms after the last landed."""
    rows = [(t, "admit_wait", -1, admit), (t + 0.001, "register", -1, 0.0),
            (t + 0.003, "scheduled", -1, 0.0)]
    if register_again:
        rows.append((t + 0.004, "register", -1, 0.0))
    at = t + 0.001 + first_request / 1000.0
    for piece in range(pieces):
        rows += [(at, "request", piece, 0.0),
                 (at + 0.008, "hbm_start", piece, 0.0),
                 (at + 0.009, "hbm_landed", piece, 0.0)]
        at += 0.010
    done = at - 0.010 + 0.009 + tail / 1000.0
    rows.append((done, "task_done", -1, 0.0))
    return {"flight": rows}, done


def ranged_op(t0, plan_ms, views_ms, spans, nbytes=1_356_895_232):
    """An operation: the header's task, then ``spans`` tasks given as
    (admit ms, first request ms, pieces, tail ms)."""
    header, done = task(t0 + 0.001, admit=0.0, first_request=40.0, pieces=1,
                        tail=2.0)
    header["flight"].append((t0 + plan_ms / 1000.0, "shard_plan",
                             len(spans), plan_ms))
    ranged, at = [header], t0 + plan_ms / 1000.0
    for admit, first_request, pieces, tail in spans:
        row, done = task(at + admit / 1000.0, admit=admit,
                         first_request=first_request, pieces=pieces,
                         tail=tail)
        ranged.append(row)
    end = done + views_ms / 1000.0
    header["flight"].append((end, "shard_views", 240, views_ms))
    return types.SimpleNamespace(
        t0=t0, t1=end + 0.02, nbytes=nbytes, views_span=None, ranged=ranged,
        flight=sorted(e for row in ranged for e in row["flight"]))


# Fixed costs of the tasks, ms. Header 40 + 2 = 42 in every operation.
#   op A spans: 60+4, 50+6, 70+8        -> 42, 64, 56, 78  median 60
#   op B spans: 80+4, 90+6              -> 42, 84, 96      median 84
#   op C spans: 30+2, 30+4, 30+6, 30+8  -> 42, 32..38      median 36
OPS = [ranged_op(10.0, 100.0, 50.0, [(0.0, 60.0, 5, 4.0), (0.0, 50.0, 28, 6.0),
                                     (300.0, 70.0, 5, 8.0)]),
       ranged_op(20.0, 120.0, 70.0, [(0.0, 80.0, 5, 4.0),
                                     (500.0, 90.0, 27, 6.0)]),
       ranged_op(30.0, 110.0, 60.0, [(0.0, 30.0, 5, 2.0), (0.0, 30.0, 5, 4.0),
                                     (0.0, 30.0, 5, 6.0),
                                     (700.0, 30.0, 31, 8.0)])]
OPS[1].ranged[1]["flight"].insert(3, (20.2, "register", -1, 0.0))


def run_of(ops):
    return types.SimpleNamespace(ops=ops, trace=None,
                                 windows=[(op.t0, op.t1) for op in ops])


def read(name, run):
    return importlib.import_module("layers." + name).read(run)


@pytest.mark.parametrize("name, want", [
    ("rank_plan_ms", 110.0),               # the median of 100, 120, 110
    ("rank_admit_wait_ms", 500.0),         # of 300, 500, 700 (sums a task set)
    ("rank_task_fixed_ms", 60.0),          # of 60, 84, 36
    # registers: 4, 3 + the one sent round again, 5, over 1.356895232 GB.
    ("rank_registers_per_GB", 4 / 1.356895232),
    ("rank_views_ms", 60.0),               # of 50, 70, 60
])
def test_reader_on_recorded_flights(name, want):
    got = read(name, run_of(OPS))
    assert got is not None and got == pytest.approx(want, rel=1e-9)


@pytest.mark.parametrize("name", RANGED_LAYERS)
@pytest.mark.parametrize("what", ["another_driver", "older_program",
                                  "no_operation"])
def test_reader_reads_nothing_where_nothing_is_stamped(name, what):
    """An operation of ``Cell.operation`` (no ``op.ranged``), a program
    older than the events (tasks with neither stamp nor piece), or no
    operation at all: the line leaves the metric out, and nothing raises."""
    ops = {"another_driver": [types.SimpleNamespace(
               t0=50.0, t1=50.6, nbytes=10, views_span=None,
               flight=[(50.1, "register", -1, 0.0),
                       (50.45, "sink_finalize", 0, 450.0)])],
           "older_program": [types.SimpleNamespace(
               t0=50.0, t1=50.6, nbytes=0, views_span=None, flight=[],
               ranged=[{"flight": []}, {"flight": [
                   (50.2, "scheduled", -1, 0.0)]}])],
           "no_operation": []}[what]
    assert read(name, run_of(ops)) is None


# -- the rehearsal ---------------------------------------------------------

def rehearse(script: str, *extra: str) -> dict:
    """One whole run of the cell's rehearsal in a process of its own."""
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, script), *extra,
         "--manifest", os.path.join(BENCH, "rehearsal",
                                    "manifest-ranged.json"),
         "--workload", "tiny-rank-cold", "--seed", "2147484031",
         "--seconds", "1", "--trace", "0"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=600, cwd=REPO)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("script, extra, correct", [
    ("run.py", (), True),
    ("tests/control_ranged.py", ("--break", "flip"), False),
    ("tests/control_ranged.py", ("--break", "stray"), False),
], ids=["sound", "one_byte_of_one_span_flipped", "a_tensor_of_another_rank"])
def test_the_rehearsal_and_both_controls(script, extra, correct):
    line = rehearse(script, *extra)
    assert line["failed"] == 0 and line["attempted"] >= 2
    assert line["correct"] is correct, line
    assert line["metrics"] == {} and line["device"]["platform"] == "cpu"
