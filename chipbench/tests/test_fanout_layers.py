"""The fan-out cell's names and readers: every name of the cell leads to a
file, the configuration states the deployment ISSUE 38 set out, the seven
readers of the layer "fan-out between peers" are worked out by hand on
recorded flights of nine daemons (a host served only by the seed, one served
only by fellow hosts), they read nothing where a daemon's flight is missing,
from an operation of another driver or from a program that stamps none of
their events, and the rehearsal of the cell on the CPU runs the new driver
end to end, with ``correct`` true when nothing is broken and false under
either control."""

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

FANOUT_LAYERS = ("fanout_peer_share_pct", "fanout_seed_upload_ms",
                 "fanout_first_piece_ms", "fanout_skew_ms",
                 "fanout_cert_wait_ms", "fanout_resched_per_host",
                 "fanout_origin_x")
CONTENT = 8 * 100          # bytes of the recorded object: 8 pieces of 100


def load(path: str) -> dict:
    with open(os.path.join(REPO, path)) as f:
        return json.load(f)


def test_the_cells_names_resolve():
    m = load("BENCHMARK.json")
    cell = next(w for w in m["workloads"] if w["name"] == "shard-cold-fanout")
    assert cell["chips"] == 1 and cell["traffic"] == "cold-8hosts-at-once"
    assert m["workloads"][-1] is cell and len(cell["why"]) <= 200
    entry = next(c for c in m["configs"] if c["name"] == cell["config"])
    assert m["configs"][-1] is entry and len(entry["source"]) <= 200
    assert entry["reduced"] == ["num_hidden_layers", "hosts_landing_in_hbm"]
    # No earlier deployment of the object begins its source the same way.
    assert not any(c["source"][:40] == entry["source"][:40]
                   for c in m["configs"][:-1])
    on_file = load(entry["file"])
    published = load("chipbench/configs/moonlight-shard-1p7g.json")
    assert {k: v for k, v in on_file.items()
            if isinstance(v, (int, float))} == {
                k: v for k, v in published.items()
                if isinstance(v, (int, float))}
    assert on_file["object"] == published["object"]
    assert on_file["source"] == entry["source"]
    assert set(on_file["reduced"]) == set(entry["reduced"])
    deployment = on_file["deployment"]
    assert deployment["hosts"] == 8 and deployment["chips"] == 1
    assert deployment["sink"] == published["deployment"]["sink"]
    assert on_file["guarantees"]["origin_amplification_max"] == 1.1
    assert {"every_host_complete", "nobody_back_to_source",
            "origin_once_for_all_hosts", "bit_identical",
            "piece_checksums_on_device"} <= set(on_file["guarantees"])
    traffic = load(f"chipbench/traffic/{cell['traffic']}.json")
    assert (traffic["kind"], traffic["clients"], traffic["mode"]) == (
        "closed_loop_fanout", 1, "cold")
    assert traffic["trace"] == {"operations": 1}
    driver = importlib.import_module("drivers." + traffic["kind"])
    assert hasattr(driver, "warm_up") and hasattr(driver, "window")
    listed = [p for p in m["per_layer"]
              if "shard-cold-fanout" in p.get("workloads", [])]
    assert [p["name"] for p in listed] == list(FANOUT_LAYERS)
    assert [p["name"] for p in m["per_layer"][-7:]] == list(FANOUT_LAYERS)
    assert all(p["layer"] == "fan-out between peers"
               and p["moves"] == "resident_MBps" for p in listed)
    for name in FANOUT_LAYERS:
        assert hasattr(importlib.import_module("layers." + name), "read")


# -- the readers, on recorded flights of nine daemons -----------------------

def host(t0, *, first_landed, done, seed=0, peer=0, origin=0, parents=1,
         cert=None, pushes=0, reschedules=0):
    """One host's flight of one operation: registered as it starts, first
    piece ``first_landed`` s after the request, ``task_done`` at ``done`` s;
    its ``task_sources`` just before."""
    rows = [(t0 + 0.001, "register", -1, 0.0, ""),
            (t0 + 0.004, "scheduled", -1, 0.0, "normal_task")]
    rows += [(t0 + 0.5 + 0.1 * i, "sched_push", -1, 0.0, "normal_task")
             for i in range(pushes)]
    rows += [(t0 + 0.6 + 0.1 * i, "reschedule", -1, 0.0, "")
             for i in range(reschedules)]
    rows += [(t0 + first_landed, "landed", 0, 12.0, "unlabeled"),
             (t0 + done - 0.3, "landed", 7, 11.0, "intra")]
    if cert is not None:
        rows.append((t0 + done - 0.002, "cert_wait", 1, cert, "certified"))
    rows += [(t0 + done - 0.001, "task_sources", parents, float(peer),
              f"seed={seed} peer={peer} origin={origin}"),
             (t0 + done, "task_done", -1, 0.0, "")]
    return sorted(rows)


def seed_flight(t0, sends, origin=CONTENT):
    """The seed's: ``sends`` as (end s after the request, ms); its own
    bytes all from the origin."""
    rows = [(t0 + 0.01, "register", -1, 0.0, ""),
            (t0 + 0.02, "back_source", -1, 0.0, "")]
    rows += [(t0 + end, "upload_serve", i, ms, "100")
             for i, (end, ms) in enumerate(sends)]
    rows += [(t0 + 3.0, "task_sources", 0, 0.0,
              f"seed=0 peer=0 origin={origin}"),
             (t0 + 3.001, "task_done", -1, 0.0, "")]
    return sorted(rows)


def fanout_op(t0, hosts, seed):
    rows = [{"host": i, "flight": f} for i, f in enumerate(hosts)]
    rows.append({"host": "seed", "flight": seed})
    return types.SimpleNamespace(t0=t0, t1=t0 + 9.0, nbytes=CONTENT,
                                 views_span=None, hosts=rows,
                                 flight=[e[:4] for e in hosts[0]])


def eight(t0, first, done, **kw):
    """Eight hosts: host 0 served ONLY by the seed, host 7 ONLY by fellow
    hosts (five of them), the others half and half."""
    out = []
    for i in range(8):
        if i == 0:
            took = dict(seed=CONTENT, peer=0, parents=1)
        elif i == 7:
            took = dict(seed=0, peer=CONTENT, parents=5)
        else:
            took = dict(seed=CONTENT // 2, peer=CONTENT // 2, parents=3)
        out.append(host(t0, first_landed=first[i], done=done[i], **took,
                        **{k: v[i] for k, v in kw.items()}))
    return out


# Op A: firsts 4.0 .. 4.7 (median 4.35), done 5.0 .. 5.7 (skew 700 ms),
#   cert waits 100 .. 800 (max 800), 2 pushes on host 3, 1 reschedule on
#   host 5 (3 / 8); the seed's sends (end, ms): (4.2, 200), (4.3, 200),
#   (4.8, 100): union 4.0-4.3 and 4.7-4.8 = 400 ms; origin 800 / 800.
# Op B: firsts all 5.0, done 6.0 .. 6.35 (skew 350), cert waits all 50,
#   none rescheduled; the seed's sends (5.5, 500), (6.5, 500): 1,000 ms;
#   host 2 went back to the source for 80 bytes: origin (800 + 80) / 800.
# Op C: as A at another time, with done 5.0 .. 6.4 (skew 1,400), cert max
#   900, one push a host (8 / 8), sends one of 300 ms.
# Peer share in all three: (6 * 400 + 800) / 6,400 = 50 % (B: host 2 took
#   80 more from the origin: 3,200 / 6,480).
A = fanout_op(100.0, eight(
    100.0, [4.0 + 0.1 * i for i in range(8)], [5.0 + 0.1 * i for i in range(8)],
    cert=[100.0 * (i + 1) for i in range(8)],
    pushes=[0, 0, 0, 2, 0, 0, 0, 0], reschedules=[0, 0, 0, 0, 0, 1, 0, 0]),
    seed_flight(100.0, [(4.2, 200.0), (4.3, 200.0), (4.8, 100.0)]))
B_hosts = eight(200.0, [5.0] * 8, [6.0 + 0.05 * i for i in range(8)],
                cert=[50.0] * 8)
B_hosts[2] = host(200.0, first_landed=5.0, done=6.1, seed=CONTENT // 2,
                  peer=CONTENT // 2, origin=80, parents=3, cert=50.0)
B = fanout_op(200.0, B_hosts,
              seed_flight(200.0, [(5.5, 500.0), (6.5, 500.0)]))
C = fanout_op(300.0, eight(
    300.0, [4.0 + 0.1 * i for i in range(8)], [5.0 + 0.2 * i for i in range(8)],
    cert=[900.0 - 100.0 * i for i in range(8)], pushes=[1] * 8),
    seed_flight(300.0, [(4.5, 300.0)]))
OPS = [A, B, C]


def run_of(ops):
    return types.SimpleNamespace(ops=ops, trace=None,
                                 windows=[(op.t0, op.t1) for op in ops])


def read(name, run):
    return importlib.import_module("layers." + name).read(run)


@pytest.mark.parametrize("name, want", [
    ("fanout_peer_share_pct", 50.0),         # of 50, 49.38, 50
    ("fanout_seed_upload_ms", 400.0),        # of 400, 1,000, 300
    ("fanout_first_piece_ms", 4350.0),       # of 4,350, 5,000, 4,350
    ("fanout_skew_ms", 700.0),               # of 700, 350, 1,400
    ("fanout_cert_wait_ms", 800.0),          # of 800, 50, 900
    ("fanout_resched_per_host", 3 / 8),      # of 3/8, 0, 1
    ("fanout_origin_x", 1.0),                # of 1.0, 1.1, 1.0
])
def test_reader_on_recorded_flights(name, want):
    got = read(name, run_of(OPS))
    assert got is not None and got == pytest.approx(want, rel=1e-9)


def test_one_operation_by_hand():
    """Op B alone: the host that went back to the source shows in both
    counts, and a host served only by fellow hosts in neither's way."""
    run = run_of([B])
    assert read("fanout_origin_x", run) == pytest.approx(880 / 800)
    assert read("fanout_peer_share_pct", run) == pytest.approx(
        100.0 * 3200 / 6480)
    assert read("fanout_seed_upload_ms", run) == pytest.approx(1000.0)
    assert read("fanout_skew_ms", run) == pytest.approx(350.0)


@pytest.mark.parametrize("name", FANOUT_LAYERS)
@pytest.mark.parametrize("what", ["missing_child_flight", "no_raw_reply",
                                  "another_driver", "older_program",
                                  "no_operation"])
def test_reader_reads_nothing_where_nothing_is_stamped(name, what):
    """A child whose flight did not come back, a program whose
    ``Daemon.FlightReport`` knows no ``raw`` (host 0's ring alone), an
    operation of ``Cell.operation`` (no ``op.hosts``), a program older than
    the events, or no operation at all: the line leaves the metric out, and
    nothing raises."""
    lone = fanout_op(400.0, eight(400.0, [4.0] * 8, [5.0] * 8),
                     seed_flight(400.0, [(4.5, 300.0)]))
    if what == "missing_child_flight":
        lone.hosts[4]["flight"] = None
        if name == "fanout_seed_upload_ms":
            # The seed's own span needs the seed's flight alone.
            assert read(name, run_of([lone])) == pytest.approx(300.0)
            return
    elif what == "no_raw_reply":
        for row in lone.hosts[1:]:
            row["flight"] = None
    elif what == "older_program":
        # Flights came back, with none of this PR's events (the seed's
        # upload_serve then carries bytes, not ms: not a span either).
        for row in lone.hosts:
            row["flight"] = [e for e in row["flight"]
                             if e[1] not in ("task_sources", "upload_serve",
                                             "landed", "task_done")]
        if name in ("fanout_cert_wait_ms", "fanout_resched_per_host"):
            # Events every program stamped: 0 where none fell.
            assert read(name, run_of([lone])) == 0.0
            return
    ops = {"another_driver": [types.SimpleNamespace(
               t0=50.0, t1=50.6, nbytes=10, views_span=None,
               flight=[(50.1, "register", -1, 0.0)])],
           "no_operation": []}.get(what, [lone])
    assert read(name, run_of(ops)) is None


# -- the rehearsal ---------------------------------------------------------

def rehearse(script: str, *extra: str) -> dict:
    """One whole run of the cell's rehearsal in a process of its own."""
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, script), *extra,
         "--manifest", os.path.join(BENCH, "rehearsal",
                                    "manifest-fanout.json"),
         "--workload", "tiny-shard-cold-fanout", "--seed", "2147484038",
         "--seconds", "1", "--trace", "0"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=600, cwd=REPO)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("script, extra, correct", [
    ("run.py", (), True),
    ("tests/control_fanout.py", ("--break", "flip"), False),
    ("tests/control_fanout.py", ("--break", "source"), False),
], ids=["sound", "one_byte_of_one_hosts_file_flipped",
        "one_host_back_to_source"])
def test_the_rehearsal_and_both_controls(script, extra, correct):
    line = rehearse(script, *extra)
    assert line["failed"] == 0 and line["attempted"] >= 2
    assert line["correct"] is correct, line
    assert line["metrics"] == {} and line["device"]["platform"] == "cpu"
