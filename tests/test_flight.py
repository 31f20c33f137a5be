"""Flight recorder: event rings, critical-path autopsy, debug endpoints,
pod-level straggler attribution, and the chaos-seeded black-box e2e.

The acceptance case: a degraded download (stalled parent + one corrupt
body, seeded via pkg/chaos) must yield a /debug/flight/<task_id> autopsy
whose phase breakdown sums to the task's wall time (±5%) with ``stall``
dominant — and ``dfget --explain`` renders the same waterfall end-to-end.
"""

from __future__ import annotations

import asyncio
import dis
import gc
import json
import math
import time
import weakref

import pytest

from dragonfly2_tpu.pkg import chaos as chaos_mod
from dragonfly2_tpu.pkg import flight
from dragonfly2_tpu.pkg import tracing
from dragonfly2_tpu.storage import (
    StorageManager,
    StorageOption,
    TaskStoreMetadata,
)


@pytest.fixture(autouse=True)
def _chaos_disabled():
    chaos_mod.disable()
    yield
    chaos_mod.disable()


def synthetic(events, wall):
    """A TaskFlight with a hand-authored event timeline (tuples of
    (t, code, piece, aux, note)) — analyzer tests need exact clocks."""
    tf = flight.TaskFlight("synthetic")
    for e in events:
        tf._ring[next(tf._seq) % tf._cap] = e
    tf.state = "done"
    tf._end_pc = wall
    return tf


# --------------------------------------------------------------------- #
# Recorder core: bounds, eviction, hot-path allocation guard
# --------------------------------------------------------------------- #

class TestRecorderBounds:
    def test_ring_and_index_stay_capped_under_soak(self):
        """Thousands of pieces across dozens of tasks: the per-task ring
        never grows, the piece-timing index stays capped, and the global
        task index evicts instead of growing."""
        rec = flight.FlightRecorder(capacity=128, max_tasks=8)
        for t in range(40):
            tf = rec.task(f"soak-{t}")
            for n in range(3000):
                tf.record(flight.EV_REQUEST, n, 0.0, "10.0.0.1:80")
                tf.record(flight.EV_LANDED, n, 1.0, "cross")
            if t % 2 == 0:
                rec.finish_task(f"soak-{t}", "done")
        assert len(rec._tasks) <= 8
        for tf in rec._tasks.values():
            assert len(tf._ring) == 128          # preallocated, never grew
            assert len(tf._piece_track) <= tf._piece_cap
            assert tf.events_total == 6000
            assert tf.events_dropped == 6000 - 128
            assert len(tf.events()) == 128

    def test_eviction_releases_memory(self):
        rec = flight.FlightRecorder(capacity=32, max_tasks=4)
        probe = rec.task("probe")
        probe.finish("done")
        ref = weakref.ref(probe)
        del probe
        for i in range(8):
            rec.task(f"filler-{i}")
        gc.collect()
        assert ref() is None, "evicted TaskFlight still referenced"

    def test_eviction_prefers_finished_tasks(self):
        rec = flight.FlightRecorder(capacity=32, max_tasks=2)
        rec.task("running-1")
        rec.task("done-1").finish("done")
        rec.task("new-1")
        assert "running-1" in rec._tasks and "done-1" not in rec._tasks

    def test_a_ring_that_is_no_tasks_outlives_every_task(self):
        """A loop's account (``runtime:loop:*``) lies outside the index of
        tasks: 200 finished and running flights later, with the index at
        its cap and evicting running ones, it is the same ring, read like
        any flight and stamped by nobody but its owner."""
        rec = flight.FlightRecorder(capacity=32, max_tasks=8)
        ring = rec.ring("runtime:loop:ut", 64)
        assert rec.ring("runtime:loop:ut") is ring      # get-or-create
        assert len(ring._ring) == 64
        ring.record(flight.EV_LOOP_ACCT, 4000, 5.0, "late=0.0 gc=0.0 it=9 n=9")
        for i in range(200):
            rec.task(f"churn-{i}")
            if i % 2:
                rec.finish_task(f"churn-{i}", "done")
            rec.stamp_running(flight.EV_GC_PAUSE, 0.06, "gc_pause")
        assert len(rec._tasks) == 8
        assert rec.get("churn-0") is None               # evicted, running
        assert rec.get("runtime:loop:ut") is ring
        assert "runtime:loop:ut" not in rec._tasks
        assert [e[1] for e in ring.events()] == [flight.EV_LOOP_ACCT]
        assert rec.finish_task("runtime:loop:ut", "done") is None
        assert ring.state == "running"
        index = {row["task_id"]: row for row in rec.summary()}
        assert index["runtime:loop:ut"]["events"] == 1
        assert len(index) == 9

    def test_stamp_running_dates_an_event_that_ended_before_now(self):
        rec = flight.FlightRecorder(capacity=32, max_tasks=8)
        tf = rec.task("held")
        ended = time.perf_counter() - 0.5
        rec.stamp_running(flight.EV_LOOP_LAG, 0.3, "held n=1 gc=0.0 who=?",
                          7, ended)
        rec.stamp_running(flight.EV_GC_PAUSE, 0.06, "gc_pause")
        (t, code, piece, aux, note), (t_gc, *_rest) = tf.events()
        assert (code, piece, aux) == (flight.EV_LOOP_LAG, 7, 0.3)
        assert t == pytest.approx(ended - tf._start_pc) and t < 0 <= t_gc

    def test_record_allocates_no_dicts_on_hot_path(self):
        """The always-on contract: one tuple per event, no per-event dict
        construction in the record bytecode."""
        ops = {i.opname
               for i in dis.get_instructions(flight.TaskFlight.record)}
        assert "BUILD_MAP" not in ops and "MAP_ADD" not in ops, ops

    def test_finish_is_idempotent_and_observes_histogram(self):
        from dragonfly2_tpu.pkg import metrics as metrics_mod

        rec = flight.FlightRecorder()
        tf = rec.task("hist-t")
        tf.record(flight.EV_REQUEST, 0, 0.0, "p")
        tf.record(flight.EV_LANDED, 0, 5.0, "cross")
        rec.finish_task("hist-t", "done")
        wall_first = tf.wall_s()
        rec.finish_task("hist-t", "failed")   # no-op: already terminal
        assert tf.state == "done"
        assert tf.wall_s() == wall_first
        text = metrics_mod.render()[0].decode()
        assert "dragonfly_tpu_peer_task_phase_seconds" in text


# --------------------------------------------------------------------- #
# Analyzer: the phase fold
# --------------------------------------------------------------------- #

class TestAnalyzer:
    def test_phases_partition_wall_exactly(self):
        tf = synthetic([
            (0.0, flight.EV_REGISTER, -1, 0.0, ""),
            (1.0, flight.EV_SCHEDULED, -1, 0.0, "normal_task"),
            (1.0, flight.EV_REQUEST, 0, 0.0, "1.1.1.1:80"),
            (1.1, flight.EV_FIRST_BYTE, 0, 0.0, ""),
            (2.0, flight.EV_LANDED, 0, 900.0, "cross"),
            (2.0, flight.EV_STORE_START, 0, 0.0, ""),
            (2.5, flight.EV_STORED, 0, 0.0, ""),
            (2.5, flight.EV_VERIFY_START, -1, 0.0, ""),
            (3.0, flight.EV_VERIFIED, -1, 0.0, ""),
        ], wall=4.0)
        rep = flight.analyze(tf)
        p = rep["phases"]
        assert p["sched_wait"] == pytest.approx(1.0)
        assert p["dcn"] == pytest.approx(1.0)
        assert p["store"] == pytest.approx(0.5)
        assert p["verify"] == pytest.approx(0.5)
        assert rep["other_s"] == pytest.approx(1.0)
        assert sum(p.values()) + rep["other_s"] == pytest.approx(4.0)
        assert rep["dominant_phase"] in ("dcn", "sched_wait")

    def test_overlap_priority_work_beats_waiting(self):
        """A stall that overlaps a concurrent healthy transfer did not
        cost wall time: the dcn segment wins the overlap."""
        tf = synthetic([
            (0.0, flight.EV_REQUEST, 0, 0.0, "a:1"),
            # piece 0 never produces: request..failed(stall) at 2.0
            (0.0, flight.EV_REQUEST, 1, 0.0, "b:1"),
            (0.1, flight.EV_FIRST_BYTE, 1, 0.0, ""),
            (1.0, flight.EV_LANDED, 1, 1000.0, "cross"),
            (2.0, flight.EV_FAILED, 0, 0.0, "stall"),
        ], wall=2.0)
        rep = flight.analyze(tf)
        assert rep["phases"]["dcn"] == pytest.approx(1.0)
        assert rep["phases"]["stall"] == pytest.approx(1.0)
        assert rep["dominant_phase"] in ("dcn", "stall")

    def test_slow_first_byte_splits_into_stall(self):
        tf = synthetic([
            (0.0, flight.EV_REQUEST, 0, 0.0, "a:1"),
            (1.0, flight.EV_FIRST_BYTE, 0, 0.0, ""),
            (1.2, flight.EV_LANDED, 0, 1200.0, "cross"),
        ], wall=1.2)
        rep = flight.analyze(tf)
        assert rep["phases"]["stall"] == pytest.approx(1.0)
        assert rep["phases"]["dcn"] == pytest.approx(0.2)
        assert rep["dominant_phase"] == "stall"

    def test_intra_slice_transfers_fold_into_ici(self):
        tf = synthetic([
            (0.0, flight.EV_REQUEST, 0, 0.0, "a:1"),
            (0.5, flight.EV_LANDED, 0, 500.0, "intra"),
        ], wall=0.5)
        rep = flight.analyze(tf)
        assert rep["phases"]["ici"] == pytest.approx(0.5)
        assert rep["dominant_phase"] == "ici"

    def test_origin_interval_from_cost(self):
        tf = synthetic([
            (2.0, flight.EV_SOURCE_LANDED, 0, 1500.0, ""),
        ], wall=2.0)
        rep = flight.analyze(tf)
        assert rep["phases"]["origin"] == pytest.approx(1.5)

    def test_open_request_tail_is_the_black_box_stall(self):
        """A request still unanswered when the task ends (the classic
        black-box failure) classifies its tail as stall."""
        tf = synthetic([
            (0.0, flight.EV_REQUEST, 0, 0.0, "a:1"),
        ], wall=3.0)
        rep = flight.analyze(tf)
        assert rep["phases"]["stall"] == pytest.approx(3.0)

    def test_waterfall_rows_and_render(self):
        tf = synthetic([
            (0.0, flight.EV_REQUEST, 0, 0.0, "a:1"),
            (0.5, flight.EV_FAILED, 0, 0.0, "corrupt"),
            (0.5, flight.EV_REQUEST, 0, 0.0, "b:1"),
            (0.9, flight.EV_LANDED, 0, 400.0, "cross"),
        ], wall=1.0)
        rep = flight.analyze(tf)
        row = rep["pieces"][0]
        assert row["attempts"] == 2
        assert row["status"] == "ok"
        assert row["reason"] == "corrupt"   # the retry's cause stays visible
        assert row["parent"] == "b:1"
        text = flight.render_waterfall(rep)
        assert "phase breakdown:" in text
        assert "p0" in text and "x2 ok" in text

    def test_waterfall_truncates_past_cap(self):
        events = []
        for n in range(600):
            events.append((n * 0.001, flight.EV_REQUEST, n, 0.0, "a:1"))
            events.append((n * 0.001 + 0.0005, flight.EV_LANDED, n, 1.0,
                           "cross"))
        tf = synthetic(events, wall=1.0)
        rep = flight.analyze(tf)
        assert len(rep["pieces"]) == 256 and rep["pieces_truncated"]


# --------------------------------------------------------------------- #
# The completion path: cert_wait, the parent's spans, the sync stream's relay
# --------------------------------------------------------------------- #

COMPLETION_EVENTS = {
    flight.EV_SOURCE_FIRST_BYTE: "source_first_byte",
    flight.EV_CERT_WAIT: "cert_wait",
    flight.EV_PARENT_DONE: "parent_done",
    flight.EV_PARENT_SOURCE_FIRST_BYTE: "parent_source_first_byte",
    flight.EV_PARENT_VERIFIED: "parent_verified",
}

# A cold pull as the peer sees it: scheduled, the seed's first announcement
# after 4 s, two pieces, then 1.8 s in _await_certification for the seed's
# done, which carries the seed's verify.
COLD_PULL = [
    (0.00, flight.EV_REGISTER, -1, 0.0, ""),
    (0.01, flight.EV_SCHEDULED, -1, 0.0, "normal"),
    (4.00, flight.EV_PARENT_SOURCE_FIRST_BYTE, 0, 3950.0, ""),
    (4.00, flight.EV_PARENT_SOURCE_FIRST_BYTE, 14, 3990.0, ""),
    (4.00, flight.EV_PARENT_PIECES, 0, 1.0, ""),
    (4.01, flight.EV_REQUEST, 0, 0.0, "s:1"),
    (4.50, flight.EV_LANDED, 0, 490.0, "cross"),
    (4.50, flight.EV_REQUEST, 1, 0.0, "s:1"),
    (5.00, flight.EV_LANDED, 1, 500.0, "cross"),
    (6.79, flight.EV_PARENT_VERIFIED, 41, 1750.0, ""),
    (6.79, flight.EV_PARENT_DONE, 55, 0.0, ""),
    (6.80, flight.EV_CERT_WAIT, 1, 1800.0, "certified"),
]


class TestCompletionSpans:
    @pytest.mark.parametrize("code,name", sorted(COMPLETION_EVENTS.items()))
    def test_names_round_trip(self, code, name):
        assert flight.EVENT_NAMES[code] == name
        assert [c for c, n in flight.EVENT_NAMES.items() if n == name] == [code]
        tf = flight.TaskFlight("names")
        tf.record(code, 3, 12.5, "x")
        (_, got, piece, aux, note), = tf.events()
        assert (got, piece, aux, note) == (code, 3, 12.5, "x")

    def test_parent_spans_name_the_child_events(self):
        assert flight.PARENT_SPANS == {
            "source_first_byte": flight.EV_PARENT_SOURCE_FIRST_BYTE,
            "verified": flight.EV_PARENT_VERIFIED}
        for name, code in flight.PARENT_SPANS.items():
            assert flight.EVENT_NAMES[code] == "parent_" + name

    def test_cert_wait_is_booked_as_verify_and_leaves_other(self):
        without = flight.analyze(synthetic(COLD_PULL[:-1], wall=6.81))
        with_it = flight.analyze(synthetic(COLD_PULL, wall=6.81))
        assert without["phases"]["verify"] == 0.0
        assert with_it["phases"]["verify"] == pytest.approx(1.8)
        assert without["other_s"] - with_it["other_s"] == pytest.approx(1.8)
        for rep in (without, with_it):
            assert sum(rep["phases"].values()) + rep["other_s"] == \
                pytest.approx(rep["wall_s"])
        # The parent's spans are durations on ITS clock: no phase of this
        # task moves by them.
        bare = [e for e in COLD_PULL if e[1] not in COMPLETION_EVENTS]
        assert flight.analyze(synthetic(bare, wall=6.81))["phases"] == \
            without["phases"]

    def test_own_first_byte_wait_is_origin_time(self):
        rep = flight.analyze(synthetic([
            (0.0, flight.EV_BACK_SOURCE, -1, 0.0, ""),
            (2.0, flight.EV_SOURCE_FIRST_BYTE, 0, 1990.0, ""),
            (2.5, flight.EV_SOURCE_LANDED, 0, 500.0, ""),
        ], wall=2.5))
        assert rep["phases"]["origin"] == pytest.approx(2.49)
        assert rep["other_s"] == pytest.approx(0.01)
        assert rep["parent"] == {}

    def test_parent_block_and_the_explain_line(self):
        rep = flight.analyze(synthetic(COLD_PULL, wall=6.81))
        assert rep["parent"] == {
            "cert_wait_ms": 1800.0, "cert_wait": "certified",
            "verified_ms": 1750.0, "hash_behind": 41, "pieces": 55,
            "source_first_byte_ms": 3950.0}
        assert rep["event_counts"]["parent_source_first_byte"] == 2
        text = flight.render_waterfall(rep)
        assert ("cert_wait=1800.0 ms (certified); seed verify 1750.0 ms, "
                "41 of 55 pieces behind; origin first byte 3950.0 ms") in text
        # A pull that ends at its bound has no parent span to show.
        timed_out = [e for e in COLD_PULL[:-3]] + [
            (8.0, flight.EV_CERT_WAIT, 0, 3000.0, "timeout")]
        text = flight.render_waterfall(
            flight.analyze(synthetic(timed_out, wall=8.0)))
        assert "cert_wait=3000.0 ms (timeout); origin first byte" in text
        assert "seed verify" not in text

    def test_a_report_without_the_events_is_unchanged(self):
        bare = [e for e in COLD_PULL if e[1] not in COMPLETION_EVENTS]
        rep = flight.analyze(synthetic(bare, wall=6.81))
        assert rep["parent"] == {}
        assert rep["other_s"] == pytest.approx(6.81 - 0.01 - 0.99)
        text = flight.render_waterfall(rep)
        assert "cert_wait" not in text and "completion" not in text
        # A parent that only said done (a task with no digest to certify)
        # adds no line either.
        done_only = bare + [(5.0, flight.EV_PARENT_DONE, 55, 0.0, "")]
        rep = flight.analyze(synthetic(done_only, wall=6.81))
        assert rep["parent"] == {"pieces": 55}
        assert flight.render_waterfall(rep) == text


class TestSpanRelay:
    def test_a_span_is_sent_once(self):
        tf = flight.TaskFlight("seed")
        relay = flight.SpanRelay(tf)
        assert relay.take() == []
        tf.record(flight.EV_BACK_SOURCE)
        tf.record(flight.EV_SOURCE_FIRST_BYTE, 0, 40.0, "native")
        tf.record(flight.EV_SOURCE_FIRST_BYTE, 14, 55.5)
        tf.record(flight.EV_SOURCE_LANDED, 0, 45.0)
        assert relay.take() == [["source_first_byte", 40.0, 0],
                                ["source_first_byte", 55.5, 14]]
        tf.record(flight.EV_SOURCE_LANDED, 1, 45.0)
        assert relay.take() == []            # the next message carries none
        tf.record(flight.EV_VERIFY_START, 14, 41.0)
        assert relay.take() == []            # a verify still open is no span
        tf.record(flight.EV_VERIFIED, 40, 1750.25, "prefix")
        assert relay.take() == [["verified", 1750.25, 41]]
        assert relay.take() == []

    def test_a_stream_opened_late_gets_what_closed_before_it(self):
        tf = flight.TaskFlight("seed")
        tf.record(flight.EV_SOURCE_FIRST_BYTE, 0, 40.0)
        first, late = flight.SpanRelay(tf), flight.SpanRelay(tf)
        assert first.take() == [["source_first_byte", 40.0, 0]]
        assert late.take() == [["source_first_byte", 40.0, 0]]

    def test_a_wrapped_ring_resends_nothing(self):
        """A slot another thread has taken and not yet written still holds
        the event of a lap ago: the relay tells it by its time."""
        tf = flight.TaskFlight("seed", capacity=8)
        relay = flight.SpanRelay(tf)
        tf.record(flight.EV_SOURCE_FIRST_BYTE, 0, 40.0)
        assert len(relay.take()) == 1
        for n in range(7):
            tf.record(flight.EV_SOURCE_LANDED, n, 1.0)
        assert relay.take() == []
        next(tf._seq)     # slot 0 handed out again, the old event still in it
        assert relay.take() == []
        assert flight.SpanRelay(tf).take() == [["source_first_byte", 40.0, 0]]

    def test_tail_is_by_slot_and_bounded_by_the_ring(self):
        tf = flight.TaskFlight("t", capacity=4)
        for n in range(3):
            tf.record(flight.EV_LANDED, n, 1.0)
        events, seen = tf.tail(0)
        assert [e[2] for e in events] == [0, 1, 2] and seen == 3
        assert tf.tail(seen) == ([], 3)
        for n in range(3, 9):
            tf.record(flight.EV_LANDED, n, 1.0)
        events, seen = tf.tail(seen)
        assert [e[2] for e in events] == [5, 6, 7, 8] and seen == 9


# --------------------------------------------------------------------- #
# Post-mortem bundles
# --------------------------------------------------------------------- #

def _read_bundle(path):
    import gzip

    if str(path).endswith(".gz"):
        with gzip.open(path, "rt") as f:
            return json.load(f)
    return json.loads(path.read_text())


class TestPostmortem:
    def test_failure_dumps_bounded_gzip_bundles(self, tmp_path):
        rec = flight.FlightRecorder(capacity=64, max_tasks=64,
                                    dump_dir=str(tmp_path), keep_bundles=5)
        rec.scorecard_snapshot = {"serve_ewma_ms": 12.5, "straggler": False}
        for i in range(9):
            tf = rec.task(f"boom-{i}")
            tf.record(flight.EV_REQUEST, 0, 0.0, "a:1")
            rec.finish_task(f"boom-{i}", "failed", note="chaos ate it")
        bundles = sorted(tmp_path.glob("flight-*.json.gz"))
        assert 0 < len(bundles) <= 5, bundles
        doc = _read_bundle(bundles[-1])
        assert doc["report"]["state"] == "failed"
        assert doc["report"]["note"] == "chaos ate it"
        names = [e["event"] for e in doc["events"]]
        assert "request" in names and "task_failed" in names
        # The subject host's fleet scorecard rides in the bundle — the
        # failure autopsy carries the host's fleet-wide standing.
        assert doc["scorecard"]["serve_ewma_ms"] == 12.5

    def test_rotation_keeps_the_newest_bundles(self, tmp_path):
        """The dump dir is a ring, not a landfill: with keep_bundles=3,
        nine failures leave exactly the three NEWEST bundles on disk
        (mtime-ordered; same-second ties break on the filename stamp)."""
        import os

        rec = flight.FlightRecorder(dump_dir=str(tmp_path), keep_bundles=3)
        for i in range(9):
            tf = rec.task(f"rot-{i}")
            tf.record(flight.EV_REQUEST, 0, 0.0, "a:1")
            rec.finish_task(f"rot-{i}", "failed")
            # Force a strict mtime order even on coarse filesystems.
            for j, p in enumerate(sorted(
                    tmp_path.glob("flight-*.json.gz"))):
                os.utime(p, (1000 + j, 1000 + j))
        survivors = sorted(tmp_path.glob("flight-*.json.gz"))
        assert len(survivors) == 3
        kept_tasks = {_read_bundle(p)["report"]["task_id"]
                      for p in survivors}
        assert kept_tasks == {"rot-6", "rot-7", "rot-8"}

    def test_rotation_counts_json_and_gz_alike(self, tmp_path):
        """Pre-gzip-era ``.json`` bundles and fresh ``.json.gz`` ones
        share ONE rotation budget: with keep_bundles=4, three legacy
        plain bundles plus four fresh failures leave exactly the four
        newest files — the oldest legacies are reaped, not grandfathered
        into a second budget."""
        import os

        for i in range(3):
            p = tmp_path / f"flight-legacy-{i}-{i}.json"
            p.write_text(json.dumps({"report": {"task_id": f"legacy-{i}"}}))
            os.utime(p, (500 + i, 500 + i))
        rec = flight.FlightRecorder(dump_dir=str(tmp_path), keep_bundles=4)
        for i in range(4):
            tf = rec.task(f"mix-{i}")
            tf.record(flight.EV_REQUEST, 0, 0.0, "a:1")
            rec.finish_task(f"mix-{i}", "failed")
            for j, p in enumerate(sorted(
                    tmp_path.glob("flight-mix-*.json.gz"))):
                os.utime(p, (1000 + j, 1000 + j))
        rec._prune()
        survivors = sorted(str(p.name) for p in tmp_path.glob("flight-*"))
        assert len(survivors) == 4, survivors
        kept = {_read_bundle(tmp_path / name)["report"]["task_id"]
                for name in survivors}
        assert kept == {"mix-0", "mix-1", "mix-2", "mix-3"}

    def test_default_rotation_budget_is_32(self):
        assert flight.FlightRecorder().keep_bundles == 32
        from dragonfly2_tpu.daemon.config import DaemonConfig

        assert DaemonConfig().flight_keep_bundles == 32

    def test_success_does_not_dump(self, tmp_path):
        rec = flight.FlightRecorder(dump_dir=str(tmp_path))
        rec.task("fine")
        rec.finish_task("fine", "done")
        assert not list(tmp_path.glob("flight-*"))


# --------------------------------------------------------------------- #
# Pod aggregation (scheduler side)
# --------------------------------------------------------------------- #

class TestPodAggregator:
    def test_straggler_attribution_and_quarantine_correlation(self):
        agg = flight.PodAggregator()
        # Host A: fast, dcn-bound; host B: few pieces, stall-bound.
        for _ in range(10):
            agg.note_piece("t1", "host-a", {"dcn_ms": 20, "stall_ms": 0,
                                            "store_ms": 5})
        for _ in range(2):
            agg.note_piece("t1", "host-b", {"dcn_ms": 30, "stall_ms": 900,
                                            "store_ms": 5})
        agg.note_failure("t1", "host-c", "corrupt")
        agg.note_quarantine("t1", "host-c", "corrupt")
        rep = agg.report("t1")
        assert rep["slowest_host"] == "host-b"
        assert rep["dominant_phase"] == "stall"
        by_host = {h["host"]: h for h in rep["hosts"]}
        assert by_host["host-b"]["dominant_phase"] == "stall"
        assert by_host["host-c"]["failures"] == {"corrupt": 1}
        assert rep["quarantine"] == [{"host": "host-c", "reason": "corrupt"}]
        assert agg.report("nope") is None

    def test_legacy_report_without_timings_counts_as_dcn(self):
        agg = flight.PodAggregator()
        agg.note_piece("t2", "h", None, cost_ms=40)
        rep = agg.report("t2")
        assert rep["hosts"][0]["ms"]["dcn"] == 40

    def test_bounded_task_index(self):
        agg = flight.PodAggregator(max_tasks=4)
        for i in range(20):
            agg.note_piece(f"t{i}", "h", None, 1)
        assert len(agg._tasks) <= 4

    def test_scheduler_feeds_aggregator_from_piece_reports(self, run_async):
        from dragonfly2_tpu.scheduler.config import SchedulerConfig
        from dragonfly2_tpu.scheduler.service import SchedulerService

        async def body():
            svc = SchedulerService(SchedulerConfig())
            mk = lambda host, peer: {  # noqa: E731
                "host": {"id": host, "hostname": host, "ip": "10.0.0.1",
                         "port": 1, "upload_port": 2},
                "peer_id": peer, "task_id": "pod-task", "url": "http://o/f"}
            host_a, task, peer_a = svc._resolve(mk("host-a", "peer-a"))
            _hb, _t, peer_b = svc._resolve(mk("host-b", "peer-b"))
            svc._handle_pieces_finished({"pieces": [
                {"piece_num": 0, "range_start": 0, "range_size": 4,
                 "download_cost_ms": 25,
                 "timings": {"dcn_ms": 20, "stall_ms": 0, "store_ms": 5}},
                {"piece_num": 1, "range_start": 4, "range_size": 4,
                 "download_cost_ms": 1000,
                 "timings": {"dcn_ms": 100, "stall_ms": 880,
                             "store_ms": 20}},
            ]}, task, peer_a)
            svc._handle_piece_finished({"piece": {
                "piece_num": 0, "range_start": 0, "range_size": 4,
                "download_cost_ms": 7,
                "timings": {"dcn_ms": 7, "stall_ms": 0}}}, task, peer_b)
            # Typed failure against a known parent host.
            svc._handle_piece_failed({"piece_num": 2, "parent_id": "peer-b",
                                      "temporary": False,
                                      "reason": "corrupt"}, task, peer_a)
            rep = svc.pod_flight.report("pod-task")
            assert rep is not None
            by_host = {h["host"]: h for h in rep["hosts"]}
            assert by_host["host-a"]["pieces"] == 2
            assert by_host["host-a"]["dominant_phase"] == "stall"
            assert rep["slowest_host"] == "host-a"
            assert by_host["host-b"]["failures"] == {"corrupt": 1}
            # One corrupt strike quarantines the host — correlated.
            assert rep["quarantine"] == [{"host": "host-b",
                                          "reason": "corrupt"}]

        run_async(body(), timeout=30)


# --------------------------------------------------------------------- #
# Debug endpoints
# --------------------------------------------------------------------- #

class TestDebugEndpoints:
    def test_flight_and_pod_routes(self, run_async):
        import aiohttp

        from dragonfly2_tpu.pkg.metrics_server import MetricsServer

        async def body():
            rec = flight.FlightRecorder()
            tf = rec.task("dbg-task")
            tf.record(flight.EV_REQUEST, 0, 0.0, "a:1")
            tf.record(flight.EV_LANDED, 0, 12.0, "cross")
            rec.finish_task("dbg-task", "done")
            agg = flight.PodAggregator()
            agg.note_piece("dbg-task", "host-a",
                           {"dcn_ms": 12, "stall_ms": 0})
            srv = MetricsServer(flight=rec, pod_flight=agg)
            port = await srv.serve("127.0.0.1", 0)
            base = f"http://127.0.0.1:{port}"
            try:
                async with aiohttp.ClientSession() as sess:
                    async with sess.get(f"{base}/debug/flight") as r:
                        assert r.status == 200
                        idx = await r.json()
                    assert any(t["task_id"] == "dbg-task"
                               for t in idx["tasks"])
                    async with sess.get(f"{base}/debug/flight/dbg-task") as r:
                        assert r.status == 200
                        rep = await r.json()
                    assert rep["state"] == "done"
                    assert set(rep["phases"]) == set(flight.PHASES)
                    async with sess.get(
                            f"{base}/debug/flight/dbg-task?format=text") as r:
                        text = await r.text()
                    assert "phase breakdown:" in text
                    async with sess.get(f"{base}/debug/flight/absent") as r:
                        assert r.status == 404
                    # A loop's account is served by its name, like a task.
                    rec.ring("runtime:loop:daemon").record(
                        flight.EV_LOOP_LAG, 3, 0.05,
                        "held n=1 gc=0.0 who=device_feed.py:_land:207")
                    async with sess.get(
                            f"{base}/debug/flight/runtime:loop:daemon"
                            "?raw=1") as r:
                        assert r.status == 200
                        own = await r.json()
                    assert [e[1] for e in own["events"]] == ["loop_lag"]
                    async with sess.get(f"{base}/debug/pod/dbg-task") as r:
                        assert r.status == 200
                        pod = await r.json()
                    assert pod["hosts"][0]["host"] == "host-a"
            finally:
                await srv.close()

        run_async(body(), timeout=60)

    def test_routes_404_without_providers(self, run_async):
        import aiohttp

        from dragonfly2_tpu.pkg.metrics_server import MetricsServer

        async def body():
            srv = MetricsServer()
            port = await srv.serve("127.0.0.1", 0)
            try:
                async with aiohttp.ClientSession() as sess:
                    for path in ("/debug/flight", "/debug/flight/x",
                                 "/debug/pod/x"):
                        async with sess.get(
                                f"http://127.0.0.1:{port}{path}") as r:
                            assert r.status == 404, path
            finally:
                await srv.close()

        run_async(body(), timeout=60)


# --------------------------------------------------------------------- #
# Wire schema
# --------------------------------------------------------------------- #

class TestWireSchema:
    def test_piece_timings_field(self):
        from dragonfly2_tpu.proto import wire

        wire.validate_stream_msg("Scheduler.AnnouncePeer", {
            "type": "piece_finished",
            "piece": {"piece_num": 1, "range_start": 0, "range_size": 4,
                      "download_cost_ms": 9,
                      "timings": {"dcn_ms": 7, "stall_ms": 0,
                                  "store_ms": 2}}})
        with pytest.raises(wire.SchemaError, match="timings"):
            wire.validate_stream_msg("Scheduler.AnnouncePeer", {
                "type": "piece_finished",
                "piece": {"piece_num": 1, "timings": 7}})

    def test_flight_report_schema(self):
        from dragonfly2_tpu.proto import wire

        wire.validate_unary("Daemon.FlightReport", {"task_id": "t"})
        with pytest.raises(wire.SchemaError, match="task_id"):
            wire.validate_unary("Daemon.FlightReport", {})


# --------------------------------------------------------------------- #
# Chaos-seeded degraded download: the /debug/flight acceptance case
# --------------------------------------------------------------------- #

class _ParentDaemon:
    """Minimal real parent: storage with the completed task + the REAL
    peer rpc (SyncPieceTasks) and upload (piece HTTP) servers."""

    def __init__(self, rpc, upload, storage, peer_id):
        self.rpc = rpc
        self.upload = upload
        self.storage = storage
        self.peer_id = peer_id

    @property
    def wire(self):
        return {"id": self.peer_id,
                "host": {"ip": "127.0.0.1",
                         "port": self.rpc.peer_server.port(),
                         "upload_port": self.upload.port}}

    async def close(self):
        await self.rpc.close()
        await self.upload.close()
        self.storage.close()


async def _start_parent(tmp_path, name, task_id, content, piece_size):
    from dragonfly2_tpu.daemon.peer.piece_manager import PieceManager
    from dragonfly2_tpu.daemon.peer.task_manager import TaskManager
    from dragonfly2_tpu.daemon.rpcserver import DaemonRpcServer
    from dragonfly2_tpu.daemon.upload import UploadManager
    from dragonfly2_tpu.pkg.types import NetAddr

    storage = StorageManager(
        StorageOption(data_dir=str(tmp_path / f"{name}-data")))
    total = math.ceil(len(content) / piece_size)
    store = storage.register_task(TaskStoreMetadata(
        task_id=task_id, peer_id=name, url="http://origin/blob",
        piece_size=piece_size, content_length=len(content),
        total_piece_count=total))
    for n in range(total):
        store.write_piece(n, content[n * piece_size:(n + 1) * piece_size])
    store.mark_done()
    tm = TaskManager(storage, PieceManager())
    rpc = DaemonRpcServer(tm)
    await rpc.serve_peer(NetAddr.tcp("127.0.0.1", 0))
    upload = UploadManager(storage)
    await upload.serve("127.0.0.1", 0)
    return _ParentDaemon(rpc, upload, storage, name)


class TestChaosAutopsyE2E:
    def test_stalled_parent_autopsy_names_stall(self, run_async, tmp_path):
        """Stalled parent + one corrupt body: the task still completes;
        the autopsy's phases sum to wall time (±5%) and name ``stall``
        dominant; /debug/flight serves the same report."""
        import random

        import aiohttp

        from tests.test_chaos import FakeAnnounceStream, FakeSchedulerClient
        from dragonfly2_tpu.daemon.peer.conductor import PeerTaskConductor
        from dragonfly2_tpu.daemon.peer.piece_downloader import (
            PieceDownloader,
        )
        from dragonfly2_tpu.daemon.peer.piece_manager import PieceManager
        from dragonfly2_tpu.pkg.metrics_server import MetricsServer

        piece_size = 8192
        content = bytes(random.Random(42).randbytes(6 * piece_size))
        task_id = "flight-chaos-task"

        async def body():
            parent_a = await _start_parent(tmp_path, "parent-a", task_id,
                                           content, piece_size)
            parent_b = await _start_parent(tmp_path, "parent-b", task_id,
                                           content, piece_size)
            child_storage = StorageManager(
                StorageOption(data_dir=str(tmp_path / "child-data")))
            store = child_storage.register_task(TaskStoreMetadata(
                task_id=task_id, peer_id="child-peer",
                url="http://origin/blob"))
            announce = FakeAnnounceStream([{
                "type": "normal_task",
                "task": {"content_length": len(content),
                         "piece_size": piece_size,
                         "total_piece_count": 6},
                "parents": [parent_a.wire, parent_b.wire],
            }])
            sched = FakeSchedulerClient([announce])
            conductor = PeerTaskConductor(
                task_id=task_id, peer_id="child-peer",
                url="http://origin/blob", store=store,
                scheduler_client=sched, piece_manager=PieceManager(),
                host_info={"id": "child-host"}, disable_back_source=True)
            # A fast watchdog so the seeded stall trips in ~1s, not 10.
            await conductor.downloader.close()
            conductor.downloader = PieceDownloader(idle_timeout=1.0)

            # The seeded schedule: parent A's FIRST piece body goes silent
            # (30s > the watchdog) and one body anywhere arrives corrupt.
            chaos_mod.enable(chaos_mod.parse_spec({"seed": 7, "rules": [
                {"site": "piece.body", "kind": "stall", "rate": 1.0,
                 "stall_s": 30.0, "max_fires": 1,
                 "key_substr": f":{parent_a.upload.port}|"},
                {"site": "piece.body", "kind": "corrupt", "at": [1],
                 "max_fires": 1,
                 "key_substr": f":{parent_b.upload.port}|"},
            ]}))
            try:
                await conductor.run()
                assert store.is_complete()
                assert store.read_range(0, len(content)) == content
                flight.recorder().finish_task(task_id, "done")

                fabric = chaos_mod.enabled()
                kinds = fabric.injected_by_kind()
                assert kinds.get("stall", 0) == 1, kinds
                assert kinds.get("corrupt", 0) == 1, kinds

                # The autopsy, served exactly as operators reach it.
                srv = MetricsServer(flight=flight.recorder())
                port = await srv.serve("127.0.0.1", 0)
                try:
                    async with aiohttp.ClientSession() as sess:
                        async with sess.get(
                                f"http://127.0.0.1:{port}/debug/flight/"
                                f"{task_id}") as r:
                            assert r.status == 200
                            rep = await r.json()
                        async with sess.get(
                                f"http://127.0.0.1:{port}/debug/flight/"
                                f"{task_id}?format=text") as r:
                            text = await r.text()
                finally:
                    await srv.close()

                # Phase breakdown sums to the task wall time (±5%) ...
                covered = sum(rep["phases"].values())
                assert covered + rep["other_s"] == \
                    pytest.approx(rep["wall_s"], rel=1e-6)
                assert covered >= 0.95 * rep["wall_s"], rep
                # ... and the stalled parent is named the dominant cause.
                assert rep["dominant_phase"] == "stall", rep["phases"]
                assert rep["phases"]["stall"] >= 0.5 * rep["wall_s"]
                counts = rep["event_counts"]
                assert counts.get("failed", 0) >= 2   # stall + corrupt
                rows = {p["piece"]: p for p in rep["pieces"]}
                assert len(rows) == 6
                assert all(p["status"] == "ok" for p in rows.values())
                assert any(p["reason"] == "stall" for p in rows.values())
                assert "dominant=stall" in text
                # Piece reports carried the per-phase timings upstream for
                # the scheduler's pod aggregation.
                reported = []
                for m in announce.sent:
                    if m.get("type") == "piece_finished":
                        reported.append(m["piece"])
                    elif m.get("type") == "pieces_finished":
                        reported.extend(m["pieces"])
                assert len(reported) == 6
                assert any("timings" in p and p["timings"].get("dcn_ms", -1)
                           >= 0 for p in reported), reported
            finally:
                chaos_mod.disable()
                await parent_a.close()
                await parent_b.close()
                child_storage.close()

        run_async(body(), timeout=120)


# --------------------------------------------------------------------- #
# dfget --explain: the same waterfall end-to-end
# --------------------------------------------------------------------- #

class TestDfgetExplain:
    def test_explain_renders_waterfall_end_to_end(self, run_async,
                                                  tmp_path):
        from dragonfly2_tpu.client import dfget as dfget_lib
        from dragonfly2_tpu.daemon.peer.piece_manager import PieceManager
        from dragonfly2_tpu.daemon.peer.task_manager import TaskManager
        from dragonfly2_tpu.daemon.rpcserver import DaemonRpcServer
        from dragonfly2_tpu.pkg.testing import start_range_origin
        from dragonfly2_tpu.pkg.types import NetAddr

        async def body():
            content = b"flight" * 4096
            origin, url, _stats = await start_range_origin(content)
            storage = StorageManager(
                StorageOption(data_dir=str(tmp_path / "d-data")))
            tm = TaskManager(storage, PieceManager())
            rpc = DaemonRpcServer(tm)
            sock = str(tmp_path / "daemon.sock")
            await rpc.serve_download(NetAddr.unix(sock))
            out = str(tmp_path / "out.bin")
            try:
                result = await dfget_lib.download(dfget_lib.DfgetConfig(
                    url=url, output=out, daemon_sock=sock, explain=True,
                    allow_source_fallback=False))
                assert result["state"] == "done"
                assert open(out, "rb").read() == content
                fl = result["flight"]
                rep = fl["report"]
                assert rep["task_id"] == result["task_id"]
                assert rep["state"] == "done"
                # No scheduler: the time went to origin, and the autopsy
                # says so.
                assert rep["phases"]["origin"] > 0
                assert rep["dominant_phase"] == "origin"
                # The CLI prints fl["text"]; it is EXACTLY the renderer's
                # output for this report — the same waterfall /debug/flight
                # serves.
                assert fl["text"] == flight.render_waterfall(rep)
                assert "phase breakdown:" in fl["text"]
            finally:
                from dragonfly2_tpu.source.client import default_registry

                await rpc.close()
                storage.close()
                await origin.cleanup()
                await default_registry().close_all()

        run_async(body(), timeout=120)


# --------------------------------------------------------------------- #
# Traceparent across the piece HTTP hop
# --------------------------------------------------------------------- #

class TestPieceHopTracing:
    def test_upload_serve_joins_the_requesters_trace(self, run_async,
                                                     tmp_path):
        from dragonfly2_tpu.daemon.peer.piece_downloader import (
            PieceDownloader,
        )
        from dragonfly2_tpu.daemon.upload import UploadManager

        async def body():
            storage = StorageManager(
                StorageOption(data_dir=str(tmp_path / "p-data")))
            store = storage.register_task(TaskStoreMetadata(
                task_id="trace-task", peer_id="p", url="http://o/f",
                piece_size=4, content_length=4, total_piece_count=1))
            store.write_piece(0, b"abcd")
            store.mark_done()
            # rate_limit forces the aiohttp server (the native one cannot
            # extract headers).
            upload = UploadManager(storage, rate_limit=1 << 40)
            port = await upload.serve("127.0.0.1", 0)
            dl = PieceDownloader()
            tracing.exporter().clear()
            try:
                with tracing.span("client.pull") as sp:
                    chunks, size, _cost, _dig = await dl.download_piece(
                        "127.0.0.1", port, "trace-task", 0, expected_size=4)
                assert size == 4
                serve = tracing.exporter().find(name="upload.serve")
                assert serve, "upload server recorded no serve span"
                # Same trace id: the hop no longer severs the trace.
                assert serve[0].context.trace_id == sp.context.trace_id
                assert serve[0].attrs.get("bytes") == 4
            finally:
                await dl.close()
                await upload.close()
                storage.close()

        run_async(body(), timeout=60)

    def test_untraced_pull_still_serves(self, run_async, tmp_path):
        from dragonfly2_tpu.daemon.peer.piece_downloader import (
            PieceDownloader,
        )
        from dragonfly2_tpu.daemon.upload import UploadManager

        async def body():
            storage = StorageManager(
                StorageOption(data_dir=str(tmp_path / "u-data")))
            store = storage.register_task(TaskStoreMetadata(
                task_id="plain-task", peer_id="p", url="http://o/f",
                piece_size=4, content_length=4, total_piece_count=1))
            store.write_piece(0, b"wxyz")
            store.mark_done()
            upload = UploadManager(storage, rate_limit=1 << 40)
            port = await upload.serve("127.0.0.1", 0)
            dl = PieceDownloader()
            try:
                chunks, size, _c, _d = await dl.download_piece(
                    "127.0.0.1", port, "plain-task", 0, expected_size=4)
                assert size == 4
            finally:
                await dl.close()
                await upload.close()
                storage.close()

        run_async(body(), timeout=60)
