"""What a fan-out adds to the tracing (ISSUE 38): a daemon's raw ring for one
task readable from outside it, the uploader's side as a span stamped at the
send's end (the aiohttp server and the native one alike), a child's bytes by
where they came from, the scheduler's count of a task's fan-out, and the
program's own fold booking all of it without a new ``other_s``."""

import random
import time

import aiohttp
import pytest

from dragonfly2_tpu.daemon.upload import UploadManager
from dragonfly2_tpu.pkg import flight
from dragonfly2_tpu.storage import StorageManager, StorageOption
from dragonfly2_tpu.storage.local_store import TaskStoreMetadata

PIECE = 64 * 1024


# -- the ring ---------------------------------------------------------------

def test_record_at_places_an_event_where_it_ended():
    tf = flight.TaskFlight("t")
    tf.record(flight.EV_REGISTER)
    time.sleep(0.02)
    tf.record(flight.EV_SCHEDULED)
    # A send that ended between the two, learnt afterwards.
    between = tf._start_pc + (tf.events()[0][0] + tf.events()[1][0]) / 2
    tf.record_at(between, flight.EV_UPLOAD_SERVE, 3, 12.5, "4096")
    names = [flight.EVENT_NAMES[code] for _, code, *_ in tf.events()]
    assert names == ["register", "upload_serve", "scheduled"]
    assert tf.events_total == 3


def test_raw_is_the_ring_uncapped_with_aux_and_notes():
    tf = flight.TaskFlight("task-raw")
    for piece in range(300):
        tf.record(flight.EV_UPLOAD_SERVE, piece, 1.25,
                  flight.serve_note(PIECE, 0.0))
    tf.record(flight.EV_TASK_SOURCES, 2, 7.0, "seed=5 peer=7 origin=0")
    tf.finish("done")
    raw = flight.raw(tf)
    assert raw["task_id"] == "task-raw" and raw["state"] == "done"
    assert raw["events_total"] == 302 and raw["events_dropped"] == 0
    assert raw["start_wall"] == tf.start_wall
    assert len(raw["events"]) == 302           # digest() keeps 96 at most
    assert len(flight.digest(tf)["events"]) <= 96
    t, name, piece, aux, note = raw["events"][299]
    assert (name, piece, aux, note) == ("upload_serve", 299, 1.25, str(PIECE))
    assert raw["events"][-2][1:] == ["task_sources", 2, 7.0,
                                     "seed=5 peer=7 origin=0"]
    assert raw["events"][-1][1] == "task_done"
    times = [e[0] for e in raw["events"]]
    assert times == sorted(times)


def test_raw_says_what_the_ring_dropped():
    tf = flight.TaskFlight("t", capacity=16)
    for piece in range(40):
        tf.record(flight.EV_LANDED, piece, 1.0, "intra")
    raw = flight.raw(tf)
    assert raw["events_total"] == 40 and raw["events_dropped"] == 24
    assert [e[2] for e in raw["events"]] == list(range(24, 40))


@pytest.mark.parametrize("nbytes, wait_ms, note", [
    (33554432, 0.0, "33554432"),
    (33554432, 0.04, "33554432"),            # under a twentieth of a ms
    (4096, 12.34, "4096 wait=12.3"),
    (0, 1500.0, "0 wait=1500.0"),
])
def test_serve_note_round_trip(nbytes, wait_ms, note):
    assert flight.serve_note(nbytes, wait_ms) == note
    got_bytes, got_wait = flight.parse_serve_note(note)
    assert got_bytes == nbytes
    assert got_wait == pytest.approx(wait_ms if " wait=" in note else 0.0,
                                     abs=0.05)


@pytest.mark.parametrize("note, want", [
    ("seed=10 peer=20 origin=30", (10, 20, 30)),
    ("seed=0 peer=0 origin=1843431563", (0, 0, 1843431563)),
    ("", (0, 0, 0)),
    ("33554432", (0, 0, 0)),                 # an older event's note
    ("peer=5 bogus=7 seed=x", (0, 5, 0)),
])
def test_parse_sources_note(note, want):
    got = flight.parse_sources_note(note)
    assert (got["seed_bytes"], got["peer_bytes"], got["origin_bytes"]) == want


# -- the program's own fold -------------------------------------------------

def child_flight(with_new_events: bool) -> flight.TaskFlight:
    """A child served by two fellow hosts and the seed, which then serves a
    mate itself; built on a fixed clock so that two of them compare."""
    tf = flight.TaskFlight("child")
    at = tf._start_pc

    def ev(t, code, piece=-1, aux=0.0, note=""):
        tf.record_at(at + t, code, piece, aux, note)

    ev(0.000, flight.EV_REGISTER)
    ev(0.010, flight.EV_SCHEDULED, -1, 0.0, "normal_task")
    for piece, parent in enumerate(["10.0.0.9:1", "10.0.0.2:1",
                                    "10.0.0.3:1", "10.0.0.2:1"]):
        ev(0.10 + 0.1 * piece, flight.EV_REQUEST, piece, 0.0, parent)
        ev(0.18 + 0.1 * piece, flight.EV_LANDED, piece, 80.0,
           "unlabeled" if piece == 0 else "intra")
    if with_new_events:
        ev(0.30, flight.EV_UPLOAD_SERVE, 0, 40.0, "100")
        ev(0.32, flight.EV_UPLOAD_SERVE, 1, 40.0, "100 wait=2.5")
        ev(0.50, flight.EV_TASK_SOURCES, 3, 300.0,
           "seed=100 peer=300 origin=0")
    ev(0.52, flight.EV_TASK_DONE)
    tf.state = "done"
    tf._end_pc = 0.52
    return tf


def test_analyze_books_the_new_events_and_no_new_other():
    with_new = flight.analyze(child_flight(True))
    without = flight.analyze(child_flight(False))
    # Serving and the byte count are no phase of this task's own pull.
    assert with_new["phases"] == without["phases"]
    assert with_new["other_s"] == without["other_s"]
    assert with_new["sources"] == {"seed_bytes": 100, "peer_bytes": 300,
                                   "origin_bytes": 0, "parents": 3}
    # Two sends of 40 ms ending 20 ms apart: 60 ms busy.
    assert with_new["upload"] == {"serves": 2, "bytes": 200,
                                  "busy_ms": pytest.approx(60.0, abs=0.01),
                                  "wait_ms": 2.5}
    assert without["sources"] == {} and without["upload"] == {}
    text = flight.render_waterfall(with_new)
    assert ("sources, bytes: seed=100 peers=300 origin=0 from 3 parent(s)"
            in text)
    assert "upload: served 2 piece(s), 200 bytes" in text
    assert "sources" not in flight.render_waterfall(without)


# -- the scheduler's view ---------------------------------------------------

def test_pod_aggregator_counts_a_fan_out():
    pod = flight.PodAggregator()
    for host in ("seed", "h0", "h1", "h2"):
        pod.note_fanout("t", "register", host)
    pod.note_fanout("t", "back_source")
    for _ in range(5):
        pod.note_fanout("t", "handout")
    pod.note_fanout("t", "reschedule")
    time.sleep(0.01)
    for host in ("seed", "h0", "h1"):
        pod.note_fanout("t", "finished", host)
    pod.note_fanout("t", "failed", "h2")
    got = pod.report("t")["fanout"]
    assert {k: got[k] for k in ("register", "handout", "reschedule",
                                "back_source", "finished", "failed",
                                "hosts")} == {
        "register": 4, "handout": 5, "reschedule": 1, "back_source": 1,
        "finished": 3, "failed": 1, "hosts": 4}
    assert got["register_to_last_finished_s"] >= 0.01
    # A task nobody registered for in the normal way has no such block.
    pod.note_piece("u", "h0", None, 5)
    assert pod.report("u")["fanout"] == {}


def test_a_task_still_running_has_no_last_finish():
    pod = flight.PodAggregator()
    pod.note_fanout("t", "register", "h0")
    got = pod.report("t")["fanout"]
    assert got["register_to_last_finished_s"] is None
    assert got["first_register_at"] > 0


# -- the uploader's side ----------------------------------------------------

def _store(tmp_path, task_id: str):
    storage = StorageManager(StorageOption(data_dir=str(tmp_path / "d")))
    content = random.Random(38).randbytes(3 * PIECE)
    store = storage.register_task(TaskStoreMetadata(
        task_id=task_id, content_length=len(content), piece_size=PIECE,
        total_piece_count=3))
    for n in range(3):
        store.write_piece(n, content[n * PIECE:(n + 1) * PIECE])
    return storage, content


@pytest.mark.parametrize("path", ["aiohttp", "native"])
def test_upload_serve_is_a_span_stamped_at_the_sends_end(run_async, tmp_path,
                                                         path):
    """``upload_serve``: ONE event a served piece, after its last byte,
    ``aux`` = the send's ms, ``note`` = its bytes; on the native server the
    events reach the ring when it is read (``FlightRecorder.sync``), at the
    time the send ended."""

    async def body():
        task_id = f"serve-{path}"
        storage, content = _store(tmp_path, task_id)
        # The aiohttp path is the one a rate limit forces.
        upload = UploadManager(storage,
                               rate_limit=1 << 40 if path == "aiohttp" else 0)
        recorder = upload.flight = flight.FlightRecorder()
        tf = recorder.task(task_id)
        tf.record(flight.EV_REGISTER)
        port = await upload.serve("127.0.0.1", 0)
        if (upload._native_srv is not None) != (path == "native"):
            await upload.close()
            pytest.skip("native library unavailable")
        base = f"http://127.0.0.1:{port}/download/up/{task_id}"
        try:
            async with aiohttp.ClientSession() as http:
                for n in (2, 0):
                    async with http.get(base,
                                        params={"pieceNum": str(n)}) as r:
                        assert await r.read() == \
                            content[n * PIECE:(n + 1) * PIECE]
                async with http.get(
                        base, headers={"Range": "bytes=10-109"}) as r:
                    assert await r.read() == content[10:110]
            received = time.perf_counter() - tf._start_pc
            if path == "native":
                assert not [e for e in tf.events()
                            if e[1] == flight.EV_UPLOAD_SERVE]
            recorder.sync()
            recorder.sync()            # a second read brings nothing twice
            sends = [e for e in tf.events()
                     if e[1] == flight.EV_UPLOAD_SERVE]
            assert [(piece, flight.parse_serve_note(note)[0])
                    for _, _, piece, _, note in sends] == [
                (2, PIECE), (0, PIECE), (-1, 100)]
            assert all(flight.parse_serve_note(note)[1] < 1000.0
                       for *_, note in sends)
            for t, _, _, ms, _ in sends:
                assert 0.0 <= ms < 5000.0
                # It ended before its bytes were all read here, and its
                # start (t - ms) lies after the flight's own.
                assert 0.0 < t - ms / 1000.0 <= t <= received + 0.005
            report = flight.analyze(tf)
            assert report["upload"]["serves"] == 3
            assert report["upload"]["bytes"] == 2 * PIECE + 100
        finally:
            await upload.close()
        assert upload.drain_serves not in recorder.feeders

    run_async(body(), timeout=60)


def test_a_send_of_a_task_without_a_ring_is_skipped(run_async, tmp_path):
    """The native server's log may outlive a task's flight: draining it must
    not make rings for tasks nobody here follows."""

    async def body():
        storage, content = _store(tmp_path, "no-ring")
        upload = UploadManager(storage)
        recorder = upload.flight = flight.FlightRecorder()
        port = await upload.serve("127.0.0.1", 0)
        try:
            if upload._native_srv is None:
                pytest.skip("native library unavailable")
            async with aiohttp.ClientSession() as http:
                async with http.get(
                        f"http://127.0.0.1:{port}/download/up/no-ring",
                        params={"pieceNum": "1"}) as r:
                    assert await r.read() == content[PIECE:2 * PIECE]
            recorder.sync()
            assert recorder.get("no-ring") is None
        finally:
            await upload.close()

    run_async(body(), timeout=60)
