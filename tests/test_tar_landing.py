"""Dataset shards under a three-worker dataloader, at a small size.

``laion-tar-250m`` (chipbench/configs) is three clients re-landing kept
webdataset tars: 30 pieces, the last one short, no whole-object digest,
landed whole as words. Here the same geometry at 16 KiB a piece goes
through one ``DeviceSinkManager`` at the program's defaults on the CPU
backend, from a real ``LocalTaskStore``: three landings at once, by the
re-land path (a whole landing is ONE job on the one thread) and by
interleaved ``on_piece`` calls, compared word for word with a plain
reference that uses no program code. And the span that deployment adds:
every job stamps one ``sink_wait``, the time it stood queued for the
thread, which ends where the job's ``sink_land`` / ``sink_finalize``
begins.

No clock is asserted: counts, equality and containment only.
"""

from __future__ import annotations

import asyncio
import gc
import random

import numpy as np
import pytest

from dragonfly2_tpu.pkg import flight

PIECE = 16 * 1024                  # a multiple of 512 and of a word
PIECES = 30
LENGTH = 29 * PIECE + 26 * 512     # the last piece short, whole tar blocks
TASKS = 3
ROUNDS = 3
ON_THREAD = ("sink_land", "sink_finalize")


def reference_words(content: bytes) -> np.ndarray:
    """What a landing has to equal: the bytes, zero-padded to a word."""
    return np.frombuffer(content + bytes(-len(content) % 4), "<u4")


# ``as_words`` is the buffer of whole pieces: past the content's words it
# holds zeros, up to the end of the short last piece's slot.
SLOT_WORDS = PIECES * PIECE // 4


def make_store(root, task_id: str, seed: int):
    from dragonfly2_tpu.storage.local_store import (
        LocalTaskStore,
        TaskStoreMetadata,
    )

    content = bytes(random.Random(seed).randbytes(LENGTH))
    store = LocalTaskStore(
        str(root / task_id),
        TaskStoreMetadata(task_id=task_id, content_length=LENGTH,
                          piece_size=PIECE, total_piece_count=PIECES))
    for n in range(PIECES):
        store.write_piece(n, content[n * PIECE:(n + 1) * PIECE])
    return store, content


class Landed:
    """What one scenario left: per round and task the landed words and
    the flight, and what the manager's books read on the way."""

    def __init__(self):
        self.contents: list[bytes] = []
        self.words: dict = {}       # (round, task) -> np.ndarray
        self.flights: dict = {}     # (round, task) -> TaskFlight
        self.jobs: dict = {}        # (round, task) -> jobs submitted
        self.alive: list[int] = []  # sinks the manager held, at each create
        self.landing: list[float] = []   # the gauge, at each create
        self.landing_left = 0.0     # the gauge at the end, less the start
        self.outstanding_left = 0   # staging stacks out, less the start
        self.wait_seconds = 0.0     # the counter's rise
        self.max_tasks = 0


def scenario(tmp_path_factory, name: str, land) -> Landed:
    """Three digest-less tar-shaped objects in real stores, one manager at
    the defaults; ``land(mgr, stores, out)`` does the landing."""
    from dragonfly2_tpu.daemon.peer import device_sink
    from dragonfly2_tpu.ops import hbm_sink

    root = tmp_path_factory.mktemp(name)
    out = Landed()
    stores = []
    for i in range(TASKS):
        store, content = make_store(root, f"{name}-{i}", 1000 + i)
        stores.append(store)
        out.contents.append(content)

    async def body():
        mgr = device_sink.DeviceSinkManager()
        out.max_tasks = mgr.max_tasks
        gauge = device_sink.SINKS_LANDING._value
        create = mgr._create

        def counted(*args):
            sink = create(*args)
            out.alive.append(len(mgr._sinks))
            out.landing.append(gauge.get() - landing_before)
            return sink

        mgr._create = counted
        gc.collect()
        landing_before = gauge.get()
        waited_before = device_sink.SINK_WAIT_SECONDS._value.get()
        outstanding_before = hbm_sink._STAGING.stats()["outstanding"]
        try:
            await land(mgr, stores, out)
        finally:
            mgr.close()
        gc.collect()
        out.landing_left = gauge.get() - landing_before
        out.wait_seconds = (device_sink.SINK_WAIT_SECONDS._value.get()
                            - waited_before)
        out.outstanding_left = (hbm_sink._STAGING.stats()["outstanding"]
                                - outstanding_before)

    asyncio.run(asyncio.wait_for(body(), 300))
    return out


def claim(mgr, out: Landed, key, store) -> None:
    """What download_to_device does with a verified sink: take it, read
    the words, let the sink go."""
    sink = mgr.take(store.metadata.task_id)
    assert sink is not None and sink.verified
    out.words[key] = np.asarray(sink.as_words())


@pytest.fixture(scope="module")
def relanded(tmp_path_factory) -> Landed:
    """The re-land path, three at once, three times over: each round's
    landings go through staging stacks the round before gave back."""

    async def land(mgr, stores, out):
        for r in range(ROUNDS):
            flights = [flight.TaskFlight(s.metadata.task_id) for s in stores]
            sinks = await asyncio.gather(*(
                mgr.finalize(s.metadata.task_id, s, tf)
                for s, tf in zip(stores, flights)))
            assert all(sink is not None for sink in sinks)
            for i, (store, tf) in enumerate(zip(stores, flights)):
                out.flights[r, i], out.jobs[r, i] = tf, 1
                claim(mgr, out, (r, i), store)
            del sinks

    return scenario(tmp_path_factory, "reland", land)


@pytest.fixture(scope="module")
def interleaved(tmp_path_factory) -> Landed:
    """The three landed piece by piece, the 90 ``on_piece`` calls in a
    seeded shuffled order and five at a time, then the three finalizes."""

    async def land(mgr, stores, out):
        flights = [flight.TaskFlight(s.metadata.task_id) for s in stores]
        records = [{rec.num: rec for rec in s.get_pieces()} for s in stores]
        calls = [(i, n) for i in range(TASKS) for n in range(PIECES)]
        random.Random(27).shuffle(calls)
        for at in range(0, len(calls), 5):
            await asyncio.gather(*(
                mgr.on_piece(stores[i].metadata.task_id, stores[i],
                             records[i][n], flights[i])
                for i, n in calls[at:at + 5]))
        sinks = await asyncio.gather(*(
            mgr.finalize(s.metadata.task_id, s, tf)
            for s, tf in zip(stores, flights)))
        assert all(sink is not None for sink in sinks)
        for i, (store, tf) in enumerate(zip(stores, flights)):
            out.flights[0, i], out.jobs[0, i] = tf, PIECES + 1
            claim(mgr, out, (0, i), store)
        del sinks

    return scenario(tmp_path_factory, "interleaved", land)


@pytest.fixture(scope="module")
def lone(tmp_path_factory) -> Landed:
    """One re-land with the thread to itself."""

    async def land(mgr, stores, out):
        store = stores[0]
        tf = flight.TaskFlight(store.metadata.task_id)
        assert await mgr.finalize(store.metadata.task_id, store, tf)
        out.flights[0, 0], out.jobs[0, 0] = tf, 1
        claim(mgr, out, (0, 0), store)

    return scenario(tmp_path_factory, "lone", land)


# (fixture, round, task) of every landing, and of every flight, above.
LANDINGS = ([("relanded", r, i) for r in range(ROUNDS) for i in range(TASKS)]
            + [("interleaved", 0, i) for i in range(TASKS)]
            + [("lone", 0, 0)])
SCENARIOS = ("relanded", "interleaved", "lone")


def landing_id(case) -> str:
    return "{}-round{}-task{}".format(*case)


def events_of(tf) -> list:
    """(name, start, end, piece, ms) of the flight's spans, in time."""
    return [(flight.EVENT_NAMES[code], t - aux / 1000.0, t, piece, aux)
            for t, code, piece, aux, _ in tf.events()]


@pytest.mark.parametrize("case", LANDINGS, ids=landing_id)
def test_landed_words_equal_the_plain_reference(case, request):
    name, r, i = case
    out = request.getfixturevalue(name)
    want = reference_words(out.contents[i])
    got = out.words[r, i]
    assert got.dtype == np.uint32 and got.shape == (SLOT_WORDS,)
    assert np.array_equal(got[:want.size], want)
    assert not got[want.size:].any()


@pytest.mark.parametrize("case", LANDINGS, ids=landing_id)
def test_every_job_stamps_one_wait(case, request):
    name, r, i = case
    out = request.getfixturevalue(name)
    rows = events_of(out.flights[r, i])
    waits = [row for row in rows if row[0] == "sink_wait"]
    assert len(waits) == out.jobs[r, i]
    assert all(ms >= 0 for *_, ms in waits)
    if out.jobs[r, i] == 1:
        # A re-land: the whole landing is the one job, a finalize.
        assert [piece for _, _, _, piece, _ in waits] == [0]
        assert [row[3] for row in rows if row[0] == "sink_finalize"] == [
            PIECES]
    else:
        # A wait for every piece's job, and the finalize's (piece 0) last.
        assert sorted(piece for _, _, _, piece, _ in waits[:-1]) == list(
            range(PIECES))
        assert waits[-1][3] == 0


@pytest.mark.parametrize("case", LANDINGS, ids=landing_id)
def test_a_wait_ends_where_its_job_begins(case, request):
    """``sink_wait`` is a sibling of the job's span, never inside one: the
    thread stamps it between two jobs. So the job's own span, the next
    ``sink_land`` / ``sink_finalize`` to end, begins no earlier than the
    wait ended, and no on-thread span of the task straddles the stamp."""
    name, r, i = case
    rows = events_of(request.getfixturevalue(name).flights[r, i])
    spans = [row for row in rows if row[0] in ON_THREAD]
    for _, submitted, started, piece, _ in (
            row for row in rows if row[0] == "sink_wait"):
        assert submitted <= started
        job = min((s for s in spans if s[2] >= started
                   and (s[0] == "sink_finalize" or s[3] == piece)),
                  key=lambda s: s[2])
        assert job[1] >= started - 1e-9
        assert not [s for s in spans if s[1] < started - 1e-9
                    and s[2] > started + 1e-9]


@pytest.mark.parametrize("case", LANDINGS, ids=landing_id)
def test_analyze_reports_the_summed_wait(case, request):
    name, r, i = case
    tf = request.getfixturevalue(name).flights[r, i]
    waited = sum(ms for row_name, *_, ms in events_of(tf)
                 if row_name == "sink_wait")
    report = flight.analyze(tf)
    assert report["hbm"]["wait_ms"] == pytest.approx(waited, abs=0.001)
    assert list(report["hbm"])[-1] == "wait_ms"
    assert report["event_counts"]["sink_wait"] == request.getfixturevalue(
        name).jobs[r, i]
    assert f"wait={report['hbm']['wait_ms']:.1f}" in flight.render_waterfall(
        report)


@pytest.mark.parametrize("r", range(ROUNDS))
def test_of_three_finalizes_at_once_two_waited(relanded, r):
    """One thread: the second landing waits for the first, the third for
    both. (A lone one may carry 0: ``lone`` asserts only one stamp.)"""
    waits = sorted(ms for i in range(TASKS)
                   for name, *_, ms in events_of(relanded.flights[r, i])
                   if name == "sink_wait")
    assert len(waits) == TASKS and sum(ms > 0 for ms in waits) >= 2


def test_interleaved_pieces_queued_behind_each_other(interleaved):
    """Five ``on_piece`` jobs were submitted at a time."""
    waits = [ms for i in range(TASKS)
             for name, *_, ms in events_of(interleaved.flights[0, i])
             if name == "sink_wait"]
    assert len(waits) == TASKS * (PIECES + 1)
    assert sum(ms > 0 for ms in waits) >= len(waits) // 2


@pytest.mark.parametrize("name", SCENARIOS)
def test_never_more_sinks_than_the_cap_and_none_left_landing(name, request):
    out = request.getfixturevalue(name)
    tasks = 1 if name == "lone" else TASKS
    rounds = ROUNDS if name == "relanded" else 1
    # A sink a landing: claimed sinks are gone before the next round.
    assert len(out.alive) == tasks * rounds
    assert 1 <= max(out.alive) <= min(tasks, out.max_tasks)
    assert 1 <= max(out.landing) <= min(tasks, out.max_tasks)
    assert out.landing_left == 0


@pytest.mark.parametrize("name", SCENARIOS)
def test_no_staging_stack_left_out(name, request):
    assert request.getfixturevalue(name).outstanding_left == 0


@pytest.mark.parametrize("name", SCENARIOS)
def test_wait_counter_rose_by_the_stamped_waits(name, request):
    out = request.getfixturevalue(name)
    stamped = sum(ms for tf in out.flights.values()
                  for row_name, *_, ms in events_of(tf)
                  if row_name == "sink_wait")
    assert out.wait_seconds == pytest.approx(stamped / 1000.0, rel=1e-6,
                                             abs=1e-9)


def test_a_sink_dropped_before_it_verified_is_no_longer_landing(tmp_path):
    """The gauge's other way down: discard of a sink mid-landing."""
    from dragonfly2_tpu.daemon.peer import device_sink

    store, _ = make_store(tmp_path, "dropped", 7)
    gauge = device_sink.SINKS_LANDING._value

    async def body():
        mgr = device_sink.DeviceSinkManager()
        before = gauge.get()
        try:
            rec = next(iter(store.get_pieces()))
            await mgr.on_piece("dropped", store, rec)
            during = gauge.get() - before
            mgr.discard("dropped")
            mgr.discard("dropped")
            return during, gauge.get() - before
        finally:
            mgr.close()

    assert asyncio.run(asyncio.wait_for(body(), 120)) == (1, 0)


# -- a re-land's host pass takes a staging stack at once (S1 b) ------------

def passes_of(tf) -> dict:
    """name -> [(piece, note)] of the flight's ``sink_land``, ``sink_read``
    and ``sink_checksum`` events: one of each a host pass."""
    out: dict = {"sink_land": [], "sink_read": [], "sink_checksum": []}
    for _, code, piece, _, note in tf.events():
        name = flight.EVENT_NAMES[code]
        if name in out:
            out[name].append((piece, note))
    return out


@pytest.mark.parametrize("i", range(TASKS))
def test_a_reland_a_stack_a_pass_lands_what_piece_by_piece_does(
        relanded, interleaved, i):
    """The two scenarios' stores hold the same bytes (same seeds): every
    round of the re-land, whose pieces went a stack a pass, holds word for
    word what the landing of one piece a pass holds."""
    assert relanded.contents[i] == interleaved.contents[i]
    for r in range(ROUNDS):
        assert np.array_equal(relanded.words[r, i], interleaved.words[0, i])


@pytest.mark.parametrize("case", LANDINGS, ids=landing_id)
def test_a_pass_a_stack_in_a_reland_and_a_pass_a_piece_streamed(case,
                                                                request):
    """Thirty pieces into stacks of eight (the default): a re-land is four
    passes, of 8, 8, 8 and 6 pieces, each named by its lowest piece; a
    streamed landing is thirty of one, whatever else queues at the thread.
    Pieces of 16 KiB are under the chunk floor either way: no hand-over,
    no note."""
    name, r, i = case
    out = request.getfixturevalue(name)
    passes = passes_of(out.flights[r, i])
    if out.jobs[r, i] == 1:
        want = [(0, ""), (8, ""), (16, ""), (24, "")]
    else:
        want = [(n, "") for n in range(PIECES)]
    for name, events in passes.items():
        assert sorted(events) == want, name
