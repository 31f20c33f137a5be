"""Spans inside the landing thread (pkg/flight ``sink_*`` events).

The device sink's one worker thread stamps its steps into the task's
flight ring: one event at a span's end, ``aux`` = its ms. These tests land
a small object through ``DeviceSinkManager`` on the CPU backend and check
the events' shape (which exist, what lies inside what), the fold
(``analyze()`` books landing as ``hbm``, not ``ici``), the ring under two
writers, and that none of it pulled jax into a daemon that holds no sink.
"""

from __future__ import annotations

import asyncio
import dataclasses
import gc
import importlib.util
import os
import random
import subprocess
import sys
import threading
import time
import types
import weakref

import numpy as np
import pytest

from dragonfly2_tpu.pkg import flight
from tests.test_device_sink import _pieces_counted as pieces_counted

SINK_NAMES = ("sink_land", "sink_read", "sink_checksum", "sink_stage",
              "sink_put", "sink_assemble", "sink_compile", "sink_finalize")
PIECES = 10
BATCH = 4
ORDER = [3, 0, 1, 2, 7, 6, 5, 4, 9, 8]     # how the cold landing's pieces arrive
# The steps of a piece (or of a flush in finalize), which contain nothing.
LEAVES = ("sink_read", "sink_checksum", "sink_stage", "sink_put")


@pytest.fixture(params=["whole", "split"])
def passes(request, monkeypatch):
    """How a piece's read and checksum run: on the landing thread alone, as
    pieces of this size do, or, with the chunk floor patched down to a
    quarter of a piece, in chunks on the helper threads."""
    if request.param == "split":
        from dragonfly2_tpu.ops import hbm_sink

        monkeypatch.setattr(hbm_sink, "_CHUNK_FLOOR", 16 * 1024)
    return request.param


class WatchedFlight(flight.TaskFlight):
    """A flight that also keeps which thread stamped each sink_* event."""

    def __init__(self, task_id: str):
        super().__init__(task_id)
        self.stampers: list = []

    def record(self, code, piece=-1, aux=0.0, note=""):
        if flight.EVENT_NAMES[code].startswith("sink_"):
            self.stampers.append(threading.current_thread().name)
        super().record(code, piece, aux, note)


def check_passes(tf, passes: str, notes: dict) -> None:
    """One ``sink_read`` and one ``sink_checksum`` a pass whether or not
    it ran in chunks on the helpers (the counts are checked beside this):
    both say into how many (``notes[passes]``), and the landing thread
    stamped them like every other span, the helpers nothing."""
    for name in ("sink_read", "sink_checksum"):
        assert [note for _, code, _, _, note in tf.events()
                if flight.EVENT_NAMES[code] == name] == notes[passes], name
    assert tf.stampers and all(
        name.startswith("df-device-sink") for name in tf.stampers)


def make_store(tmp_path, task_id: str, piece_size: int, seed: int = 5):
    """A completed store of PIECES pieces, the last one short."""
    from dragonfly2_tpu.storage.local_store import (
        LocalTaskStore,
        TaskStoreMetadata,
    )

    length = piece_size * PIECES - piece_size // 2
    content = bytes(random.Random(seed).randbytes(length))
    store = LocalTaskStore(
        str(tmp_path / task_id),
        TaskStoreMetadata(task_id=task_id, content_length=length,
                          piece_size=piece_size, total_piece_count=PIECES))
    for n in range(PIECES):
        store.write_piece(n, content[n * piece_size:(n + 1) * piece_size])
    return store, content


async def land_cold(mgr, store, tf, order) -> object:
    """What task_manager's on_piece hook and _finalize_device do."""
    task_id = store.metadata.task_id
    records = {rec.num: rec for rec in store.get_pieces()}
    for n in order:
        tf.record(flight.EV_HBM_START, n)
        await mgr.on_piece(task_id, store, records[n], tf)
        tf.record(flight.EV_HBM_LANDED, n)
    return await mgr.finalize(task_id, store, tf)


def spans_of(tf) -> dict:
    """name -> [(start, end, piece)] of the ring's sink_* spans."""
    out: dict = {name: [] for name in SINK_NAMES}
    for t, code, piece, aux, _ in tf.events():
        name = flight.EVENT_NAMES[code]
        if name in out:
            out[name].append((t - aux / 1000.0, t, piece))
    return out


def seconds(rows) -> float:
    return sum(e - s for s, e, _ in rows)


@dataclasses.dataclass
class Span:
    name: str
    piece: int
    ms: float
    children: list

    def named(self, *names: str) -> list:
        return [c for c in self.children if c.name in names]


def tree_of(tf) -> list:
    """The ring's sink_* spans as the landing thread nested them. One
    thread stamps every span, at its end, so the ring's order alone says
    what lies inside what, with no clock compared: a ``sink_land`` holds
    the leaves stamped since the span before it, a ``sink_assemble`` the
    compiles, and a ``sink_finalize`` what is loose by then and the
    ``sink_land``s of its backfill, at most the pieces it counted in
    ``piece`` (a ``sink_land`` of a backfill is a group of them)."""
    top: list = []
    for _, code, piece, aux, _ in tf.events():
        name = flight.EVENT_NAMES[code]
        if name not in SINK_NAMES:
            continue
        span = Span(name, piece, aux, [])
        if name == "sink_finalize":
            first = len(top)
            while first and top[first - 1].name != "sink_finalize" and (
                    top[first - 1].name != "sink_land"
                    or len([c for c in top[first:]
                            if c.name == "sink_land"]) < piece):
                first -= 1
            span.children, top[first:] = top[first:], []
        elif name in ("sink_land", "sink_assemble"):
            held = LEAVES if name == "sink_land" else ("sink_compile",)
            first = len(top)
            while first and top[first - 1].name in held:
                first -= 1
            span.children, top[first:] = top[first:], []
        top.append(span)
    return top


def check_sums(spans: list) -> None:
    """Children never add up to more than their parent: they were timed
    one after the other inside it."""
    for span in spans:
        assert sum(c.ms for c in span.children) <= span.ms + 1e-6, span
        check_sums(span.children)


def test_cold_landing_stamps_every_span_children_inside_parents(
        run_async, tmp_path, fresh_compiles, passes):
    from dragonfly2_tpu.daemon.peer.device_sink import DeviceSinkManager

    async def body():
        store, content = make_store(tmp_path, "t-cold", 64 * 1024 + 64)
        tf = WatchedFlight("t-cold")
        mgr = DeviceSinkManager(batch_pieces=BATCH)
        counted = pieces_counted()
        try:
            sink = await land_cold(mgr, store, tf, ORDER)
            assert sink is not None and sink.verified
            got = bytes(np.asarray(sink.as_bytes_array()))
            assert got == content
        finally:
            mgr.close()
        return tf, {how: n - counted[how]
                    for how, n in pieces_counted().items()}

    tf, counted = run_async(body(), timeout=120)
    # A pass a piece, in the order they came: 64 KiB + 64 bytes in four
    # chunks, the short last piece (9) in two.
    check_passes(tf, passes, {"split": ["4"] * 8 + ["2", "4"],
                              "whole": [""] * PIECES})
    # The hook missed nothing: no piece shared its pass.
    assert counted == {"batched": 0, "split": 0, "whole": 0,
                       passes: PIECES}
    spans = spans_of(tf)
    counts = {name: len(rows) for name, rows in spans.items()}
    # Ten pieces, each read, staged and checksummed once on the thread;
    # three stacks opened; two full batches flushed while landing and the
    # rest in finalize; one assembly of a geometry never met before;
    # nothing left to backfill.
    assert counts == {"sink_land": PIECES, "sink_read": PIECES,
                      "sink_checksum": PIECES, "sink_stage": PIECES + 3 + 3,
                      "sink_put": 3, "sink_assemble": 1, "sink_compile": 1,
                      "sink_finalize": 1}
    # A batch is named by its lowest slot.
    assert sorted(p for _, _, p in spans["sink_put"]) == [0, 4, 8]

    top = tree_of(tf)
    lands, (final,) = top[:-1], top[-1:]
    assert [span.name for span in lands] == ["sink_land"] * PIECES
    assert [span.piece for span in lands] == ORDER
    assert (final.name, final.piece) == ("sink_finalize", 0)
    for at, land in enumerate(lands):
        # Where a stack is opened (-1), the one pass that read the piece
        # into its row and checksummed it, stamped as its two parts, the
        # row's own staging; with the batch's last piece the flush.
        want = ([("sink_stage", -1)] if at % BATCH == 0 else []) + [
            ("sink_read", land.piece), ("sink_checksum", land.piece),
            ("sink_stage", land.piece)]
        if at % BATCH == BATCH - 1:
            lowest = min(ORDER[at - BATCH + 1:at + 1])
            want += [("sink_stage", lowest), ("sink_put", lowest)]
        assert [(c.name, c.piece) for c in land.children] == want, at
    assert [(c.name, c.piece) for c in final.children[:2]] == [
        ("sink_stage", 8), ("sink_put", 8)]
    (assemble,) = final.children[2:]
    assert assemble.name == "sink_assemble"
    assert [c.name for c in assemble.children] == ["sink_compile"]
    check_sums(top)


def test_reland_backfill_stamps_the_same_steps_and_one_finalize(
        run_async, tmp_path, passes):
    """A re-land: nothing streamed in, ``_finalize_inner`` backfills every
    piece from the store through the same landing code, a stack's free
    rows a pass: ONE ``sink_land`` > ``sink_read``, ``sink_checksum`` a
    group, named by its lowest piece, then its pieces' ``sink_stage``s."""
    from dragonfly2_tpu.daemon.peer.device_sink import DeviceSinkManager

    async def body():
        store, content = make_store(tmp_path, "t-reland", 64 * 1024)
        tf = WatchedFlight("t-reland")
        tf.finish("done")       # as the cold pull left it
        mgr = DeviceSinkManager(batch_pieces=BATCH)
        counted = pieces_counted()
        try:
            sink = await mgr.finalize("t-reland", store, tf)
            assert sink is not None and sink.verified
            assert bytes(np.asarray(sink.as_bytes_array())) == content
        finally:
            mgr.close()
        return tf, {how: n - counted[how]
                    for how, n in pieces_counted().items()}

    tf, counted = run_async(body(), timeout=120)
    # Two stacks of four pieces of 64 KiB, four chunks each, then a piece
    # and a half: four chunks and two. Under the real floor no group of
    # these is handed to anyone.
    check_passes(tf, passes, {"split": ["16", "16", "6"],
                              "whole": ["", "", ""]})
    # Every piece shared its pass with others of its stack.
    assert counted == {"batched": PIECES, "split": 0, "whole": 0}
    spans = spans_of(tf)
    groups = list(range(0, PIECES, BATCH))
    for name in ("sink_land", "sink_read", "sink_checksum"):
        assert [p for _, _, p in spans[name]] == groups, name
    assert len(spans["sink_stage"]) == PIECES + 3 + 3
    assert len(spans["sink_put"]) == 3
    # piece: the staged batches the assembly read.
    assert [p for _, _, p in spans["sink_assemble"]] == [3]
    # Everything lies in the one finalize, which counted its backfill in
    # pieces.
    (final,) = tree_of(tf)
    assert (final.name, final.piece) == ("sink_finalize", PIECES)
    lands = final.named("sink_land")
    assert [c.piece for c in lands] == groups
    for land in lands:
        first, held = land.piece, min(BATCH, PIECES - land.piece)
        want = ([("sink_stage", -1), ("sink_read", first),
                 ("sink_checksum", first)]
                + [("sink_stage", first + i) for i in range(held)])
        if held == BATCH:
            want += [("sink_stage", first), ("sink_put", first)]
        assert [(c.name, c.piece) for c in land.children] == want, first
        # One pair a group, inside the group's ``sink_land``.
        assert sum(c.ms for c in land.named(
            "sink_read", "sink_checksum")) <= land.ms + 1e-6
    assert [c.name for c in final.children[len(groups):]] == [
        "sink_stage", "sink_put", "sink_assemble"]
    assert sum(len(c.children) for c in final.children) + len(
        final.children) + 1 == sum(len(rows) for rows in spans.values())
    check_sums([final])


# -- a finalize's tail, off the landing thread (PR 43) ----------------------

class OrderedFlight(flight.TaskFlight):
    """A flight that tells ``seen(task, event name)`` of each event before
    it is recorded: the order of two tasks' stamps with no clock compared."""

    def __init__(self, task_id: str, seen):
        super().__init__(task_id)
        self.seen = seen

    def record(self, code, piece=-1, aux=0.0, note=""):
        self.seen(self.task_id, flight.EVENT_NAMES[code])
        super().record(code, piece, aux, note)


def tails_counted() -> dict:
    from dragonfly2_tpu.daemon.peer import device_sink

    return {how: device_sink.SINK_TAILS.labels(how)._value.get()
            for how in ("overlapped", "alone")}


def named(tf, name: str) -> list:
    """(start, end, piece) of the flight's ``name`` events, on its clock."""
    return [(t - aux / 1000.0, t, piece) for t, code, piece, aux, _
            in tf.events() if flight.EVENT_NAMES[code] == name]


def test_a_tail_runs_beside_the_next_sinks_host_pass(run_async, tmp_path,
                                                     monkeypatch):
    """Two finalizes queued at the one landing thread: the first's tail is
    held (an event, no clock) until the second's first ``sink_land`` has
    been stamped, which can only happen if the thread took the second job
    while the first's sink was still being verified. The first's
    ``sink_tail`` counts that job; its ``sink_finalize`` still covers job
    start -> verified, and ``finalize()`` returned after it."""
    from dragonfly2_tpu.daemon.peer import device_sink

    order: list = []
    second_lands = threading.Event()
    verified_at_finalize: dict = {}

    async def body():
        mgr = device_sink.DeviceSinkManager(batch_pieces=BATCH)

        def seen(task_id, name):
            order.append((task_id, name))
            if (task_id, name) == ("t-second", "sink_land"):
                second_lands.set()
            if name == "sink_finalize":
                sink = mgr.get(task_id)
                verified_at_finalize[task_id] = (
                    sink is not None and sink.verified)

        real = device_sink.TaskDeviceSink.verify

        def held(self):
            if self.task_id == "t-first":
                assert second_lands.wait(60), \
                    "the landing thread took no job while the tail ran"
            real(self)

        monkeypatch.setattr(device_sink.TaskDeviceSink, "verify", held)
        stores = {name: make_store(tmp_path, name, 64 * 1024, seed)[0]
                  for seed, name in enumerate(("t-first", "t-second"))}
        flights = {name: OrderedFlight(name, seen) for name in stores}
        before = tails_counted()
        try:
            sinks = await asyncio.gather(*(
                mgr.finalize(name, store, flights[name])
                for name, store in stores.items()))
            assert all(sink is not None and sink.verified for sink in sinks)
            assert mgr._tails == {}
            # finalize() resolved only after its span was stamped.
            for tf in flights.values():
                assert len(named(tf, "sink_finalize")) == 1
        finally:
            mgr.close()
        return flights, {how: n - before[how]
                         for how, n in tails_counted().items()}

    flights, counted = run_async(body(), timeout=120)
    first, second = flights["t-first"], flights["t-second"]
    assert order.index(("t-second", "sink_land")) < order.index(
        ("t-first", "sink_tail"))
    (tail,), (final,) = named(first, "sink_tail"), named(first,
                                                         "sink_finalize")
    assert tail[2] >= 1                 # jobs started beside the tail
    # The tail lies inside its finalize, which ends it: job start ->
    # verified, and the sink was verified when the span was stamped.
    assert final[0] <= tail[0] <= tail[1] <= final[1]
    assert order.index(("t-first", "sink_assemble")) < order.index(
        ("t-first", "sink_tail")) < order.index(("t-first", "sink_finalize"))
    assert verified_at_finalize == {"t-first": True, "t-second": True}
    # The second found no successor queued: nothing ran beside its tail.
    (alone,) = named(second, "sink_tail")
    assert alone[2] == 0
    assert counted == {"overlapped": 1, "alone": 1}
    # Every span of a task is stamped by the landing thread or, from the
    # hand-over on, by the completer: the tree of one task is as ever.
    (tree,) = tree_of(first)
    assert tree.name == "sink_finalize" and tree.piece == PIECES
    assert [c.name for c in tree.children][-1] == "sink_assemble"


def test_a_lone_sinks_tail_hides_behind_nothing(run_async, tmp_path):
    from dragonfly2_tpu.daemon.peer.device_sink import DeviceSinkManager

    async def body():
        store, _ = make_store(tmp_path, "t-lone", 64 * 1024)
        tf = WatchedFlight("t-lone")
        mgr = DeviceSinkManager(batch_pieces=BATCH)
        before = tails_counted()
        try:
            sink = await mgr.finalize("t-lone", store, tf)
            assert sink is not None and sink.verified
        finally:
            mgr.close()
        return tf, {how: n - before[how]
                    for how, n in tails_counted().items()}

    tf, counted = run_async(body(), timeout=120)
    (tail,) = named(tf, "sink_tail")
    assert tail[2] == 0 and counted == {"overlapped": 0, "alone": 1}
    # Landing thread up to the hand-over, the completer from there.
    assert {name.rsplit("_", 1)[0] for name in tf.stampers} == {
        "df-device-sink", "df-device-sink-tail"}
    rep = flight.analyze(tf)
    assert rep["hbm"]["tail_ms"] == pytest.approx(
        (tail[1] - tail[0]) * 1000.0, abs=0.01)
    assert "tail=" in flight.render_waterfall(rep)


def _tail_run(*ops):
    """A window as the harness hands it to a reader: ``flight`` rows are
    (t, name, piece, aux)."""
    return types.SimpleNamespace(ops=[
        types.SimpleNamespace(t0=t0, t1=t1, flight=sorted(events))
        for t0, t1, events in ops])


@pytest.mark.parametrize("name,ops,want", [
    # One client, one sink: its own passes end where its tail begins.
    ("one sink", [(10.0, 10.4, [(10.10, "sink_land", 0, 90.0),
                                (10.20, "sink_land", 8, 90.0),
                                (10.30, "sink_tail", 0, 80.0),
                                (10.30, "sink_finalize", 16, 290.0)])], 0.0),
    # Two tasks: the first's tail of 50 ms lies wholly inside the second's
    # pass; the second's own tail of 50 ms behind nothing: half of each.
    ("inside another task's pass",
     [(20.0, 20.2, [(20.10, "sink_land", 0, 90.0),
                    (20.16, "sink_tail", 1, 50.0)]),
      (20.0, 20.3, [(20.20, "sink_land", 0, 95.0),
                    (20.25, "sink_tail", 0, 50.0)])], 50.0),
    ("wholly hidden",
     [(30.0, 30.2, [(30.16, "sink_tail", 1, 50.0)]),
      (30.0, 30.3, [(30.20, "sink_land", 0, 95.0)])], 100.0),
    # Two passes of another task with a gap of 10 ms between them, under a
    # tail of 40 ms: 30 of 40.
    ("a gap between two passes",
     [(40.0, 40.2, [(40.14, "sink_tail", 2, 40.0)]),
      (40.0, 40.3, [(40.115, "sink_land", 0, 20.0),
                    (40.145, "sink_land", 8, 20.0)])], 75.0),
    ("no sink_tail stamped (the parent's program)",
     [(50.0, 50.3, [(50.10, "sink_land", 0, 90.0),
                    (50.30, "sink_finalize", 8, 290.0)])], None),
    ("no operation", [], None),
])
def test_land_tail_hidden_pct_on_hand_made_runs(monkeypatch, name, ops, want):
    value = load_reader(monkeypatch, "land_tail_hidden_pct")(_tail_run(*ops))
    assert value == (want if want is None else pytest.approx(want)), name


# -- the program <-> benchmark contract ------------------------------------
# chipbench/layers/<metric>.py finds the program's flight events by name and
# reads nothing where a name is gone; the ledger then shows a null. These
# are the readers whose events only the device sink stamps.

SINK_READERS = ("land_read_ms", "land_checksum_ms", "land_stage_ms",
                "land_put_ms", "finalize_ms", "plan_compile_ms",
                "land_wait_ms", "land_thread_util_pct",
                "land_tail_hidden_pct")


def load_reader(monkeypatch, metric: str):
    """``read`` of chipbench/layers/<metric>.py, loaded from its source
    under the names it imports (``layers``, ``reduce_trace``), which leave
    ``sys.modules`` again with the test: chipbench/ itself never lands on
    ``sys.path``, where a second ``tests`` package lives."""
    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "chipbench")

    def load(name: str, *path: str):
        spec = importlib.util.spec_from_file_location(
            name, os.path.join(bench, *path))
        module = importlib.util.module_from_spec(spec)
        monkeypatch.setitem(sys.modules, name, module)
        spec.loader.exec_module(module)
        return module

    package = types.ModuleType("layers")
    monkeypatch.setitem(sys.modules, "layers", package)
    load("reduce_trace", "reduce_trace.py")
    package.sink_events = load("layers.sink_events", "layers",
                               "sink_events.py")
    package.ranged_events = load("layers.ranged_events", "layers",
                                 "ranged_events.py")
    return load("layers." + metric, "layers", metric + ".py").read


@pytest.fixture(scope="module")
def window_of_two(tmp_path_factory):
    """What the harness hands a reader after a window of two operations on
    one task, a streamed landing and a re-land: ``run.ops``, each with its
    ``t0``, ``t1`` and the task's flight events between them as the
    ``(t, name, piece, aux)`` rows of ``harness._read_flight``."""
    from dragonfly2_tpu.daemon.peer.device_sink import DeviceSinkManager

    async def body():
        store, _ = make_store(tmp_path_factory.mktemp("contract"),
                              "t-contract", 64 * 1024)
        tf = flight.TaskFlight("t-contract")
        mgr = DeviceSinkManager(batch_pieces=BATCH)
        spans = []
        try:
            for land in (lambda: land_cold(mgr, store, tf, ORDER),
                         lambda: mgr.finalize("t-contract", store, tf)):
                t0 = time.perf_counter()
                sink = await land()
                assert sink is not None and sink.verified
                spans.append((t0, time.perf_counter()))
                # As the client does: the words are its own now, and the
                # next landing of the task starts from the store.
                assert mgr.take("t-contract") is sink
        finally:
            mgr.close()
        return tf, spans

    tf, spans = asyncio.run(asyncio.wait_for(body(), 120))
    start = time.perf_counter() - (flight.anchored_wall() - tf.start_wall)
    run = types.SimpleNamespace(ops=[
        types.SimpleNamespace(
            t0=t0, t1=t1,
            flight=[(start + t, flight.EVENT_NAMES.get(code, str(code)),
                     piece, aux)
                    for t, code, piece, aux, _ in tf.events()
                    if t0 <= start + t <= t1])
        for t0, t1 in spans])
    for op in run.ops:      # each landing's events fell to its own operation
        assert {name for _, name, _, _ in op.flight} >= {
            "sink_wait", "sink_finalize", "sink_read"}
    return run


@pytest.mark.parametrize("metric", SINK_READERS)
def test_the_benchmarks_reader_finds_its_events(monkeypatch, window_of_two,
                                                metric):
    value = load_reader(monkeypatch, metric)(window_of_two)
    assert isinstance(value, float) and value >= 0.0, (metric, value)
    # 0.0 where no plan was new, and where one client lands one sink at a
    # time: no other pass for a tail to lie behind.
    if metric not in ("plan_compile_ms", "land_tail_hidden_pct"):
        assert value > 0.0, metric


def test_a_new_geometry_stamps_one_compile_and_another_order_of_it_none(
        run_async, tmp_path, fresh_compiles):
    from dragonfly2_tpu.daemon.peer import device_sink
    from dragonfly2_tpu.ops import hbm_sink

    def assemblies() -> dict:
        return {how: hbm_sink.SINK_ASSEMBLIES.labels(how)._value.get()
                for how in ("compiled", "cached")}

    async def body():
        mgr = device_sink.DeviceSinkManager(batch_pieces=BATCH)
        # Two orders whose batches hold different slots at different rows.
        orders = {"t-order-a": [0, 4, 1, 5, 2, 6, 3, 7, 9, 8],
                  "t-order-b": [9, 2, 7, 0, 5, 8, 3, 6, 1, 4]}
        out = []
        try:
            for task_id, order in orders.items():
                # A piece size no other test lands: the geometry is new.
                store, _ = make_store(tmp_path, task_id, 64 * 1024 + 192)
                tf = flight.TaskFlight(task_id)
                compiles = device_sink.SINK_COMPILES._value.get()
                seconds_before = device_sink.SINK_COMPILE_SECONDS._value.get()
                how = assemblies()
                sink = await land_cold(mgr, store, tf, order)
                assert sink is not None and sink.verified
                out.append((
                    spans_of(tf),
                    device_sink.SINK_COMPILES._value.get() - compiles,
                    device_sink.SINK_COMPILE_SECONDS._value.get()
                    - seconds_before,
                    {k: v - how[k] for k, v in assemblies().items()}))
        finally:
            mgr.close()
        return out

    (first, n1, s1, how1), (second, n2, s2, how2) = run_async(
        body(), timeout=120)
    assert len(first["sink_compile"]) == 1 and n1 == 1
    assert s1 == pytest.approx(seconds(first["sink_compile"]), rel=1e-6)
    assert how1 == {"compiled": 1, "cached": 0}
    # piece: the staged batches, as on its assembly.
    assert first["sink_compile"][0][2] == first["sink_assemble"][0][2] == 3
    assert second["sink_compile"] == [] and (n2, s2) == (0, 0.0)
    assert len(second["sink_assemble"]) == 1
    assert how2 == {"compiled": 0, "cached": 1}


def test_a_dropped_sink_goes_with_its_last_reference():
    """The stamp must not tie the HBMSink and its owner into a cycle: a
    sink's device buffers are content-sized, and in a cycle they would stay
    until the cyclic collector came round to an old generation (the chip
    showed it: one more content-sized buffer on the device's peak for
    every earlier pull)."""
    from dragonfly2_tpu.daemon.peer.device_sink import TaskDeviceSink

    piece = 64 * 1024
    data = bytes(random.Random(9).randbytes(piece * 2))
    tf = flight.TaskFlight("t-drop")
    gc.collect()
    gc.disable()
    try:
        sink = TaskDeviceSink("t-drop", len(data), piece, batch_pieces=BATCH)
        sink.stamp.flight = tf
        sink.land(0, data[:piece])
        sink.land(1, data[piece:])
        sink.verify()
        assert tf.events_total >= 5   # 2 checksums, stage, put, assemble
        gone = [weakref.ref(sink), weakref.ref(sink.sink)]
        del sink
        assert [ref() for ref in gone] == [None, None]
    finally:
        gc.enable()


def test_analyze_books_landing_as_hbm_not_ici(run_async, tmp_path):
    from dragonfly2_tpu.daemon.peer.device_sink import DeviceSinkManager

    async def body():
        store, _ = make_store(tmp_path, "t-fold", 64 * 1024)
        rec = flight.FlightRecorder()
        tf = rec.task("t-fold")
        mgr = DeviceSinkManager(batch_pieces=BATCH)
        try:
            await land_cold(mgr, store, tf, list(range(PIECES)))
        finally:
            mgr.close()
        rec.finish_task("t-fold", "done")
        return tf

    tf = run_async(body(), timeout=120)
    rep = flight.analyze(tf)
    assert set(rep["phases"]) == set(flight.PHASES) and "hbm" in flight.PHASES
    assert rep["phases"]["hbm"] > 0 and rep["phases"]["ici"] == 0
    assert rep["dominant_phase"] == "hbm"
    assert sum(rep["phases"].values()) + rep["other_s"] == pytest.approx(
        rep["wall_s"], abs=1e-4)
    spans = spans_of(tf)
    block = rep["hbm"]
    # The jobs' waits for the thread come last (tests/test_tar_landing.py
    # has them); before them a key per step seen (this plan may have been
    # compiled before).
    assert block.pop("wait_ms") >= 0
    # The finalize's tail, off the thread, lies inside its finalize.
    assert list(block).index("tail_ms") == list(block).index(
        "finalize_ms") + 1
    assert 0 < block.pop("tail_ms") <= block["finalize_ms"]
    assert list(block) == [name[5:] + "_ms" for name in SINK_NAMES
                           if spans[name]]
    assert set(block) >= {"land_ms", "read_ms", "put_ms", "finalize_ms"}
    for key, ms in block.items():
        assert ms == pytest.approx(
            seconds(spans["sink_" + key[:-3]]) * 1000.0, abs=0.01)
    text = flight.render_waterfall(rep)
    assert "hbm landing" in text and "read=" in text and "put=" in text
    assert any(line.lstrip().startswith("hbm ") for line in text.split("\n"))


def test_analyze_partition_with_hbm_above_ici():
    """Hand-made clocks: landing overlaps an intra-slice transfer; the
    landing wins the overlap, ici keeps only what landing does not cover,
    and spans stamped after the terminal event still reach the block."""
    tf = flight.TaskFlight("synthetic")
    events = [
        (0.0, flight.EV_REQUEST, 0, 0.0, "a:1"),
        (1.0, flight.EV_LANDED, 0, 1000.0, "intra"),
        (0.5, flight.EV_HBM_START, 7, 0.0, ""),
        (0.9, flight.EV_SINK_READ, 7, 100.0, ""),
        (1.4, flight.EV_SINK_LAND, 7, 600.0, ""),
        (1.5, flight.EV_HBM_LANDED, 7, 0.0, ""),
        (2.6, flight.EV_SINK_COMPILE, 2, 400.0, ""),
        (2.7, flight.EV_SINK_FINALIZE, 0, 600.0, ""),
    ]
    for e in events:
        tf._ring[next(tf._seq) % tf._cap] = e
    tf.state = "done"
    tf._end_pc = 2.0
    rep = flight.analyze(tf)
    assert rep["phases"]["ici"] == pytest.approx(0.5)
    assert rep["phases"]["hbm"] == pytest.approx(1.0)
    assert rep["other_s"] == pytest.approx(0.5)
    assert sum(rep["phases"].values()) + rep["other_s"] == pytest.approx(2.0)
    assert rep["hbm"] == {"land_ms": 600.0, "read_ms": 100.0,
                          "compile_ms": 400.0, "finalize_ms": 600.0}
    # events() is by time, whatever order the slots were taken in.
    assert [e[0] for e in tf.events()] == sorted(e[0] for e in events)


def test_plain_task_has_empty_hbm_block_and_digest_stays_bounded():
    tf = flight.TaskFlight("plain")
    tf.record(flight.EV_REQUEST, 0, 0.0, "p")
    tf.record(flight.EV_LANDED, 0, 1.0, "cross")
    tf.finish("done")
    rep = flight.analyze(tf)
    assert rep["hbm"] == {} and rep["phases"]["hbm"] == 0
    assert "hbm landing" not in flight.render_waterfall(rep)
    # A shard's worth of landing events on top of its transfer events.
    big = flight.TaskFlight("shard")
    for n in range(55):
        big.record(flight.EV_PARENT_PIECES, n, 1.0)
        big.record(flight.EV_REQUEST, n, 0.0, "10.0.0.1:4000")
        big.record(flight.EV_LANDED, n, 30.0, "cross")
        big.record(flight.EV_HBM_START, n)
        big.record(flight.EV_SINK_READ, n, 40.0)
        big.record(flight.EV_SINK_CHECKSUM, n, 15.0)
        big.record(flight.EV_SINK_LAND, n, 140.0)
        big.record(flight.EV_HBM_LANDED, n)
    big.record(flight.EV_SINK_FINALIZE, 0, 300.0)
    big.finish("done")
    d = flight.digest(big)
    assert d["bytes"] <= flight.DIGEST_MAX_BYTES
    assert set(d["phases"]) == set(flight.PHASES)


@pytest.mark.parametrize("capacity", [32768, 256])
def test_two_threads_lose_no_event(capacity):
    """The event loop and the landing thread record into one ring."""
    per_thread = 10_000
    tf = flight.TaskFlight("two-writers", capacity=capacity)
    start = threading.Barrier(2)

    def writer(who: int) -> None:
        start.wait(timeout=10)
        for i in range(per_thread):
            tf.record(flight.EV_SINK_READ, i, float(who))

    threads = [threading.Thread(target=writer, args=(who,)) for who in (0, 1)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert tf.events_total == 2 * per_thread
    assert tf.events_dropped == max(0, 2 * per_thread - capacity)
    events = tf.events()
    assert len(events) == min(capacity, 2 * per_thread)
    seen = {(int(aux), piece) for _, _, piece, aux, _ in events}
    assert len(seen) == len(events)      # no slot written twice
    if capacity >= 2 * per_thread:
        assert seen == {(who, i) for who in (0, 1) for i in range(per_thread)}
    assert [e[0] for e in events] == sorted(e[0] for e in events)


def test_dispatcher_stamps_parent_pieces():
    from dragonfly2_tpu.daemon.peer.piece_dispatcher import PieceDispatcher

    tf = flight.TaskFlight("announce")
    d = PieceDispatcher(flight=tf)
    d.upsert_parent("seed-1", "10.0.0.1", 4000)
    d.on_parent_pieces("seed-1", [])            # a keepalive: nothing held
    d.on_parent_pieces("nobody", [1, 2])        # not a parent of ours
    d.on_parent_pieces("seed-1", [5, 3, 4], total_piece_count=8)
    rows = [(piece, aux) for _, code, piece, aux, _ in tf.events()
            if code == flight.EV_PARENT_PIECES]
    assert rows == [(3, 3.0)]
    assert flight.EVENT_NAMES[flight.EV_PARENT_PIECES] == "parent_pieces"


def test_dfget_device_explain_shows_the_hbm_block(run_async, tmp_path):
    """The served path: a ``--device tpu`` pull's flight report books the
    landing as ``hbm`` and carries the per-step block; a re-land adds its
    backfill's spans to the same flight; ``--explain`` prints it."""
    from dragonfly2_tpu.client import dfget as dfget_lib
    from dragonfly2_tpu.proto.common import UrlMeta
    from tests.test_device_sink import SHA, _start_sink_daemon
    from tests.test_p2p_e2e import start_origin, start_scheduler

    async def body():
        origin, oport, _ = await start_origin()
        sched = await start_scheduler()
        peer = await _start_sink_daemon(tmp_path, "explain", sched.port())
        try:
            def pull():
                return dfget_lib.download(dfget_lib.DfgetConfig(
                    url=f"http://127.0.0.1:{oport}/blob", output="",
                    daemon_sock=peer.config.unix_sock,
                    meta=UrlMeta(digest=SHA), device="tpu", explain=True,
                    allow_source_fallback=False, timeout=60.0))

            r1 = await pull()
            assert r1["device_verified"] and not r1["from_reuse"]
            assert peer.task_manager.device_sinks.take(r1["task_id"])
            r2 = await pull()
            assert r2["device_verified"] and r2["from_reuse"]
            return r1["flight"], r2["flight"]
        finally:
            await peer.stop()
            await sched.stop()
            await origin.cleanup()

    cold, reland = run_async(body(), timeout=120)
    rep = cold["report"]
    assert rep["phases"]["hbm"] > 0 and rep["phases"]["ici"] == 0
    assert rep["event_counts"]["sink_land"] == 3
    assert rep["event_counts"]["sink_finalize"] == 1
    for key in ("land_ms", "read_ms", "checksum_ms", "stage_ms", "put_ms",
                "assemble_ms", "finalize_ms"):
        assert rep["hbm"][key] > 0, key
    assert "hbm landing, ms on the landing thread" in cold["text"]
    assert cold["digest"]["phases"]["hbm"] == rep["phases"]["hbm"]
    again = reland["report"]
    # The re-land's three pieces are one group of its one stack.
    assert again["event_counts"]["sink_land"] == 3 + 1
    assert again["event_counts"]["sink_finalize"] == 2
    assert again["hbm"]["read_ms"] > rep["hbm"]["read_ms"]


def test_daemon_without_a_sink_still_imports_no_jax():
    """The spans live beside the sink (ops/hbm_sink.py imports jax); the
    modules every daemon imports must not reach it."""
    code = (
        "import sys\n"
        "import dragonfly2_tpu.cli.main\n"
        "import dragonfly2_tpu.daemon.daemon\n"
        "import dragonfly2_tpu.daemon.peer.task_manager\n"
        "import dragonfly2_tpu.daemon.peer.piece_dispatcher\n"
        "import dragonfly2_tpu.daemon.peer.device_sink\n"
        "import dragonfly2_tpu.pkg.flight\n"
        "import dragonfly2_tpu.pkg.metrics_server\n"
        "assert 'jax' not in sys.modules, 'jax imported'\n"
        "assert 'dragonfly2_tpu.ops.hbm_sink' not in sys.modules\n")
    done = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=120)
    assert done.returncode == 0, done.stderr[-2000:]


# -- the completion path's readers (PR 36) ----------------------------------
# cert_wait / parent_* come from the conductor and the sync stream, not from
# the sink; their readers are held to synthetic flights as the sink's are.

COMPLETION_READERS = ("cert_wait_ms", "seed_verify_ms", "seed_hash_behind_pct",
                      "origin_first_byte_ms")


def _op(events, nbytes=55 << 20, piece_bytes=1 << 20, ranged=None):
    """An operation as a driver leaves it: ``flight`` is (t, name, piece,
    aux) by time; ``ranged`` one ``{"flight": ...}`` a task where the
    operation was a sharded pull."""
    op = types.SimpleNamespace(flight=sorted(events), nbytes=nbytes,
                               piece_bytes=piece_bytes)
    if ranged is not None:
        op.ranged = [{"flight": sorted(f)} for f in ranged]
        op.flight = sorted(e for f in ranged for e in f)
    return op


def _cold(first_byte, verify, behind, waited):
    """One task's flight: four origin groups' first bytes, the last of
    them stamped first, then the seed's verify on ``done``."""
    return [(1.0, "scheduled", -1, 0.0),
            (4.99, "parent_source_first_byte", 41, first_byte + 30.0),
            (5.0, "parent_source_first_byte", 0, first_byte),
            (5.1, "parent_pieces", 0, 1.0),
            (7.9, "parent_verified", behind, verify),
            (7.9, "parent_done", 55, 0.0),
            (8.0, "cert_wait", 1, waited),
            (8.0, "task_done", -1, 0.0)]


@pytest.mark.parametrize("metric,value", [
    ("cert_wait_ms", 1800.0),              # median of 1700, 1800, 1900
    ("seed_verify_ms", 1750.0),
    ("seed_hash_behind_pct", 100.0 * 41 / 55),
    # The earliest event of the task, not the smallest wait.
    ("origin_first_byte_ms", 4030.0),
])
def test_completion_readers_over_one_task_operations(monkeypatch, metric,
                                                     value):
    run = types.SimpleNamespace(ops=[
        _op(_cold(3900.0, 1650.0, 38, 1700.0)),
        _op(_cold(4000.0, 1750.0, 41, 1800.0)),
        _op(_cold(4100.0, 1850.0, 44, 1900.0)),
        _op([(1.0, "scheduled", -1, 0.0)]),       # stamped none: left out
    ])
    assert load_reader(monkeypatch, metric)(run) == pytest.approx(value)


def test_origin_first_byte_sums_a_ranged_operations_tasks(monkeypatch):
    """A sharded pull: the earliest first byte of each of its tasks,
    summed; a task that met a seed already complete carries none."""
    tasks = [[(1.0, "parent_source_first_byte", 0, 12.0)],
             [(2.0, "parent_source_first_byte", 7, 610.0),
              (2.1, "parent_source_first_byte", 0, 400.0)],
             [(3.0, "parent_pieces", 0, 1.0)]]
    read = load_reader(monkeypatch, "origin_first_byte_ms")
    run = types.SimpleNamespace(ops=[_op([], ranged=tasks)])
    assert read(run) == pytest.approx(12.0 + 610.0)
    # The merged flight is not what is read: the tasks stay apart.
    assert len(run.ops[0].flight) == 4


@pytest.mark.parametrize("metric", COMPLETION_READERS)
def test_completion_readers_read_nothing_without_their_events(monkeypatch,
                                                              metric):
    """A re-land, a task with no digest, a program older than the events:
    None, and the result line leaves the metric out."""
    read = load_reader(monkeypatch, metric)
    bare = [(1.0, "scheduled", -1, 0.0), (1.5, "parent_pieces", 0, 1.0),
            (2.0, "hbm_landed", 0, 0.0), (2.1, "task_done", -1, 0.0)]
    assert read(types.SimpleNamespace(ops=[])) is None
    assert read(types.SimpleNamespace(ops=[_op(bare), _op([])])) is None
    assert read(types.SimpleNamespace(
        ops=[_op([], ranged=[bare, []])])) is None
