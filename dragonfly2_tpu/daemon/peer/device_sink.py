"""Device sink manager: the daemon-side terminal store for ``--device=tpu``.

The reference daemon's terminal store is always the filesystem
(client/daemon/storage/storage_manager.go:54-131 — TaskStorageDriver with
one local-disk implementation). The TPU build adds a second, selectable
terminal: TPU HBM. When a download request carries ``device="tpu"``, every
verified piece is landed into a preallocated device buffer as it arrives
(ops/hbm_sink.HBMSink), completion re-verifies the landed bytes ON DEVICE
against host-side checksums, and the result is consumable as a JAX array
(``as_tensor``) or a mesh-sharded array (``shard_to_mesh``) without ever
re-reading host storage.

Threading: all sink mutations run on ONE dedicated worker thread — the
piece read-back, host→device staging and the jit dispatches would
otherwise stall the daemon's event loop (upload serving, RPC) for the
duration of each copy. The async surface awaits that thread, so the
download path still backpressures on landing. The thread has helpers
(``ops/hbm_sink.py``: ``df-sink-helper``, one small pool a process) for
the host pass over a group of pieces of two chunk floors and more
(``HBMSink.read_pieces``; a group is one piece as it arrives, and in a
finalize's backfill as many as the open staging stack has rows free): each
takes the next chunk of the group, reads it from the store into the same
chunk of its piece's row and checksums it there before it returns; they
touch no sink and no manager state, and the landing thread waits ONCE a
group for every one of them before it goes on, a failed chunk's error in
hand or not.

A finalize is cut in two where the host's work on the sink's bytes is over,
the ``device_put`` call of its last stack: from there the landing thread
takes the next queued job, and the TAIL (the wait for the sink's puts, the
assembly's dispatch, the fetched checksums, their comparison with the
host's, the bookkeeping) runs on one completer thread (``df-device-sink-tail``),
tails one at a time in the order they were handed over, so two assemblies
are never in flight together. A sink in its tail belongs to the tail alone:
the landing thread waits for the tail before it would touch such a sink
again, and a second finalize of the task joins the tail in flight.
``finalize()`` resolves only when the tail has verified the sink. What both
threads and the event loop share of the manager (``_sinks``, ``_degraded``,
``_errors``, ``_tails``) changes under one lock.

Spans: that thread stamps its steps into the task's flight ring
(``sink_land`` > ``sink_read``, ``sink_checksum``, ``sink_stage``,
``sink_put``; ``sink_finalize`` > the backfill's ``sink_land``s,
``sink_assemble`` > ``sink_compile``), one event at a step's end with its
ms — a child is a span that lies inside another, a task's steps being
stamped one after the other: by the landing thread up to the hand-over, by
the completer from there (``sink_assemble``, ``sink_compile``, then
``sink_tail``, the hand-over -> the tail's end with ``piece`` = the jobs the
landing thread started meanwhile, and ``sink_finalize``, job start ->
verified as ever). The helpers stamp nothing, so ``sink_read`` and
``sink_checksum`` are the two parts of the one pass's wall time (what
the thread that read longest spent reading, and the rest) however many ran
it. A ``sink_land`` is one pass and the staging of its pieces: a piece as
it arrives, a group of the backfill (``piece`` the group's lowest).
Before them each job stamps ``sink_wait``, the time it stood queued for
the thread: a re-land is ONE job (``_finalize_sync``), so several tasks
landing at once wait for each other's host passes there. A landing that
the caller wants whole on every chip of a mesh (``replicate``) is one more
job of the same thread after the verified one: ``sink_replicate`` (the
fan-out dispatched -> every chip's copy ready) and ``sink_verify_chips``
(each chip's checksums of its own copy dispatched -> all compared).

Lifecycle: sinks are created lazily at the first landed piece (task
metadata — length and piece size — is unknown at request time), verified
at completion, and held up to a TTL for the consuming process to claim
(``take``) — under cap pressure a verified resident past its claim
grace may be evicted early for a new landing (the disk store stays
authoritative). Failed or aborted tasks discard their sink immediately;
unclaimed sinks expire so HBM is not leaked. The disk store remains
authoritative for upload/reuse — the sink is an *additional* terminal,
which is what lets other peers still fetch pieces from this host.
"""

from __future__ import annotations

import asyncio
import threading
import time
from concurrent.futures import Future, ThreadPoolExecutor, wait

from dragonfly2_tpu.pkg import dflog, flight as flightlib, metrics

log = dflog.get("peer.device_sink")

SINK_LANDED_BYTES = metrics.counter(
    "device_sink_landed_bytes_total",
    "Bytes landed into device sinks, by the local device (jax's id) whose "
    "sink took them", ("chip",))
SINK_STORE_READ_BYTES = metrics.counter(
    "device_sink_store_read_bytes_total",
    "Bytes the landing thread and its helpers read back from the piece "
    "store into staging rows (a re-land reads every byte it lands)")
SINK_VERIFY_COUNT = metrics.counter(
    "device_sink_verify_total", "Device sink verifications", ("result",))
# An assembly of a geometry met for the first time compiles, in its
# finalize's tail, while the tails handed over behind it wait: once per
# object geometry, whatever order the pieces arrived in.
SINK_COMPILES = metrics.counter(
    "device_sink_compiles_total",
    "Assemblies that compiled their program (a new geometry)")
SINK_COMPILE_SECONDS = metrics.counter(
    "device_sink_compile_seconds_total",
    "Backend-compile seconds spent inside device sink assemblies")
SINK_WAIT_SECONDS = metrics.counter(
    "device_sink_wait_seconds_total",
    "Seconds jobs stood queued for the one landing thread")
SINK_HOP_BYTES = metrics.counter(
    "device_sink_hop_bytes_total",
    "Bytes that reached a local device by a copy from another device and not "
    "from the host: received over the fan-out by devices other than the "
    "landing device (fanout: a landing placed whole on every chip of a mesh, "
    "its padding to whole pieces included), or copied by the client API's "
    "jax.device_put of landed tensors or shards to a sharding (device_put)",
    ("how",))
SINK_CHIP_VERIFY_COUNT = metrics.counter(
    "device_sink_chip_verify_total",
    "Placements whole-on-every-chip by what the per-chip verification found: "
    "every device's copy equal to the host's checksums, or one that differs",
    ("result",))
SINK_TAILS = metrics.counter(
    "device_sink_tails_total",
    "Finalize tails (the wait for the sink's last put, the assembly's "
    "dispatch, the fetched checksums and their comparison, off the landing "
    "thread) by whether the landing thread started another job while the "
    "tail ran (overlapped) or found none queued (alone)", ("how",))
SINKS_LANDING = metrics.gauge(
    "device_sink_landing",
    "Device sinks created and not yet verified or dropped")


class DeviceSinkError(Exception):
    pass


class _SpanStamp:
    """Where one sink's spans go: the task's flight ring (``flight``, set
    by the manager before each step on the landing thread) and the compile
    counters. An object of its own, so that the HBMSink that calls it
    holds nothing that leads back to its owner: in a cycle, a dropped
    sink's content-sized device buffers would wait for the cyclic
    collector instead of going with the last reference."""

    __slots__ = ("flight",)

    def __init__(self, flight: "flightlib.TaskFlight | None" = None):
        self.flight = flight

    def __call__(self, code: int, piece: int, ms: float,
                 note: str = "") -> None:
        if code == flightlib.EV_SINK_COMPILE:
            SINK_COMPILES.inc()
            SINK_COMPILE_SECONDS.inc(ms / 1000.0)
        elif code == flightlib.EV_SINK_WAIT:
            SINK_WAIT_SECONDS.inc(ms / 1000.0)
        if self.flight is not None:
            self.flight.record(code, piece, ms, note)


class _FinalizeSpan:
    """A finalize's ``sink_finalize``, job start -> verified: begun on the
    landing thread and ended by whichever thread ends the finalize, the
    completer where there is a tail. ``piece`` counts the pieces the
    backfill landed, ``note`` names the sink's chip."""

    __slots__ = ("stamp", "t0", "piece", "note")

    def __init__(self, tf):
        self.stamp = _SpanStamp(tf)
        self.t0 = time.perf_counter()
        self.piece = 0
        self.note = ""

    def end(self) -> None:
        self.stamp(flightlib.EV_SINK_FINALIZE, self.piece,
                   (time.perf_counter() - self.t0) * 1000.0, self.note)


class TaskDeviceSink:
    """One task's HBM landing: wraps ops.hbm_sink.HBMSink with the piece
    bookkeeping the daemon needs (which pieces landed, their host digests,
    staleness)."""

    def __init__(self, task_id: str, content_length: int, piece_size: int, *,
                 device=None, batch_pieces: int = 8):
        from dragonfly2_tpu.ops.hbm_sink import HBMSink

        # HBM offsets are word-addressed: a non-word-aligned piece size
        # (only possible for single-piece tasks, where it equals the
        # content length) rounds up — zero padding is checksum-neutral.
        total_pieces = max(
            1, (content_length + piece_size - 1) // piece_size)
        if piece_size % 4 and total_pieces > 1:
            raise DeviceSinkError(
                f"piece size {piece_size} not 4-byte aligned")
        aligned = piece_size + ((-piece_size) % 4)
        self.task_id = task_id
        self.stamp = _SpanStamp()
        self.sink = HBMSink(content_length, aligned, device=device,
                            batch_pieces=batch_pieces, stamp=self.stamp)
        self.created_at = time.time()
        self.verified = False
        self.verified_at = 0.0
        # Counted in device_sink_landing: set by the manager that holds
        # it, cleared once, when it verifies or is dropped.
        self.landing = False
        # Host-side piece digests at land time: lets a later finalize
        # detect that the store's content changed under a resident sink.
        self.piece_digests: dict[int, str] = {}
        self._landed_bytes = SINK_LANDED_BYTES.labels(str(self.sink.device.id))

    @property
    def device(self):
        """The local device the bytes land on."""
        return self.sink.device

    def land(self, piece_num: int, data: bytes, digest: str = "") -> None:
        self.sink.land_piece(piece_num, data)
        self.piece_digests[piece_num] = digest
        self._landed_bytes.inc(len(data))

    @property
    def landed(self) -> set[int]:
        return self.sink.landed

    @property
    def content_length(self) -> int:
        return self.sink.content_length

    def verify(self) -> None:
        try:
            self.sink.verify()
        except ValueError as e:
            SINK_VERIFY_COUNT.labels("corrupt").inc()
            raise DeviceSinkError(str(e)) from e
        SINK_VERIFY_COUNT.labels("ok").inc()
        self.verified = True
        self.verified_at = time.time()

    # Consumption — delegates to the HBMSink.

    def as_words(self):
        return self.sink.as_words()

    def as_bytes_array(self):
        return self.sink.as_bytes_array()

    def as_tensor(self, dtype, shape):
        return self.sink.as_tensor(dtype, shape)

    def shard_to_mesh(self, mesh, axis_name: str = "d"):
        return self.sink.shard_to_mesh(mesh, axis_name)

    def replicate(self, mesh, axis_name: str = "d") -> None:
        """Whole on every chip of ``mesh``, each copy verified on the chip
        that holds it (``HBMSink.replicate``): ``as_words()`` and every
        view cut from it are replicated from here on. A copy that differs
        raises DeviceSinkError naming the chip, and is counted."""
        if not self.verified:
            raise DeviceSinkError(
                f"replicate on unverified sink {self.task_id[:16]}")
        try:
            received = self.sink.replicate(mesh, axis_name)
        except ValueError as e:
            SINK_CHIP_VERIFY_COUNT.labels("corrupt").inc()
            raise DeviceSinkError(str(e)) from e
        if received:
            SINK_HOP_BYTES.labels("fanout").inc(
                received * 4 * self.sink.padded_words)
            SINK_CHIP_VERIFY_COUNT.labels("ok").inc()


class DeviceSinkManager:
    """Owns the per-task sinks a daemon is landing. Selected per request
    (FileTaskRequest.device == "tpu"); gated by TPUSinkOption.enabled."""

    def admit(self):
        """Admission bound for CLIENT-API device pulls: an async context
        holding one HBM-sink slot (one below ``max_tasks``, so an
        unrelated RPC-path device task is never starved). Shared across
        every download_to_device/download_sharded on this daemon —
        per-call bounds compose into cap overruns when calls run
        concurrently. RPC-path requests deliberately do not admit: their
        contract is graceful disk-only degradation at the cap, while the
        client API's contract is a verified device landing or an error."""
        if self._admission is None:
            self._admission = asyncio.Semaphore(max(1, self.max_tasks - 1))
        return self._admission

    def __init__(self, *, batch_pieces: int = 8, max_tasks: int = 4,
                 ttl: float = 600.0, device=None):
        # jax comes in with the first sink manager, not with this module.
        from dragonfly2_tpu.ops import hbm_sink

        hbm_sink.watch_compiles()
        self._span = hbm_sink.span
        self._admission = None
        self.claim_grace_s = 10.0   # see _create's eviction rule
        # Task ids a client pull has announced it WILL claim (set before
        # the landing starts, cleared after take) — never evicted.
        # Refcounted: concurrent claimers of one deduped task each hold
        # a reference; the first to finish must not strip the others'.
        self._protected: dict[str, int] = {}
        self.batch_pieces = batch_pieces
        self.max_tasks = max_tasks
        self.ttl = ttl
        self._device = device
        self._sinks: dict[str, TaskDeviceSink] = {}
        # Tasks whose sink hit a device error mid-download: disk-only for
        # the rest of this attempt (cleared on discard → retry is fresh).
        self._degraded: set[str] = set()
        # The FIRST device error that left a task disk-only, kept until
        # the task's final progress reports it (outcome) or the task is
        # discarded: the degrade contract stays, its silence does not.
        self._errors: dict[str, str] = {}
        # Single worker: serializes sink mutation (HBMSink is not
        # thread-safe) and keeps device copies off the event loop.
        self._exec = ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="df-device-sink")
        # The finalizes' tails, one at a time in the order they were handed
        # over: at most one assembly in flight, one sink mid-assembly in HBM.
        self._completer = ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="df-device-sink-tail")
        # The tails in flight, task id -> (the sink that is the tail's alone
        # meanwhile, the finalize's future).
        self._tails: dict[str, tuple[TaskDeviceSink, Future]] = {}
        # Jobs the landing thread has started: what a tail reads at its
        # hand-over and at its end to say whether anything ran beside it.
        self._started = 0
        # _sinks, _degraded, _errors and _tails change under it: the
        # landing thread, the completer and the event loop (take, discard,
        # outcome, gc) all do.
        self._lock = threading.RLock()

    def close(self) -> None:
        self._exec.shutdown(wait=False, cancel_futures=True)
        self._completer.shutdown(wait=False, cancel_futures=True)

    async def _run(self, tf, piece: int, fn, *args):
        """One job for the landing thread. As it starts there it stamps
        ``sink_wait``: how long it stood queued since this call."""
        submitted = time.perf_counter()

        def job():
            self._started += 1
            _SpanStamp(tf)(flightlib.EV_SINK_WAIT, piece,
                           (time.perf_counter() - submitted) * 1000.0)
            return fn(*args)

        return await asyncio.get_running_loop().run_in_executor(
            self._exec, job)

    @staticmethod
    def _settle(sink: TaskDeviceSink) -> None:
        """The sink is no longer landing: verified, or dropped before."""
        if sink.landing:
            sink.landing = False
            SINKS_LANDING.dec()

    def _drop(self, task_id: str) -> TaskDeviceSink | None:
        """Forget the task's sink. One in its tail is forgotten at once all
        the same: the tail ends on its own reference, hands the finalize
        that waits for it what it found, and the sink's stacks and HBM go
        with it; a later finalize of the task starts from the store and
        joins nothing."""
        with self._lock:
            sink = self._sinks.pop(task_id, None)
            if sink is not None:
                self._settle(sink)
                self._tail_over(task_id, sink)
            return sink

    def _tail_over(self, task_id: str, sink: TaskDeviceSink) -> None:
        """Under the lock: the task's tail in flight, if it is ``sink``'s,
        is no longer one to join or to wait for."""
        if self._tails.get(task_id, (None,))[0] is sink:
            del self._tails[task_id]

    def _await_tail(self, task_id: str) -> None:
        """On the landing thread, before it touches the task's sink: a sink
        in its tail is the completer's alone (HBMSink is not thread-safe)."""
        tail = self._tails.get(task_id)
        if tail is not None:
            wait([tail[1]])

    # -- landing ----------------------------------------------------------

    async def on_piece(self, task_id: str, store, rec, tf=None,
                       device=None) -> None:
        """Land one verified piece as it arrives (conductor/back-source
        on_piece hook). Creation is lazy: the first piece to arrive after
        the task's length and piece size are known allocates the buffer,
        on the local ``device`` the request names (none: the manager's).
        ``tf`` is the task's flight, for the landing thread's spans."""
        await self._run(tf, rec.num, self._land_sync, task_id, store, rec,
                        tf, device)

    def _land_sync(self, task_id: str, store, rec, tf=None,
                   device=None) -> None:
        with self._span(tf and tf.record, flightlib.EV_SINK_LAND, rec.num):
            self._land_inner(task_id, store, rec, tf, device)

    def _land(self, sink: TaskDeviceSink, store, recs, tf) -> None:
        """Read a group of pieces back from the store, straight into the
        rows of the sink's staging stack they will be put from,
        checksummed in the same pass, and stage them: at most the rows the
        open stack has free (``HBMSink.free_rows``)."""
        sink.stamp.flight = tf
        stored = [store.piece(rec.num) for rec in recs]

        # A range of a piece into the same range of its row. Where helpers
        # read the ranges, a failure in one is raised when all are back,
        # so none can still write into a stack that the degraded sink has
        # given up.
        def read_into(i: int, row, start: int, stop: int) -> None:
            store.read_into(stored[i].offset + start, stop - start, row,
                            at=start)

        landed = sink.sink.read_pieces(
            [(piece.num, piece.size) for piece in stored], read_into)
        SINK_STORE_READ_BYTES.inc(sum(piece.size for piece in stored))
        store.touch()
        for rec, data in zip(recs, landed):
            sink.land(rec.num, data, rec.digest)

    def _land_inner(self, task_id: str, store, rec, tf, device) -> None:
        if task_id in self._degraded:
            return
        self._await_tail(task_id)
        sink = self._sinks.get(task_id)
        if sink is None:
            m = store.metadata
            if m.content_length < 0 or m.piece_size <= 0:
                return  # metadata not known yet; backfill catches it later
            sink = self._create(task_id, m.content_length, m.piece_size,
                                device)
            if sink is None:
                return
        if rec.num in sink.landed:
            return
        if rec.num >= sink.sink.total_pieces:
            log.warning("piece out of sink range, skipped",
                        task=task_id[:16], piece=rec.num)
            return
        try:
            self._land(sink, store, [rec], tf)
        except Exception as e:
            # Device trouble mid-stream (HBM OOM in the staging device_put,
            # runtime errors): degrade THIS task to disk-only — the
            # download itself must not fail, and later pieces must not
            # retry a doomed sink.
            log.warning("device landing failed; degrading to disk-only",
                        task=task_id[:16], error=str(e)[:200])
            with self._lock:
                self._note_error(task_id, "landing", e)
                self._drop(task_id)
                self._degraded.add(task_id)

    def _note_error(self, task_id: str, stage: str, err) -> None:
        text = err if isinstance(err, str) else f"{type(err).__name__}: {err}"
        with self._lock:
            self._errors.setdefault(task_id, f"{stage}: {text}"[:600])

    def _create(self, task_id: str, content_length: int,
                piece_size: int, device=None) -> TaskDeviceSink | None:
        """A sink for the task on ``device`` (none named: the manager's
        own, the first local device where it has none). The cap counts the
        sinks of every chip together: it bounds the staging stacks on the
        host as much as the HBM."""
        with self._lock:
            return self._create_locked(task_id, content_length, piece_size,
                                       device or self._device)

    def _create_locked(self, task_id: str, content_length: int,
                       piece_size: int, device) -> TaskDeviceSink | None:
        self._expire()
        if len(self._sinks) >= self.max_tasks:
            # Residents are cached conveniences — the disk store stays
            # authoritative — so a verified, unclaimed sink yields its
            # HBM to a NEW landing rather than failing it (oldest first).
            # Mid-landing sinks are never evicted, and a freshly verified
            # sink gets a claim grace: its requester is typically between
            # verify and take() (both await points), and evicting there
            # would strand a successful download in a lose-the-sink loop.
            now = time.time()
            verified = sorted(
                (s for s in self._sinks.values()
                 if s.verified and s.task_id not in self._protected),
                key=lambda s: s.created_at)
            # Grace is a PREFERENCE, not a guarantee: evict out-of-grace
            # residents first, but when every (unprotected) resident is
            # freshly verified (e.g. an RPC preheat just warmed max_tasks
            # sinks) still evict the oldest rather than hard-failing the
            # new landing. Sinks a client pull has announced it will
            # claim (protect/unprotect) are never candidates — evicting
            # one strands a completed, verified download in a
            # lose-the-sink retry loop.
            evictable = ([s for s in verified
                          if now - s.verified_at > self.claim_grace_s]
                         or verified)
            if evictable:
                # A resident of the chip the new landing goes to gives its
                # HBM where it is needed; one of another chip only a slot.
                victim = min(evictable, key=lambda s: (
                    device is not None and s.device != device, s.created_at))
                log.info("evicting resident device sink for new landing",
                         evicted=victim.task_id[:16], task=task_id[:16])
                del self._sinks[victim.task_id]
            else:
                log.warning("device sink cap reached; landing to disk only",
                            task=task_id[:16], cap=self.max_tasks)
                self._note_error(
                    task_id, "create",
                    f"sink cap reached ({self.max_tasks} tasks resident)")
                return None
        try:
            sink = TaskDeviceSink(task_id, content_length, piece_size,
                                  device=device,
                                  batch_pieces=self.batch_pieces)
        except Exception as e:
            # Includes device OOM (XlaRuntimeError): degrade to disk-only
            # rather than failing the whole download.
            log.warning("device sink unavailable for task",
                        task=task_id[:16], error=str(e)[:200])
            self._note_error(task_id, "create", e)
            return None
        self._sinks[task_id] = sink
        sink.landing = True
        SINKS_LANDING.inc()
        log.info("device sink created", task=task_id[:16],
                 bytes=content_length, chip=sink.device.id)
        return sink

    # -- completion -------------------------------------------------------

    async def finalize(self, task_id: str, store, tf=None,
                       device=None) -> TaskDeviceSink | None:
        """Complete the landing: backfill pieces the streaming hook missed
        (reuse path, tiny/small shortcuts, pre-metadata arrivals), then
        verify every landed piece on device. Returns None when no sink
        could be allocated (cap reached, misaligned pieces) — disk-only
        degradation; raises DeviceSinkError on device-copy CORRUPTION.
        ``tf`` and ``device`` as for ``on_piece``; a sink of the task that
        lies on another chip than the one named (a resident that an earlier
        request left there) is dropped and built again from the store on
        the named one. The landing thread's job ends with the last stack's
        ``device_put``; this call returns when the tail behind it has
        verified the sink."""
        tail = await self._run(tf, 0, self._finalize_sync, task_id, store,
                               tf, device)
        # Shielded: a caller that gives up must not cancel a tail that
        # other claimers of the task wait for, nor leave a queued one unrun
        # with its sink neither verified nor dropped.
        return await asyncio.shield(asyncio.wrap_future(tail))

    def _finalize_sync(self, task_id: str, store, tf=None,
                       device=None) -> Future:
        """The landing thread's part of a finalize, up to the hand-over.
        Returns the future of the finalize's result: its tail's, or one
        that is over already where there is nothing to verify."""
        step = _FinalizeSpan(tf)
        # The profiler's view of the thread's part; the flight's
        # sink_finalize is stamped where the finalize ends (``step.end``).
        with self._span(None, flightlib.EV_SINK_FINALIZE):
            tail = None
            try:
                with self._lock:
                    degraded = task_id in self._degraded
                    self._degraded.discard(task_id)  # next attempt starts fresh
                if not degraded:
                    tail = self._finalize_inner(task_id, store, tf, step,
                                                device)
            except Exception as e:
                self._disk_only(task_id, e)
        if tail is None:
            step.end()
            tail = Future()
            tail.set_result(None)
        return tail

    def _disk_only(self, task_id: str, err,
                   only: TaskDeviceSink | None = None) -> None:
        """Environment failures (OOM during backfill staging, assembly
        dispatch errors, store read races) degrade to disk-only — the
        digest-verified disk result must not be discarded over a
        device-side hiccup. ``only``: the sink that failed, in its tail;
        where the manager has forgotten it meanwhile (discarded, a retry's
        sink in its place) there is nothing of the task's to degrade."""
        log.warning("device finalize failed; disk-only result",
                    task=task_id[:16], error=str(err)[:200])
        with self._lock:
            if only is None or self._sinks.get(task_id) is only:
                self._note_error(task_id, "finalize", err)
                self._drop(task_id)

    def _finalize_inner(self, task_id: str, store, tf, step,
                        device=None) -> Future | None:
        m = store.metadata
        in_tail = self._tails.get(task_id)
        if in_tail is not None:
            sink, tail = in_tail
            if ((device is None or sink.device == device)
                    and not self._stale(sink, store)):
                # Another claimer of the task, while its sink is being
                # verified: the one verification answers both.
                step.note = f"chip={sink.device.id}"
                tail.add_done_callback(lambda _: step.end())
                return tail
            wait([tail])
        sink = self._sinks.get(task_id)
        if sink is not None and device is not None and sink.device != device:
            log.info("device sink on another chip than asked; rebuilding",
                     task=task_id[:16], chip=sink.device.id, asked=device.id)
            self._drop(task_id)
            sink = None
        if sink is not None and self._stale(sink, store):
            # The store's content changed under a resident sink (same task
            # id, new bytes — e.g. origin changed between invalidate and
            # retry): a mixed buffer must never verify. Rebuild.
            log.warning("device sink stale vs store; rebuilding",
                        task=task_id[:16])
            self._drop(task_id)
            sink = None
        if sink is None:
            sink = self._create(task_id, m.content_length, m.piece_size,
                                device)
            if sink is None:
                return None
        sink.stamp.flight = tf
        step.note = f"chip={sink.device.id}"
        # Every missing piece is in the store before the first is read and
        # nothing orders the reads: a host pass takes as many as the open
        # stack has rows free.
        missing = [rec for rec in store.get_pieces()
                   if rec.num not in sink.landed]
        while missing:
            group = missing[:sink.sink.free_rows()]
            del missing[:len(group)]
            with self._span(tf and tf.record, flightlib.EV_SINK_LAND,
                            group[0].num):
                self._land(sink, store, group, tf)
            step.piece += len(group)
        # The host's work on the sink's bytes ends with the last stack's
        # device_put: what is left waits for the device, and this thread
        # takes the next queued job, whose first act is a host pass.
        sink.sink.flush()
        with self._lock:
            tail = self._completer.submit(
                self._tail, task_id, sink, step, time.perf_counter(),
                self._started)
            self._tails[task_id] = (sink, tail)
        return tail

    def _tail(self, task_id: str, sink: TaskDeviceSink, step,
              handed: float, started: int) -> TaskDeviceSink | None:
        """What is left of a finalize once its last stack was put, on the
        completer: the wait for the sink's puts, the assembly's dispatch,
        the fetched checksums and their comparison with the host's
        (``TaskDeviceSink.verify``; a corrupt piece's DeviceSinkError goes
        to the caller of ``finalize``), then the bookkeeping. Stamps
        ``sink_tail`` (``aux`` = ms since the hand-over, ``piece`` = jobs
        the landing thread started in that time: 0 says nothing ran beside
        it) and ends the finalize's own span."""
        try:
            with self._span(None, flightlib.EV_SINK_TAIL):
                try:
                    sink.verify()
                except DeviceSinkError:
                    raise
                except Exception as e:
                    self._disk_only(task_id, e, sink)
                    return None
                with self._lock:
                    self._settle(sink)
                log.info("device sink verified", task=task_id[:16],
                         pieces=len(sink.landed))
                return sink
        finally:
            with self._lock:
                self._tail_over(task_id, sink)
            beside = self._started - started
            SINK_TAILS.labels("overlapped" if beside else "alone").inc()
            step.stamp(flightlib.EV_SINK_TAIL, beside,
                       (time.perf_counter() - handed) * 1000.0)
            step.end()

    async def replicate(self, sink: TaskDeviceSink, mesh,
                        axis_name: str = "d", tf=None) -> None:
        """Place a verified sink whole on every chip of ``mesh`` and verify
        every copy (``TaskDeviceSink.replicate``), as one more job of the
        landing thread: the sink is touched by no other thread, and the
        two spans are stamped where every ``sink_*`` span is. Raises
        DeviceSinkError where a chip's copy differs."""
        await self._run(tf, 0, self._replicate_sync, sink, mesh, axis_name,
                        tf)

    @staticmethod
    def _replicate_sync(sink: TaskDeviceSink, mesh, axis_name: str,
                        tf) -> None:
        sink.stamp.flight = tf
        sink.replicate(mesh, axis_name)

    @staticmethod
    def _stale(sink: TaskDeviceSink, store) -> bool:
        pieces = store.metadata.pieces
        for num, digest in sink.piece_digests.items():
            rec = pieces.get(num)
            if rec is None or (digest and rec.digest and rec.digest != digest):
                return True
        return False

    # -- consumption / lifecycle ------------------------------------------

    def protect(self, task_id: str) -> None:
        """Announce an imminent claim: the sink for ``task_id`` (existing
        or about to land) is exempt from cap-pressure eviction until
        ``unprotect``. Callers must pair with unprotect in a finally."""
        self._protected[task_id] = self._protected.get(task_id, 0) + 1

    def unprotect(self, task_id: str) -> None:
        n = self._protected.get(task_id, 0) - 1
        if n > 0:
            self._protected[task_id] = n
        else:
            self._protected.pop(task_id, None)

    def get(self, task_id: str) -> TaskDeviceSink | None:
        return self._sinks.get(task_id)

    def take(self, task_id: str) -> TaskDeviceSink | None:
        """Claim the sink (caller owns the buffer; manager forgets it)."""
        return self._drop(task_id)

    def discard(self, task_id: str) -> None:
        with self._lock:
            self._drop(task_id)
            self._degraded.discard(task_id)
            self._errors.pop(task_id, None)

    def outcome(self, task_id: str, verified: bool) -> dict:
        """What a device request's final progress says beside
        ``device_verified``: the device that holds the bytes, or the
        error that kept them off it."""
        with self._lock:
            sink = self._sinks.get(task_id)
            error = self._errors.pop(task_id, "")
        if verified and sink is not None:
            return {"device_platform": sink.sink.platform,
                    "device_kind": sink.sink.device_kind}
        return {"device_error": error} if error else {}

    def gc(self) -> None:
        """Periodic TTL sweep (daemon GC hook) — unclaimed sinks must not
        hold content-sized HBM for the daemon's lifetime."""
        self._expire()

    def _expire(self) -> None:
        now = time.time()
        with self._lock:
            for tid in [t for t, s in self._sinks.items()
                        if now - s.created_at > self.ttl]:
                log.info("device sink expired", task=tid[:16])
                self._drop(tid)
