"""Peer task conductor: orchestrates one P2P download.

Reference: client/daemon/peer/peertask_conductor.go (1636 LoC) — the
concurrency web tying together: the scheduler AnnouncePeer stream
(register :255, receive loop :673), the P2P piece pull (pullPieces :533)
with N download workers (:1009-1077 init, :1043 downloadPieceWorker hot
loop), per-parent synchronizer streams, back-to-source fallback
(backSource :503), piece result reporting (:1252-1314) and completion
(done/fail :1378+).

Flow:
  run() → announce register → dispatch on scheduler response:
    empty_task        → create empty content, finish
    need_back_source  → piece_manager.download_source, announcing pieces
    normal_task       → sync parents, spawn piece workers, fetch pieces
                        over HTTP, report results, reschedule on starvation
"""

from __future__ import annotations

import asyncio
import os
import time
from collections import deque

import msgpack

from dragonfly2_tpu.daemon.peer.piece_dispatcher import (
    PieceAssignment,
    PieceDispatcher,
    parent_key,
)
from dragonfly2_tpu.daemon.peer.piece_downloader import (
    PieceDownloader,
    failure_reason,
)
from dragonfly2_tpu.daemon.peer.piece_manager import PieceManager
from dragonfly2_tpu.daemon.peer.synchronizer import PieceTaskSynchronizer
from dragonfly2_tpu.pkg import dflog, metrics
from dragonfly2_tpu.pkg import flight as flightlib
from dragonfly2_tpu.pkg import retry as retrylib
from dragonfly2_tpu.pkg.errors import Code, DfError
from dragonfly2_tpu.pkg.piece import PieceInfo, Range, compute_piece_count
from dragonfly2_tpu.pkg.ratelimit import Limiter
from dragonfly2_tpu.proto import reportcodec
from dragonfly2_tpu import qos as qoslib
from dragonfly2_tpu.storage.local_store import LocalTaskStore

log = dflog.get("peer.conductor")

PIECE_DOWNLOAD_COUNT = metrics.counter(
    "peer_piece_download_total", "P2P piece downloads", ("result",))
BACK_SOURCE_COUNT = metrics.counter(
    "peer_back_source_total", "Tasks that fell back to origin")
# Typed degradation telemetry: every piece failure by reason code, parent
# quarantine entries by the reason that tipped them, and announce-stream
# recoveries. These are what the chaos e2e (and operators) read to see
# WHICH degradation path fired, not just that something failed.
PIECE_FAIL_REASON = metrics.counter(
    "peer_piece_failures_total",
    "P2P piece failures by typed reason code", ("reason",))
PARENT_QUARANTINE_COUNT = metrics.counter(
    "peer_parent_quarantine_total",
    "Parents entering the daemon-wide quarantine, by tipping reason",
    ("reason",))
# Seconds P2P children stood in _await_certification, by how the wait
# ended: beside peer_completion_rehash_total{skipped|hashed} it says what the
# skips cost (certified) and what the misses wasted (timeout).
CERT_WAIT_SECONDS = metrics.counter(
    "peer_task_cert_wait_seconds_total",
    "Seconds spent waiting for a certifying parent's done at completion",
    ("result",))
ANNOUNCE_RECONNECT_COUNT = metrics.counter(
    "peer_announce_reconnects_total",
    "Mid-download announce-stream recovery attempts", ("result",))
# The striped-broadcast yardstick: P2P piece bytes split by parent
# locality — intra rides the ICI fabric, cross is real DCN traffic,
# unlabeled means either end lacked TPU coordinates.
PIECE_BYTES = metrics.counter(
    "peer_piece_bytes_total",
    "P2P piece bytes downloaded, by parent ICI locality",
    ("locality",))
# The same bytes by what the parent is: a seed peer (its copy came from
# the origin) or a fellow peer (its copy came over P2P too). The share
# from peers is what a fan-out saves the seed; locality cannot say it (a
# seed may sit inside the slice, a fellow peer outside it).
PIECE_BYTES_BY_PARENT = metrics.counter(
    "peer_piece_bytes_by_parent_total",
    "P2P piece bytes downloaded, by the parent's kind (seed | peer)",
    ("parent",))
# Announce-wire weight: serialized msgpack bytes this daemon exchanged
# with the scheduler over announce streams. The packed-report encoding
# exists to shrink ``sent``.
ANNOUNCE_BYTES = metrics.counter(
    "peer_announce_bytes_total",
    "Serialized announce-stream traffic with the scheduler, by direction "
    "(sent = reports/registers, recv = schedule pushes and answers)",
    ("direction",))

MAX_RESCHEDULES = 8


def piece_report(rec, parent_id: str) -> dict:
    """A landed piece as ``piece_finished`` / ``pieces_finished`` carry it to
    the scheduler; ``parent_id`` "" for a piece this peer produced itself."""
    return {
        "piece_num": rec.num,
        "range_start": rec.offset,
        "range_size": rec.size,
        "digest": rec.digest,
        "download_cost_ms": rec.cost_ms,
        "dst_peer_id": parent_id,
    }


class PeerTaskConductor:
    def __init__(
        self,
        *,
        task_id: str,
        peer_id: str,
        url: str,
        store: LocalTaskStore,
        scheduler_client,
        piece_manager: PieceManager,
        host_info: dict,
        meta: dict | None = None,
        is_seed: bool = False,
        piece_parallelism: int = 4,
        limiter: Limiter | None = None,
        on_piece=None,
        disable_back_source: bool = False,
        local_range_source=None,
        quarantine=None,
        flight=None,
        wfq=None,
        report_batch: int = 32,
    ):
        self.task_id = task_id
        self.peer_id = peer_id
        self.url = url
        self.store = store
        self.scheduler_client = scheduler_client
        self.piece_manager = piece_manager
        self.host_info = host_info
        self.meta = meta or {}
        self.is_seed = is_seed
        self.piece_parallelism = piece_parallelism
        self.limiter = limiter or Limiter()
        self.on_piece = on_piece
        self.disable_back_source = disable_back_source
        # async (store, on_piece) -> bool: fill a ranged store from a
        # LOCAL covering parent task instead of origin (task_manager
        # import_range_from_local_parent) — the warm-seed path for
        # scheduler-triggered ranged seeds.
        self.local_range_source = local_range_source
        # Ranged task (task id encodes the range): the content of THIS task
        # is the slice, and a back-source demotion must fetch exactly it —
        # dropping the range here once fetched (and emitted) the whole
        # object for a 1 MiB request. Derived from the ONE range
        # representation (meta["range"], also what registers with the
        # scheduler) so no caller can desynchronize the two.
        range_header = self.meta.get("range", "")
        self.content_range = (Range.parse_http(range_header)
                              if range_header else None)

        # Daemon-wide bad-parent quarantine (pkg/quarantine), shared across
        # conductors via the task manager; None = no quarantine filter.
        self.quarantine = quarantine
        # Flight recorder: this task's bounded event ring (pkg/flight) —
        # every choke point below stamps it so /debug/flight can autopsy
        # the download after the fact. Injectable so embedded multi-daemon
        # tests can keep per-daemon recorders (and per-daemon wall
        # offsets); defaults to the process-wide recorder.
        self.flight = flight if flight is not None \
            else flightlib.for_task(task_id)
        # Announce-path clock samples ([t0, t1, sched_echo] on this
        # host's anchored wall clock): each register/reconnect answer
        # that carries the scheduler's ``sched_wall`` yields one; they
        # ship inside the terminal flight digest so the scheduler's pod
        # lens can align this host's timeline. Bounded.
        self._clock_samples: list = []
        self.dispatcher = PieceDispatcher(quarantine=quarantine,
                                          flight=self.flight)
        self.downloader = PieceDownloader()
        # Tenant QoS plane (dragonfly2_tpu/qos): the daemon-wide WFQ
        # dispatch gate shared across conductors (None = ungated), plus
        # this task's attribution identity. The normalized tenant rides
        # every upstream piece request as a query param so the serving
        # peer can account and rate-split per tenant.
        self.wfq = wfq
        self.tenant = qoslib.normalize_tenant(self.meta.get("tenant"))
        self._qos_priority = int(self.meta.get("priority", 3) or 3)
        self.synchronizer: PieceTaskSynchronizer | None = None
        # Striped slice broadcast: this host's ICI domain, and the bytes
        # pulled per parent locality (intra = same slice / ICI, cross =
        # DCN, unlabeled = no coordinates on one end). The task manager
        # snapshots locality_bytes for benches/tests.
        self.own_slice = (host_info or {}).get("tpu_slice", "") or ""
        self.locality_bytes = {"intra": 0, "cross": 0, "unlabeled": 0}
        # Bytes by where they came from, and the parents that served any:
        # stamped once, as ``task_sources``, when the conductor ends.
        self.source_bytes = {"seed": 0, "peer": 0, "origin": 0}
        self._parents_used: set[str] = set()
        self._sources_stamped = False
        self._stream = None
        self._reschedules = 0
        self._from_p2p = False
        self._report_lock = asyncio.Lock()
        self._resched_lock = asyncio.Lock()
        self._sched_update = asyncio.Event()   # receiver loop applied a push
        self._need_back_source = False
        # Piece-finished reports coalesce into pieces_finished batches: the
        # first report flushes immediately (the scheduler's "peer became a
        # usable parent" wakeup must not lag), subsequent ones within the
        # flush window ride one message. Peer-to-peer piece DISCOVERY does
        # not ride these reports at all (the synchronizer syncs piece maps
        # parent-direct), so batching costs scheduling metadata freshness
        # only, bounded by the window. The cap is adaptive by
        # construction: idle traffic flushes singles (wait <= 0 on the
        # first report), backlog grows batches toward ``report_batch``
        # (DaemonConfig download.report_batch) and a recovery re-report
        # drains in report_batch-sized messages instead of one giant one.
        self.report_batch = max(1, int(report_batch))
        self._pending_reports: deque = deque()
        self._flush_task: asyncio.Task | None = None
        self._last_flush = 0.0
        # Wire capability learned from stamped scheduler answers: packed
        # piece-report batches + resume bitmaps (proto/reportcodec).
        # Refreshed on every register/reconnect answer so failover to an
        # older scheduler downgrades the encoding.
        self._packed_ok = False
        # Mid-download announce-stream recovery state: the register body
        # (saved for re-registration), the serialized-reconnect lock, and
        # the terminal flag that stops recovery racing teardown.
        self._open_body: dict | None = None
        self._announce_lock = asyncio.Lock()
        self._announce_done = False
        # The whole-content digest a parent's done carried, for a task
        # that was told to take it from there (``meta["digest_from_parent"]``).
        self.content_digest = ""
        self._stream_reconnects = 0
        # Ring-rebuild re-homing: set when dynconfig moved this task's
        # ownership to a different live member — the next successful
        # reconnect books as result="rehomed" instead of "ok".
        self._rehome_pending = False

    # ------------------------------------------------------------------ #

    async def run(self) -> None:
        """Complete the task into self.store, or raise DfError."""
        open_body = {
            "host": self.host_info,
            "peer_id": self.peer_id,
            "task_id": self.task_id,
            "url": self.url,
            "tag": self.meta.get("tag", ""),
            "application": self.meta.get("application", ""),
            "digest": self.meta.get("digest", ""),
            "filters": self.meta.get("filters") or [],
            "header": self.meta.get("header") or {},
            "priority": self.meta.get("priority", 3),
            "tenant": self.meta.get("tenant", ""),
            "range": self.meta.get("range", ""),
            "is_seed": self.is_seed,
            "disable_back_source": self.disable_back_source,
            "pod_broadcast": bool(self.meta.get("pod_broadcast")),
        }
        self._open_body = open_body
        # Registration phase: any transport failure BEFORE a scheduler
        # answer arrives (connect refused, connect-then-drop, silence)
        # demotes to back-to-source instead of failing the task (reference
        # behavior — the piece store still gets populated for reuse/PEX,
        # and clients without source-fallback permission still succeed).
        # A scheduler-SENT rejection (schedule_failed) stays fatal via the
        # dispatch below.
        # Ring-rebuild observation (dynconfig scheduler-set changes):
        # when ownership moves to a different LIVE member, drain and
        # re-home instead of riding the stale shard until it dies.
        watch = getattr(self.scheduler_client, "watch_ring", None)
        if watch is not None:
            watch(self.task_id, self._on_ring_change)
        msg = None
        register_error = "scheduler closed stream at register"
        self.flight.record(flightlib.EV_REGISTER)
        t0_clock = self.flight.wall_now()
        try:
            self._stream = await self.scheduler_client.open_announce_stream(
                open_body)
            reg: dict = {"type": "register"}
            if self.store.metadata.pieces:
                # Daemon restart with a partial store (or a re-run over
                # persisted pieces): the scheduler rebuilds our state
                # instead of treating us as fresh.
                reg["resume"] = self._resume_state()
            await self._stream.send(reg)
            msg = await self._stream.recv(timeout=60.0)
            self._note_clock_sample(t0_clock, msg)
        except DfError as e:
            if self.disable_back_source:
                await self._teardown()
                raise
            register_error = str(e)
        if msg is None:
            self.flight.record(flightlib.EV_SCHEDULED, -1, 0.0, "unavailable")
            if not self.disable_back_source:
                log.warning("scheduler unavailable at register; "
                            "degrading to back-to-source",
                            task=self.task_id[:16], error=register_error)
            if self.disable_back_source:
                await self._teardown()
                raise DfError(Code.SchedError,
                              "scheduler unavailable at register")
            try:
                await self._back_source()
            finally:
                await self._teardown()
            return
        self.flight.record(flightlib.EV_SCHEDULED, -1, 0.0,
                           str(msg.get("type", "")))
        try:
            await self._dispatch_schedule(msg)
        except BaseException:
            await self._safe_send({"type": "download_failed"})
            raise
        finally:
            await self._teardown()

    async def _dispatch_schedule(self, msg: dict) -> None:
        """Dispatch the scheduler's answer to a register/reschedule."""
        kind = msg.get("type")
        if kind == "empty_task":
            await self._finish_empty()
        elif kind == "tiny_task":
            await self._finish_tiny(msg)
        elif kind == "small_task":
            await self._finish_small(msg)
        elif kind == "need_back_source":
            await self._back_source()
        elif kind == "normal_task":
            await self._pull_pieces_p2p(msg)
        elif kind == "schedule_failed":
            raise DfError(Code.SchedError, msg.get("reason", "schedule failed"))
        else:
            raise DfError(Code.SchedError, f"unexpected scheduler response {kind}")

    @property
    def from_p2p(self) -> bool:
        return self._from_p2p

    # -- empty (reference storeEmptyPeerTask :595) -------------------------

    async def _finish_empty(self) -> None:
        self.store.update_task(content_length=0, total_piece_count=0, piece_size=1)
        await self._safe_send({"type": "download_finished", "content_length": 0})

    # -- tiny: content inlined by the scheduler (ref storeTinyPeerTask :569)

    async def _finish_tiny(self, msg: dict) -> None:
        content = bytes(msg.get("content") or b"")
        self._from_p2p = True
        self.store.update_task(content_length=len(content),
                               piece_size=max(len(content), 1),
                               total_piece_count=1)
        if 0 not in self.store.metadata.pieces:
            self.store.write_piece(0, content)
        await self._safe_send({"type": "download_finished",
                               "content_length": len(content),
                               "piece_size": max(len(content), 1),
                               "total_piece_count": 1})

    # -- small: one direct parent + piece 0 (ref pullSinglePiece :904) -----

    async def _finish_small(self, msg: dict) -> None:
        task_wire = msg.get("task") or {}
        parent = msg.get("parent") or {}
        piece = PieceInfo.from_wire(msg.get("piece") or {})
        host = parent.get("host") or {}
        self._apply_task_meta(task_wire)
        try:
            if piece.piece_num not in self.store.metadata.pieces:
                chunks, size, cost_ms, received_digest = \
                    await self.downloader.download_piece(
                        host.get("ip", ""), host.get("upload_port", 0),
                        self.task_id, piece.piece_num,
                        src_peer_id=parent.get("id", ""),
                        expected_size=piece.range_size,
                        expected_digest=piece.digest)
                await self.limiter.wait(size)
                rec = self.store.write_piece_chunks(
                    piece.piece_num, chunks, received_digest,
                    expected_digest=piece.digest, cost_ms=cost_ms)
                self.flight.record(flightlib.EV_LANDED, piece.piece_num,
                                   float(cost_ms))
                self._note_source_bytes(bool(host.get("type", 0)),
                                        parent.get("id", ""), size)
                await self._report_piece(rec, parent_id=parent.get("id", ""))
                if self.on_piece is not None:
                    await self.on_piece(self.store, rec)
            self._from_p2p = True
            await self._safe_send({
                "type": "download_finished",
                "content_length": self.store.metadata.content_length,
                "piece_size": self.store.metadata.piece_size,
                "total_piece_count": self.store.metadata.total_piece_count,
            })
        except DfError as e:
            # The handed-out parent was bad: ask for a reschedule and run
            # whatever the scheduler answers (normal/back-source path).
            log.warning("small-task direct pull failed, rescheduling",
                        task=self.task_id[:16], error=str(e))
            await self._safe_send({"type": "reschedule",
                                   "blocklist": [parent.get("id", "")]})
            nxt = await self._stream.recv(timeout=60.0)
            if nxt is None:
                raise DfError(Code.SchedError,
                              "scheduler closed stream after small-task retry")
            if nxt.get("type") == "small_task":
                # Don't ping-pong between bad small parents forever.
                raise DfError(Code.ClientPieceDownloadFail,
                              "small-task retry returned another direct parent")
            await self._dispatch_schedule(nxt)

    # -- back-to-source (reference backSource :503) ------------------------

    async def _back_source(self) -> None:
        self.flight.record(flightlib.EV_BACK_SOURCE)
        # Announce-only fast path: content already complete locally (seed
        # re-announce after a scheduler restart) — report pieces, no origin.
        if self.store.metadata.done and self.store.is_complete():
            m = self.store.metadata
            await self._safe_send({
                "type": "download_started",
                "content_length": m.content_length,
                "piece_size": m.piece_size,
                "total_piece_count": m.total_piece_count,
            })
            for rec in self.store.get_pieces():
                await self._report_piece(rec, parent_id="")
            await self._safe_send({
                "type": "download_finished",
                "content_length": m.content_length,
                "piece_size": m.piece_size,
                "total_piece_count": m.total_piece_count,
            })
            return

        started_sent = False

        async def on_piece(store: LocalTaskStore, rec) -> None:
            nonlocal started_sent
            if not started_sent and store.metadata.piece_size > 0:
                started_sent = True
                await self._safe_send({
                    "type": "download_started",
                    "content_length": store.metadata.content_length,
                    "piece_size": store.metadata.piece_size,
                    "total_piece_count": store.metadata.total_piece_count,
                })
            await self._report_piece(rec, parent_id="")
            if self.on_piece is not None:
                await self.on_piece(store, rec)

        async def on_source_piece(store: LocalTaskStore, rec) -> None:
            self.source_bytes["origin"] += rec.size
            await on_piece(store, rec)

        # A ranged slice a LOCAL parent store covers imports warm — the
        # scheduler-triggered ranged seed on a preheated host never
        # re-touches origin. This is not a back-source: it runs BEFORE
        # the disable gate (origin stays off the table) and is neither
        # counted nor logged as one.
        imported = (self.content_range is not None
                    and self.local_range_source is not None
                    and await self.local_range_source(self.store, on_piece))
        if not imported:
            if self.disable_back_source:
                # dfget --disable-back-source / dfcache export: origin is
                # off the table, fail instead (reference
                # peertask_conductor needBackSource vs disableBackSource).
                raise DfError(Code.ClientBackSourceError,
                              "scheduler demanded back-to-source but it "
                              "is disabled")
            BACK_SOURCE_COUNT.inc()
            log.info("back-to-source", task=self.task_id[:16],
                     seed=self.is_seed)
            if LocalTaskStore.completion_digest_applies(
                    self.meta.get("digest", ""),
                    self.content_range is not None):
                # Self-computed pieces are never certifiable: the
                # completion re-hash is certain; overlap it with the
                # transfer.
                self.store.start_prefix_hasher(self.meta.get("digest", ""))
            await self.piece_manager.download_source(
                self.store, self.url, self.meta.get("header") or {},
                content_range=self.content_range,
                on_piece=on_source_piece, limiter=self.limiter,
            )
        await self._safe_send({
            "type": "download_finished",
            "content_length": self.store.metadata.content_length,
            "piece_size": self.store.metadata.piece_size,
            "total_piece_count": self.store.metadata.total_piece_count,
        })

    # -- P2P pull (reference pullPiecesWithP2P :552) -----------------------

    async def _pull_pieces_p2p(self, schedule_msg: dict) -> None:
        self._from_p2p = True
        self._apply_task_meta(schedule_msg.get("task") or {})
        # Dead parents need no extra hook here: the synchronizer's
        # drop_parent marks them blocked, and the next starvation pass
        # sends them in the reschedule blocklist (ref reportInvalidPeer).
        self.synchronizer = PieceTaskSynchronizer(
            self.task_id, self.peer_id, self.dispatcher,
            own_slice=self.own_slice)
        self.synchronizer.sync_parents(schedule_msg.get("parents") or [])
        self._apply_stripe(schedule_msg.get("stripe"))
        # Where the digest to hold the content against comes with a parent's
        # done: hash behind the pieces from the first one on, so that only
        # the tail is left to hash when it comes ("" starts nothing).
        self.store.start_prefix_hasher(self._digest_from_parent())
        # Resume support: pieces already on disk need no re-download.
        self.dispatcher.mark_known_downloaded(self.store.metadata.pieces.keys())

        receiver = asyncio.ensure_future(self._receive_scheduler_loop())
        workers = [asyncio.ensure_future(self._piece_worker(i))
                   for i in range(self.piece_parallelism)]
        try:
            try:
                await asyncio.gather(*workers)
            except BaseException:
                # First failure cancels siblings so they can't race teardown.
                for w in workers:
                    w.cancel()
                await asyncio.gather(*workers, return_exceptions=True)
                raise
            if self._need_back_source and not self._complete():
                # Scheduler demoted us mid-flight: finish the remainder from
                # origin (pieces already on disk are skipped).
                await self._back_source()
                return
            if not self._complete():
                raise DfError(Code.ClientPieceDownloadFail,
                              f"p2p download stalled at "
                              f"{self.dispatcher.downloaded_count()} pieces")
            # A completed parent's digest map can certify the
            # completion-time re-hash skip (the store compares what each
            # piece was verified against to the map). Every done parent's
            # map is tried, and when none verifies yet the bounded wait
            # keeps running — a corrupt early finisher can't mask an
            # honest parent whose done is still in flight.
            await self._await_certification()
            await self._await_parent_digest()
            await self._safe_send({
                "type": "download_finished",
                "content_length": self.store.metadata.content_length,
                "piece_size": self.store.metadata.piece_size,
                "total_piece_count": self.store.metadata.total_piece_count,
            })
        finally:
            receiver.cancel()

    async def _await_certification(self) -> bool:
        """Cold-race closer: in a fan-out the children's last pieces land
        moments before the seed's own completion gate (the seed validates
        the whole-content digest BEFORE its sync streams say done), so
        each child would pay a redundant whole-content re-hash that the
        warm path skips. Waiting — bounded near the break-even point —
        turns N children × O(content) hashing into the seed's one
        validation. No provenance change: this only gives the parent's
        done a chance to arrive on the already-open sync stream;
        store.apply_certification (the single scan-and-install point)
        decides whether the skip engages, so only a map that actually
        certifies ends the wait — a corrupt parent's done must not eat
        the budget an honest parent's in-flight done could still use.
        Returns True when a verifying map was installed."""
        if not LocalTaskStore.completion_digest_applies(
                self.meta.get("digest", ""), self.content_range is not None):
            return False  # no completion re-hash would run: nothing to save
        t0 = time.perf_counter()
        how, tried = await self._wait_certified()
        waited = time.perf_counter() - t0
        # One span for the whole stay, whichever way it ended: between the
        # last piece and task_done nothing else of a cold pull is stamped.
        self.flight.record(flightlib.EV_CERT_WAIT, tried, waited * 1000.0,
                           how)
        CERT_WAIT_SECONDS.labels(how).inc(waited)
        return how == "certified"

    async def _wait_certified(self) -> "tuple[str, int]":
        """The wait itself: how it ended (``certified``, ``timeout``,
        ``no_certifier``: no parent left whose done could still come,
        ``unverifiable``: a piece landed without a verified-against digest)
        and how many digest maps were tried on the way."""
        content = self.store.metadata.content_length
        if content <= 0:
            return "no_certifier", 0
        if not self.store.pieces_verified_against_digests():
            # Some piece landed without a verified-against digest: no
            # certified map can ever engage the skip — waiting is futile.
            return "unverifiable", 0
        loop = asyncio.get_running_loop()
        deadline = loop.time() + self._cert_wait_bound(content)
        disp = self.dispatcher
        how, tried = "no_certifier", 0
        while disp.pending_certifiers():
            remaining = deadline - loop.time()
            if remaining <= 0:
                how = "timeout"
                break  # deadline-edge done still gets the final attempt
            disp.certified_event.clear()
            maps = disp.certified_digest_maps()
            tried += len(maps)
            if self.store.apply_certification(maps):
                return "certified", tried
            try:
                await asyncio.wait_for(disp.certified_event.wait(), remaining)
            except asyncio.TimeoutError:
                how = "timeout"
                break
        maps = disp.certified_digest_maps()
        if self.store.apply_certification(maps):
            how = "certified"
        return how, tried + len(maps)

    def _digest_from_parent(self) -> str:
        """The algorithm of the whole-content digest this task takes from a
        parent's done, or "" (it was given the digest, or has none)."""
        return ("" if self.meta.get("digest")
                else self.meta.get("digest_from_parent", ""))

    async def _await_parent_digest(self) -> None:
        """A replica that was started before the content's digest existed:
        every piece has landed, each held against its parent's piece digest;
        the value to hold this store's own hash against rides the done of a
        parent that hashed what it produced (``content_digest``). Waits for
        it while a parent's done can still come; none means the task fails,
        since it is marked done only behind that comparison
        (``TaskManager._finalize_content_digest``)."""
        algorithm = self._digest_from_parent()
        if not algorithm:
            return
        disp = self.dispatcher
        loop = asyncio.get_running_loop()
        t0 = loop.time()
        deadline = t0 + max(60.0, self.store.metadata.content_length
                            / (50 << 20))
        while True:
            disp.certified_event.clear()
            self.content_digest = next(
                (d for pid, d in disp.content_digests.items()
                 if pid in disp.done_parents
                 and d.startswith(algorithm + ":")), "")
            if self.content_digest or not disp.pending_certifiers():
                break
            try:
                await asyncio.wait_for(disp.certified_event.wait(),
                                       deadline - loop.time())
            except asyncio.TimeoutError:
                break
        self.flight.record(flightlib.EV_CERT_WAIT, len(disp.done_parents),
                           (loop.time() - t0) * 1000.0,
                           "parent_digest" if self.content_digest
                           else "no_parent_digest")
        if not self.content_digest:
            raise DfError(Code.ClientPieceDownloadFail,
                          f"no parent's done carried the content's "
                          f"{algorithm} digest")

    @staticmethod
    def _cert_wait_bound(content_length: int) -> float:
        """Wait budget: 50 ms done-propagation epsilon + 2× the ~1 GBps
        solo hash estimate. The 2× is deliberate: the alternative to
        waiting is N children hashing CONCURRENTLY on shared cores (each
        paying ~N× the solo cost), while the wait is idle CPU that lets
        the one certifier finish sooner — so the worst case (no done ever
        arrives) loses ~the hash cost, and the common case saves all N."""
        return min(3.0, 0.05 + 2 * content_length / 1.0e9)

    def _note_clock_sample(self, t0: float, msg: "dict | None") -> None:
        """Round-trip clock sample from a register/reconnect answer that
        carried the scheduler's ``sched_wall`` echo: t0/t1 on this host's
        anchored wall clock bracket the exchange, so the NTP midpoint
        error is bounded by (t1-t0)/2 no matter how asymmetric the two
        legs were. Ships inside the terminal flight digest."""
        if not msg:
            return
        self._note_recv(msg)
        # Capability negotiation rides the same stamped answers: refresh
        # on EVERY register/reconnect answer (not just the first) so a
        # failover to an older scheduler drops back to the dict wire.
        self._packed_ok = bool(msg.get("packed_reports"))
        echo = msg.get("sched_wall")
        if not isinstance(echo, (int, float)) or echo <= 0:
            return
        self._clock_samples.append(
            (t0, self.flight.wall_now(), float(echo)))
        del self._clock_samples[:-4]

    def _apply_stripe(self, stripe: dict | None) -> None:
        """Enter/reshuffle/exit stripe mode from a scheduler handout. The
        plan's mates ride a dedicated field (not the parent DAG — mutual
        intra-slice serving would be a DAG cycle): sync them like parents,
        marked same_slice, so non-stripe pieces fill intra-slice while the
        conductor DCN-fetches only its own stripe."""
        if stripe and int(stripe.get("slice_size", 0)) >= 2:
            self.flight.record(flightlib.EV_STRIPE, -1,
                               float(stripe["slice_size"]), "applied")
            self.dispatcher.set_stripe(int(stripe["slice_size"]),
                                       int(stripe.get("slice_rank", -1)))
            mates = stripe.get("mates") or []
            if mates and self.synchronizer is not None:
                self.synchronizer.sync_parents(mates)
            log.info("stripe plan applied", task=self.task_id[:16],
                     slice_size=stripe["slice_size"],
                     slice_rank=stripe.get("slice_rank"), mates=len(mates))
        else:
            if self.dispatcher.stripe is not None:
                self.flight.record(flightlib.EV_STRIPE, -1, 0.0, "cleared")
            self.dispatcher.clear_stripe()

    def _note_piece_failure(self, parent, err: DfError) -> str:
        """Typed failure accounting: classify the error, feed the
        daemon-wide quarantine, emit the reason-coded metric. Returns the
        reason string for the scheduler report."""
        reason = failure_reason(err)
        PIECE_FAIL_REASON.labels(reason).inc()
        if self.quarantine is not None:
            if self.quarantine.penalize(parent_key(parent), reason):
                PARENT_QUARANTINE_COUNT.labels(reason).inc()
                self.flight.record(
                    flightlib.EV_QUARANTINE, -1, 0.0,
                    f"{parent_key(parent)}|{reason}")
                log.warning("parent quarantined",
                            parent=parent.peer_id[:24],
                            endpoint=parent_key(parent), reason=reason,
                            task=self.task_id[:16])
                self.dispatcher._wakeup.set()
        return reason

    def _parent_locality(self, parent) -> str:
        if not self.own_slice or not parent.tpu_slice:
            return "unlabeled"
        if parent.same_slice or parent.tpu_slice == self.own_slice:
            return "intra"
        return "cross"

    def _note_piece_bytes(self, parent, size: int) -> None:
        if size <= 0:
            return
        key = self._parent_locality(parent)
        self.locality_bytes[key] += size
        PIECE_BYTES.labels(key).inc(size)
        self._note_source_bytes(parent.is_seed, parent.peer_id, size)

    def _note_source_bytes(self, from_seed: bool, parent_id: str,
                           size: int) -> None:
        kind = "seed" if from_seed else "peer"
        self.source_bytes[kind] += size
        self._parents_used.add(parent_id)
        PIECE_BYTES_BY_PARENT.labels(kind).inc(size)

    def _stamp_sources(self) -> None:
        if self._sources_stamped:
            return
        self._sources_stamped = True
        b = self.source_bytes
        self.flight.record(
            flightlib.EV_TASK_SOURCES, len(self._parents_used),
            float(b["peer"]),
            f"seed={b['seed']} peer={b['peer']} origin={b['origin']}")

    def _apply_task_meta(self, task_wire: dict) -> None:
        cl = task_wire.get("content_length", -1)
        ps = task_wire.get("piece_size", 0)
        tp = task_wire.get("total_piece_count", -1)
        if cl >= 0 and ps > 0 and tp < 0:
            tp = compute_piece_count(cl, ps)
        self.store.update_task(content_length=cl if cl >= 0 else None,
                               piece_size=ps if ps > 0 else None,
                               total_piece_count=tp if tp >= 0 else None)
        self.dispatcher.content_length = self.store.metadata.content_length
        self.dispatcher.piece_size = self.store.metadata.piece_size
        if self.store.metadata.total_piece_count >= 0:
            self.dispatcher.total_piece_count = self.store.metadata.total_piece_count

    def _complete(self) -> bool:
        m = self.store.metadata
        if m.total_piece_count < 0 and self.dispatcher.total_piece_count >= 0:
            self.store.update_task(
                total_piece_count=self.dispatcher.total_piece_count,
                content_length=self.dispatcher.content_length
                if self.dispatcher.content_length >= 0 else None,
                piece_size=self.dispatcher.piece_size
                if self.dispatcher.piece_size > 0 else None,
            )
        return m.total_piece_count >= 0 and self.store.is_complete()

    async def _receive_scheduler_loop(self) -> None:
        """The ONLY reader of the scheduler stream after registration:
        applies pushed parent sets / back-source demotions and signals
        waiters (reference receivePeerPacket :673). A stream death
        MID-DOWNLOAD (scheduler crash/restart, net partition) is not
        terminal: the piece workers keep pulling from their live parents
        while this loop reconnects with ring failover, re-registers
        preserving completed pieces, and flushes the buffered reports —
        only an exhausted reconnect budget demotes to back-to-source."""
        try:
            while True:
                try:
                    msg = await self._stream.recv()
                except DfError:
                    msg = None   # stream lost: same recovery as a close
                if msg is None:
                    if self._announce_done or self._complete():
                        return
                    if await self._recover_announce_stream():
                        continue
                    self._degrade_after_scheduler_loss()
                    return
                self._note_recv(msg)
                kind = msg.get("type")
                self.flight.record(flightlib.EV_SCHED_PUSH, -1, 0.0,
                                   str(kind))
                if kind == "normal_task":
                    self._apply_task_meta(msg.get("task") or {})
                    if self.synchronizer is not None:
                        self.synchronizer.sync_parents(msg.get("parents") or [])
                    self._apply_stripe(msg.get("stripe"))
                    self._sched_update.set()
                elif kind in ("need_back_source", "schedule_failed"):
                    if kind == "need_back_source":
                        self._need_back_source = True
                    # drop_parent (not a bare blocked=True) so both waiter
                    # classes wake: dispatcher.get() AND a completion-time
                    # _await_certification that can now never be certified.
                    for pid in list(self.dispatcher.parents):
                        self.dispatcher.drop_parent(pid)
                    self._sched_update.set()
        except asyncio.CancelledError:
            pass

    # Announce-stream recovery budget: attempts per disruption. With the
    # ANNOUNCE backoff policy the whole budget spans a few seconds — long
    # enough for a scheduler restart, short enough that origin fallback
    # still beats a wedged transfer. MAX_STREAM_RECONNECTS caps the
    # task-lifetime total: a perpetually flapping scheduler must
    # eventually push the task to the degradation path, not hold the
    # receiver in a reconnect loop forever.
    RECONNECT_BUDGET = 4
    MAX_STREAM_RECONNECTS = 8

    def _resume_state(self) -> dict:
        """This task's full local state for a (re-)register: landed piece
        bitset, task geometry, the verified content digest once the store
        completed (mid-flight the per-piece digests ride the idempotent
        re-report instead), stripe membership and the pod-broadcast flag.
        A failover ring member — or a restarted scheduler — rebuilds its
        Task/Peer FSMs from this instead of treating us as fresh."""
        m = self.store.metadata
        nums = sorted(m.pieces.keys())
        resume: dict = {
            "piece_nums": nums,
            "content_length": m.content_length,
            "piece_size": m.piece_size,
            "total_piece_count": m.total_piece_count,
            "prefix_digest": m.digest or "",
            "pod_broadcast": bool(self.meta.get("pod_broadcast")),
        }
        if self._packed_ok and len(nums) >= 16:
            # Negotiated bitmap form: a restart storm re-registers with
            # one bit per piece instead of a msgpack int list. Density
            # gate keeps pathologically sparse sets on the list form.
            bitmap = reportcodec.nums_to_bitmap(nums)
            if len(bitmap) <= 2 * len(nums):
                resume["piece_bitmap"] = bitmap
                resume["piece_nums"] = []
        stripe = self.dispatcher.stripe
        if stripe is not None:
            resume["stripe"] = {"slice_size": stripe[0],
                                "slice_rank": stripe[1]}
        return resume

    def _on_ring_change(self, new_owner: str) -> None:
        """SchedulerClient ring-rebuild callback: this task's ownership
        moved to a different live member (the old one may be perfectly
        healthy — just no longer owning). Drain gracefully and re-home:
        flush buffered reports to the old member, close the stream, and
        let the receiver loop's recovery path reconnect — the ring now
        resolves to the new owner, and the re-register carries resume
        state so the new member adopts the task mid-flight."""
        if self._announce_done:
            return
        self._rehome_pending = True
        log.info("task ownership moved; re-homing announce stream",
                 task=self.task_id[:16], new_owner=new_owner)
        asyncio.ensure_future(self._rehome())

    async def _rehome(self) -> None:
        try:
            await self._flush_reports()
        except Exception:
            pass  # stream already dying: recovery re-reports anyway
        stream = self._stream
        if stream is not None and not stream.closed:
            await stream.close()
        # The receiver loop's recv now returns None → recovery reconnects
        # on the rebuilt ring (and books result="rehomed").

    def _degrade_after_scheduler_loss(self) -> None:
        """Reconnect budget exhausted: the schedulerless endgame. With
        origin allowed the workers hand the remainder to back-to-source
        (pieces on disk are kept); without it they ride out their current
        parents and fail via the starvation path if those run dry."""
        log.warning("announce stream unrecoverable; degrading",
                    task=self.task_id[:16],
                    back_source=not self.disable_back_source)
        if not self.disable_back_source:
            self._need_back_source = True
            for pid in list(self.dispatcher.parents):
                self.dispatcher.drop_parent(pid)
        self._sched_update.set()

    async def _recover_announce_stream(self) -> bool:
        """Reopen the announce stream (ring failover lives in
        scheduler_client), re-register, re-report completed pieces, flush
        buffered piece reports. Returns False when the budget is spent or
        the scheduler authoritatively rejected us."""
        async with self._announce_lock:
            if self._announce_done:
                return False
            if self._stream is not None and not self._stream.closed:
                return True   # a racing caller already recovered it
            if self._stream_reconnects >= self.MAX_STREAM_RECONNECTS:
                ANNOUNCE_RECONNECT_COUNT.labels("exhausted").inc()
                return False
            policy = retrylib.ANNOUNCE
            for attempt in range(self.RECONNECT_BUDGET):
                await asyncio.sleep(policy.delay(attempt))
                if self._announce_done:
                    return False
                try:
                    t0_clock = self.flight.wall_now()
                    stream = await self.scheduler_client.open_announce_stream(
                        self._open_body)
                    # Re-register with FULL resume state: a failover ring
                    # member (or restarted scheduler) rebuilds Task/Peer
                    # FSMs from it instead of demoting us to origin.
                    await stream.send({"type": "register",
                                       "resume": self._resume_state()})
                    msg = await stream.recv(timeout=30.0)
                    self._note_clock_sample(t0_clock, msg)
                except DfError as e:
                    ANNOUNCE_RECONNECT_COUNT.labels("retry").inc()
                    self.flight.record(flightlib.EV_RECONNECT, -1, 0.0,
                                       "retry")
                    log.warning("announce reconnect failed",
                                task=self.task_id[:16], attempt=attempt,
                                error=str(e))
                    continue
                if msg is None:
                    ANNOUNCE_RECONNECT_COUNT.labels("retry").inc()
                    continue
                old, self._stream = self._stream, stream
                if old is not None:
                    await old.close()
                self._stream_reconnects += 1
                kind = msg.get("type")
                if kind == "normal_task":
                    self._apply_task_meta(msg.get("task") or {})
                    if self.synchronizer is not None:
                        self.synchronizer.sync_parents(
                            msg.get("parents") or [])
                    self._apply_stripe(msg.get("stripe"))
                elif kind == "need_back_source":
                    self._need_back_source = True
                    for pid in list(self.dispatcher.parents):
                        self.dispatcher.drop_parent(pid)
                elif kind == "schedule_failed":
                    # An ANSWER, not an outage: the scheduler's verdict
                    # stands; fall through to degradation.
                    ANNOUNCE_RECONNECT_COUNT.labels("rejected").inc()
                    self._sched_update.set()
                    return False
                self._sched_update.set()
                # Re-register preserving completed pieces: a restarted
                # scheduler (or a failover ring member) has no idea what
                # this peer already holds — report every landed piece so
                # it becomes a usable parent again immediately. The
                # scheduler applies reports idempotently, so overlap with
                # still-buffered reports is harmless.
                for rec in self.store.get_pieces():
                    self._pending_reports.append({
                        "piece_num": rec.num,
                        "range_start": rec.offset,
                        "range_size": rec.size,
                        "digest": rec.digest,
                        "download_cost_ms": rec.cost_ms,
                        "dst_peer_id": "",
                    })
                await self._flush_reports()
                outcome = "rehomed" if self._rehome_pending else "ok"
                self._rehome_pending = False
                ANNOUNCE_RECONNECT_COUNT.labels(outcome).inc()
                self.flight.record(flightlib.EV_RECONNECT, -1, 0.0, outcome)
                log.info("announce stream recovered",
                         task=self.task_id[:16], attempt=attempt,
                         result=outcome,
                         reconnects=self._stream_reconnects)
                return True
            ANNOUNCE_RECONNECT_COUNT.labels("exhausted").inc()
            self.flight.record(flightlib.EV_RECONNECT, -1, 0.0, "exhausted")
            return False

    # Coalescing bound: one ranged GET covers up to this many contiguous
    # pieces (32 MiB at the default 4 MiB piece size). Availability gates
    # real run lengths — a warming parent advertises pieces incrementally,
    # so cold-chain runs stay short while warm pulls ride full spans.
    # Env-overridable for A/B measurement on noisy shared hosts.
    SPAN_MAX_PIECES = int(os.environ.get("DF_SPAN_MAX_PIECES", "8"))

    async def _piece_worker(self, index: int) -> None:
        """Hot loop (reference downloadPieceWorker :1043)."""
        while True:
            if self._complete() or self._need_back_source:
                return
            assignment = await self.dispatcher.get(timeout=10.0)
            if assignment is None:
                if self._complete() or self._need_back_source:
                    return
                if not await self._handle_starvation():
                    return
                continue
            run = self.dispatcher.extend_run(assignment, self.SPAN_MAX_PIECES)
            if self.wfq is None:
                await self._dispatch_assignment(assignment, run)
                continue
            # QoS gate: the assignment (a per-task reservation) is held
            # while this worker waits its DWRR turn, so cross-task piece
            # ISSUE order follows class weights while per-task dispatcher
            # state stays untouched. Acquired after dispatcher.get() so a
            # parked worker never pins a slot through starvation waits.
            await self.wfq.acquire(self._qos_priority)
            try:
                await self._dispatch_assignment(assignment, run)
            finally:
                self.wfq.release()

    async def _dispatch_assignment(self, assignment: PieceAssignment,
                                   run: list[PieceAssignment]) -> None:
        if len(run) > 1 and await self._download_run(run):
            return
        for extra in run[1:]:
            # Span path ineligible: hand the reservations back and pull
            # the head piece the per-piece way.
            self.dispatcher.release_assignment(extra)
        await self._download_one(assignment)

    async def _download_run(self, run: list[PieceAssignment]) -> bool:
        """One coalesced ranged fetch; returns False when the downloader
        deemed the span ineligible (caller falls back per-piece). Piece
        results arrive through the streaming callback as each lands, so
        progress frames and broker piece discovery stay piece-granular."""
        from dragonfly2_tpu.daemon.peer.piece_downloader import is_parent_gone

        p = run[0].parent
        penalized: list = []   # error OBJECTS — an id() set would alias a
        # freed error's reused address to a fresh distinct failure

        async def on_result(a: PieceAssignment, rec, err) -> None:
            if rec is not None:
                self.dispatcher.report_success(a, rec.cost_ms)
                PIECE_DOWNLOAD_COUNT.labels("ok").inc()
                self._note_piece_bytes(p, rec.size)
                self.flight.record(flightlib.EV_LANDED, a.piece_num,
                                   float(rec.cost_ms),
                                   self._parent_locality(p))
                await self._report_piece(rec, parent_id=p.peer_id)
                if self.on_piece is not None:
                    await self.on_piece(self.store, rec)
            else:
                PIECE_DOWNLOAD_COUNT.labels("fail").inc()
                gone = is_parent_gone(err)
                # One span-level event (429, 416, dead stream) arrives as
                # the SAME error object for every affected piece: penalize
                # the parent once — per-piece penalties would double the
                # cost EWMA 8x and block a parent over a single temporary
                # throttle. Distinct errors (per-piece crc mismatches)
                # still count individually, matching the per-piece path.
                if any(e is err for e in penalized):
                    self.dispatcher.release_assignment(a)
                    reason = failure_reason(err)
                else:
                    penalized.append(err)
                    self.dispatcher.report_failure(a, parent_gone=gone)
                    reason = self._note_piece_failure(p, err)
                self.flight.record(flightlib.EV_FAILED, a.piece_num, 0.0,
                                   reason)
                await self._safe_send({
                    "type": "piece_failed",
                    "piece_num": a.piece_num,
                    "parent_id": p.peer_id,
                    "temporary": not gone,
                    "reason": reason,
                })

        return await self.downloader.download_span_to_store(
            p.ip, p.upload_port, self.task_id, run, self.store,
            src_peer_id=self.peer_id, limiter=self.limiter,
            on_result=on_result, tenant=self.tenant)

    async def _download_one(self, assignment: PieceAssignment) -> None:
        from dragonfly2_tpu.daemon.peer.piece_downloader import (
            is_parent_gone,
            pull_one_piece,
        )

        p = assignment.parent
        try:
            rec = await pull_one_piece(
                self.downloader, self.store, self.dispatcher, assignment,
                task_id=self.task_id, peer_id=self.peer_id,
                limiter=self.limiter, tenant=self.tenant)
            self.dispatcher.report_success(assignment, rec.cost_ms)
            PIECE_DOWNLOAD_COUNT.labels("ok").inc()
            self._note_piece_bytes(p, rec.size)
            self.flight.record(flightlib.EV_LANDED, assignment.piece_num,
                               float(rec.cost_ms), self._parent_locality(p))
            await self._report_piece(rec, parent_id=p.peer_id)
            if self.on_piece is not None:
                await self.on_piece(self.store, rec)
        except DfError as e:
            PIECE_DOWNLOAD_COUNT.labels("fail").inc()
            gone = is_parent_gone(e)
            self.dispatcher.report_failure(assignment, parent_gone=gone)
            reason = self._note_piece_failure(p, e)
            self.flight.record(flightlib.EV_FAILED, assignment.piece_num,
                               0.0, reason)
            await self._safe_send({
                "type": "piece_failed",
                "piece_num": assignment.piece_num,
                "parent_id": p.peer_id,
                "temporary": not gone,
                "reason": reason,
            })

    async def _handle_starvation(self) -> bool:
        """No assignable pieces: ask the scheduler for new parents. Only one
        worker at a time runs the reschedule dance; the scheduler's answer
        arrives through the receiver loop. Returns False when the worker
        should exit (back-source takeover or terminal starvation)."""
        async with self._resched_lock:
            if self._complete() or self._need_back_source:
                return False
            # Another worker may have already refreshed the parent set
            # (peek only — try_get would leak an in-flight reservation). An
            # active parent with nothing assignable does NOT count: missing
            # pieces held only by dead parents must still trigger reschedule.
            if self.dispatcher.has_assignable():
                return True
            self._reschedules += 1
            if self._reschedules > MAX_RESCHEDULES:
                raise DfError(Code.ClientScheduleTimeout,
                              f"starved after {MAX_RESCHEDULES} reschedules")
            blocklist = self.dispatcher.unusable_parent_ids()
            self._sched_update.clear()
            self.flight.record(flightlib.EV_RESCHEDULE, -1, 0.0,
                               "starvation")
            await self._safe_send({"type": "reschedule", "blocklist": blocklist,
                                   "description": "piece starvation"})
            try:
                # Longer than the scheduler's 30s seed-patience hold: a
                # reschedule during a slow seed fetch must outwait it, not
                # deterministically tie and abort.
                await asyncio.wait_for(self._sched_update.wait(), timeout=60.0)
            except asyncio.TimeoutError:
                raise DfError(Code.SchedError, "scheduler silent during reschedule")
            self.flight.record(flightlib.EV_SCHED_ANSWER)
            return not self._need_back_source

    # -- reporting ---------------------------------------------------------

    _REPORT_FLUSH_S = 0.05

    async def _report_piece(self, rec, parent_id: str) -> None:
        report = piece_report(rec, parent_id)
        # Per-phase timings ride the report so the scheduler can attribute
        # stragglers per host (flight.PodAggregator, /debug/pod/<task>).
        timings = self.flight.piece_report_timings(rec.num)
        if timings:
            report["timings"] = timings
        self._pending_reports.append(report)
        if self._flush_task is None or self._flush_task.done():
            self._flush_task = asyncio.ensure_future(self._flush_soon())

    async def _flush_soon(self) -> None:
        # Loop until drained: a report appended while _flush_reports is
        # mid-send sees this task as not-done and schedules nothing — the
        # re-check here is what keeps it from stranding past the window.
        loop = asyncio.get_running_loop()
        while True:
            wait = self._last_flush + self._REPORT_FLUSH_S - loop.time()
            if wait > 0 and len(self._pending_reports) < self.report_batch:
                # Under backlog (a full batch already waiting) skip the
                # coalescing window — it only exists to grow batches.
                await asyncio.sleep(wait)
            if not await self._flush_reports():
                # Stream down: reports stay BUFFERED (not dropped) for the
                # announce-recovery flush; spinning here would just burn
                # the loop until the receiver finishes reconnecting.
                return
            if not self._pending_reports:
                return

    def _batch_msg(self, batch: list) -> dict:
        """The wire form of one report batch: packed columns when the
        scheduler negotiated them AND the encoder can represent the batch
        exactly (it refuses anything lossy — see reportcodec); otherwise
        the legacy per-piece dict list."""
        if len(batch) == 1:
            return {"type": "piece_finished", "piece": batch[0]}
        if self._packed_ok:
            packed = reportcodec.encode_reports(batch)
            if packed is not None:
                return {"type": "pieces_finished", "packed": packed}
        return {"type": "pieces_finished", "pieces": batch}

    async def _flush_reports(self) -> bool:
        """Send buffered piece reports, draining the queue in
        report_batch-capped messages. Returns False when the stream was
        down — the unsent batch is RESTORED in order, not dropped, so the
        reports survive for the announce-stream recovery path to flush."""
        async with self._report_lock:
            pending = self._pending_reports
            while pending:
                cap = min(self.report_batch, len(pending))
                batch = [pending.popleft() for _ in range(cap)]
                self._last_flush = asyncio.get_running_loop().time()
                try:
                    sent = await self._safe_send(self._batch_msg(batch))
                except BaseException:
                    # A cancellation (teardown racing a flush) must not
                    # drop the popped batch: restore it — in order, O(batch)
                    # not O(queue) — so the teardown's own final flush
                    # still reports these pieces.
                    pending.extendleft(reversed(batch))
                    raise
                if not sent:
                    pending.extendleft(reversed(batch))
                    return False
            return True

    @staticmethod
    def _note_recv(msg: dict) -> None:
        """Book a received announce message's serialized weight (the
        recv half of peer_announce_bytes_total)."""
        try:
            ANNOUNCE_BYTES.labels("recv").inc(
                len(msgpack.packb(msg, use_bin_type=True)))
        except Exception:
            pass   # accounting must never break the stream

    async def _safe_send(self, msg: dict) -> bool:
        """Send on the announce stream; returns False when the stream is
        down (the receiver loop owns reconnection — callers must not race
        it with their own)."""
        # Scheduler-visible ordering: buffered piece reports precede any
        # terminal or reschedule message (the scheduler's piece counts must
        # be current when it acts on those).
        if msg.get("type") in ("download_finished", "reschedule",
                               "download_failed"):
            await self._flush_reports()
        if msg.get("type") in ("download_finished", "download_failed") \
                and "flight" not in msg:
            # Flight shipping: the terminal announce message carries the
            # compact bounded digest of this task's event ring (plus the
            # clock samples) so the scheduler's pod lens can merge a
            # cross-host timeline without a pull round-trip per host.
            # Advisory — a digest failure must never fail the task path.
            try:
                msg["flight"] = flightlib.digest(
                    self.flight, clock_samples=self._clock_samples)
            except Exception:
                log.warning("flight digest failed",
                            task=self.task_id[:16], exc_info=True)
        stream = self._stream
        if stream is None or stream.closed:
            return False
        try:
            await stream.send(msg)
            ANNOUNCE_BYTES.labels("sent").inc(
                len(msgpack.packb(msg, use_bin_type=True)))
            return True
        except DfError:
            return False

    async def _teardown(self) -> None:
        self._announce_done = True   # recovery must not race teardown
        self._stamp_sources()
        unwatch = getattr(self.scheduler_client, "unwatch_ring", None)
        if unwatch is not None:
            unwatch(self.task_id)
        if self._flush_task is not None and not self._flush_task.done():
            self._flush_task.cancel()
        await self._flush_reports()
        if self.synchronizer is not None:
            await self.synchronizer.close()
        await self.downloader.close()
        if self._stream is not None:
            await self._stream.close()
