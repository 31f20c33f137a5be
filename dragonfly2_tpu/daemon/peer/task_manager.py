"""Peer task manager: task front-end, dedup, reuse, conductors.

Reference: client/daemon/peer/peertask_manager.go — StartFileTask (:328),
StartSeedTask (:401), conductor dedup (getOrCreatePeerTaskConductor :201),
Subscribe (:439) via the piece broker; peertask_reuse.go for local reuse.
"""

from __future__ import annotations

import asyncio
import time
from dataclasses import dataclass, field
from typing import AsyncIterator

from dragonfly2_tpu.daemon.peer.broker import PieceBroker, PieceEvent
from dragonfly2_tpu.daemon.peer.conductor import piece_report
from dragonfly2_tpu.daemon.peer.piece_manager import PieceManager
from dragonfly2_tpu.pkg import aio, dflog, idgen, metrics
from dragonfly2_tpu.pkg import flight as flightlib
from dragonfly2_tpu.pkg.errors import Code, DfError, StorageError, describe
from dragonfly2_tpu.pkg.piece import (
    Range,
    compute_piece_count,
    compute_piece_size,
)
from dragonfly2_tpu.pkg.ratelimit import Limiter
from dragonfly2_tpu.proto.common import UrlMeta
from dragonfly2_tpu.storage import (
    LocalTaskStore,
    StorageManager,
    TaskStoreMetadata,
)
from dragonfly2_tpu.storage.local_store import (
    acquire_read_buffer,
    release_read_buffer,
)

log = dflog.get("peer.task_manager")

# Completion-time whole-content digest decision: "skipped" = the certified
# piece chain proved it (warm path / cold-race wait succeeded); "hashed" =
# the O(content) re-hash ran. The skipped:hashed ratio is the fleet-visible
# measure of how often the certification chain is doing its job.
COMPLETION_REHASH = metrics.counter(
    "peer_completion_rehash_total",
    "Completion-time whole-content digest decisions", ("result",))


@dataclass
class FileTaskRequest:
    url: str
    output: str
    meta: UrlMeta = field(default_factory=UrlMeta)
    peer_id: str = ""
    disable_back_source: bool = False
    range: Range | None = None
    # Terminal device: "" = disk only; "tpu" additionally lands verified
    # pieces into an HBM sink (daemon/peer/device_sink.py) as they arrive.
    device: str = ""
    # Which local device the sink lies on (a jax Device of this process; a
    # client-API request's alone, never on the wire and no part of the task
    # id): None lands where the sink manager lands.
    sink_device: object = None
    # Striped slice broadcast: register the task as a pod broadcast so the
    # scheduler stripes the DCN pull across same-slice hosts (1/S of the
    # bytes each; the rest fills intra-slice).
    pod_broadcast: bool = False
    # A triggered replication (persistent cache): this daemon pulls from
    # peers as a plain peer even where it is configured as a seed peer, whose
    # every other task registers as a seed and is sent back to source.
    as_peer: bool = False
    # A replication triggered while the content is still being imported on
    # its producer: no digest exists yet, and the algorithm named here says
    # that the value comes with a parent's done. The pull hashes what it
    # stores from its first piece on and is marked done only once that hash
    # equals the value (``_finalize_content_digest``, as with a digest given
    # at the start).
    digest_from_parent: str = ""

    def task_id(self) -> str:
        return idgen.task_id_v1(
            self.url,
            digest=self.meta.digest,
            tag=self.meta.tag,
            application=self.meta.application,
            filters=self.meta.filter,
            range_header=self.meta.range,
        )

    def parent_task_id(self) -> str:
        """Whole-content task id for ranged requests (reference
        task_id.go:40-44) — the store partial/completed reuse looks up."""
        return idgen.parent_task_id_v1(
            self.url,
            digest=self.meta.digest,
            tag=self.meta.tag,
            application=self.meta.application,
            filters=self.meta.filter,
        )


@dataclass
class StreamTaskRequest:
    """Stream task: ordered bytes delivered as pieces land (reference
    peertask_stream.go). The task id excludes the range so concurrent ranged
    readers share one underlying whole-content task."""

    url: str
    meta: UrlMeta = field(default_factory=UrlMeta)
    peer_id: str = ""
    range: Range | None = None          # bytes to emit (None = everything)
    disable_back_source: bool = False

    def task_id(self) -> str:
        return idgen.task_id_v1(
            self.url,
            digest=self.meta.digest,
            tag=self.meta.tag,
            application=self.meta.application,
            filters=self.meta.filter,
        )


@dataclass
class FileTaskProgress:
    state: str                  # running | done | failed
    task_id: str = ""
    peer_id: str = ""
    content_length: int = -1
    completed_length: int = 0
    piece_count: int = 0
    total_piece_count: int = -1
    digest: str = ""
    error: dict | None = None
    from_reuse: bool = False
    from_p2p: bool = False
    # True when the content also landed in a device sink and passed
    # on-device verification (device="tpu" requests).
    device_verified: bool = False
    # Which device holds the verified bytes (jax's platform and
    # device_kind of the sink's device), or the first device error that
    # left a device="tpu" request with a disk-only result.
    device_platform: str = ""
    device_kind: str = ""
    device_error: str = ""

    def to_wire(self) -> dict:
        return {
            "state": self.state,
            "task_id": self.task_id,
            "peer_id": self.peer_id,
            "content_length": self.content_length,
            "completed_length": self.completed_length,
            "piece_count": self.piece_count,
            "total_piece_count": self.total_piece_count,
            "digest": self.digest,
            "error": self.error,
            "from_reuse": self.from_reuse,
            "from_p2p": self.from_p2p,
            "device_verified": self.device_verified,
            "device_platform": self.device_platform,
            "device_kind": self.device_kind,
            "device_error": self.device_error,
        }


class _RunningTask:
    def __init__(self, store):
        self.store = store
        self.done = asyncio.Event()
        self.error: DfError | None = None


class _ProducerReports:
    """What a peer that produces a task's bytes itself tells the scheduler
    on its announce stream, for an import, which has no conductor: each
    committed piece as ``piece_finished``, then ``download_finished`` or
    ``download_failed``. It never registers (the scheduler entered the peer
    when the import told it ``Started``) and nothing is awaited back. Best
    effort: the children learn the pieces from this host's sync stream, so
    a report that cannot be sent is logged once and the import goes on."""

    def __init__(self, stream):
        self._stream = stream

    @classmethod
    async def open(cls, scheduler_client, host_wire, task_id: str,
                   peer_id: str, req) -> "_ProducerReports | None":
        host_info = host_wire() if host_wire is not None else {}
        if not host_info:
            return None
        host_info.pop("telemetry", None)
        try:
            return cls(await scheduler_client.open_announce_stream({
                "host": host_info, "peer_id": peer_id, "task_id": task_id,
                "url": req.url, "tag": req.meta.tag,
                "application": req.meta.application,
                "digest": req.meta.digest}))
        except DfError as e:
            log.warning("producer's announce stream not opened",
                        task_id=task_id[:16], error=str(e))
            return None

    async def _send(self, msg: dict) -> None:
        if self._stream is None:
            return
        try:
            await self._stream.send(msg)
        except DfError as e:
            log.warning("producer's report not sent", error=str(e))
            self._stream = None

    async def piece(self, rec) -> None:
        await self._send({"type": "piece_finished",
                          "piece": piece_report(rec, "")})

    async def end(self, store) -> None:
        """``store``: the completed store, or None for a failed import."""
        if store is None:
            await self._send({"type": "download_failed"})
        else:
            m = store.metadata
            await self._send({"type": "download_finished",
                              "content_length": m.content_length,
                              "piece_size": m.piece_size,
                              "total_piece_count": m.total_piece_count})
        stream, self._stream = self._stream, None
        if stream is not None:
            try:
                await stream.close()
            except DfError:
                pass


class TaskManager:
    """Front-end for file/stream/seed tasks; owns conductor dedup and the
    piece broker."""

    def __init__(
        self,
        storage: StorageManager,
        piece_manager: PieceManager,
        *,
        host_ip: str = "127.0.0.1",
        scheduler_client=None,
        conductor_factory=None,
        total_rate_limit: int = 0,
        host_wire=None,
        traffic_shaper: str = "plain",
        pex=None,
        prefetch: bool = False,
        device_sinks=None,
        flight=None,
    ):
        self.storage = storage
        self.piece_manager = piece_manager
        # HBM terminal store (daemon/peer/device_sink.DeviceSinkManager) —
        # present iff TPUSinkOption.enabled; requests select it per task
        # via FileTaskRequest.device == "tpu".
        self.device_sinks = device_sinks
        # Ranged-request prefetch: a range miss also kicks off a background
        # whole-task download (reference peertask_manager.go:288).
        self.prefetch = prefetch
        self.host_ip = host_ip
        self.scheduler_client = scheduler_client
        self.conductor_factory = conductor_factory
        # () -> AnnounceHost-shaped dict (or {} before the daemon starts);
        # used to advertise imported tasks under the daemon's one identity.
        self.host_wire = host_wire
        # Gossip peer exchange (daemon/pex.py): schedulerless peer discovery
        # + task-possession broadcast (reference client/daemon/pex/).
        self.pex = pex
        from dragonfly2_tpu.daemon.peer.traffic_shaper import TrafficShaper
        from dragonfly2_tpu.pkg.quarantine import ParentQuarantine

        # Daemon-wide bad-parent quarantine: ONE decaying-penalty registry
        # shared by every conductor (and the PEX pull path), keyed by the
        # parent's serving endpoint — a parent that served corrupt bytes
        # for one task is not trusted for the next.
        self.quarantine = ParentQuarantine()
        self.shaper = TrafficShaper(
            total_rate_limit if total_rate_limit > 0 else float("inf"),
            algorithm=traffic_shaper)
        # Shared bucket (plain algorithm / non-task transfers).
        self.limiter = self.shaper._shared
        self.broker = PieceBroker()
        # Flight recorder (pkg/flight): the bounded task index; download
        # paths stamp events, terminal paths finish the flight
        # (histograms + post-mortem dump on failure). Injectable so
        # embedded multi-daemon tests keep per-daemon recorders; real
        # daemons share the process-wide one.
        self.flight = flight if flight is not None else flightlib.recorder()
        self._running: dict[str, _RunningTask] = {}
        # Parents whose direct range read failed, each warned of once.
        self._unreadable_parents: set[str] = set()
        # Last completed P2P pull's bytes per parent locality
        # (conductor.locality_bytes), keyed by task id — the striped
        # e2e/bench per-host DCN-bytes readout. Bounded: small dicts,
        # overwritten per task id, cleared with the entry cap below.
        self.locality_bytes: dict[str, dict] = {}
        # Last delta landing's byte/chunk accounting per task id
        # (delta/resolver.py): reused vs fetched bytes, corrupt-base
        # refetches. Same bounding discipline as locality_bytes.
        self.delta_stats: dict[str, dict] = {}

    # -- shared download core ---------------------------------------------

    async def _run_download(self, task_id: str, peer_id: str, req: FileTaskRequest,
                            store, progress_q: "_ProgressAggregator | None",
                            *, is_seed: bool = False) -> bool:
        """Run the download into ``store``; returns from_p2p. Publishes piece
        events to the broker so SyncPieceTasks children see pieces live."""

        # Ranged tasks land too: the store's piece grid is slice-relative
        # (download_source treats the range as the content), so the sink's
        # geometry is simply the slice's. This is what sharded checkpoint
        # pulls ride — each host lands only its own tensors' byte ranges
        # (client/device.py download_sharded).
        sink_wanted = (req.device == "tpu" and self.device_sinks is not None)
        tf = self.flight.task(task_id)

        async def on_piece(st, rec) -> None:
            m = st.metadata
            self.broker.publish(task_id, PieceEvent(
                [rec.num], m.total_piece_count, m.content_length, m.piece_size,
                digests={rec.num: rec.digest}))
            if sink_wanted:
                # Land into HBM as the piece verifies — by completion the
                # device buffer only awaits the final on-device check.
                tf.record(flightlib.EV_HBM_START, rec.num)
                await self.device_sinks.on_piece(task_id, st, rec, tf,
                                                 req.sink_device)
                tf.record(flightlib.EV_HBM_LANDED, rec.num)
            if progress_q is not None:
                await progress_q.on_piece(st, rec)

        use_p2p = self.scheduler_client is not None and self.conductor_factory is not None
        limiter = self.shaper.start_task(task_id)
        try:
            if use_p2p:
                conductor = self.conductor_factory(
                    task_id=task_id, peer_id=peer_id, request=req, store=store,
                    on_piece=on_piece, is_seed=is_seed, limiter=limiter,
                )
                try:
                    await conductor.run()
                finally:
                    if len(self.locality_bytes) > 256:
                        self.locality_bytes.clear()
                    self.locality_bytes[task_id] = dict(
                        getattr(conductor, "locality_bytes", {}) or {})
                if req.digest_from_parent and not req.meta.digest:
                    # What the completion decision holds the store against.
                    # No value (the scheduler answered with another way than
                    # a pull from parents) leaves nothing to compare with.
                    if not conductor.content_digest:
                        raise DfError(
                            Code.ClientPieceDownloadFail,
                            "no parent's done carried the content's digest")
                    req.meta.digest = conductor.content_digest
                return conductor.from_p2p
            if self.pex is not None:
                # Schedulerless P2P: gossip told us who holds this task.
                # A failed attempt (stale holders, mid-transfer stall) falls
                # through to back-source rather than failing the task.
                try:
                    if await self._pex_download(task_id, peer_id, store,
                                                on_piece, limiter):
                        return True
                except DfError as e:
                    if req.disable_back_source:
                        raise
                    log.warning("pex download failed, falling back to source",
                                task_id=task_id[:16], error=str(e))
            # A ranged task whose slice a LOCAL parent store already
            # covers imports it without touching origin (not a
            # back-source at all — allowed even when origin is off
            # the table).
            if await self.import_range_from_local_parent(store, req,
                                                         on_piece):
                return False
            if req.disable_back_source:
                raise DfError(Code.ClientBackSourceError,
                              "no scheduler and back-to-source disabled")
            if LocalTaskStore.completion_digest_applies(
                    req.meta.digest, req.range is not None):
                # Back-source pieces are self-computed — no parent map can
                # ever certify them — so the completion re-hash is certain:
                # overlap it with the download (storage _PrefixHasher).
                store.start_prefix_hasher(req.meta.digest)
            await self.piece_manager.download_source(
                store, req.url, req.meta.header,
                content_range=req.range,
                on_piece=on_piece,
                limiter=limiter,
            )
            return False
        finally:
            self.shaper.finish_task(task_id)

    async def _pex_download(self, task_id: str, peer_id: str, store,
                            on_piece, limiter) -> bool:
        """Pull every piece from PEX-discovered holders (no scheduler in the
        loop — reference pex/peer_exchange.go's scheduler-free path). Returns
        False when gossip knows no live holder; raises only on mid-transfer
        failure with no usable parent left."""
        from dragonfly2_tpu.daemon.peer.piece_dispatcher import PieceDispatcher
        from dragonfly2_tpu.daemon.peer.piece_downloader import (
            PieceDownloader,
            is_parent_gone,
            pull_one_piece,
        )
        from dragonfly2_tpu.daemon.peer.synchronizer import PieceTaskSynchronizer

        holders = self.pex.find_holders(task_id)
        holders = [m for m in holders if m.peer_port and m.upload_port]
        if not holders:
            return False
        dispatcher = PieceDispatcher(quarantine=self.quarantine)
        synchronizer = PieceTaskSynchronizer(task_id, peer_id, dispatcher)
        downloader = PieceDownloader()
        dispatcher.mark_known_downloaded(store.metadata.pieces.keys())
        synchronizer.sync_parents([
            {"id": m.node_id,
             "host": {"ip": m.ip, "port": m.peer_port,
                      "upload_port": m.upload_port}}
            for m in holders])
        log.info("pex download", task_id=task_id[:16], holders=len(holders))

        async def worker() -> None:
            while not dispatcher.is_complete():
                assignment = await dispatcher.get(timeout=15.0)
                if assignment is None:
                    if dispatcher.is_complete():
                        return
                    raise DfError(Code.ClientPieceDownloadFail,
                                  "pex download stalled (no usable holders)")
                try:
                    rec = await pull_one_piece(
                        downloader, store, dispatcher, assignment,
                        task_id=task_id, peer_id=peer_id, limiter=limiter)
                except DfError as e:
                    dispatcher.report_failure(assignment,
                                              parent_gone=is_parent_gone(e))
                    from dragonfly2_tpu.daemon.peer.piece_downloader import (
                        failure_reason,
                    )
                    from dragonfly2_tpu.daemon.peer.piece_dispatcher import (
                        parent_key,
                    )

                    self.quarantine.penalize(parent_key(assignment.parent),
                                             failure_reason(e))
                    continue
                dispatcher.report_success(assignment, rec.cost_ms)
                await on_piece(store, rec)

        try:
            workers = [asyncio.ensure_future(worker()) for _ in range(4)]
            try:
                await asyncio.gather(*workers)
            except BaseException:
                for w in workers:
                    w.cancel()
                await asyncio.gather(*workers, return_exceptions=True)
                raise
        finally:
            await synchronizer.close()
            await downloader.close()
        if not dispatcher.is_complete():
            raise DfError(Code.ClientPieceDownloadFail, "pex download incomplete")
        if (store.metadata.content_length < 0
                and dispatcher.content_length >= 0):
            store.update_task(content_length=dispatcher.content_length)
        return True

    def _pex_announce(self, task_id: str) -> None:
        if self.pex is not None:
            self.pex.add_task(task_id)

    # -- import / export (dfcache — reference client/dfcache + ImportFile) --

    async def import_task(self, path: str, req: "FileTaskRequest", *,
                          persistent: bool = False, replica_count: int = 1,
                          ttl: float = 0.0) -> dict:
        """Import a local file as a completed P2P task (reference
        piece_manager.go:662 ImportFile + dfcache Import). With
        ``persistent``, the scheduler records it as a persistent cache task
        and replicates it to ``replica_count`` hosts (reference
        UploadPersistentCacheTask* family, service_v2.go:1726-1895): the
        scheduler answers ``Finished`` at once and replicates behind it."""
        async def fill(store, on_piece) -> None:
            await self.piece_manager.import_file(store, path, on_piece)
            if req.meta.digest:
                # Whole-content hash: off the loop (hashlib releases
                # the GIL; inline it stalls every active transfer).
                await asyncio.to_thread(
                    store.validate_digest, req.meta.digest)
                store.metadata.digest = req.meta.digest

        return await self._import(fill, req, persistent=persistent,
                                  replica_count=replica_count, ttl=ttl)

    async def import_source(self, source, req: "FileTaskRequest", *,
                            replica_count: int = 2, ttl: float = 0.0,
                            wait_replicas_s: float = 120.0,
                            stamp=None) -> dict:
        """Import content that lies in memory (``source``:
        ``PieceManager.import_pieces``'s) as a persistent cache task, and
        return only when ``replica_count`` hosts, this one among them, hold
        a copy whose pieces and sha256 verified. ``Started`` carries the
        task's geometry, which is known before the first byte is fetched:
        the scheduler enters this host as the peer that produces the task
        and asks the replicas' hosts to pull then, and each piece is served
        to them from its commit on (``_import_local``). ``Finished`` carries
        the digest this import took and is answered by the scheduler once
        the replicas are made and each holder's daemon says so (``holders``
        in the result), or refused after ``wait_replicas_s``; the task is
        then reported ``Failed``. An entry of the same id that this host
        already holds is refused: a save never answers with another save's
        bytes."""
        if self.storage.find_completed_task(req.task_id()) is not None:
            raise DfError(Code.BadRequest,
                          f"{req.url} is already held by this host")

        async def fill(store, on_piece) -> None:
            store.metadata.digest = await self.piece_manager.import_pieces(
                store, source, stamp, on_piece)

        return await self._import(
            fill, req, persistent=True, replica_count=replica_count, ttl=ttl,
            wait_replicas_s=wait_replicas_s, stamp=stamp,
            geometry={"content_length": source.content_length,
                      "piece_size": source.piece_size,
                      "total_piece_count": compute_piece_count(
                          source.content_length, source.piece_size)})

    async def _import(self, fill, req: "FileTaskRequest", *,
                      persistent: bool, replica_count: int, ttl: float,
                      wait_replicas_s: float = 0.0, stamp=None,
                      geometry: "dict | None" = None) -> dict:
        task_id = req.task_id()
        peer_id = req.peer_id or idgen.peer_id_v1(self.host_ip)
        # perf_counter as ``Started`` was answered; None until then.
        answered: "float | None" = None

        async def started() -> "_ProducerReports | None":
            nonlocal answered
            if not persistent:
                return None
            await self._persistent_call(
                "Scheduler.UploadPersistentCacheTaskStarted", task_id, peer_id,
                {"url": req.url, "tag": req.meta.tag,
                 "application": req.meta.application,
                 "replica_count": replica_count, "ttl": ttl,
                 "digest": req.meta.digest, **(geometry or {})})
            answered = time.perf_counter()
            if geometry is None:
                return None
            # The scheduler holds this host as the task's producing peer
            # now: its pieces are reported as any such peer's are.
            return await _ProducerReports.open(
                self.scheduler_client, self.host_wire, task_id, peer_id, req)

        try:
            result = await self._import_local(fill, req, task_id, peer_id,
                                              started)
            if persistent:
                if geometry is not None and stamp is not None:
                    self._stamp_replica_ahead(result, answered, stamp)
                sent = time.perf_counter()
                reply = await self._persistent_call(
                    "Scheduler.UploadPersistentCacheTaskFinished", task_id,
                    peer_id,
                    {"content_length": result["content_length"],
                     "piece_size": result.get("piece_size", 0),
                     "total_piece_count": result.get("total_piece_count", -1),
                     "digest": result["digest"],
                     "wait_replicas_s": float(wait_replicas_s)},
                    timeout=10.0 + wait_replicas_s)
                result["holders"] = list((reply or {}).get("holders") or [])
                if wait_replicas_s and stamp is not None:
                    stamp(flightlib.EV_SAVE_REPLICATED,
                          len(result["holders"]),
                          (time.perf_counter() - sent) * 1000.0,
                          ",".join(result["holders"]))
        except BaseException:
            if answered is not None:
                try:
                    # Best-effort: a scheduler/network error here must not
                    # mask the real import failure. An import that asked
                    # for verified replicas and got none leaves no task
                    # that reads as durable.
                    await self._persistent_call(
                        "Scheduler.UploadPersistentCacheTaskFailed",
                        task_id, peer_id,
                        {"unreplicated": bool(wait_replicas_s)})
                except Exception as notify_err:
                    log.warning("persistent-failed notify failed",
                                error=str(notify_err))
            raise
        return result

    def _stamp_replica_ahead(self, result: dict, answered: float,
                             stamp) -> None:
        """``save_replica_ahead``, as ``Finished`` is about to be sent: the
        pieces of the task that this host's upload side has already served
        to other hosts (the bytes of the ``upload_serve`` events on the
        task's flight, the native server's sends drained first, as a share
        of the content times its piece count: a run of pieces is one send),
        and the ms from ``Started`` answered to the first send's start (0.0
        with none)."""
        self.flight.sync()
        tf = self.flight.get(result["task_id"])
        sent, first = 0, None
        for t, code, _, aux, note in (tf.events() if tf is not None else ()):
            if code == flightlib.EV_UPLOAD_SERVE:
                sent += flightlib.parse_serve_note(note)[0]
                began = t - aux / 1000.0
                first = began if first is None else min(first, began)
        total = max(result["total_piece_count"], 0)
        pieces = min(total, round(total * sent
                                  / max(result["content_length"], 1)))
        first_ms = 0.0
        if first is not None:
            # The flight's clock is perf_counter since its start.
            start_pc = time.perf_counter() - tf.wall_s()
            first_ms = max(0.0, (first - (answered - start_pc)) * 1000.0)
        stamp(flightlib.EV_SAVE_REPLICA_AHEAD, pieces, first_ms, str(sent))

    async def _persistent_call(self, method: str, task_id: str, peer_id: str,
                               extra: dict, timeout: float = 10.0):
        if self.scheduler_client is None:
            raise DfError(Code.BadRequest,
                          "persistent import needs a scheduler connection")
        host_info = self.host_wire() if self.host_wire is not None else {}
        host_info.pop("telemetry", None)
        return await self.scheduler_client.unary(
            task_id, method,
            {"task_id": task_id, "peer_id": peer_id,
             "host": host_info, **extra}, timeout=timeout)

    async def _import_local(self, fill, req: "FileTaskRequest",
                            task_id: str, peer_id: str, started) -> dict:
        """Fill a new store of this host through ``fill(store, on_piece)``
        and announce it complete. While it is filled the task is RUNNING
        here (``is_task_running``) as any download is: each committed piece
        is published to the broker with its digest, so a child's
        ``Peer.SyncPieceTasks`` is served the store's snapshot and then the
        pieces as they commit, and the end publishes ``done`` with the
        content's digest, or ``failed``. ``started()`` tells the scheduler
        once the store exists (whom it then sends here finds the task), and
        returns where each piece is reported to it, or None."""
        existing = self.storage.find_completed_task(task_id)
        if existing is None:
            store = self.storage.register_task(TaskStoreMetadata(
                task_id=task_id, peer_id=peer_id, url=req.url,
                tag=req.meta.tag, application=req.meta.application))
            run = self._running[task_id] = _RunningTask(store)
            reports = None

            async def on_piece(st, rec) -> None:
                m = st.metadata
                self.broker.publish(task_id, PieceEvent(
                    [rec.num], m.total_piece_count, m.content_length,
                    m.piece_size, digests={rec.num: rec.digest}))
                if reports is not None:
                    await reports.piece(rec)

            try:
                with store:
                    try:
                        reports = await started()
                        await fill(store, on_piece)
                        store.mark_done()
                        self._pex_announce(task_id)
                    except BaseException as e:
                        # A half-imported store must not be resumed by a
                        # retry: stale piece records would outlive a changed
                        # source file (start_file_task applies the same
                        # rule). A child's stream ends as a failed parent's.
                        store.mark_invalid()
                        run.error = e if isinstance(e, DfError) else DfError(
                            Code.UnknownError, describe(e))
                        self.broker.publish(task_id,
                                            PieceEvent([], failed=True))
                        if reports is not None:
                            await reports.end(None)
                        raise
            finally:
                run.done.set()
                self._running.pop(task_id, None)
            m = store.metadata
            self.broker.publish(task_id, PieceEvent(
                [], m.total_piece_count, m.content_length, m.piece_size,
                done=True, content_digest=m.digest))
            if reports is not None:
                await reports.end(store)
        else:
            store = existing
            await started()
        await self._announce_local_task(store, task_id, peer_id)
        return {"task_id": task_id, "peer_id": peer_id,
                "pieces": len(store.metadata.pieces),
                "piece_size": store.metadata.piece_size,
                "total_piece_count": store.metadata.total_piece_count,
                "content_length": store.metadata.content_length,
                "digest": store.metadata.digest}

    async def _announce_local_task(self, store, task_id: str, peer_id: str) -> None:
        """Tell the scheduler this host holds the complete task so it can be
        scheduled as a parent (Scheduler.AnnounceTask)."""
        if self.scheduler_client is None or self.host_wire is None:
            return
        try:
            host_info = self.host_wire()
            if not host_info:
                return
            host_info.pop("telemetry", None)
            m = store.metadata
            await self.scheduler_client.announce_task({
                "task_id": task_id, "peer_id": peer_id, "url": m.url,
                "tag": m.tag, "application": m.application, "host": host_info,
                "content_length": m.content_length, "piece_size": m.piece_size,
                "total_piece_count": m.total_piece_count,
                "piece_nums": sorted(m.pieces.keys()),
            })
        except Exception as e:
            log.warning("announce_task failed", task_id=task_id[:16], error=str(e))

    # -- file task (reference peertask_manager.go:328) ---------------------

    async def start_file_task(self, req: FileTaskRequest) -> AsyncIterator[FileTaskProgress]:
        task_id = req.task_id()
        peer_id = req.peer_id or idgen.peer_id_v1(self.host_ip)

        # 1. Reuse: completed local task (reference peertask_reuse.go:50).
        reused = self.storage.find_completed_task(task_id)
        if reused is not None:
            log.info("reusing completed task", task_id=task_id[:16])
            if req.output:
                # Pin across the off-loop copy: the await yields, and an
                # unpinned store can be GC-reclaimed mid-hardlink.
                with reused:
                    await asyncio.to_thread(reused.store_to, req.output)
            try:
                dev = await self._finalize_device(req, task_id, reused)
            except DfError as e:
                yield FileTaskProgress(state="failed", task_id=task_id,
                                       peer_id=peer_id, error=e.to_wire())
                return
            yield self._final_progress(reused, task_id, peer_id,
                                       from_reuse=True, device=req.device,
                                       device_verified=dev)
            return

        # 1b. Ranged request: serve the slice off the whole-content parent
        # task when its pieces cover the range — completed OR partial
        # (reference peertask_reuse.go:234 + FindPartialCompletedTask).
        # Device requests skip this (the export path is file-only; a
        # fresh ranged task below lands into the sink), and so do
        # output-less requests (gateway ranged prefetch: nothing to
        # export). Their fresh ranged task imports from the warm parent
        # only where no scheduler is configured or the conductor is
        # demoted to back-to-source (_run_download); under a scheduler
        # it registers and pulls its pieces like any task. A caller with
        # a buffer of its own asks read_range_from_local_parent BEFORE
        # it makes a task (dataset/shard_reader.py). The local parent
        # keeps serving its pieces to other peers either way.
        if req.meta.range and req.device != "tpu" and req.output:
            covering = self._covering_local_parent(req)
            if covering is not None:
                parent, rng = covering
                log.info("reusing ranged slice from parent task",
                         parent=parent.metadata.task_id[:16],
                         start=rng.start, length=rng.length)
                with parent:
                    await asyncio.to_thread(parent.export_range, req.output,
                                            rng.start, rng.length)
                yield FileTaskProgress(
                    state="done", task_id=task_id, peer_id=peer_id,
                    content_length=rng.length, completed_length=rng.length,
                    piece_count=0, total_piece_count=0, from_reuse=True)
                return
            # Miss: the ranged task downloads just its delta below; with
            # prefetch on, the whole task starts in the background so the
            # next overlapping range hits the parent store.
            self._maybe_prefetch(req.parent_task_id(), req)

        # 2. Dedup: piggyback on a running conductor for the same task
        # (reference getOrCreatePeerTaskConductor :201).
        running = self._running.get(task_id)
        if running is not None:
            log.info("waiting on running task", task_id=task_id[:16])
            await running.done.wait()
            if running.error is not None:
                yield FileTaskProgress(state="failed", task_id=task_id, peer_id=peer_id,
                                       error=running.error.to_wire())
                return
            store = self.storage.find_completed_task(task_id)
            if store is None:
                yield FileTaskProgress(
                    state="failed", task_id=task_id, peer_id=peer_id,
                    error=DfError(Code.UnknownError, "dedup race: no store").to_wire())
                return
            if req.output:
                with store:
                    await asyncio.to_thread(store.store_to, req.output)
            try:
                dev = await self._finalize_device(req, task_id, store)
            except DfError as e:
                yield FileTaskProgress(state="failed", task_id=task_id,
                                       peer_id=peer_id, error=e.to_wire())
                return
            yield self._final_progress(store, task_id, peer_id,
                                       from_reuse=True, device=req.device,
                                       device_verified=dev)
            return

        store = self.storage.register_task(
            TaskStoreMetadata(
                task_id=task_id,
                peer_id=peer_id,
                url=req.url,
                tag=req.meta.tag,
                application=req.meta.application,
                header=dict(req.meta.header),
            )
        )
        run = _RunningTask(store)
        self._running[task_id] = run
        progress_q = _ProgressAggregator(task_id, peer_id, store)
        store.pin()
        from_p2p = False
        download = asyncio.ensure_future(
            self._run_download(task_id, peer_id, req, store, progress_q))
        try:
            async for p in self._stream_progress(download, progress_q):
                yield p
            from_p2p = download.result()
            # Verify + land output inside the same failure envelope.
            await self._finalize_content_digest(req, store)
            store.mark_done()
            self.flight.finish_task(task_id, "done")
            self._pex_announce(task_id)
            if req.output:
                await asyncio.to_thread(store.store_to, req.output)
        except DfError as e:
            self._discard_sink(req, task_id)
            store.mark_invalid()
            run.error = e
            self.flight.finish_task(task_id, "failed", note=str(e))
            self.broker.publish(task_id, PieceEvent([], failed=True))
            yield FileTaskProgress(state="failed", task_id=task_id, peer_id=peer_id,
                                   error=e.to_wire())
            return
        except Exception as e:  # pragma: no cover - defensive
            log.error("file task crashed", exc_info=True)
            self._discard_sink(req, task_id)
            store.mark_invalid()
            run.error = DfError(Code.UnknownError, describe(e))
            self.flight.finish_task(task_id, "failed", note=describe(e))
            self.broker.publish(task_id, PieceEvent([], failed=True))
            yield FileTaskProgress(state="failed", task_id=task_id, peer_id=peer_id,
                                   error=run.error.to_wire())
            return
        finally:
            # Early generator close (client disconnect) must not leave the
            # download running against an unpinned, deregistered store.
            if not download.done():
                download.cancel()
                try:
                    await download
                except BaseException:
                    pass
                if run.error is None:
                    run.error = DfError(Code.ClientContextCanceled,
                                        "download aborted by client")
                self._discard_sink(req, task_id)
                store.mark_invalid()
                self.flight.finish_task(task_id, "failed",
                                        note=str(run.error))
                self.broker.publish(task_id, PieceEvent([], failed=True))
            store.unpin()
            run.done.set()
            self._running.pop(task_id, None)

        self.broker.publish(task_id, PieceEvent(
            [], store.metadata.total_piece_count, store.metadata.content_length,
            store.metadata.piece_size, done=True))

        # Device finalize AFTER the disk result is final: a corrupt DEVICE
        # copy fails this requesting stream only — the store is complete,
        # digest-verified, announced, and reusable (dedup waiters and
        # future requests are served from disk).
        try:
            device_verified = await self._finalize_device(req, task_id, store)
        except DfError as e:
            yield FileTaskProgress(state="failed", task_id=task_id,
                                   peer_id=peer_id, error=e.to_wire())
            return
        yield self._final_progress(store, task_id, peer_id, from_p2p=from_p2p,
                                   device=req.device,
                                   device_verified=device_verified)

    # -- delta task (checkpoint-delta plane, delta/resolver.py) ------------

    async def start_delta_task(self, req: FileTaskRequest,
                               base_task_id: str) -> AsyncIterator[FileTaskProgress]:
        """Land ``req`` as a delta against the locally-landed base task:
        chunks the base already holds are copied (and digest-verified)
        locally; only changed chunks cross the wire as ranged P2P tasks.
        Degrades to a plain ``start_file_task`` whenever the delta path
        is not viable (no base, no published manifest, zero overlap)."""
        from dragonfly2_tpu.delta.resolver import run_delta_task

        async for p in run_delta_task(self, req, base_task_id):
            yield p

    # -- seed task (reference StartSeedTask :401 + seeder ObtainSeeds) -----

    async def start_seed_task(self, spec: dict) -> None:
        """Seed this daemon with a task (scheduler trigger). Runs inline;
        callers fire it as a background task."""
        try:
            # Canonical form before ANYTHING hashes it: a raw trigger span
            # ('0-7') must land under the same task id as client pulls of
            # 'bytes=0-7' or the warmed store never dedups. Defensive even
            # though the RPC chokepoint validates: this runs in a spawned
            # task where an escape would be an unretrieved exception.
            norm_range = Range.normalize_header(spec.get("range", ""))
        except ValueError as e:
            log.warning("seed trigger with malformed range dropped",
                        range=str(spec.get("range"))[:64], error=str(e)[:100])
            return
        meta = UrlMeta(
            digest=spec.get("digest", ""),
            tag=spec.get("tag", ""),
            application=spec.get("application", ""),
            header=spec.get("header") or {},
            filter="&".join(spec.get("filters") or []),
            range=norm_range,
            # QoS: a triggered preheat keeps the triggering caller's
            # tenant/priority so its pieces dispatch and account like
            # any other pull of that tenant's.
            priority=int(spec.get("priority", 3) or 3),
            tenant=spec.get("tenant", ""),
        )
        # seed=False: run as a normal peer (persistent-cache replication —
        # the scheduler wants this host to PULL from peers, not re-seed from
        # origin; dfcache:// tasks have no origin at all).
        is_seed = spec.get("seed", True)
        req = FileTaskRequest(url=spec.get("url", ""), output="", meta=meta,
                              disable_back_source=bool(
                                  spec.get("disable_back_source")),
                              device=spec.get("device", ""),
                              pod_broadcast=bool(spec.get("pod_broadcast")),
                              as_peer=not is_seed,
                              digest_from_parent=spec.get(
                                  "digest_from_parent", ""))
        if meta.range:
            req.range = Range.parse_http(meta.range)
        task_id = spec.get("task_id") or req.task_id()
        running = self._running.get(task_id)
        if running is not None:
            # Already seeding. A device=tpu trigger must still land the
            # content in HBM (device is not part of the task identity, so a
            # plain seed in flight would otherwise silently swallow it):
            # wait for the running download, then finalize the sink.
            if req.device != "tpu":
                return
            await running.done.wait()
            if running.error is None:
                store = self.storage.find_completed_task(task_id)
                if store is not None:
                    await self._finalize_device_for_seed(req, task_id, store)
            return
        peer_id = (idgen.seed_peer_id_v1(self.host_ip) if is_seed
                   else idgen.peer_id_v1(self.host_ip))

        store = self.storage.register_task(
            TaskStoreMetadata(task_id=task_id, peer_id=peer_id, url=req.url,
                              tag=meta.tag, application=meta.application,
                              header=dict(meta.header)))
        run = _RunningTask(store)
        self._running[task_id] = run
        store.pin()
        try:
            await self._run_download(task_id, peer_id, req, store, None,
                                     is_seed=is_seed)
            # The seed is the TRUST ANCHOR of the piece-digest chain: its
            # back-sourced pieces carry self-computed crcs (never
            # certified), so the helper's re-hash branch proves the full
            # digest HERE, before announce — otherwise a corrupted origin
            # response would fan out pod-wide under per-piece digests that
            # faithfully match the corruption.
            await self._finalize_content_digest(req, store)
            store.mark_done()
            self.flight.finish_task(task_id, "done")
            # Disk result is final: announce and publish FIRST (peers and
            # dedup waiters must not stall behind the HBM backfill — the
            # device copy cannot affect the disk result either way).
            self._pex_announce(task_id)
            self.broker.publish(task_id, PieceEvent(
                [], store.metadata.total_piece_count, store.metadata.content_length,
                store.metadata.piece_size, done=True))
            device_verified = await self._finalize_device_for_seed(
                req, task_id, store)
            log.info("seed task complete", task_id=task_id[:16],
                     pieces=len(store.metadata.pieces),
                     **({"device_verified": device_verified}
                        if req.device else {}))
        except Exception as e:
            log.error("seed task failed", error=describe(e))
            store.mark_invalid()
            run.error = e if isinstance(e, DfError) else DfError(Code.UnknownError, describe(e))
            self.flight.finish_task(task_id, "failed", note=describe(e))
            self.broker.publish(task_id, PieceEvent([], failed=True))
        finally:
            store.unpin()
            run.done.set()
            self._running.pop(task_id, None)

    # -- stream task (reference StartStreamTask :357, peertask_stream.go) --

    class _StreamBody:
        """Ordered-piece stream body that releases its broker subscription
        even when aclose()d before the first iteration — an unstarted async
        generator's finally never runs (PEP 525), which would leak the
        queue for the lifetime of the daemon."""

        def __init__(self, broker, task_id: str, gen, q):
            self._broker = broker
            self._task_id = task_id
            self._gen = gen
            self._q = q

        def __aiter__(self):
            return self

        async def __anext__(self):
            return await self._gen.__anext__()

        async def aclose(self) -> None:
            try:
                await self._gen.aclose()
            finally:
                # Idempotent: the generator's own finally also unsubscribes
                # when it got far enough to run.
                self._broker.unsubscribe(self._task_id, self._q)

    async def start_stream_task(self, req: StreamTaskRequest):
        """Returns (attrs, body_iterator). attrs carries task/peer id,
        content_length (may be -1 for unknown-length origins until done) and
        reuse flags; the iterator yields ordered byte chunks as pieces land
        (reference peertask_stream.go:274 writeOrderedPieces)."""
        task_id = req.task_id()
        peer_id = req.peer_id or idgen.peer_id_v1(self.host_ip)

        store = self.storage.find_completed_task(task_id)
        if store is not None:
            attrs = self._stream_attrs(store, task_id, peer_id, from_reuse=True)
            rng = self._resolve_range(req.range, attrs["content_length"])
            attrs["range"] = rng
            # Completed-store reuse: expose the store so HTTP gateways can
            # sendfile the window instead of iterating bytes through Python
            # (daemon/objectstorage.py warm path).
            attrs["local_store"] = store
            return attrs, self._stream_from_store(store, rng)

        # Ranged stream against a partially-downloaded task: serve straight
        # off the store when the range's pieces already landed (reference
        # tryReuseStreamPeerTask :234 partial reuse).
        if req.range is not None:
            partial = self.storage.find_partial_completed_task(task_id)
            if partial is not None and partial.metadata.piece_size > 0:
                rng = self._resolve_range(req.range,
                                          partial.metadata.content_length)
                if (rng is not None and rng.length > 0
                        and partial.covers_range(rng.start, rng.length)):
                    attrs = self._stream_attrs(partial, task_id, peer_id,
                                               from_reuse=True)
                    attrs["range"] = rng
                    # Landed window of an in-progress task: expose the
                    # store so HTTP gateways sendfile the covered range
                    # (sendfile_window re-checks coverage) instead of
                    # iterating bytes through Python.
                    attrs["local_store"] = partial
                    return attrs, self._stream_from_store(partial, rng)

        q = self.broker.subscribe(task_id)
        run = self._running.get(task_id)
        if run is None:
            # The task may have completed between the reuse check and the
            # subscribe — re-check before starting a fresh download.
            store = self.storage.find_completed_task(task_id)
            if store is not None:
                self.broker.unsubscribe(task_id, q)
                attrs = self._stream_attrs(store, task_id, peer_id, from_reuse=True)
                rng = self._resolve_range(req.range, attrs["content_length"])
                attrs["range"] = rng
                attrs["local_store"] = store
                return attrs, self._stream_from_store(store, rng)
            file_req = FileTaskRequest(
                url=req.url, output="", meta=req.meta, peer_id=peer_id,
                disable_back_source=req.disable_back_source)
            store = self.storage.register_task(TaskStoreMetadata(
                task_id=task_id, peer_id=peer_id, url=req.url,
                tag=req.meta.tag, application=req.meta.application,
                header=dict(req.meta.header)))
            run = _RunningTask(store)
            self._running[task_id] = run
            store.pin()
            aio.spawn(
                self._run_background_download(task_id, peer_id, file_req, store, run))
        else:
            store = run.store

        # Wait for enough metadata to answer headers: content length, the
        # first piece, or a terminal event.
        try:
            while (store.metadata.content_length < 0
                   and not store.has_piece(0)
                   and run.error is None and not run.done.is_set()):
                ev = await q.get()
                if ev.failed:
                    break
        except asyncio.CancelledError:
            self.broker.unsubscribe(task_id, q)
            raise
        if run.error is not None:
            self.broker.unsubscribe(task_id, q)
            raise run.error
        attrs = self._stream_attrs(store, task_id, peer_id)
        rng = self._resolve_range(req.range, attrs["content_length"])
        attrs["range"] = rng
        # In-progress store exposed: if the requested window's pieces have
        # already landed by the time the gateway/proxy picks a serving
        # strategy, sendfile_window lets it skip the Python iterator
        # entirely; otherwise it falls back to the ordered stream below.
        attrs["local_store"] = store
        return attrs, self._StreamBody(
            self.broker, task_id, self._stream_ordered(task_id, store, run, q, rng), q)

    @staticmethod
    def _resolve_range(rng: Range | None, content_length: int) -> Range | None:
        """Open-ended ranges (``bytes=N-`` parsed as length=-1) resolve to
        [start, content_length) once the length is known; with an
        unknown-length origin the open end means "to EOF"."""
        if rng is not None and rng.length < 0 and content_length >= 0:
            return Range(rng.start, max(0, content_length - rng.start))
        return rng

    def _maybe_prefetch(self, parent_id: str, req: FileTaskRequest) -> None:
        """Kick off a background whole-task download after a ranged-request
        miss (reference peertask_manager.go:288 prefetch)."""
        if not self.prefetch or parent_id in self._running:
            return
        if self.storage.find_completed_task(parent_id) is not None:
            return
        from dataclasses import replace

        meta = replace(req.meta, range="", header=dict(req.meta.header))
        meta.header.pop("Range", None)
        peer_id = idgen.peer_id_v1(self.host_ip)
        file_req = FileTaskRequest(url=req.url, output="", meta=meta,
                                   peer_id=peer_id)
        store = self.storage.register_task(TaskStoreMetadata(
            task_id=parent_id, peer_id=peer_id, url=req.url, tag=meta.tag,
            application=meta.application, header=dict(meta.header)))
        run = _RunningTask(store)
        self._running[parent_id] = run
        store.pin()
        log.info("prefetching whole task for ranged request",
                 task=parent_id[:16])
        aio.spawn(self._run_background_download(
            parent_id, peer_id, file_req, store, run))

    async def _run_background_download(self, task_id: str, peer_id: str,
                                       req: FileTaskRequest, store, run: _RunningTask) -> None:
        """Download driver for stream tasks (no output file, no progress
        aggregator; completion is observed through the broker)."""
        try:
            await self._run_download(task_id, peer_id, req, store, None)
            await self._finalize_content_digest(req, store)
            store.mark_done()
            self.flight.finish_task(task_id, "done")
            self._pex_announce(task_id)
            self.broker.publish(task_id, PieceEvent(
                [], store.metadata.total_piece_count,
                store.metadata.content_length, store.metadata.piece_size,
                done=True))
        except DfError as e:
            store.mark_invalid()
            run.error = e
            self.flight.finish_task(task_id, "failed", note=str(e))
            self.broker.publish(task_id, PieceEvent([], failed=True))
        except Exception as e:  # pragma: no cover - defensive
            log.error("stream download crashed", exc_info=True)
            store.mark_invalid()
            run.error = DfError(Code.UnknownError, describe(e))
            self.flight.finish_task(task_id, "failed", note=describe(e))
            self.broker.publish(task_id, PieceEvent([], failed=True))
        finally:
            store.unpin()
            run.done.set()
            self._running.pop(task_id, None)

    def _stream_attrs(self, store, task_id: str, peer_id: str, *,
                      from_reuse: bool = False) -> dict:
        m = store.metadata
        return {
            "task_id": task_id,
            "peer_id": peer_id,
            "content_length": m.content_length,
            "piece_size": m.piece_size,
            "total_piece_count": m.total_piece_count,
            "from_reuse": from_reuse,
        }

    # Bound on one coalesced span read/yield: two fleet-default (4 MiB)
    # pieces per submission; small-piece tasks batch many more.
    _STREAM_SPAN = 8 << 20

    async def _stream_from_store(self, store, rng: Range | None) -> AsyncIterator[bytes]:
        """Completed task: emit the requested window straight off disk in
        bounded spans (pooled preadv — contiguous on a complete store),
        touching only the bytes that intersect the range. Yielded chunks
        are BORROWED pooled views, valid until the consumer asks for the
        next chunk (docs/ZERO_COPY.md rule 6); retainers must copy."""
        store.pin()
        try:
            m = store.metadata
            end = m.content_length if m.content_length >= 0 else \
                store.disk_usage()
            start = 0
            if rng is not None:
                start = min(rng.start, end)
                if rng.length >= 0:
                    end = min(end, rng.start + rng.length)
            span = max(m.piece_size, 1 << 20)
            off = start
            while off < end:
                take = min(span, end - off)
                chunk = await asyncio.to_thread(store.read_range, off, take)
                try:
                    yield chunk
                finally:
                    # Runs when the consumer resumes us (it is done with
                    # the view) or closes the generator: either way the
                    # buffer recycles for the next span.
                    release_read_buffer(chunk)
                off += take
        finally:
            store.unpin()

    async def _stream_ordered(self, task_id: str, store, run: _RunningTask,
                              q: asyncio.Queue, rng: Range | None) -> AsyncIterator[bytes]:
        """Running task: emit pieces in order as they land; pieces ahead of
        the contiguous frontier wait in the store until the gap fills.
        Adjacent landed pieces coalesce into ONE bounded pooled preadv
        (batched submission) instead of a bytes() allocation per piece;
        yielded chunks are borrowed pooled views (docs/ZERO_COPY.md
        rule 6), valid until the next chunk is requested."""
        next_num = 0
        store.pin()
        try:
            while True:
                m = store.metadata
                while store.has_piece(next_num):
                    # Pieces wholly before the range advance the frontier
                    # without touching disk.
                    if (rng is not None and m.piece_size > 0
                            and (next_num + 1) * m.piece_size <= rng.start):
                        next_num += 1
                        continue
                    # Coalesce the landed run starting at next_num into one
                    # span, bounded by _STREAM_SPAN and the range end.
                    first = m.pieces[next_num]
                    lo, hi = first.offset, first.offset + first.size
                    last = next_num
                    while hi - lo < self._STREAM_SPAN:
                        nxt = m.pieces.get(last + 1)
                        if nxt is None:
                            break
                        if rng is not None and rng.length >= 0 and \
                                hi >= rng.start + rng.length:
                            break
                        hi = nxt.offset + nxt.size
                        last = nxt.num
                    if rng is not None:
                        lo = max(lo, rng.start)
                        if rng.length >= 0:
                            hi = min(hi, rng.start + rng.length)
                    if hi > lo:
                        chunk = await asyncio.to_thread(
                            store.read_range, lo, hi - lo)
                        try:
                            yield chunk
                        finally:
                            release_read_buffer(chunk)
                    next_num = last + 1
                    # Past the requested range: nothing further to emit
                    # (open-ended ranges run to EOF).
                    if rng is not None and rng.length >= 0 and m.piece_size > 0 and \
                            next_num * m.piece_size >= rng.start + rng.length:
                        return
                if run.error is not None:
                    raise run.error
                if m.total_piece_count >= 0 and next_num >= m.total_piece_count:
                    return
                if run.done.is_set() and not store.has_piece(next_num):
                    # Completed without the piece we need -> invalidated.
                    raise DfError(Code.UnknownError, "stream task ended short")
                ev = await q.get()
                if ev.failed and run.error is not None:
                    raise run.error
        finally:
            store.unpin()
            self.broker.unsubscribe(task_id, q)

    def is_task_running(self, task_id: str) -> bool:
        return task_id in self._running

    # -- helpers -----------------------------------------------------------

    def _final_progress(self, store, task_id: str, peer_id: str, *,
                        from_reuse: bool = False, from_p2p: bool = False,
                        device: str = "",
                        device_verified: bool = False) -> FileTaskProgress:
        m = store.metadata
        # Only the request that asked for the device reads (and clears)
        # its landing's outcome.
        outcome = (self.device_sinks.outcome(task_id, device_verified)
                   if device and self.device_sinks is not None else {})
        return FileTaskProgress(
            state="done",
            task_id=task_id,
            peer_id=peer_id,
            content_length=m.content_length,
            completed_length=store.downloaded_bytes(),
            piece_count=len(m.pieces),
            total_piece_count=m.total_piece_count,
            digest=m.digest,
            from_reuse=from_reuse,
            from_p2p=from_p2p,
            device_verified=device_verified,
            **outcome,
        )

    def _discard_sink(self, req: "FileTaskRequest", task_id: str) -> None:
        """Drop a partially-landed sink on any failure/abort path: a stale
        resident sink could otherwise shadow a later retry's bytes."""
        if req.device and self.device_sinks is not None:
            self.device_sinks.discard(task_id)

    def _covering_local_parent(self, req):
        """(parent_store, resolved_range) when a LOCAL completed/partial
        parent task covers ``req``'s range, else None. The ONE
        parent-coverage gate — the ranged-reuse export (step 1b) and the
        ranged import share it, so their eligibility can never fork."""
        if not req.meta.range:
            return None
        parent_id = req.parent_task_id()
        parent = (self.storage.find_completed_task(parent_id)
                  or self.storage.find_partial_completed_task(parent_id))
        if parent is None or parent.metadata.piece_size <= 0:
            return None
        total = parent.metadata.content_length
        try:
            rng = Range.parse_http(req.meta.range, total)
        except ValueError:
            return None
        if rng is None:
            return None
        # Clamp EOF-overshooting spans exactly like download_source does
        # before fetching: origin clamps 'bytes=0-262143' on a 100 KiB
        # object, so the warm local parent must serve the same clamped
        # slice — otherwise every overshooting range (the header guess on
        # a small checkpoint, a generous user range) skips the warm store
        # and re-touches origin.
        length = rng.length
        if total >= 0:
            length = min(length, max(0, total - rng.start))
        if length <= 0 or not parent.covers_range(rng.start, length):
            return None
        return parent, Range(rng.start, length)

    async def read_range_from_local_parent(self, req, buf) -> "int | None":
        """The bytes of ``req``'s range straight into ``buf``, when THIS
        store's parent covers them (the one gate above): no task is made,
        none registered, nothing leaves this process. Returns the bytes
        read (the range clamped to the parent's end), or None where the
        caller must run the ranged task: no covering parent, or a parent
        that cannot be read (truncated under its metadata, reclaimed),
        which the task's own import and its fall to the origin then
        handle as they did."""
        covering = self._covering_local_parent(req)
        if covering is None:
            return None
        parent, rng = covering
        try:
            with parent:  # pin across the off-loop read
                await asyncio.to_thread(parent.read_into, rng.start,
                                        rng.length, buf)
        except (StorageError, OSError) as e:
            parent_id = parent.metadata.task_id
            if parent_id not in self._unreadable_parents:
                self._unreadable_parents.add(parent_id)
                log.warning("local range read failed; its ranges go "
                            "through ranged tasks", parent=parent_id[:16],
                            start=rng.start, length=rng.length,
                            error=str(e)[:200])
            return None
        return rng.length

    async def import_range_from_local_parent(self, store, req, on_piece) -> bool:
        """Ranged back-source shortcut: when THIS daemon already holds a
        whole-content (or covering partial) parent task, the slice
        imports from the local store instead of touching origin.

        This is what makes plain whole-file preheats compose with
        sharded pulls: a ranged task is a distinct task id, so without
        this every span the scheduler triggers on a warm seed would
        re-fetch from origin despite the seed holding every byte.
        Imported pieces flow through ``on_piece`` like downloaded ones
        (piece reports, device-sink landings, progress). Returns True
        when the ranged store completed from the parent; any import
        failure (e.g. a parent truncated under its metadata) returns
        False so the caller falls back to origin — the pre-feature
        recovery path must survive the optimization."""
        covering = self._covering_local_parent(req)
        if covering is None:
            return False
        parent, rng = covering
        piece_size = store.metadata.piece_size or compute_piece_size(rng.length)
        store.update_task(content_length=rng.length, piece_size=piece_size,
                          total_piece_count=compute_piece_count(
                              rng.length, piece_size))
        log.info("ranged task imports from local parent",
                 task=store.metadata.task_id[:16],
                 parent=parent.metadata.task_id[:16],
                 start=rng.start, length=rng.length)
        moved_s, pieces = 0.0, 0
        try:
            with parent:  # pin: GC must not reclaim the parent mid-import
                # ONE pooled buffer reused for every piece of the import:
                # read_into fills it in place (unified read path), the
                # write lands (and digests) straight from it.
                buf = acquire_read_buffer(piece_size)
                try:
                    for n in range(store.metadata.total_piece_count):
                        if n in store.metadata.pieces:
                            continue   # resume semantics match back-source
                        off = n * piece_size
                        size = min(piece_size, rng.length - off)
                        t0 = time.perf_counter()
                        await asyncio.to_thread(
                            parent.read_into, rng.start + off, size, buf)
                        rec = await asyncio.to_thread(
                            store.write_piece, n, buf[:size])
                        moved_s += time.perf_counter() - t0
                        pieces += 1
                        if on_piece is not None:
                            await on_piece(store, rec)
                finally:
                    release_read_buffer(buf)
                    self.flight.task(store.metadata.task_id).record(
                        flightlib.EV_RANGE_IMPORT, pieces, moved_s * 1000.0,
                        str(rng.length))
        except (StorageError, OSError) as e:
            log.warning("local range import failed; falling back to origin",
                        task=store.metadata.task_id[:16], error=str(e)[:200])
            return False
        return store.is_complete()

    async def _finalize_content_digest(self, req: "FileTaskRequest",
                                       store) -> None:
        """THE single completion-digest decision point (every download
        path calls this; the skip precondition must never fork). Ranged
        tasks skip entirely — the digest names the full object, the store
        holds a slice. Complete tasks either (a) skip the O(content)
        re-hash when every piece's verified-against digest matches a
        certified parent's map (pieces_all_digest_verified — provenance-
        checked, anchored at the seed's full validation), or (b) re-hash
        off-loop (a whole-content sha256 of a multi-GB task would freeze
        this daemon's serving for seconds)."""
        if not LocalTaskStore.completion_digest_applies(
                req.meta.digest, req.range is not None):
            return
        if store.pieces_all_digest_verified():
            COMPLETION_REHASH.labels("skipped").inc()
        else:
            COMPLETION_REHASH.labels("hashed").inc()
            tf = self.flight.task(store.metadata.task_id)
            # Where the prefix hasher stands as the last piece has landed:
            # what is still to hash is the whole of the wait below.
            hashed = store.digest_frontier()
            t0 = time.perf_counter()
            tf.record(flightlib.EV_VERIFY_START, hashed, float(
                max(0, store.metadata.total_piece_count - hashed)))
            await asyncio.to_thread(store.validate_digest, req.meta.digest)
            how, read_back, (ready, waited) = store.digest_pass
            ms = (time.perf_counter() - t0) * 1000.0
            tf.record(flightlib.EV_VERIFIED, read_back, ms, how)
            # ready / (ready + waited) near 1: sha256 was the wait's limit;
            # near 0: the read-back was (store_digest_chunks_total).
            log.info("content digest verified",
                     task_id=store.metadata.task_id[:16], how=how,
                     ms=round(ms, 1), hashed_before=hashed,
                     read_back=read_back, chunks_ready=ready,
                     chunks_waited=waited)
        store.metadata.digest = req.meta.digest

    async def _finalize_device_for_seed(self, req: "FileTaskRequest",
                                        task_id: str, store) -> bool:
        """Seed/preheat variant of _finalize_device: device-copy corruption
        must NOT fail the task — the disk result is already digest-verified
        and peers depend on it (the finalize contract: fail only a
        requesting stream, and a preheat has none). Degrades to disk-only
        warm-up, loudly."""
        try:
            with store:  # pin: finalize preads run in executor threads
                return await self._finalize_device(req, task_id, store)
        except Exception as e:
            # Broad by contract: ANY escape here would reach the seed
            # task's generic handler, which marks the digest-verified,
            # already-PEX-announced disk store invalid — destroying a good
            # store peers depend on (advisor round 3). The partial sink is
            # discarded: a DeviceSinkError arrives pre-discarded, but e.g.
            # an OSError from a backfill pread would otherwise leave an
            # unverified content-sized HBM buffer parked in a sink slot.
            if self.device_sinks is not None:
                self.device_sinks.discard(task_id)
            log.error("device sink finalize failed; disk warm-up stands",
                      task_id=task_id[:16], error=describe(e))
            return False

    async def _finalize_device(self, req: "FileTaskRequest", task_id: str,
                               store) -> bool:
        """Run the device-sink completion for a ``device='tpu'`` request:
        backfill + on-device verify. Sink *unavailability* (cap reached,
        misaligned pieces, option disabled) degrades to disk-only — the
        file result is already digest-verified. Device-copy CORRUPTION
        raises: silently handing back a bad buffer would defeat
        verify-on-land. The DISK store stays valid either way — callers
        must fail only the requesting stream, not the task."""
        if req.device != "tpu":
            return False
        if self.device_sinks is None:
            log.warning("device=tpu requested but sink disabled "
                        "(TPUSinkOption.enabled=false)", task_id=task_id[:16])
            return False
        from dragonfly2_tpu.daemon.peer.device_sink import DeviceSinkError

        try:
            # The same flight the download stamped (a re-land finds the
            # finished one): the landing thread's spans go beside them.
            return await self.device_sinks.finalize(
                task_id, store, self.flight.task(task_id),
                req.sink_device) is not None
        except DeviceSinkError as e:
            self.device_sinks.discard(task_id)
            raise DfError(Code.ClientPieceDownloadFail,
                          f"device sink verification failed: {e}")

    async def _stream_progress(self, task: asyncio.Task, progress_q: "_ProgressAggregator"):
        while True:
            snap = await progress_q.next_or_done(task)
            if snap is not None:
                yield snap
            if task.done():
                task.result()  # re-raise
                while (s := progress_q.try_next()) is not None:
                    yield s
                return


class _ProgressAggregator:
    def __init__(self, task_id: str, peer_id: str, store):
        self.task_id = task_id
        self.peer_id = peer_id
        self.store = store
        self._event = asyncio.Event()
        self._last_report = 0.0

    async def on_piece(self, store, rec) -> None:
        self._event.set()

    def _snapshot(self) -> FileTaskProgress:
        m = self.store.metadata
        return FileTaskProgress(
            state="running",
            task_id=self.task_id,
            peer_id=self.peer_id,
            content_length=m.content_length,
            completed_length=self.store.downloaded_bytes(),
            piece_count=len(m.pieces),
            total_piece_count=m.total_piece_count,
        )

    def try_next(self) -> FileTaskProgress | None:
        if self._event.is_set():
            self._event.clear()
            now = time.monotonic()
            if now - self._last_report >= 0.1:  # throttle progress frames
                self._last_report = now
                return self._snapshot()
        return None

    async def next_or_done(self, task) -> FileTaskProgress | None:
        waiter = asyncio.ensure_future(self._event.wait())
        try:
            await asyncio.wait({waiter, task}, return_when=asyncio.FIRST_COMPLETED)
        finally:
            waiter.cancel()
        return self.try_next()
