"""Daemon drpc server: download service (unix sock) + peer service (TCP).

Reference: client/daemon/rpcserver/rpcserver.go — Download streaming file
task (:388), SyncPieceTasks serving children (:277), GetPieceTasks (:160),
StatTask/DeleteTask (:847+). The download service faces dfget on the local
host; the peer service faces other daemons (stage 3).
"""

from __future__ import annotations

import asyncio

from dragonfly2_tpu.daemon.peer.task_manager import FileTaskRequest, TaskManager
from dragonfly2_tpu.pkg import aio, dflog
from dragonfly2_tpu.pkg import flight as flightlib
from dragonfly2_tpu.pkg.errors import Code, DfError
from dragonfly2_tpu.pkg.piece import Range
from dragonfly2_tpu.pkg.types import NetAddr
from dragonfly2_tpu.proto.common import UrlMeta
from dragonfly2_tpu.rpc import RpcContext, Server, ServerStream

log = dflog.get("daemon.rpcserver")


class DaemonRpcServer:
    def __init__(self, task_manager: TaskManager):
        self.task_manager = task_manager
        self.download_server = Server("daemon.download")
        self.peer_server = Server("daemon.peer")
        self._register()

    def _register(self) -> None:
        self.download_server.register_stream("Daemon.Download", self._download)
        self.download_server.register_unary("Daemon.StatTask", self._stat_task)
        self.download_server.register_unary("Daemon.ImportTask", self._import_task)
        self.download_server.register_stream("Daemon.ExportTask", self._export_task)
        self.download_server.register_unary("Daemon.DeleteTask", self._delete_task)
        self.download_server.register_unary("Daemon.Health", self._health)
        self.download_server.register_unary("Daemon.FlightReport",
                                            self._flight_report)
        self.download_server.register_unary("Daemon.PodTimeline",
                                            self._pod_timeline)
        # Peer-facing service (reference rpcserver.go peer server): piece
        # availability sync for children + seed triggering by the scheduler.
        self.peer_server.register_stream("Peer.SyncPieceTasks", self._sync_piece_tasks)
        # Scheduler-side on-demand flight pull: a host that never shipped
        # its digest (crashed stream, old daemon) can still be merged
        # into the pod timeline.
        self.peer_server.register_unary("Daemon.FlightReport",
                                        self._flight_report)
        self.peer_server.register_unary("Peer.GetPieceTasks", self._get_piece_tasks)
        self.peer_server.register_unary("Peer.TriggerDownloadTask", self._trigger_download)
        self.peer_server.register_unary("Peer.StatTask", self._stat_task)
        self.peer_server.register_unary("Peer.DeleteTask", self._delete_task)
        self.peer_server.register_unary("Daemon.Health", self._health)

    async def serve_download(self, addr: NetAddr) -> None:
        await self.download_server.serve(addr)

    async def serve_peer(self, addr: NetAddr) -> None:
        await self.peer_server.serve(addr)

    async def close(self) -> None:
        await self.download_server.close()
        await self.peer_server.close()

    # -- handlers ----------------------------------------------------------

    async def _download(self, stream: ServerStream, ctx: RpcContext) -> None:
        """One file download; progress frames stream back to dfget
        (reference rpcserver.go:388 Download → :740 download)."""
        body = stream.open_body or {}
        url = body.get("url", "")
        output = body.get("output", "")
        device = body.get("device", "")
        # Output may be omitted only when the content terminates in a
        # device sink (--device=tpu): the result lives in HBM, not a path.
        if not url or (not output and device != "tpu"):
            raise DfError(Code.BadRequest, "url and output are required")
        req = FileTaskRequest(
            url=url,
            output=output,
            meta=UrlMeta.from_wire(body.get("meta")),
            disable_back_source=body.get("disable_back_source", False),
            device=device,
            pod_broadcast=bool(body.get("pod_broadcast")),
        )
        if req.meta.range:
            # Canonicalize at the wire chokepoint: the header is task
            # identity, and raw RPC clients must dedup with dfget /
            # preheat / device pulls of the same span.
            try:
                req.meta.range = Range.normalize_header(req.meta.range)
                req.range = Range.parse_http(req.meta.range)
            except ValueError as e:
                raise DfError(Code.BadRequest,
                              f"bad range {req.meta.range!r}: {e}")
        delta_base = body.get("delta_base", "")
        if delta_base:
            # Checkpoint-delta plane: copy chunks the local base version
            # already holds, fetch only changed chunks as ranged tasks
            # (delta/resolver.py; degrades to a plain download when the
            # delta path is not viable).
            progress_iter = self.task_manager.start_delta_task(
                req, delta_base)
        else:
            progress_iter = self.task_manager.start_file_task(req)
        async for progress in progress_iter:
            await stream.send(progress.to_wire())

    async def _stat_task(self, body, ctx: RpcContext):
        """Local task presence/completeness (reference rpcserver.go:847)."""
        task_id = (body or {}).get("task_id", "")
        store = self.task_manager.storage.try_get(task_id)
        if store is None:
            raise DfError(Code.PeerTaskNotFound, f"task {task_id} not found")
        m = store.metadata
        return {
            "task_id": m.task_id,
            "done": m.done,
            "content_length": m.content_length,
            "piece_count": len(m.pieces),
            "total_piece_count": m.total_piece_count,
            "digest": m.digest,
        }

    async def _import_task(self, body, ctx: RpcContext):
        """dfcache Import: local file → completed P2P task + scheduler
        announce (reference dfcache.go:112 Import, AnnounceTask)."""
        body = body or {}
        path = body.get("path", "")
        if not path:
            raise DfError(Code.BadRequest, "path required")
        req = self._cache_request(body)
        return await self.task_manager.import_task(
            path, req,
            persistent=bool(body.get("persistent")),
            replica_count=int(body.get("replica_count", 1)),
            ttl=float(body.get("ttl", 0)))

    async def _export_task(self, stream: ServerStream, ctx: RpcContext) -> None:
        """dfcache Export: land a cached task at an output path, pulling
        over P2P (never origin) when not local — reference dfcache.go:174."""
        body = stream.open_body or {}
        output = body.get("output", "")
        if not output:
            raise DfError(Code.BadRequest, "output required")
        req = self._cache_request(body)
        req.output = output
        req.disable_back_source = True
        async for progress in self.task_manager.start_file_task(req):
            await stream.send(progress.to_wire())

    @staticmethod
    def _cache_request(body: dict) -> "FileTaskRequest":
        """Cache-entry task identity: dfcache:// URL from the cache id, so
        import/export agree on the task id across hosts (reference dfcache
        computes the task id from the content id)."""
        from dragonfly2_tpu.daemon.peer.task_manager import FileTaskRequest
        from dragonfly2_tpu.proto.common import UrlMeta

        cache_id = body.get("cache_id", "")
        if not cache_id:
            raise DfError(Code.BadRequest, "cache_id required")
        meta = UrlMeta(tag=body.get("tag", ""),
                       application=body.get("application", ""),
                       digest=body.get("digest", ""))
        return FileTaskRequest(url=f"dfcache://{cache_id}", output="", meta=meta)

    async def _delete_task(self, body, ctx: RpcContext):
        """Refuses while the task is running or its store is pinned by an
        active stream/upload — same safety rule storage GC applies
        (storage/manager.py skips pinned stores)."""
        task_id = (body or {}).get("task_id", "")
        if self.task_manager.is_task_running(task_id):
            return {"ok": False, "reason": "task running"}
        store = self.task_manager.storage.try_get(task_id)
        if store is not None and store.pinned:
            return {"ok": False, "reason": "task store in use"}
        self.task_manager.storage.delete_task(task_id)
        if self.task_manager.device_sinks is not None:
            # A resident sink of the task goes with its store entry.
            self.task_manager.device_sinks.discard(task_id)
        if self.task_manager.pex is not None:
            self.task_manager.pex.remove_task(task_id)
        return {"ok": True}

    async def _health(self, body, ctx: RpcContext):
        return {"ok": True, "version": "0.1.0"}

    async def _flight_report(self, body, ctx: RpcContext):
        """Flight-recorder autopsy for a task this daemon ran: the phase
        breakdown + per-piece waterfall, JSON plus the rendered text
        (dfget --explain prints the latter — identical to the
        /debug/flight/<task_id>?format=text rendering) plus the compact
        digest the scheduler's pod lens merges on an on-demand pull. With
        ``raw`` true the reply also holds ``raw``: the task's named events
        as they lie in the ring (``flight.raw``), uncapped, so a caller can
        fold several daemons' rings on one clock."""
        task_id = (body or {}).get("task_id", "")
        self.task_manager.flight.sync()
        tf = self.task_manager.flight.get(task_id)
        if tf is None:
            raise DfError(Code.PeerTaskNotFound,
                          f"no flight data for task {task_id}")
        report = flightlib.analyze(tf)
        reply = {"report": report,
                 "text": flightlib.render_waterfall(report),
                 "digest": flightlib.digest(tf)}
        if (body or {}).get("raw"):
            reply["raw"] = flightlib.raw(tf)
        return reply

    async def _pod_timeline(self, body, ctx: RpcContext):
        """dfget --pod: proxy the merged cross-host timeline from the
        scheduler (the daemon owns the ring client; dfget only has the
        unix socket)."""
        sc = self.task_manager.scheduler_client
        if sc is None:
            raise DfError(Code.SchedError,
                          "no scheduler configured on this daemon")
        task_id = (body or {}).get("task_id", "")
        return await sc.unary(task_id, "Scheduler.PodTimeline",
                              {"task_id": task_id}, timeout=15.0,
                              idempotent=True)

    # -- peer service ------------------------------------------------------

    def _piece_snapshot(self, task_id: str) -> dict | None:
        store = self.task_manager.storage.try_get(task_id)
        if store is None or store.metadata.invalid:
            # What a failed task left is no parent's store: a child that
            # comes after the failure is told so at once, as one that was
            # there is by the stream's end.
            return None
        m = store.metadata
        snapshot = {
            "pieces": sorted(m.pieces.keys()),
            "total_piece_count": m.total_piece_count,
            "content_length": m.content_length,
            "piece_size": m.piece_size,
            "done": m.done,
            "digests": {n: p.digest for n, p in m.pieces.items() if p.digest},
        }
        if m.done and m.digest:
            # What a live ``done`` of this store carried (PieceEvent's
            # ``content_digest``), for the child that comes after it.
            snapshot["content_digest"] = m.digest
        return snapshot

    async def _sync_piece_tasks(self, stream: ServerStream, ctx: RpcContext) -> None:
        """Serve piece availability to a child peer, pushing updates as
        pieces land (reference rpcserver.go:277 SyncPieceTasks +
        subscriber.go push)."""
        body = stream.open_body or {}
        task_id = body.get("task_id", "")
        snapshot = self._piece_snapshot(task_id)
        running = self.task_manager.is_task_running(task_id)
        if snapshot is None and not running:
            raise DfError(Code.StorageTaskNotFound, f"task {task_id} not on this peer")
        broker = self.task_manager.broker
        q = broker.subscribe(task_id)

        async def drain_keepalives() -> None:
            # Children send {interested: true} keep-alives on idle streams;
            # without a reader they would pool in the stream inbox for the
            # download's lifetime.
            while await stream.recv() is not None:
                pass

        # What only this side can measure of the task while it runs here
        # (its origin's first byte, its whole-object verify) rides the
        # messages as an optional ``spans``; a task that was complete before
        # the child came has kept nobody waiting and sends none.
        tf = self.task_manager.flight.get(task_id) if running else None
        relay = flightlib.SpanRelay(tf) if tf is not None else None

        def with_spans(msg: dict) -> dict:
            spans = relay.take() if relay is not None else None
            if spans:
                msg["spans"] = spans
            return msg

        drainer = asyncio.ensure_future(drain_keepalives())
        try:
            if snapshot is not None:
                await stream.send(with_spans(snapshot))
                if snapshot["done"]:
                    return
            while True:
                event = await q.get()
                if event.failed:
                    raise DfError(Code.ClientPieceDownloadFail,
                                  "parent download failed")
                msg = {
                    "pieces": event.piece_nums,
                    "total_piece_count": event.total_piece_count,
                    "content_length": event.content_length,
                    "piece_size": event.piece_size,
                    "done": event.done,
                    "digests": event.digests,
                }
                if event.content_digest:
                    msg["content_digest"] = event.content_digest
                await stream.send(with_spans(msg))
                if event.done:
                    return
        finally:
            drainer.cancel()
            broker.unsubscribe(task_id, q)

    async def _get_piece_tasks(self, body, ctx: RpcContext):
        """One-shot piece listing (reference rpcserver.go:160 GetPieceTasks)."""
        task_id = (body or {}).get("task_id", "")
        snapshot = self._piece_snapshot(task_id)
        if snapshot is None:
            raise DfError(Code.StorageTaskNotFound, f"task {task_id} not on this peer")
        return snapshot

    async def _trigger_download(self, body, ctx: RpcContext):
        """Scheduler asks this (seed) daemon to fetch a task from origin
        (reference seeder.go:56 ObtainSeeds / v2 DownloadTask)."""
        spec = body or {}
        if not spec.get("url"):
            raise DfError(Code.BadRequest, "url required")
        if spec.get("range"):
            # Validate BEFORE the ACK: a malformed span would otherwise
            # kill the spawned seed task with an unretrieved ValueError
            # while the triggering job burns its full wait timeout
            # against a task that never existed.
            try:
                spec["range"] = Range.normalize_header(spec["range"])
            except ValueError as e:
                raise DfError(Code.BadRequest,
                              f"bad range {spec.get('range')!r}: {e}")
        task_id = spec.get("task_id", "")
        already = bool(task_id and
                       self.task_manager.storage.find_completed_task(task_id) is not None)
        if (spec.get("device") == "tpu"
                or not (task_id and self.task_manager.is_task_running(task_id))):
            # Runs even when complete: the announce-only fast path re-reports
            # local pieces so the scheduler can hand this seed out as parent.
            # device=tpu triggers ALWAYS enter start_seed_task — its dedup
            # waits for an in-flight plain seed and still lands the HBM
            # copy; skipping here would swallow the device request.
            aio.spawn(self.task_manager.start_seed_task(spec))
        return {"ok": True, "already_complete": already}
