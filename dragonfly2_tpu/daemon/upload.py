"""Upload server: HTTP endpoint other peers hit for piece payloads.

Reference: client/daemon/upload/upload_manager.go — gin server with
``GET /download/:task_prefix/:task_id`` + Range header (:181-188), rate
limiting (WithLimiter :79). Piece payloads ride HTTP (not drpc) exactly like
the reference, so transfers stream zero-copy from the page cache via
sendfile-ish paths and any HTTP client can fetch.

Serving is the READ half of the zero-copy data plane (docs/ZERO_COPY.md):
both servers move piece bytes kernel→socket without them ever entering
Python — _PieceFileResponse rides aiohttp's sendfile, the native server
(native/src/dfupload.cc) does its own sendfile loop — so the daemon's
single hot core spends its cycles on the receive/verify side only.

Routes:
  GET /download/{task_prefix}/{task_id}?peerId=...          Range: bytes=a-b
  GET /download/{task_prefix}/{task_id}?peerId=...&pieceNum=N   (whole piece)
  GET /metrics, GET /healthy
"""

from __future__ import annotations

import asyncio
import threading
import time

from aiohttp import web

from dragonfly2_tpu.pkg import dflog, metrics, tracing
from dragonfly2_tpu.pkg import flight as flightlib
from dragonfly2_tpu.pkg.piece import Range
from dragonfly2_tpu.pkg.ratelimit import Limiter
from dragonfly2_tpu.storage import StorageManager

log = dflog.get("daemon.upload")

UPLOAD_BYTES = metrics.counter("upload_bytes_total", "Piece bytes served to other peers")
UPLOAD_REQUESTS = metrics.counter("upload_requests_total", "Piece upload requests", ("result",))
CONCURRENT_UPLOADS = metrics.gauge("upload_concurrency", "In-flight piece uploads")


class _PieceFileResponse(web.FileResponse):
    """FileResponse serving exactly one byte window of the task data file
    via sendfile. The window rides a synthesized Range header injected at
    prepare time (FileResponse reads the REQUEST's Range), and prepare is
    made idempotent — aiohttp's finish_response prepares again after the
    handler returns, and the base class asserts on the second call.

    The transfer happens AFTER the handler returns (aiohttp prepares the
    response in finish_response), so this response owns the store pin and
    the upload-concurrency slot and releases them when the send is done —
    releasing in the handler would let GC rmtree the data file mid-
    sendfile."""

    def __init__(self, path, range_header: str | None, release,
                 content_total: int | None = None):
        super().__init__(path)
        self._df_range = range_header  # None → whole file, plain 200
        self._df_prepared = False
        self._df_release = release
        self._df_total = content_total

    def _df_done(self) -> None:
        release, self._df_release = self._df_release, None
        if release is not None:
            release()

    async def _start(self, request):
        # FileResponse derives Content-Range denominators from the FILE
        # size. While a task is in progress the data file is shorter than
        # the content (only a landed prefix/window exists), so the serve-
        # from-in-progress fast path would advertise a lying complete-
        # length; rewrite the denominator to the task's true content
        # length just before the headers go out.
        total = self._df_total
        cr = self.headers.get("Content-Range")
        if total is not None and total >= 0 and cr and "/" in cr:
            span, _, _ = cr.rpartition("/")
            self.headers["Content-Range"] = f"{span}/{total}"
        return await super()._start(request)

    async def prepare(self, request):
        if self._df_prepared:
            return self._payload_writer
        self._df_prepared = True
        try:
            if self._df_range is None:
                headers = {k: v for k, v in request.headers.items()
                           if k.lower() != "range"}
                return await super().prepare(request.clone(headers=headers))
            cloned = request.clone(headers={**request.headers,
                                            "Range": self._df_range})
            return await super().prepare(cloned)
        finally:
            self._df_done()


class UploadManager:
    def __init__(self, storage: StorageManager, *, rate_limit: int = 0,
                 concurrent_limit: int = 0, ssl_context=None,
                 qos_buckets=None):
        self.storage = storage
        self._ssl = ssl_context   # optional (m)TLS — reference WithTLS/certify
        self._rate_limit = rate_limit
        self.limiter = Limiter(rate_limit if rate_limit > 0 else float("inf"))
        # Tenant QoS plane (dragonfly2_tpu/qos.TenantBuckets): when set,
        # serve admission debits the requesting tenant's bucket instead
        # of the flat daemon limiter, and every served byte lands in
        # peer_upload_bytes_total{tenant}.
        self.qos_buckets = qos_buckets
        self.concurrent_limit = concurrent_limit
        self.concurrent = 0
        # The recorder whose rings the serving side stamps: the process's
        # own unless the owner gives another (daemons sharing a process).
        self.flight = flightlib.recorder()
        self._runner: web.AppRunner | None = None
        self._native_srv: int | None = None
        self._port = 0

    def _native_eligible(self, host: str):
        """The C++ server (native/src/dfupload.cc) serves plaintext HTTP
        only and has no token-bucket limiter: (m)TLS, rate-limited and
        tenant-QoS configs stay on the aiohttp path (per-tenant limiting
        and byte attribution live there). Returns the binding or None."""
        import ipaddress

        if (self._ssl is not None or self._rate_limit > 0
                or self.qos_buckets is not None):
            return None
        try:
            ipaddress.IPv4Address(host)
        except ValueError:
            return None
        from dragonfly2_tpu.storage.local_store import _native

        return _native()

    async def serve(self, host: str, port: int = 0) -> int:
        nb = self._native_eligible(host)
        if nb is not None:
            srv = nb.upload_start(host, port,
                                  concurrent_limit=self.concurrent_limit)
            self._native_srv = srv
            self._port = nb.upload_port(srv)
            # Mirror the piece map into the serving registry: replay what
            # exists (reloaded tasks), then stay current via observer
            # callbacks — requests never consult Python.
            self.storage.set_observer(_NativeServingIndex(nb, srv))
            # The native server's sends reach the flight rings when a ring
            # is read (FlightRecorder.sync), not as they happen: requests
            # never consult Python.
            self.flight.feeders.append(self.drain_serves)
            log.info("upload server up (native)", port=self._port)
            return self._port
        app = web.Application()
        app.router.add_get("/download/{task_prefix}/{task_id}", self._download)
        app.router.add_get("/healthy", self._healthy)
        app.router.add_get("/metrics", self._metrics)
        self._runner = web.AppRunner(app, access_log=None)
        await self._runner.setup()
        site = web.TCPSite(self._runner, host, port, ssl_context=self._ssl)
        await site.start()
        self._port = site._server.sockets[0].getsockname()[1]
        log.info("upload server up", port=self._port, tls=self._ssl is not None)
        return self._port

    @property
    def port(self) -> int:
        return self._port

    def native_counters(self) -> dict | None:
        if self._native_srv is None:
            return None
        from dragonfly2_tpu.storage.local_store import _native

        return _native().upload_counters(self._native_srv)

    def drain_serves(self) -> None:
        """Stamp an ``upload_serve`` for every send the native server has
        finished since the last call, at the time it ended, into the ring
        of its task (a task without a ring here is skipped: the log may
        outlive it)."""
        if self._native_srv is None:
            return
        from dragonfly2_tpu.storage.local_store import _native

        for task_id, piece, nbytes, end_s, send_ms, wait_ms in \
                _native().upload_drain(self._native_srv):
            tf = self.flight.get(task_id)
            if tf is not None:
                tf.record_at(end_s, flightlib.EV_UPLOAD_SERVE, piece, send_ms,
                             flightlib.serve_note(nbytes, wait_ms))

    async def close(self) -> None:
        if self._native_srv is not None:
            from dragonfly2_tpu.storage.local_store import _native

            feeders = self.flight.feeders
            if self.drain_serves in feeders:
                feeders.remove(self.drain_serves)
            srv, self._native_srv = self._native_srv, None
            # Detach + barrier BEFORE the stop frees the handle: observer
            # callbacks arrive from executor threads (piece commits), and a
            # register racing upload_stop would call into freed memory.
            index = self.storage.observer
            self.storage.clear_observer()
            if isinstance(index, _NativeServingIndex):
                # May wait behind an in-flight callback's native call; keep
                # the event loop free.
                await asyncio.to_thread(index.close)
            # stop() joins serving threads; keep the event loop free.
            await asyncio.to_thread(_native().upload_stop, srv)
        if self._runner is not None:
            await self._runner.cleanup()

    # -- handlers ----------------------------------------------------------

    async def _download(self, request: web.Request) -> web.StreamResponse:
        # Adopt the requester's trace context from the piece HTTP hop
        # (piece_downloader injects it): the serving span joins the SAME
        # trace, so a pod download is one trace, not N disconnected ones.
        tp = request.headers.get(tracing.TRACEPARENT, "")
        with tracing.extract({tracing.TRACEPARENT: tp} if tp else None,
                             "upload.serve") as sp:
            return await self._download_traced(request, sp)

    async def _download_traced(self, request: web.Request,
                               sp) -> web.StreamResponse:
        task_id = request.match_info["task_id"]
        sp.set_attr("task", task_id[:16])
        store = self.storage.try_get(task_id)
        if store is None:
            UPLOAD_REQUESTS.labels("not_found").inc()
            raise web.HTTPNotFound(text=f"task {task_id} not found")
        if self.concurrent_limit and self.concurrent >= self.concurrent_limit:
            UPLOAD_REQUESTS.labels("throttled").inc()
            raise web.HTTPTooManyRequests()

        self.concurrent += 1
        CONCURRENT_UPLOADS.inc()
        store.pin()
        released = False
        t_asked = time.perf_counter()
        sending = None   # (piece, bytes, perf_counter at the send's start)

        def release() -> None:
            nonlocal released
            if not released:
                released = True
                store.unpin()
                self.concurrent -= 1
                CONCURRENT_UPLOADS.dec()
                if sending is not None:
                    # Serving-side flight event: the parent's own timeline
                    # records which pieces it handed out and how long each
                    # send took (pod autopsies correlate a child's stall
                    # against the parent's serve log; the union of the
                    # sends is the parent's busy time). The wait is the
                    # tenant bucket's and the rate limiter's.
                    piece, nbytes, t_send = sending
                    wait_ms = (t_send - t_asked) * 1000.0
                    self.flight.task(task_id).record(
                        flightlib.EV_UPLOAD_SERVE, piece,
                        (time.perf_counter() - t_send) * 1000.0,
                        flightlib.serve_note(nbytes, wait_ms))

        try:
            piece_num = request.query.get("pieceNum")
            if piece_num is not None:
                try:
                    rec = store.metadata.pieces.get(int(piece_num))
                except ValueError:
                    UPLOAD_REQUESTS.labels("bad_request").inc()
                    raise web.HTTPBadRequest(
                        text=f"bad pieceNum {piece_num!r}")
                if rec is None:
                    UPLOAD_REQUESTS.labels("piece_missing").inc()
                    raise web.HTTPNotFound(text=f"piece {piece_num} not found")
                start, length = rec.offset, rec.size
            else:
                rng_header = request.headers.get("Range")
                if not rng_header:
                    UPLOAD_REQUESTS.labels("bad_request").inc()
                    raise web.HTTPBadRequest(text="Range or pieceNum required")
                try:
                    rng = Range.parse_http(rng_header, store.metadata.content_length)
                except ValueError as e:
                    UPLOAD_REQUESTS.labels("bad_request").inc()
                    raise web.HTTPBadRequest(text=str(e))
                if not store.covers_range(rng.start, rng.length):
                    UPLOAD_REQUESTS.labels("piece_missing").inc()
                    raise web.HTTPRequestRangeNotSatisfiable()
                start, length = rng.start, rng.length
            if self.qos_buckets is not None:
                # Per-tenant serve admission: the tenant's split of the
                # daemon cap, plus byte attribution. The flat limiter
                # still applies as the aggregate ceiling.
                await self.qos_buckets.wait(
                    request.query.get("tenant", ""), length)
            await self.limiter.wait(length)
            UPLOAD_BYTES.inc(length)
            UPLOAD_REQUESTS.labels("ok").inc()
            sp.set_attr("bytes", length)
            sending = (int(piece_num) if piece_num is not None else -1,
                       length, time.perf_counter())
            # sendfile the byte range straight from the page cache: no
            # pread into Python bytes and no user→kernel copy in sendmsg
            # on the serving side.
            # Pin + slot transfer to the response (released after the send).
            # content_total keeps Content-Range honest while the store is
            # still mid-download (in-progress pieces serve the same way).
            return _PieceFileResponse(
                store.data_path, f"bytes={start}-{start + length - 1}",
                release, content_total=store.metadata.content_length)
        except BaseException:
            release()
            raise

    async def _healthy(self, request: web.Request) -> web.Response:
        return web.Response(text="ok")

    async def _metrics(self, request: web.Request) -> web.Response:
        body, ctype = metrics.render()
        return web.Response(body=body, content_type=ctype.split(";")[0])


class _NativeServingIndex:
    """StorageManager observer mirroring task/piece state into the native
    upload server's registry. Pure ctypes calls guarded by the C side's
    mutex — safe from any thread (piece commits arrive from workers).

    The close() barrier upholds the binding layer's handle-ownership
    contract: callbacks may arrive from executor threads right up to
    teardown, so every native call holds a lock that close() takes before
    upload_stop frees the server — after close() returns, no callback can
    touch the dead handle (it sees _closed and returns)."""

    def __init__(self, nb, srv: int):
        self._nb = nb
        self._srv = srv
        self._mu = threading.Lock()
        self._closed = False

    def task_updated(self, store) -> None:
        m = store.metadata
        with self._mu:
            if self._closed:
                return
            self._nb.upload_register_task(self._srv, m.task_id,
                                          store.data_path,
                                          m.content_length, m.piece_size)

    def piece_recorded(self, task_id: str, rec) -> None:
        with self._mu:
            if self._closed:
                return
            self._nb.upload_register_piece(self._srv, task_id, rec.num,
                                           rec.offset, rec.size)

    def task_deleted(self, task_id: str) -> None:
        with self._mu:
            if self._closed:
                return
            self._nb.upload_unregister_task(self._srv, task_id)

    def close(self) -> None:
        """After this returns, no further native call will be made; any
        in-flight callback has completed."""
        with self._mu:
            self._closed = True
