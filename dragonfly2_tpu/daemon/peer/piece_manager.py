"""Piece manager: origin (back-to-source) piece pipeline.

Reference: client/daemon/peer/piece_manager.go — DownloadSource (:304),
known-length sequential (:481), unknown-length streaming (:539), concurrent
back-to-source by piece group with byte ranges (:796-1000, pieceGroup
:876-922), optional digest computation (WithCalculateDigest :91), file
import for dfcache (ImportFile :662). Parent-peer piece downloads live in
piece_downloader.py; this module owns origin fetches and storage writes.
"""

from __future__ import annotations

import asyncio
import os
import time
from dataclasses import dataclass
from typing import Awaitable, Callable

from dragonfly2_tpu.daemon.peer.piece_downloader import (
    abandonable_native_call,
    native_connect,
)
from dragonfly2_tpu.pkg import dflog
from dragonfly2_tpu.pkg import digest as pkgdigest
from dragonfly2_tpu.pkg import flight as flightlib
from dragonfly2_tpu.pkg import retry as retrylib
from dragonfly2_tpu.pkg.errors import Code, DfError, SourceError
from dragonfly2_tpu.pkg.piece import Range, compute_piece_count, compute_piece_size
from dragonfly2_tpu.pkg.ratelimit import Limiter
from dragonfly2_tpu.pkg.wordsum import checksum_numpy
from dragonfly2_tpu.source import Request as SourceRequest
from dragonfly2_tpu.source import get_client
from dragonfly2_tpu.storage.local_store import LocalTaskStore, PieceRecord, _native

log = dflog.get("peer.piece_manager")

# An import from buffers (``import_pieces``): pieces a fetched group holds,
# and groups fetched and not yet committed (the host's memory: that many
# groups and the one being fetched).
_GROUP_PIECES = 4
_GROUPS_IN_FLIGHT = 2

# piece arrival callback: fired after each piece lands in storage, with the
# record and the store (conductor reports to scheduler + notifies subscribers)
PieceCallback = Callable[[LocalTaskStore, PieceRecord], Awaitable[None]]


@dataclass
class PieceManagerOption:
    concurrency: int = 4                  # concurrent range streams to origin
    compute_digest: bool = True           # per-piece md5 during write
    concurrent_min_length: int = 32 << 20 # below this, a single stream wins
    chunk_size: int = 1 << 20
    # Origin fetch retry budget: attempts for TEMPORARY failures only
    # (connect resets, 5xx, short reads). Permanent client errors
    # (403/404/416 — SourceError.temporary=False) fail on the first try:
    # re-asking the origin for a URL it authoritatively rejected can never
    # succeed, it only delays the task's failure verdict.
    origin_attempts: int = 3
    # Origin body chunk-gap watchdog (pkg/retry.watch_idle): bounds the
    # silence between chunks so a stalled origin trips in bounded time
    # instead of at the 300s request deadline. <= 0 disables.
    origin_idle_timeout: float = 60.0


class PieceManager:
    def __init__(self, opt: PieceManagerOption | None = None, limiter: Limiter | None = None):
        self.opt = opt or PieceManagerOption()
        self._limiter = limiter or Limiter()

    # -- origin download entry (reference piece_manager.go:304) ------------

    async def download_source(
        self,
        store: LocalTaskStore,
        url: str,
        header: dict[str, str] | None = None,
        *,
        content_range: Range | None = None,
        on_piece: PieceCallback | None = None,
        limiter: Limiter | None = None,
    ) -> None:
        """Fetch the full content from origin into ``store``. Decides between
        sequential, concurrent-range-group and unknown-length paths."""
        client = get_client(url)
        header = dict(header or {})
        header.pop("Range", None)
        request = SourceRequest(url, header)
        limiter = limiter or self._limiter

        content_length = store.metadata.content_length
        # A ranged store that has its length has the SLICE's (a local import
        # that failed part-way, a run before this one): not the object's
        # total to clamp the range against.
        slice_known = content_range is not None and content_length >= 0
        range_known: bool | None = None
        if content_length < 0:
            try:
                content_length, range_known = await client.probe(request)
            except SourceError:
                content_length = -1
        if content_range is not None and not slice_known:
            # Ranged task: treat the range as the content.
            total = content_length if content_length >= 0 else -1
            if total >= 0:
                if content_range.start >= total:
                    raise SourceError(f"range start {content_range.start} beyond length {total}",
                                      Code.BadRequest)
                length = min(content_range.length, total - content_range.start) \
                    if content_range.length >= 0 else total - content_range.start
            else:
                length = content_range.length
            content_length = length

        if content_length is not None and content_length >= 0:
            piece_size = store.metadata.piece_size or compute_piece_size(content_length)
            total_pieces = compute_piece_count(content_length, piece_size)
            store.update_task(content_length=content_length, piece_size=piece_size,
                              total_piece_count=total_pieces)
            support_range = False
            if content_length >= self.opt.concurrent_min_length and self.opt.concurrency > 1:
                if range_known is not None:
                    support_range = range_known  # answered by the same probe
                else:
                    try:
                        support_range = await client.is_support_range(request)
                    except SourceError:
                        support_range = False
            if support_range:
                fetch = lambda: self._download_known_length_concurrent(  # noqa: E731
                    store, client, request, content_range, on_piece, limiter)
            else:
                fetch = lambda: self._download_streaming(  # noqa: E731
                    store, client, request, content_range, on_piece, limiter,
                    known_length=content_length)
        else:
            if store.metadata.piece_size <= 0:
                store.update_task(piece_size=compute_piece_size(-1))
            fetch = lambda: self._download_streaming(  # noqa: E731
                store, client, request, content_range, on_piece, limiter,
                known_length=-1)

        # Origin retry rides the ONE policy module (capped exponential,
        # full jitter) and retries TEMPORARY failures only: a 5xx burst or
        # a dropped stream earns another attempt (landed pieces are
        # skipped on resume), a permanent 403/404/416 fails immediately.
        await retrylib.run(
            fetch, policy=retrylib.SOURCE,
            max_attempts=max(1, self.opt.origin_attempts),
            retryable=lambda e: isinstance(e, SourceError) and e.temporary)

        if not store.is_complete():
            raise SourceError(
                f"source download incomplete: {len(store.metadata.pieces)}/"
                f"{store.metadata.total_piece_count} pieces", Code.BackToSourceAborted)

    @staticmethod
    def _stamp_first_byte(store: LocalTaskStore, piece: int, issued: float,
                          note: str = "") -> None:
        """One origin request's wait for its first body byte, on the flight
        of the task that pulls: ``issued`` is ``time.monotonic()`` as the
        request went out (connect included), ``piece`` the first piece the
        request covers."""
        flightlib.for_task(store.metadata.task_id).record(
            flightlib.EV_SOURCE_FIRST_BYTE, piece,
            (time.monotonic() - issued) * 1000.0, note)

    # -- native-engine span fetch (no Python byte handling) ----------------

    @staticmethod
    def _span_status_error(client, status: int, req: SourceRequest) -> SourceError:
        mapper = getattr(client, "status_error", None)
        if mapper is not None:
            return mapper(status, req.url)
        return SourceError(f"origin {status}: {req.url}", Code.BackToSourceAborted,
                           temporary=status in (408, 429, 500, 502, 503, 504))

    async def _native_fetch_span(
        self,
        store: LocalTaskStore,
        client,
        req: SourceRequest,
        first: int,
        last: int,
        byte_len: int,
        on_piece: PieceCallback | None,
        limiter: Limiter,
        *,
        ranged: bool,
    ) -> bool:
        """Fetch pieces [first, last) over one native-engine connection:
        the body streams socket→crc32c→pwrite (native/src/dfhttp.cc) and
        Python sees only per-piece records. Returns False when ineligible
        (https, no native lib, client without a plan) so the caller falls
        back to the aiohttp path; raises coded SourceErrors on failures,
        matching the Python path's semantics."""
        nb = _native()
        plan_fn = getattr(client, "native_fetch_plan", None)
        if nb is None or plan_fn is None:
            return False
        plan = plan_fn(req)
        if plan is None:
            return False
        host, port, head = plan
        m = store.metadata
        issued = time.monotonic()
        try:
            h = await native_connect(nb, host, port, 60000)
        except nb.NativeHttpError:
            return False  # let the aiohttp path produce its own coded error
        dup_fd = os.dup(store.data_fd())
        abandoned = False

        def cleanup() -> None:
            nb.http_close(h)
            os.close(dup_fd)

        async def ncall(fn, *args):
            nonlocal abandoned
            try:
                return await abandonable_native_call(fn, *args,
                                                     on_abandon=cleanup)
            except asyncio.CancelledError:
                abandoned = True  # the worker thread now owns cleanup()
                raise

        try:
            try:
                status, clen, _keep = await ncall(nb.http_start, h, head)
            except nb.NativeHttpError:
                # Start-phase failure (chunked origin, odd framing, stalled
                # connect): no body consumed, nothing recorded — let the
                # aiohttp path take over and produce its own coded errors.
                return False
            if 300 <= status < 400:
                # aiohttp follows redirects (CDN/presigned handoffs); the
                # native engine doesn't — hand the request back to it.
                return False
            if ranged and status == 200:
                raise SourceError("origin ignored range request",
                                  Code.SourceRangeUnsupported, temporary=True)
            if status != (206 if ranged else 200):
                raise self._span_status_error(client, status, req)
            if clen < 0:
                # Identity body without Content-Length (read-until-close):
                # only the streaming Python path can delimit it.
                return False
            if clen != byte_len:
                raise SourceError(
                    f"origin returned {clen} bytes, expected {byte_len}",
                    Code.BackToSourceAborted, temporary=True)
            for num in range(first, last):
                take = min(m.piece_size, m.content_length - num * m.piece_size)
                await limiter.wait(take)
                t0 = time.monotonic()
                if store.has_piece(num):
                    # Resume overlap: the bytes still arrive on this stream;
                    # drain without touching the already-verified piece.
                    await ncall(nb.http_read_to_file, h, -1, 0, take)
                    crc = None
                else:
                    crc = await ncall(nb.http_read_to_file, h, dup_fd,
                                      num * m.piece_size, take)
                if num == first:
                    # The engine hands back whole pieces: the first one's
                    # arrival stands in for the first body byte.
                    self._stamp_first_byte(store, first, issued, "native")
                if crc is None:
                    continue
                # Off-loop: record_piece's batched metadata save serializes
                # the whole piece map — a loop stall if run inline.
                cost_ms = int((time.monotonic() - t0) * 1000)
                rec = await asyncio.to_thread(
                    store.record_piece, num, take, crc, cost_ms)
                # Float ms for the recorder: sub-ms loopback pieces must
                # not collapse to a zero-length origin interval.
                flightlib.for_task(m.task_id).record(
                    flightlib.EV_SOURCE_LANDED, num,
                    (time.monotonic() - t0) * 1000.0)
                if on_piece is not None:
                    await on_piece(store, rec)
            return True
        except nb.NativeHttpError as e:
            raise SourceError(f"origin {host}:{port} native fetch: {e}",
                              Code.BackToSourceAborted, temporary=True)
        finally:
            if not abandoned:
                cleanup()

    # -- sequential / unknown-length (reference :481,:539) -----------------

    async def _download_streaming(
        self,
        store: LocalTaskStore,
        client,
        request: SourceRequest,
        content_range: Range | None,
        on_piece: PieceCallback | None,
        limiter: Limiter,
        known_length: int,
    ) -> None:
        req = request
        if content_range is not None:
            req = request.with_range(content_range.to_http())
        if (known_length >= 0 and store.metadata.total_piece_count >= 0
                and await self._native_fetch_span(
                    store, client, req, 0, store.metadata.total_piece_count,
                    known_length, on_piece, limiter,
                    ranged=content_range is not None)):
            return
        issued = time.monotonic()
        resp = await client.download(req)
        piece_size = store.metadata.piece_size
        num = 0
        total = 0
        # Zero-copy carve: piece boundaries are memoryview windows over the
        # wire chunks exactly as they arrived — no assembly bytearray, no
        # bytes() copy, no O(piece) del-memmove. The store lands each
        # window list with the per-piece digest FUSED into the write
        # (write_piece_chunks: seeded crc while pwriting — one memory walk
        # for hash+write; digest_reader.go single-pass parity).
        views: list[memoryview] = []
        filled = 0
        start = time.monotonic()
        # Depth-1 landing pipeline: piece N's write+digest runs in a worker
        # thread (GIL released in the native crc+pwrite and the sha feed)
        # WHILE the loop receives piece N+1's chunks — wall becomes
        # max(receive, hash+write) instead of their sum on a busy core.
        # Exactly one landing is in flight, awaited before the next
        # launches, so commits (and the prefix-hasher's in-memory frontier
        # feed) stay in piece order.
        pending: "asyncio.Future | None" = None
        body = retrylib.watch_idle(resp.body, self.opt.origin_idle_timeout,
                                   what=f"origin {request.url[:96]}")
        try:
            try:
                async for chunk in body:
                    if not total:
                        self._stamp_first_byte(store, 0, issued)
                    total += len(chunk)
                    cv = memoryview(chunk)
                    while len(cv):
                        take = min(piece_size - filled, len(cv))
                        views.append(cv[:take])
                        cv = cv[take:]
                        filled += take
                        if filled == piece_size:
                            if pending is not None:
                                await pending
                            pending = asyncio.ensure_future(
                                self._land_piece_chunks(
                                    store, num, views, piece_size,
                                    on_piece, limiter, start))
                            num += 1
                            views, filled = [], 0
                            start = time.monotonic()
                if pending is not None:
                    await pending
                    pending = None
            except BaseException:
                if pending is not None:
                    pending.cancel()
                    await asyncio.gather(pending, return_exceptions=True)
                raise
        except retrylib.ProgressTimeout as e:
            # Stalled origin (slow-loris): temporary — the retry policy
            # may try again; landed pieces are skipped on resume.
            raise SourceError(str(e), Code.BackToSourceAborted,
                              temporary=True)
        finally:
            await resp.close()
        # Length check BEFORE the trailing partial piece lands: a dropped
        # connection must never persist a truncated piece in metadata.
        if known_length >= 0 and total != known_length:
            raise SourceError(f"origin returned {total} bytes, expected {known_length}",
                              Code.BackToSourceAborted, temporary=True)
        if views:
            await self._land_piece_chunks(
                store, num, views, filled, on_piece, limiter, start)
            num += 1
        if known_length < 0:
            # Learned the length at EOF (reference downloadUnknownLengthSource
            # finishes by updating task metadata).
            store.update_task(content_length=total, total_piece_count=num)

    # -- concurrent piece groups (reference :796-1000) ---------------------

    async def _download_known_length_concurrent(
        self,
        store: LocalTaskStore,
        client,
        request: SourceRequest,
        content_range: Range | None,
        on_piece: PieceCallback | None,
        limiter: Limiter,
    ) -> None:
        m = store.metadata
        total_pieces = m.total_piece_count
        # Resume: never re-fetch the contiguous landed prefix (reference
        # continuePieceNum, piece_manager.go:804-815 — groups start at the
        # first missing piece; mid-range holes still stream-and-drain
        # inside their group, matching the reference).
        continue_piece = 0
        while continue_piece < total_pieces and store.has_piece(continue_piece):
            continue_piece += 1
        to_download = total_pieces - continue_piece
        if to_download <= 0:
            return
        concurrency = min(self.opt.concurrency, to_download)
        # Contiguous piece groups (reference pieceGroup :876-922): group g
        # covers pieces [g*per + min(g, rem) ... ), sizes differ by ≤1.
        per, rem = divmod(to_download, concurrency)
        groups: list[tuple[int, int]] = []
        start_piece = continue_piece
        for g in range(concurrency):
            count = per + (1 if g < rem else 0)
            groups.append((start_piece, start_piece + count))
            start_piece += count

        base_offset = content_range.start if content_range is not None else 0

        async def fetch_group(first: int, last: int) -> None:
            byte_start = base_offset + first * m.piece_size
            byte_len = min(last * m.piece_size, m.content_length) - first * m.piece_size
            req = request.with_range(Range(byte_start, byte_len).to_http())
            if await self._native_fetch_span(store, client, req, first, last,
                                             byte_len, on_piece, limiter,
                                             ranged=True):
                return
            issued = time.monotonic()
            resp = await client.download(req)
            if resp.status != 206:
                await resp.close()
                raise SourceError("origin ignored range request",
                                  Code.SourceRangeUnsupported, temporary=True)
            num = first
            got = 0
            # Same zero-copy carve as the sequential path; the group's
            # LAST piece accumulates to EOF (its size is the range
            # remainder) and lands only after the length check below.
            views: list[memoryview] = []
            filled = 0
            t0 = time.monotonic()
            # Depth-1 landing pipeline per group (see _download_streaming).
            pending: "asyncio.Future | None" = None
            body = retrylib.watch_idle(
                resp.body, self.opt.origin_idle_timeout,
                what=f"origin group [{first},{last}) {request.url[:96]}")
            try:
                try:
                    async for chunk in body:
                        if not got:
                            self._stamp_first_byte(store, first, issued)
                        got += len(chunk)
                        cv = memoryview(chunk)
                        while len(cv):
                            if num >= last - 1:
                                views.append(cv)
                                filled += len(cv)
                                break
                            take = min(m.piece_size - filled, len(cv))
                            views.append(cv[:take])
                            cv = cv[take:]
                            filled += take
                            if filled == m.piece_size:
                                if pending is not None:
                                    await pending
                                pending = asyncio.ensure_future(
                                    self._land_piece_chunks(
                                        store, num, views, m.piece_size,
                                        on_piece, limiter, t0))
                                num += 1
                                views, filled = [], 0
                                t0 = time.monotonic()
                    if pending is not None:
                        await pending
                        pending = None
                except BaseException:
                    if pending is not None:
                        pending.cancel()
                        await asyncio.gather(pending, return_exceptions=True)
                    raise
            except retrylib.ProgressTimeout as e:
                raise SourceError(str(e), Code.BackToSourceAborted,
                                  temporary=True)
            finally:
                await resp.close()
            # Length check first — a short stream must not persist its
            # trailing buffer as a (truncated) piece.
            if got != byte_len:
                raise SourceError(f"group [{first},{last}) got {got} bytes, want {byte_len}",
                                  Code.BackToSourceAborted, temporary=True)
            if views:
                await self._land_piece_chunks(
                    store, num, views, filled, on_piece, limiter, t0)
                num += 1

        results = await asyncio.gather(
            *(fetch_group(f, l) for f, l in groups), return_exceptions=True
        )
        errors = [r for r in results if isinstance(r, BaseException)]
        if errors:
            raise errors[0]

    # -- shared piece writer -----------------------------------------------

    async def _land_piece_chunks(
        self,
        store: LocalTaskStore,
        num: int,
        views: list,
        size: int,
        on_piece: PieceCallback | None,
        limiter: Limiter,
        started_at: float,
    ) -> None:
        """Land a carved piece: one write_piece_chunks call (digest fused
        into the write) — off-loop, because it still blocks on disk."""
        await limiter.wait(size)
        cost_ms = int((time.monotonic() - started_at) * 1000)
        if store.has_piece(num):
            return   # resume overlap: bytes already verified on disk
        rec = await asyncio.to_thread(
            store.write_piece_chunks, num, views, cost_ms=cost_ms)
        # Float ms (receive + write): sub-ms loopback pieces must not
        # collapse to a zero-length origin interval in the analyzer.
        flightlib.for_task(store.metadata.task_id).record(
            flightlib.EV_SOURCE_LANDED, num,
            (time.monotonic() - started_at) * 1000.0)
        if on_piece is not None:
            await on_piece(store, rec)

    async def _write_piece(
        self,
        store: LocalTaskStore,
        num: int,
        data: bytes,
        on_piece: PieceCallback | None,
        limiter: Limiter,
        started_at: float,
    ) -> None:
        await limiter.wait(len(data))
        cost_ms = int((time.monotonic() - started_at) * 1000)
        if store.has_piece(num):
            return
        # Thread offload: the fused crc+pwrite releases the GIL; writing
        # inline would block the loop (and upload serving) per 4 MiB piece.
        if self.opt.compute_digest:
            rec = await asyncio.to_thread(store.write_piece, num, data,
                                          cost_ms=cost_ms)
        else:
            rec = await asyncio.to_thread(store.write_piece, num, data,
                                          expected_digest="", cost_ms=cost_ms)
        if on_piece is not None:
            await on_piece(store, rec)

    # -- file import for dfcache (reference :662 ImportFile) ---------------

    async def import_file(self, store: LocalTaskStore, path: str,
                          on_piece: PieceCallback | None = None) -> None:
        import os

        from dragonfly2_tpu.storage.local_store import (
            acquire_read_buffer,
            release_read_buffer,
        )

        size = os.path.getsize(path)
        piece_size = store.metadata.piece_size or compute_piece_size(size)
        total = compute_piece_count(size, piece_size)
        store.update_task(content_length=size, piece_size=piece_size, total_piece_count=total)
        # One pooled buffer for the whole import (pieces land sequentially,
        # the write digests+lands from the view before the next readinto).
        buf = acquire_read_buffer(piece_size)
        try:
            with open(path, "rb") as f:
                for num in range(total):
                    n = f.readinto(buf)
                    t0 = time.monotonic()
                    await self._write_piece(store, num, buf[:n], on_piece,
                                            self._limiter, t0)
        finally:
            release_read_buffer(buf)

    # -- import from buffers (client/device.py save_from_device) -----------

    async def import_pieces(self, store: LocalTaskStore, source,
                            stamp=None,
                            on_piece: PieceCallback | None = None) -> str:
        """Import content that lies in memory and not in a file: ``source``
        has ``content_length``, ``piece_size``, ``fetch(first, count)`` (a
        blocking call, run on a thread: the bytes of pieces [first, first +
        count) as one buffer, the last piece cut to the content) and
        ``sums``, piece -> the (sum32, xor32) the producer took of it, or
        None; where it has ``prefetch(first, count)`` the next group is
        asked for while this one is waited for. Returns the content's
        ``sha256:`` digest.

        A group is fetched on one thread, one group after the other, at most
        ``_GROUPS_IN_FLIGHT`` fetched and not yet committed. Each of its
        pieces is committed on a worker thread of its own: ``checksum_numpy``
        of exactly the bytes handed to ``write_piece``, held equal to
        ``source.sums`` (a mismatch fails the import: what is stored is what
        the producer held), the piece digest fused into the write, the pair
        kept with the piece (``word_sums``). The whole-content sha256 follows
        the pieces on ONE thread of its own, in piece order, over the same
        memory the workers write from: nothing is read back. ``stamp(code,
        piece, ms, note)`` gets save_d2h, save_commit and save_digest;
        ``on_piece(store, rec)`` is awaited on the loop with each piece as
        its commit returns, as ``download_source``'s is: the piece can be
        served from then on."""
        import hashlib
        import queue
        import threading
        from concurrent.futures import Future

        length, piece_size = source.content_length, source.piece_size
        total = compute_piece_count(length, piece_size)
        store.update_task(content_length=length, piece_size=piece_size,
                          total_piece_count=total)
        stamp = stamp or (lambda *a: None)
        hasher = hashlib.sha256()
        hashing: "queue.SimpleQueue" = queue.SimpleQueue()

        def digest_loop() -> None:
            while True:
                item = hashing.get()
                if item is None:
                    return
                first, views, done = item
                t0 = time.perf_counter()
                try:
                    for view in views:
                        hasher.update(view)
                    stamp(flightlib.EV_SAVE_DIGEST, first,
                          (time.perf_counter() - t0) * 1000.0,
                          str(sum(len(v) for v in views)))
                    done.set_result(None)
                except BaseException as e:  # noqa: BLE001 - the job raises it
                    done.set_exception(e)

        def commit(num: int, view):
            t0 = time.perf_counter()
            sums = checksum_numpy(view)
            want = source.sums.get(num) if source.sums else None
            if want is not None and tuple(want) != sums:
                raise DfError(
                    Code.ClientPieceDownloadFail,
                    f"piece {num}: the host's (sum, xor) {sums} differ from "
                    f"the producer's {tuple(want)}")
            rec = store.write_piece(num, view, word_sums=sums)
            stamp(flightlib.EV_SAVE_COMMIT, num,
                  (time.perf_counter() - t0) * 1000.0, str(len(view)))
            return rec

        async def commit_piece(num: int, view) -> None:
            rec = await asyncio.to_thread(commit, num, view)
            if on_piece is not None:
                await on_piece(store, rec)

        async def commit_group(first: int, buf) -> None:
            views = [buf[i:i + piece_size]
                     for i in range(0, len(buf), piece_size)]
            hashed: Future = Future()
            hashing.put((first, views, hashed))
            try:
                await asyncio.gather(*(
                    commit_piece(first + i, view)
                    for i, view in enumerate(views)))
                await asyncio.wrap_future(hashed)
            finally:
                in_flight.release()

        groups = [(first, min(_GROUP_PIECES, total - first))
                  for first in range(0, total, _GROUP_PIECES)]
        in_flight = asyncio.Semaphore(_GROUPS_IN_FLIGHT)
        thread = threading.Thread(
            target=digest_loop, daemon=True,
            name=f"df-save-digest-{store.metadata.task_id[:12]}")
        thread.start()
        jobs: list = []
        try:
            for i, (first, count) in enumerate(groups):
                await in_flight.acquire()
                failed = [j for j in jobs if j.done() and j.exception()]
                if failed:
                    raise failed[0].exception()
                t0 = time.perf_counter()
                if i + 1 < len(groups) and hasattr(source, "prefetch"):
                    source.prefetch(*groups[i + 1])
                buf = await asyncio.to_thread(source.fetch, first, count)
                stamp(flightlib.EV_SAVE_D2H, first,
                      (time.perf_counter() - t0) * 1000.0, str(len(buf)))
                jobs.append(asyncio.ensure_future(commit_group(first, buf)))
                del buf
            await asyncio.gather(*jobs)
        except BaseException:
            for job in jobs:
                job.cancel()
            await asyncio.gather(*jobs, return_exceptions=True)
            raise
        finally:
            hashing.put(None)
        await asyncio.to_thread(thread.join)
        return "sha256:" + hasher.hexdigest()

    # -- whole-content digest ----------------------------------------------

    @staticmethod
    def validate_content(store: LocalTaskStore, expected_digest: str = "") -> str:
        return store.validate_digest(expected_digest)
