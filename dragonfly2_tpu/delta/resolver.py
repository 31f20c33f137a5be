"""Delta resolver: land version N+1 by copying version N locally.

Given a locally-landed base task (version N) and version N+1's chunk
manifest, partition N+1's chunks into *reused* (same sha256 present
anywhere in the base — copied out of the base store through the pooled
read engine, digest verified DURING the copy) and *fetched* (pulled as
ranged P2P tasks, one per coalesced span, byte-identical task ids across
every host running the same delta so the fabric dedups per span). The
patched result lands as a completely normal task: piece-structured
store, verified end digest, announced to the scheduler, served to other
peers, resumable (already-landed pieces are skipped on retry).

Manifests travel over the fabric itself: ``dfdelta://<content_task_id>``
is a tiny P2P task (keyed by the content's task id) that any host
holding the full content can build and publish — the first host to land
a version cold publishes its manifest, every later host deltas.

Accounting invariant (pinned by bench + e2e):
``peer_delta_bytes_total{kind=reused} + {kind=fetched}`` over one task
equals the content length EXACTLY — every byte is attributed to exactly
one transfer class, and a corrupt base chunk re-fetches under
``fetched`` (plus a ``corrupt_base`` chunk count), never double-books.
"""

from __future__ import annotations

import asyncio
import hashlib
import os
import time
from contextlib import aclosing
from dataclasses import dataclass, field

from dragonfly2_tpu.delta.chunker import CDCParams, Chunk
from dragonfly2_tpu.delta.manifest import (
    MANIFEST_FETCHES,
    DeltaManifest,
    ManifestError,
    manifest_from_store,
)
from dragonfly2_tpu.pkg import dflog, metrics
from dragonfly2_tpu.pkg import flight as flightlib
from dragonfly2_tpu.pkg.errors import Code, DfError, StorageError, describe
from dragonfly2_tpu.pkg.piece import compute_piece_count, compute_piece_size
from dragonfly2_tpu.pkg.wordsum import checksum_numpy
from dragonfly2_tpu.storage.local_store import (
    LocalTaskStore,
    acquire_read_buffer,
    release_read_buffer,
)

log = dflog.get("delta.resolver")

# The accounting yardstick: every content byte of a delta task lands as
# exactly one of these.
DELTA_BYTES = metrics.counter(
    "peer_delta_bytes_total",
    "Delta-task content bytes by transfer class (reused = copied from "
    "the local base version, fetched = pulled as ranged P2P tasks); the "
    "two sum exactly to the task's content length", ("kind",))
DELTA_CHUNKS = metrics.counter(
    "peer_delta_chunks_total",
    "Delta-task chunks by resolution (corrupt_base = base copy failed "
    "its digest during the copy and was transparently re-fetched)",
    ("result",))
DELTA_PIECES = metrics.counter(
    "peer_delta_pieces_total",
    "Delta-task target pieces by how they landed (built = a piece job read, "
    "verified and wrote the piece; resumed = the store already had it and "
    "the job was skipped whole)", ("how",))

# Piece jobs of one landing in flight at once, each on a worker thread of
# the loop's default executor. The smallest number on the flat of
# benchmarks/delta_probe.py on the chip's host (13 vCPUs; 55 pieces of
# 32 MiB, 8.4 % fetched; a landing request -> done, median of 3): 1 in
# flight 3.86 s, 2 1.61, 4 1.42, 6 1.42, 8 1.40. From 4 up the landing is
# as long as the whole-object sha256 that follows its pieces on one thread
# (PERF.md section 5, "The delta landing, alone").
_JOBS_IN_FLIGHT = 4

# URL scheme of fabric-published manifests: task id of the manifest task
# is a pure function of the CONTENT task id, so every host resolves the
# same manifest task without origin cooperation.
MANIFEST_SCHEME = "dfdelta"
MANIFEST_TAG = "dfdelta-manifest"


def manifest_url(content_task_id: str) -> str:
    return f"{MANIFEST_SCHEME}://{content_task_id}"


@dataclass
class DeltaPlan:
    """Partition of the new version's chunks against a base manifest."""

    reused: list[tuple[Chunk, Chunk]] = field(default_factory=list)  # (new, base)
    fetched: list[Chunk] = field(default_factory=list)

    @property
    def reused_bytes(self) -> int:
        return sum(c.length for c, _ in self.reused)

    @property
    def fetched_bytes(self) -> int:
        return sum(c.length for c in self.fetched)

    def fetch_spans(self) -> list[tuple[int, int]]:
        """ADJACENT fetched chunks coalesced into ranged-task spans.
        Only zero-gap merges: a gap byte is a reused byte, and reused
        bytes must never ride the wire (the accounting invariant)."""
        spans: list[list[int]] = []
        for c in self.fetched:
            if spans and c.offset == spans[-1][1]:
                spans[-1][1] = c.end
            else:
                spans.append([c.offset, c.end])
        return [(s, e) for s, e in spans]


def plan_delta(new_m: DeltaManifest, base_m: DeltaManifest) -> DeltaPlan:
    """Chunk-level dedup: a new chunk whose (sha256, length) appears
    anywhere in the base is reused from there; everything else is
    fetched. Pure function — both manifests must share chunking params
    (callers rebuild the base manifest otherwise)."""
    if new_m.params != base_m.params:
        raise ManifestError(
            f"chunking params differ: {new_m.params} vs {base_m.params}")
    base_map = base_m.digest_map()
    plan = DeltaPlan()
    for c in new_m.chunks:
        b = base_map.get(c.sha256)
        if b is not None and b.length == c.length:
            plan.reused.append((c, b))
        else:
            plan.fetched.append(c)
    return plan


# ------------------------------------------------------------------ #
# Fabric-published manifests (dfdelta:// tasks)
# ------------------------------------------------------------------ #

def _manifest_request(content_task_id: str):
    from dragonfly2_tpu.daemon.peer.task_manager import FileTaskRequest
    from dragonfly2_tpu.proto.common import UrlMeta

    return FileTaskRequest(
        url=manifest_url(content_task_id), output="",
        meta=UrlMeta(tag=MANIFEST_TAG),
        # dfdelta:// has no origin; the manifest either exists in the
        # fabric or it doesn't.
        disable_back_source=True)


async def fetch_manifest(tm, content_task_id: str,
                         timeout: float = 8.0) -> DeltaManifest | None:
    """Pull the fabric-published manifest for a content task id; None
    when no host has published one (callers fall back to a full
    download, after which they publish it themselves). The timeout is
    deliberately short: an unpublished manifest costs the scheduler's
    full no-source patience before failing, and every miss has a cheap
    recovery (build locally / plain download)."""
    req = _manifest_request(content_task_id)

    async def _drain():
        final = None
        async for p in tm.start_file_task(req):
            if p.state == "failed":
                return None
            if p.state == "done":
                final = p
        return final

    try:
        # wait_for, not asyncio.timeout: this runs on 3.10 too.
        final = await asyncio.wait_for(_drain(), timeout)
    except (DfError, asyncio.TimeoutError):
        MANIFEST_FETCHES.labels("miss").inc()
        return None
    if final is None:
        MANIFEST_FETCHES.labels("miss").inc()
        return None
    store = tm.storage.find_completed_task(final.task_id)
    if store is None:
        return None
    n = store.metadata.content_length
    buf = acquire_read_buffer(n)
    try:
        with store:
            await asyncio.to_thread(store.read_into, 0, n, buf)
        m = DeltaManifest.from_json_bytes(bytes(buf[:n]))
    except ManifestError as e:
        log.warning("fabric manifest corrupt; ignoring",
                    task=content_task_id[:16], error=str(e)[:200])
        MANIFEST_FETCHES.labels("corrupt").inc()
        return None
    finally:
        release_read_buffer(buf)
    MANIFEST_FETCHES.labels("hit").inc()
    return m


async def publish_manifest_for(tm, content_task_id: str, *,
                               params: CDCParams | None = None,
                               manifest: DeltaManifest | None = None,
                               ) -> DeltaManifest | None:
    """Build the manifest from THIS host's completed copy of the content
    (or take a prebuilt one) and import it as the ``dfdelta://`` task
    (announced to the scheduler like any dfcache import, so peers can
    pull it). Idempotent: an already-published manifest task is reused.
    Returns the manifest, or None when the content is not landed here."""
    store = tm.storage.find_completed_task(content_task_id)
    if store is None:
        log.warning("cannot publish manifest: content not landed",
                    task=content_task_id[:16])
        return None
    m = manifest
    if m is None:
        m = await asyncio.to_thread(manifest_from_store, store,
                                    store.metadata.url, params)
    req = _manifest_request(content_task_id)
    if tm.storage.find_completed_task(req.task_id()) is not None:
        MANIFEST_FETCHES.labels("published").inc()
        return m
    path = os.path.join(tm.storage.opt.data_dir,
                        f".manifest-{content_task_id[:16]}.json")
    try:
        data = m.to_json_bytes()
        await asyncio.to_thread(_write_file, path, data)
        await tm.import_task(path, req)
    finally:
        try:
            os.unlink(path)
        except OSError:
            pass
    MANIFEST_FETCHES.labels("published").inc()
    return m


def _write_file(path: str, data: bytes) -> None:
    with open(path, "wb") as f:
        f.write(data)


# ------------------------------------------------------------------ #
# The delta landing engine
# ------------------------------------------------------------------ #

class _SpanFetches:
    """Concurrent ranged-task pulls of the fetch spans, bounded, with
    per-span buffers released after the last consuming piece job."""

    def __init__(self, fetcher, spans: list[tuple[int, int]],
                 consumers: dict[tuple[int, int], int],
                 concurrency: int = 4):
        self.fetcher = fetcher
        self._bufs: dict[tuple[int, int], memoryview] = {}
        self._remaining = dict(consumers)
        self._sem = asyncio.Semaphore(concurrency)
        self._tasks = {
            span: asyncio.ensure_future(self._pull(span)) for span in spans}

    async def _pull(self, span: tuple[int, int]) -> memoryview:
        s, e = span
        async with self._sem:
            # Inside the gate: a fresh pooled buffer is zero-filled on the
            # loop's thread, and the spans still waiting need none yet.
            buf = acquire_read_buffer(e - s)
            try:
                await self.fetcher.fetch_into(s, e, buf[:e - s])
            except BaseException:
                release_read_buffer(buf)
                raise
        self._bufs[span] = buf
        return buf

    async def view(self, span: tuple[int, int]) -> memoryview:
        buf = await self._tasks[span]
        s, e = span
        return buf[:e - s]

    def consumed(self, span: tuple[int, int]) -> None:
        self._remaining[span] -= 1
        if self._remaining[span] <= 0:
            buf = self._bufs.pop(span, None)
            if buf is not None:
                release_read_buffer(buf)

    async def close(self) -> None:
        for t in self._tasks.values():
            t.cancel()
        await asyncio.gather(*self._tasks.values(), return_exceptions=True)
        for buf in self._bufs.values():
            release_read_buffer(buf)
        self._bufs.clear()


def _range_fetcher(tm, req):
    """Ranged-task fetcher for the delta spans: the dataset plane's
    DaemonRangeFetcher, parameterized so span task ids agree across
    every host running the same delta (tag/application/header ride
    along, and so does the whole-content digest: it verifies no slice,
    but it is part of the new version's identity, and a host that holds
    that version whole under it, the seed that landed it first, serves a
    span out of its store and not from the origin again)."""
    from dragonfly2_tpu.dataset.shard_reader import DaemonRangeFetcher

    return DaemonRangeFetcher(
        tm, req.url, tag=req.meta.tag, application=req.meta.application,
        header=dict(req.meta.header), pod_broadcast=req.pod_broadcast,
        digest=req.meta.digest)


async def _resolve_manifests(tm, req, task_id: str, base_store, *,
                             params: CDCParams | None):
    """(new_manifest, base_manifest, whether the base's was built here) or
    None when the delta path is not viable (no published manifest for the
    new version)."""
    new_m = await fetch_manifest(tm, task_id)
    if new_m is None:
        return None
    want = new_m.params
    base_id = base_store.metadata.task_id
    base_m = await fetch_manifest(tm, base_id)
    built = (base_m is None or base_m.params != want
             or base_m.content_length != base_store.metadata.content_length)
    if built:
        base_m = await asyncio.to_thread(
            manifest_from_store, base_store, base_store.metadata.url, want)
        # Publish the freshly-built base manifest (best effort): the
        # next host deltaing from the same base then fabric-fetches it
        # instead of paying the miss patience + a local chunk walk.
        try:
            await publish_manifest_for(tm, base_id, manifest=base_m)
        except Exception as e:
            log.warning("base manifest publish failed (non-fatal)",
                        base=base_id[:16], error=describe(e))
    if params is not None and want != params:
        log.info("delta using published chunk params", task=task_id[:16])
    return new_m, base_m, built


async def run_delta_task(tm, req, base_task_id: str, *,
                         params: CDCParams | None = None,
                         fetch_concurrency: int = 4):
    """Drive one delta download on a TaskManager; yields
    FileTaskProgress frames exactly like ``start_file_task`` (the
    ``Daemon.Download`` handler streams them verbatim).

    Degradation ladder — every rung lands the bytes:
      1. completed/running task → plain reuse/dedup via start_file_task;
      2. no landed base, or no published manifest, or zero chunk overlap
         → plain full download (then this host best-effort PUBLISHES the
         manifest so the next host deltas);
      3. the delta proper — and inside it, a base chunk that fails its
         digest during the local copy is re-fetched as a ranged task
         (counted ``corrupt_base``), never trusted into the result.
    """
    task_id = req.task_id()

    async def _fallback(publish: bool):
        ok = False
        async for p in tm.start_file_task(req):
            if p.state == "done":
                ok = True
            yield p
        if ok and publish:
            try:
                await publish_manifest_for(tm, task_id, params=params)
            except Exception as e:     # best effort, never fails the task
                log.warning("manifest publish after full landing failed",
                            task=task_id[:16], error=describe(e))

    if (tm.storage.find_completed_task(task_id) is not None
            or tm.is_task_running(task_id)):
        async for p in _fallback(publish=False):
            yield p
        return

    base_store = tm.storage.find_completed_task(base_task_id)
    if base_store is None:
        log.info("delta base not landed; full download",
                 task=task_id[:16], base=base_task_id[:16])
        async for p in _fallback(publish=True):
            yield p
        return
    planning = time.perf_counter()
    manifests = await _resolve_manifests(tm, req, task_id, base_store,
                                         params=params)
    if manifests is None:
        log.info("no published manifest; full download + publish",
                 task=task_id[:16])
        async for p in _fallback(publish=True):
            yield p
        return
    new_m, base_m, built = manifests
    plan = plan_delta(new_m, base_m)
    # The flight begins here, with the task: the manifests' ms lie before
    # its first event, as an admission wait does.
    tm.flight.task(task_id).record(
        flightlib.EV_SWAP_PLAN, new_m.num_chunks,
        (time.perf_counter() - planning) * 1000.0,
        "built" if built else "fetched")
    if plan.reused_bytes == 0:
        log.info("zero chunk overlap with base; full download",
                 task=task_id[:16], base=base_task_id[:16])
        async for p in _fallback(publish=True):
            yield p
        return

    # Closed with this generator, inner first: a client that goes away
    # between two frames must find the landing's jobs ended before the
    # stores are unpinned, not whenever the collector reaches them.
    async with aclosing(_run_delta(tm, req, task_id, base_store, new_m, plan,
                                   fetch_concurrency)) as landing:
        async for p in landing:
            yield p


async def _run_delta(tm, req, task_id: str, base_store,
                     new_m: DeltaManifest, plan: DeltaPlan,
                     fetch_concurrency: int):
    from dragonfly2_tpu.daemon.peer.broker import PieceEvent
    from dragonfly2_tpu.daemon.peer.task_manager import (
        TaskStoreMetadata,
        _RunningTask,
    )
    from dragonfly2_tpu.pkg import idgen

    peer_id = req.peer_id or idgen.peer_id_v1(tm.host_ip)
    store = tm.storage.register_task(TaskStoreMetadata(
        task_id=task_id, peer_id=peer_id, url=req.url, tag=req.meta.tag,
        application=req.meta.application, header=dict(req.meta.header)))
    run = _RunningTask(store)
    tm._running[task_id] = run
    store.pin()
    base_store.pin()
    stats = {"reused_bytes": 0, "fetched_bytes": 0, "chunks_reused": 0,
             "chunks_fetched": 0, "corrupt_base": 0,
             "chunks_total": new_m.num_chunks,
             "content_length": new_m.content_length}
    log.info("delta landing", task=task_id[:16],
             base=base_store.metadata.task_id[:16],
             chunks=new_m.num_chunks, reuse_frac=round(
                 plan.reused_bytes / max(1, new_m.content_length), 4))
    try:
        if LocalTaskStore.completion_digest_applies(
                req.meta.digest, req.range is not None):
            # The pieces are this host's own copies: no parent map can
            # certify them, so the completion re-hash is certain. Let it
            # follow the pieces as they commit, as a back-to-source pull's
            # does (storage _PrefixHasher).
            store.start_prefix_hasher(req.meta.digest)
        async with aclosing(_assemble(
                tm, req, store, base_store, new_m, plan,
                _range_fetcher(tm, req), fetch_concurrency, stats,
                tm.flight.task(task_id), peer_id)) as pieces:
            async for p in pieces:
                yield p
    except DfError as e:
        await _fail(tm, req, store, run, task_id, peer_id, e)
        yield _failed_progress(task_id, peer_id, run.error)
        return
    except Exception as e:     # pragma: no cover - defensive
        log.error("delta task crashed", exc_info=True)
        await _fail(tm, req, store, run, task_id, peer_id,
                    DfError(Code.UnknownError, describe(e)))
        yield _failed_progress(task_id, peer_id, run.error)
        return
    finally:
        base_store.unpin()
        store.unpin()
        if run.error is None and not store.metadata.done:
            # Generator closed early (client disconnect). The LANDED
            # pieces are digest-verified chunk copies, so the store
            # survives for resume (a retry skips them) — but waiters must
            # see a terminal state.
            run.error = DfError(Code.ClientContextCanceled,
                                "delta download aborted by client")
            tm.flight.finish_task(task_id, "failed", note=str(run.error))
            tm.broker.publish(task_id, PieceEvent([], failed=True))
        run.done.set()
        tm._running.pop(task_id, None)


async def _fail(tm, req, store, run, task_id, peer_id, err: DfError) -> None:
    from dragonfly2_tpu.daemon.peer.broker import PieceEvent

    store.mark_invalid()
    run.error = err
    tm.flight.finish_task(task_id, "failed", note=str(err))
    tm.broker.publish(task_id, PieceEvent([], failed=True))


def _failed_progress(task_id: str, peer_id: str, err: DfError):
    from dragonfly2_tpu.daemon.peer.task_manager import FileTaskProgress

    return FileTaskProgress(state="failed", task_id=task_id,
                            peer_id=peer_id, error=err.to_wire())


@dataclass
class _PieceJob:
    """One target piece of a delta landing: the new manifest's chunks that
    overlap it, each with where its bytes come from. The job's buffer spans
    ``[lo, hi)``, the piece widened to the boundaries of the chunks that
    straddle its edges (at most ``piece_size + 2 * params.max_size``), so
    every chunk lies in it whole and is verified in the bytes that are
    written. A chunk is booked by the job in which it starts."""

    num: int
    start: int
    end: int
    lo: int
    hi: int
    reused: list[tuple[Chunk, Chunk]] = field(default_factory=list)  # (new, base)
    fetched: list[tuple[Chunk, tuple[int, int]]] = field(
        default_factory=list)                                 # (new, span)

    def spans(self) -> list[tuple[int, int]]:
        """The fetched spans the job reads, each once, in offset order."""
        return list(dict.fromkeys(span for _, span in self.fetched))

    def owns(self, c: Chunk) -> bool:
        return self.start <= c.offset < self.end


def _piece_jobs(new_m: DeltaManifest, plan: DeltaPlan,
                piece_size: int) -> list[_PieceJob]:
    """Cut the new manifest's chunks (which tile the content in offset
    order) into one job a target piece, before any byte moves."""
    base_of = {c.offset: b for c, b in plan.reused}
    spans = plan.fetch_spans()
    span_of: dict[int, tuple[int, int]] = {}
    si = 0
    for c in plan.fetched:
        while spans[si][1] <= c.offset:
            si += 1
        span_of[c.offset] = spans[si]
    chunks = new_m.chunks
    total = new_m.content_length
    jobs: list[_PieceJob] = []
    first = 0
    for num in range(compute_piece_count(total, piece_size)):
        start = num * piece_size
        end = min(start + piece_size, total)
        while chunks[first].end <= start:
            first += 1
        job = _PieceJob(num, start, end, lo=chunks[first].offset, hi=end)
        i = first
        while i < len(chunks) and chunks[i].offset < end:
            c = chunks[i]
            i += 1
            job.hi = max(end, c.end)
            b = base_of.get(c.offset)
            if b is None:
                job.fetched.append((c, span_of[c.offset]))
            else:
                job.reused.append((c, b))
        jobs.append(job)
    return jobs


def _sha256_hex(view) -> str:
    return hashlib.sha256(view).hexdigest()


def _commit_piece(job: _PieceJob, buf: memoryview, store) -> None:
    """Write the piece's own slice of ``buf``, every chunk of which has held
    its digest, with the (sum32, xor32) of exactly that slice, taken here by
    the thread that commits it: a hot-swap's flip gate compares them with
    the device's sums and reads the landing no second time
    (``client.device._host_piece_checksums``). What ``buf`` holds past the
    slice is a straddling chunk's, the next piece's to sum."""
    piece = buf[job.start - job.lo:job.end - job.lo]
    store.write_piece(job.num, piece, word_sums=checksum_numpy(piece))


def _build_piece(job: _PieceJob, buf: memoryview, views: dict, base_store,
                 store) -> tuple[list[tuple[Chunk, Chunk]], float, float]:
    """A piece job's thread half, whole: the fetched slices copied to their
    places in ``buf``, every reused chunk read there straight from the base
    store and sha256-ed where it lies, and, when every digest held, the
    piece's own slice of ``buf`` summed and written (``_commit_piece``).
    Returns the reused chunks that failed (the coroutine re-fetches them and
    commits the piece itself) and the clock readings around the reads and
    digests. Touches nothing of the event loop's."""
    lo = job.lo
    for c, span in job.fetched:
        at = c.offset - span[0]
        buf[c.offset - lo:c.end - lo] = views[span][at:at + c.length]
    bad = []
    t0 = time.perf_counter()
    for c, b in job.reused:
        at = c.offset - lo
        try:
            base_store.read_into(b.offset, b.length, buf, at=at)
            ok = _sha256_hex(buf[at:at + c.length]) == c.sha256
        except (StorageError, OSError) as e:
            log.warning("base chunk read failed; re-fetching",
                        base_offset=b.offset, error=str(e)[:200])
            ok = False
        if not ok:
            bad.append((c, b))
    t1 = time.perf_counter()
    if not bad:
        _commit_piece(job, buf, store)
    return bad, t0, t1


async def _in_thread(fn, *args):
    """``asyncio.to_thread`` that a cancellation does not leave behind: the
    thread works in a pooled buffer and on pinned stores, all the caller's
    to release, so a cancelled caller waits for it to return first."""
    work = asyncio.ensure_future(asyncio.to_thread(fn, *args))
    try:
        return await asyncio.shield(work)
    except asyncio.CancelledError:
        await asyncio.gather(work, return_exceptions=True)
        raise


async def _land_piece(job: _PieceJob, bufs: list, widest: int, store,
                      base_store, fetches: _SpanFetches, fetcher,
                      stats: dict, tf) -> None:
    """One piece job: wait for the fetched spans the piece needs, build the
    piece on a worker thread, repair what the thread handed back, book the
    chunks that start in the piece."""
    spans = job.spans()
    mine = [c for c, _ in job.fetched if job.owns(c)]
    buf = None
    try:
        views = {}
        if spans:
            t0 = time.perf_counter()
            for span in spans:
                views[span] = await fetches.view(span)
            tf.record(flightlib.EV_DELTA_FETCH, job.num,
                      (time.perf_counter() - t0) * 1000.0,
                      str(sum(c.length for c in mine)))
        # A buffer a finished job put back, or a fresh one (zero-filled on
        # this thread, beside the jobs already on theirs).
        buf = bufs.pop() if bufs else acquire_read_buffer(widest)
        bad, t0, t1 = await _in_thread(_build_piece, job, buf, views,
                                       base_store, store)
        failed = {c.offset for c, _ in bad}
        reused = [c for c, _ in job.reused
                  if job.owns(c) and c.offset not in failed]
        if len(bad) < len(job.reused):
            # The thread's own clock readings, stamped from here: the ring
            # is not written from worker threads.
            tf.record_at(t1, flightlib.EV_DELTA_REUSE, job.num,
                         (t1 - t0) * 1000.0,
                         str(sum(c.length for c in reused)))
        for c, b in bad:
            # Corrupt (or unreadable) base chunk: the digest gate caught it
            # before any byte of it was written. Re-fetch THIS chunk as its
            # own ranged task into its place and verify it again.
            log.warning("base chunk digest mismatch; re-fetching",
                        new_offset=c.offset, base_offset=b.offset,
                        length=c.length)
            t0 = time.perf_counter()
            view = buf[c.offset - job.lo:c.end - job.lo]
            await fetcher.fetch_into(c.offset, c.end, view)
            if await _in_thread(_sha256_hex, view) != c.sha256:
                raise DfError(Code.ClientPieceDownloadFail,
                              f"delta chunk at {c.offset} failed its "
                              f"manifest digest even after re-fetch")
            tf.record(flightlib.EV_DELTA_FETCH, job.num,
                      (time.perf_counter() - t0) * 1000.0, str(c.length))
        if bad:
            await _in_thread(_commit_piece, job, buf, store)
    finally:
        if buf is not None:
            bufs.append(buf)
        for span in spans:
            fetches.consumed(span)
    DELTA_PIECES.labels("built").inc()
    refetched = [c for c, _ in bad if job.owns(c)]
    stats["corrupt_base"] += len(refetched)
    DELTA_CHUNKS.labels("corrupt_base").inc(len(refetched))
    _book(stats, "reused", reused)
    _book(stats, "fetched", mine + refetched)


def _book(stats: dict, kind: str, chunks: list[Chunk]) -> None:
    nbytes = sum(c.length for c in chunks)
    stats[kind + "_bytes"] += nbytes
    stats["chunks_" + kind] += len(chunks)
    DELTA_BYTES.labels(kind).inc(nbytes)
    DELTA_CHUNKS.labels(kind).inc(len(chunks))


async def _assemble(tm, req, store, base_store, new_m: DeltaManifest,
                    plan: DeltaPlan, fetcher, fetch_concurrency: int,
                    stats: dict, tf, peer_id: str):
    """Land the new version a target piece at a time: ``_JOBS_IN_FLIGHT``
    piece jobs side by side, admitted in piece order, each built on a
    worker thread (``_build_piece``); then finalize exactly like a
    downloaded task. A piece the store already has (a resumed landing) is
    skipped whole, its chunks booked as the plan has them."""
    from dragonfly2_tpu.daemon.peer.broker import PieceEvent
    from dragonfly2_tpu.daemon.peer.task_manager import FileTaskProgress

    total = new_m.content_length
    piece_size = store.metadata.piece_size or compute_piece_size(total)
    store.update_task(content_length=total, piece_size=piece_size,
                      total_piece_count=compute_piece_count(
                          total, piece_size))
    jobs = []
    for job in _piece_jobs(new_m, plan, piece_size):
        if store.has_piece(job.num):
            DELTA_PIECES.labels("resumed").inc()
            _book(stats, "reused", [c for c, _ in job.reused if job.owns(c)])
            _book(stats, "fetched", [c for c, _ in job.fetched if job.owns(c)])
        else:
            jobs.append(job)
    # A span is pulled for the jobs that read it; one that only resumed
    # pieces wanted is not pulled again.
    consumers: dict[tuple[int, int], int] = {}
    for job in jobs:
        for span in job.spans():
            consumers[span] = consumers.get(span, 0) + 1
    # The jobs' buffers, one size so any job takes any: at most
    # ``_JOBS_IN_FLIGHT`` are ever made, each by the first job to want one.
    widest = max((j.hi - j.lo for j in jobs), default=0)
    bufs: list[memoryview] = []
    fetches = _SpanFetches(fetcher, list(consumers), consumers,
                           concurrency=fetch_concurrency)
    started: list[asyncio.Future] = []
    pending: set[asyncio.Future] = set()
    last_progress = 0.0
    try:
        for job in (*jobs, None):       # None: the last jobs drain
            while pending and (job is None
                               or len(pending) >= _JOBS_IN_FLIGHT):
                done, pending = await asyncio.wait(
                    pending, return_when=asyncio.FIRST_COMPLETED)
                for t in done:
                    t.result()
                store.touch()
                now = time.monotonic()
                if now - last_progress >= 0.1:
                    last_progress = now
                    yield FileTaskProgress(
                        state="running", task_id=store.metadata.task_id,
                        peer_id=peer_id, content_length=total,
                        completed_length=store.downloaded_bytes(),
                        piece_count=len(store.metadata.pieces),
                        total_piece_count=store.metadata.total_piece_count)
            if job is not None:
                started.append(asyncio.ensure_future(_land_piece(
                    job, bufs, widest, store, base_store, fetches, fetcher,
                    stats, tf)))
                pending.add(started[-1])
    finally:
        # No job outlives the landing: one still waiting for a span is
        # cancelled, one in its thread is waited for (``_in_thread``), so
        # nothing writes into a store the caller is about to unpin.
        for t in pending:
            t.cancel()
        await asyncio.gather(*started, return_exceptions=True)
        await fetches.close()
        for buf in bufs:
            release_read_buffer(buf)

    # Exact-accounting invariant before anything is announced.
    booked = stats["reused_bytes"] + stats["fetched_bytes"]
    if booked != total:
        raise DfError(Code.UnknownError,
                      f"delta accounting drift: {booked} != {total}")
    task_id = store.metadata.task_id
    await tm._finalize_content_digest(req, store)
    store.mark_done()
    tm.flight.finish_task(task_id, "done")
    tm._pex_announce(task_id)
    # Announce like an import: no conductor registered this task with the
    # scheduler, and peers must be able to pull it from here.
    await tm._announce_local_task(store, task_id, peer_id)
    if len(tm.delta_stats) > 256:
        tm.delta_stats.clear()
    tm.delta_stats[task_id] = dict(stats)
    tm.broker.publish(task_id, PieceEvent(
        [], store.metadata.total_piece_count, total,
        store.metadata.piece_size, done=True))
    if req.output:
        with store:
            await asyncio.to_thread(store.store_to, req.output)
    device_verified = False
    if req.device == "tpu":
        device_verified = await tm._finalize_device(req, task_id, store)
    log.info("delta landed", task=task_id[:16],
             reused_mb=round(stats["reused_bytes"] / 1e6, 2),
             fetched_mb=round(stats["fetched_bytes"] / 1e6, 2),
             corrupt_base=stats["corrupt_base"])
    yield tm._final_progress(store, task_id, peer_id, device=req.device,
                             device_verified=device_verified)
