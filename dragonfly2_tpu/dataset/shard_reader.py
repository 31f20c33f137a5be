"""Sample-addressed reads: a sample's tar byte spans → a read of this
host's store where it holds the shard, ranged P2P tasks where it does not.

The point of the dataset plane: a host that needs sample ``000123`` of a
16 GB shard must fetch the few hundred KB covering that sample's members,
not the shard. Both fetchers below resolve a byte span to a RANGED file
task on a daemon — range is part of task identity (pkg/idgen
task_id_v1), so every host in the pod pulling the same sample issues a
byte-identical task and the fabric dedupes per SPAN, exactly like
sharded checkpoint pulls (client/device.py _pull_ranges); repeated reads
ride completed-task reuse. A span that a parent in THIS host's store
covers (the shard whole after ``PodShardedLoader.over_daemon``'s
``prepare()``, or a partial parent with the span's pieces) is no task at
all on the embedded daemon: ``DaemonRangeFetcher`` asks the task
manager's one parent gate first (task_manager.
read_range_from_local_parent) and the bytes are one ``preadv`` out of
that store, with no register, no scheduler and no seed in a sample's
path. The gateway's fetcher always makes the task.

Two transports:
  * ``DaemonRangeFetcher`` — embedded daemon (the north-star JAX process
    hosting its own dfdaemon): this store's parent first, else ranged
    FileTasks directly on the TaskManager.
  * ``GatewayRangeFetcher`` — over HTTP against the daemon's object
    gateway (`?ranged_task=1` GETs, daemon/objectstorage.py).

Span buffers ride the shared BufferPool (pkg/bufpool): readahead keeps a
bounded fleet of in-flight spans, and pooled backing arrays stop the
per-sample allocate/free churn.
"""

from __future__ import annotations

import asyncio
import time

from dragonfly2_tpu.pkg import dflog, metrics
from dragonfly2_tpu.pkg import flight as flightlib
from dragonfly2_tpu.pkg.bufpool import BufferPool
from dragonfly2_tpu.dataset.tar_index import Sample, ShardIndex

log = dflog.get("dataset.shard_reader")

DATASET_BYTES = metrics.counter(
    "dataset_bytes_total",
    "Dataset plane bytes: fetched (ranged spans) vs yielded (sample "
    "member payloads) vs device (what the feed put to the device, padding "
    "included)", ("direction",))
RANGE_READS = metrics.counter(
    "dataset_range_reads_total",
    "Sample span reads by outcome", ("result",))


# Where a span's bytes came from, worst last: a sample that took several
# spans is booked under the worst of them. ``local`` alone ran no task.
SOURCES = ("local", "reuse", "import", "peer", "cold", "origin")


class ShardReadError(Exception):
    pass


class DaemonRangeFetcher:
    """Spans of one shard on an in-process daemon/TaskManager: read out of
    its store where a parent of the same identity covers them, ranged file
    tasks otherwise. ``url`` is the shard's origin URL (e.g.
    backend.object_url(bucket, key)); ``tag`` must match whatever other
    consumers use (the gateway uses the bucket name) so ranged tasks
    dedupe across surfaces, and whatever pulled the shard whole, so that
    it is found as the parent."""

    def __init__(self, task_manager, url: str, *, tag: str = "",
                 application: str = "", header: dict | None = None,
                 pod_broadcast: bool = False, digest: str = ""):
        self.tm = task_manager
        self.url = url
        self.tag = tag
        # The whole object's digest where whoever pulled it whole named it
        # by one: part of the parent's identity, so a host that holds the
        # object under it (this one, a seed that was preheated) serves the
        # span out of its store. A slice is never verified against it
        # (``LocalTaskStore.completion_digest_applies``).
        self.digest = digest
        # Extra task-identity fields for consumers whose spans must dedup
        # with other surfaces carrying them (the delta plane threads the
        # original request's application/header through so every host
        # running the same delta issues byte-identical span tasks).
        self.application = application
        self.header = dict(header or {})
        self.pod_broadcast = pod_broadcast
        # Span reads by outcome: ``local`` read from this store's parent
        # with no task, ``reuse`` a ranged task already complete here,
        # ``cold`` a ranged task run; the local hit share is ``local`` over
        # the three.
        self.stats = {"local": 0, "cold": 0, "reuse": 0}

    async def fetch_into(self, start: int, end: int,
                         buf: memoryview) -> "tuple[str, float, float]":
        """One span into ``buf``: read from this host's store where a parent
        there covers it, else fetched as ONE ranged task. Returns what the
        feed's ring books for it: where the bytes came from (``local``:
        this store's parent, no task; ``reuse``: the ranged task was
        complete in this store; ``import``: a task that copied them from
        the parent; ``peer``; ``origin``), the ms a task spent moving them
        (the import's reads and writes; the pieces' transfers), and the ms
        of the read from the store into ``buf``."""
        from dragonfly2_tpu.daemon.peer.task_manager import FileTaskRequest
        from dragonfly2_tpu.pkg.errors import Code, DfError
        from dragonfly2_tpu.pkg.piece import Range
        from dragonfly2_tpu.proto.common import UrlMeta

        rng = Range.normalize_header(f"{start}-{end - 1}")
        req = FileTaskRequest(url=self.url, output="",
                              meta=UrlMeta(digest=self.digest, tag=self.tag,
                                           application=self.application,
                                           header=dict(self.header),
                                           range=rng),
                              pod_broadcast=self.pod_broadcast)
        req.range = Range.parse_http(rng)
        n = end - start
        t0 = time.perf_counter()
        got = await self.tm.read_range_from_local_parent(req, buf)
        if got is not None:
            if got != n:
                raise ShardReadError(
                    f"this store's parent holds {got}B of a {n}B span of "
                    f"{self.url}")
            self._count("local")
            return "local", 0.0, (time.perf_counter() - t0) * 1000.0
        final = None
        async for p in self.tm.start_file_task(req):
            if p.state == "failed":
                raise DfError.from_wire(p.error or {})
            if p.state == "done":
                final = p
        if final is None:
            raise DfError(Code.UnknownError, "ranged task ended silently")
        store = self.tm.storage.find_completed_task(final.task_id)
        if store is None:
            raise DfError(Code.StorageTaskNotFound,
                          f"ranged task {final.task_id[:16]} has no store")
        if store.metadata.content_length != n:
            raise ShardReadError(
                f"ranged task returned {store.metadata.content_length}B "
                f"for a {n}B span of {self.url}")
        t0 = time.perf_counter()
        with store:   # pin across the off-loop read
            # Unified read path: preadv straight into the caller's pooled
            # span buffer — no intermediate store buffer, no copy.
            await asyncio.to_thread(store.read_into, 0, n, buf)
        read_ms = (time.perf_counter() - t0) * 1000.0
        self._count("reuse" if final.from_reuse else "cold")
        if final.from_reuse:
            return "reuse", 0.0, read_ms
        # The task's own ring, while it is still there (a recorder keeps
        # 128 tasks'): a slice copied out of a parent that came into this
        # store after the gate said no stamped range_import; a piece from a
        # peer landed, one from the origin source_landed, each with its cost.
        tf = self.tm.flight.get(final.task_id)
        spent = {flightlib.EV_RANGE_IMPORT: 0.0, flightlib.EV_LANDED: 0.0,
                 flightlib.EV_SOURCE_LANDED: 0.0}
        imported = 0
        for _, code, piece, aux, _ in (tf.events() if tf is not None else ()):
            if code in spent:
                spent[code] += aux
                imported += code == flightlib.EV_RANGE_IMPORT and piece > 0
        if final.from_p2p:
            return "peer", spent[flightlib.EV_LANDED], read_ms
        if imported:
            return "import", spent[flightlib.EV_RANGE_IMPORT], read_ms
        return "origin", spent[flightlib.EV_SOURCE_LANDED], read_ms

    def _count(self, result: str) -> None:
        self.stats[result] += 1
        RANGE_READS.labels(result).inc()


class GatewayRangeFetcher:
    """Ranged-task GETs over the daemon's object gateway (Dfstore
    ``read_object_range`` with ranged_task=1)."""

    def __init__(self, store, bucket: str, key: str):
        self.store = store
        self.bucket = bucket
        self.key = key
        self.stats = {"cold": 0, "reuse": 0}

    async def fetch_into(self, start: int, end: int,
                         buf: memoryview) -> "tuple[str, float, float]":
        """As ``DaemonRangeFetcher.fetch_into``; the gateway says only
        whether the ranged task was reused (else ``cold``), and the bytes
        arrive in ``buf`` as the response's body, no read of its own."""
        attrs, _ = await self.store.read_object_range(
            self.bucket, self.key, start, end, buf=buf)
        outcome = "reuse" if attrs.get("from_reuse") else "cold"
        self.stats[outcome] += 1
        RANGE_READS.labels(outcome).inc()
        return outcome, 0.0, 0.0


class ShardReader:
    """Sample-level reads over one indexed shard. Adjacent member spans
    closer than ``coalesce_gap`` merge into one ranged task (the gap
    bytes ride along — fewer tasks beats fewer bytes at tar header
    granularity, and webdataset members are adjacent by construction)."""

    def __init__(self, fetcher, index: ShardIndex, *,
                 extensions=None, coalesce_gap: int = 256 << 10,
                 include_headers: bool = False,
                 pool: BufferPool | None = None, flight=None):
        self.fetcher = fetcher
        # The feed-level ring (pkg/flight.TaskFlight) that every sample's
        # read stamps ``feed_sample`` on, or None.
        self.flight = flight
        self.index = index
        self.extensions = (None if extensions is None
                           else tuple(extensions))
        self.coalesce_gap = coalesce_gap
        # include_headers widens spans to the members' header blocks —
        # useful when re-emitting valid tar bytes rather than payloads.
        self.include_headers = include_headers
        self.pool = pool if pool is not None else BufferPool(
            name="dataset_span")

    def sample_spans(self, sample: Sample) -> list[tuple[int, int]]:
        """Coalesced absolute byte spans covering the sample's members."""
        pairs = self.index.members_of(sample, self.extensions)
        if not pairs:
            raise ShardReadError(
                f"sample {sample.key!r} has no members"
                + (f" for extensions {self.extensions}" if self.extensions
                   else ""))
        raw = sorted(
            ((m.offset if self.include_headers else m.data_offset),
             m.data_offset + m.size)
            for _, m in pairs)
        spans: list[list[int]] = []
        for s, e in raw:
            if spans and s - spans[-1][1] <= self.coalesce_gap:
                spans[-1][1] = max(spans[-1][1], e)
            else:
                spans.append([s, e])
        return [(s, e) for s, e in spans]

    async def read_sample(self, sample: Sample, seq: int = -1) -> dict:
        """Fetch one sample; returns ``{"__key__", "__shard__",
        <ext>: bytes, ...}``. Multiple spans fetch concurrently (rare —
        coalescing usually leaves one). ``seq`` is the sample's place in
        the caller's plan, for the feed's ring."""
        t0 = time.perf_counter()
        spans = self.sample_spans(sample)
        bufs: dict[tuple[int, int], memoryview] = {}
        fetched: list[tuple] = []
        try:
            for s, e in spans:
                bufs[(s, e)] = self.pool.acquire(e - s)

            async def pull(s: int, e: int) -> None:
                fetched.append(
                    await self.fetcher.fetch_into(s, e, bufs[(s, e)])
                    or ("cold", 0.0, 0.0))

            if len(spans) == 1:
                await pull(*spans[0])
            else:
                tasks = [asyncio.ensure_future(pull(s, e)) for s, e in spans]
                try:
                    await asyncio.gather(*tasks)
                except BaseException:
                    for t in tasks:
                        t.cancel()
                    await asyncio.gather(*tasks, return_exceptions=True)
                    raise
            t1 = time.perf_counter()   # the bytes are in the pooled buffers
            out: dict = {"__key__": sample.key, "__shard__": self.index.shard}
            yielded = 0
            for ext, m in self.index.members_of(sample, self.extensions):
                span = next((s, e) for s, e in spans
                            if s <= m.data_offset
                            and m.data_offset + m.size <= e)
                buf = bufs[span]
                lo = m.data_offset - span[0]
                out[ext] = bytes(buf[lo:lo + m.size])
                yielded += m.size
            nbytes = sum(e - s for s, e in spans)
            DATASET_BYTES.labels("fetched").inc(nbytes)
            DATASET_BYTES.labels("yielded").inc(yielded)
            if self.flight is not None:
                ms = (t1 - t0) * 1000.0
                read_ms = max(f[2] for f in fetched)
                self.flight.record_at(
                    t1, flightlib.EV_FEED_SAMPLE, seq, ms,
                    f"src={max((f[0] for f in fetched), key=SOURCES.index)} "
                    f"tasks={sum(f[0] != 'local' for f in fetched)} "
                    f"bytes={nbytes} task={ms - read_ms:.3f} "
                    f"move={max(f[1] for f in fetched):.3f} "
                    f"read={read_ms:.3f}")
            return out
        finally:
            for buf in bufs.values():
                self.pool.release(buf)
