"""One task's on-disk store: a ``data`` file plus ``metadata.json``.

Reference: client/daemon/storage/local_storage.go — WritePiece with MD5
(:102-196), ReadPiece (:283), digest validation (:247), hardlink/copy
Store-to-output (:353), GetPieces listing for upload (:434), metadata
persistence (:647 saveMetadata). Piece ``n`` lives at byte offset
``n * piece_size`` in ``data``; unknown-length downloads extend the file as
pieces arrive in order.
"""

from __future__ import annotations

import json
import os
import shutil
import threading
import time
from dataclasses import asdict, dataclass, field
from itertools import accumulate

from dragonfly2_tpu.pkg import digest as pkgdigest
from dragonfly2_tpu.pkg import metrics
from dragonfly2_tpu.pkg.bufpool import BufferPool
from dragonfly2_tpu.pkg.errors import Code, StorageError
from dragonfly2_tpu.pkg.piece import compute_piece_count
from dragonfly2_tpu.storage import io_ring

DATA_FILE = "data"
METADATA_FILE = "metadata.json"

# Pooled read buffers for the unified read path (ownership:
# docs/ZERO_COPY.md). read_range/read_piece hand out views over these;
# callers on recycling hot paths (span streaming, the ranged local-parent
# import) release via release_read_buffer, everyone else just lets theirs
# be garbage-collected — the pool only ever retains returned buffers, so
# forgetting to release costs reuse, never correctness. The pool is
# scrapeable as bufpool_*{pool="storage_read"}.
_READ_BUFFERS = BufferPool(name="storage_read")


def acquire_read_buffer(size: int) -> memoryview:
    return _READ_BUFFERS.acquire(size)


def release_read_buffer(view) -> None:
    _READ_BUFFERS.release(view)


def read_buffer_stats() -> dict:
    return _READ_BUFFERS.stats()


def _preadv_exact(fd: int, view: memoryview, offset: int) -> None:
    """Fill ``view`` with the file's bytes from ``offset``; a short read
    (EOF inside the span) raises StorageError."""
    got = 0
    while got < len(view):
        n = os.preadv(fd, [view[got:]], offset + got)
        if n <= 0:
            raise StorageError(
                f"short read at offset {offset + got}: "
                f"{got}/{len(view)} bytes (EOF)")
        got += n


_NATIVE = None
_NATIVE_PROBED = False


def _native():
    """The C++ data-plane core (dragonfly2_tpu/native), or None. Fuses
    checksum+pwrite into one buffer pass and parallelizes re-verification."""
    global _NATIVE, _NATIVE_PROBED
    if not _NATIVE_PROBED:
        _NATIVE_PROBED = True
        try:
            from dragonfly2_tpu.native import binding

            _NATIVE = binding
        except Exception:
            _NATIVE = None
    return _NATIVE


@dataclass
class PieceRecord:
    num: int
    offset: int
    size: int
    digest: str = ""      # "md5:..." per-piece digest
    cost_ms: int = 0

    def to_wire(self) -> dict:
        return asdict(self)

    @classmethod
    def from_wire(cls, d: dict) -> "PieceRecord":
        return cls(num=d["num"], offset=d["offset"], size=d["size"],
                   digest=d.get("digest", ""), cost_ms=d.get("cost_ms", 0))


@dataclass
class TaskStoreMetadata:
    task_id: str
    peer_id: str = ""
    url: str = ""
    tag: str = ""
    application: str = ""
    content_length: int = -1
    piece_size: int = 0
    total_piece_count: int = -1
    digest: str = ""                  # whole-content digest once verified
    header: dict = field(default_factory=dict)
    done: bool = False
    invalid: bool = False
    pieces: dict[int, PieceRecord] = field(default_factory=dict)
    created_at: float = field(default_factory=time.time)
    last_access: float = field(default_factory=time.time)

    def to_json(self) -> dict:
        d = asdict(self)
        d["pieces"] = {str(k): v.to_wire() for k, v in self.pieces.items()}
        return d

    @classmethod
    def from_json(cls, d: dict) -> "TaskStoreMetadata":
        pieces = {int(k): PieceRecord.from_wire(v) for k, v in d.get("pieces", {}).items()}
        return cls(
            task_id=d["task_id"],
            peer_id=d.get("peer_id", ""),
            url=d.get("url", ""),
            tag=d.get("tag", ""),
            application=d.get("application", ""),
            content_length=d.get("content_length", -1),
            piece_size=d.get("piece_size", 0),
            total_piece_count=d.get("total_piece_count", -1),
            digest=d.get("digest", ""),
            header=d.get("header", {}) or {},
            done=d.get("done", False),
            invalid=d.get("invalid", False),
            pieces=pieces,
            created_at=d.get("created_at", time.time()),
            last_access=d.get("last_access", time.time()),
        )


# The completion digest's ring (see _ReadAhead): chunks of _CHUNK bytes, at
# most _RING_DEPTH of them out of the pool at once (being filled, filled, or
# under the hash), filled by _RING_READERS threads. Constants from
# benchmarks/digest_probe.py on the chip's host (PERF.md section 5), not
# settings.
_CHUNK = 4 << 20
_RING_DEPTH = 4
_RING_READERS = 1

DIGEST_CHUNKS = metrics.counter(
    "store_digest_chunks_total",
    "Completion-digest chunks the hashing thread found read ahead (ready) "
    "against chunks it had to wait for (waited)", ("how",))
_CHUNKS_READY = DIGEST_CHUNKS.labels("ready")
_CHUNKS_WAITED = DIGEST_CHUNKS.labels("waited")


class _ReadAhead:
    """The one pipelined read+hash loop of the completion digest: the
    thread that hashes never reads.

    ``_RING_READERS`` threads (``df-prefix-read-*``) walk the spans that
    ``next_span`` hands out, cut them into ``_CHUNK`` requests, and fill
    pooled buffers with ``os.preadv`` from a private O_RDONLY fd (the
    store's own may be GC-closed mid-life); ``hash_into``, on the caller's
    thread, takes the filled buffers in request order, updates the hasher,
    and hands each back to the pool. Both calls release the GIL, so chunk
    k+1 is copied while chunk k is hashed. No ``mmap``: a file truncated
    under a mapping is a SIGBUS, where a short ``preadv`` is an error that
    ``err`` carries.

    ``next_span`` is called under ``cv`` (the owner's own condition, so its
    frontier and the ring change together) and returns ``(offset, size,
    tag)``, or None when the next span is not there yet, or raises
    StopIteration when there will be no more. ``close`` is idempotent and
    does not block: filled buffers go back to the pool at once, a buffer in
    a thread's hands when that thread next looks, the fd with the last
    reader out."""

    def __init__(self, path: str, cv: threading.Condition, next_span,
                 name: str):
        self._cv = cv
        self._next_span = next_span
        self._fd = os.open(path, os.O_RDONLY)
        self._cut: tuple[int, int, object] | None = None  # span being cut
        self._issued = 0     # chunk requests taken by a reader
        self._hashed = 0     # chunks hashed and back in the pool
        self._filled: dict[int, tuple] = {}   # request -> (view, size, tag)
        self._readers = _RING_READERS
        self.closed = False
        self.err: str | None = None
        # Chunks hash_into found filled when it asked / had to wait for.
        self.ready = 0
        self.waited = 0
        self.threads = [
            threading.Thread(target=self._read, daemon=True,
                             name=f"df-prefix-read-{name}")
            for _ in range(_RING_READERS)]
        for t in self.threads:
            t.start()

    def close(self) -> None:
        with self._cv:
            self.closed = True
            for view, _, _ in self._filled.values():
                _READ_BUFFERS.release(view)
            self._filled.clear()
            self._cv.notify_all()

    def fail(self, why: str) -> None:
        with self._cv:
            if self.err is None:
                self.err = why
            self.close()

    def _read(self) -> None:
        cv = self._cv
        try:
            while True:
                with cv:
                    while True:
                        if self.closed:
                            return
                        if self._issued - self._hashed < _RING_DEPTH:
                            if self._cut is None:
                                self._cut = self._next_span()
                            if self._cut is not None:
                                break
                        # Timed: total_piece_count can be set by
                        # update_task without a piece commit notifying.
                        cv.wait(timeout=1.0)
                    off, left, tag = self._cut
                    take = min(left, _CHUNK)
                    if left > take:
                        self._cut = (off + take, left - take, tag)
                        tag = None   # only a span's last chunk carries it
                    else:
                        self._cut = None
                    request = self._issued
                    self._issued += 1
                view = _READ_BUFFERS.acquire(take)
                try:
                    _preadv_exact(self._fd, view[:take], off)
                except BaseException:
                    _READ_BUFFERS.release(view)
                    raise
                with cv:
                    if self.closed:
                        _READ_BUFFERS.release(view)
                        return
                    self._filled[request] = (view, take, tag)
                    cv.notify_all()
        except StopIteration:
            pass
        except Exception as e:  # noqa: BLE001 - carried by err; caller re-hashes
            self.fail(str(e))
        finally:
            with cv:
                self._readers -= 1
                if not self._readers:
                    os.close(self._fd)
                cv.notify_all()

    def hash_into(self, h, span_done=None) -> None:
        """The hashing side, on the caller's thread: returns when every
        span has been hashed or the ring is closed (``closed`` / ``err``
        say which; it does not raise). ``span_done(tag)`` is called under
        ``cv`` as a span's last chunk has been hashed."""
        cv = self._cv
        try:
            while True:
                with cv:
                    filled = self._filled.pop(self._hashed, None)
                    ready = filled is not None
                    while filled is None:
                        if self.closed or (not self._readers
                                           and self._issued == self._hashed):
                            return
                        cv.wait(timeout=1.0)
                        filled = self._filled.pop(self._hashed, None)
                if ready:
                    self.ready += 1
                    _CHUNKS_READY.inc()
                else:
                    self.waited += 1
                    _CHUNKS_WAITED.inc()
                view, size, tag = filled
                try:
                    h.update(view[:size])   # GIL released for >2 KiB
                finally:
                    _READ_BUFFERS.release(view)
                with cv:
                    self._hashed += 1
                    if tag is not None and span_done is not None:
                        span_done(tag)
                    cv.notify_all()
        except Exception as e:  # noqa: BLE001 - carried by err; caller re-hashes
            self.fail(str(e))


class _PrefixHasher:
    """Background contiguous-prefix hasher: overlaps the completion-time
    whole-content digest with the download itself.

    Started only for back-to-source transfers with a known full-content
    digest: self-computed piece digests can never be certified by a done
    parent, so those tasks always pay the completion re-hash (the
    reference hashes after download completes — digest_reader.go); hashing
    committed pieces in piece order WHILE later pieces stream turns that
    serial tail into overlap. P2P children keep the certification skip and
    never start one of these.

    The background side is two roles (``_ReadAhead``). The READER claims
    the committed piece at the read frontier ``_read_next`` — commitment is
    the store's byte-finality point — and copies it into the ring; the
    HASHER (``df-prefix-hash-*``) takes the ring's chunks in order and
    advances the hash frontier ``_next`` as a piece's last chunk is done.
    Pieces in ``[_next, _read_next)`` are the reader's: copied, or being
    copied, outside the lock. Any anomaly (a piece re-recorded at or behind
    the READ frontier, a short read, an fd error) poisons the hasher;
    ``finish`` then returns None and the caller falls back to the full
    re-hash, so this is an optimization that can only be bypassed, never
    wrong.

    Zero-copy feed: when the committing writer still holds the piece's
    bytes in memory (the Python receive paths), it hands them to ``feed``
    right after the commit and both frontiers advance WITHOUT re-reading
    landed bytes from disk — the hash runs in the writer's worker thread,
    over memory it owns for the duration of the call. Only a piece the
    reader has not claimed is fed (``_next == _read_next``: the ring is
    empty), and the reader claims none while a feed runs, so no piece is
    hashed twice or skipped. The reader only ever preads pieces that never
    came through memory (native-engine landings, out-of-order arrivals)."""

    def __init__(self, store: "LocalTaskStore", algorithm: str):
        self.store = store
        self.algorithm = algorithm
        self._h = pkgdigest.new_hasher(algorithm)
        self._next = 0         # hash frontier: pieces [0, _next) are in _h
        self._read_next = 0    # read frontier: the reader's next claim
        self._cv = threading.Condition()
        # A feed() is hashing piece _next outside the lock.
        self._feeding = False
        # Commit→feed handshake: a commit that WILL be followed by a feed
        # of the frontier piece reserves it so the reader does not race in
        # and pread it first (stamped so a feed that never arrives —
        # observer raised mid-commit — only stalls us briefly).
        self._reserved: int | None = None
        self._reserved_at = 0.0
        # Pieces the reader read back from the store: what the frontier
        # could not take from memory (``verified``'s piece).
        self.disk_reads = 0
        # Chunks (ready, waited) of the ring inside finish(): the tail.
        self.tail_chunks = (0, 0)
        name = store.metadata.task_id[:12]
        self._ring = _ReadAhead(store._data_path, self._cv, self._claim, name)
        self._thread = threading.Thread(
            target=self._ring.hash_into, args=(self._h, self._piece_hashed),
            daemon=True, name=f"df-prefix-hash-{name}")
        self._thread.start()

    # Called from _commit_piece_record (under the store's _meta_lock; lock
    # order store._meta_lock → self._cv, and no thread of ours takes
    # _meta_lock).
    def piece_recorded(self, num: int, replaced: bool,
                       will_feed: bool = False) -> None:
        with self._cv:
            # At or behind the READ frontier: bytes of every piece below it
            # may already be copied, and <=, not <, because a feed() may be
            # hashing piece _read_next OUTSIDE the lock — a re-record there
            # would hash a torn mix of old and new bytes without this
            # poison.
            if replaced and num <= self._read_next:
                self._ring.fail(
                    f"piece {num} re-recorded at/behind the read frontier")
            if (will_feed and not self._ring.closed and not self._feeding
                    and num == self._next == self._read_next):
                self._reserved = num
                self._reserved_at = time.monotonic()
                return   # no notify: the imminent feed() advances instead
            self._cv.notify_all()

    def feed(self, num: int, chunks) -> None:
        """Advance the frontier with in-memory bytes (one buffer or a list
        of buffers, in order). Called by the committing writer AFTER
        ``piece_recorded``, outside the store's _meta_lock, while it still
        owns the buffers. No-op unless ``num`` is exactly the frontier and
        the reader has not claimed it — anything else stays the reader's
        job."""
        with self._cv:
            if self._reserved == num:
                self._reserved = None
            if (self._ring.closed or self._feeding
                    or not num == self._next == self._read_next):
                self._cv.notify_all()
                return
            self._feeding = True
        try:
            if isinstance(chunks, (bytes, bytearray, memoryview)):
                chunks = (chunks,)
            for c in chunks:
                self._h.update(c)   # GIL released for >2 KiB
        except Exception as e:  # noqa: BLE001 - poisons; caller re-hashes
            with self._cv:
                self._feeding = False
                self._ring.fail(str(e))
            return
        with self._cv:
            self._feeding = False
            self._next += 1
            self._read_next += 1
            self._cv.notify_all()

    def stop(self) -> None:
        self._ring.close()

    def _claim(self):
        """The reader's ``next_span``, under ``_cv``: the committed piece
        at the read frontier, unless a feed holds or is about to take it."""
        m = self.store.metadata
        rec = m.pieces.get(self._read_next)
        if rec is None:
            if 0 <= m.total_piece_count <= self._read_next:
                raise StopIteration   # drained
            return None
        if self._feeding:
            return None
        if self._reserved == self._read_next:
            # A feed() is imminent for this piece; only reclaim a
            # reservation whose feed never came (commit-path exception
            # between record and feed — rare, and the cost is one pread).
            if time.monotonic() - self._reserved_at <= 1.0:
                return None
            self._reserved = None
        self._read_next += 1
        self.disk_reads += 1
        return rec.offset, rec.size, rec.num

    def _piece_hashed(self, num: int) -> None:
        self._next = num + 1

    def finish(self, timeout: float = 60.0) -> str | None:
        """Wait for the frontier to drain; hex digest, or None on any
        error/timeout (caller falls back to the full re-hash)."""
        deadline = time.monotonic() + timeout
        ring = self._ring
        ready, waited = ring.ready, ring.waited
        with self._cv:
            while True:
                if ring.closed:
                    return None
                total = self.store.metadata.total_piece_count
                if total >= 0 and self._next >= total:
                    break
                left = deadline - time.monotonic()
                if left <= 0 or not self._cv.wait(timeout=min(left, 2.0)):
                    if time.monotonic() >= deadline:
                        return None
        for t in (*ring.threads, self._thread):
            t.join(timeout=5.0)
        self.tail_chunks = (ring.ready - ready, ring.waited - waited)
        return self._h.hexdigest()


class LocalTaskStore:
    """Synchronous piece IO over one data file. Writes go through the page
    cache (pwrite); metadata saves are atomic (tmp+rename)."""

    def __init__(self, base_dir: str, metadata: TaskStoreMetadata):
        self.dir = base_dir
        self.metadata = metadata
        os.makedirs(self.dir, exist_ok=True)
        self._data_path = os.path.join(self.dir, DATA_FILE)
        self._fd: int | None = None
        # Called with this store after it has opened its data file, from
        # whichever thread did (StorageManager: its budget of open files).
        self.on_open = None
        self._pins = 0
        self._unsaved_pieces = 0
        self._last_meta_save = 0.0
        self._output_lock = threading.Lock()
        # num -> digest string each piece was verified AGAINST at landing
        # time (the parent-announced value), vs self-computed. In-memory
        # only: the completion-time decision to skip the whole-content
        # re-hash is made in the process that landed the pieces
        # (pieces_all_digest_verified).
        self._verified_pieces: dict[int, str] = {}
        # Set by the conductor at completion: the piece-digest map of a
        # parent whose sync stream reported done (its completion gate
        # passed — seeds validate the full digest before done). The skip
        # compares verified-against values to THIS map, piece by piece.
        self.certified_digests: "dict[int, str] | None" = None
        # num -> (sum32, xor32) of the piece's bytes (ops/checksum.py's
        # definition), for the pieces whose committing writer took them
        # from the bytes it wrote (a delta landing's piece jobs): the host
        # side of a hot-swap's flip gate without a second read of the
        # landing. In memory only and tied to the bytes: set by the commit
        # that wrote them, dropped when the piece is re-recorded and when
        # the task is invalidated; a store read back from disk has none.
        self._word_sums: dict[int, tuple[int, int]] = {}
        # Optional StorageObserver (see storage/manager.py): notified on
        # piece commits and geometry updates so external indexes (the
        # native upload server's serving registry) stay current. Called
        # from worker threads — implementations must be thread-safe.
        self.observer = None
        # Piece writes are thread-offloaded (daemon/peer paths): the
        # native crc+pwrite runs GIL-free and offset-disjoint, but fd
        # creation and metadata record/serialize must serialize.
        self._meta_lock = threading.Lock()
        # Optional background contiguous-prefix hasher (back-source tasks
        # with a known content digest — see _PrefixHasher).
        self._prefix_hasher: _PrefixHasher | None = None
        # How the last validate_digest got its digest, for the flight's
        # ``verified``: ("prefix" | "rehash", pieces it read from the store,
        # the ring's chunks (ready, waited) while validate_digest ran).
        self.digest_pass: tuple[str, int, tuple[int, int]] = ("", 0, (0, 0))

    # -- pinning: GC must not reclaim a store mid-download/upload ----------

    def pin(self) -> "LocalTaskStore":
        self._pins += 1
        return self

    def unpin(self) -> None:
        self._pins = max(0, self._pins - 1)

    @property
    def pinned(self) -> bool:
        return self._pins > 0

    def __enter__(self) -> "LocalTaskStore":
        return self.pin()

    def __exit__(self, *exc) -> None:
        self.unpin()

    # -- lifecycle ---------------------------------------------------------

    @classmethod
    def create(cls, base_dir: str, metadata: TaskStoreMetadata) -> "LocalTaskStore":
        store = cls(base_dir, metadata)
        store.save_metadata()
        return store

    @classmethod
    def load(cls, base_dir: str) -> "LocalTaskStore":
        meta_path = os.path.join(base_dir, METADATA_FILE)
        with open(meta_path) as f:
            metadata = TaskStoreMetadata.from_json(json.load(f))
        return cls(base_dir, metadata)

    def _ensure_fd(self) -> int:
        if self._fd is None:
            opened = False
            with self._meta_lock:
                if self._fd is None:
                    self._fd = os.open(self._data_path,
                                       os.O_RDWR | os.O_CREAT, 0o644)
                    opened = True
            # Outside the lock: the manager may close OTHER stores' fds
            # here (each under its own lock) to stay inside its budget.
            if opened and self.on_open is not None:
                self.on_open(self)
        return self._fd

    def close(self) -> None:
        # Under _meta_lock: serializes with _ensure_fd's lazy reopen — GC
        # now closes idle stores' fds mid-life, not only at destroy time.
        with self._meta_lock:
            if self._fd is not None:
                os.close(self._fd)
                self._fd = None

    def destroy(self) -> None:
        ph = self._prefix_hasher
        if ph is not None:
            self._prefix_hasher = None
            ph.stop()
        with self._meta_lock:
            self._word_sums.clear()
        self.close()
        shutil.rmtree(self.dir, ignore_errors=True)

    # -- metadata ----------------------------------------------------------

    def save_metadata(self) -> None:
        with self._meta_lock:
            tmp = os.path.join(self.dir, METADATA_FILE + ".tmp")
            with open(tmp, "w") as f:
                json.dump(self.metadata.to_json(), f)
            os.replace(tmp, os.path.join(self.dir, METADATA_FILE))
            self._unsaved_pieces = 0
            self._last_meta_save = time.monotonic()

    # Piece-arrival persistence is batched: re-serializing every record per
    # piece is O(pieces²) json work (profiled at ~80 ms/piece on big tasks,
    # dominating the download loop). A crash loses at most one batch — those
    # pieces simply re-fetch on resume; completion (mark_done) always saves.
    # The 2 s timer is the PRIMARY trigger: a standard ~32-piece task that
    # transfers inside the window does O(1) metadata serializations total
    # (one mid-flight at most, plus completion), where the old 16-piece
    # count trigger made it O(pieces/16) each a full-map json dump. The
    # count is only a backstop bounding replay for many-hundred-piece
    # tasks on slow links.
    _SAVE_EVERY_PIECES = 64
    _SAVE_EVERY_SECONDS = 2.0

    def _piece_recorded_save(self) -> None:
        if (self._unsaved_pieces >= self._SAVE_EVERY_PIECES
                or time.monotonic() - self._last_meta_save >= self._SAVE_EVERY_SECONDS):
            self.save_metadata()

    def touch(self) -> None:
        self.metadata.last_access = time.time()

    def update_task(self, *, content_length: int | None = None,
                    total_piece_count: int | None = None,
                    piece_size: int | None = None,
                    digest: str | None = None,
                    header: dict | None = None) -> None:
        m = self.metadata
        if content_length is not None and content_length >= 0:
            m.content_length = content_length
            if m.piece_size and m.total_piece_count < 0:
                m.total_piece_count = compute_piece_count(content_length, m.piece_size)
        if total_piece_count is not None and total_piece_count >= 0:
            m.total_piece_count = total_piece_count
        if piece_size is not None and piece_size > 0:
            m.piece_size = piece_size
        if digest is not None:
            m.digest = digest
        if header is not None:
            m.header = header
        self.save_metadata()
        obs = self.observer
        if obs is not None:
            obs.task_updated(self)

    # -- piece IO ----------------------------------------------------------

    def write_piece(self, num: int, data, expected_digest: str = "",
                    cost_ms: int = 0, algorithm: str = "",
                    word_sums: "tuple[int, int] | None" = None) -> PieceRecord:
        """Write piece ``num`` (``data`` is any bytes-like — pooled read
        buffers land without a bytes() copy). Verifies the per-piece digest
        before the write lands (reference local_storage.go:102-196 hashes
        in-flight). With no ``expected_digest``, a fresh digest is computed
        with ``algorithm`` (default: preferred_piece_algorithm — hardware
        crc32c fused into the write when the native library is present).
        Receive paths that hold the body as wire chunks use
        ``write_piece_chunks`` instead (digest fused into the write).
        ``word_sums``: the (sum32, xor32) the caller took of exactly
        ``data``, kept with the piece by the commit (``word_sums()``); no
        sum is taken here, so a writer that brings none pays nothing."""
        m = self.metadata
        if m.piece_size <= 0:
            raise StorageError("piece size not set")
        offset = num * m.piece_size
        fd = self._ensure_fd()
        native = _native()
        fused = False
        # The fused paths write before verifying, which is only safe when no
        # valid bytes exist at this offset yet: re-writing a recorded piece
        # with corrupt data would leave bad bytes under a digest that still
        # claims the old content. Recorded pieces verify in memory first.
        piece_is_new = num not in m.pieces
        if expected_digest:
            d = pkgdigest.parse(expected_digest)
            if (native is not None and piece_is_new
                    and d.algorithm == pkgdigest.ALGORITHM_CRC32C):
                # Fused path: the C++ core checksums while pwrite()ing (one
                # memory walk). A mismatched piece is re-requested and the
                # same offsets are simply overwritten — metadata below is
                # only recorded on success, so the bad bytes are invisible.
                crc = native.write_piece_crc(fd, offset, data)
                if f"{crc:08x}" != d.encoded:
                    raise StorageError(
                        f"piece {num} digest mismatch: want {d.encoded}, got {crc:08x}",
                        Code.ClientPieceDownloadFail,
                    )
                fused = True
            else:
                actual = pkgdigest.hash_bytes(d.algorithm, data)
                if actual.encoded != d.encoded:
                    raise StorageError(
                        f"piece {num} digest mismatch: want {d.encoded}, got {actual.encoded}",
                        Code.ClientPieceDownloadFail,
                    )
            digest_str = expected_digest
            self._verified_pieces[num] = expected_digest
        else:
            algorithm = algorithm or pkgdigest.preferred_piece_algorithm()
            if (native is not None and piece_is_new
                    and algorithm == pkgdigest.ALGORITHM_CRC32C):
                crc = native.write_piece_crc(fd, offset, data)
                digest_str = f"{pkgdigest.ALGORITHM_CRC32C}:{crc:08x}"
                fused = True
            else:
                digest_str = str(pkgdigest.hash_bytes(algorithm, data))
        if not fused:
            mv = data if isinstance(data, memoryview) else memoryview(data)
            written = 0
            while written < len(mv):
                written += os.pwrite(fd, mv[written:], offset + written)
        rec = PieceRecord(num=num, offset=offset, size=len(data),
                          digest=digest_str, cost_ms=cost_ms)
        return self._commit_piece_record(rec, feed_chunks=(data,),
                                         word_sums=word_sums)

    def _pwritev_chunks(self, fd: int, chunks: list, offset: int,
                        num: int) -> None:
        views = [c if isinstance(c, memoryview) else memoryview(c)
                 for c in chunks if len(c)]
        if len(views) > 1:
            ring = io_ring.get_ring()
            if ring.backend in ("batch", "io_uring"):
                # One submission for the whole chunk list (the serial
                # pwritev was already one syscall when it didn't split;
                # the ring keeps that true for arbitrarily many chunks
                # and absorbs partial writes natively).
                offsets = []
                at = offset
                for v in views:
                    offsets.append(at)
                    at += len(v)
                ring.write_chunks(fd, views, offsets)
                return
        written = 0
        while views:
            n = os.pwritev(fd, views, offset + written)
            if n <= 0:
                raise StorageError(f"pwritev returned {n} at piece {num}")
            written += n
            # Partial vector write (rare on regular files): drop the fully
            # written views, trim the boundary one, continue.
            while views and n >= len(views[0]):
                n -= len(views[0])
                views.pop(0)
            if views and n:
                views[0] = views[0][n:]

    def write_piece_chunks(self, num: int, chunks: list, digest_str: str = "",
                           expected_digest: str = "",
                           cost_ms: int = 0) -> PieceRecord:
        """Land piece ``num`` from an ordered list of bytes-like chunks —
        the streaming receive paths hand over their chunk views exactly as
        the wire delivered them, with no assembly buffer and no
        concatenation copy. Single-pass, never re-reading landed bytes,
        in one of three shapes:

          - ``digest_str`` given: the caller hashed these exact chunks
            while they arrived (non-crc32c algorithms overlap the socket
            wait that way); verification is a string compare, the write
            one pwritev.
          - crc32c target + native + unrecorded piece: FUSED — each chunk
            is checksummed while being pwritten (seeded crc continues
            across chunks), one memory walk per byte for hash+write
            combined. Safe to write before verifying for the same reason
            as write_piece's fused path: no valid bytes exist at the
            offset yet, and a mismatch leaves the bytes unrecorded.
          - otherwise: hash the in-memory chunks, verify, then pwritev
            (no native lib, or re-writing a recorded piece where
            write-before-verify would be unsafe)."""
        m = self.metadata
        if m.piece_size <= 0:
            raise StorageError("piece size not set")
        offset = num * m.piece_size
        fd = self._ensure_fd()
        native = _native()
        size = sum(len(c) for c in chunks)
        want = pkgdigest.parse(expected_digest) if expected_digest else None
        target_alg = (want.algorithm if want is not None
                      else pkgdigest.preferred_piece_algorithm())
        if digest_str:
            if want is not None and \
                    digest_str != f"{want.algorithm}:{want.encoded}":
                raise StorageError(
                    f"piece {num} digest mismatch: want {want}, got {digest_str}",
                    Code.ClientPieceDownloadFail,
                )
            self._pwritev_chunks(fd, chunks, offset, num)
        elif (native is not None and num not in m.pieces
                and target_alg == pkgdigest.ALGORITHM_CRC32C):
            crc, off = 0, offset
            for c in chunks:
                if len(c):
                    crc = native.write_chunk_crc(fd, off, c, crc)
                    off += len(c)
            if want is not None and f"{crc:08x}" != want.encoded:
                raise StorageError(
                    f"piece {num} digest mismatch: want {want.encoded}, "
                    f"got {crc:08x}",
                    Code.ClientPieceDownloadFail,
                )
            digest_str = f"{pkgdigest.ALGORITHM_CRC32C}:{crc:08x}"
        else:
            h = pkgdigest.new_hasher(target_alg)
            for c in chunks:
                h.update(c)
            digest_str = f"{target_alg}:{h.hexdigest()}"
            if want is not None and \
                    digest_str != f"{want.algorithm}:{want.encoded}":
                raise StorageError(
                    f"piece {num} digest mismatch: want {want}, got {digest_str}",
                    Code.ClientPieceDownloadFail,
                )
            self._pwritev_chunks(fd, chunks, offset, num)
        if expected_digest:
            self._verified_pieces[num] = expected_digest
            digest_str = expected_digest
        rec = PieceRecord(num=num, offset=offset, size=size,
                          digest=digest_str, cost_ms=cost_ms)
        return self._commit_piece_record(rec, feed_chunks=chunks)

    def data_fd(self) -> int:
        """The data file's fd, for transports that land bytes directly
        (native/src/dfhttp.cc socket→crc32c→pwrite). Callers passing it to
        a worker thread should os.dup() it so a concurrent close() cannot
        redirect the thread's pwrite into an unrelated file."""
        return self._ensure_fd()

    def record_piece(self, num: int, size: int, crc: int,
                     cost_ms: int = 0, verified: bool = False) -> PieceRecord:
        """Commit a piece whose bytes the native HTTP engine already landed
        at ``num * piece_size``, with ``crc`` computed in the same memory
        walk that wrote them. The caller must have verified ``crc`` against
        the expected digest BEFORE this call — registration is the commit
        point (mirrors write_piece: unverified bytes may sit in the file,
        but are invisible until a record claims them), and must only be
        used for pieces not yet recorded (write_piece's piece_is_new rule).
        ``verified=True`` asserts the crc matched an externally-announced
        digest (not merely self-computed)."""
        m = self.metadata
        if m.piece_size <= 0:
            raise StorageError("piece size not set")
        rec = PieceRecord(num=num, offset=num * m.piece_size, size=size,
                          digest=f"{pkgdigest.ALGORITHM_CRC32C}:{crc:08x}",
                          cost_ms=cost_ms)
        if verified:
            self._verified_pieces[num] = rec.digest
        return self._commit_piece_record(rec)

    def start_prefix_hasher(self, expected_digest: str) -> None:
        """Begin hashing the contiguous piece prefix in the background so
        ``validate_digest`` at completion is (near-)free. Idempotent;
        silently a no-op for unknown algorithms. Callers gate on
        ``completion_digest_applies`` — only tasks that will actually run
        the completion digest decision should pay for this. A bare
        algorithm name (``sha256``) starts it where the value is not known
        yet and will come with a parent's done: ``validate_digest`` is then
        given the value to hold the hash against."""
        if self._prefix_hasher is not None or not expected_digest:
            return
        try:
            algorithm = (pkgdigest.parse(expected_digest).algorithm
                         if ":" in expected_digest else expected_digest)
            # The hasher opens its own O_RDONLY fd immediately; make sure
            # the data file exists even before the first piece write.
            self._ensure_fd()
            self._prefix_hasher = _PrefixHasher(self, algorithm)
        except (ValueError, StorageError, OSError):
            return

    def digest_frontier(self) -> int:
        """Pieces the prefix hasher has hashed by now; 0 without one (the
        whole object is then still to hash)."""
        ph = self._prefix_hasher
        return ph._next if ph is not None else 0

    @staticmethod
    def completion_digest_applies(digest: str, ranged: bool) -> bool:
        """Would the completion-time whole-content digest decision run at
        all? Ranged tasks never (the digest names the full object; the
        store holds a slice); digestless tasks never. BOTH call sites —
        task_manager._finalize_content_digest (the decision point) and
        conductor._await_certification (the wait that tries to turn the
        decision into a skip) — share this gate so it can never fork."""
        return bool(digest) and not ranged

    def pieces_verified_against_digests(self) -> bool:
        """Every landed piece carries a verified-against digest — the
        necessary precondition for ANY certified map to engage the
        re-hash skip (pieces_all_digest_verified compares these values).
        False means a completion-time wait for certification is futile."""
        with self._meta_lock:
            return all(n in self._verified_pieces for n in self.metadata.pieces)

    def certifies(self, certified: "dict[int, str] | None") -> bool:
        """Pure predicate: would this candidate digest map certify the
        store — content complete and every piece's verified-against
        digest matching the map? The per-piece comparison is what makes
        provenance stick: pieces verified against a corrupt
        still-downloading parent's self-computed digests will not match
        an honest done parent's map, so they force the full re-hash
        instead of being laundered by it (reference parity: Dragonfly2
        children trust the verified piece-digest chain, pieceMd5Sign)."""
        if not certified or not self.is_complete():
            return False
        with self._meta_lock:
            return all(self._verified_pieces.get(n) is not None
                       and self._verified_pieces[n] == certified.get(n)
                       for n in self.metadata.pieces)

    def apply_certification(self, candidate_maps) -> bool:
        """Install the first candidate digest map that certifies the
        store (``certifies``); trying every map means a corrupt parent
        that completed first cannot mask an honest completed parent's
        certification. An already-installed verifying map is never
        downgraded; non-verifying candidates install nothing (the
        completion decision re-hashes either way). Returns True when a
        verifying map is installed."""
        if self.certifies(self.certified_digests):
            return True
        for m in candidate_maps:
            if self.certifies(m):
                # Snapshot: the candidate is the dispatcher's live
                # per-parent dict; a later re-announcement must not
                # mutate the installed certification.
                self.certified_digests = dict(m)
                return True
        return False

    def pieces_all_digest_verified(self) -> bool:
        """True when the installed ``certified_digests`` map (set at
        completion from a done parent's own announcements) certifies the
        store — the precondition for skipping the whole-content re-hash
        on completion. See ``certifies`` for the provenance argument."""
        return self.certifies(self.certified_digests)

    def _commit_piece_record(self, rec: PieceRecord, feed_chunks=None,
                             word_sums=None) -> PieceRecord:
        """The single metadata-commit point for all write paths (in-memory
        write_piece/write_piece_chunks and native-transport record_piece):
        record under the lock, then persist the piece map in batches so a
        daemon restart resumes from the bitmap (reference: checkpoint/
        resume of downloads). ``feed_chunks`` are the piece's in-memory
        bytes when the writer still holds them — the prefix hasher
        advances from memory instead of re-reading landed bytes (fed
        after the lock, in this worker thread, while the buffers are
        still owned by the caller). ``word_sums`` are the committed
        bytes' own (``write_piece``); a piece re-recorded without any loses
        the pair its old bytes carried."""
        with self._meta_lock:
            existing = self.metadata.pieces.get(rec.num)
            self.metadata.pieces[rec.num] = rec
            self.touch()
            if word_sums is not None:
                self._word_sums[rec.num] = word_sums
            elif existing is not None:
                self._word_sums.pop(rec.num, None)
            if existing is None:
                self._unsaved_pieces += 1
            ph = self._prefix_hasher
            if ph is not None:
                ph.piece_recorded(rec.num, existing is not None,
                                  will_feed=feed_chunks is not None)
        if ph is not None and feed_chunks is not None:
            ph.feed(rec.num, feed_chunks)
        if existing is None:
            self._piece_recorded_save()
        obs = self.observer
        if obs is not None:
            obs.piece_recorded(self.metadata.task_id, rec)
        return rec

    # -- unified read primitives (serve-side zero-copy, docs/ZERO_COPY.md) --
    #
    # ONE preadv engine under every read surface: read_into fills a caller
    # (usually pooled) buffer, read_spans_into packs disjoint spans, and
    # read_piece/read_range/export_range/validate/reverify are thin shapes
    # over them — the aiohttp serve path, the gateway, the ranged
    # local-parent import, and the dataset shard reader all read through
    # here instead of carrying private pread+bytes loops.

    def read_into(self, offset: int, length: int, buf, at: int = 0) -> None:
        """Fill ``buf[at:at+length]`` with file bytes [offset, offset+length)
        via preadv — no intermediate allocation. Raises StorageError on a
        short read (EOF inside the span: the caller asked for bytes the
        store never landed, or the file was truncated under us)."""
        if length <= 0:
            return
        mv = buf if isinstance(buf, memoryview) else memoryview(buf)
        if at + length > len(mv):
            raise StorageError(
                f"read buffer too small: need {at + length}, have {len(mv)}")
        _preadv_exact(self._ensure_fd(), mv[at:at + length], offset)

    def read_spans_into(self, spans, buf) -> int:
        """Pack the byte spans ``[(offset, length), ...]`` back to back into
        ``buf``; returns the total byte count. Spans may be disjoint; a
        short read anywhere raises StorageError with nothing partial
        hidden. This is the batched-submission primitive: a multi-span
        batch goes to the submission ring (storage/io_ring.py) as ONE
        submission — a native syscall batch (or io_uring / thread-pooled
        preadv, per the ring's ladder) — and bytes still land directly in
        the caller's (pooled) buffer, exactly as the serial loop landed
        them."""
        spans = list(spans)
        # One pass yields both the packing offsets and (as the final
        # accumulated value) the total byte count.
        buf_offsets = list(accumulate((ln for _, ln in spans), initial=0))
        total = buf_offsets.pop()
        mv = buf if isinstance(buf, memoryview) else memoryview(buf)
        if total > len(mv):
            raise StorageError(
                f"read buffer too small: need {total}, have {len(mv)}")
        if len(spans) > 1:
            ring = io_ring.get_ring()
            if ring.backend != "serial":
                try:
                    ring.read_spans(self._ensure_fd(), spans, mv,
                                    buf_offsets)
                except io_ring.ShortReadError as e:
                    raise StorageError(str(e)) from None
                self.touch()
                return total
        at = 0
        for offset, length in spans:
            self.read_into(offset, length, mv, at=at)
            at += length
        self.touch()
        return total

    def piece(self, num: int) -> PieceRecord:
        """Piece ``num``'s record (where it lies in the data file, its
        size): what a reader with a way of its own to cut the read needs
        beside ``read_into``."""
        rec = self.metadata.pieces.get(num)
        if rec is None:
            raise StorageError(f"piece {num} not found", Code.StoragePieceNotFound)
        return rec

    def read_piece_into(self, num: int, buf) -> PieceRecord:
        """Read piece ``num``'s bytes into ``buf`` (pooled or caller-owned);
        returns the piece record (size says how much of ``buf`` is valid)."""
        rec = self.piece(num)
        self.read_spans_into(((rec.offset, rec.size),), buf)
        return rec

    def read_piece(self, num: int) -> bytes:
        """Piece bytes as a fresh ``bytes`` — the compatibility/oracle shape
        (tests compare the serve paths and the device landing against it).
        Hot paths use read_piece_into with a buffer of their own instead:
        pooled on the serve side, a row of the staging stack in the device
        sink."""
        rec = self.piece(num)
        out = bytearray(rec.size)
        self.read_spans_into(((rec.offset, rec.size),), out)
        return bytes(out)

    def get_pieces(self, start_num: int = 0, limit: int = 0) -> list[PieceRecord]:
        """Contiguous-known pieces from start_num (upload-server listing —
        reference local_storage.go:434 GetPieces)."""
        out = []
        with self._meta_lock:  # writers mutate from worker threads
            nums = sorted(n for n in self.metadata.pieces if n >= start_num)
            for n in nums:
                out.append(self.metadata.pieces[n])
                if limit and len(out) >= limit:
                    break
        return out

    def has_piece(self, num: int) -> bool:
        return num in self.metadata.pieces

    def word_sums(self) -> dict[int, tuple[int, int]]:
        """num -> (sum32, xor32) of the pieces whose committing writer
        brought the pair (``write_piece``'s ``word_sums``); the others are
        the reader's to sum."""
        with self._meta_lock:
            return dict(self._word_sums)

    @property
    def data_path(self) -> str:
        """Path of the on-disk data file (upload server sendfile source)."""
        return self._data_path

    def downloaded_bytes(self) -> int:
        with self._meta_lock:  # writers mutate from worker threads
            return sum(p.size for p in self.metadata.pieces.values())

    def disk_usage(self) -> int:
        try:
            return os.path.getsize(self._data_path)
        except OSError:
            return 0

    # -- completion --------------------------------------------------------

    def is_complete(self) -> bool:
        m = self.metadata
        return (
            m.total_piece_count >= 0
            and len(m.pieces) >= m.total_piece_count
            and all(n in m.pieces for n in range(m.total_piece_count))
        )

    def mark_done(self) -> None:
        self.metadata.done = True
        self.touch()
        self.save_metadata()

    def mark_invalid(self) -> None:
        ph = self._prefix_hasher
        if ph is not None:
            self._prefix_hasher = None
            ph.stop()
        with self._meta_lock:
            self._word_sums.clear()
        self.metadata.invalid = True
        self.save_metadata()

    def validate_digest(self, expected: str = "") -> str:
        """Whole-content digest over piece ranges in order; checks against
        ``expected`` (or metadata digest) when present. Returns the actual
        digest string (reference local_storage.go:247)."""
        want = expected or self.metadata.digest
        algorithm = pkgdigest.parse(want).algorithm if want else pkgdigest.ALGORITHM_SHA256
        ph = self._prefix_hasher
        if ph is not None:
            # Detach unconditionally: an algorithm-mismatched hasher must
            # not keep pread'ing in parallel with the re-hash below.
            self._prefix_hasher = None
            if ph.algorithm != algorithm:
                ph.stop()
                ph = None
        if ph is not None:
            # The drain wait scales with content size: even a fully lagged
            # hasher re-reads from page cache and is faster than the cold
            # full re-hash below, so waiting is always cheaper than
            # falling through on a mere timeout.
            cl = self.metadata.content_length
            prefix_hex = ph.finish(
                timeout=max(60.0, cl / (50 << 20)) if cl > 0 else 60.0)
            if prefix_hex is not None:
                self.digest_pass = ("prefix", ph.disk_reads, ph.tail_chunks)
                return self._checked_digest(want, f"{algorithm}:{prefix_hex}")
            # Poisoned/timed-out hasher: fall through to the full re-hash
            # — and stop its threads so a merely-lagging hasher does not
            # keep pread'ing in parallel with the re-hash below.
            ph.stop()
        # The full re-hash: the same read-ahead, over every recorded piece
        # in order, this thread hashing.
        h = pkgdigest.new_hasher(algorithm)
        recs = [self.metadata.pieces[n] for n in sorted(self.metadata.pieces)]
        self._ensure_fd()   # the ring opens the data file by path
        ring = _ReadAhead(
            self._data_path, threading.Condition(),
            ((r.offset, r.size, r.num) for r in recs).__next__,
            self.metadata.task_id[:12])
        try:
            ring.hash_into(h)
        finally:
            ring.close()
        if ring.err is not None:
            raise StorageError(ring.err)
        self.digest_pass = ("rehash", len(recs), (ring.ready, ring.waited))
        return self._checked_digest(want, f"{algorithm}:{h.hexdigest()}")

    @staticmethod
    def _checked_digest(want: str, actual: str) -> str:
        if want and actual != want:
            raise StorageError(
                f"content digest mismatch: want {want}, got {actual}",
                Code.ClientPieceDownloadFail)
        return actual

    def reverify_pieces(self, threads: int = 0) -> list[int]:
        """Re-verify all crc32c-digested pieces against on-disk bytes; returns
        the piece numbers that fail. Uses the parallel C++ digest table when
        available (seed re-verification / dfcache import integrity sweep)."""
        recs = [self.metadata.pieces[n] for n in sorted(self.metadata.pieces)]
        crc_recs = [r for r in recs
                    if r.digest.startswith(pkgdigest.ALGORITHM_CRC32C + ":")]
        bad: list[int] = []
        native = _native()
        checked: set[int] = set()
        if native is not None and crc_recs:
            fd = self._ensure_fd()
            try:
                crcs = native.hash_pieces_crc(
                    fd, [r.offset for r in crc_recs],
                    [r.size for r in crc_recs], threads=threads)
            except OSError:
                # Truncated/unreadable data file: the native batch hasher
                # fails whole; fall through to the per-piece Python path,
                # which reports short reads as bad pieces instead of
                # crashing the sweep.
                pass
            else:
                for r, crc in zip(crc_recs, crcs):
                    if f"{pkgdigest.ALGORITHM_CRC32C}:{crc:08x}" != r.digest:
                        bad.append(r.num)
                checked = {r.num for r in crc_recs}
        py_recs = [r for r in recs if r.num not in checked and r.digest]
        if py_recs:
            mv = _READ_BUFFERS.acquire(max(r.size for r in py_recs))
            try:
                for r in py_recs:
                    d = pkgdigest.parse(r.digest)
                    try:
                        self.read_into(r.offset, r.size, mv)
                    except (StorageError, OSError):
                        bad.append(r.num)  # short read/unreadable = bad piece
                        continue
                    actual = pkgdigest.hash_bytes(d.algorithm, mv[:r.size])
                    if actual.encoded != d.encoded:
                        bad.append(r.num)
            finally:
                _READ_BUFFERS.release(mv)
        return sorted(bad)

    def covers_range(self, start: int, length: int) -> bool:
        """True when every piece overlapping [start, start+length) is
        present — the partial-reuse predicate (reference
        storage_manager.go:564 FindPartialCompletedTask checks piece
        coverage of the requested range the same way)."""
        m = self.metadata
        if m.piece_size <= 0 or length <= 0 or start < 0:
            return False
        if m.content_length >= 0 and start + length > m.content_length:
            return False
        first = start // m.piece_size
        last = (start + length - 1) // m.piece_size
        with self._meta_lock:  # writers mutate from worker threads
            return all(n in m.pieces for n in range(first, last + 1))

    def read_range(self, start: int, length: int) -> memoryview:
        """Bytes ``[start, start+length)`` — caller must have checked
        ``covers_range`` first (pieces sit at ``num * piece_size``, so
        covered bytes are literally contiguous in the data file). Returns
        a pooled memoryview filled by one preadv span (release via
        ``release_read_buffer`` on recycling paths)."""
        mv = _READ_BUFFERS.acquire(length)
        try:
            self.read_spans_into(((start, length),), mv)
        except BaseException:
            _READ_BUFFERS.release(mv)
            raise
        return mv

    def export_range(self, dest: str, start: int, length: int) -> None:
        """Write the byte range [start, start+length) to ``dest`` straight
        off the data file in bounded spans (caller checks covers_range
        first — covered bytes are contiguous, so no per-piece slicing)."""
        os.makedirs(os.path.dirname(os.path.abspath(dest)), exist_ok=True)
        mv = _READ_BUFFERS.acquire(min(4 << 20, length))
        try:
            remaining, off = length, start
            with open(dest, "wb") as out:
                while remaining > 0:
                    take = min(len(mv), remaining)
                    self.read_into(off, take, mv)
                    out.write(mv[:take])
                    off += take
                    remaining -= take
        finally:
            _READ_BUFFERS.release(mv)

    def store_to(self, dest: str, *, hardlink: bool = True) -> None:
        """Land the completed content at ``dest``: hardlink when possible,
        else copy (reference local_storage.go:353). Runs in worker threads
        (task_manager offloads it), so it serializes on a per-store lock,
        and the copy path writes a temp file + atomic rename — opening
        ``dest`` with O_TRUNC in place could truncate the task's own data
        file through a concurrently-created hardlink to the same inode."""
        if not self.is_complete():
            raise StorageError("task incomplete; refusing to store output")
        with self._output_lock:
            dest_dir = os.path.dirname(os.path.abspath(dest))
            os.makedirs(dest_dir, exist_ok=True)
            try:
                os.unlink(dest)
            except FileNotFoundError:
                pass
            # The data file is exactly the content when pieces are contiguous
            # from offset 0; truncate to content length guards a sparse tail.
            cl = self.metadata.content_length
            if cl >= 0 and self.disk_usage() != cl:
                with open(self._data_path, "r+b") as f:
                    f.truncate(cl)
            if hardlink:
                try:
                    os.link(self._data_path, dest)
                    return
                except FileExistsError:
                    return  # a concurrent lander won the race: same content
                except OSError:
                    pass
            tmp = f"{dest}.df-tmp-{os.getpid()}-{threading.get_ident()}"
            try:
                native = _native()
                if native is not None:
                    size = os.path.getsize(self._data_path)
                    in_fd = os.open(self._data_path, os.O_RDONLY)
                    out_fd = os.open(tmp, os.O_WRONLY | os.O_CREAT | os.O_EXCL,
                                     0o644)
                    try:
                        native.copy_range(in_fd, out_fd, size)
                    finally:
                        os.close(in_fd)
                        os.close(out_fd)
                else:
                    shutil.copyfile(self._data_path, tmp)
                os.replace(tmp, dest)
            finally:
                if os.path.exists(tmp):
                    os.unlink(tmp)
