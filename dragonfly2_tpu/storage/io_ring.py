"""Batched-IO submission ring: many spans, one syscall.

The store engine's multi-span serves (ranged gateway reads, delta-chunk
span fetches) and chunked landings used to pay one preadv/pwritev per
span. This module batches them behind ``LocalTaskStore.read_spans_into``
and ``write_piece_chunks`` — no caller changes, and the pooled-buffer
discipline of docs/ZERO_COPY.md rule 6 is untouched: bytes land directly
in the caller's (usually pooled) buffer, nothing is allocated or copied
here.

Backend ladder, selected once per process (DF_RING_BACKEND pins a rung):

  batch    — the whole batch goes to native/src/dfring.cc in ONE
             Python->C call; completion is a tight p{read,write} loop.
             Default rung: it removes the ~1.4 us/span of interpreter
             overhead the serial path pays, and on page-cache-hot or
             tmpfs-backed stores the syscall fast path (~0.7 us/span
             measured) beats an io_uring op (~1.5 us/span measured, all
             setup-flag and READ_FIXED variants — the per-op io_uring
             setup exceeds the whole syscall when data is DRAM-hot).
  io_uring — dfring.cc fills SQEs in userspace and submits a whole
             batch with ONE io_uring_enter (raw syscalls, no liburing).
             Pinnable for stores on genuinely asynchronous media (cold
             NVMe/spinning reads at depth) via DF_RING_BACKEND=io_uring.
  threads  — a small worker pool issues the existing preadv/pwritev
             calls concurrently (boxes without the native library).
  serial   — the plain per-span loop (forced via DF_RING_BACKEND=serial/
             off).

Every backend produces byte-identical results and the same failure
shapes: EOF inside a requested span raises ShortReadError (the store
translates it to the StorageError its serial path raises), IO errors
raise OSError. Scrapeable as storage_ring_submissions_total{backend}
(one per batch) and storage_ring_spans_total{op} (spans carried).
"""

from __future__ import annotations

import os
import threading
from concurrent.futures import ThreadPoolExecutor

from dragonfly2_tpu.pkg import metrics

RING_SUBMISSIONS = metrics.counter(
    "storage_ring_submissions_total",
    "Batched-IO submissions by backend (one per batch, however many "
    "spans it carries)", ("backend",))
RING_SPANS = metrics.counter(
    "storage_ring_spans_total",
    "Spans (reads) and chunks (writes) carried by batched-IO "
    "submissions", ("op",))

_DEPTH = 64          # SQ entries; batches longer than this wave internally
_POOL_WORKERS = 4


class ShortReadError(OSError):
    """EOF inside a requested span — the bytes were never landed or the
    file was truncated under us. Callers map this to the same
    StorageError the serial read path raises."""

    def __init__(self, detail: str = "EOF inside requested span"):
        super().__init__(5, f"short read: {detail}")


def _read_span(fd: int, offset: int, length: int, mv) -> None:
    """The serial per-span primitive (same loop read_into always ran)."""
    got = 0
    while got < length:
        n = os.preadv(fd, [mv[got:length]], offset + got)
        if n <= 0:
            raise ShortReadError(
                f"at offset {offset + got}: {got}/{length} bytes (EOF)")
        got += n


def _write_chunk(fd: int, offset: int, mv) -> None:
    put = 0
    length = len(mv)
    while put < length:
        put += os.pwrite(fd, mv[put:], offset + put)


class SubmissionRing:
    """One process-wide batch submitter. ``backend`` says which rung of
    the ladder is live; read/write semantics are identical on every rung
    (tests/test_io_ring.py pins byte-equality and failure shapes)."""

    def __init__(self, backend: str, handle: int | None = None,
                 binding=None):
        self.backend = backend
        self._handle = handle
        self._binding = binding
        self._pool: ThreadPoolExecutor | None = None
        self._pool_lock = threading.Lock()
        # Labeled metric children resolved once: label lookup is ~1.5 us
        # and the batch path budgets single-digit microseconds per layer.
        self._m_subs = RING_SUBMISSIONS.labels(backend)
        self._m_read = RING_SPANS.labels("read")
        self._m_write = RING_SPANS.labels("write")

    # -- plumbing ----------------------------------------------------------

    def _executor(self) -> ThreadPoolExecutor:
        with self._pool_lock:
            if self._pool is None:
                self._pool = ThreadPoolExecutor(
                    max_workers=_POOL_WORKERS,
                    thread_name_prefix="df-ioring")
            return self._pool

    def close(self) -> None:
        """Release backend resources (tests; the process singleton lives
        for the process). Owner's last call, per the native handle
        contract."""
        if self._handle is not None and self._binding is not None:
            self._binding.ring_close(self._handle)
            self._handle = None
        if self._pool is not None:
            self._pool.shutdown(wait=True)
            self._pool = None

    # -- batched reads -----------------------------------------------------

    def read_spans(self, fd: int, spans, buf, buf_offsets) -> int:
        """Fill ``buf`` at ``buf_offsets[i]`` with span ``spans[i]`` =
        (file_offset, length); one submission for the whole batch.
        Returns total bytes. Raises ShortReadError / OSError."""
        spans = spans if isinstance(spans, list) else list(spans)
        if any(ln <= 0 for _, ln in spans):     # rare: drop empty spans
            work = [((off, ln), at)
                    for (off, ln), at in zip(spans, buf_offsets) if ln > 0]
            spans = [s for s, _ in work]
            buf_offsets = [at for _, at in work]
        if not spans:
            return 0
        self._m_subs.inc()
        self._m_read.inc(len(spans))
        if self.backend == "batch":
            try:
                return self._binding.batch_read(fd, spans, buf, buf_offsets)
            except self._binding.RingShortRead:
                raise ShortReadError() from None
        if self.backend == "io_uring":
            try:
                return self._binding.ring_read_batch(
                    self._handle, fd, spans, buf, buf_offsets)
            except self._binding.RingShortRead:
                raise ShortReadError() from None
        mv = buf if isinstance(buf, memoryview) else memoryview(buf)
        total = sum(ln for _, ln in spans)
        if self.backend == "threads" and len(spans) > 1:
            futs = [self._executor().submit(
                _read_span, fd, off, ln, mv[at:at + ln])
                for (off, ln), at in zip(spans, buf_offsets)]
            for f in futs:
                f.result()
            return total
        for (off, ln), at in zip(spans, buf_offsets):
            _read_span(fd, off, ln, mv[at:at + ln])
        return total

    # -- batched writes ----------------------------------------------------

    def write_chunks(self, fd: int, chunks, offsets) -> int:
        """Write each bytes-like in ``chunks`` at ``offsets[i]``; one
        submission for the whole batch. Returns total bytes written."""
        work = [(c, off) for c, off in zip(chunks, offsets) if len(c)]
        if not work:
            return 0
        self._m_subs.inc()
        self._m_write.inc(len(work))
        if self.backend == "batch":
            return self._binding.batch_write(
                fd, [c for c, _ in work], [off for _, off in work])
        if self.backend == "io_uring":
            return self._binding.ring_write_batch(
                self._handle, fd, [c for c, _ in work],
                [off for _, off in work])
        total = 0
        if self.backend == "threads" and len(work) > 1:
            futs = []
            for c, off in work:
                mv = c if isinstance(c, memoryview) else memoryview(c)
                futs.append(self._executor().submit(
                    _write_chunk, fd, off, mv))
                total += len(mv)
            for f in futs:
                f.result()
            return total
        for c, off in work:
            mv = c if isinstance(c, memoryview) else memoryview(c)
            _write_chunk(fd, off, mv)
            total += len(mv)
        return total


# --------------------------------------------------------------------- #
# Selection (ladder probed once; DF_RING_BACKEND pins a rung)
# --------------------------------------------------------------------- #

_ring: SubmissionRing | None = None
_ring_lock = threading.Lock()


def _probe_batch() -> SubmissionRing | None:
    try:
        from dragonfly2_tpu.native import binding
    except ImportError:
        return None
    if not hasattr(binding, "batch_read"):
        return None          # stale prebuilt library without df_batch_*
    return SubmissionRing("batch", binding=binding)


def _probe_io_uring() -> SubmissionRing | None:
    try:
        from dragonfly2_tpu.native import binding
    except ImportError:
        return None
    if not hasattr(binding, "ring_create"):
        return None          # stale prebuilt library without dfring
    try:
        handle = binding.ring_create(_DEPTH)
    except OSError:
        return None          # ENOSYS/EPERM: kernel refuses io_uring
    return SubmissionRing("io_uring", handle=handle, binding=binding)


def _select_ring() -> SubmissionRing:
    forced = os.environ.get("DF_RING_BACKEND", "").strip().lower()
    if forced in ("serial", "off", "none"):
        return SubmissionRing("serial")
    if forced == "threads":
        return SubmissionRing("threads")
    if forced == "io_uring":
        ring = _probe_io_uring()
        if ring is not None:
            return ring
        # A pinned rung that probes unavailable falls through —
        # degrading beats breaking IO.
    ring = _probe_batch()
    if ring is not None:
        return ring
    return SubmissionRing("threads")


def get_ring() -> SubmissionRing:
    """The process-wide submission ring (lazy; see module docstring)."""
    global _ring
    if _ring is None:
        with _ring_lock:
            if _ring is None:
                _ring = _select_ring()
    return _ring


def ring_backend() -> str:
    """Which submission backend the store uses: "batch", "io_uring",
    "threads", or "serial"."""
    return get_ring().backend


def swap_ring(ring: SubmissionRing | None) -> SubmissionRing | None:
    """Install ``ring`` as the process singleton and return the previous
    one (None = re-probe lazily). Test/benchmark hook: the paired
    ring-on/ring-off rounds flip backends mid-process with this."""
    global _ring
    with _ring_lock:
        prev, _ring = _ring, ring
    return prev
