"""Reusable piece-buffer pool for the zero-copy receive AND serve paths.

Piece bodies used to materialize as throwaway ``bytes`` at every hop
(``resp.read()``, ``bytes(buf[:piece_size])``, ``b"".join``) — at 4-32 MiB
a piece, that is allocator churn plus a full memory copy per hop on the
daemon's one hot core. The pool hands out ``memoryview`` windows over
recycled bytearrays instead; receive loops fill them in place, the store
writes straight from them, serve paths preadv into them, and release()
parks the backing buffer for the next piece.

Ownership rules (documented in docs/ZERO_COPY.md):
  - acquire() transfers ownership to the caller; exactly one release()
    returns it. Double-release is refused (the buffer is already free).
  - A released view must not be read again — the next acquire() will
    overwrite its bytes.
  - Consumers that must RETAIN piece bytes past the call that handed them
    over (caches) must copy (``bytes(view)``); everything on the
    receive→verify→store→serve path only borrows. Device sinks own the
    buffers the store reads into (pool ``hbm_stage``, ops/hbm_sink.py).

Every pool is observable: acquire/release counts and retained bytes feed
the shared Prometheus registry (``bufpool_acquires_total{pool=...}``,
``bufpool_retained_bytes{pool=...}``) so any binary's metrics endpoint
(pkg/metrics_server) exposes read-path buffer behavior, and the
acquire/release balance is assertable in leak-guard tests
(``outstanding`` in stats()).
"""

from __future__ import annotations

import threading

from dragonfly2_tpu.pkg import metrics

_MB = 1 << 20

BUFPOOL_ACQUIRES = metrics.counter(
    "bufpool_acquires_total",
    "Buffer-pool acquires (pooled-hit vs fresh allocation)",
    ("pool", "source"))
BUFPOOL_RELEASES = metrics.counter(
    "bufpool_releases_total",
    "Buffer-pool releases (retained for reuse vs dropped over cap)",
    ("pool", "outcome"))
BUFPOOL_RETAINED = metrics.gauge(
    "bufpool_retained_bytes",
    "Bytes currently parked in the buffer-pool free list", ("pool",))


class BufferPool:
    """Free-list of bytearrays, bounded by total retained bytes. Thread-safe
    (release happens on worker threads after off-loop store writes/reads)."""

    def __init__(self, max_retained_bytes: int = 64 * _MB,
                 name: str = "default"):
        self.name = name
        self._free: list[bytearray] = []
        self._retained = 0
        self._max_retained = max_retained_bytes
        self._mu = threading.Lock()
        self._acquires = 0
        self._releases = 0
        # Labeled children resolved once: .labels() is a dict lookup plus
        # tuple hash per call — measurable at per-piece frequency.
        self._m_pooled = BUFPOOL_ACQUIRES.labels(name, "pooled")
        self._m_fresh = BUFPOOL_ACQUIRES.labels(name, "fresh")
        self._m_retained_rel = BUFPOOL_RELEASES.labels(name, "retained")
        self._m_dropped_rel = BUFPOOL_RELEASES.labels(name, "dropped")
        self._m_retained_bytes = BUFPOOL_RETAINED.labels(name)

    def acquire(self, size: int) -> memoryview:
        """A writable ``memoryview`` of exactly ``size`` bytes over a pooled
        (or fresh) bytearray."""
        size = max(size, 1)
        with self._mu:
            self._acquires += 1
            # First fit that's large enough; the fleet of piece buffers in
            # one daemon is near-uniform in size, so this is ~always hit #0.
            for i, ba in enumerate(self._free):
                if len(ba) >= size:
                    self._free.pop(i)
                    self._retained -= len(ba)
                    self._m_pooled.inc()
                    self._m_retained_bytes.set(self._retained)
                    return memoryview(ba)[:size]
        self._m_fresh.inc()
        return memoryview(bytearray(size))

    def release(self, view: "memoryview | bytearray | bytes | None") -> None:
        """Return a buffer obtained from acquire(). Tolerant of plain bytes
        (non-pooled fallback paths): those are simply dropped."""
        if isinstance(view, memoryview):
            obj = view.obj
            view.release()
        else:
            obj = view
        if not isinstance(obj, bytearray):
            return
        with self._mu:
            self._releases += 1
            if self._retained + len(obj) <= self._max_retained:
                self._free.append(obj)
                self._retained += len(obj)
                self._m_retained_rel.inc()
                self._m_retained_bytes.set(self._retained)
            else:
                self._m_dropped_rel.inc()

    def stats(self) -> dict:
        with self._mu:
            return {"free_buffers": len(self._free),
                    "retained_bytes": self._retained,
                    "acquires": self._acquires,
                    "releases": self._releases,
                    # Views handed out and not yet returned. Paths that
                    # legitimately drop views (the pool only loses reuse)
                    # keep this >0; leak-guard tests snapshot before/after
                    # a balanced path and assert the DELTA is zero.
                    "outstanding": self._acquires - self._releases}
