"""Rolling-hash content-defined chunking (gear CDC).

The cut decision at byte ``i`` depends ONLY on the ``WINDOW`` bytes
ending at ``i`` (the gear hash is a shifted sum over a sliding window,
never reset at cut points), so identical content regions produce
identical chunk boundaries regardless of what precedes them — inserting
or deleting bytes re-chunks the file locally and every chunk outside the
edit neighborhood keeps its digest. That is the property the delta plane
buys dedup with: version N+1's manifest mostly names chunks version N
already landed.

Determinism contract: the gear table is derived from SHA-256 (no process
seed), the hash window is fixed, and ``feed()`` may split the stream
anywhere — the emitted chunk sequence is a pure function of (content,
params). tests/test_delta.py pins split-independence and the
shift-resistance property; tests/test_chunker_oracle.py pins that every
backend produces byte-identical cut points.

The candidate scan (hash every position, report the rare ones whose top
``mask_bits`` are zero) is the hot loop and sits behind a backend ladder
selected the way pkg/digest picks crc32c implementations:

  native  — dragonfly2_tpu/native/src/dfchunk.cc, interleaved scalar
            recurrences (~GB/s; ships the same SHA-256 gear table down)
  numpy   — log-doubling shifted-sum convolution (~tens of MiB/s)
  python  — per-byte rolling hash (correctness fallback)

``chunker_backend()`` reports the selection; DF_CHUNKER_BACKEND forces
one ladder rung (tests pin one to compare cut points across rungs).
min/max/forced-cut selection (``_emit``) is shared by all backends, so a
backend can only ever change speed, never cut points.
"""

from __future__ import annotations

import hashlib
import os
from dataclasses import dataclass

try:
    import numpy as np
except ImportError:          # pragma: no cover - numpy is everywhere in CI
    np = None

from dragonfly2_tpu.pkg import metrics

# Sliding window of the gear hash: how many bytes influence a cut
# decision. The hash is the classic gear recurrence h = 2h + gear[b]
# carried mod 2^32, whose infinite-window form is EXACTLY a 32-byte
# window (older contributions shift out of the register) — so 32 is not
# a tuning choice, it is the arithmetic.
WINDOW = 32

# Gear table: 256 deterministic 32-bit values (sha256 of the byte value;
# NOT random.seed — two builds must always agree).
_GEAR_LIST = [
    int.from_bytes(hashlib.sha256(bytes([i])).digest()[:4], "little")
    for i in range(256)
]
_GEAR = np.array(_GEAR_LIST, dtype=np.uint32) if np is not None else None
_GEAR_BYTES = b"".join(v.to_bytes(4, "little") for v in _GEAR_LIST)

CHUNKER_BACKEND_ACTIVE = metrics.gauge(
    "delta_chunker_backend",
    "Selected CDC candidate-scan backend (1 = active; ladder "
    "native > numpy > python, see delta/chunker.py)", ("backend",))


@dataclass(frozen=True)
class CDCParams:
    """Chunking geometry. ``mask_bits`` sets the expected spacing of cut
    candidates (2^mask_bits bytes); the expected chunk size is
    ``min_size + 2^mask_bits`` (candidates inside the first ``min_size``
    bytes of a chunk are skipped). Defaults target ~1.25 MiB chunks with
    hard [256 KiB, 4 MiB] bounds."""

    mask_bits: int = 20
    min_size: int = 256 << 10
    max_size: int = 4 << 20

    def __post_init__(self):
        if not (0 < self.min_size <= self.max_size):
            raise ValueError(f"bad CDC bounds [{self.min_size}, {self.max_size}]")
        if not (1 <= self.mask_bits <= 31):
            raise ValueError(f"bad mask_bits {self.mask_bits}")


@dataclass(frozen=True)
class Chunk:
    offset: int
    length: int
    sha256: str        # hex, no "sha256:" prefix

    @property
    def end(self) -> int:
        return self.offset + self.length


def _window_hashes(data) -> "np.ndarray":
    """H[i] = sum_{j<WINDOW} gear[data[i-j]] << j (mod 2^32), vectorized.

    Computed by log-doubling instead of one pass per window position:
    with H_k[i] = sum_{j<2^k} gear[data[i-j]] << j, the next level is
    H_{k+1}[i] = H_k[i] + (H_k[i - 2^k] << 2^k) — so the 32-byte window
    is ONE table gather plus log2(32) = 5 ping-ponged shifted-add passes
    (the naive form's one-gather-per-position measured ~10x slower).
    Positions with a partial window (i < WINDOW-1) use the available
    prefix — callers pass WINDOW-1 bytes of left context except at
    stream start, where the zero-padded prefix is itself deterministic."""
    n = len(data)
    h = _GEAR[data]
    if n < 2:
        return h
    tmp = np.empty_like(h)
    span = 1
    while span < min(WINDOW, n):
        np.left_shift(h[:-span], np.uint32(span), out=tmp[span:])
        tmp[span:] += h[span:]
        tmp[:span] = h[:span]
        h, tmp = tmp, h
        span *= 2
    return h


# --------------------------------------------------------------------- #
# Candidate-scan backends. Each takes (region, ctx, mask_bits) — region
# is a bytes-like whose first ctx bytes are left context — and returns
# ascending region-relative indices (>= ctx) of bytes whose gear hash
# has its top mask_bits zero. Identical output is pinned by
# tests/test_chunker_oracle.py; _emit turns candidates into cuts.
# --------------------------------------------------------------------- #

def _scan_python(region, ctx: int, mask_bits: int) -> list[int]:
    limit = 1 << (32 - mask_bits)
    gear = _GEAR_LIST
    h = 0
    out = []
    for i, b in enumerate(memoryview(region)):
        h = ((h << 1) + gear[b]) & 0xFFFFFFFF
        if h < limit and i >= ctx:
            out.append(i)
    return out


def _scan_numpy(region, ctx: int, mask_bits: int) -> list[int]:
    data = np.frombuffer(region, dtype=np.uint8)
    h = _window_hashes(data)[ctx:]
    shift = np.uint32(32 - mask_bits)
    return [ctx + int(i)
            for i in np.nonzero((h >> shift) == np.uint32(0))[0]]


def _native_scanner():
    """The dfchunk.cc kernel as a scan function, or None. Self-checked
    against the pure-python reference on a deterministic vector before
    selection (mirrors pkg/digest's probe discipline)."""
    try:
        from dragonfly2_tpu.native import binding
    except ImportError:
        return None
    if not hasattr(binding, "chunk_scan"):
        return None      # stale prebuilt library without the kernel

    def scan(region, ctx: int, mask_bits: int) -> list[int]:
        return binding.chunk_scan(region, _GEAR_BYTES, mask_bits, ctx)

    probe = hashlib.sha256(b"dfchunk-probe").digest() * 256   # 8 KiB
    try:
        if scan(probe, 5, 8) != _scan_python(probe, 5, 8):
            return None
    except Exception:
        return None
    return scan


_scanner = None
_backend_name = "unset"


def _select_scanner():
    """Pick the fastest available backend (native > numpy > python),
    honoring DF_CHUNKER_BACKEND={native,numpy,python} to pin a rung."""
    global _scanner, _backend_name
    forced = os.environ.get("DF_CHUNKER_BACKEND", "").strip().lower()
    native = None if forced in ("numpy", "python") else _native_scanner()
    if native is not None:
        _scanner, _backend_name = native, "native"
    elif np is not None and forced != "python":
        _scanner, _backend_name = _scan_numpy, "numpy"
    else:
        _scanner, _backend_name = _scan_python, "python"
    CHUNKER_BACKEND_ACTIVE.labels(_backend_name).set(1)
    return _scanner


def chunker_backend() -> str:
    """Which candidate-scan implementation chunking uses:
    "native" (dfchunk.cc), "numpy", or "python"."""
    if _scanner is None:
        _select_scanner()
    return _backend_name


class GearChunker:
    """Streaming CDC chunker: ``feed()`` arbitrary byte chunks (any
    split), collect emitted ``Chunk``s from ``feed``'s return value (or
    ``chunks`` afterwards), then ``finish()`` for the tail. Offsets are
    absolute stream offsets; chunks are contiguous and exactly cover the
    stream."""

    def __init__(self, params: CDCParams | None = None):
        self.params = params or CDCParams()
        self.chunks: list[Chunk] = []
        self._tail = bytearray()        # bytes not yet emitted
        self._tail_start = 0            # absolute offset of _tail[0]
        self._scanned = 0               # absolute position hashed so far
        self._cands: list[int] = []     # absolute cut positions (chunk END)
        self._ci = 0                    # consumed prefix of _cands
        self._finished = False
        if _scanner is None:
            _select_scanner()

    # -- feeding -----------------------------------------------------------

    def feed(self, data: bytes) -> list[Chunk]:
        """Consume ``data``; returns the chunks this call completed."""
        if self._finished:
            raise RuntimeError("feed() after finish()")
        if not data:
            return []
        self._tail += data
        self._scan()
        return self._emit()

    def finish(self) -> list[Chunk]:
        """End of stream: the remaining tail becomes the final chunk
        (shorter than min_size is legal only here)."""
        self._finished = True
        out = self._emit()
        if self._tail:
            out.append(self._cut(len(self._tail)))
        return out

    @property
    def consumed(self) -> int:
        return self._tail_start + len(self._tail)

    # -- internals ---------------------------------------------------------

    # One scan block: bounds the numpy backend's uint64 temporaries to
    # ~3 x 8 x 4 MiB regardless of how much one feed() delivers.
    _SCAN_BLOCK = 4 << 20

    def _scan(self) -> None:
        """Scan the not-yet-scanned region (with WINDOW-1 bytes of left
        context so boundaries are split-independent) and append new cut
        candidates. Processes in bounded blocks through the selected
        backend; the cut condition — the TOP mask_bits of the hash are
        zero — sees the whole 32-byte window at every mask width."""
        scan = _scanner
        while True:
            lo = self._scanned - self._tail_start   # first unscanned, tail-rel
            hi = min(len(self._tail), lo + self._SCAN_BLOCK)
            if hi <= lo:
                return
            ctx = min(lo, WINDOW - 1)
            region = memoryview(self._tail)[lo - ctx:hi]
            for i in scan(region, ctx, self.params.mask_bits):
                # Cut AFTER the matching byte: chunk end = position + 1.
                self._cands.append(self._scanned + (i - ctx) + 1)
            self._scanned = self._tail_start + hi

    def _emit(self) -> list[Chunk]:
        p = self.params
        # Decide every cut first, then materialize them off one view and
        # trim the tail ONCE — the per-chunk `del tail[:length]` memmove
        # was O(tail x chunks) when a feed() completed many chunks.
        lengths: list[int] = []
        start = self._tail_start
        while True:
            # First candidate cut that respects min_size for this chunk.
            while (self._ci < len(self._cands)
                   and self._cands[self._ci] - start < p.min_size):
                self._ci += 1
            cut = -1
            if self._ci < len(self._cands):
                c = self._cands[self._ci]
                if c - start <= p.max_size:
                    cut = c - start
            if cut < 0 and self._scanned - start >= p.max_size:
                cut = p.max_size                    # forced cut at the bound
            if cut < 0:
                break
            lengths.append(cut)
            start += cut
        if not lengths:
            return []
        out: list[Chunk] = []
        mv = memoryview(self._tail)
        off = 0
        for length in lengths:
            ck = Chunk(self._tail_start + off, length,
                       hashlib.sha256(mv[off:off + length]).hexdigest())
            out.append(ck)
            self.chunks.append(ck)
            off += length
        del mv
        del self._tail[:off]
        self._tail_start += off
        return out

    def _cut(self, length: int) -> Chunk:
        view = memoryview(self._tail)[:length]
        ck = Chunk(self._tail_start, length,
                   hashlib.sha256(view).hexdigest())
        del view
        del self._tail[:length]
        self._tail_start += length
        self.chunks.append(ck)
        return ck


def chunk_bytes(data: bytes, params: CDCParams | None = None) -> list[Chunk]:
    """One-shot chunking of in-memory content."""
    ch = GearChunker(params)
    ch.feed(data)
    ch.finish()
    return ch.chunks
