"""HBM sink: land verified pieces directly into TPU device memory.

The ``--device=tpu`` sink from BASELINE.json: instead of hardlinking a
completed task to disk, the daemon hands pieces to an HBMSink which stages
them into device-resident batches, verifies on-device checksums against
host-side values, and exposes the result as a JAX array (bitcast to the
checkpoint dtype) or a mesh-sharded array for the slice.

Architecture: **land-by-append + one-shot assembly**. Scattering each
piece batch into one flat preallocated buffer would copy the whole buffer
per flush (a donated dynamic-update-slice does): O(buffer) per flush and
quadratic over a download. This design does zero buffer mutation during
arrival:

  * ``land_piece`` stages a piece as one row of a host stack of
    ``batch_pieces`` rows; ``flush`` moves the stack to the sink's device
    and puts its rows into slot order there. The content is not touched
    again until consumption.
  * consumption assembles all batches into the flat uint32 content ONCE
    with a fused slice+concatenate jit that also folds the per-piece
    (sum32, xor32) checksums from the same staged copy.

Host staging: the sink owns its stacks, and they are reused. A stack comes
from a process-wide free list (``pkg/bufpool``, pool ``hbm_stage``), so
its pages have been touched before: a fresh 32 MiB buffer costs 32-41 ms
of page faults on the chip's host, a fresh 256 MiB stack 260 ms (PERF.md
section 5), against 35-65 ms for the transfer itself. The daemon reads a
piece from its store straight into ``next_row()`` and hands that row to
``land_piece``, which then checksums it where it lies; any other bytes
are copied into the row once. Rows lie in arrival order. ``flush`` puts
the stack and dispatches ``_reorder_jit``, a row gather by a traced
permutation, so a staged batch is in slot order whatever the arrival
order was and the assembly plan (a static argument: one compile each)
depends on which pieces shared a batch, not on their order in it. The
gather's output is a device buffer of its own, and only when it is ready
does the stack go back to the free list: ``jax.device_put`` returns
before the runtime has read the host buffer, and on the CPU backend an
aligned buffer is aliased, not copied, for the device array's whole life.

Host passes: a piece is passed over twice on the host, by the read into
its row (the daemon's, ``daemon/peer/device_sink.py``) and by the checksum
of that row (``land_piece``). Both are plain memory traffic that lets go
of the GIL, so a piece of at least two ``_CHUNK_FLOOR``s has each pass cut
into word-aligned chunks (``cuts``), views of the row itself, which a few
helper threads run side by side (``side_by_side``) while the thread that
lands the piece waits for all of them. The helpers hold no sink state and
stamp nothing; the piece's checksum is the fold of its chunks' and is
``checksum_numpy`` of the row, bit for bit. Smaller pieces are handled
whole where they are, with no hand-over.

Rates: not measured on this round's chip. Memory, as the v5e compiler
reports it for the assembly program (``memory_analysis()``,
tests/test_chip_compile.py): staged batches (argument) + flat content
(output) + a content-sized temporary for the checksum reshape = **3x
content** while the program runs, **5x** on the fragmented-arrival gather
path (4x was read at 2 GiB of 4 MiB pieces). So one chip's share above
roughly 5 GiB (3 GiB fragmented) cannot land on a 16 GB v5e. Staging
batches are dropped after a verified complete assembly, which leaves 1x
resident.

Consumers read the flat word buffer through ops/bitview.py: a byte or
16-bit view made with a plain ``bitcast_convert_type`` is padded 32-128x
by the TPU's tiled layout and is refused at checkpoint-shard sizes.

No reference analog: Dragonfly2's terminal store is the filesystem
(client/daemon/storage); ours is HBM.
"""

from __future__ import annotations

import functools
import operator
import threading
import time
from concurrent.futures import ThreadPoolExecutor, wait

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from dragonfly2_tpu.ops import bitview
from dragonfly2_tpu.ops.checksum import (
    _chunk_checksums_xla,
    checksum_numpy,
)
from dragonfly2_tpu.pkg import dflog, flight, metrics
from dragonfly2_tpu.pkg.bufpool import BufferPool
from dragonfly2_tpu.pkg.piece import PIECE_SIZE_LIMIT

log = dflog.get("ops.hbm_sink")

# A landing sink holds at most this many staging stacks: the one it fills
# and the one the runtime may still be reading. A put takes 35-65 ms and its
# reorder 9.6; since PR 28 the next batch of 8 x 32 MiB is read and
# checksummed in about 60 ms (200-300 before), so the wait for the older
# stack is no longer far off. On the chip it was still not met
# (land_stage_ms 3.6 a re-land, as before; PERF.md section 5): a host pass
# that gets faster again meets the link here first.
_STACKS_PER_SINK = 2
# The free list of staging stacks, shared by every sink of the process: a
# second landing touches no new page. It keeps what a daemon at its
# defaults can have in use at once: max_tasks (4) sinks mid-landing, each
# with _STACKS_PER_SINK stacks of batch_pieces (8) pieces of the largest
# piece size: 2 GiB. What is given back beyond that is dropped, which
# costs the next landing its page faults and nothing else.
_STAGING = BufferPool(_STACKS_PER_SINK * 4 * 8 * PIECE_SIZE_LIMIT,
                      name="hbm_stage")
SINK_ROWS = metrics.counter(
    "device_sink_rows_total",
    "Pieces staged for the device: read from the store into the sink's own "
    "row (in_place) or copied there from the caller's bytes (copied)",
    ("how",))
_ROWS_IN_PLACE = SINK_ROWS.labels("in_place")
_ROWS_COPIED = SINK_ROWS.labels("copied")
SINK_PIECES = metrics.counter(
    "device_sink_pieces_total",
    "Pieces landed, by how their host passes (read-back, checksum) ran: cut "
    "into chunks over the helper threads (split) or on the landing thread "
    "alone (whole)",
    ("how",))
_PIECES_SPLIT = SINK_PIECES.labels("split")
_PIECES_WHOLE = SINK_PIECES.labels("whole")

# A host pass over a piece is cut into at most _HELPERS chunks of about
# _CHUNK_FLOOR bytes or more each; under two floors it is not cut. Fixed
# from a re-land on the chip's 13-core host (PERF.md section 6, PR 28): at
# 32 MiB pieces 4 helpers land in 0.45 s what one thread lands in 1.16, 2 in
# 0.75 and 8 in 0.39; at 8 MiB pieces 4 chunks of 2 MiB land a shard in 0.105
# s against 0.178 whole, 2 of 4 MiB in 0.13, and with 8 of 1 MiB the checksum
# is slower than whole: a hand-over costs what 1 MiB of either pass does.
_CHUNK_FLOOR = 2 << 20
_HELPERS = 4
# A chunk is whole pages of the store's file (a piece begins on one), hence
# whole words of the row.
_CHUNK_ALIGN = 4096
# The helper threads, one pool for the process as _STAGING is one free
# list: a thread starts when a chunk is handed over and none is idle, so a
# process that splits no piece has none.
_POOL = ThreadPoolExecutor(max_workers=_HELPERS,
                           thread_name_prefix="df-sink-helper")


def cuts(size: int) -> "list[tuple[int, int]]":
    """The ``(start, stop)`` byte ranges a pass over ``size`` bytes is cut
    into: as many as there are helpers, as long as each holds a floor, and
    one, the whole, under two floors."""
    parts = min(_HELPERS, size // _CHUNK_FLOOR)
    if parts < 2:
        return [(0, size)]
    step = -(-size // parts)
    step += (-step) % _CHUNK_ALIGN
    return [(at, min(at + step, size)) for at in range(0, size, step)]


def side_by_side(fn, ranges) -> list:
    """``fn(start, stop)`` of every range, on the helper threads at once;
    the results in the ranges' order. Returns, or raises the first
    failure, only when EVERY call has come back: a range is a view of a
    buffer that its owner may give away the moment this returns."""
    futures = [_POOL.submit(fn, start, stop) for start, stop in ranges]
    wait(futures)
    try:
        return [f.result() for f in futures]
    finally:
        # A failure holds this frame (its traceback) and is held by its
        # future: with the futures still in the frame that is a cycle, and
        # the failed landing's sink, three frames up, would keep its stacks
        # and its HBM until the cyclic collector came by.
        del futures


def checksum_row(row: np.ndarray, ranges) -> "tuple[int, int]":
    """``checksum_numpy(row)`` of a row of whole words, taken range by
    range (``cuts`` of its size) where there are several: sum32 is the
    ranges' sums mod 2^32 and xor32 the xor of their xors, so the cut
    changes no bit."""
    if len(ranges) < 2:
        return checksum_numpy(row)
    parts = side_by_side(lambda a, b: checksum_numpy(row[a:b]), ranges)
    return (sum(s for s, _ in parts) & 0xFFFFFFFF,
            functools.reduce(operator.xor, (x for _, x in parts)))


def _give_back(view: memoryview) -> None:
    """A stack back to the free list. Its bytearray, not the view: a view
    that is released lets go of the memory, and should an array over it
    still be alive somewhere (the runtime's, after a dispatch that
    failed), that array must keep it."""
    _STAGING.release(view.obj)


# ---------------------------------------------------------------------- #
# Spans of the landing's host steps, and the compiles inside them
# ---------------------------------------------------------------------- #

class span:
    """One host step of a landing. A ``df:<event name>`` annotation lies
    around it, so a ``jax.profiler`` session over the daemon shows the step
    beside the device's operations (outside a session an annotation is a
    flag test). At its end ``stamp(code, piece, ms, note)`` is called once, if
    there is a stamp: the flight ring's span convention, one event at the
    end whose aux is the duration. ``piece`` may be set inside the block,
    where it is only known then, and so may ``note``, the event's text
    (``sink_read`` / ``sink_checksum``: into how many chunks the pass was
    cut, nothing where it was not)."""

    __slots__ = ("stamp", "code", "piece", "note", "_annotation", "_t0")

    def __init__(self, stamp, code: int, piece: int = -1):
        self.stamp = stamp
        self.code = code
        self.piece = piece
        self.note = ""

    def __enter__(self) -> "span":
        self._annotation = TraceAnnotation(
            "df:" + flight.EVENT_NAMES[self.code])
        self._annotation.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        ms = (time.perf_counter() - self._t0) * 1000.0
        self._annotation.__exit__(*exc)
        if self.stamp is not None:
            self.stamp(self.code, self.piece, ms, self.note)


# What THIS thread's backend compiles came to, from jax's own monitoring
# events, which jax raises on the compiling thread: so reading it before
# and after a call gives that call's compiles, whatever other threads
# compile meanwhile. A persistent-cache hit raises the duration event too
# (for the retrieval), after a cache_hits event: it is left out.
_compiled = threading.local()
_watching = False


def _on_event(event: str, **_) -> None:
    if event == "/jax/compilation_cache/cache_hits":
        _compiled.hit = True


def _on_duration(event: str, seconds: float, **_) -> None:
    if event != "/jax/core/compile/backend_compile_duration":
        return
    if getattr(_compiled, "hit", False):
        _compiled.hit = False
        return
    count, so_far = compiled()
    _compiled.count, _compiled.seconds = count + 1, so_far + seconds


def watch_compiles() -> None:
    """Start counting compiles (once a process; jax keeps no way to take a
    listener off again). Called where a sink manager is built."""
    global _watching
    if not _watching:
        _watching = True
        jax.monitoring.register_event_listener(_on_event)
        jax.monitoring.register_event_duration_secs_listener(_on_duration)


def compiled() -> "tuple[int, float]":
    """(count, seconds) of the calling thread's backend compiles so far."""
    return getattr(_compiled, "count", 0), getattr(_compiled, "seconds", 0.0)


# ---------------------------------------------------------------------- #
# Assembly: slices of staged batches → the flat content + per-piece
# checksums, in ONE fused jit dispatch. The checksums reduce the INPUT
# segments, from the same staged copy the concatenate reads. Rates: not
# measured on this round's chip; memory: see the module docstring.
# ---------------------------------------------------------------------- #

@functools.partial(jax.jit, static_argnames=("plan", "piece_words"))
def _assemble_checksum_jit(batches: tuple, plan: tuple, piece_words: int):
    """Assemble AND checksum in one dispatch. plan: tuple of
    ("b", batch_idx, row_start, row_stop) — rows of a staged batch, in
    slot order — or ("z", n_words) zero filler for not-landed slots.
    Returns (flat, sums, xors) with sums/xors indexed by slot (zero
    fillers contribute zero checksums — pad-neutral by definition).
    Verify-on-land semantics: the checksums fold from the same staged
    device copy the flat buffer is assembled from."""
    parts = []
    checks = []
    for op in plan:
        if op[0] == "b":
            _, bi, r0, r1 = op
            seg = batches[bi][r0:r1].reshape(-1)
            parts.append(seg)
            checks.append(_chunk_checksums_xla(seg, piece_words))
        else:
            parts.append(jnp.zeros((op[1],), jnp.uint32))
            z = op[1] // piece_words
            checks.append((jnp.zeros((z,), jnp.uint32),
                           jnp.zeros((z,), jnp.uint32)))
    flat = (jax.lax.concatenate(parts, 0) if len(parts) > 1 else parts[0])
    if len(checks) > 1:
        sums = jnp.concatenate([c[0] for c in checks])
        xors = jnp.concatenate([c[1] for c in checks])
    else:
        sums, xors = checks[0]
    return flat, sums, xors


@jax.jit
def _merge_jit(arrs: tuple):
    """Consolidate equal-shaped staged batches into one superbatch (all
    groups are _MERGE_GROUP × (batch_pieces, piece_words): one compile)."""
    return jnp.concatenate(list(arrs), axis=0)


@jax.jit
def _reorder_jit(staged, order):
    """A staged batch's rows, which lie in arrival order, in slot order:
    row i of the result is row ``order[i]``. The permutation is traced, so
    one program serves every arrival order of a batch shape. A loop of
    row copies and not ``jnp.take``: for 8 rows of 32 MiB the v5e compiler
    unrolls that gather into 22 MB of program, which stays on the device
    (tests/test_chip_compile.py; the chip's ``peak_hbm_x`` showed it)."""
    def place(i, out):
        row = jax.lax.dynamic_slice_in_dim(staged, order[i], 1, axis=0)
        return jax.lax.dynamic_update_slice_in_dim(out, row, i, axis=0)

    return jax.lax.fori_loop(0, staged.shape[0], place,
                             jnp.zeros_like(staged))


@functools.partial(jax.jit,
                   static_argnames=("count", "piece_size", "record_bytes"))
def _record_batch_jit(flat, *, count: int, piece_size: int,
                      record_bytes: int):
    u8 = bitview.typed_view(flat, 0, jnp.uint8, (count, piece_size))
    return u8[:, :record_bytes]


@functools.partial(jax.jit, static_argnames=("piece_words",))
def _gather_checksum_jit(batches: tuple, perm, piece_words: int):
    """Fragmented-arrival fallback: stack the staged batches, reorder the
    piece rows by a TRACED permutation (missing slots point at a zero
    row), and checksum. The graph depends only on batch shapes — no
    per-plan retrace — at the cost of one extra read+write over the fused
    segment path; used when the segment plan would unroll too many
    concatenate operands."""
    stacked = (jnp.concatenate(list(batches), axis=0) if len(batches) > 1
               else batches[0])
    zero = jnp.zeros((1, stacked.shape[1]), stacked.dtype)
    stacked = jnp.concatenate([stacked, zero], axis=0)
    flat = jnp.take(stacked, perm, axis=0).reshape(-1)
    sums, xors = _chunk_checksums_xla(flat, piece_words)
    return flat, sums, xors


class HBMSink:
    """Accumulates one task's pieces on device; flat content materializes
    once at consumption."""

    def __init__(self, content_length: int, piece_size: int, *, device=None,
                 batch_pieces: int = 8, stamp=None):
        # The stack being filled: the pool's view of it, its bytes as
        # (batch_pieces, piece_size) rows, and the slots of the rows filled
        # so far, in row order. Then the stacks that were put, oldest
        # first, each with the device array whose readiness says the
        # runtime has read it.
        self._view: memoryview | None = None
        self._stack: np.ndarray | None = None
        self._rows: list[int] = []
        self._in_flight: list[tuple[memoryview, jax.Array]] = []
        if piece_size % 4:
            raise ValueError("piece_size must be 4-byte aligned")
        # ``stamp(code, piece, ms, note)``: where the owner keeps the spans of
        # this sink's host steps (the task's flight ring), or None.
        self.stamp = stamp
        self.content_length = content_length
        self.piece_size = piece_size
        self.piece_words = piece_size // 4
        self.total_words = (content_length + 3) // 4
        self.total_pieces = max(
            1, (content_length + piece_size - 1) // piece_size)
        self.padded_words = self.total_pieces * self.piece_words
        # local_devices, not devices: under jax.distributed the global
        # list leads with process 0's devices, and staging to another
        # process's device is an INVALID_ARGUMENT copy error. Identical
        # off-pod (local == global).
        self.device = device or jax.local_devices()[0]
        # Named for whoever reports the landing (daemon progress, dfget):
        # under a CPU backend local_devices()[0] is a CPU device, and a
        # verified landing there must not read as one on a chip.
        self.platform = self.device.platform
        self.device_kind = self.device.device_kind
        self.host_checksums: dict[int, tuple[int, int]] = {}
        self.landed: set[int] = set()
        self.batch_pieces = batch_pieces
        # Staged device batches: (slot ndarray, (k, piece_words) uint32),
        # rows in slot order.
        self._batches: list[tuple[np.ndarray, jax.Array]] = []
        self._slot_to_batch: dict[int, tuple[int, int]] = {}
        self._assembled: jax.Array | None = None
        # Device checksums by slot, produced by the assembly dispatch.
        self._dev_sums: np.ndarray | None = None
        self._dev_xors: np.ndarray | None = None
        self._verified = False

    # -- landing -----------------------------------------------------------

    def _open_stack(self) -> None:
        """A stack to fill: first those the runtime has read go back to
        the free list (waiting for the oldest only if _STACKS_PER_SINK are
        out), then one is taken from it."""
        self._retire(keep=_STACKS_PER_SINK - 1)
        self._view = _STAGING.acquire(self.batch_pieces * self.piece_size)
        self._stack = np.frombuffer(self._view, np.uint8).reshape(
            self.batch_pieces, self.piece_size)

    def _retire(self, keep: int) -> None:
        """Give back, oldest first, every stack whose put the runtime is
        done with; wait for it while more than ``keep`` are out. A put
        that failed raises here, and its stack goes back all the same: no
        one reads it any more."""
        while self._in_flight:
            view, moved = self._in_flight[0]
            if len(self._in_flight) <= keep and not moved.is_ready():
                return
            try:
                moved.block_until_ready()
            finally:
                del self._in_flight[0]
                _give_back(view)

    def __del__(self):
        # A sink dropped mid-landing (its task degraded, failed, expired)
        # still holds stacks. Nothing can write to them any more, which is
        # why they go back here and not where the owner forgets the sink:
        # that may be another thread than the one landing into it.
        if self._view is not None:
            _give_back(self._view)
            self._view = self._stack = None
        while self._in_flight:
            try:
                self._retire(keep=0)
            except jax.errors.JaxRuntimeError:
                pass

    def next_row(self) -> memoryview:
        """The row the next piece will lie in, as ``piece_size`` writable
        bytes: read the piece into it, then hand ``row[:size]`` to
        ``land_piece``, which copies nothing then. A row taken and never
        landed is handed out again."""
        if self._stack is None:
            with span(self.stamp, flight.EV_SINK_STAGE):
                self._open_stack()
        return memoryview(self._stack[len(self._rows)])

    def land_piece(self, piece_num: int, data: bytes) -> None:
        """Stage one piece as the next row of the open stack, zero-padded
        to the piece size. ``data`` is the piece's bytes: the start of the
        row ``next_row()`` gave, already in place, or any other bytes-like
        object, copied into the row once. The host checksum is taken from
        the row, and recorded for the verification on the device.
        Batched: flushes every ``batch_pieces``."""
        if piece_num < 0 or piece_num >= self.total_pieces:
            # A stray out-of-range piece must not invalidate (and on a
            # drained sink, zero out) the assembled content.
            raise ValueError(
                f"piece {piece_num} out of range for "
                f"{self.total_pieces}-piece sink")
        if piece_num in self.landed:
            return
        with span(self.stamp, flight.EV_SINK_STAGE, piece_num):
            if self._stack is None:
                self._open_stack()
            row = self._stack[len(self._rows)]
            given = np.frombuffer(data, np.uint8)
            if given.size > row.size:
                raise ValueError(
                    f"piece {piece_num} of {given.size} bytes in a sink of "
                    f"{self.piece_size}-byte pieces")
            if given.ctypes.data == row.ctypes.data:
                _ROWS_IN_PLACE.inc()
            else:
                row[:given.size] = given
                _ROWS_COPIED.inc()
            # The stack is reused: past the piece lies an earlier one.
            row[given.size:] = 0
        with span(self.stamp, flight.EV_SINK_CHECKSUM, piece_num) as step:
            # Whole words, the zero padding included: nothing to copy.
            words = row[:given.size + (-given.size) % 4]
            ranges = cuts(words.size)
            if len(ranges) > 1:
                step.note = str(len(ranges))
                _PIECES_SPLIT.inc()
            else:
                _PIECES_WHOLE.inc()
            self.host_checksums[piece_num] = checksum_row(words, ranges)
        self._rows.append(piece_num)
        self.landed.add(piece_num)
        if len(self._rows) >= self.batch_pieces:
            self.flush()

    # Every _MERGE_GROUP full batches consolidate into one superbatch
    # (single fixed-shape concat jit, compiled once): a 70B-scale task is
    # ~1200 staged batches, and assembling over 1200 concat operands
    # costs minutes of XLA compile — consolidation bounds the operand
    # count at ~_MERGE_GROUP + B/_MERGE_GROUP for one extra read+write
    # of the content (device-side, ~free next to the transport).
    _MERGE_GROUP = 32

    def flush(self) -> None:
        """Move the open stack's filled rows to the device as one batch,
        in slot order. Pure staging: the single assembly dispatch
        checksums everything later. The stack itself stays out until the
        reordered batch is ready (``_retire``)."""
        if not self._rows:
            return
        with span(self.stamp, flight.EV_SINK_STAGE) as step:
            slots = np.asarray(self._rows, np.int64)
            order = np.argsort(slots)
            slots = slots[order]
            lowest = step.piece = int(slots[0])
        # Straight from the host buffer to the sink's device: staging via
        # jnp.asarray would first place the batch on the default device.
        with span(self.stamp, flight.EV_SINK_PUT, lowest):
            batch = _reorder_jit(
                jax.device_put(self._stack[:len(slots)].view(np.uint32),
                               self.device),
                order.astype(np.int32))
        self._in_flight.append((self._view, batch))
        self._view = self._stack = None
        self._rows = []
        bi = len(self._batches)
        self._batches.append((slots, batch))
        for i, n in enumerate(slots):
            self._slot_to_batch[int(n)] = (bi, i)
        self._maybe_consolidate()
        self._assembled = None
        self._dev_sums = self._dev_xors = None

    def _maybe_consolidate(self) -> None:
        """Merge the trailing _MERGE_GROUP equal-shaped batches into one
        superbatch. Only ever merges ORIGINAL full batches (all shapes
        (batch_pieces, piece_words)), so the concat jit compiles once."""
        group = self._MERGE_GROUP
        if len(self._batches) < group:
            return
        tail = self._batches[-group:]
        if any(arr.shape[0] != self.batch_pieces for _, arr in tail):
            return  # irregular flush in the window: leave as-is
        merged_arr = _merge_jit(tuple(arr for _, arr in tail))
        merged_slots = np.concatenate([s for s, _ in tail])
        self._batches = self._batches[:-group] + [(merged_slots, merged_arr)]
        # Rebuild the slot map (indices after the merge point shifted).
        self._slot_to_batch = {
            int(n): (bi, i)
            for bi, (slots, _) in enumerate(self._batches)
            for i, n in enumerate(slots)}

    def complete(self) -> bool:
        return len(self.landed) >= self.total_pieces

    # -- verification ------------------------------------------------------

    def verify(self) -> bool:
        """On-device checksums vs host-recorded values for every landed
        piece. Raises ValueError naming the first corrupt piece. The
        checksums come out of the same single dispatch that assembles the
        buffer (verify-on-land: folded from the staged device copy)."""
        self._assemble()
        assert self._dev_sums is not None
        for piece_num, (want_s, want_x) in sorted(self.host_checksums.items()):
            have = (int(self._dev_sums[piece_num]),
                    int(self._dev_xors[piece_num]))
            if have != (want_s, want_x):
                raise ValueError(
                    f"piece {piece_num} corrupt in HBM: "
                    f"sum {have[0]:#x}!={want_s:#x} "
                    f"xor {have[1]:#x}!={want_x:#x}")
        self._verified = True
        self._maybe_drop_staging()
        return True

    # -- assembly / consumption --------------------------------------------

    def _plan(self) -> tuple:
        plan: list[tuple] = []
        slot = 0
        while slot < self.total_pieces:
            loc = self._slot_to_batch.get(slot)
            if loc is None:
                run = 1
                while (slot + run < self.total_pieces
                       and slot + run not in self._slot_to_batch):
                    run += 1
                plan.append(("z", run * self.piece_words))
                slot += run
            else:
                bi, row = loc
                run = 1
                while True:
                    nxt = self._slot_to_batch.get(slot + run)
                    if nxt != (bi, row + run):
                        break
                    run += 1
                plan.append(("b", bi, row, row + run))
                slot += run
        return tuple(plan)

    # Above this many slot-order segments, the fused plan would unroll an
    # O(segments) concat graph and retrace per arrival order — switch to
    # the traced-permutation gather (fixed graph, one extra pass).
    _SEGMENT_CAP = 128

    def _assemble(self) -> jax.Array:
        """Materialize the flat uint32 content + per-slot checksums: ONE
        fused dispatch (read once, write once — the input-side checksum
        reduction fuses with the concatenate's read)."""
        self.flush()
        if self._assembled is not None:
            return self._assembled
        batches = tuple(b for _, b in self._batches)
        if not batches:
            self._assembled = jnp.zeros((self.padded_words,), jnp.uint32,
                                        device=self.device)
            self._dev_sums = np.zeros((self.total_pieces,), np.uint32)
            self._dev_xors = np.zeros((self.total_pieces,), np.uint32)
            return self._assembled
        # From the wait for the staged transfers to the checksums on the
        # host, with any compile between them.
        with span(self.stamp, flight.EV_SINK_ASSEMBLE) as step:
            # Every batch ready before the dispatch, not only after it:
            # the last put's own device buffer is then gone when the flat
            # content is allocated, and the peak stays staged + flat.
            self._retire(keep=0)
            plan = self._plan()
            step.piece = len(plan)
            count, seconds = compiled()
            if len(plan) <= self._SEGMENT_CAP:
                flat, sums, xors = _assemble_checksum_jit(
                    batches, plan, self.piece_words)
            else:
                flat, sums, xors = self._assemble_fragmented(batches)
            count_after, seconds_after = compiled()
            if count_after > count and self.stamp is not None:
                # A plan met for the first time (it is a static argument,
                # and follows the order pieces arrived in).
                self.stamp(flight.EV_SINK_COMPILE, len(plan),
                           (seconds_after - seconds) * 1000.0)
            self._assembled = flat
            self._dev_sums = np.asarray(sums)
            self._dev_xors = np.asarray(xors)
        self._maybe_drop_staging()
        self._bound_jit_cache()
        return self._assembled

    def _assemble_fragmented(self, batches: tuple):
        """Badly scrambled arrival: slot→row permutation as a traced array
        (missing slots → the appended zero row)."""
        row_offset = []
        off = 0
        for slots, b in self._batches:
            row_offset.append(off)
            off += b.shape[0]
        zero_row = off
        perm = np.full((self.total_pieces,), zero_row, np.int32)
        for slot, (bi, row) in self._slot_to_batch.items():
            perm[slot] = row_offset[bi] + row
        return _gather_checksum_jit(
            batches, jax.device_put(perm, self.device), self.piece_words)

    @staticmethod
    def _bound_jit_cache() -> None:
        """Every task's segment plan is a distinct static argument; a
        long-lived daemon must not accumulate compiled executables without
        bound."""
        try:
            if _assemble_checksum_jit._cache_size() > 64:
                _assemble_checksum_jit.clear_cache()
        except AttributeError:
            pass

    def _maybe_drop_staging(self) -> None:
        if self._assembled is not None and self.complete() and self._verified:
            # The staging batches are no longer needed: free half the HBM
            # footprint. landed/checksum bookkeeping stays; re-landing a
            # piece is a no-op via `landed`.
            self._batches = []
            self._slot_to_batch = {}

    def as_words(self):
        """The landed content as the flat device uint32 buffer it was
        assembled into (whole pieces: zero-padded past the content). The
        form every typed consumer reads through ops/bitview.py."""
        return self._assemble()

    def as_bytes_array(self):
        """The landed content as a device uint8 array (exact length): a
        second content-sized array. Typed consumers take ``as_words``."""
        return bitview.typed_view(self._assemble(), 0, jnp.uint8,
                                  (self.content_length,))

    def as_record_batch(self, count: int, record_bytes: int):
        """The landed content as a ``(count, record_bytes)`` uint8 device
        array, for piece-per-record landings (dataset/device_feed.py):
        each piece slot holds one record zero-padded to the piece size,
        so the batch is the padded words viewed as bytes plus a column
        slice — no host copies."""
        if count != self.total_pieces:
            raise ValueError(
                f"record batch of {count} over a {self.total_pieces}-piece "
                "sink")
        if record_bytes > self.piece_size:
            raise ValueError(
                f"record_bytes {record_bytes} exceeds piece size "
                f"{self.piece_size}")
        return _record_batch_jit(self._assemble(), count=count,
                                 piece_size=self.piece_size,
                                 record_bytes=record_bytes)

    def as_tensor(self, dtype, shape):
        """The landed bytes as a checkpoint tensor, staying on device
        (e.g. ('bfloat16', [8192, 4096]))."""
        return bitview.typed_view(self._assemble(), 0, dtype, tuple(shape))

    def shard_to_mesh(self, mesh, axis_name: str = "d"):
        """Spread the landed content across the slice mesh: device i holds
        piece-contiguous shard i (ICI transfers, not NIC)."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        buf = self._assemble()
        n = mesh.shape[axis_name]
        per = (self.padded_words + n - 1) // n
        if per * n != self.padded_words:
            # Pad UP to a shard multiple — truncating would silently drop
            # tail content bytes.
            buf = jnp.pad(buf, (0, per * n - self.padded_words))
        # device_put on a device array → XLA moves shards device-to-device
        # (ICI on a TPU slice), no host staging.
        return jax.device_put(buf, NamedSharding(mesh, P(axis_name)))

    def ring_replicate(self, mesh, axis_name: str = "d", n_chunks: int = 4):
        """The ICI leg of the striped broadcast: spread the landed content
        over the mesh (one shard per device) and complete the copy with
        the chunked ppermute ring, so every device ends with the full
        word buffer without any further NIC traffic. Returns the
        replicated uint32 array (padded words; callers trim/bitcast)."""
        from dragonfly2_tpu.parallel.ici import chunked_ring_all_gather

        return chunked_ring_all_gather(
            mesh, self.shard_to_mesh(mesh, axis_name),
            axis_name=axis_name, n_chunks=n_chunks)


# ---------------------------------------------------------------------- #
# Double-buffer hot-swap (checkpoint-delta plane, delta/)
#
# A serving process keeps the LIVE checkpoint generation on device while
# the next one assembles in a spare buffer: reused delta chunks are
# device-to-device slices of the live buffer (they never leave HBM, let
# alone re-cross DCN), fetched chunks are host-staged once, and the
# verified result replaces the live generation with ONE atomic reference
# swap — a reader always sees a complete (generation, buffer, tensors)
# triple, never a mix.
# ---------------------------------------------------------------------- #

def assemble_delta_u8(live_u8, parts):
    """Assemble the next generation's uint8 content buffer.

    ``parts`` is the new content in offset order, each element either
    ``("r", src_offset, length)`` — a device-side slice of ``live_u8``
    (a reused chunk at its OLD offset) — or ``("f", bytes)`` — a fetched
    chunk's host bytes, staged to device here. One concatenate
    materializes the buffer; reused bytes move HBM→HBM only."""
    segs = []
    for part in parts:
        if part[0] == "r":
            _, src, length = part
            segs.append(live_u8[src:src + length])
        else:
            staged = np.frombuffer(part[1], dtype=np.uint8)
            # Beside the live generation, not on the default device.
            segs.append(jnp.asarray(staged) if live_u8 is None
                        else jax.device_put(staged, live_u8.sharding))
    if not segs:
        return jnp.zeros((0,), jnp.uint8)
    return jnp.concatenate(segs) if len(segs) > 1 else segs[0]


def _byte_lane_checksums(rows):
    """(sum32, xor32) as int32 scalars over a (n, 4k) uint8 block read
    as little-endian words: byte lane k of every word folds on its own
    and is shifted into place, so no word array is ever formed."""
    total = jnp.int32(0)
    fold = jnp.int32(0)
    for k in range(4):
        lane = rows[:, k::4].astype(jnp.int32)
        total = total + (jnp.sum(lane, dtype=jnp.int32) << (8 * k))
        fold = fold | (jax.lax.reduce(
            lane, jnp.int32(0), jax.lax.bitwise_xor, (0, 1)) << (8 * k))
    return total, fold


@functools.partial(jax.jit, static_argnames=("piece_size",))
def _u8_checksums_jit(u8, piece_size: int):
    """Per-piece (sum32[n], xor32[n]) of a flat uint8 buffer, one piece
    per loop iteration: temporaries of the order of a piece. (Packing the
    bytes into words first pads a (n, 4) array to 128 lanes, 32x the
    buffer.)"""
    full, tail = divmod(u8.shape[0], piece_size)
    row = 512               # four 128-lane rows of words, where it divides
    while piece_size % row:
        row //= 2
    sums, xors = [], []
    if full:
        def one(i):
            piece = jax.lax.dynamic_slice(u8, (i * piece_size,),
                                          (piece_size,))
            return _byte_lane_checksums(piece.reshape(-1, row))

        s, x = jax.lax.map(one, jnp.arange(full, dtype=jnp.int32))
        sums.append(s)
        xors.append(x)
    if tail:
        rest = jnp.pad(u8[full * piece_size:], (0, (-tail) % row))
        s, x = _byte_lane_checksums(rest.reshape(-1, row))
        sums.append(s[None])
        xors.append(x[None])
    return (jax.lax.bitcast_convert_type(jnp.concatenate(sums), jnp.uint32),
            jax.lax.bitcast_convert_type(jnp.concatenate(xors), jnp.uint32))


def verify_u8_against_host(u8, piece_size: int,
                           host_checksums: "dict[int, tuple[int, int]]") -> None:
    """On-device verification gate for a hot-swap flip: per-piece
    (sum32, xor32) of the device buffer compared against host-side values
    (checksum_numpy over the disk copy's pieces). Raises ValueError
    naming the first mismatching piece; the flip must not happen."""
    if piece_size % 4:
        raise ValueError(f"piece size {piece_size} not 4-byte aligned")
    bitview.check_u8_indexable(u8)
    if u8.shape[0] == 0:
        sums = xors = np.zeros((1,), np.uint32)
    else:
        sums, xors = (np.asarray(c)
                      for c in _u8_checksums_jit(u8, piece_size))
    for num, (want_s, want_x) in sorted(host_checksums.items()):
        have = (int(sums[num]), int(xors[num]))
        if have != (want_s, want_x):
            raise ValueError(
                f"piece {num} corrupt in spare buffer: "
                f"sum {have[0]:#x}!={want_s:#x} "
                f"xor {have[1]:#x}!={want_x:#x}")


class DoubleBuffer:
    """Atomic generation holder for hot-swapped device checkpoints.

    Readers call ``snapshot()`` (or ``tensors()``) and get one complete
    generation — the state is a single tuple swapped in one reference
    assignment, so a concurrently flipping writer can never expose a
    half-updated tensor set. Writers assemble + verify the next
    generation OFF to the side and ``flip()`` only after the verify
    gate passed; the previous generation's buffer is released when the
    last reader drops its snapshot (ordinary refcounting)."""

    __slots__ = ("_state",)

    def __init__(self):
        self._state: tuple = (0, None, {})

    @property
    def generation(self) -> int:
        return self._state[0]

    def snapshot(self) -> tuple:
        """(generation, buffer_u8, tensors) — one consistent triple."""
        return self._state

    def tensors(self) -> dict:
        return self._state[2]

    def buffer(self):
        return self._state[1]

    def flip(self, buffer, tensors: dict) -> int:
        """Install the next generation. Callers flip ONLY verified
        buffers (verify_u8_against_host / HBMSink.verify)."""
        gen = self._state[0] + 1
        self._state = (gen, buffer, dict(tensors))
        return gen
