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
    as it lies, and keeps beside it which slot each row belongs to. The
    content is not touched again until consumption.
  * consumption assembles all batches into the flat uint32 content ONCE
    with one jit that copies every staged row to its slot and folds the
    per-piece (sum32, xor32) checksums from the same staged copy. Where
    each row belongs is an ARGUMENT of that program (an int32 array), so
    the program is one for a geometry (the batches' shapes, the count of
    pieces) and no arrival order compiles anything: a complete 55-piece
    sink is always batches of 8,8,8,8,8,8,7, whatever reached the thread
    first. (A placement that is part of the program costs a compile of
    0.8-3.5 s for almost every cold pull: PERF.md section 6.)

Host staging: the sink owns its stacks, and they are reused. A stack comes
from a process-wide free list (``pkg/bufpool``, pool ``hbm_stage``), so
its pages have been touched before: a fresh 32 MiB buffer costs 32-41 ms
of page faults on the chip's host, a fresh 256 MiB stack 260 ms (PERF.md
section 5), against 35-65 ms for the transfer itself. The daemon's piece
is read from its store straight into the next row and checksummed there
in the same pass (``read_pieces``), and that row is handed to
``land_piece``, which copies nothing then; any other bytes are copied into
the row once and checksummed where they lie. Rows lie in arrival order, on
the host and on the device. ``flush`` puts the stack (``_put``), and only
when the device array is ready does the stack go back to the free list:
``jax.device_put`` returns before the runtime has read the host buffer.
On the CPU backend an aligned buffer is aliased, not copied, for the
device array's whole life, so there ``_put`` copies the rows first.

Host passes: the pieces the sink reads itself cost the host ONE pass a
group (``read_pieces`` -> ``read_checksummed``): a group is the pieces
that go into the next free rows of the open stack, one as a piece arrives
(``on_piece``), as many as the stack has rows free where every piece is in
the store before the first is read (a re-land's backfill). Their bytes are
read from the store into their rows and checksummed there while they are
still in the cache. Both halves are plain memory traffic that lets go of
the GIL, so a group of at least two ``_CHUNK_FLOOR``s becomes a work list
of page-aligned chunks, views of the rows themselves and none across two
pieces (``cuts`` of every piece), and each of a few helper threads takes
the next chunk, reads it and checksums it before it returns
(``side_by_side``), while the thread that lands the group waits ONCE for
all of them. The helpers hold no sink state and stamp nothing; a piece's
checksum is the fold of its chunks' and is ``checksum_numpy`` of the
padded row, bit for bit. Smaller groups run the same read-then-checksum
where they are, with no hand-over. Bytes a caller brings to
``land_piece`` (a record of ``DeviceFeed``, a delta chunk, the benchmark's
controls) were read by someone else: they are copied into the row and cost
a checksum pass of their own there (``checksum_row``), cut the same way.
Either way the host checksums exactly the bytes it hands to
``device_put``.

Memory, as the v5e compiler reports it for the assembly program
(``memory_analysis()``, tests/test_chip_compile.py): staged batches
(argument) + flat content (output) and under 2 MiB beside them = **2x
content** while the program runs (a staged piece is whole (8, 128) tiles,
``_piece_shape``, so neither the copy nor the checksum nor the flat view
needs a relayout), and the chip's ``peak_hbm_x`` reads 2.007. So one
chip's share above roughly 7 GiB cannot land on a 16 GB v5e.
Staging batches are dropped after a verified complete assembly, which
leaves 1x resident. Rates: PERF.md section 5.

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
# and the one the runtime may still be reading. A put takes 30-65 ms; since
# PR 28 the next batch of 8 x 32 MiB is read and checksummed in about 60 ms
# (200-300 before), so the wait for the older stack is no longer far off.
# On the chip it was still not met (land_stage_ms 3.6 a re-land, as before;
# PERF.md section 5): a host pass that gets faster again meets the link
# here first.
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
    "Pieces landed, by how the host pass that checksummed them ran: one "
    "pass over several pieces of a stack at once (batched), or a pass of "
    "the piece's own, cut into chunks over the helper threads (split) or "
    "on the landing thread alone (whole)",
    ("how",))
_PIECES = {how: SINK_PIECES.labels(how)
           for how in ("batched", "split", "whole")}
SINK_PASSES = metrics.counter(
    "device_sink_host_passes_total",
    "Host passes over pieces' bytes: a group of pieces read from the store "
    "into their rows and checksummed under one wait (fused), or a checksum "
    "alone, of one piece's bytes that a caller brought (checksum). "
    "device_sink_pieces_total over the fused passes: pieces a pass, 1.0 "
    "where pieces land as they arrive, the stack's rows in a re-land",
    ("kind",))
_PASSES_FUSED = SINK_PASSES.labels("fused")
_PASSES_CHECKSUM = SINK_PASSES.labels("checksum")
SINK_ASSEMBLIES = metrics.counter(
    "device_sink_assemblies_total",
    "Assembly dispatches, by whether the dispatching thread compiled the "
    "program for them (compiled: a geometry this process had not assembled "
    "before, and the persistent cache did not hold) or not (cached)",
    ("how",))
_ASSEMBLIES_COMPILED = SINK_ASSEMBLIES.labels("compiled")
_ASSEMBLIES_CACHED = SINK_ASSEMBLIES.labels("cached")

# The host pass over a piece is cut into at most _HELPERS chunks of about
# _CHUNK_FLOOR bytes or more each; under two floors it is not cut. Fixed
# from a re-land on the chip's 13-core host (benchmarks/land_probe.py;
# PERF.md section 5, "The passes, alone", PR 35; finalize ms, medians of 3).
# 55 pieces of 32 MiB: 4 helpers 357-371, 6 323, **8 294-295**, 12 279; one
# thread 988; and end to end 8 land a shard a quarter faster than 4 with
# nothing lost in a cold pull beside the wire. 30 pieces of 8 MiB: 4 chunks
# of 2 MiB 75-78 against 157 whole and 104 in 2; 8 chunks of 1 MiB 77 with
# the checksum half again as long, and at 32 MiB a floor of 1 MiB or of 4
# changes nothing while one of 4 MiB takes the 8 MiB pieces to 104: a
# hand-over still costs what 1 MiB of the pass does.
_CHUNK_FLOOR = 2 << 20
_HELPERS = 8
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


def side_by_side(fn, work) -> list:
    """``fn(*args)`` of every entry of ``work`` (a range ``(start, stop)``
    of one row, a chunk ``(row, start, stop)`` of several), on the helper
    threads at once; the results in ``work``'s order. Returns, or raises
    the first failure, only when EVERY call has come back: a chunk is a
    view of a buffer that its owner may give away the moment this
    returns."""
    futures = [_POOL.submit(fn, *args) for args in work]
    wait(futures)
    try:
        return [f.result() for f in futures]
    finally:
        # A failure holds this frame (its traceback) and is held by its
        # future: with the futures still in the frame that is a cycle, and
        # the failed landing's sink, three frames up, would keep its stacks
        # and its HBM until the cyclic collector came by.
        del futures


def _fold(parts) -> "tuple[int, int]":
    """The checksum of a row from its ranges' ``(sum32, xor32, ...)``:
    sum32 is the ranges' sums mod 2^32 and xor32 the xor of their xors, so
    a cut changes no bit."""
    return (sum(p[0] for p in parts) & 0xFFFFFFFF,
            functools.reduce(operator.xor, (p[1] for p in parts)))


def checksum_row(row: np.ndarray, ranges) -> "tuple[int, int]":
    """``checksum_numpy(row)`` of a row of whole words, taken range by
    range (``cuts`` of its size) where there are several."""
    if len(ranges) < 2:
        return checksum_numpy(row)
    return _fold(side_by_side(lambda a, b: checksum_numpy(row[a:b]), ranges))


def read_checksummed(rows: np.ndarray, sizes,
                     read_into) -> "tuple[list, float, int]":
    """A group of pieces read into consecutive rows of a staging stack and
    checksummed there in ONE pass: piece ``i`` of ``sizes[i]`` bytes into
    the start of ``rows[i]``, where ``read_into(i, row, start, stop)``
    fills ``row[start:stop]`` with that piece's bytes ``[start, stop)``.
    The work list is every piece's ``cuts``, so no chunk lies across two
    pieces; where the group holds two floors and more than one chunk, the
    helpers take the chunks in turn and the caller waits once for all of
    them, else the caller runs them where it is. Whoever read a chunk
    checksums it before it returns, a piece's last one up to the whole
    word, over padding zeroed here. Returns ``checksum_numpy`` of every
    padded piece, the seconds of the pass that the thread which read
    longest spent reading (a pass of one piece on idle helpers: its
    longest chunk's read), and the number of chunks the helpers were
    handed, 1 where the caller ran the pass itself. Past a piece's padding
    its row is as it was."""
    padded = [size + (-size) % 4 for size in sizes]
    for row, size, end in zip(rows, sizes, padded):
        row[size:end] = 0

    def one(i: int, start: int, stop: int) -> tuple:
        row = rows[i]
        t0 = time.perf_counter()
        read_into(i, row, start, stop)
        read_s = time.perf_counter() - t0
        s, x = checksum_numpy(row[start:padded[i] if stop == sizes[i]
                                  else stop])
        return i, s, x, read_s, threading.get_ident()

    work = [(i, start, stop) for i, size in enumerate(sizes)
            for start, stop in cuts(size)]
    handed = len(work) if sum(sizes) >= 2 * _CHUNK_FLOOR else 1
    parts = (side_by_side(one, work) if handed > 1
             else [one(*chunk) for chunk in work])
    of_piece: list = [[] for _ in sizes]
    reading: dict = {}
    for i, s, x, read_s, thread in parts:
        of_piece[i].append((s, x))
        reading[thread] = reading.get(thread, 0.0) + read_s
    return ([_fold(chunks) for chunks in of_piece], max(reading.values()),
            handed)


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
    (``sink_checksum``: into how many chunks the pass was cut, nothing
    where it was not)."""

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
# Assembly: every staged row to its slot of the flat content + per-piece
# checksums, in ONE dispatch of a program that depends on shapes alone.
# Rates and memory: see the module docstring.
# ---------------------------------------------------------------------- #

# A staged piece is ``piece_words / _LANES`` rows of ``_LANES`` words where
# that makes whole tiles: the TPU tiles an array's last two dimensions
# (8 x 128), so a piece is then one linear run of tiles, which a copy reads
# and writes as such, and the flat view of all pieces is the same memory. As
# one row of a ``(k, piece_words)`` batch a piece is one sublane of every
# tile, and a copy of it reads eight times its size: 112 ms for 55 pieces of
# 32 MiB against 12.3 (PERF.md section 6, PR 30). That shape is left to the
# pieces that are no whole tiles (records of a few KB), which as rows of
# tiles would be padded to the next eight.
_LANES = 128
_TILE = 8 * _LANES


def _piece_shape(piece_words: int) -> tuple:
    if piece_words % _TILE:
        return (piece_words,)
    return (piece_words // _LANES, _LANES)


@functools.partial(jax.jit, static_argnames=("total_pieces",))
def _assemble_checksum_jit(batches: tuple, slots, total_pieces: int):
    """Assemble AND checksum in one dispatch. ``batches``: the staged
    batches, ``(k, *piece shape)`` each, rows in whatever order they
    arrived; ``slots``: int32, for every row of every batch in turn the
    slot it belongs to, TRACED, so the program is one for a geometry (the
    batches' shapes and ``total_pieces``) whatever the order was. Returns
    (flat, sums, xors): row ``i`` at ``slots[i] * piece_words`` of the flat
    content, zeros in the slots that no row names, and sums/xors indexed
    by slot (zero there too: pad-neutral by definition). Verify-on-land
    semantics: a row's checksums fold from the same staged device copy
    that is placed."""
    piece_words = batches[0][0].size
    flat = jnp.zeros((total_pieces, *batches[0].shape[1:]), jnp.uint32)
    sums = xors = jnp.zeros((total_pieces,), jnp.uint32)
    first = 0
    for batch in batches:
        # One loop a batch, not one copy a row: the program grows with the
        # operands (which _maybe_consolidate bounds), not with the pieces.
        def place(i, out, batch=batch, first=first):
            flat, sums, xors = out
            slot = slots[first + i]
            piece = jax.lax.dynamic_slice_in_dim(batch, i, 1, axis=0)
            s, x = _chunk_checksums_xla(piece.reshape(-1), piece_words)
            return (jax.lax.dynamic_update_slice_in_dim(flat, piece, slot, 0),
                    jax.lax.dynamic_update_slice_in_dim(sums, s, slot, 0),
                    jax.lax.dynamic_update_slice_in_dim(xors, x, slot, 0))

        flat, sums, xors = jax.lax.fori_loop(0, batch.shape[0], place,
                                             (flat, sums, xors))
        first += batch.shape[0]
    return flat.reshape(-1), sums, xors


@jax.jit
def _merge_jit(arrs: tuple):
    """Consolidate equal-shaped staged batches into one superbatch (all
    groups are _MERGE_GROUP × (batch_pieces, piece_words): one compile)."""
    return jnp.concatenate(list(arrs), axis=0)


def _put(rows: np.ndarray, device) -> jax.Array:
    """A stack's filled rows on ``device``, straight from the host buffer
    (staging via jnp.asarray would first place them on the default
    device), as a buffer of the device's own. Returns before the runtime
    has read the rows: ready is when it has. The CPU backend copies
    nothing from an aligned host buffer, the array IS the stack for its
    whole life, so there the rows are copied first."""
    if device.platform == "cpu":
        rows = rows.copy()
    return jax.device_put(rows, device)


@functools.partial(jax.jit,
                   static_argnames=("count", "piece_size", "record_bytes"))
def _record_batch_jit(flat, *, count: int, piece_size: int,
                      record_bytes: int):
    u8 = bitview.typed_view(flat, 0, jnp.uint8, (count, piece_size))
    return u8[:, :record_bytes]


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
        # What ``read_pieces`` left for the ``land_piece``s that follow it:
        # (piece, size, checksum, how its pass ran) of every row it
        # filled, the next row's first.
        self._read: list[tuple] = []
        self.landed: set[int] = set()
        self.batch_pieces = batch_pieces
        # Staged device batches: (the rows' slots, (k, *piece shape)
        # uint32), rows in the order they arrived.
        self._batches: list[tuple[np.ndarray, jax.Array]] = []
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
        bytes: write the piece into it, then hand ``row[:size]`` to
        ``land_piece``, which copies nothing then. A row taken and never
        landed is handed out again."""
        if self._stack is None:
            with span(self.stamp, flight.EV_SINK_STAGE):
                self._open_stack()
        return memoryview(self._stack[len(self._rows)])

    def free_rows(self) -> int:
        """How many pieces the next ``read_pieces`` may take: the rows the
        open stack has left, a whole stack where none is open."""
        return self.batch_pieces - len(self._rows)

    def read_pieces(self, pieces, read_into) -> "list[memoryview]":
        """A group of pieces, ``(piece_num, size)`` each and at most
        ``free_rows()`` of them, read from the caller's store into the
        next rows and checksummed there, in one pass over their bytes
        (``read_checksummed``, which says what ``read_into`` is). Returns
        each piece in its row, for ``land_piece``: handed exactly those,
        in this order, it copies nothing and takes this pass's checksums.
        Stamps the pass once, under the group's lowest piece, as
        ``sink_read``, what the thread that read longest spent reading,
        and ``sink_checksum``, the rest of the pass's wall time, each with
        the number of chunks as its note where there were several."""
        self.next_row()                 # a stack is open from here on
        first = len(self._rows)
        sizes = [size for _, size in pieces]
        if not 0 < len(pieces) <= self.free_rows():
            raise ValueError(
                f"a group of {len(pieces)} pieces into a stack with "
                f"{self.free_rows()} rows free")
        if max(sizes) > self.piece_size:
            raise ValueError(
                f"a piece of {max(sizes)} bytes in a sink of "
                f"{self.piece_size}-byte pieces")
        rows = self._stack[first:first + len(pieces)]
        t0 = time.perf_counter()
        with TraceAnnotation("df:sink_read_checksum"):
            checksums, read_s, chunks = read_checksummed(rows, sizes,
                                                         read_into)
        pass_s = time.perf_counter() - t0
        _PASSES_FUSED.inc()
        if self.stamp is not None:
            lowest = min(num for num, _ in pieces)
            note = str(chunks) if chunks > 1 else ""
            self.stamp(flight.EV_SINK_READ, lowest, read_s * 1000.0, note)
            self.stamp(flight.EV_SINK_CHECKSUM, lowest,
                       (pass_s - read_s) * 1000.0, note)
        how = ("batched" if len(pieces) > 1
               else "split" if chunks > 1 else "whole")
        self._read = [(num, size, checksum, how)
                      for (num, size), checksum in zip(pieces, checksums)]
        return [memoryview(row)[:size] for row, size in zip(rows, sizes)]

    def land_piece(self, piece_num: int, data: bytes) -> None:
        """Stage one piece as the next row of the open stack, zero-padded
        to the piece size. ``data`` is the piece's bytes: what
        ``read_pieces`` returned for it (or the start of the row
        ``next_row()`` gave), already in place, or any other bytes-like
        object, copied into the row once. The host checksum recorded for
        the verification on the device is the one ``read_pieces`` took of
        this row with these bytes in it; after anything else it is taken
        from the row here. Batched: flushes every ``batch_pieces``."""
        if piece_num < 0 or piece_num >= self.total_pieces:
            # A stray out-of-range piece must not invalidate (and on a
            # drained sink, zero out) the assembled content.
            raise ValueError(
                f"piece {piece_num} out of range for "
                f"{self.total_pieces}-piece sink")
        # The reading of the next row, if there is one. Whatever is landed
        # now that is not its piece in its row, no later piece may take a
        # reading.
        read = self._read.pop(0) if self._read else None
        if piece_num in self.landed:
            self._read.clear()
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
            in_place = given.ctypes.data == row.ctypes.data
            if in_place:
                _ROWS_IN_PLACE.inc()
            else:
                row[:given.size] = given
                _ROWS_COPIED.inc()
            # The stack is reused: past the piece lies an earlier one.
            row[given.size:] = 0
        if in_place and read is not None and read[:2] == (piece_num,
                                                          given.size):
            checksum, how = read[2:]
        else:
            self._read.clear()
            with span(self.stamp, flight.EV_SINK_CHECKSUM,
                      piece_num) as step:
                # Whole words, the zero padding included: nothing to copy.
                words = row[:given.size + (-given.size) % 4]
                ranges = cuts(words.size)
                if len(ranges) > 1:
                    step.note = str(len(ranges))
                checksum = checksum_row(words, ranges)
            _PASSES_CHECKSUM.inc()
            how = "split" if len(ranges) > 1 else "whole"
        _PIECES[how].inc()
        self.host_checksums[piece_num] = checksum
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
        as they lie: which slot each belongs to goes with the batch. Pure
        staging: the single assembly dispatch places and checksums
        everything later. The stack itself stays out until the runtime
        has read it (``_retire``)."""
        if not self._rows:
            return
        with span(self.stamp, flight.EV_SINK_STAGE) as step:
            slots = np.asarray(self._rows, np.int32)
            lowest = step.piece = int(slots.min())
        with span(self.stamp, flight.EV_SINK_PUT, lowest):
            rows = self._stack[:len(slots)].view(np.uint32).reshape(
                len(slots), *_piece_shape(self.piece_words))
            batch = _put(rows, self.device)
        self._in_flight.append((self._view, batch))
        self._view = self._stack = None
        self._rows = []
        self._batches.append((slots, batch))
        self._maybe_consolidate()
        self._assembled = None
        self._dev_sums = self._dev_xors = None

    def _maybe_consolidate(self) -> None:
        """Merge the trailing _MERGE_GROUP equal-shaped batches into one
        superbatch. Only ever merges ORIGINAL full batches (all of
        batch_pieces pieces), so the concat jit compiles once."""
        group = self._MERGE_GROUP
        if len(self._batches) < group:
            return
        tail = self._batches[-group:]
        if any(arr.shape[0] != self.batch_pieces for _, arr in tail):
            return  # irregular flush in the window: leave as-is
        merged_arr = _merge_jit(tuple(arr for _, arr in tail))
        merged_slots = np.concatenate([s for s, _ in tail])
        self._batches = self._batches[:-group] + [(merged_slots, merged_arr)]

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

    def _assemble(self) -> jax.Array:
        """Materialize the flat uint32 content + per-slot checksums: ONE
        dispatch, of a program that the sink's geometry names and the
        arrival order does not."""
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
            step.piece = len(batches)
            count, seconds = compiled()
            flat, sums, xors = _assemble_checksum_jit(
                batches, np.concatenate([s for s, _ in self._batches]),
                self.total_pieces)
            count_after, seconds_after = compiled()
            if count_after > count:
                # A geometry (the batches' shapes, the count of pieces)
                # that this process assembles for the first time.
                _ASSEMBLIES_COMPILED.inc()
                if self.stamp is not None:
                    self.stamp(flight.EV_SINK_COMPILE, len(batches),
                               (seconds_after - seconds) * 1000.0)
            else:
                _ASSEMBLIES_CACHED.inc()
            self._assembled = flat
            self._dev_sums = np.asarray(sums)
            self._dev_xors = np.asarray(xors)
        self._maybe_drop_staging()
        self._bound_jit_cache()
        return self._assembled

    @staticmethod
    def _bound_jit_cache() -> None:
        """Every geometry a daemon lands is a program of its own; a
        long-lived one must not accumulate compiled executables without
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
        sharding = NamedSharding(mesh, P(axis_name))
        if per * n == self.padded_words:
            # device_put on a device array: XLA moves shards device-to-device
            # (ICI on a TPU slice), no host staging.
            return jax.device_put(buf, sharding)
        # The words do not divide by the mesh (a single-piece task, whose
        # piece is the content): pad UP to a shard multiple, truncating
        # would silently drop tail content bytes. Only the last shard is
        # padded, and the shards go out one after the other, so the landing
        # device holds one shard beside the content, not a padded second
        # copy of it for at most n - 1 words.
        shards = []
        for device, (index,) in sharding.addressable_devices_indices_map(
                (per * n,)).items():
            start = index.start or 0
            shard = jax.lax.slice(
                buf, (start,), (min(start + per, self.padded_words),))
            if shard.shape[0] < per:
                shard = jnp.pad(shard, (0, per - shard.shape[0]))
            shards.append(jax.block_until_ready(
                jax.device_put(shard, device)))
        return jax.make_array_from_single_device_arrays(
            (per * n,), sharding, shards)

    def replicate(self, mesh, axis_name: str = "d") -> int:
        """Place the verified content whole on every device of ``mesh``
        and verify every copy where it lies: from here on ``as_words()``
        is ONE array, replicated over the mesh, and every typed view cut
        from it lies on every device too. The copies travel device to
        device, never through the host again: one shard to each device
        (``shard_to_mesh``), then XLA's all-gather
        (``parallel/ici.all_gather_shards``; ICI on a TPU host), the fastest
        of the ways measured on four chips that keeps the landing device at
        or under three contents while it runs: the content, the shards cut
        from it, the result (PERF.md section 6, PR 31). Then each device
        computes the per-piece (sum32, xor32) of its own copy
        (``_chip_checksums_jit``: one program over the mesh, every device
        reading what it holds), and all must equal the host's. Raises ValueError naming the first device and
        piece that differ; the content stays where it was then. Returns
        how many devices received a copy. A mesh that is the landing
        device alone changes nothing and costs nothing."""
        from dragonfly2_tpu.parallel.ici import all_gather_shards

        if not self._verified:
            raise ValueError("replicate before the landing was verified")
        if mesh.shape[axis_name] != mesh.devices.size:
            raise ValueError(
                f"replicate over axis {axis_name!r} of a mesh of shape "
                f"{dict(mesh.shape)}: the devices lie on one axis")
        devices = list(mesh.devices.flat)
        flat = self._assemble()
        if devices == [self.device] or flat.devices() == set(devices):
            return 0    # the plain landing; or placed, and verified, before
        received = len(set(devices) - {self.device})
        with span(self.stamp, flight.EV_SINK_REPLICATE, received):
            words = all_gather_shards(
                mesh, self.shard_to_mesh(mesh, axis_name), axis_name)
            if words.shape[0] != self.padded_words:
                words = words[:self.padded_words]   # shard_to_mesh's pad
            words = jax.block_until_ready(words)
        with span(self.stamp, flight.EV_SINK_VERIFY_CHIPS, len(devices)):
            # Row i is what device i computed from the copy it holds.
            have = np.asarray(_chip_checksums_jit(
                words, mesh=mesh, axis_name=axis_name,
                piece_words=self.piece_words))
            nums = sorted(self.host_checksums)
            want = np.array([self.host_checksums[n] for n in nums],
                            np.uint32)
            differ = np.argwhere((have[:, nums] != want).any(axis=2))
            if differ.size:
                chip, k = (int(v) for v in differ[0])
                got = have[chip, nums[k]]
                raise ValueError(
                    f"piece {nums[k]} corrupt on {devices[chip]} after the "
                    f"fan-out: sum {int(got[0]):#x}!={int(want[k, 0]):#x} "
                    f"xor {int(got[1]):#x}!={int(want[k, 1]):#x}")
        # The landing device's single copy goes with its last reference.
        self._assembled = words
        return received


@functools.partial(jax.jit,
                   static_argnames=("mesh", "axis_name", "piece_words"))
def _chip_checksums_jit(words, *, mesh, axis_name: str, piece_words: int):
    """Per-piece (sum32, xor32) of EVERY device's copy of a replicated word
    buffer, each computed by the device that holds the copy: uint32
    ``(devices, pieces, 2)``, row i on device i. A piece at a time, so a
    device's temporaries are a piece and not a second content."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    pieces = words.shape[0] // piece_words

    @functools.partial(shard_map, mesh=mesh, in_specs=P(),
                       out_specs=P(axis_name), check_vma=False)
    def each(copy):
        def one(i):
            piece = jax.lax.dynamic_slice(copy, (i * piece_words,),
                                          (piece_words,))
            s, x = _chunk_checksums_xla(piece, piece_words)
            return jnp.stack([s[0], x[0]])

        return jax.lax.map(one, jnp.arange(pieces, dtype=jnp.int32))[None]

    return each(words)


# ---------------------------------------------------------------------- #
# Double-buffer hot-swap (checkpoint-delta plane, delta/)
#
# A serving process keeps the LIVE checkpoint generation on device while
# the next one assembles beside it: the words of reused runs are copied
# HBM -> HBM out of the live generation's word buffer (they never leave
# HBM, let alone re-cross DCN), the rest is staged once from the verified
# landing in the host's store, every piece of the result is checksummed on
# the device against the host's sums, and only then does the result replace
# the live generation with ONE atomic reference swap: a reader always sees
# a complete (generation, words, tensors) triple, never a mix.
#
# The assembly is ONE program a geometry (``_swap_copy_jit``): the buffers'
# lengths and the block are its shape, and what is copied from where is an
# int32 operand (``segs``), so no version's set of changed tensors compiles
# anything (a slice a chunk and one concatenate of them all was a program a
# version: some 1,400 operands at the benchmark's shard). Buffers are
# uint32 words, as every landing's are, seen as rows of 128: a step copies
# ``block`` rows from the source to the new buffer under a word mask, so a
# run may begin and end at any word. The new buffer is donated from call to
# call: the live generation first, then each staging slab.
# ---------------------------------------------------------------------- #

_SWAP_BLOCK_ROWS = 1024     # rows of 128 words a step copies: 512 KiB
_SWAP_SLAB_ROWS = 65536     # a staging slab: 32 MiB of pooled host memory

SWAP_BYTES = metrics.counter(
    "device_swap_bytes_total",
    "Content bytes of hot-swapped generations by how they reached the new "
    "buffer: copied HBM -> HBM out of the live generation (hbm_reused), or "
    "read from the verified landing in the store and put (staged); the two "
    "sum to the content", ("how",))
SWAP_HOST_SUMS = metrics.counter(
    "device_swap_host_sums_total",
    "Pieces of hot-swapped landings by where the flip gate's host sums came "
    "from: carried from the commit by the delta job that wrote the piece "
    "(carried), or read back from the store and summed before the gate "
    "(walked)", ("how",))
SWAP_RESULTS = metrics.counter(
    "device_swap_total",
    "Hot-swaps of the client API by how they ended: the verified generation "
    "installed (flipped; returned unflipped where the caller holds no "
    "DoubleBuffer), refused by the verify gate with the old generation "
    "still live (refused), or handed back as a host buffer after the device "
    "path failed (fallback)", ("result",))
SWAP_ASSEMBLIES = metrics.counter(
    "device_swap_assemblies_total",
    "Hot-swap assemblies by whether any of their programs (the copy, the "
    "checksums) was compiled for the swap: a geometry's first (compiled) or "
    "not (cached)", ("how",))


def _pow2_floor(n: int) -> int:
    return 1 << (max(1, n).bit_length() - 1)


def _pow2_ceil(n: int) -> int:
    return 1 << (max(1, n) - 1).bit_length()


class SwapPlan:
    """What ``plan_swap`` made of a delta's runs: the segments that copy
    the live generation's words, and the slabs that bring the rest."""

    __slots__ = ("block", "out_rows", "slab_rows", "live_segs", "slabs",
                 "reused_bytes", "runs")

    def __init__(self, block: int, out_rows: int, slab_rows: int):
        self.block = block
        self.out_rows = out_rows
        self.slab_rows = slab_rows
        self.live_segs: list = []      # (out row, src row, lo, hi)
        # Each slab: (reads, segs); a read is (word in the slab, byte of
        # the content, bytes).
        self.slabs: list = []
        self.reused_bytes = 0
        self.runs = 0


def plan_swap(runs, total: int, padded_words: int,
              live_words: int = 0) -> SwapPlan:
    """The next generation's buffer (``padded_words`` uint32 words holding
    ``total`` content bytes) as copies of whole words: ``runs`` is the new
    content in offset order, ``(dst, src, length, reused)`` in bytes, a
    reused run lying at ``src`` of the live generation's ``live_words``
    words. A word is copied out of the live buffer where it lies wholly
    inside ONE reused run whose ``src - dst`` is whole rows (a multiple of
    512 bytes: versions that replace tensors in place have 0) and the copy
    stays inside both buffers; every other word of the content (a run's
    first and last partial word, a run shifted by less than a row, all of
    it where nothing is live) is read from the verified landing in the
    store. Pure arithmetic: no device, no store."""
    lanes = _LANES
    out_rows = -(-padded_words // lanes)
    live_rows = live_words // lanes if live_words % lanes == 0 else 0
    block = _pow2_floor(min(_SWAP_BLOCK_ROWS, out_rows,
                            live_rows or out_rows))
    plan = SwapPlan(block, out_rows, max(4 * block, min(
        _SWAP_SLAB_ROWS, _pow2_ceil(out_rows))))
    span = block * lanes
    content_words = -(-total // 4)
    staged: list = []       # [a, b) words of the new buffer, in order
    at = 0
    for dst, src, length, reused in runs:
        a, b = -(-dst // 4), min((dst + length) // 4, total // 4)
        dw, rem = divmod(src - dst, 4 * lanes)
        dw *= lanes
        if not (reused and live_rows and rem == 0 and a < b
                and 0 <= a + dw and b + dw <= live_words):
            continue
        drow = dw // lanes
        low = max(0, -drow)
        high = min(out_rows - block, live_rows - block - drow)
        segs = []
        x = a
        while x < b:
            row = min(x // lanes, high)
            if row < low:
                break
            y = min(b, (row + block) * lanes)
            segs.append((row, row + drow, x - row * lanes, y - row * lanes))
            x = y
        if x < b:
            continue        # no block of the live buffer holds it: staged
        plan.runs += 1
        if a > at:
            staged.append((at, a))
        at = b
        plan.live_segs += segs
        plan.reused_bytes += 4 * (b - a)
    if content_words > at:
        staged.append((at, content_words))
    # The staged words packed into slabs: a piece lies at the lane it has
    # in the new buffer, a block clear of either end, so that a step's
    # window of the slab never leaves it.
    slab_words = plan.slab_rows * lanes
    reads: list = []
    segs = []
    q = span
    for a, b in staged:
        while a < b:
            q += (a - q) % lanes
            take = min(b - a, slab_words - span - q)
            if take <= 0:
                plan.slabs.append((reads, segs))
                reads, segs, q = [], [], span
                continue
            reads.append((q, 4 * a, min(4 * (a + take), total) - 4 * a))
            x = a
            while x < a + take:
                row = min(x // lanes, out_rows - block)
                y = min(a + take, (row + block) * lanes)
                segs.append((row, row + (q - a) // lanes, x - row * lanes,
                             y - row * lanes))
                x = y
            q += take
            a += take
    if reads:
        plan.slabs.append((reads, segs))
    return plan


def _seg_table(segs, src, block: int) -> "tuple[np.ndarray, np.int32]":
    """A plan's segments as the operand of ``_swap_copy_jit``: int32 rows
    padded to a power of two (the table's shape is part of the program, the
    count a scalar beside it), at least twice the blocks ``src`` holds: a
    source copied whole with a run's end in every other block still takes
    the table that a dozen runs do."""
    table = np.zeros((_pow2_ceil(max(len(segs), 2 * src.shape[0]
                                     // (block * _LANES))), 4), np.int32)
    if segs:
        table[:len(segs)] = segs
    return table, np.int32(len(segs))


@functools.partial(jax.jit, static_argnames=("block",), donate_argnums=(0,))
def _swap_copy_jit(out, src, segs, count, *, block: int):
    """``count`` segments of ``src`` copied into ``out``, which is donated:
    segment ``(o, s, lo, hi)`` puts words ``lo..hi`` of the ``block`` rows
    from row ``s`` of ``src`` over the same words of the rows from ``o`` of
    ``out``. Both are flat uint32 buffers of whole rows of 128 words."""
    rows = out.reshape(-1, _LANES)
    src = src.reshape(-1, _LANES)
    at = (jax.lax.broadcasted_iota(jnp.int32, (block, _LANES), 0) * _LANES
          + jax.lax.broadcasted_iota(jnp.int32, (block, _LANES), 1))

    def place(k, rows):
        o, s, lo, hi = segs[k, 0], segs[k, 1], segs[k, 2], segs[k, 3]
        new = jax.lax.dynamic_slice(src, (s, 0), (block, _LANES))
        old = jax.lax.dynamic_slice(rows, (o, 0), (block, _LANES))
        return jax.lax.dynamic_update_slice(
            rows, jnp.where((at >= lo) & (at < hi), new, old), (o, 0))

    return jax.lax.fori_loop(0, count, place, rows).reshape(-1)


def stage_swap(plan: SwapPlan, read_into, device) -> list:
    """The plan's slabs on ``device``: each filled from the store
    (``read_into(start, length, buffer)``) in pooled host memory, put, and
    given back once the runtime has read it."""
    slabs = []
    nbytes = plan.slab_rows * _LANES * 4
    for reads, _ in plan.slabs:
        view = _STAGING.acquire(nbytes)
        try:
            for q, start, length in reads:
                read_into(start, length, view[4 * q:4 * q + length])
                view[4 * q + length:4 * q + length + (-length) % 4] = \
                    bytes((-length) % 4)
            slabs.append(jax.block_until_ready(
                _put(np.frombuffer(view, np.uint32), device)))
        finally:
            _give_back(view)
    return slabs


def assemble_swap_words(live, plan: SwapPlan, slabs: list, device):
    """The next generation's word buffer: zeros, then the live generation's
    reused words, then each slab's, one dispatch each of the one program."""
    out = jnp.zeros((plan.out_rows * _LANES,), jnp.uint32, device=device)
    if plan.live_segs:
        out = _swap_copy_jit(
            out, live, *_seg_table(plan.live_segs, live, plan.block),
            block=plan.block)
    for slab, (_, segs) in zip(slabs, plan.slabs):
        out = _swap_copy_jit(out, slab, *_seg_table(segs, slab, plan.block),
                             block=plan.block)
    return jax.block_until_ready(out)


@functools.partial(jax.jit, static_argnames=("piece_words",))
def _words_checksums_jit(words, *, piece_words: int):
    """Per-piece (sum32[n], xor32[n]) of a flat word buffer of whole
    pieces, one piece per loop iteration: temporaries of the order of a
    piece, and no bound but the device's memory."""
    def one(i):
        piece = jax.lax.dynamic_slice(words, (i * piece_words,),
                                      (piece_words,))
        s, x = _chunk_checksums_xla(piece, piece_words)
        return s[0], x[0]

    return jax.lax.map(one, jnp.arange(words.shape[0] // piece_words,
                                       dtype=jnp.int32))


class SwapVerifyError(ValueError):
    """The new generation's words differ from the verified landing."""


def verify_words_against_host(words, piece_size: int,
                              host_checksums: "dict[int, tuple[int, int]]") -> None:
    """On-device verification gate for a hot-swap flip: per-piece
    (sum32, xor32) of the device's word buffer (whole pieces, zeros past
    the content) compared against host-side values (checksum_numpy of the
    verified landing's pieces, wherever taken: by the delta job that
    committed a piece or by a walk of the store; none is trusted over the
    device's). Raises SwapVerifyError (a ValueError) naming the first
    mismatching piece; the flip must not happen."""
    if piece_size % 4:
        raise ValueError(f"piece size {piece_size} not 4-byte aligned")
    piece_words = piece_size // 4
    if words.shape[0] % piece_words:
        raise ValueError(f"{words.shape[0]} words are no whole pieces of "
                         f"{piece_words}")
    sums, xors = (np.asarray(c) for c in _words_checksums_jit(
        words, piece_words=piece_words))
    for num, (want_s, want_x) in sorted(host_checksums.items()):
        have = (int(sums[num]), int(xors[num]))
        if have != (want_s, want_x):
            raise SwapVerifyError(
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
        """(generation, words, tensors) — one consistent triple."""
        return self._state

    def tensors(self) -> dict:
        return self._state[2]

    def buffer(self):
        return self._state[1]

    def flip(self, buffer, tensors: dict) -> int:
        """Install the next generation: its uint32 word buffer (whole
        pieces, as ``HBMSink.as_words``) and the tensors cut from it.
        Callers flip ONLY verified buffers (verify_words_against_host /
        HBMSink.verify)."""
        gen = self._state[0] + 1
        self._state = (gen, buffer, dict(tensors))
        return gen
