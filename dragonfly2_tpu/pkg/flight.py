"""Flight recorder: always-on per-task event timelines + critical-path autopsy.

Reference posture: the reference wires OpenTelemetry per binary
(cmd/dependency/dependency.go:263-271), but spans answer "what called
what", not "where did the wall time go" — when a pod broadcast degrades,
the question is Dapper/Mystery-Machine shaped: reconstruct the critical
path from always-on, bounded-cost event logs. This module is that black
box for the data plane:

  * every task gets a bounded ring of typed, monotonic-clocked events
    emitted at the choke points chaos already instruments (register,
    schedule pushes, piece assign/request/first-byte/landed/verified,
    parent drops, quarantine, stripe reshuffles, back-to-source, HBM
    landing, upload serving);
  * ``analyze()`` folds a task's events into a phase breakdown
    (sched_wait / dcn / ici / hbm / verify / store / stall / origin) whose
    segments partition the task's wall time exactly (a residual bucket
    ``other`` absorbs uninstrumented gaps), plus a per-piece waterfall;
  * the daemon serves it at ``/debug/flight[/<task_id>]`` (pkg/
    metrics_server), dumps a post-mortem JSON bundle on task failure,
    and feeds ``peer_task_phase_seconds{phase}`` histograms;
  * piece reports carry per-piece phase timings on the wire so the
    scheduler's ``PodAggregator`` can attribute stragglers per host
    (``/debug/pod/<task_id>``: slowest host, dominant phase, quarantine
    correlation).

Hot-path contract: ``TaskFlight.record`` appends ONE tuple into a
preallocated ring — no per-event dict, no I/O, no lock — so the recorder
stays on in production (tests/test_flight.py pins the bound and the
no-dict property).
"""

from __future__ import annotations

import gzip
import itertools
import json
import operator
import os
import threading
import time
from collections import OrderedDict

from dragonfly2_tpu.pkg import dflog, metrics

log = dflog.get("flight")

# Monotonic-anchored wall clock: wall is sampled ONCE at import and every
# later reading is anchor + perf_counter delta, so an NTP step mid-run
# cannot skew any timeline or clock sample built from it. Everything this
# module (and the pod-lens clock alignment on top of it) calls "wall time"
# is this clock, optionally plus a per-recorder offset (the chaos knob
# that lets a test inject a known skew).
_WALL_ANCHOR = time.time()
_PC_ANCHOR = time.perf_counter()


def anchored_wall() -> float:
    return _WALL_ANCHOR + (time.perf_counter() - _PC_ANCHOR)

# --------------------------------------------------------------------- #
# Event vocabulary (ints in the ring; names only at export time)
# --------------------------------------------------------------------- #

EV_REGISTER = 1        # announce register sent
EV_SCHEDULED = 2       # scheduler answered the register (note=kind)
EV_SCHED_PUSH = 3      # mid-task scheduler push (note=kind)
EV_RESCHEDULE = 4      # reschedule sent (starvation)
EV_SCHED_ANSWER = 5    # reschedule answered / schedule update applied
EV_RECONNECT = 6       # announce-stream recovery attempt (note=result)
EV_REQUEST = 7         # piece GET issued (note=parent ip:port)
EV_FIRST_BYTE = 8      # first body chunk arrived
EV_LANDED = 9          # piece verified+recorded (aux=cost_ms, note=locality)
EV_FAILED = 10         # piece attempt failed (note=typed reason)
EV_STORE_START = 11    # store write handed to the executor
EV_STORED = 12         # store write committed
EV_VERIFY_START = 13   # completion whole-content digest started (piece=pieces the prefix hasher had hashed, aux=pieces still to hash)
EV_VERIFIED = 14       # ... done (aux=ms since verify_start, piece=pieces read back from the store, note=prefix|rehash)
EV_PARENT_DROP = 15    # dispatcher dropped a parent (note=peer id)
EV_QUARANTINE = 16     # parent entered quarantine (note=endpoint|reason)
EV_STRIPE = 17         # stripe plan applied/cleared (aux=slice_size)
EV_BACK_SOURCE = 18    # task demoted to origin
EV_SOURCE_LANDED = 19  # origin piece landed (aux=cost_ms)
EV_HBM_START = 20      # device-sink landing started
EV_HBM_LANDED = 21     # device-sink landing done
EV_UPLOAD_SERVE = 22   # this daemon served a piece of the task: stamped at the send's end (aux=ms of the send, piece=num or -1 for a range, note="<bytes>" or "<bytes> wait=<ms>")
EV_TASK_DONE = 23
EV_TASK_FAILED = 24
EV_DELTA_REUSE = 25    # delta chunk copied from the local base (aux=cost_ms)
EV_DELTA_FETCH = 26    # delta chunk pulled as a ranged task (aux=cost_ms)
# A HOLD of the event loop, 20 ms and more, during this task: ONE event at
# its end, aux = its SECONDS, piece = the loop thread's cpu ms inside it, note
# = "held n=<handles> gc=<ms> who=<file:func:line>" (a turn that long: what
# every other handle waited behind) or "late" (a due timer waited that long
# while the loop still sat in select). pkg/prof stamps it, on the loop's own
# ring (below, EV_LOOP_ACCT) and into every running task's.
EV_LOOP_LAG = 27
EV_GC_PAUSE = 28       # slow cyclic-GC pause during this task (aux=pause_s)
# Spans of the device-sink landing thread: ONE event at the span's end,
# aux = its duration in ms (start = t - aux/1000, as landed/source_landed
# back theirs out). A task's are stamped one after the other, by the one
# df-device-sink thread and, from a finalize's hand-over on, by the one
# completer behind it (sink_assemble, sink_compile, sink_tail,
# sink_finalize), so a span's children are the spans its interval contains.
EV_SINK_LAND = 29      # one host pass and the staging of its pieces: a piece as it arrives, a group of a finalize's backfill (piece=num, the group's lowest)
# The pieces the sink reads itself cost ONE host pass a group
# (HBMSink.read_pieces: each helper takes the next chunk, reads it from the
# store into its row and checksums it before it returns); the two events are
# that pass's two parts, stamped together, once a group.
EV_SINK_READ = 30      # what the thread that read longest spent reading inside the pass (piece=num, the group's lowest, note=chunks)
EV_SINK_CHECKSUM = 31  # the pass less that (same piece and note); or a checksum pass over bytes a caller brought (piece=num, note=chunks)
EV_SINK_STAGE = 32     # what is left of staging: taking a stack, copying foreign bytes, zeroing a short tail, the batch's order (piece=num, lowest slot, or -1)
EV_SINK_PUT = 33       # flush: the device_put call (piece=lowest slot)
EV_SINK_ASSEMBLE = 34  # the sink's puts awaited, assembly dispatch -> checksums on host (piece=batches)
EV_SINK_COMPILE = 35   # backend compile inside that assembly (piece=batches)
EV_SINK_FINALIZE = 36  # backfill + assemble + verify, job start -> verified (piece=pieces backfilled, note="chip=<id>": the local device the sink lies on)
EV_PARENT_PIECES = 37  # a parent announced pieces (piece=lowest, aux=how many)
# A job's wait for the landing thread, stamped as the job starts there:
# submission on the event loop -> start on the thread. A sibling of the
# sink_land / sink_finalize that follows it, not a child: it ends where
# that one begins.
EV_SINK_WAIT = 38      # (piece=num for on_piece, 0 for finalize)
# A landing placed whole on every chip of a mesh (the same thread, after the
# verified flat is on the landing chip): the copies travel chip to chip.
EV_SINK_REPLICATE = 39     # fan-out dispatched -> every chip's copy ready (piece=other chips)
EV_SINK_VERIFY_CHIPS = 40  # per-chip checksums dispatched -> all compared (piece=chips)
# The client API's own steps (client/device.py), stamped on the event loop:
# ONE event at the step's end, aux = its ms, as the sink_* spans. The first on
# the flight of the task that waited; the other two on the flight of a sharded
# pull's header task (download_sharded's or download_global's), which stands
# for the whole call.
EV_ADMIT_WAIT = 41     # a device pull's wait at device_sinks.admit(), stamped as the task starts
EV_SHARD_PLAN = 42     # download_sharded / download_global called -> header landed, parsed, spans planned, by destination in download_global (piece=ranged tasks planned)
EV_SHARD_VIEWS = 43    # the typed views of every span dispatched and, in download_global, the global arrays made of them (piece=tensors returned)
# The completion path and the source client: ONE event at the span's end,
# aux = its ms, as above. source_first_byte is on the flight of whichever
# task pulls from the origin (a seed's, a peer's own back-to-source);
# cert_wait and parent_done on a P2P child's. The parent_* pair are a
# PARENT's spans, carried by its sync stream (``spans``, see SpanRelay) and
# stamped on the child's flight as they arrive: durations on the parent's
# clock, not intervals of the child's.
EV_SOURCE_FIRST_BYTE = 44  # an origin request's first body byte (piece=first piece it covers, note="native": its first piece)
EV_CERT_WAIT = 45      # conductor._await_certification returned (piece=digest maps tried, note=how it ended)
EV_PARENT_DONE = 46    # a parent's sync stream said done: a point (piece=pieces that parent holds)
EV_PARENT_SOURCE_FIRST_BYTE = 47  # the parent's source_first_byte (piece=the parent's first piece)
EV_PARENT_VERIFIED = 48  # the parent's verify_start -> verified (piece=pieces its hasher had still to hash)
# Where a task's bytes came from: ONE event as the conductor ends, whichever
# way (piece=distinct parents that served a piece, aux=bytes from parents that
# are not seeds, note="seed=<bytes> peer=<bytes> origin=<bytes>").
EV_TASK_SOURCES = 49
# A device pull of the client API, whole: ONE event as download_to_device has
# the verified sink in hand, placed where the caller asked (aux = ms since its
# admission, so the task, its landing and any fan-out lie inside; piece = the
# id of the local device the bytes landed on; note = "chips=<id>,<id>,.." where
# the words lie on more chips than that one, the landing chip first).
EV_DEVICE_PULL = 50
# A finalize's tail, off the landing thread: ONE event as the tail ends,
# inside its sink_finalize (aux = ms since the landing thread handed the
# sink over with its last stack's device_put: the wait behind earlier tails,
# the wait for the sink's puts, the assembly, the fetched checksums, the
# comparison; piece = jobs the landing thread STARTED in that time, 0 where
# nothing ran beside the tail).
EV_SINK_TAIL = 51
# A ranged task's slice read out of a whole (or covering partial) parent store
# of THIS host: ONE event as the import ends, on the ranged task's flight
# (aux = ms of its reads and writes, piece = pieces imported, note = bytes).
EV_RANGE_IMPORT = 52
# The dataset plane (dataset/): the loader, its shard readers and the device
# feed stamp ONE feed-level ring (``PodShardedLoader.flight``; a task's own
# ring lives for 128 tasks, a sample is a task or none), each span ONE event
# at its end with aux = its ms, as the sink_* spans.
# feed_sample: a sample's read, launched by the readahead -> its spans
# resolved -> each read out of this store's parent or its ranged task done ->
# the bytes in the pooled buffers (piece = its place in the host's epoch
# plan; note = "src=<local|reuse|import|peer|cold|origin> tasks=<n>
# bytes=<fetched> task=<ms> move=<ms> read=<ms>": where the bytes came from,
# the worst of its spans, ``local`` this store's parent with no task; ranged
# tasks the spans ran, 0 for a sample read from this store; span bytes; the
# read less ``read``; what a task spent moving the bytes, its range_import or
# its pieces' transfers, 0 with no task; the store -> the pooled buffer).
EV_FEED_SAMPLE = 53
# feed_wait: what the feed's consumer side stood waiting for samples while it
# gathered one batch (piece = batch, aux = the summed ms, note = records).
EV_FEED_WAIT = 54
# feed_batch: a batch's landing, first record staged -> last flushed ->
# verified on the device -> as_record_batch dispatched (piece = batch; note =
# "path=<hbm|numpy> n=<records> rows=<rows> payload=<bytes> put=<bytes>
# stage=<ms> verify=<ms> view=<ms>": ``rows`` the rows landed, on the device
# path the feed's one geometry (batch_size, the epoch's short last batch
# too: its rows past ``n`` are empty records and ``view`` holds its slice),
# ``put`` those rows' padded bytes; on the NumPy path ``n`` and 0). The
# feed's HBMSink stamps its sink_stage, sink_checksum (one a row, the empty
# ones too), sink_put, sink_assemble and sink_compile on the same ring with
# "batch=<k>" leading their note.
EV_FEED_BATCH = 55
# A hot-swap of the client API (client/device.py ``download_delta``), on the
# delta task's flight: each span ONE event at its end with aux = its ms, as
# the sink_* spans. swap_plan is stamped twice where the delta proper ran:
# by the resolver as the task starts (both manifests fetched over the fabric,
# or the base's built from its store: note = "fetched" | "built"; piece =
# chunks of the new version) and by the device half (the manifests read
# again from this store, ``plan_delta``, ``plan_swap``; piece = runs copied
# out of the live generation, note as above, "whole" where nothing is
# reused). Then, in order: swap_stage (the words no run holds read from the
# verified landing into slabs and put; piece = slabs, note = bytes),
# swap_assemble (the copy programs dispatched -> the new words ready; piece
# = dispatches, note = "compiled" where a program was), swap_verify (the
# host's sums of every piece of the landing, the device's of every piece of
# the new words, the comparison; piece = pieces), swap_views (the typed
# tensors cut from the new words; piece = tensors), swap_flip (a point: the
# generation installed, or handed back where the caller holds no
# DoubleBuffer; piece = generation, aux = ms since download_delta was
# called, note = "fallback" where the generation is a host buffer).
EV_SWAP_PLAN = 56
EV_SWAP_STAGE = 57
EV_SWAP_ASSEMBLE = 58
EV_SWAP_VERIFY = 59
EV_SWAP_VIEWS = 60
EV_SWAP_FLIP = 61
# The account the loop's thread keeps of itself (pkg/prof LoopLagProbe), on a
# ring of its own, ``runtime:loop:<name>``, that is no task's: a SLICE, one
# event as a turn ends once 5 ms of busy time have gathered since the last
# (and at every hold's end), aux = those busy ms (select exit -> the next
# select entry, summed), piece = the thread's cpu us inside them, note =
# "late=<ms> gc=<ms> it=<iterations> n=<handles>". The same ring holds the
# loop's ``loop_lag`` holds.
EV_LOOP_ACCT = 62
# A save of the client API (client/device.py ``save_from_device``), on the
# persistent cache task's flight: each span ONE event at its end with aux =
# its ms, as the sink_* spans. In order: save_pack (the programs that place
# the tensors' bytes in the file's words and checksum them, dispatched ->
# the sums on the host; piece = tensors, note = the file's bytes),
# save_snapshot (the call -> the handle returned: the caller's stall, the
# layout and save_pack inside it; piece = pieces, note = bytes); then behind
# the caller save_d2h (a group of pieces copied to the host; piece = the
# group's first, note = bytes), save_commit (one a piece, on a worker
# thread: its host sums held equal to the device's, its digest, its write;
# piece = num, note = bytes), save_digest (the digest thread's sha256 over
# a group; piece = the group's first, note = bytes), save_replicated
# (``Finished`` sent -> the scheduler's answer that ``replicas`` hosts hold
# a verified copy; piece = holders, note = their host ids). With replicas
# asked at ``Started`` (the import carried its geometry) that span is the
# tail only, and ONE point says so as ``Finished`` is sent:
# save_replica_ahead (piece = the pieces of the task this host's upload side
# has served to other hosts by then, aux = ms from ``Started`` answered to
# the first such send's start, note = the bytes sent).
EV_SAVE_SNAPSHOT = 63
EV_SAVE_PACK = 64
EV_SAVE_D2H = 65
EV_SAVE_COMMIT = 66
EV_SAVE_DIGEST = 67
EV_SAVE_REPLICATED = 68
EV_SAVE_REPLICA_AHEAD = 69

EVENT_NAMES = {
    EV_REGISTER: "register", EV_SCHEDULED: "scheduled",
    EV_SCHED_PUSH: "sched_push", EV_RESCHEDULE: "reschedule",
    EV_SCHED_ANSWER: "sched_answer", EV_RECONNECT: "reconnect",
    EV_REQUEST: "request", EV_FIRST_BYTE: "first_byte",
    EV_LANDED: "landed", EV_FAILED: "failed",
    EV_STORE_START: "store_start", EV_STORED: "stored",
    EV_VERIFY_START: "verify_start", EV_VERIFIED: "verified",
    EV_PARENT_DROP: "parent_drop", EV_QUARANTINE: "quarantine",
    EV_STRIPE: "stripe", EV_BACK_SOURCE: "back_source",
    EV_SOURCE_LANDED: "source_landed", EV_HBM_START: "hbm_start",
    EV_HBM_LANDED: "hbm_landed", EV_UPLOAD_SERVE: "upload_serve",
    EV_TASK_DONE: "task_done", EV_TASK_FAILED: "task_failed",
    EV_DELTA_REUSE: "delta_reuse", EV_DELTA_FETCH: "delta_fetch",
    EV_LOOP_LAG: "loop_lag", EV_GC_PAUSE: "gc_pause",
    EV_SINK_LAND: "sink_land", EV_SINK_READ: "sink_read",
    EV_SINK_CHECKSUM: "sink_checksum", EV_SINK_STAGE: "sink_stage",
    EV_SINK_PUT: "sink_put", EV_SINK_ASSEMBLE: "sink_assemble",
    EV_SINK_COMPILE: "sink_compile", EV_SINK_FINALIZE: "sink_finalize",
    EV_PARENT_PIECES: "parent_pieces", EV_SINK_WAIT: "sink_wait",
    EV_SINK_REPLICATE: "sink_replicate",
    EV_SINK_VERIFY_CHIPS: "sink_verify_chips",
    EV_ADMIT_WAIT: "admit_wait", EV_SHARD_PLAN: "shard_plan",
    EV_SHARD_VIEWS: "shard_views",
    EV_SOURCE_FIRST_BYTE: "source_first_byte", EV_CERT_WAIT: "cert_wait",
    EV_PARENT_DONE: "parent_done",
    EV_PARENT_SOURCE_FIRST_BYTE: "parent_source_first_byte",
    EV_PARENT_VERIFIED: "parent_verified",
    EV_TASK_SOURCES: "task_sources", EV_DEVICE_PULL: "device_pull",
    EV_SINK_TAIL: "sink_tail", EV_RANGE_IMPORT: "range_import",
    EV_FEED_SAMPLE: "feed_sample", EV_FEED_WAIT: "feed_wait",
    EV_FEED_BATCH: "feed_batch",
    EV_SWAP_PLAN: "swap_plan", EV_SWAP_STAGE: "swap_stage",
    EV_SWAP_ASSEMBLE: "swap_assemble", EV_SWAP_VERIFY: "swap_verify",
    EV_SWAP_VIEWS: "swap_views", EV_SWAP_FLIP: "swap_flip",
    EV_LOOP_ACCT: "loop_acct",
    EV_SAVE_SNAPSHOT: "save_snapshot", EV_SAVE_PACK: "save_pack",
    EV_SAVE_D2H: "save_d2h", EV_SAVE_COMMIT: "save_commit",
    EV_SAVE_DIGEST: "save_digest", EV_SAVE_REPLICATED: "save_replicated",
    EV_SAVE_REPLICA_AHEAD: "save_replica_ahead",
}

# Runtime-interference events (pkg/prof stamps them into every RUNNING
# flight): not phase markers — the analyzer summarizes them separately
# so --explain can say the LOOP was wedged, not just "nothing happened".
_RUNTIME_EVENTS = (EV_LOOP_LAG, EV_GC_PAUSE)

# The landing thread's steps and a finalize's tail behind it, then the
# fan-out over the mesh and the verification on every chip, and last the
# jobs' wait for the thread, summed into the report's ``hbm`` block.
_SINK_STEPS = (EV_SINK_LAND, EV_SINK_READ, EV_SINK_CHECKSUM, EV_SINK_STAGE,
               EV_SINK_PUT, EV_SINK_ASSEMBLE, EV_SINK_COMPILE,
               EV_SINK_FINALIZE, EV_SINK_TAIL, EV_SINK_REPLICATE,
               EV_SINK_VERIFY_CHIPS, EV_SINK_WAIT)
# The client API's steps, summed into the report's ``client`` block.
_CLIENT_STEPS = (EV_ADMIT_WAIT, EV_SHARD_PLAN, EV_SHARD_VIEWS, EV_SWAP_PLAN,
                 EV_SWAP_STAGE, EV_SWAP_ASSEMBLE, EV_SWAP_VERIFY,
                 EV_SWAP_VIEWS, EV_SWAP_FLIP, EV_SAVE_SNAPSHOT, EV_SAVE_PACK,
                 EV_SAVE_D2H, EV_SAVE_COMMIT, EV_SAVE_DIGEST,
                 EV_SAVE_REPLICATED)
# Chip-to-chip work of a landing: booked under ``ici`` beside the
# intra-slice piece transfers.
_ICI_STEPS = (EV_SINK_REPLICATE, EV_SINK_VERIFY_CHIPS)
# A parent's spans as its sync stream names them -> the event a child
# stamps for each (SpanRelay sends, PieceDispatcher.note_parent_spans stamps).
PARENT_SPANS = {"source_first_byte": EV_PARENT_SOURCE_FIRST_BYTE,
                "verified": EV_PARENT_VERIFIED}

# Canonical phase model. ``other`` (residual uninstrumented time) rides
# alongside so the fold partitions wall time exactly.
PHASES = ("sched_wait", "dcn", "ici", "hbm", "verify", "store", "stall",
          "origin")

# Overlap priority: when two phases cover the same wall segment, the one
# doing WORK wins (a stall that overlaps a concurrent healthy transfer
# did not cost wall time).
_PRIORITY = {"verify": 7, "store": 6, "hbm": 5, "ici": 4, "dcn": 3,
             "origin": 2, "stall": 1, "sched_wait": 0}

# A first byte later than this after the request counts the gap as stall
# (the parent was connected but silent) instead of transfer time.
STALL_TTFB_S = 0.25
# A hold of the loop this long is a wedge (ProfConfig.lag_slow_s' default
# and the loop_lag SLO's threshold); shorter ones only name their holder.
WEDGED_S = 0.25

PHASE_SECONDS = metrics.histogram(
    "peer_task_phase_seconds",
    "Per-task phase durations from the flight recorder's critical-path fold",
    ("phase",),
    buckets=(0.005, 0.02, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0,
             60.0))

# record() keeps per-piece slots for the wire-report timings; maps event
# code -> slot index in the 5-float row [request, first_byte, landed,
# store_start, stored].
_TRACK_SLOT = {EV_REQUEST: 0, EV_FIRST_BYTE: 1, EV_LANDED: 2,
               EV_STORE_START: 3, EV_STORED: 4}


class TaskFlight:
    """One task's bounded event ring. All times are seconds relative to
    the task's start on the monotonic clock (NTP steps cannot skew a
    timeline); ``start_wall`` anchors export to wall time."""

    __slots__ = ("task_id", "start_wall", "_start_pc", "_cap", "_ring",
                 "_seq", "state", "note", "_end_pc", "_piece_track",
                 "_piece_cap", "__weakref__")

    def __init__(self, task_id: str, capacity: int = 2048,
                 piece_track_cap: int = 4096, wall_offset: float = 0.0):
        self.task_id = task_id
        self.start_wall = anchored_wall() + wall_offset
        self._start_pc = time.perf_counter()
        self._cap = capacity
        self._ring: list = [None] * capacity
        # Slot allocator. The event loop and the device-sink thread record
        # into one ring: next() on a count hands each caller its own index
        # in one C call, where ``n += 1`` would lose updates.
        self._seq = itertools.count()
        self.state = "running"
        self.note = ""
        self._end_pc = -1.0
        self._piece_track: dict[int, list] = {}
        self._piece_cap = piece_track_cap

    # -- hot path ----------------------------------------------------------

    def record(self, code: int, piece: int = -1, aux: float = 0.0,
               note: str = "") -> None:
        """Append one event: a tuple into the preallocated ring. MUST stay
        allocation-light (no dict literals / kwargs expansion on this
        path — test_flight pins the bytecode)."""
        t = time.perf_counter() - self._start_pc
        self._ring[next(self._seq) % self._cap] = (t, code, piece, aux, note)
        if piece >= 0 and code in _TRACK_SLOT:
            slot = _TRACK_SLOT[code]
            track = self._piece_track.get(piece)
            if track is None:
                if len(self._piece_track) >= self._piece_cap:
                    self._piece_track.pop(next(iter(self._piece_track)))
                track = self._piece_track[piece] = [-1.0, -1.0, -1.0, -1.0,
                                                    -1.0]
            if slot == 0:
                # New attempt: the previous attempt's marks are stale.
                track[1] = track[2] = track[3] = track[4] = -1.0
            track[slot] = t

    def record_at(self, t_pc: float, code: int, piece: int = -1,
                  aux: float = 0.0, note: str = "") -> None:
        """An event that ended at ``t_pc`` on ``time.perf_counter()``, stamped
        after the fact: what a thread outside Python measured (the native
        upload server's sends) and Python only learns when it drains."""
        self._ring[next(self._seq) % self._cap] = (
            t_pc - self._start_pc, code, piece, aux, note)

    # -- accessors ---------------------------------------------------------

    @property
    def events_total(self) -> int:
        # A count cannot be read without advancing it, except through its
        # repr, "count(n)": n slots have been handed out.
        return int(repr(self._seq)[6:-1])

    @property
    def events_dropped(self) -> int:
        return max(0, self.events_total - self._cap)

    def wall_s(self) -> float:
        end = self._end_pc if self._end_pc >= 0 else (
            time.perf_counter() - self._start_pc)
        return max(0.0, end)

    def wall_now(self) -> float:
        """This task's anchored wall clock right now (start_wall + the
        monotonic delta, so it carries the recorder's wall offset and is
        NTP-step-proof) — what clock-alignment samples stamp."""
        return self.start_wall + (time.perf_counter() - self._start_pc)

    def events(self) -> list:
        """Chronological retained events (oldest dropped on overflow).
        Two threads can take their slots in the other order than their
        clocks, and a slot handed out may not be written yet: so by time,
        not by slot, and empty slots left out."""
        out = [e for e in self._ring if e is not None]
        out.sort(key=operator.itemgetter(0))
        return out

    def tail(self, seen: int) -> "tuple[list, int]":
        """The events in the slots handed out since ``seen`` (an earlier
        call's second value; 0: every retained one), in slot order, and the
        count to pass next time. A slot another thread has taken and not
        written yet is empty or, once the ring has wrapped, still holds the
        event of a lap ago: the caller tells those by their time."""
        total = self.events_total
        ring, cap = self._ring, self._cap
        out = [e for e in (ring[i % cap]
                           for i in range(max(seen, total - cap), total))
               if e is not None]
        return out, total

    def finish(self, state: str, note: str = "") -> None:
        self.record(EV_TASK_DONE if state == "done" else EV_TASK_FAILED,
                    -1, 0.0, note)
        self.state = state
        self.note = note
        self._end_pc = time.perf_counter() - self._start_pc

    def piece_report_timings(self, piece: int) -> "dict | None":
        """Per-phase ms for the wire piece report (scheduler straggler
        attribution): dcn_ms / stall_ms / store_ms. None when this piece
        recorded no request (origin/imported pieces)."""
        tr = self._piece_track.get(piece)
        if tr is None or tr[0] < 0:
            return None
        out: dict = {}
        store = 0.0
        if tr[3] >= 0 and tr[4] >= tr[3]:
            store = (tr[4] - tr[3]) * 1000.0
            out["store_ms"] = int(store)
        if tr[2] >= 0:
            total = (tr[2] - tr[0]) * 1000.0
            stall = 0.0
            if tr[1] >= 0 and (tr[1] - tr[0]) > STALL_TTFB_S:
                stall = (tr[1] - tr[0]) * 1000.0
            # dcn is what remains of the attempt after the silent gap and
            # the store write — the phases must not double-count.
            out["dcn_ms"] = int(max(0.0, total - stall - store))
            out["stall_ms"] = int(stall)
        return out or None


class SpanRelay:
    """One sync stream's cursor over the PARENT's ring for a task: the two
    spans only the parent can measure, each handed out once, for the next
    message to the child (``spans``: ``[[name, ms, piece], ...]``).
    ``source_first_byte`` goes as it is; ``verified`` goes with its
    ``verify_start``'s pieces still to hash as its piece. Costs one pass over
    the events recorded since the last message. Both names are stamped on the
    event loop that also sends the messages, so they are written before they
    are looked for and their times only grow."""

    __slots__ = ("_tf", "_seen", "_t", "_behind")

    def __init__(self, tf: TaskFlight):
        self._tf = tf
        self._seen = 0
        self._t = -1.0       # newest time met so far: older is a lap ago
        self._behind = -1

    def take(self) -> list:
        events, self._seen = self._tf.tail(self._seen)
        floor, out = self._t, []
        for t, code, piece, aux, _note in events:
            if t <= floor:
                continue
            if t > self._t:
                self._t = t
            if code == EV_SOURCE_FIRST_BYTE:
                out.append(["source_first_byte", round(aux, 3), piece])
            elif code == EV_VERIFY_START:
                self._behind = int(aux)
            elif code == EV_VERIFIED:
                out.append(["verified", round(aux, 3), self._behind])
        return out


# --------------------------------------------------------------------- #
# Critical-path analyzer
# --------------------------------------------------------------------- #

def _fold_phases(intervals: list, wall: float) -> "tuple[dict, float, list]":
    """Partition [0, wall] across phase intervals: a sweep assigns each
    elementary segment to the highest-priority phase active in it, so the
    per-phase sums plus the residual ``other`` equal ``wall`` exactly.
    Also returns the assigned timeline as merged ``(start, end, phase)``
    segments (gaps omitted) — the pod lens ships these so a cross-host
    merge can draw phase-colored bars without re-shipping raw rings."""
    marks: list = []
    for s, e, ph in intervals:
        s = min(max(s, 0.0), wall)
        e = min(max(e, 0.0), wall)
        if e > s:
            marks.append((s, 1, ph))
            marks.append((e, -1, ph))
    phases = {ph: 0.0 for ph in PHASES}
    if not marks:
        return phases, wall, []
    marks.sort(key=lambda m: m[0])
    active = {ph: 0 for ph in PHASES}
    other = 0.0
    prev = 0.0
    segments: list = []
    i, n = 0, len(marks)
    while i < n:
        t = marks[i][0]
        if t > prev:
            best, bp = "", -1
            for ph, count in active.items():
                if count > 0 and _PRIORITY[ph] > bp:
                    best, bp = ph, _PRIORITY[ph]
            if best:
                phases[best] += t - prev
                if segments and segments[-1][2] == best \
                        and segments[-1][1] == prev:
                    segments[-1][1] = t
                else:
                    segments.append([prev, t, best])
            else:
                other += t - prev
            prev = t
        while i < n and marks[i][0] == t:
            active[marks[i][2]] += marks[i][1]
            i += 1
    if wall > prev:
        other += wall - prev
    return phases, other, segments


def serve_note(nbytes: int, wait_ms: float) -> str:
    return f"{nbytes} wait={wait_ms:.1f}" if wait_ms >= 0.05 else str(nbytes)


def parse_serve_note(note: str) -> "tuple[int, float]":
    """An ``upload_serve`` event's note, "<bytes>" or "<bytes> wait=<ms>":
    the bytes sent and the ms the request waited before its send began."""
    head, _, wait = note.partition(" wait=")
    try:
        return int(head or 0), float(wait or 0.0)
    except ValueError:
        return 0, 0.0


def parse_sources_note(note: str) -> dict:
    """A ``task_sources`` event's note, "seed=<n> peer=<n> origin=<n>", as
    ``{"seed_bytes": n, "peer_bytes": n, "origin_bytes": n}``."""
    out = {"seed_bytes": 0, "peer_bytes": 0, "origin_bytes": 0}
    for part in note.split():
        key, _, value = part.partition("=")
        if key + "_bytes" in out and value.isdigit():
            out[key + "_bytes"] = int(value)
    return out


def _union_s(spans: list) -> float:
    total, end = 0.0, float("-inf")
    for s, e in sorted(spans):
        if e > end:
            total += e - max(s, end)
            end = e
    return total


def raw(tf: TaskFlight) -> dict:
    """The task's named events as they lie in the ring, uncapped: what
    lets a reader outside this process fold several daemons' rings on one
    clock (``start_wall`` is this host's anchored wall clock at the ring's
    zero). ``digest()`` is the bounded form that ships unasked."""
    return {
        "task_id": tf.task_id,
        "state": tf.state,
        "start_wall": tf.start_wall,
        "wall_s": round(tf.wall_s(), 6),
        "events_total": tf.events_total,
        "events_dropped": tf.events_dropped,
        "events": [[round(t, 6), EVENT_NAMES.get(code, str(code)), piece,
                    round(aux, 3), note]
                   for t, code, piece, aux, note in tf.events()],
    }


def analyze(tf: TaskFlight, *, stall_ttfb_s: float = STALL_TTFB_S,
            max_waterfall: int = 256, max_segments: int = 256) -> dict:
    """Fold a task's event ring into the phase breakdown + per-piece
    waterfall. Pure function of the ring — safe to call on a live task
    (the in-flight tail classifies as stall/sched_wait as appropriate)."""
    events = tf.events()
    wall = tf.wall_s()
    intervals: list = []          # (start_s, end_s, phase)
    open_req: dict = {}           # piece -> [t_req, t_first_byte, parent]
    open_marks: dict = {}         # paired-mark key -> t
    rows: dict = {}               # piece -> waterfall row
    sched_open: "float | None" = None

    def row_for(piece: int) -> dict:
        row = rows.get(piece)
        if row is None:
            row = rows[piece] = {
                "piece": piece, "attempts": 0, "parent": "",
                "t_request": -1.0, "t_first_byte": -1.0, "t_landed": -1.0,
                "status": "pending", "reason": "", "cost_ms": 0}
        return row

    for t, code, piece, aux, note in events:
        if code in (EV_REGISTER, EV_RESCHEDULE):
            if sched_open is None:
                sched_open = t
        elif code in (EV_SCHEDULED, EV_SCHED_ANSWER, EV_SCHED_PUSH):
            if sched_open is not None:
                intervals.append((sched_open, t, "sched_wait"))
                sched_open = None
        elif code == EV_REQUEST:
            open_req[piece] = [t, -1.0, note]
            row = row_for(piece)
            row["attempts"] += 1
            row["parent"] = note
            row["t_request"] = t
            row["t_first_byte"] = row["t_landed"] = -1.0
        elif code == EV_FIRST_BYTE:
            r = open_req.get(piece)
            if r is not None and r[1] < 0:
                r[1] = t
            if piece in rows:
                rows[piece]["t_first_byte"] = t
        elif code in (EV_LANDED, EV_FAILED):
            r = open_req.pop(piece, None)
            row = row_for(piece)
            if code == EV_LANDED:
                row["status"] = "ok"
                row["t_landed"] = t
                row["cost_ms"] = int(aux)
            else:
                row["status"] = "failed"
                row["reason"] = note
            if r is None:
                # Landed without a recorded request (native span interior,
                # local import): back out the interval from the cost.
                if code == EV_LANDED and aux > 0:
                    phase = "ici" if note == "intra" else "dcn"
                    intervals.append((max(0.0, t - aux / 1000.0), t, phase))
                continue
            t_req, t_fb = r[0], r[1]
            if code == EV_FAILED and note == "stall":
                intervals.append((t_req, t, "stall"))
                continue
            phase = "ici" if (code == EV_LANDED and note == "intra") \
                else "dcn"
            if t_fb >= 0 and (t_fb - t_req) > stall_ttfb_s:
                intervals.append((t_req, t_fb, "stall"))
                intervals.append((t_fb, t, phase))
            else:
                intervals.append((t_req, t, phase))
        elif code in (EV_DELTA_REUSE, EV_DELTA_FETCH):
            # Delta tasks: local base copies book as store (host-local
            # work), ranged-span pulls as dcn — so --explain separates
            # local-copy time from wire time while the partition stays
            # wall-time-exact (cost-backed intervals like source_landed).
            if aux > 0:
                phase = "store" if code == EV_DELTA_REUSE else "dcn"
                intervals.append((max(0.0, t - aux / 1000.0), t, phase))
        elif code == EV_SOURCE_LANDED:
            intervals.append((max(0.0, t - aux / 1000.0), t, "origin"))
            row = row_for(piece)
            row["status"] = "ok"
            row["parent"] = "origin"
            row["t_landed"] = t
            row["cost_ms"] = int(aux)
        elif code == EV_STORE_START:
            open_marks[("store", piece)] = t
        elif code == EV_STORED:
            t0 = open_marks.pop(("store", piece), None)
            if t0 is not None:
                intervals.append((t0, t, "store"))
        elif code == EV_SOURCE_FIRST_BYTE:
            # The request's wait for the origin, before any piece of it
            # could land.
            intervals.append((max(0.0, t - aux / 1000.0), t, "origin"))
        elif code == EV_CERT_WAIT:
            # The wait for a verify: the parent's, whose done certifies
            # this task's pieces (or, at its bound, nobody's).
            intervals.append((max(0.0, t - aux / 1000.0), t, "verify"))
        elif code == EV_VERIFY_START:
            open_marks["verify"] = t
        elif code == EV_VERIFIED:
            t0 = open_marks.pop("verify", None)
            if t0 is not None:
                intervals.append((t0, t, "verify"))
        elif code == EV_HBM_START:
            open_marks[("hbm", piece)] = t
        elif code == EV_HBM_LANDED:
            t0 = open_marks.pop(("hbm", piece), None)
            if t0 is not None:
                # Host -> HBM landing (the piece's wait for the landing
                # thread included); ``ici`` is chip-to-chip traffic only.
                intervals.append((t0, t, "hbm"))
        elif code in _ICI_STEPS:
            # One event at the span's end, aux = its ms. Like every
            # interval it counts where it falls inside the task's wall
            # time; the ``hbm`` block below holds its ms wherever it fell.
            intervals.append((max(0.0, t - aux / 1000.0), t, "ici"))

    # Tails: a request still open at the end of the timeline is the
    # black-box case — the piece never came back. Beyond the first-byte
    # threshold that is a stall, not transfer time.
    for piece, (t_req, t_fb, _parent) in open_req.items():
        if wall - t_req > stall_ttfb_s:
            intervals.append((t_req, wall, "stall"))
        else:
            intervals.append((t_req, wall, "dcn"))
    if sched_open is not None:
        intervals.append((sched_open, wall, "sched_wait"))

    phases, other, segments = _fold_phases(intervals, wall)
    dominant = ""
    if any(v > 0 for v in phases.values()):
        dominant = max(PHASES, key=lambda p: phases[p])

    ordered = [rows[k] for k in sorted(rows)]
    truncated = len(ordered) > max_waterfall
    counts: dict = {}
    runtime: dict = {}
    holders: dict = {}            # who -> summed seconds of its holds
    holds = 0
    hbm: dict = {}
    client: dict = {}
    parent: dict = {}
    sources: dict = {}
    serves: list = []             # (start_s, end_s) of each upload_serve
    served_bytes, serve_wait_ms = 0, 0.0
    for _t, code, piece, aux, note in events:
        name = EVENT_NAMES.get(code, str(code))
        counts[name] = counts.get(name, 0) + 1
        if code == EV_CERT_WAIT:
            parent["cert_wait_ms"] = round(
                parent.get("cert_wait_ms", 0.0) + aux, 3)
            parent["cert_wait"] = note
        elif code == EV_PARENT_VERIFIED:
            parent["verified_ms"] = round(aux, 3)
            parent["hash_behind"] = piece
        elif code == EV_PARENT_DONE:
            parent["pieces"] = piece
        elif code == EV_PARENT_SOURCE_FIRST_BYTE:
            # The earliest: the wait before the parent's first piece.
            parent.setdefault("source_first_byte_ms", round(aux, 3))
        elif code == EV_UPLOAD_SERVE:
            # Serving is no phase of this task's own download (a seed
            # serves while and after it pulls): a block of its own.
            serves.append((_t - aux / 1000.0, _t))
            nbytes, wait_ms = parse_serve_note(note)
            served_bytes += nbytes
            serve_wait_ms += wait_ms
        elif code == EV_TASK_SOURCES:
            sources = parse_sources_note(note)
            sources["parents"] = piece
        if code in _SINK_STEPS:
            # Where a landing's time went, by step, whether or not it fell
            # inside the task's wall time (finalize runs after the
            # terminal event).
            hbm[code] = hbm.get(code, 0.0) + aux
        elif code in _CLIENT_STEPS:
            # The admission wait ends where the task's wall time begins,
            # and a sharded pull's plan and views span its other tasks.
            client[code] = client.get(code, 0.0) + aux
        elif code in _RUNTIME_EVENTS:
            if code == EV_LOOP_LAG:
                who = note.partition("who=")[2]
                if who:
                    holds += 1
                    holders[who] = holders.get(who, 0.0) + aux
                if aux < WEDGED_S:
                    continue      # a hold names its holder; a wedge counts
            r = runtime.get(name)
            if r is None:
                r = runtime[name] = {"count": 0, "max_s": 0.0, "total_s": 0.0}
            r["count"] += 1
            r["total_s"] += aux
            if aux > r["max_s"]:
                r["max_s"] = aux
    for r in runtime.values():
        r["max_s"] = round(r["max_s"], 4)
        r["total_s"] = round(r["total_s"], 4)
    if holders:
        # Turns of 20 ms and more inside the task's wall, and what ran in
        # them by summed seconds, the longest first.
        runtime["holds"] = holds
        runtime["holders"] = {
            who: round(s, 4) for who, s in
            sorted(holders.items(), key=lambda kv: -kv[1])}
    return {
        "task_id": tf.task_id,
        "state": tf.state,
        "note": tf.note,
        "started_at": tf.start_wall,
        "wall_s": round(wall, 6),
        "phases": {ph: round(v, 6) for ph, v in phases.items()},
        "other_s": round(other, 6),
        "dominant_phase": dominant,
        "segments": [[round(s, 6), round(e, 6), ph]
                     for s, e, ph in segments[:max_segments]],
        "segments_truncated": len(segments) > max_segments,
        "events": tf.events_total,
        "events_dropped": tf.events_dropped,
        "event_counts": counts,
        "runtime": runtime,
        # "sink_read" -> "read_ms", in the order of _SINK_STEPS.
        "hbm": {EVENT_NAMES[code][5:] + "_ms": round(hbm[code], 3)
                for code in _SINK_STEPS if code in hbm},
        "client": {EVENT_NAMES[code] + "_ms": round(client[code], 3)
                   for code in _CLIENT_STEPS if code in client},
        # A P2P child's completion: its wait for a certifying parent, and
        # that parent's own spans as its sync stream carried them.
        "parent": parent,
        # Where the bytes came from (a child), and what this daemon sent
        # to others of the task (busy_ms: the union of the sends).
        "sources": sources,
        "upload": {"serves": len(serves), "bytes": served_bytes,
                   "busy_ms": round(_union_s(serves) * 1000.0, 3),
                   "wait_ms": round(serve_wait_ms, 3)} if serves else {},
        "pieces": ordered[:max_waterfall],
        "pieces_truncated": truncated,
    }


def runtime_advisory(report: dict) -> str:
    """One-line loop-lag/GC advisory from an ``analyze()`` report's
    runtime-interference events, or "" when the runtime stayed quiet.
    Rendered under the --explain waterfall so a stall phase caused by a
    wedged loop or a GC storm names its culprit."""
    rt = report.get("runtime") or {}
    parts = []
    for who, seconds in (rt.get("holders") or {}).items():
        parts.append(f"event loop held {rt['holds']}x, {seconds:.2f} s by "
                     f"{who}")
        break
    ll = rt.get("loop_lag")
    if ll:
        parts.append(f"event loop wedged {ll['count']}x "
                     f"(max {ll['max_s']:.2f}s, {ll['total_s']:.2f}s total)")
    gp = rt.get("gc_pause")
    if gp:
        parts.append(f"gc paused {gp['count']}x "
                     f"(max {gp['max_s']:.2f}s, {gp['total_s']:.2f}s total)")
    if not parts:
        return ""
    return ("runtime interference: " + ", ".join(parts) +
            " during this task — see /debug/prof")


def render_waterfall(report: dict) -> str:
    """Text rendering of an ``analyze()`` report: phase bars + per-piece
    waterfall. The SAME renderer backs ``/debug/flight/<id>?format=text``
    and ``dfget --explain`` so the two can never diverge."""
    wall = report["wall_s"] or 1e-9
    width = 30
    lines = [
        f"task {report['task_id'][:40]} state={report['state']} "
        f"wall={report['wall_s']:.3f}s "
        f"dominant={report['dominant_phase'] or '-'}",
        "phase breakdown:",
    ]
    entries = [(ph, report["phases"].get(ph, 0.0)) for ph in PHASES]
    entries.append(("other", report.get("other_s", 0.0)))
    for ph, v in entries:
        bar = "#" * int(round(width * v / wall))
        lines.append(f"  {ph:<10} {v:8.3f}s {100 * v / wall:5.1f}% {bar}")
    hbm = report.get("hbm")
    if hbm:
        lines.append("hbm landing, ms on the landing thread (tail: the end "
                     "of finalize off it; wait: queued for it): " + " ".join(
                         f"{k[:-3]}={v:.1f}" for k, v in hbm.items()))
    client = report.get("client")
    if client:
        lines.append("client api, ms (admit_wait: queued for a sink slot "
                     "before the task; shard_*: the sharded pull this task "
                     "heads; swap_*: the hot-swap this delta task feeds; "
                     "save_*: the save_from_device this task stores): "
                     + " ".join(
                         f"{k[:-3]}={v:.1f}" for k, v in client.items()))
    parent = report.get("parent") or {}
    parts = []
    if "cert_wait_ms" in parent:
        parts.append(f"cert_wait={parent['cert_wait_ms']:.1f} ms "
                     f"({parent['cert_wait']})")
    if "verified_ms" in parent:
        of = f" of {parent['pieces']}" if "pieces" in parent else ""
        parts.append(f"seed verify {parent['verified_ms']:.1f} ms, "
                     f"{parent['hash_behind']}{of} pieces behind")
    if "source_first_byte_ms" in parent:
        parts.append("origin first byte "
                     f"{parent['source_first_byte_ms']:.1f} ms")
    if parts:
        lines.append("completion, the parent's spans on its own clock: "
                     + "; ".join(parts))
    src = report.get("sources") or {}
    if src:
        lines.append(
            f"sources, bytes: seed={src['seed_bytes']} "
            f"peers={src['peer_bytes']} origin={src['origin_bytes']} from "
            f"{src['parents']} parent(s)")
    up = report.get("upload") or {}
    if up:
        lines.append(
            f"upload: served {up['serves']} piece(s), {up['bytes']} bytes, "
            f"to others of this task; sending {up['busy_ms']:.1f} ms "
            f"(union), waited {up['wait_ms']:.1f} ms before sends")
    advisory = runtime_advisory(report)
    if advisory:
        lines.append(advisory)
    pieces = report.get("pieces") or []
    suffix = " (truncated)" if report.get("pieces_truncated") else ""
    lines.append(f"pieces: {len(pieces)}{suffix}")
    for row in pieces:
        start = row["t_request"] if row["t_request"] >= 0 else row["t_landed"]
        end = row["t_landed"] if row["t_landed"] >= 0 else start
        if start < 0:
            continue
        lead = int(width * min(start, wall) / wall)
        span = max(1, int(width * max(0.0, end - start) / wall))
        bar = ("." * lead + "#" * span)[:width]
        extra = f" reason={row['reason']}" if row["reason"] else ""
        lines.append(
            f"  p{row['piece']:<5} {bar:<{width}} +{start:7.3f}s "
            f"{max(0.0, end - start) * 1000:7.1f}ms "
            f"x{row['attempts']} {row['status']}{extra} {row['parent']}")
    return "\n".join(lines)


# --------------------------------------------------------------------- #
# Flight digest: the compact, bounded form that ships off-host
# --------------------------------------------------------------------- #

# Hard byte budget for one shipped digest (serialized JSON). The daemon
# attaches one per task to its terminal announce message, so the bound is
# per TASK, not per piece (tests/test_podlens.py holds a digest to it).
DIGEST_MAX_BYTES = 16384

# Compact piece row order inside a digest (arrays, not dicts — at 64
# pieces the keys would dominate the byte budget):
# [piece, attempts, t_request, t_first_byte, t_landed, ok, reason, parent]
DIGEST_PIECE_FIELDS = ("piece", "attempts", "t_request", "t_first_byte",
                       "t_landed", "ok", "reason", "parent")


def _digest_encoded_len(d: dict) -> int:
    return len(json.dumps(d, separators=(",", ":")))


def digest(tf: TaskFlight, *, max_bytes: int = DIGEST_MAX_BYTES,
           max_pieces: int = 64, max_events: int = 96,
           max_segments: int = 64,
           clock_samples: "list | None" = None) -> dict:
    """Fold a task's ring into the compact digest the daemon ships to the
    scheduler on task completion/failure: phase totals + merged phase
    segments + a truncated piece waterfall + the newest named events,
    hard-capped at ``max_bytes`` of serialized JSON (pieces, events and
    segments are halved until the cap holds). ``clock_samples`` carries
    the announce-stream round-trip samples ([t0, t1, sched_echo] triples
    on this host's anchored wall clock) the scheduler's clock aligner
    consumes."""
    report = analyze(tf, max_waterfall=max_pieces,
                     max_segments=max_segments)
    pieces = [[r["piece"], r["attempts"], round(r["t_request"], 4),
               round(r["t_first_byte"], 4), round(r["t_landed"], 4),
               1 if r["status"] == "ok" else 0, r["reason"],
               r["parent"]] for r in report["pieces"]]
    events = [[round(t, 4), EVENT_NAMES.get(code, str(code)), piece,
               note] for t, code, piece, _aux, note
              in tf.events()[-max_events:]]
    d = {
        "v": 1,
        "task_id": tf.task_id,
        "state": tf.state,
        "note": tf.note[:200],
        "start_wall": round(tf.start_wall, 6),
        "wall_s": report["wall_s"],
        "phases": report["phases"],
        "other_s": report["other_s"],
        "dominant_phase": report["dominant_phase"],
        "segments": report["segments"],
        "pieces": pieces,
        "pieces_total": len(report["pieces"]),
        "pieces_truncated": report["pieces_truncated"],
        "events": events,
        "events_total": tf.events_total,
        "events_dropped": tf.events_dropped,
    }
    if clock_samples:
        d["clock"] = [[round(t0, 6), round(t1, 6), round(echo, 6)]
                      for t0, t1, echo in clock_samples[-4:]]
    # Byte cap: drop detail (events first — the segments/pieces carry the
    # analytic payload), never the phase totals.
    size = _digest_encoded_len(d)
    while size > max_bytes and (d["events"] or len(d["pieces"]) > 8
                                or len(d["segments"]) > 16):
        if d["events"]:
            d["events"] = d["events"][len(d["events"]) // 2:] \
                if len(d["events"]) > 8 else []
        elif len(d["pieces"]) > 8:
            d["pieces"] = d["pieces"][:len(d["pieces"]) // 2]
            d["pieces_truncated"] = True
        else:
            d["segments"] = d["segments"][:len(d["segments"]) // 2]
        size = _digest_encoded_len(d)
    d["bytes"] = size
    return d


def digest_piece_rows(d: dict) -> list:
    """Expand a digest's compact piece arrays back into dict rows."""
    return [dict(zip(DIGEST_PIECE_FIELDS, row))
            for row in d.get("pieces") or []]


# --------------------------------------------------------------------- #
# Recorder: the bounded per-process task index
# --------------------------------------------------------------------- #

class FlightRecorder:
    """Bounded index of TaskFlights. Eviction prefers finished tasks;
    the caps make "always-on" safe (memory is O(max_tasks * capacity)
    tuples regardless of how many tasks a daemon serves)."""

    def __init__(self, *, capacity: int = 2048, max_tasks: int = 128,
                 dump_dir: str = "", keep_bundles: int = 32,
                 wall_offset: float = 0.0):
        self.capacity = capacity
        self.max_tasks = max_tasks
        self.dump_dir = dump_dir
        self.keep_bundles = keep_bundles
        # Chaos/test knob: skew every wall stamp this recorder's flights
        # report (start_wall, clock samples) by a known amount — what the
        # pod-lens alignment e2e injects and must then recover.
        self.wall_offset = wall_offset
        # Latest fleet-scorecard row the scheduler returned for THIS host
        # (announcer stashes it each announce); embedded into post-mortem
        # bundles so a failure autopsy carries the subject host's
        # fleet-wide standing at failure time.
        self.scorecard_snapshot: dict = {}
        # Runtime observatory (pkg/prof), when this role armed one: its
        # pruned snapshot rides along in post-mortem bundles so a failed
        # task's autopsy shows what the PROCESS was doing, not just what
        # the task saw.
        self.runtime = None
        # Callables that bring in events measured outside Python (the
        # native upload server's serve log): sync() runs them before a
        # ring is read for a report.
        self.feeders: list = []
        self._tasks: "OrderedDict[str, TaskFlight]" = OrderedDict()
        # Rings that are no task's (a loop's account, ``runtime:loop:*``):
        # outside the index, so no eviction reaches them.
        self._rings: "dict[str, TaskFlight]" = {}
        self._lock = threading.Lock()

    def sync(self) -> None:
        for bring in list(self.feeders):
            bring()

    def task(self, task_id: str, capacity: int = 0) -> TaskFlight:
        """Get-or-create ``task_id``'s flight; ``capacity`` sizes a new
        one's ring where the recorder's own would be too short (a dataset
        feed's ring holds a batch of samples, not a task's pieces)."""
        tf = self._tasks.get(task_id)
        if tf is not None:
            return tf
        with self._lock:
            tf = self._tasks.get(task_id)
            if tf is None:
                while len(self._tasks) >= self.max_tasks:
                    self._evict_one()
                tf = self._tasks[task_id] = TaskFlight(
                    task_id, capacity or self.capacity,
                    wall_offset=self.wall_offset)
        return tf

    def ring(self, name: str, capacity: int = 0) -> TaskFlight:
        """Get-or-create ``name``'s ring OUTSIDE the index of tasks: it
        lives as long as the recorder, is read like any flight (``get``,
        ``/debug/flight/<name>``) and is never stamped by
        ``stamp_running``."""
        tf = self._rings.get(name)
        if tf is None:
            with self._lock:
                tf = self._rings.get(name)
                if tf is None:
                    tf = self._rings[name] = TaskFlight(
                        name, capacity or self.capacity,
                        wall_offset=self.wall_offset)
        return tf

    def _evict_one(self) -> None:
        for tid, tf in self._tasks.items():
            if tf.state != "running":
                del self._tasks[tid]
                return
        self._tasks.popitem(last=False)

    def get(self, task_id: str) -> "TaskFlight | None":
        return self._tasks.get(task_id) or self._rings.get(task_id)

    def stamp_running(self, code: int, aux: float = 0.0, note: str = "",
                      piece: int = -1, end_pc: "float | None" = None) -> None:
        """Record one event into EVERY running flight — how pkg/prof
        stamps runtime interference (a hold of the loop, a slow GC pause)
        into the task windows it overlapped, as an event that ended at
        ``end_pc`` (now). Bounded by max_tasks."""
        if end_pc is None:
            end_pc = time.perf_counter()
        for tf in list(self._tasks.values()):
            if tf.state == "running":
                tf.record_at(end_pc, code, piece, aux, note)

    def summary(self) -> list:
        return [{"task_id": tf.task_id, "state": tf.state,
                 "wall_s": round(tf.wall_s(), 3),
                 "events": tf.events_total,
                 "events_dropped": tf.events_dropped}
                for tf in (*self._tasks.values(), *self._rings.values())]

    def finish_task(self, task_id: str, state: str,
                    note: str = "") -> "TaskFlight | None":
        """Terminal transition: stamps the wall clock, feeds the phase
        histograms, and (on failure, with a dump dir configured) writes
        the post-mortem bundle. Idempotent per task."""
        tf = self._tasks.get(task_id)
        if tf is None or tf.state != "running":
            return tf
        tf.finish(state, note)
        report = analyze(tf)
        for ph in PHASES:
            v = report["phases"][ph]
            if v > 0:
                PHASE_SECONDS.labels(ph).observe(v)
        if state == "failed" and self.dump_dir:
            self._dump(tf, report)
        return tf

    def _dump(self, tf: TaskFlight, report: dict) -> None:
        """Post-mortem bundle: the autopsy + the raw (named) event
        timeline + this host's latest fleet-scorecard row, gzipped
        (bundles are JSON text — gzip is ~10x on event timelines), pruned
        to ``keep_bundles`` files. Best-effort — a full disk must never
        fail the task path that triggered the dump."""
        try:
            os.makedirs(self.dump_dir, exist_ok=True)
            path = os.path.join(
                self.dump_dir,
                f"flight-{tf.task_id[:16]}-"
                f"{int(time.time() * 1000)}.json.gz")
            bundle = {
                "report": report,
                "events": [
                    {"t": round(t, 6),
                     "event": EVENT_NAMES.get(code, str(code)),
                     "piece": piece, "aux": aux, "note": note}
                    for t, code, piece, aux, note in tf.events()],
            }
            if self.scorecard_snapshot:
                bundle["scorecard"] = dict(self.scorecard_snapshot)
            if self.runtime is not None:
                # Pruned prof snapshot + loop-lag/GC summary: best-effort
                # like the rest of the dump path.
                try:
                    bundle["runtime"] = self.runtime.postmortem()
                except Exception:
                    log.warning("runtime snapshot for bundle failed",
                                exc_info=True)
            with gzip.open(path, "wt") as f:
                json.dump(bundle, f)
            log.info("flight post-mortem dumped", task=tf.task_id[:16],
                     path=path)
            self._prune()
        except OSError:
            pass

    def _prune(self) -> None:
        """Newest-``keep_bundles`` rotation: a crash-looping task dumping
        a bundle per attempt must not grow the log volume forever. mtime
        orders; the filename's ms stamp breaks same-second ties. Counts
        ``.json`` (pre-gzip era) and ``.json.gz`` bundles alike — one
        budget, not one per extension."""

        def stamp(path: str) -> int:
            tail = path.rsplit("-", 1)[-1]
            for suffix in (".json.gz", ".json"):
                if tail.endswith(suffix):
                    tail = tail[:-len(suffix)]
                    break
            try:
                return int(tail)
            except ValueError:
                return 0

        try:
            bundles = sorted(
                (os.path.join(self.dump_dir, name)
                 for name in os.listdir(self.dump_dir)
                 if name.startswith("flight-")
                 and name.endswith((".json", ".json.gz"))),
                key=lambda p: (os.path.getmtime(p), stamp(p)))
            drop = bundles[:-self.keep_bundles] if self.keep_bundles > 0 \
                else bundles
            for path in drop:
                os.unlink(path)
        except OSError:
            pass


_RECORDER = FlightRecorder()


def recorder() -> FlightRecorder:
    return _RECORDER


def for_task(task_id: str) -> TaskFlight:
    """Get-or-create the default recorder's flight for ``task_id`` — the
    one call every instrumented choke point makes."""
    return _RECORDER.task(task_id)


def get(task_id: str) -> "TaskFlight | None":
    return _RECORDER.get(task_id)


# --------------------------------------------------------------------- #
# Pod-level aggregation (scheduler side)
# --------------------------------------------------------------------- #

class PodAggregator:
    """Per-task, per-host phase attribution from the piece reports'
    ``timings`` (proto/wire PIECE), plus typed failure / quarantine
    correlation — the ``/debug/pod/<task_id>`` straggler view. Bounded
    like the recorder: the oldest task aggregate is evicted past
    ``max_tasks``."""

    _PHASE_KEYS = ("dcn", "stall", "store")

    def __init__(self, max_tasks: int = 256):
        self.max_tasks = max_tasks
        self._tasks: "OrderedDict[str, dict]" = OrderedDict()

    def _task(self, task_id: str) -> dict:
        entry = self._tasks.get(task_id)
        if entry is None:
            while len(self._tasks) >= self.max_tasks:
                self._tasks.popitem(last=False)
            entry = self._tasks[task_id] = {"hosts": {}, "quarantine": [],
                                            "fanout": {}}
        return entry

    def note_fanout(self, task_id: str, what: str, host_id: str = "") -> None:
        """The task as the scheduler saw it fan out: ``register`` /
        ``finished`` / ``failed`` of a peer on ``host_id``, a ``handout`` of
        parents, a ``reschedule`` asked for, a peer sent ``back_source``.
        Counts, and the anchored wall time of the first register and the
        last finish: a slow fan-out (many handouts, a long first-to-last)
        can be told from one slow host."""
        f = self._task(task_id)["fanout"]
        f[what] = f.get(what, 0) + 1
        now = anchored_wall()
        if what == "register":
            f.setdefault("first_register_at", now)
            f.setdefault("hosts", set()).add(host_id)
        elif what == "finished":
            f["last_finished_at"] = now

    def _host(self, task_id: str, host_id: str) -> dict:
        hosts = self._task(task_id)["hosts"]
        h = hosts.get(host_id)
        if h is None:
            h = hosts[host_id] = {
                "pieces": 0,
                "ms": {k: 0 for k in self._PHASE_KEYS},
                "failures": {},
            }
        return h

    def note_piece(self, task_id: str, host_id: str,
                   timings: "dict | None", cost_ms: int = 0) -> None:
        h = self._host(task_id, host_id)
        h["pieces"] += 1
        ms = h["ms"]
        if timings:
            ms["dcn"] += int(timings.get("dcn_ms", 0) or 0)
            ms["stall"] += int(timings.get("stall_ms", 0) or 0)
            ms["store"] += int(timings.get("store_ms", 0) or 0)
        else:
            # Legacy report (no per-phase split): the whole cost is
            # transfer time.
            ms["dcn"] += int(cost_ms or 0)

    def note_pieces(self, task_id: str, host_id: str, n: int,
                    phase_ms) -> None:
        """Batch form of note_piece for the packed ingest fast path:
        ``n`` pieces with pre-summed (dcn, stall, store) milliseconds —
        untimed pieces already folded their whole cost into dcn
        (proto/reportcodec computes the sums with note_piece's exact
        semantics, so N note_piece calls and one note_pieces call land
        the same aggregate)."""
        h = self._host(task_id, host_id)
        h["pieces"] += n
        ms = h["ms"]
        ms["dcn"] += phase_ms[0]
        ms["stall"] += phase_ms[1]
        ms["store"] += phase_ms[2]

    def note_failure(self, task_id: str, host_id: str, reason: str) -> None:
        h = self._host(task_id, host_id)
        h["failures"][reason] = h["failures"].get(reason, 0) + 1

    def note_quarantine(self, task_id: str, host_id: str,
                        reason: str) -> None:
        q = self._task(task_id)["quarantine"]
        q.append({"host": host_id, "reason": reason})
        del q[:-64]   # bounded

    def report(self, task_id: str) -> "dict | None":
        entry = self._tasks.get(task_id)
        if entry is None:
            return None
        hosts = []
        totals = {k: 0 for k in self._PHASE_KEYS}
        for host_id, h in entry["hosts"].items():
            total_ms = sum(h["ms"].values())
            for k in self._PHASE_KEYS:
                totals[k] += h["ms"][k]
            dominant = max(self._PHASE_KEYS, key=lambda k: h["ms"][k]) \
                if total_ms else ""
            hosts.append({
                "host": host_id,
                "pieces": h["pieces"],
                "ms": dict(h["ms"]),
                "mean_piece_ms": round(total_ms / h["pieces"], 2)
                if h["pieces"] else 0.0,
                "dominant_phase": dominant,
                "failures": dict(h["failures"]),
            })
        hosts.sort(key=lambda h: -h["mean_piece_ms"])
        slowest = hosts[0]["host"] if hosts and hosts[0]["mean_piece_ms"] > 0 \
            else ""
        dominant = max(self._PHASE_KEYS, key=lambda k: totals[k]) \
            if any(totals.values()) else ""
        return {
            "task_id": task_id,
            "hosts": hosts,
            "slowest_host": slowest,
            "dominant_phase": dominant,
            "quarantine": list(entry["quarantine"]),
            "fanout": self._fanout_report(entry["fanout"]),
        }

    @staticmethod
    def _fanout_report(f: dict) -> dict:
        if not f:
            return {}
        out = {k: f.get(k, 0) for k in ("register", "handout", "reschedule",
                                        "back_source", "finished", "failed")}
        out["hosts"] = len(f.get("hosts", ()))
        first, last = f.get("first_register_at"), f.get("last_finished_at")
        out["first_register_at"] = first
        out["register_to_last_finished_s"] = round(last - first, 6) \
            if first is not None and last is not None else None
        return out
