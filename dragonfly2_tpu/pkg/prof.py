"""Runtime observatory: continuous in-process profiling for every role.

The flight recorder (pkg/flight), fleet observatory (pkg/fleet) and pod
lens (pkg/podlens) are all event/task-centric; nothing watches the
RUNTIME itself — yet the scheduler's one real CPU regression so far
(cyclic GC rescanning live digest dicts) was only caught by accident in
a bench. This module is the missing process-level layer, the Python
analog of the reference's per-binary pprof endpoints
(cmd/dependency/dependency.go --pprof-port): always on and bounded.

Three instruments, one ``RuntimeObservatory``:

  * ``StackSampler`` — a named daemon thread (``df-prof-sampler``) walks
    ``sys._current_frames()`` at a configurable hz and folds each
    thread's stack into a bounded call-tree trie keyed by code object.
    The flight-ring discipline applies: the walk buffer is preallocated,
    trie nodes are interned (a sample through an existing path allocates
    nothing), and the node budget is a hard cap with an eviction/
    truncation counter — a pathological stack explosion degrades to a
    counter, never to unbounded memory. Attribution is per THREAD NAME,
    which is why every long-lived thread in this tree carries a ``df-``
    prefix (tier-1 guard in tests/test_prof.py): dispatcher, upload,
    io-ring, chunker and sampler work separate cleanly in one glance.
  * ``LoopLagProbe`` — the loop's thread keeps an account, from inside
    its own iteration: ``arm()`` wraps the running loop's
    ``selector.select``, and an iteration is ``select`` exit → the next
    ``select`` entry, the ready handles of one turn. Two clock readings
    a turn, and one of the thread's CPU time where a slice is stamped,
    give, exactly and cumulatively, what ran the thread (``busy``), what
    it cost on a core (``cpu``: busy − cpu is a turn held off a core, by a
    blocking call, the GIL, a page fault), when it was due and did not run
    (``late``)
    and what the cyclic GC took of it (``gc``). Every 5 ms of busy time
    is one ``loop_acct`` slice, and every turn or late wake of 20 ms and
    more one ``loop_lag`` hold that names its holder (``who``: the frame
    the sampler met on that thread inside the hold), on a ring of the
    probe's own (``runtime:loop:<name>``); holds are also stamped into
    every RUNNING task flight, so ``dfget --explain`` can say *who held
    the loop*, not just *nothing happened*. The holds also back the
    ``loop_lag`` SLO (pkg/slo kind="probe"): wedged wall-seconds over
    observed wall-seconds.
  * ``GCObservatory`` — ``gc.callbacks`` pause histograms per
    generation + collection counters; pauses above ``gc_slow_s`` stamp
    EV_GC_PAUSE the same way. ``/proc/self`` gauges (RSS, open fds,
    threads, ctx switches) refresh on snapshot, not continuously.

Served by pkg/metrics_server on daemon AND scheduler:
  GET /debug/prof                   JSON top-N self-time per thread
  GET /debug/prof/flame?format=folded   flamegraph-ready folded stacks
  GET /debug/prof/runtime           loop lag + GC + /proc gauges

The observatory is a process singleton (``install()``/``release()``
refcounted): a test process embedding a daemon and a scheduler must not
run two sampler threads or double-book GC pauses.
"""

from __future__ import annotations

import asyncio
import gc
import os
import sys
import threading
import time
from collections import deque
from dataclasses import dataclass

from dragonfly2_tpu.pkg import dflog, flight as flightlib, metrics

log = dflog.get("prof")

SAMPLES_TOTAL = metrics.counter(
    "runtime_profiler_samples_total",
    "Sampling passes the stack profiler completed (one pass folds every "
    "live thread's stack into the bounded trie)")

TRUNCATED_TOTAL = metrics.counter(
    "runtime_profiler_truncated_total",
    "Stack folds cut short by the trie node cap — the bounded-memory "
    "degradation counter (raise max_nodes if this moves)")

LAG_SECONDS = metrics.histogram(
    "runtime_loop_lag_seconds",
    "How long a due handle of the asyncio loop waited: each turn of 1 ms "
    "and more (what every other handle waited behind) and each late wake "
    "(a due timer, the loop still in select); behind the loop_lag SLO",
    buckets=(0.001, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5,
             5.0))

SLOW_TICKS_TOTAL = metrics.counter(
    "runtime_loop_slow_ticks_total",
    "Turns and late wakes of the loop of lag_slow_s and more: the wedges "
    "that the loop_lag SLO and a task's autopsy count")

LOOP_BUSY_SECONDS = metrics.counter(
    "runtime_loop_busy_seconds_total",
    "Wall seconds the loop's thread spent running ready handles (select "
    "exit to the next select entry), by loop; updated a slice at a time",
    ("loop",))

LOOP_CPU_SECONDS = metrics.counter(
    "runtime_loop_cpu_seconds_total",
    "The loop thread's own CPU seconds inside those turns: busy less cpu "
    "is a turn held off a core (a blocking call, the GIL, a page fault)",
    ("loop",))

LOOP_LATE_SECONDS = metrics.counter(
    "runtime_loop_late_seconds_total",
    "Seconds a due timer waited while the loop still sat in select: the "
    "loop due and not running (no GIL, no core), by loop",
    ("loop",))

GC_PAUSE_SECONDS = metrics.histogram(
    "runtime_gc_pause_seconds",
    "Cyclic-GC pause per collection, by generation (gc.callbacks "
    "start/stop delta)",
    ("generation",),
    buckets=(0.0005, 0.001, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
             1.0))

GC_COLLECTIONS_TOTAL = metrics.counter(
    "runtime_gc_collections_total",
    "Cyclic-GC collections observed by generation",
    ("generation",))

RSS_BYTES = metrics.gauge(
    "runtime_rss_bytes",
    "Resident set size from /proc/self/statm (refreshed on scrape)")

OPEN_FDS = metrics.gauge(
    "runtime_open_fds",
    "Open file descriptors from /proc/self/fd (refreshed on scrape)")

THREADS_GAUGE = metrics.gauge(
    "runtime_threads",
    "Live threads in this process (refreshed on scrape)")

CTX_SWITCHES = metrics.gauge(
    "runtime_ctx_switches",
    "Context switches from /proc/self/status by kind "
    "(voluntary/involuntary; cumulative counters mirrored as gauges)",
    ("kind",))


@dataclass
class ProfConfig:
    """Runtime-observatory knobs, shared by daemon and scheduler config
    (``prof:`` block). Always on by default and bounded by the caps
    below; ``enabled=False`` removes every hook."""

    enabled: bool = True
    hz: float = 19.0              # sampler passes per second
    max_nodes: int = 8192         # trie node hard cap (then truncation)
    max_depth: int = 48           # frames folded per stack
    lag_slow_s: float = flightlib.WEDGED_S    # a hold this long is a wedge
    gc_slow_s: float = 0.05       # GC pause threshold -> flight events
    lag_ring: int = 4096          # holds retained for the SLO probe


# Internal fixed bucket edges for the JSON-served lag/GC histograms
# (preallocated count arrays; the Prometheus families use their own).
_LAG_EDGES = (0.001, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5,
              5.0)
# The account's three steps, in ns. A turn or a late wake from _NOTE_NS up
# is a wait worth booking (note_lag); 5 ms of busy time are one loop_acct
# slice, so a saturated loop stamps 200 a second and an idle one none; a
# turn or a late wake of _HOLD_NS and more is a hold, one loop_lag event
# that names its holder: under the feed's 23 ms landing, under a tenth of
# the shortest operation the benchmark times, at most 50 a second.
_NOTE_NS = 1_000_000
_SLICE_NS = 5_000_000
_HOLD_NS = 20_000_000
# The probe's own ring: five minutes of a saturated loop's slices.
_ACCT_RING = 65536
_PKG_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__))) \
    + os.sep


def proc_stats() -> dict:
    """Best-effort /proc/self gauges; zeros off-Linux. Cheap enough to
    call per scrape (two small reads + one dirlist)."""
    out = {"rss_bytes": 0, "open_fds": 0, "threads": threading.active_count(),
           "voluntary_ctx_switches": 0, "involuntary_ctx_switches": 0}
    try:
        with open("/proc/self/statm") as f:
            out["rss_bytes"] = int(f.read().split()[1]) * os.sysconf(
                "SC_PAGE_SIZE")
    except (OSError, IndexError, ValueError):
        pass
    try:
        out["open_fds"] = len(os.listdir("/proc/self/fd"))
    except OSError:
        pass
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("voluntary_ctxt_switches:"):
                    out["voluntary_ctx_switches"] = int(line.split()[1])
                elif line.startswith("nonvoluntary_ctxt_switches:"):
                    out["involuntary_ctx_switches"] = int(line.split()[1])
    except (OSError, IndexError, ValueError):
        pass
    return out


# --------------------------------------------------------------------- #
# (a) Sampling stack profiler
# --------------------------------------------------------------------- #

class StackSampler:
    """Folded-stack trie fed by a sampling daemon thread.

    Trie nodes are ``[self_count, {code: child}]`` keyed by code object —
    interning by identity means a steady-state sample allocates nothing
    in OUR structures (``sys._current_frames`` itself builds one dict per
    pass; that is the floor). Node creation stops at ``max_nodes``; the
    overflow shows up in ``truncated`` instead of memory."""

    def __init__(self, hz: float = 19.0, max_nodes: int = 8192,
                 max_depth: int = 48):
        self.hz = max(0.5, float(hz))
        self.max_nodes = max_nodes
        self.max_depth = max_depth
        self.samples = 0
        self.truncated = 0
        self._roots: "dict[str, list]" = {}     # thread name -> node
        self._nodes = 0
        self._labels: dict = {}                 # code -> "file:func"
        self._stackbuf: list = [None] * max_depth
        self._names: "dict[int, str]" = {}      # ident -> thread name
        self._names_refreshed = 0.0
        # Armed loop probes by their thread: a pass that meets one of them
        # inside a hold keeps the thread's frame for it (``who``).
        self.loops: "dict[int, LoopLagProbe]" = {}
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._thread: "threading.Thread | None" = None

    # -- lifecycle ---------------------------------------------------------

    def start(self) -> None:
        if self._thread is not None:
            return
        self._stop.clear()
        self._thread = threading.Thread(
            target=self._run, daemon=True, name="df-prof-sampler")
        self._thread.start()

    def stop(self) -> None:
        t = self._thread
        if t is None:
            return
        self._stop.set()
        t.join(timeout=2.0)
        self._thread = None

    def _run(self) -> None:
        interval = 1.0 / self.hz
        while not self._stop.wait(interval):
            with self._lock:
                self._sample_once()
            SAMPLES_TOTAL.inc()

    # -- the sampling pass -------------------------------------------------

    def _thread_name(self, ident: int, now: float) -> str:
        name = self._names.get(ident)
        if name is None or now - self._names_refreshed > 1.0:
            self._names = {t.ident: t.name for t in threading.enumerate()}
            self._names_refreshed = now
            name = self._names.get(ident)
        return name or f"tid-{ident}"

    def _sample_once(self) -> None:
        me = threading.get_ident()
        now = time.monotonic()
        buf = self._stackbuf
        for ident, frame in sys._current_frames().items():
            if ident == me:
                continue
            probe = self.loops.get(ident)
            if probe is not None:
                probe.note_frame(frame)
            n = 0
            while frame is not None and n < self.max_depth:
                buf[n] = frame.f_code
                n += 1
                frame = frame.f_back
            name = self._thread_name(ident, now)
            node = self._roots.get(name)
            if node is None:
                node = self._roots[name] = [0, {}]
            truncated = False
            for i in range(n - 1, -1, -1):      # outermost first
                children = node[1]
                child = children.get(buf[i])
                if child is None:
                    if self._nodes >= self.max_nodes:
                        truncated = True
                        break
                    child = children[buf[i]] = [0, {}]
                    self._nodes += 1
                node = child
            node[0] += 1
            if truncated:
                self.truncated += 1
                TRUNCATED_TOTAL.inc()
        self.samples += 1

    @property
    def nodes(self) -> int:
        return self._nodes

    # -- rendering ---------------------------------------------------------

    def _label(self, code) -> str:
        label = self._labels.get(code)
        if label is None:
            label = self._labels[code] = (
                f"{os.path.basename(code.co_filename)}:{code.co_name}")
        return label

    def folded(self, max_lines: int = 4096) -> str:
        """Flamegraph-ready folded stacks: ``thread;frame;frame count``
        per line, leaf self-counts only (standard collapse format)."""
        lines: list = []
        with self._lock:
            for tname, root in sorted(self._roots.items()):
                stack = [tname]
                self._fold(root, stack, lines, max_lines)
        return "\n".join(lines) + ("\n" if lines else "")

    def _fold(self, node: list, stack: list, out: list,
              max_lines: int) -> None:
        if len(out) >= max_lines:
            return
        if node[0] > 0:
            out.append(f"{';'.join(stack)} {node[0]}")
        for code, child in node[1].items():
            stack.append(self._label(code))
            self._fold(child, stack, out, max_lines)
            stack.pop()

    def report(self, topn: int = 20) -> dict:
        """Top-N self-time frames per thread plus sampler state — the
        ``/debug/prof`` JSON body."""
        threads: dict = {}
        with self._lock:
            for tname, root in self._roots.items():
                per_frame: "dict[str, int]" = {}
                total = self._self_counts(root, per_frame)
                top = sorted(per_frame.items(), key=lambda kv: -kv[1])[:topn]
                threads[tname] = {
                    "samples": total,
                    "top_self": [
                        {"frame": frame, "self": count,
                         "frac": round(count / total, 4) if total else 0.0}
                        for frame, count in top],
                }
            return {
                "hz": self.hz,
                "samples": self.samples,
                "nodes": self._nodes,
                "max_nodes": self.max_nodes,
                "truncated": self.truncated,
                "threads": threads,
            }

    def _self_counts(self, node: list, acc: dict) -> int:
        total = node[0]
        for code, child in node[1].items():
            if child[0] > 0:
                label = self._label(code)
                acc[label] = acc.get(label, 0) + child[0]
            total += self._self_counts(child, acc)
        return total


# --------------------------------------------------------------------- #
# (b) Event-loop lag probe
# --------------------------------------------------------------------- #

class LoopLagProbe:
    """The account of one loop's thread, kept from inside its iteration.

    ``arm()`` wraps the loop's ``selector.select`` (an attribute of the
    selector object; ``disarm()`` takes it off again). An iteration is
    ``select`` exit -> the next ``select`` entry: the ready handles of one
    turn. Cumulative and exact: ``busy`` (ns running handles), ``late`` (ns
    a due timer waited while the loop still sat in ``select``: a held loop
    is busy, a starved one is late), ``gc`` (cyclic collections that ran on
    this thread), ``iterations``, ``handles``; idle is the rest of the wall
    time since ``arm()``, by construction.

    ``cpu``, the thread's own CPU ns, is read where a slice is stamped and
    not every turn: that clock is a system call, microseconds on some
    machines, and on some it ticks every 10 ms. It is a difference of ONE
    clock, so right in sum and as coarse as the clock in one slice; a slice's
    cpu is that of the turns since the last slice and of the ``select`` calls
    between them, which use none while they sleep.

    ``note_lag`` is the one entry for a wait: the account calls it with each
    turn and each late wake of 1 ms and more; from 20 ms up the wait is a
    HOLD, one ``loop_lag`` event on the probe's ring and in every running
    task flight, and one row of the ring behind ``wedged_seconds``: the SLO
    probe counts wedged WALL TIME, each hold its full length."""

    def __init__(self, obs: "RuntimeObservatory", name: str,
                 slow_s: float = 0.25, ring: int = 4096):
        self.obs = obs
        self.name = name
        self.slow_s = slow_s
        self._ring: list = [None] * ring        # (mono_t, lag_s): the holds
        self._cap = ring
        self._n = 0
        self.started_mono = time.monotonic()
        self.ticks = 0
        self.max_lag_s = 0.0
        self.slow_ticks = 0
        self.longest: list = []                 # (lag_s, cpu_ms, note) x 5
        self._buckets = [0] * (len(_LAG_EDGES) + 1)
        # The account. Totals up to the last slice; the open slice beside
        # them (``_s_*``), folded in where a slice is stamped.
        self.busy_ns = self.cpu_ns = self.late_ns = self.gc_ns = 0
        self.iterations = self.handles = 0
        self._s_busy = self._s_late = self._s_gc = 0
        self._s_it = self._s_n = 0
        self._armed_ns = 0          # perf_counter_ns at arm()
        self._in_ns = 0             # the last select's entry ...
        self._out_ns = 0            # ... and exit: >= _in_ns inside a turn
        self._cpu_at = 0            # the thread's CPU ns at the last slice
        self._turn_n = 0            # handles ready at that exit
        self._frames: "deque" = deque(maxlen=128)   # (exit ns, code, line)
        self._selector = None
        self._inner = None
        self._wrapper = None
        self._thread = 0
        self._busy_c = LOOP_BUSY_SECONDS.labels(name)
        self._cpu_c = LOOP_CPU_SECONDS.labels(name)
        self._late_c = LOOP_LATE_SECONDS.labels(name)

    # -- lifecycle ---------------------------------------------------------

    def arm(self) -> "LoopLagProbe":
        """Start the account on the RUNNING loop (call from it). A probe
        that is armed stays as it is: its wrapper never nests."""
        if self._wrapper is not None:
            return self
        loop = asyncio.get_running_loop()
        selector = getattr(loop, "_selector", None)
        self.started_mono = time.monotonic()
        if selector is None:
            log.warning("loop has no selector to account for", loop=self.name)
            return self
        self._selector = selector
        self._inner = selector.select
        self._wrapper = selector.select = self._account(loop, self._inner)
        self._thread = threading.get_ident()
        self.obs.sampler.loops[self._thread] = self
        self.obs.loop_ring(self.name)   # its clock starts with the account
        return self

    def disarm(self) -> None:
        """Close the open slice and leave ``select`` as ``arm()`` found it.
        Where another wrapper was laid over this one since, this one stays
        in the chain and only passes the call on."""
        if self._wrapper is None:
            return
        if threading.get_ident() == self._thread:
            # Inside a turn of the loop itself: book it up to here, so the
            # slices sum to the busy time.
            try:
                now = time.perf_counter_ns()
                if self._out_ns >= self._in_ns:
                    self._s_busy += now - self._out_ns
                    self._out_ns = now
                self._slice(now)
            except Exception:
                log.error("loop account failed at disarm", exc_info=True,
                          loop=self.name)
        self._unwrap()

    def _unwrap(self) -> None:
        selector, wrapper = self._selector, self._wrapper
        self._wrapper = self._selector = None
        if vars(selector).get("select") is wrapper:
            if getattr(self._inner, "__self__", None) is selector:
                del selector.select      # the class's own method again
            else:
                selector.select = self._inner
        if self.obs.sampler.loops.get(self._thread) is self:
            del self.obs.sampler.loops[self._thread]
        self._inner = None

    def _failed(self) -> None:
        """The account raised: say so once, with the traceback, and take the
        wrapper off. Never into the loop, and never line after line."""
        log.error("loop account failed; disarmed", exc_info=True,
                  loop=self.name)
        self._unwrap()

    # -- the iteration -----------------------------------------------------

    def _account(self, loop, inner):
        """``select``'s wrapper: the hot path. Two readings of the clock
        and arithmetic on this object's own numbers; whatever formats,
        stamps, counts or asks the system for the thread's CPU time runs in
        ``_turn_end`` / ``_woke_late``, a turn in hundreds."""
        pc = time.perf_counter_ns
        ready = loop._ready
        # A timer's due time is on loop.time()'s clock, in seconds.
        to_pc = pc() - int(loop.time() * 1e9)
        self._armed_ns = self._out_ns = pc()
        self._cpu_at = time.thread_time_ns()
        self._turn_n = 1            # the handle that runs arm()
        probe = self

        def select(timeout=None):
            if probe._wrapper is None:          # disarmed under a wrapper
                return inner(timeout)
            t_in = pc()
            busy = t_in - probe._out_ns
            probe._in_ns = t_in
            probe._s_busy += busy
            probe._s_it += 1
            probe._s_n += probe._turn_n
            if busy >= _NOTE_NS or probe._s_busy >= _SLICE_NS:
                probe._turn_end(busy, t_in)
            events = inner(timeout)
            t_out = pc()
            n = len(ready) + len(events)
            scheduled = loop._scheduled
            if scheduled:
                due = int(scheduled[0]._when * 1e9) + to_pc
                if due < t_out:
                    n += 1
                    late = t_out - (due if due > t_in else t_in)
                    probe._s_late += late
                    if late >= _NOTE_NS:
                        probe._woke_late(late, t_out)
            probe._turn_n = n
            probe._out_ns = t_out
            return events

        return select

    def _turn_end(self, busy: int, t_in: int) -> None:
        """A turn of 1 ms and more, or one that filled the slice: off the
        hot path. Never raises into the loop: an account that fails takes
        itself off."""
        try:
            if busy >= _HOLD_NS:
                # The hold closes its slice, whose cpu and gc are the
                # hold's (and those of the few ms of turns before it).
                note = (f"held n={self._turn_n} gc={self._s_gc / 1e6:.1f} "
                        f"who={self._who(self._out_ns)}")
                cpu = self._slice(t_in)
                self.note_lag(busy / 1e9, note, cpu / 1e6, t_in / 1e9)
            else:
                if busy >= _NOTE_NS:
                    self.note_lag(busy / 1e9)
                if self._s_busy >= _SLICE_NS:
                    self._slice(t_in)
        except Exception:
            self._failed()

    def _woke_late(self, late: int, t_out: int) -> None:
        try:
            self.note_lag(late / 1e9, "late", 0.0, t_out / 1e9)
            if late >= _HOLD_NS:
                self._slice(t_out)
        except Exception:
            self._failed()

    def _slice(self, end_ns: int) -> int:
        """Stamp the open slice as ONE ``loop_acct`` event that ends at
        ``end_ns`` and fold it into the totals; on the loop's own thread,
        whose CPU time since the last slice is the slice's. Returns those
        CPU ns."""
        now_cpu = time.thread_time_ns()
        cpu, self._cpu_at = now_cpu - self._cpu_at, now_cpu
        busy, late = self._s_busy, self._s_late
        gc_ns, it, n = self._s_gc, self._s_it, self._s_n
        self._s_busy = self._s_late = self._s_gc = 0
        self._s_it = self._s_n = 0
        self.busy_ns += busy
        self.cpu_ns += cpu
        self.late_ns += late
        self.gc_ns += gc_ns
        self.iterations += it
        self.handles += n
        self._busy_c.inc(busy / 1e9)
        self._cpu_c.inc(cpu / 1e9)
        self._late_c.inc(late / 1e9)
        ring = self.obs.loop_ring(self.name)
        if ring is not None and (busy or late):
            ring.record_at(
                end_ns / 1e9, flightlib.EV_LOOP_ACCT, cpu // 1000, busy / 1e6,
                f"late={late / 1e6:.3f} gc={gc_ns / 1e6:.3f} it={it} n={n}")
        return cpu

    def note_gc(self, pause_ns: int) -> None:
        """A collection that ran on this loop's thread (GCObservatory)."""
        self._s_gc += pause_ns

    # -- who held it -------------------------------------------------------

    def note_frame(self, frame) -> None:
        """A sampler pass met this loop's thread at ``frame``. Inside a
        turn, keep the innermost frame of this package (else the innermost
        of any file): the ``who`` of the hold the turn may become."""
        out = self._out_ns
        if out < self._in_ns:
            return                  # in select: nobody holds the loop
        f = frame
        while f is not None and not f.f_code.co_filename.startswith(_PKG_DIR):
            f = f.f_back
        f = f or frame
        self._frames.append((out, f.f_code, f.f_lineno))

    def _who(self, out_ns: int) -> str:
        """The frame most often met inside the turn that began at
        ``out_ns``, as ``file:func:line``; ``?`` where no pass fell in."""
        met: dict = {}
        for out, code, line in list(self._frames):
            if out == out_ns:
                met[code, line] = met.get((code, line), 0) + 1
        if not met:
            return "?"
        code, line = max(met, key=met.get)
        return f"{os.path.basename(code.co_filename)}:{code.co_name}:{line}"

    # -- a wait, booked ----------------------------------------------------

    def note_lag(self, lag: float, note: str = "late", cpu_ms: float = 0.0,
                 end_pc: "float | None" = None) -> None:
        """One wait of ``lag`` seconds that a due handle stood: a turn's
        length (``note`` "held ...": what every other handle waited behind)
        or a late wake's ("late"). The account calls this from 1 ms up;
        tests and the DES sim may feed synthetic ones. From 20 ms up it is
        a hold: ONE ``loop_lag`` event that ended at ``end_pc`` (now) on the
        probe's ring and in every running task flight."""
        self.ticks += 1
        i = 0
        for edge in _LAG_EDGES:
            if lag <= edge:
                break
            i += 1
        self._buckets[i] += 1
        LAG_SECONDS.observe(lag)
        if lag > self.max_lag_s:
            self.max_lag_s = lag
        if lag >= self.slow_s:
            self.slow_ticks += 1
            SLOW_TICKS_TOTAL.inc()
        if lag * 1e9 < _HOLD_NS:
            return
        self._ring[self._n % self._cap] = (time.monotonic(), lag)
        self._n += 1
        self.longest = sorted(self.longest + [(lag, cpu_ms, note)],
                              reverse=True)[:5]
        self.obs.stamp_hold(self.name, lag, int(cpu_ms), note, end_pc)

    # -- SLO feed ----------------------------------------------------------

    def wedged_seconds(self, window: float, threshold: float,
                       now: "float | None" = None) -> "tuple[float, float]":
        """(wedged, observed) wall-seconds over the trailing window: the
        pkg/slo kind="probe" good/bad fraction. Each retained tick whose
        lag crossed ``threshold`` contributes its full lag — the wall
        time the loop was not serving."""
        if now is None:
            now = time.monotonic()
        cutoff = now - window
        bad = 0.0
        oldest_seen = now
        newest = self._n - 1
        oldest = max(0, self._n - self._cap)
        i = newest
        while i >= oldest:
            row = self._ring[i % self._cap]
            i -= 1
            if row is None or row[0] < cutoff:
                break
            oldest_seen = row[0]
            if row[1] >= threshold:
                bad += row[1]
        observed = min(window, now - max(self.started_mono, cutoff))
        # A ring that wrapped inside the window shrinks what we can vouch
        # for to the retained span.
        if self._n > self._cap:
            observed = min(observed, now - oldest_seen)
        observed = max(0.0, observed)
        return min(bad, observed), observed

    def summary(self) -> dict:
        busy, cpu = self.busy_ns + self._s_busy, self.cpu_ns
        # The account stands as of its last reading: a select's entry or
        # exit, whichever came last.
        asof = max(self._in_ns, self._out_ns)
        if (self._wrapper is not None and self._out_ns >= self._in_ns
                and threading.get_ident() == self._thread):
            # Asked from inside a turn: the turn so far is busy time.
            asof = time.perf_counter_ns()
            busy += asof - self._out_ns
            cpu += time.thread_time_ns() - self._cpu_at
        return {
            "name": self.name,
            "slow_s": self.slow_s,
            "ticks": self.ticks,
            "max_lag_s": round(self.max_lag_s, 6),
            "slow_ticks": self.slow_ticks,
            "histogram": {
                "edges_s": list(_LAG_EDGES),
                "counts": list(self._buckets),
            },
            # The account: busy + idle is the wall time since arm().
            "busy_s": busy / 1e9,
            "idle_s": (asof - self._armed_ns - busy) / 1e9,
            "cpu_s": cpu / 1e9,     # up to the last slice, or to now
            "late_s": (self.late_ns + self._s_late) / 1e9,
            "gc_s": (self.gc_ns + self._s_gc) / 1e9,
            "iterations": self.iterations + self._s_it,
            "handles": self.handles + self._s_n,
            "holds": [{"seconds": round(lag, 6), "cpu_ms": round(cpu_ms, 3),
                       "note": note} for lag, cpu_ms, note in self.longest],
        }


# --------------------------------------------------------------------- #
# (c) GC observatory
# --------------------------------------------------------------------- #

class GCObservatory:
    """gc.callbacks pause clock. Collections are not reentrant, so one
    start stamp per observatory suffices; the callback runs on whatever
    thread triggered the collection — everything it touches is a scalar
    store or a bounded bucket increment."""

    _GENS = ("0", "1", "2")

    def __init__(self, obs: "RuntimeObservatory", slow_s: float = 0.05):
        self.obs = obs
        self.slow_s = slow_s
        self.collections = [0, 0, 0]
        self.collected = 0
        self.uncollectable = 0
        self.max_pause_s = 0.0
        self.slow_pauses = 0
        self._pause_sum = [0.0, 0.0, 0.0]
        self._start_pc = -1.0
        self._armed = False
        self._pause_children = [GC_PAUSE_SECONDS.labels(g)
                                for g in self._GENS]
        self._count_children = [GC_COLLECTIONS_TOTAL.labels(g)
                                for g in self._GENS]

    def arm(self) -> None:
        if not self._armed:
            gc.callbacks.append(self._cb)
            self._armed = True

    def disarm(self) -> None:
        if self._armed:
            try:
                gc.callbacks.remove(self._cb)
            except ValueError:
                pass
            self._armed = False

    def _cb(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._start_pc = time.perf_counter()
            return
        if self._start_pc < 0:
            return
        pause = time.perf_counter() - self._start_pc
        self._start_pc = -1.0
        gen = min(2, max(0, int(info.get("generation", 0))))
        self.collections[gen] += 1
        self._pause_sum[gen] += pause
        self.collected += int(info.get("collected", 0))
        self.uncollectable += int(info.get("uncollectable", 0))
        if pause > self.max_pause_s:
            self.max_pause_s = pause
        self._pause_children[gen].observe(pause)
        self._count_children[gen].inc()
        probe = self.obs.sampler.loops.get(threading.get_ident())
        if probe is not None:
            probe.note_gc(int(pause * 1e9))
        if pause >= self.slow_s:
            self.slow_pauses += 1
            self.obs._stamp_flights_gc(pause)

    def summary(self) -> dict:
        return {
            "collections": list(self.collections),
            "pause_sum_s": [round(v, 6) for v in self._pause_sum],
            "max_pause_s": round(self.max_pause_s, 6),
            "slow_pauses": self.slow_pauses,
            "slow_s": self.slow_s,
            "collected": self.collected,
            "uncollectable": self.uncollectable,
            "tracked": gc.get_count(),
        }


# --------------------------------------------------------------------- #
# The umbrella + process singleton
# --------------------------------------------------------------------- #

class RuntimeObservatory:
    """Sampler + per-loop accounts + GC observatory behind one handle.
    ``recorder`` (a pkg/flight.FlightRecorder) is where the loops' slices
    and holds and slow GC pauses land as typed events; a role without a
    recorder (scheduler) keeps the account and stamps nothing."""

    def __init__(self, cfg: "ProfConfig | None" = None, recorder=None):
        self.cfg = cfg or ProfConfig()
        self.recorder = recorder
        self.sampler = StackSampler(self.cfg.hz, self.cfg.max_nodes,
                                    self.cfg.max_depth)
        self.gc = GCObservatory(self, self.cfg.gc_slow_s)
        self.probes: "dict[str, LoopLagProbe]" = {}
        self.started_wall = time.time()

    # -- lifecycle ---------------------------------------------------------

    def start(self) -> None:
        self.sampler.start()
        self.gc.arm()

    def stop(self) -> None:
        for probe in self.probes.values():
            probe.disarm()
        self.probes.clear()
        self.gc.disarm()
        self.sampler.stop()

    def arm_loop(self, name: str = "main") -> LoopLagProbe:
        """Start an account of the RUNNING loop (call from it). One
        probe per name; re-arming a name replaces the old probe."""
        old = self.probes.get(name)
        if old is not None:
            old.disarm()
        probe = LoopLagProbe(self, name, self.cfg.lag_slow_s,
                             self.cfg.lag_ring)
        self.probes[name] = probe
        return probe.arm()

    # -- flight stamping ---------------------------------------------------

    def loop_ring(self, name: str):
        """The ring of loop ``name``'s account, ``runtime:loop:<name>`` on
        the recorder (None for a role without one): a flight the recorder
        holds outside its index of tasks, so no task can evict it."""
        rec = self.recorder
        if rec is None:
            return None
        return rec.ring(f"runtime:loop:{name}", _ACCT_RING)

    def stamp_hold(self, name: str, lag: float, cpu_ms: int, note: str,
                   end_pc: "float | None" = None) -> None:
        """The one place a ``loop_lag`` is stamped: on the loop's own ring
        and in every running task flight, as an event that ended at
        ``end_pc`` (aux = seconds, piece = cpu ms)."""
        ring = self.loop_ring(name)
        if ring is None:
            return
        if end_pc is None:
            end_pc = time.perf_counter()
        ring.record_at(end_pc, flightlib.EV_LOOP_LAG, cpu_ms, lag, note)
        self.recorder.stamp_running(flightlib.EV_LOOP_LAG, lag, note,
                                    cpu_ms, end_pc)

    def _stamp_flights_gc(self, pause: float) -> None:
        rec = self.recorder
        if rec is not None:
            rec.stamp_running(flightlib.EV_GC_PAUSE, pause, "gc_pause")

    # -- SLO feed ----------------------------------------------------------

    def slo_probes(self) -> dict:
        """pkg/slo kind="probe" callables, keyed by spec field."""
        return {"loop_lag": self._loop_lag_counts}

    def _loop_lag_counts(self, window: float,
                         threshold: float) -> "tuple[float, float]":
        bad = total = 0.0
        for probe in self.probes.values():
            b, t = probe.wedged_seconds(window, threshold)
            bad += b
            total += t
        return bad, total

    # -- reports -----------------------------------------------------------

    def runtime_report(self) -> dict:
        """/debug/prof/runtime: loop lag + GC + /proc gauges (and the
        Prometheus runtime_* gauges refresh here too — scrape-time, not
        continuous)."""
        proc = proc_stats()
        RSS_BYTES.set(proc["rss_bytes"])
        OPEN_FDS.set(proc["open_fds"])
        THREADS_GAUGE.set(proc["threads"])
        CTX_SWITCHES.labels("voluntary").set(
            proc["voluntary_ctx_switches"])
        CTX_SWITCHES.labels("involuntary").set(
            proc["involuntary_ctx_switches"])
        return {
            "loops": [p.summary() for p in self.probes.values()],
            "gc": self.gc.summary(),
            "proc": proc,
            "uptime_s": round(time.time() - self.started_wall, 1),
        }

    def profile_report(self, topn: int = 20) -> dict:
        return self.sampler.report(topn)

    def folded(self, max_lines: int = 4096) -> str:
        return self.sampler.folded(max_lines)

    def postmortem(self, topn: int = 10) -> dict:
        """Pruned snapshot for flight post-mortem bundles: what the
        PROCESS was doing when the task died — top frames per thread,
        loop-lag and GC summaries, proc gauges."""
        prof = self.sampler.report(topn)
        return {
            "prof": {
                "samples": prof["samples"],
                "truncated": prof["truncated"],
                "threads": {
                    name: t["top_self"][:topn]
                    for name, t in prof["threads"].items() if t["top_self"]
                },
            },
            "loops": [p.summary() for p in self.probes.values()],
            "gc": self.gc.summary(),
            "proc": proc_stats(),
        }


_OBS: "RuntimeObservatory | None" = None
_REFS = 0
_OBS_LOCK = threading.Lock()


def install(cfg: "ProfConfig | None" = None,
            recorder=None) -> RuntimeObservatory:
    """Get-or-create the process observatory (refcounted — pair every
    install with a release). The first caller's config wins; a recorder
    attaches whenever one is offered and none is set."""
    global _OBS, _REFS
    with _OBS_LOCK:
        if _OBS is None:
            _OBS = RuntimeObservatory(cfg)
            _OBS.start()
        if recorder is not None and _OBS.recorder is None:
            _OBS.recorder = recorder
        _REFS += 1
        return _OBS


def release(obs: RuntimeObservatory) -> None:
    global _OBS, _REFS
    with _OBS_LOCK:
        if obs is not _OBS:
            obs.stop()      # a privately-constructed observatory
            return
        _REFS -= 1
        if _REFS <= 0:
            _OBS, _REFS = None, 0
            obs.stop()


def observatory() -> "RuntimeObservatory | None":
    return _OBS
