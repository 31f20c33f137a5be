"""Cluster control tower: manager-side fleet rollup, event journal, spool.

The scheduler-side telemetry layers (flight recorder, pod lens, fleet
observatory, runtime observatory) all stop at the scheduler boundary and
live in bounded in-memory rings. This module carries a condensed view of
each scheduler's fleet observatory across the keepalive wire and merges it
into one cluster-wide, per-scheduler-attributed picture on the manager:

  FrameBuilder     scheduler side — a bounded compact frame (time-series
                   rollup since the last ship, SLO burn rates, straggler /
                   quarantined host sets, decision-kind counts, resident
                   bytes), hard-capped in bytes with halving-until-fit
                   (the flight-digest discipline). Rides the
                   ``start_keepalive(payload=)`` hook like tenant_burn.
  ClusterSeries    manager side — folds frames into cluster totals with
                   per-scheduler attribution; /debug/cluster*.
  ClusterEventJournal
                   edge-triggered cluster events (keepalive lapse/return,
                   SLO breach, straggler flagged, quarantine storm,
                   admission 429 burst) in a bounded ring, the fleet
                   DecisionLog pattern; /debug/cluster/events.
  TelemetrySpool   compressed frames ring-buffered into the manager's
                   sqlite with a byte budget, so the cluster view and
                   ``?window=`` retrospection survive a manager restart.

A missing or malformed frame must never stall keepalives: every ingest
path is fail-open (the ``ingest_tenant_burn`` discipline), and a
scheduler on an older wire that ships no frames keeps full liveness
semantics — the cluster view marks it ``no_data`` rather than inventing
zeros.
"""

from __future__ import annotations

import json
import time
import zlib
from collections import deque

from dragonfly2_tpu.pkg import dflog, metrics
from dragonfly2_tpu.pkg.fleet import COUNTERS

log = dflog.get("pkg.cluster")

# Hard byte cap on one encoded frame. Keepalives are small control-plane
# messages; the frame must stay a rounding error next to them even on a
# scheduler tracking thousands of hosts.
FRAME_MAX_BYTES = 8192

# Cluster event kinds (the journal rejects everything else so a typo'd
# emitter cannot grow an unbounded label set).
EVENT_KINDS = ("lapse", "return", "slo_breach", "straggler",
               "quarantine_storm", "admission_burst")

FRAME_COUNT = metrics.counter(
    "manager_fleet_frames_total",
    "Fleet telemetry frames arriving on scheduler keepalives, by result "
    "(ok / malformed / error)", ("result",))

SCHEDULERS_GAUGE = metrics.gauge(
    "manager_cluster_schedulers",
    "Schedulers known to the cluster control tower, by state (active / "
    "inactive / no_data — no_data = alive keepalive but no fleet frames, "
    "an older wire)", ("state",))

EVENT_COUNT = metrics.counter(
    "manager_cluster_events_total",
    "Edge-triggered cluster events recorded in the journal, by kind "
    "(lapse / return / slo_breach / straggler / quarantine_storm / "
    "admission_burst)", ("kind",))

SPOOL_GAUGE = metrics.gauge(
    "manager_spool_bytes",
    "Compressed bytes currently held by the durable telemetry spool "
    "(pruned oldest-first to its byte budget)")


def _enc_len(frame: dict) -> int:
    return len(json.dumps(frame, separators=(",", ":")))


# --------------------------------------------------------------------- #
# Scheduler side: the frame builder
# --------------------------------------------------------------------- #

class FrameBuilder:
    """Condenses one scheduler's fleet observatory into a bounded frame.

    ``build()`` is called from the keepalive payload provider at keepalive
    cadence; it reads only O(ring) accessors (``totals()`` /
    ``gauge_column()``) and per-kind decision counts — never the decision
    ring itself — so a frame costs microseconds, not a scan.
    """

    def __init__(self, fleet, *, slo=None, hostname: str = "",
                 quarantined=None, max_bytes: int = FRAME_MAX_BYTES,
                 clock=time.monotonic):
        self.fleet = fleet
        self.slo = slo
        self.hostname = hostname
        self._quarantined = quarantined   # () -> list[str] | None
        self.max_bytes = max_bytes
        self._clock = clock
        self._last_build = 0.0            # monotonic; 0 = never
        self._last_kind_counts: dict = {}
        # resident_bytes() deep-walks every bounded structure — two
        # orders of magnitude above the rest of a build. The structures
        # are preallocated/bounded, so the number moves slowly: refresh
        # at most every RESIDENT_REFRESH_S and ship the cached value.
        self._resident = -1
        self._resident_at = 0.0
        self.built_total = 0

    RESIDENT_REFRESH_S = 60.0

    def build(self) -> "dict | None":
        """One frame covering the window since the previous build (first
        frame: two buckets). Returns None when the observatory is off."""
        if self.fleet is None:
            return None
        series = self.fleet.series
        mono = self._clock()
        if self._last_build:
            window_s = mono - self._last_build
        else:
            window_s = series.bucket_s * 2
        # Clamp to the ring span — a scheduler that slept past its own
        # history can only report what the ring still holds.
        window_s = max(series.bucket_s, min(
            window_s, series.bucket_s * series.n_buckets))
        self._last_build = mono

        totals = series.totals(window_s, COUNTERS)
        counters = {k: (int(v) if v.is_integer() else v)
                    for k, v in totals.items() if v}
        gauges = series.gauges_last(window_s)   # {} when never sampled

        frame = {
            "v": 1,
            "host": self.hostname,
            "ts": round(time.time(), 3),
            "window_s": round(window_s, 3),
            "counters": counters,
            "gauges": gauges,
            "stragglers": sorted(self.fleet.scorecards._stragglers),
            "quarantined": sorted(self._quarantined() or ())
            if self._quarantined is not None else [],
            "decisions": self._decision_delta(),
            "resident_bytes": self._resident_bytes(mono),
        }
        if self.slo is not None:
            rep = self.slo.evaluate()
            frame["slo"] = {
                s["name"]: {
                    "state": s["state"],
                    "burn": max((w["burn_rate"] for w in s["windows"]),
                                default=0.0),
                } for s in rep["slos"]}
            frame["breached"] = rep["breached"]

        # Halving-until-fit (the flight-digest discipline): host sets are
        # the only unbounded-in-principle fields, so they pay first —
        # newest-sorted-first halves keep the frame representative.
        size = _enc_len(frame)
        while size > self.max_bytes and (
                frame["stragglers"] or frame["quarantined"]):
            frame["truncated"] = True
            if len(frame["stragglers"]) >= len(frame["quarantined"]):
                frame["stragglers"] = \
                    frame["stragglers"][:len(frame["stragglers"]) // 2]
            else:
                frame["quarantined"] = \
                    frame["quarantined"][:len(frame["quarantined"]) // 2]
            size = _enc_len(frame)
        if size > self.max_bytes and frame["decisions"]:
            frame["truncated"] = True
            frame["decisions"] = {}
            size = _enc_len(frame)
        frame["bytes"] = size
        self.built_total += 1
        return frame

    def _resident_bytes(self, mono: float) -> int:
        if self._resident < 0 or \
                mono - self._resident_at >= self.RESIDENT_REFRESH_S:
            self._resident = self.fleet.resident_bytes()
            self._resident_at = mono
        return self._resident

    def _decision_delta(self) -> dict:
        """Decision-kind counts since the previous frame — deltas of the
        DecisionLog's per-kind totals, so consecutive frames sum cleanly
        on the manager without double counting."""
        cur = dict(self.fleet.decisions.kind_counts)
        prev = self._last_kind_counts
        self._last_kind_counts = cur
        out = {}
        for kind, n in cur.items():
            d = n - prev.get(kind, 0)
            if d:
                out[kind] = d
        return out


# --------------------------------------------------------------------- #
# Manager side: event journal
# --------------------------------------------------------------------- #

class ClusterEventJournal:
    """Bounded ring of cluster events (one tuple per event, the fleet
    DecisionLog discipline). Query iterates newest-first."""

    __slots__ = ("cap", "_ring", "_n", "_children")

    def __init__(self, cap: int = 1024):
        self.cap = cap
        self._ring: list = [None] * cap
        self._n = 0
        self._children: dict = {}

    def record(self, kind: str, *, scheduler: str = "",
               subject: str = "", detail: str = "") -> None:
        if kind not in EVENT_KINDS:
            return
        self._ring[self._n % self.cap] = (
            time.time(), kind, scheduler, subject, detail)
        self._n += 1
        child = self._children.get(kind)
        if child is None:
            child = self._children[kind] = EVENT_COUNT.labels(kind)
        child.inc()
        log.info("cluster event", kind=kind, scheduler=scheduler,
                 subject=subject, detail=detail)

    @property
    def recorded_total(self) -> int:
        return self._n

    def query(self, *, kind: str = "", scheduler: str = "",
              limit: int = 256, since: float = 0.0,
              before: float = 0.0) -> dict:
        """Newest-first page; ``since``/``before`` are wall-clock bounds
        (half-open [since, before)) and ``since`` terminates the scan
        early — the ring is time-ordered."""
        out = []
        truncated = False
        i = self._n - 1
        oldest = max(0, self._n - self.cap)
        while i >= oldest:
            e = self._ring[i % self.cap]
            i -= 1
            if e is None:
                continue
            ts, k, sched, subject, detail = e
            if since and ts < since:
                break
            if before and ts >= before:
                continue
            if kind and k != kind:
                continue
            if scheduler and sched != scheduler:
                continue
            if len(out) >= limit:
                truncated = True
                break
            out.append({"ts": round(ts, 3), "kind": k,
                        "scheduler": sched, "subject": subject,
                        "detail": detail})
        return {"events": out, "recorded_total": self._n,
                "dropped": max(0, self._n - self.cap),
                "truncated": truncated}


class AdmissionBurstDetector:
    """Edge-triggers one ``admission_burst`` event when REST 429s exceed
    ``threshold`` within ``window_s``, and re-arms once the rate falls
    back under — a storm of push-backs becomes one journal line, not
    one per request."""

    def __init__(self, journal: ClusterEventJournal, *,
                 threshold: int = 10, window_s: float = 10.0,
                 clock=time.monotonic):
        self.journal = journal
        self.threshold = threshold
        self.window_s = window_s
        self._clock = clock
        self._hits: deque = deque()
        self._bursting = False

    def note_429(self, subject: str = "") -> None:
        now = self._clock()
        self._hits.append(now)
        cutoff = now - self.window_s
        while self._hits and self._hits[0] < cutoff:
            self._hits.popleft()
        if len(self._hits) >= self.threshold:
            if not self._bursting:
                self._bursting = True
                self.journal.record(
                    "admission_burst", subject=subject,
                    detail=f"{len(self._hits)} 429s in "
                           f"{self.window_s:.0f}s")
        elif self._bursting and len(self._hits) <= self.threshold // 2:
            self._bursting = False


# --------------------------------------------------------------------- #
# Manager side: durable telemetry spool
# --------------------------------------------------------------------- #

class TelemetrySpool:
    """Compressed frames ring-buffered into the manager's sqlite with a
    byte budget (the SnapshotStore discipline: same embedded backend,
    prune-oldest past the budget). ``load()`` replays the surviving
    window after a manager restart."""

    def __init__(self, db, *, max_bytes: int = 2 * 1024 * 1024):
        self.db = db                      # manager Database (execute())
        self.max_bytes = max_bytes
        self.db.execute(
            "CREATE TABLE IF NOT EXISTS cluster_frames ("
            "  id INTEGER PRIMARY KEY AUTOINCREMENT,"
            "  ts REAL NOT NULL,"
            "  hostname TEXT NOT NULL,"
            "  ip TEXT NOT NULL,"
            "  nbytes INTEGER NOT NULL,"
            "  frame BLOB NOT NULL)")
        row = self.db.execute(
            "SELECT COALESCE(SUM(nbytes), 0) AS b FROM cluster_frames")[0]
        self._bytes = int(row["b"])
        SPOOL_GAUGE.set(self._bytes)

    @property
    def bytes(self) -> int:
        return self._bytes

    def store(self, hostname: str, ip: str, frame: dict) -> None:
        blob = zlib.compress(
            json.dumps(frame, separators=(",", ":")).encode())
        self.db.execute(
            "INSERT INTO cluster_frames (ts, hostname, ip, nbytes, frame) "
            "VALUES (?, ?, ?, ?, ?)",
            (float(frame.get("ts", time.time())), hostname, ip,
             len(blob), blob))
        self._bytes += len(blob)
        while self._bytes > self.max_bytes:
            rows = self.db.execute(
                "SELECT id, nbytes FROM cluster_frames "
                "ORDER BY id LIMIT 64")
            if not rows:
                break
            drop, freed = [], 0
            for r in rows:
                drop.append(r["id"])
                freed += r["nbytes"]
                if self._bytes - freed <= self.max_bytes:
                    break
            qs = ",".join("?" * len(drop))
            self.db.execute(
                f"DELETE FROM cluster_frames WHERE id IN ({qs})", drop)
            self._bytes -= freed
        SPOOL_GAUGE.set(self._bytes)

    def load(self) -> list:
        """Oldest-first (ts, hostname, ip, frame) replay of every spooled
        frame; undecodable rows are skipped, not fatal."""
        out = []
        for r in self.db.execute(
                "SELECT ts, hostname, ip, frame FROM cluster_frames "
                "ORDER BY id"):
            try:
                frame = json.loads(zlib.decompress(r["frame"]))
            except Exception:
                continue
            out.append((r["ts"], r["hostname"], r["ip"], frame))
        return out

    def frame_count(self) -> int:
        row = self.db.execute(
            "SELECT COUNT(*) AS n FROM cluster_frames")[0]
        return int(row["n"])


# --------------------------------------------------------------------- #
# Manager side: the merged cluster series
# --------------------------------------------------------------------- #

class _SchedulerState:
    __slots__ = ("hostname", "ip", "frames", "state", "last_frame_ts",
                 "first_seen", "frames_total", "prev_stragglers",
                 "prev_breached", "prev_quarantined")

    def __init__(self, hostname: str, ip: str, cap: int):
        self.hostname = hostname
        self.ip = ip
        self.frames: deque = deque(maxlen=cap)
        self.state = "active"             # active | inactive | no_data
        self.last_frame_ts = 0.0
        self.first_seen = time.time()
        self.frames_total = 0
        self.prev_stragglers: set = set()
        self.prev_breached: set = set()
        self.prev_quarantined = 0

    @property
    def key(self) -> str:
        return f"{self.hostname}@{self.ip}" if self.ip else self.hostname


class ClusterSeries:
    """Folds per-scheduler fleet frames into a cluster-wide view with
    per-scheduler attribution, emitting edge-triggered journal events on
    the way (new straggler, new SLO breach, quarantine storm). Every
    ingest path is fail-open: a bad frame is counted and dropped, never
    raised into the keepalive stream."""

    def __init__(self, *, journal: "ClusterEventJournal | None" = None,
                 spool: "TelemetrySpool | None" = None,
                 frames_per_scheduler: int = 240,
                 quarantine_storm: int = 3):
        self.journal = journal or ClusterEventJournal()
        self.spool = spool
        self.frames_per_scheduler = frames_per_scheduler
        # A jump of this many quarantined hosts between consecutive
        # frames of one scheduler is a storm event.
        self.quarantine_storm = quarantine_storm
        self.admission = AdmissionBurstDetector(self.journal)
        self._scheds: dict = {}           # (hostname, ip) -> _SchedulerState
        self.restored_frames = 0
        self._frame_children = {
            r: FRAME_COUNT.labels(r) for r in ("ok", "malformed", "error")}
        self._state_children = {
            s: SCHEDULERS_GAUGE.labels(s)
            for s in ("active", "inactive", "no_data")}
        self._refresh_state_gauge()
        if self.spool is not None:
            self._restore()

    # -- ingest ------------------------------------------------------- #

    def ingest(self, hostname: str, ip: str, frame) -> int:
        """Fold one frame in; returns 1 on accept, 0 otherwise. Fail-open
        by construction — this runs inside the keepalive stream."""
        try:
            if not isinstance(frame, dict) or frame.get("v") != 1:
                self._frame_children["malformed"].inc()
                return 0
            st = self._sched(hostname, ip, state="active")
            if st.state != "active":
                self._set_state(st, "active")
            st.frames.append(frame)
            st.frames_total += 1
            st.last_frame_ts = float(frame.get("ts", time.time()))
            self._emit_edges(st, frame)
            if self.spool is not None:
                try:
                    self.spool.store(hostname, ip, frame)
                except Exception:
                    log.warning("telemetry spool write failed",
                                exc_info=True)
            self._frame_children["ok"].inc()
            return 1
        except Exception:
            self._frame_children["error"].inc()
            return 0

    def mark_seen(self, hostname: str, ip: str) -> None:
        """A keepalive arrived without a frame: full liveness, zero data.
        An already-reporting scheduler keeps its data; an old-wire one is
        surfaced as ``no_data`` instead of inventing zeros."""
        st = self._scheds.get((hostname, ip))
        if st is None:
            st = self._sched(hostname, ip, state="no_data")
        elif st.state == "inactive":
            self._set_state(
                st, "active" if st.frames_total else "no_data")

    def note_lapse(self, hostname: str, ip: str) -> None:
        """Keepalive liveness lapsed (expire_stale flipped the row)."""
        st = self._sched(hostname, ip, state="inactive")
        if st.state != "inactive":
            self._set_state(st, "inactive")
            self.journal.record("lapse", scheduler=st.key,
                                detail="keepalive lapsed")

    def note_return(self, hostname: str, ip: str) -> None:
        """A lapsed scheduler's keepalive came back."""
        st = self._scheds.get((hostname, ip))
        if st is not None and st.state == "inactive":
            self._set_state(
                st, "active" if st.frames_total else "no_data")
            self.journal.record("return", scheduler=st.key,
                                detail="keepalive returned")

    def note_admission_429(self, subject: str = "") -> None:
        self.admission.note_429(subject)

    # -- internals ---------------------------------------------------- #

    def _sched(self, hostname: str, ip: str,
               *, state: str) -> _SchedulerState:
        st = self._scheds.get((hostname, ip))
        if st is None:
            st = _SchedulerState(hostname, ip, self.frames_per_scheduler)
            st.state = state
            self._scheds[(hostname, ip)] = st
            self._refresh_state_gauge()
        return st

    def _set_state(self, st: _SchedulerState, state: str) -> None:
        st.state = state
        self._refresh_state_gauge()

    def _refresh_state_gauge(self) -> None:
        counts = {"active": 0, "inactive": 0, "no_data": 0}
        for st in self._scheds.values():
            counts[st.state] = counts.get(st.state, 0) + 1
        for state, child in self._state_children.items():
            child.set(counts[state])

    def _emit_edges(self, st: _SchedulerState, frame: dict) -> None:
        stragglers = set(frame.get("stragglers") or ())
        for host in sorted(stragglers - st.prev_stragglers):
            self.journal.record("straggler", scheduler=st.key,
                                subject=host,
                                detail="flagged by fleet scorecard")
        st.prev_stragglers = stragglers
        breached = set(frame.get("breached") or ())
        for name in sorted(breached - st.prev_breached):
            slo = (frame.get("slo") or {}).get(name) or {}
            self.journal.record(
                "slo_breach", scheduler=st.key, subject=name,
                detail=f"burn={slo.get('burn', 0.0):.2f}")
        st.prev_breached = breached
        nq = len(frame.get("quarantined") or ())
        if nq - st.prev_quarantined >= self.quarantine_storm:
            self.journal.record(
                "quarantine_storm", scheduler=st.key,
                detail=f"{st.prev_quarantined} -> {nq} quarantined "
                       f"hosts in one frame")
        st.prev_quarantined = nq

    def _restore(self) -> None:
        """Replay the spooled window (oldest-first) without re-triggering
        edge events — restored history is context, not news."""
        try:
            rows = self.spool.load()
        except Exception:
            log.warning("telemetry spool restore failed", exc_info=True)
            return
        for ts, hostname, ip, frame in rows:
            if not isinstance(frame, dict) or frame.get("v") != 1:
                continue
            st = self._sched(hostname, ip, state="active")
            st.frames.append(frame)
            st.frames_total += 1
            st.last_frame_ts = max(st.last_frame_ts,
                                   float(frame.get("ts", ts)))
            st.prev_stragglers = set(frame.get("stragglers") or ())
            st.prev_breached = set(frame.get("breached") or ())
            st.prev_quarantined = len(frame.get("quarantined") or ())
            self.restored_frames += 1
        if self.restored_frames:
            log.info("telemetry spool restored",
                     frames=self.restored_frames,
                     schedulers=len(self._scheds))

    # -- reports ------------------------------------------------------ #

    def _frames_in(self, st: _SchedulerState, since: float) -> list:
        return [f for f in st.frames
                if float(f.get("ts", 0.0)) >= since]

    def report(self, window_s: float = 600.0) -> dict:
        """The merged cluster view: totals summed over every scheduler's
        frames in the window, latest gauges summed across schedulers,
        and straggler/quarantine/breach attribution back to the owning
        scheduler."""
        now = time.time()
        since = now - window_s
        totals: dict = {}
        gauges: dict = {}
        decisions: dict = {}
        stragglers: dict = {}
        quarantined: dict = {}
        breached: dict = {}
        schedulers = []
        for st in sorted(self._scheds.values(), key=lambda s: s.key):
            frames = self._frames_in(st, since)
            last = frames[-1] if frames else None
            for f in frames:
                for k, v in (f.get("counters") or {}).items():
                    totals[k] = totals.get(k, 0) + v
                for k, v in (f.get("decisions") or {}).items():
                    decisions[k] = decisions.get(k, 0) + v
            if last is not None:
                for k, v in (last.get("gauges") or {}).items():
                    gauges[k] = gauges.get(k, 0) + v
                for host in last.get("stragglers") or ():
                    stragglers[host] = st.key
                for host in last.get("quarantined") or ():
                    quarantined[host] = st.key
                for name in last.get("breached") or ():
                    breached.setdefault(name, []).append(st.key)
            schedulers.append(self._sched_summary(st, frames, now))
        return {
            "now": round(now, 3),
            "window_s": window_s,
            "schedulers": schedulers,
            "totals": totals,
            "gauges": gauges,
            "decisions": decisions,
            "stragglers": stragglers,
            "quarantined": quarantined,
            "breached": breached,
            "events": {"recorded_total": self.journal.recorded_total,
                       "dropped": max(0, self.journal.recorded_total
                                      - self.journal.cap)},
            "restored_frames": self.restored_frames,
            "spool": ({"bytes": self.spool.bytes,
                       "max_bytes": self.spool.max_bytes}
                      if self.spool is not None else None),
        }

    def _sched_summary(self, st: _SchedulerState, frames: list,
                       now: float) -> dict:
        last = frames[-1] if frames else None
        out = {
            "scheduler": st.key,
            "hostname": st.hostname,
            "ip": st.ip,
            "state": st.state if st.frames_total or
            st.state == "inactive" else "no_data",
            "frames": len(frames),
            "frames_total": st.frames_total,
            "last_frame_age_s": (round(now - st.last_frame_ts, 1)
                                 if st.last_frame_ts else None),
        }
        if last is not None:
            out.update({
                "stragglers": list(last.get("stragglers") or ()),
                "quarantined": list(last.get("quarantined") or ()),
                "breached": list(last.get("breached") or ()),
                "gauges": dict(last.get("gauges") or {}),
                "resident_bytes": last.get("resident_bytes"),
                "frame_bytes": last.get("bytes"),
            })
        return out

    def schedulers_report(self, window_s: float = 600.0) -> dict:
        now = time.time()
        since = now - window_s
        return {
            "now": round(now, 3),
            "window_s": window_s,
            "schedulers": [
                self._sched_summary(st, self._frames_in(st, since), now)
                for st in sorted(self._scheds.values(),
                                 key=lambda s: s.key)],
        }

    def slo_report(self, window_s: float = 600.0) -> dict:
        """Latest per-scheduler SLO condensate + the cluster-wide union
        of breached names."""
        now = time.time()
        since = now - window_s
        per = {}
        breached: set = set()
        for st in sorted(self._scheds.values(), key=lambda s: s.key):
            frames = self._frames_in(st, since)
            last = next((f for f in reversed(frames)
                         if "slo" in f), None)
            if last is None:
                per[st.key] = {"state": "no_data", "slos": {}}
                continue
            per[st.key] = {"state": "breach" if last.get("breached")
                           else "ok", "slos": last.get("slo") or {}}
            breached.update(last.get("breached") or ())
        return {"now": round(now, 3), "window_s": window_s,
                "schedulers": per, "breached": sorted(breached)}


# --------------------------------------------------------------------- #
# The one text renderer (``?format=text`` and ``dfget --cluster``)
# --------------------------------------------------------------------- #

def render_cluster(report: dict) -> str:
    """Render a ClusterSeries.report() as the operator-facing text view —
    the SAME renderer behind ``GET /debug/cluster?format=text`` and
    ``dfget --explain --cluster``."""
    lines = []
    n = len(report.get("schedulers") or ())
    lines.append(f"cluster view · {n} scheduler(s) · window "
                 f"{report.get('window_s', 0):.0f}s")
    totals = report.get("totals") or {}
    if totals:
        keys = ("pieces_landed", "handouts", "back_source", "quarantines",
                "registers", "announces")
        parts = [f"{k}={int(totals[k])}" for k in keys if totals.get(k)]
        extra = sum(v for k, v in totals.items()
                    if k.startswith("failed_"))
        if extra:
            parts.append(f"failed={int(extra)}")
        if parts:
            lines.append("  totals: " + " ".join(parts))
    gauges = report.get("gauges") or {}
    if gauges:
        parts = [f"{k}={int(v)}" for k, v in sorted(gauges.items()) if v]
        if parts:
            lines.append("  gauges: " + " ".join(parts))
    for s in report.get("schedulers") or ():
        age = s.get("last_frame_age_s")
        lines.append(
            f"  scheduler {s['scheduler']:<24} {s['state']:<9} "
            f"frames={s.get('frames', 0)}"
            + (f" last={age:.0f}s ago" if age is not None else ""))
        for label in ("stragglers", "quarantined", "breached"):
            vals = s.get(label) or ()
            if vals:
                lines.append(f"    {label}: " + ", ".join(vals))
    stragglers = report.get("stragglers") or {}
    if stragglers:
        lines.append("  stragglers (host -> scheduler):")
        for host, sched in sorted(stragglers.items()):
            lines.append(f"    {host} -> {sched}")
    breached = report.get("breached") or {}
    if breached:
        lines.append("  slo breaches:")
        for name, scheds in sorted(breached.items()):
            lines.append(f"    {name}: " + ", ".join(scheds))
    ev = report.get("events") or {}
    if ev:
        lines.append(f"  events: recorded={ev.get('recorded_total', 0)} "
                     f"dropped={ev.get('dropped', 0)}")
    if report.get("restored_frames"):
        lines.append(f"  restored from spool: "
                     f"{report['restored_frames']} frame(s)")
    spool = report.get("spool")
    if spool:
        lines.append(f"  spool: {spool['bytes']}/{spool['max_bytes']} "
                     f"bytes")
    return "\n".join(lines) + "\n"
