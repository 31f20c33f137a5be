"""BASELINE config #5 (simulated): pod-wide fan-out at 64-1024 hosts.

The real north star — a 70B checkpoint to every host of a v5p-256 in
<60 s — needs a pod; this drives the SCHEDULER through that scale on one
machine: N simulated hosts with real TPU topology labels (16 hosts per
slice) register for one task, piece transfers are simulated with a fixed
per-piece latency, and the run measures what the control plane
contributes:

  - origin_fetches       back-to-source demotions (target ≈ 1)
  - intra_slice_frac     fraction of scheduled parent picks inside the
                         child's slice (ICI locality actually engaged)
  - max_loop_lag_ms      scheduler event-loop stall under the storm
  - schedule_p50/p99_ms  register → parents-assigned latency
  - rss_peak_mb          process peak RSS (the 1024-host memory bill)
  - *_after_gc           registry sizes after the TTL sweep — the
                         reference pins its GC constants
                         (scheduler/config/constants.go:77-88); ours must
                         demonstrably drain a pod-scale run

Usage: python benchmarks/pod_sim_bench.py [--hosts 1024] [--churn]
       [--churn-waves 3]
Reference yardstick: the evaluator's IDC/location affinity
(evaluator_base.go:41-45) becomes slice/pod ICI affinity here; the churn
test (tests/test_scheduler_churn.py) covers correctness, this drives
scale behavior and prints counts. ``--churn-waves N`` kills N
different slices at staggered times (sustained churn), each followed by
its own straggler wave into the killed slice.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import random
import resource as _resource
import statistics
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from dragonfly2_tpu.proto import reportcodec  # noqa: E402
from dragonfly2_tpu.scheduler.config import SchedulerConfig  # noqa: E402
from dragonfly2_tpu.scheduler.service import SchedulerService  # noqa: E402

N_PIECES = 16
PIECE_SIZE = 1 << 20
HOSTS_PER_SLICE = 16


def _rss_mb() -> float:
    with open("/proc/self/statm") as f:
        return int(f.read().split()[1]) * os.sysconf("SC_PAGE_SIZE") / (1 << 20)


class FakeStream:
    def __init__(self, open_body):
        self.open_body = open_body
        self.to_sched: asyncio.Queue = asyncio.Queue()
        self.to_peer: asyncio.Queue = asyncio.Queue()

    async def send(self, body):
        await self.to_peer.put(body)

    async def recv(self, timeout=None):
        return await self.to_sched.get()

    async def close(self):
        await self.to_sched.put(None)


async def _serve(svc, stream):
    try:
        await svc.announce_peer(stream, None)
    except Exception:
        pass


def _open_body(i: int) -> dict:
    slice_id = i // HOSTS_PER_SLICE
    return {
        "host": {"id": f"host-{i}", "hostname": f"w{i}", "ip": "10.0.0.1",
                 "port": 8000 + i, "upload_port": 40000 + i,
                 "tpu_slice": f"slice-{slice_id}",
                 "tpu_worker_index": i % HOSTS_PER_SLICE,
                 "idc": f"slice-{slice_id}"},
        "peer_id": f"peer-{i}",
        "task_id": "pod-task",
        "url": "http://origin/ckpt.safetensors",
    }


async def run_sim(n_hosts: int, piece_latency_s: float = 0.002,
                  arrival_window_s: "float | None" = None,
                  churn: bool = False, churn_waves: int = 1,
                  gc_ttl_s: float = 1.0, report_batch: int = 1,
                  restart: bool = False,
                  packed_wire: bool = False) -> dict:
    """``churn=True`` kills whole slices mid-fan-out (their peers' streams
    drop after a few pieces, no finish) and sends straggler waves into the
    SAME slices late — ``churn_waves`` slices die at staggered times, so
    the scheduler absorbs churn repeatedly, not once. Invariants: origin
    economy holds (no fresh back-source demotions — survivors hold the
    pieces), no straggler is handed a dead parent, ICI locality holds on
    the healthy slices, and after the run the TTL GC drains every
    registry."""
    if arrival_window_s is None:
        arrival_window_s = 1.0
    rng = random.Random(11)
    cfg = SchedulerConfig()
    cfg.scheduling.retry_interval = 0.05
    cfg.scheduling.no_source_patience = 1.0
    cfg.seed_peer_enabled = False
    snapshot_path = ""
    if restart:
        # ``restart=True`` kills the scheduler mid-sim: the service is
        # snapshot-flushed, abandoned, and a NEW service restores from
        # the durable snapshot while every live peer re-registers with
        # resume state — the crash-recovery acceptance drill at DES
        # scale. The snapshot must live in a real file so the fresh
        # service (a fresh sqlite connection) can read it.
        import tempfile

        fd, snapshot_path = tempfile.mkstemp(suffix=".snapdb")
        os.close(fd)
        cfg.ha.snapshot_db = snapshot_path
    # Short registry TTLs so the post-run sweep proves pod-scale state
    # actually drains (reference scheduler/config/constants.go:77-88) —
    # well above any single peer's in-run idle gap.
    cfg.gc.peer_ttl = cfg.gc.task_ttl = cfg.gc.host_ttl = max(
        gc_ttl_s, arrival_window_s + 60 * piece_latency_s)
    # The fleet observatory's per-event hooks run (the default); the
    # scheduler-side pod-lens/SLO machinery does not, and no peer ships a
    # flight digest.
    cfg.podlens.enabled = cfg.podlens.slo_enabled = False
    svc = SchedulerService(cfg)
    # Peers resolve the CURRENT scheduler through this box: the restart
    # swaps in the restored replacement service and bumps ``gen`` so
    # every live peer re-homes (the conductor announce-recovery path,
    # DES-modeled).
    svc_box: dict = {"svc": svc, "gen": 0}
    restart_info: dict = {
        "at": 0.0, "rebuild_done_at": 0.0, "reregistered": 0,
        "resume_answers": {}, "rebuilt_piece_mismatch": 0,
        "restored_peers": 0, "restored_tasks": 0,
    }

    n_slices = max(1, n_hosts // HOSTS_PER_SLICE)
    waves_n = min(churn_waves, max(1, n_slices - 2)) if churn else 0
    killed_slice_ids = list(range(1, 1 + waves_n))
    killed_slice_names = {f"slice-{k}" for k in killed_slice_ids}

    origin_fetches = 0
    sched_client_retries = 0
    schedule_lat: list[float] = []
    parent_picks = {"intra": 0, "cross": 0}
    healthy_picks = {"intra": 0, "cross": 0}
    ceiling_picks = {"intra": 0, "total": 0}
    finished: set[int] = set()
    max_lag = 0.0
    dead_peer_ids: set[str] = set()
    # Which service GENERATION processed each death: a handout of a peer
    # whose death THIS scheduler observed is a real bug; a snapshot-
    # restored ghost whose death only the pre-crash scheduler saw is
    # inherent snapshot staleness (children detect parent-gone and
    # reschedule) — counted separately, not as a violation.
    dead_gen: dict[str, int] = {}
    dead_by_slice: dict[int, int] = {k: 0 for k in killed_slice_ids}
    straggler_dead_picks = 0
    straggler_stale_ghost_picks = 0
    straggler_pick_count = 0
    rss_start = _rss_mb()

    lag_samples: list[float] = []
    # (monotonic stamp, observed elapsed, lag) per heartbeat tick — the
    # feed for the loop_lag SLO probe below (pkg/slo kind="probe":
    # wedged wall-seconds over observed wall-seconds in a window).
    slo_ticks: list[tuple] = []
    # Announce-plane ingest events: every message a peer puts on the
    # wire toward the scheduler (registers, piece reports, terminals).
    # cpu_s / events is the flat-per-event scaling metric the 16k run
    # is held to (<= 1.15x the 4k run's per-event cost).
    events = 0

    async def heartbeat():
        nonlocal max_lag
        loop = asyncio.get_running_loop()
        while True:
            t0 = loop.time()
            await asyncio.sleep(0.01)
            lag = loop.time() - t0 - 0.01
            max_lag = max(max_lag, lag)
            lag_samples.append(lag)
            slo_ticks.append((loop.time(), 0.01 + lag, lag))

    def loop_lag_probe(window: float, threshold: float):
        """pkg/slo probe: (wedged seconds, observed seconds) within the
        trailing window — heartbeat-fed, same contract as the runtime
        observatory's prof probe."""
        now = slo_ticks[-1][0] if slo_ticks else 0.0
        bad = total = 0.0
        for t, elapsed, lag in reversed(slo_ticks):
            if now - t > window:
                break
            total += elapsed
            if lag > threshold:
                bad += lag
        return bad, total

    async def put(stream, msg):
        nonlocal events
        events += 1
        await stream.to_sched.put(msg)

    def batch_wire(pending: list) -> dict:
        """The coalesced report message: the packed columnar form when
        ``packed_wire`` (what a conductor sends after negotiating
        ``packed_reports``), else the legacy dict list."""
        if packed_wire:
            packed = reportcodec.encode_reports(pending)
            if packed is not None:
                return {"type": "pieces_finished", "packed": packed}
        return {"type": "pieces_finished", "pieces": pending}

    async def peer(i: int, *, die_after: int = -1,
                   straggler_into: int = -1):
        nonlocal origin_fetches, sched_client_retries, \
            straggler_dead_picks, \
            straggler_stale_ghost_picks, straggler_pick_count
        my_slice = f"slice-{(i // HOSTS_PER_SLICE) % n_slices}"
        body = _open_body(i)
        if straggler_into >= 0:
            # Stragglers re-join a KILLED slice with fresh peer ids.
            body["peer_id"] = f"peer-straggler-{i}"
            body["host"]["id"] = f"host-straggler-{i}"
            body["host"]["tpu_slice"] = f"slice-{straggler_into}"
            body["host"]["idc"] = f"slice-{straggler_into}"
            my_slice = f"slice-{straggler_into}"
        stream = FakeStream(body)
        server = asyncio.ensure_future(_serve(svc_box["svc"], stream))
        my_gen = svc_box["gen"]
        killed_here = False
        base_peer_id = body["peer_id"]
        try:
            sched_attempt = 0
            while True:
                t_reg = time.perf_counter()
                await put(stream, {"type": "register"})
                msg = await asyncio.wait_for(stream.to_peer.get(),
                                             timeout=300)
                schedule_lat.append(time.perf_counter() - t_reg)
                kind = msg.get("type")
                if kind != "schedule_failed":
                    break
                # The dfget model: a schedule_failed peer is failed BY
                # DESIGN (retry budget burned while the pod warms up, or
                # the bounded back-source budget is full) and the CLIENT
                # retries the download with a fresh peer — the scheduler
                # never resurrects a failed FSM. Bounded and counted:
                # completion 1.0 still requires every retry to land.
                sched_attempt += 1
                if sched_attempt > 8:
                    raise AssertionError(
                        f"peer {i} schedule_failed {sched_attempt}x "
                        f"(reason={msg.get('reason')!r} slice={my_slice})")
                sched_client_retries += 1
                await stream.to_sched.put(None)
                await asyncio.wait_for(server, timeout=300)
                await asyncio.sleep(
                    rng.uniform(0.2, 0.6) * sched_attempt)
                body = dict(body)
                body["peer_id"] = f"{base_peer_id}-r{sched_attempt}"
                stream = FakeStream(body)
                server = asyncio.ensure_future(
                    _serve(svc_box["svc"], stream))
                my_gen = svc_box["gen"]
            if kind == "need_back_source":
                origin_fetches += 1
            elif kind == "normal_task":
                # Counterfactual ceiling: even a perfect intra-first
                # scheduler can only hand out as many intra-slice parents
                # as slice-mates EXIST at this instant — early arrivals in
                # the register storm have none. Recording min(picks,
                # mates_present) per handout turns intra_slice_frac into a
                # conversion rate against what the arrival pattern allows,
                # instead of an absolute number that silently blends
                # scheduling quality with arrival timing.
                parents_in_msg = msg.get("parents") or []
                npicks = len(parents_in_msg)
                intra_in_msg = sum(
                    1 for p in parents_in_msg
                    if (p.get("host") or {}).get("tpu_slice") == my_slice)
                task_obj = svc_box["svc"].tasks.load(body["task_id"])
                mates = 0
                if task_obj is not None:
                    for pid in task_obj.slice_index.get(my_slice, ()):
                        if pid == body["peer_id"]:
                            continue
                        q = task_obj.load_peer(pid)
                        if q is not None and q.fsm.current not in (
                                "failed", "leave"):
                            mates += 1
                # mates is read at response-receipt time; a picked mate
                # that failed in between would under-count the ceiling, so
                # the scheduler's own intra picks are the floor.
                ceiling_picks["intra"] += min(npicks,
                                              max(mates, intra_in_msg))
                ceiling_picks["total"] += npicks
                for p in parents_in_msg:
                    pslice = (p.get("host") or {}).get("tpu_slice", "")
                    key = "intra" if pslice == my_slice else "cross"
                    parent_picks[key] += 1
                    if my_slice not in killed_slice_names:
                        healthy_picks[key] += 1
                    if straggler_into >= 0:
                        straggler_pick_count += 1
                        if p.get("id") in dead_peer_ids:
                            if dead_gen.get(p.get("id")) == my_gen:
                                straggler_dead_picks += 1
                            else:
                                straggler_stale_ghost_picks += 1
            elif kind == "small_task":
                finished.add(i)
                await put(stream,
                          {"type": "download_finished",
                           "content_length": N_PIECES * PIECE_SIZE,
                           "piece_size": PIECE_SIZE,
                           "total_piece_count": N_PIECES})
                return
            else:
                raise AssertionError(
                    f"peer {i} got {kind} "
                    f"(reason={msg.get('reason')!r} slice={my_slice})")

            await put(stream, {
                "type": "download_started",
                "content_length": N_PIECES * PIECE_SIZE,
                "piece_size": PIECE_SIZE,
                "total_piece_count": N_PIECES})
            pending: list = []
            for n in range(N_PIECES):
                if restart and svc_box["gen"] != my_gen:
                    # The scheduler "crashed" under us: abandon the dead
                    # member's stream, connect to the restored service
                    # and re-register with FULL resume state — the DES
                    # model of the conductor's announce recovery. The
                    # answer must rebuild our landed set (zero re-
                    # downloads) and must never demote us to origin.
                    await stream.to_sched.put(None)
                    await asyncio.wait_for(server, timeout=300)
                    my_gen = svc_box["gen"]
                    stream = FakeStream(body)
                    server = asyncio.ensure_future(
                        _serve(svc_box["svc"], stream))
                    done_nums = list(range(n))
                    resume = {"piece_nums": done_nums,
                              "content_length": N_PIECES * PIECE_SIZE,
                              "piece_size": PIECE_SIZE,
                              "total_piece_count": N_PIECES}
                    if packed_wire and len(done_nums) >= 16:
                        # The negotiated bitmap form (same density gate
                        # as the conductor's _resume_state).
                        bitmap = reportcodec.nums_to_bitmap(done_nums)
                        if len(bitmap) <= 2 * len(done_nums):
                            resume["piece_bitmap"] = bitmap
                            resume["piece_nums"] = []
                    await put(stream, {"type": "register",
                                       "resume": resume})
                    ans = await asyncio.wait_for(stream.to_peer.get(),
                                                 timeout=300)
                    kind2 = ans.get("type")
                    ra = restart_info["resume_answers"]
                    ra[kind2] = ra.get(kind2, 0) + 1
                    restart_info["reregistered"] += 1
                    restart_info["rebuild_done_at"] = time.perf_counter()
                    q = svc_box["svc"].peers.load(body["peer_id"])
                    if q is None or not set(done_nums) <= q.finished_pieces:
                        restart_info["rebuilt_piece_mismatch"] += 1
                    # Landed pieces ride the resume bitset; buffered
                    # batch reports for them are redundant.
                    pending = []
                if n == die_after:
                    # Slice kill: the stream drops mid-download, no
                    # finish — the scheduler's stream-gone path must reap
                    # this peer from the DAG. Bookkeeping happens in the
                    # finally AFTER the server task drained, so gates
                    # (stragglers, the restart snapshot) only fire once
                    # the death has actually been PROCESSED.
                    killed_here = True
                    return
                await asyncio.sleep(piece_latency_s * rng.uniform(0.5, 1.5))
                wire_piece = {"piece_num": n,
                              "range_start": n * PIECE_SIZE,
                              "range_size": PIECE_SIZE,
                              "digest": "", "download_cost_ms": 2,
                              "dst_peer_id": ""}
                if report_batch <= 1:
                    # Classic wire: one report per piece.
                    await put(stream, {"type": "piece_finished",
                                       "piece": wire_piece})
                    continue
                # Coalesced wire (what real daemons send: the conductor
                # flushes report batches).
                pending.append(wire_piece)
                if len(pending) >= report_batch:
                    await put(stream, batch_wire(pending))
                    pending = []
            if pending:
                await put(stream, batch_wire(pending))
            finish_msg = {
                "type": "download_finished",
                "content_length": N_PIECES * PIECE_SIZE,
                "piece_size": PIECE_SIZE,
                "total_piece_count": N_PIECES}
            await put(stream, finish_msg)
            finished.add(i)
        finally:
            await stream.to_sched.put(None)
            await asyncio.wait_for(server, timeout=300)
            if killed_here:
                dead_peer_ids.add(body["peer_id"])
                dead_gen[body["peer_id"]] = my_gen
                dead_by_slice[i // HOSTS_PER_SLICE] = \
                    dead_by_slice.get(i // HOSTS_PER_SLICE, 0) + 1

    # Freeze whatever heap the hosting process already carries (a full
    # pytest run drags ~700 MB of prior-test objects): cyclic-GC passes
    # over that inherited heap otherwise dominate measured loop lag, and
    # this benchmark is about the SCHEDULER's lag, not the host process's
    # garbage. Unfrozen on exit.
    import gc

    gc.collect()
    gc.freeze()
    hb = asyncio.ensure_future(heartbeat())
    t0 = time.perf_counter()
    cpu0 = time.process_time()
    try:
        async def delayed(i):
            # Host 0 leads (the preheat/seed analog — config #5 preheats
            # seed peers before the pod storms in); the rest arrive after
            # its origin fetch has first pieces to serve.
            if i:
                await asyncio.sleep(0.25 + rng.uniform(0, arrival_window_s))
            in_killed = churn and (i // HOSTS_PER_SLICE) in killed_slice_ids
            await peer(i, die_after=rng.randint(2, N_PIECES // 2)
                       if in_killed else -1)

        async def restarter():
            """Kill the scheduler mid-sim: flush the durable snapshot,
            abandon the service, bring up a replacement restored from the
            snapshot, and bump the generation so every live peer re-homes
            with resume state. Gated on the first churn wave having been
            PROCESSED (or ~1/3 completions without churn) so the snapshot
            is post-kill consistent — the real flush cadence gives the
            same property via the stream-gone path running before the
            next periodic flush."""
            loop = asyncio.get_running_loop()
            deadline = loop.time() + 600
            if churn:
                while dead_by_slice.get(killed_slice_ids[0], 0) \
                        < HOSTS_PER_SLICE:
                    if loop.time() > deadline:
                        raise AssertionError("restart gate never opened")
                    await asyncio.sleep(0.02)
            else:
                while len(finished) < max(1, n_hosts // 3):
                    if loop.time() > deadline:
                        raise AssertionError("restart gate never opened")
                    await asyncio.sleep(0.02)
            old = svc_box["svc"]
            old.snapshot_flush()
            restart_info["at"] = time.perf_counter()
            replacement = SchedulerService(cfg)   # restores from snapshot
            restart_info["restored_peers"] = len(replacement.peers.all())
            restart_info["restored_tasks"] = len(replacement.tasks.all())
            svc_box["svc"] = replacement
            svc_box["gen"] += 1

        waves = [delayed(i) for i in range(n_hosts)]
        if restart:
            waves.append(restarter())
        for w, k in enumerate(killed_slice_ids):
            async def straggle(i, k=k, w=w):
                # Join AFTER this wave's kills have actually LANDED —
                # gating on the observed dead count, not wall time, keeps
                # the no-dead-parent invariant sharp under any host load
                # (a fixed sleep races the kills when the loop lags);
                # waves still stagger via their own kill completion.
                deadline = asyncio.get_running_loop().time() + 300
                while dead_by_slice.get(k, 0) < HOSTS_PER_SLICE:
                    if asyncio.get_running_loop().time() > deadline:
                        raise AssertionError(f"slice {k} kills never landed")
                    await asyncio.sleep(0.05)
                await asyncio.sleep(rng.uniform(0.05, 0.3))
                await peer(i, straggler_into=k)

            base = n_hosts + w * HOSTS_PER_SLICE
            waves += [straggle(base + j) for j in range(HOSTS_PER_SLICE)]
        await asyncio.wait_for(asyncio.gather(*waves), timeout=900)
    finally:
        hb.cancel()
        gc.unfreeze()
        if snapshot_path:
            try:
                os.unlink(snapshot_path)
            except OSError:
                pass
    svc = svc_box["svc"]   # the post-restart service owns the end state
    wall = time.perf_counter() - t0
    # Scheduler CPU for the storm itself — read BEFORE the TTL sweep and
    # the fleet-stats export below (resident_bytes is a deliberate deep
    # walk, not part of the storm).
    cpu_s = time.process_time() - cpu0
    rss_peak = _resource.getrusage(_resource.RUSAGE_SELF).ru_maxrss / 1024

    # loop_lag SLO verdict over the whole storm: the runtime probe specs
    # (pkg/slo RUNTIME_SLOS) fed by the heartbeat above. The 16k churn
    # acceptance pins ``breached == []`` — a scale regression that wedges
    # the loop mid-sim fails here even when the run still completes.
    from dragonfly2_tpu.pkg import slo as slolib

    slo_engine = slolib.SLOEngine(slolib.RUNTIME_SLOS,
                                  probes={"loop_lag": loop_lag_probe})
    slo_report = slo_engine.evaluate()
    slo_stats = {
        "breached": slo_report["breached"],
        "loop_lag_windows": [
            {"window_s": w["window_s"], "burn_rate": w["burn_rate"],
             "state": w["state"]}
            for s in slo_report["slos"] if s["name"] == "loop_lag"
            for w in s["windows"]],
    }

    # TTL sweep: a pod-scale run must not leave registry residue. All
    # peers are terminal (finished or stream-gone); once the TTL passes,
    # one gc() round drains peers → tasks (peerless+stale) → hosts.
    registry_sizes = {
        "peers": len(svc.peers.all()), "tasks": len(svc.tasks.all()),
        "hosts": len(svc.hosts.all()),
    }
    # With host-count-scaled arrival pacing the configured TTL can be
    # minutes; the sweep proves the stale-entry DRAIN logic, not the wall
    # wait, so age the registries by shrinking their TTLs to the floor
    # instead of sleeping out the arrival window again.
    sweep_ttl = max(gc_ttl_s, 1.0)
    svc.peers._ttl = svc.tasks._ttl = svc.hosts._ttl = sweep_ttl
    await asyncio.sleep(sweep_ttl + 0.3)
    svc.peers.gc()
    svc.tasks.gc()
    svc.hosts.gc()
    after_gc = {
        "peers_after_gc": len(svc.peers.all()),
        "tasks_after_gc": len(svc.tasks.all()),
        "hosts_after_gc": len(svc.hosts.all()),
    }

    total_picks = parent_picks["intra"] + parent_picks["cross"]
    healthy_total = healthy_picks["intra"] + healthy_picks["cross"]
    # With churn: each killed slice (HOSTS_PER_SLICE peers) is replaced by
    # an equal straggler wave — the target count is n_hosts either way.
    expected_finishers = n_hosts
    fleet_stats = None
    if svc.fleet is not None:
        win = svc.fleet.series.window(3600)
        fleet_stats = {
            "resident_bytes": svc.fleet.resident_bytes(),
            "decisions_total": svc.fleet.decisions.recorded_total,
            "pieces_landed": win["totals"]["pieces_landed"],
            "registers": win["totals"]["registers"],
            "scorecard_hosts": len(svc.fleet.scorecards._hosts),
        }
    return {
        "config": "pod-fanout-sim" + ("-churn" if churn else ""),
        "hosts": n_hosts,
        "slices": n_slices,
        "churn_waves": waves_n,
        "pieces": N_PIECES,
        "finished": len(finished),
        "expected_finishers": expected_finishers,
        "origin_fetches": origin_fetches,
        "schedule_client_retries": sched_client_retries,
        "intra_slice_frac": round(parent_picks["intra"] / total_picks, 3)
        if total_picks else 0.0,
        "healthy_intra_slice_frac": round(
            healthy_picks["intra"] / healthy_total, 3)
        if healthy_total else 0.0,
        "intra_slice_ceiling": round(
            ceiling_picks["intra"] / ceiling_picks["total"], 3)
        if ceiling_picks["total"] else 0.0,
        "intra_conversion": round(
            parent_picks["intra"] / ceiling_picks["intra"], 3)
        if ceiling_picks["intra"] else 0.0,
        "killed_peers": len(dead_peer_ids),
        "straggler_parent_picks": straggler_pick_count,
        "straggler_dead_parent_picks": straggler_dead_picks,
        "straggler_stale_ghost_picks": straggler_stale_ghost_picks,
        "parent_picks": total_picks,
        "schedule_p50_ms": round(
            statistics.median(schedule_lat) * 1000, 1),
        "schedule_p99_ms": round(
            sorted(schedule_lat)[int(len(schedule_lat) * 0.99)] * 1000, 1),
        "max_loop_lag_ms": round(max_lag * 1000, 1),
        # Median heartbeat lag: the run's AMBIENT contention level. External
        # CPU pressure (sibling tests, background benches) inflates every
        # sample; a scheduler-side stall inflates only the max. The checks
        # budget their bounds from this, so a loaded host widens them while
        # a genuine scheduler pathology still trips.
        "loop_lag_p50_ms": round(
            (statistics.median(lag_samples) if lag_samples else 0.0) * 1000,
            2),
        "arrival_window_s": round(arrival_window_s, 1),
        "wall_s": round(wall, 2),
        "cpu_s": round(cpu_s, 3),
        "events": events,
        "cpu_per_event_us": round(cpu_s / events * 1e6, 3) if events else 0.0,
        "report_batch": report_batch,
        "packed_wire": packed_wire,
        "report_backend": reportcodec.report_backend(),
        "slo": slo_stats,
        "rss_start_mb": round(rss_start, 1),
        "rss_peak_mb": round(rss_peak, 1),
        "registry_peak": registry_sizes,
        **after_gc,
        "host_cores": os.cpu_count(),
        "fleet": fleet_stats,
        "restart_enabled": restart,
        "restart": {
            "rebuild_s": round(max(0.0, restart_info["rebuild_done_at"]
                                   - restart_info["at"]), 3),
            "reregistered": restart_info["reregistered"],
            "resume_answers": restart_info["resume_answers"],
            "rebuilt_piece_mismatch": restart_info["rebuilt_piece_mismatch"],
            "restored_peers": restart_info["restored_peers"],
            "restored_tasks": restart_info["restored_tasks"],
        } if restart else None,
        "completion_rate": round(len(finished) / expected_finishers, 4)
        if expected_finishers else 1.0,
    }


def slowdown_factor(result: dict) -> float:
    """How oversubscribed the host was DURING this run, from the ambient
    heartbeat lag: a median lag of L ms on a 10 ms sleep means the loop got
    the CPU (10+L)/10 times slower than an idle host would give it. Latency
    bounds scale by this so full-suite/background contention widens them
    while a scheduler-side pathology (which inflates max/p99, not the
    ambient median) still trips."""
    return 1.0 + result.get("loop_lag_p50_ms", 0.0) / 10.0


def latency_budget_ms(result: dict, idle_budget_ms: float) -> float:
    """Schedule-latency bound budgeted from observed per-op cost rather
    than fixed wall-clock: the idle budget scaled by the run's measured
    contention, floored at 20x the run's own median schedule cost (a p99
    more than 20x p50 is a scheduler tail problem regardless of load)."""
    return max(idle_budget_ms * slowdown_factor(result),
               20.0 * result.get("schedule_p50_ms", 0.0))


def check_behavior(result: dict) -> None:
    """Load-independent invariants — these must ALWAYS hold, full-suite
    contention or not (verdict r05: split them from timing so a busy CI
    host can't convert real regressions into retry noise)."""
    assert result["finished"] == result["expected_finishers"], result
    # Origin economy at pod scale: ~one copy.
    assert result["origin_fetches"] <= 3, result
    # ICI locality: with 16 hosts/slice the random-candidate base rate for
    # an intra-slice pick is ~6%; the slice affinity term must pull the
    # scheduled fraction far above it.
    assert result["intra_slice_frac"] >= 0.3, result
    # TTL GC drains the whole run's registry state (reference
    # scheduler/config/constants.go:77-88 pins the same guarantees).
    assert result["peers_after_gc"] == 0, result
    assert result["tasks_after_gc"] == 0, result
    assert result["hosts_after_gc"] == 0, result


def check_timing(result: dict) -> None:
    """The scheduler's loop survived the storm without multi-second stalls.
    Budget from observation, not wall-clock luck: ambient contention
    (slowdown_factor) widens it, and so does the run's own median
    schedule cost — when the register storm takes ~p50 ms per answer on
    a slow host, a worst stall of a few p50s is the storm draining, not
    a pathology; a deadlock or O(n^2) stall still dwarfs both terms."""
    assert result["max_loop_lag_ms"] < max(
        500 * slowdown_factor(result),
        3 * result.get("schedule_p50_ms", 0.0)), result


def check(result: dict) -> None:
    """Assertions shared by the bench and the pytest wrapper."""
    check_behavior(result)
    check_timing(result)


def check_churn_behavior(result: dict) -> None:
    """Extra load-independent invariants for the slice-kill + straggler
    variant."""
    check_behavior(result)
    assert result["killed_peers"] == result["churn_waves"] * HOSTS_PER_SLICE, result
    # Stragglers must be scheduled (not demoted to fresh origin fetches)…
    assert result["straggler_parent_picks"] > 0, result
    # …and never onto a peer whose stream already dropped.
    assert result["straggler_dead_parent_picks"] == 0, result
    # Locality on the surviving slices must not degrade below the
    # no-churn bar.
    assert result["healthy_intra_slice_frac"] >= 0.3, result


def check_churn(result: dict) -> None:
    check_churn_behavior(result)
    check_timing(result)


def check_restart_behavior(result: dict) -> None:
    """Load-independent invariants for the mid-sim scheduler restart:
    completion despite the restart, every live peer re-registered onto
    the restored service, every resume answer was normal_task (a
    back-source demotion here would be the origin-storm bug this PR
    exists to prevent), and the restored service's view of each peer's
    landed set covered the peer's actual landed set (zero re-downloaded
    landed bytes — the scheduler can never reschedule a piece it knows
    is landed)."""
    assert result["restart_enabled"], "restart invariants need restart=True"
    r = result["restart"]
    assert result["completion_rate"] == 1.0, result
    assert r["reregistered"] > 0, r
    assert set(r["resume_answers"]) == {"normal_task"}, r
    assert r["rebuilt_piece_mismatch"] == 0, r
    assert r["restored_peers"] > 0, r
    assert r["rebuild_s"] >= 0, r


def check_scale_pair(result: dict, pair: dict,
                     max_ratio: float = 1.15) -> None:
    """Flat per-event ingest cost: the big run's cpu-per-announce-event
    stays within ``max_ratio`` of its paired smaller fresh run from the
    same process — superlinear registry/DAG work shows up here long
    before completion breaks. Plus: the loop_lag SLO never breached
    mid-sim (the storm may stall the loop briefly; a burn past the
    fast-window threshold means seconds-long wedges)."""
    assert result["completion_rate"] == 1.0, result
    assert result["slo"]["breached"] == [], result["slo"]
    r_big = result["cpu_per_event_us"]
    r_small = pair["cpu_per_event_us"]
    assert r_small > 0, pair
    assert r_big <= max_ratio * r_small, (
        f"per-event ingest cost not flat: {r_big:.3f}us at "
        f"{result['hosts']} hosts vs {r_small:.3f}us at "
        f"{pair['hosts']} hosts ({r_big / r_small:.2f}x > {max_ratio}x)")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--hosts", type=int, default=256)
    ap.add_argument("--churn", action="store_true",
                    help="kill slices mid-fan-out + late stragglers")
    ap.add_argument("--churn-waves", type=int, default=1,
                    help="how many slices die (sustained churn)")
    ap.add_argument("--restart", action="store_true",
                    help="kill + snapshot-restore the scheduler mid-sim "
                         "(crash-recovery drill)")
    ap.add_argument("--piece-latency", type=float, default=0.002)
    ap.add_argument("--arrival-window", type=float, default=None,
                    help="register-storm arrival spread in seconds "
                         "(default: scaled to ~80 arrivals/s)")
    ap.add_argument("--report-batch", type=int, default=1,
                    help="coalesce piece reports into batches of N "
                         "(1 = classic per-piece wire)")
    ap.add_argument("--packed-wire", action="store_true",
                    help="send coalesced reports in the packed columnar "
                         "form (proto/reportcodec) + resume bitmaps")
    args = ap.parse_args()

    def _arrival_window(n_hosts: int) -> float:
        # Offered-load pacing: a pod's hosts take tens of seconds to storm
        # back (boot + dfdaemon start jitter), and the DES must not
        # oversubscribe its own host either — 16384 arrivals inside one
        # wall-second on one core wedge the LOOP ITSELF, and every budget
        # in play (scheduler retry, loop-lag SLO) burns against wall time.
        # ~80 arrivals/s keeps per-host offered load constant across
        # scales, so the 4k/16k per-event pair compares like with like.
        return max(1.0, n_hosts / 80.0)

    window = (args.arrival_window if args.arrival_window is not None
              else _arrival_window(args.hosts))
    sim_kwargs = dict(churn=args.churn, churn_waves=args.churn_waves,
                      piece_latency_s=args.piece_latency,
                      arrival_window_s=window,
                      restart=args.restart, report_batch=args.report_batch,
                      packed_wire=args.packed_wire)
    result = asyncio.run(run_sim(args.hosts, **sim_kwargs))
    pair = None
    if args.hosts >= 16384:
        # The 16k acceptance is a PAIR: a fresh 4k run in this same
        # process (same interpreter state, same wire options) anchors
        # the per-event cost ratio — flat cost means the 16k storm pays
        # <= 1.15x per announce event.
        pair_kwargs = dict(sim_kwargs)
        if args.arrival_window is None:
            pair_kwargs["arrival_window_s"] = _arrival_window(4096)
        pair = asyncio.run(run_sim(4096, **pair_kwargs))
        result["pair_4k"] = {
            "hosts": pair["hosts"],
            "events": pair["events"],
            "cpu_s": pair["cpu_s"],
            "cpu_per_event_us": pair["cpu_per_event_us"],
            "completion_rate": pair["completion_rate"],
        }
        result["per_event_ratio_vs_4k"] = round(
            result["cpu_per_event_us"] / pair["cpu_per_event_us"], 3)
    # Numbers first, verdicts second: a failed gate must still leave the
    # full result on stdout for diagnosis.
    print(json.dumps(result))

    if args.restart:
        # Restart runs assert BEHAVIOR only: the in-process crash window
        # (synchronous snapshot restore + the whole fleet re-registering
        # at once) IS a loop stall by design — max_loop_lag measures the
        # deliberate outage, not a scheduler pathology.
        (check_churn_behavior if args.churn else check_behavior)(result)
        check_restart_behavior(result)
    else:
        (check_churn if args.churn else check)(result)
    if pair is not None:
        check_scale_pair(result, pair)
    return 0


if __name__ == "__main__":
    sys.exit(main())
