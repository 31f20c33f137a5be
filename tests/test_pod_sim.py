"""Pod-scale scheduler simulation (BASELINE config #5 at test scale).

96 simulated hosts across 6 slices with real topology labels drive one
task through the scheduler; asserts origin economy (~1 fetch), engaged
ICI locality (same-slice parent picks far above the random base rate),
schedule latency, and event-loop stall bounds. The simulation itself is
benchmarks/pod_sim_bench.py, kept as the model this file imports.

Behavioral invariants (origin fetches, dead-parent handouts, GC drain)
assert UNCONDITIONALLY — they are load-independent. Timing bounds
(p99/loop-lag) are recorded here and never asserted: this file shares its
host with five other workers, and the run's own ambient-contention
measurement (the median heartbeat lag) read a quiet host in runs whose
worst stall was a neighbor's (``max_loop_lag_ms`` 823.9 at a median of 1.04,
the driver's run of PR 44's tree). The script run alone (``python
benchmarks/pod_sim_bench.py``) asserts both.
"""

from __future__ import annotations

import asyncio

import sys

import pytest

from benchmarks.pod_sim_bench import (
    check_behavior,
    check_churn_behavior,
    check_restart_behavior,
    latency_budget_ms,
    run_sim,
)


def _record_timing(result: dict, idle_budget_ms: float) -> None:
    """The timing bounds' readings beside their budgets (visible in -rP /
    failure triage): what the script asserts when it runs alone."""
    print(f"pod-sim timing recorded, not asserted "
          f"({result.get('loop_lag_p50_ms', 0.0):.1f}ms ambient lag): "
          f"p99={result.get('schedule_p99_ms')}ms of "
          f"{latency_budget_ms(result, idle_budget_ms):.0f}ms "
          f"max_lag={result.get('max_loop_lag_ms')}ms",
          file=sys.stderr)


def test_pod_sim_96_hosts(run_async):
    async def body():
        result = await run_sim(96, piece_latency_s=0.001,
                               arrival_window_s=0.5)
        check_behavior(result)
        _record_timing(result, 1000)

    run_async(body(), timeout=240)


def test_pod_sim_1024_hosts_sustained_churn(run_async):
    """Pod scale (1024 hosts / 64 slices) under SUSTAINED churn: three
    different slices die at staggered times, each replaced by a straggler
    wave. Origin stays one copy, no straggler gets a dead parent, healthy
    slices keep ICI locality, and the TTL sweep drains all ~1100
    peers/hosts afterwards (VERDICT r04 item 5; measured p50 1.2 ms /
    p99 6.2 ms / lag 7.8 ms / RSS +5 MiB on the 1-core CI host). Loop-lag
    and p99 are recorded, not asserted: the suite's one failure on the
    driver's machine three PRs in a row was the loop-lag bound
    (``check_timing``) tripping on sibling-test CPU spikes."""

    async def body():
        result = await run_sim(1024, piece_latency_s=0.001,
                               arrival_window_s=0.5, churn=True,
                               churn_waves=3)
        check_churn_behavior(result)
        _record_timing(result, 2000)

    run_async(body(), timeout=360)


def test_pod_sim_churn_with_scheduler_restart(run_async):
    """Churn + a mid-sim scheduler crash/restore (ISSUE 9): the service
    is snapshot-flushed and replaced mid-fan-out; every live peer
    re-registers with resume state. Completion holds, every resume
    answer is normal_task (no origin storm), the restored service's view
    of each peer's landed set covers reality (zero re-downloaded landed
    bytes), and origin economy + GC drain still hold."""

    async def body():
        result = await run_sim(96, piece_latency_s=0.002,
                               arrival_window_s=0.5, churn=True,
                               restart=True)
        check_churn_behavior(result)
        check_restart_behavior(result)
        # No timing asserts on restart runs: the crash window (restore +
        # whole-fleet re-register) is a deliberate stall, not a
        # pathology — behavioral invariants are the contract here.

    run_async(body(), timeout=240)


@pytest.mark.slow
def test_pod_sim_4096_hosts_churn_restart(run_async):
    """The 4k acceptance sim: 4096 hosts / 256 slices, three slices die at
    staggered times with straggler waves, and the scheduler restarts
    mid-sim. The 1024-host variant's load-independent invariants are
    promoted wholesale (satellite 5) plus the restart invariants; timing
    is recorded, never asserted (the crash window is a deliberate
    stall)."""

    async def body():
        result = await run_sim(4096, piece_latency_s=0.001,
                               arrival_window_s=1.0, churn=True,
                               churn_waves=3, restart=True)
        check_churn_behavior(result)
        check_restart_behavior(result)
        # Timing recorded, never asserted: the restart window is a
        # deliberate stall (see the bench's main()).

    run_async(body(), timeout=900)


def test_pod_sim_churn_slice_kill_and_stragglers(run_async):
    """Kill a whole slice mid-fan-out; a straggler wave re-joins that
    slice late. Origin stays ~one copy, no straggler is handed a dead
    parent, and surviving slices keep their ICI locality."""

    async def body():
        result = await run_sim(96, piece_latency_s=0.001,
                               arrival_window_s=0.5, churn=True)
        check_churn_behavior(result)
        _record_timing(result, 1000)

    run_async(body(), timeout=240)
