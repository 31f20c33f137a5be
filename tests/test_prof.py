"""Runtime observatory (pkg/prof): sampler attribution, loop-lag probe,
GC observatory, the loop_lag SLO probe, /debug/prof* endpoints, the
thread-naming hygiene guard — and the acceptance e2e: seeded CPU burn +
forced GC churn + a wedged loop in a real daemon mid-broadcast must be
attributed BY NAME at /debug/prof, recorded in the lag histogram,
breached at /debug/slo, and stamped into the task's flight autopsy as
typed events.
"""

from __future__ import annotations

import ast
import asyncio
import gc
import glob
import gzip
import json
import math
import os
import threading
import time

import pytest

from dragonfly2_tpu.pkg import flight
from dragonfly2_tpu.pkg import prof as proflib
from dragonfly2_tpu.pkg.prof import (
    GCObservatory,
    LoopLagProbe,
    ProfConfig,
    RuntimeObservatory,
    StackSampler,
    proc_stats,
)

PKG_ROOT = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "dragonfly2_tpu")


# --------------------------------------------------------------------- #
# Stack sampler: attribution, bounds, folded rendering
# --------------------------------------------------------------------- #

class TestStackSampler:
    def test_attributes_samples_to_thread_names(self):
        """A named CPU-burn thread shows up under ITS name with its hot
        frame carrying the self-time."""
        # Self-exclusion is only observable when OURS is the sole
        # sampler: another process-wide observatory's thread shares the
        # name and would legitimately be sampled by this one.
        assert proflib.observatory() is None, \
            "another test leaked an installed observatory"
        smp = StackSampler(hz=200)
        stop = threading.Event()

        def burn():
            while not stop.is_set():
                math.sqrt(12345.6789)

        t = threading.Thread(target=burn, daemon=True, name="df-ut-burn")
        t.start()
        smp.start()
        try:
            deadline = time.monotonic() + 5.0
            while time.monotonic() < deadline:
                rep = smp.report()
                if rep["threads"].get("df-ut-burn", {}).get("samples", 0) \
                        >= 5:
                    break
                time.sleep(0.02)
        finally:
            smp.stop()
            stop.set()
            t.join(timeout=5)
        rep = smp.report(topn=5)
        assert rep["samples"] > 0
        burn_t = rep["threads"]["df-ut-burn"]
        assert burn_t["samples"] >= 5
        frames = [f["frame"] for f in burn_t["top_self"]]
        assert any("burn" in f for f in frames), frames
        # Self-time fractions are normalized per thread.
        assert all(0 <= f["frac"] <= 1 for f in burn_t["top_self"])
        # The sampler never samples itself.
        assert "df-prof-sampler" not in rep["threads"]

    @staticmethod
    def _park_deep(depth: int):
        """A df- named thread parked ``depth`` frames deep on an Event —
        a stable stack the main thread can sample deterministically
        (``_sample_once`` skips the CALLING thread, so sampling from the
        test itself sees only other threads)."""
        ready, release = threading.Event(), threading.Event()

        def recurse(n):
            if n == 0:
                ready.set()
                release.wait(timeout=30)
                return
            recurse(n - 1)

        t = threading.Thread(target=recurse, args=(depth,), daemon=True,
                             name="df-ut-parked")
        t.start()
        assert ready.wait(timeout=10)
        return t, release

    def test_trie_node_cap_degrades_to_truncation_counter(self):
        """Past max_nodes the trie stops growing and counts truncations
        instead — the flight-ring discipline (bounded memory, visible
        degradation)."""
        smp = StackSampler(hz=1, max_nodes=4, max_depth=48)
        t, release = self._park_deep(30)
        try:
            with smp._lock:
                smp._sample_once()
        finally:
            release.set()
            t.join(timeout=10)
        assert smp.nodes <= 4
        assert smp.truncated >= 1
        rep = smp.report()
        assert rep["max_nodes"] == 4
        assert rep["truncated"] == smp.truncated

    def test_folded_output_is_collapse_format(self):
        smp = StackSampler(hz=1)
        t, release = self._park_deep(3)
        try:
            with smp._lock:
                smp._sample_once()
        finally:
            release.set()
            t.join(timeout=10)
        folded = smp.folded()
        assert folded
        for line in folded.strip().splitlines():
            stack, count = line.rsplit(" ", 1)
            assert int(count) > 0
            assert stack.split(";")[0]          # leading thread name
        assert any(line.startswith("df-ut-parked;")
                   for line in folded.splitlines())
        # Bounded rendering: max_lines caps the emission.
        assert len(smp.folded(max_lines=1).strip().splitlines()) <= 1

    def test_steady_state_sample_interns_repeated_stacks(self):
        """Two passes over the same parked stack: the second pass must
        intern the whole path (the parked thread adds zero new nodes)."""
        smp = StackSampler(hz=1)
        t, release = self._park_deep(5)
        try:
            with smp._lock:
                smp._sample_once()
            before = smp.nodes
            assert before > 0
            with smp._lock:
                smp._sample_once()
            # The parked thread's stack is frame-for-frame identical;
            # other live threads may have moved, so allow tiny growth.
            assert smp.nodes <= before + 4
        finally:
            release.set()
            t.join(timeout=10)


# --------------------------------------------------------------------- #
# The loop's account: what ran it, what held it and who; the holds' ring,
# histogram and wedged-seconds SLO feed
# --------------------------------------------------------------------- #

def _acct_events(rec, name, code):
    return [e for e in rec.get(f"runtime:loop:{name}").events()
            if e[1] == code]


def _fields(note: str) -> dict:
    return dict(part.split("=", 1) for part in note.split() if "=" in part)


class TestLoopLagProbe:
    def _probe(self, **kw) -> LoopLagProbe:
        obs = RuntimeObservatory(ProfConfig(enabled=False))
        kw.setdefault("slow_s", 0.25)
        return LoopLagProbe(obs, "ut", **kw)

    def test_note_lag_feeds_ring_histogram_and_max(self):
        p = self._probe()
        for lag in (0.001, 0.03, 0.3):
            p.note_lag(lag)
        s = p.summary()
        assert s["ticks"] == 3
        assert s["max_lag_s"] == pytest.approx(0.3)
        assert s["slow_ticks"] == 1
        assert sum(s["histogram"]["counts"]) == 3
        assert len(s["histogram"]["counts"]) == \
            len(s["histogram"]["edges_s"]) + 1
        # A wait is booked from 1 ms up; from 20 ms up it is a hold, and
        # only a hold is a row of the ring behind the SLO.
        assert p._n == 2
        assert [h["seconds"] for h in s["holds"]] == [0.3, 0.03]

    def test_wedged_seconds_counts_wall_time_not_ticks(self):
        """A single 1.5 s wedge among hundreds of short turns must
        dominate the probe output — wedged TIME over observed TIME, so
        healthy turns cannot dilute a stall (the reason this SLI is a
        probe, not a completion ratio)."""
        p = self._probe()
        p.started_mono = time.monotonic() - 5.0     # ran ~5 s already
        for _ in range(500):
            p.note_lag(0.001)
        p.note_lag(1.5)
        bad, total = p.wedged_seconds(window=3600.0, threshold=0.25)
        assert bad == pytest.approx(1.5, abs=0.01)
        assert total == pytest.approx(5.0, abs=0.5)
        # With a 0.99 objective the burn is bad/total/0.01 — a 1.5 s
        # wedge breaches any observation window under 25 s.
        assert bad / max(total, 1e-9) / 0.01 > 14.4

    def test_wedged_seconds_respects_window_cutoff(self):
        p = self._probe()
        now = time.monotonic()
        p.started_mono = now - 100.0
        p._ring[0] = (now - 50.0, 2.0)      # outside the 10 s window
        p._ring[1] = (now - 2.0, 1.0)       # inside
        p._n = 2
        bad, total = p.wedged_seconds(window=10.0, threshold=0.25, now=now)
        assert bad == pytest.approx(1.0)
        assert total == pytest.approx(10.0)

    def test_armed_probe_measures_a_real_wedge(self, run_async):
        """The account needs no tick to come round: the turn that held the
        loop is booked as it ends, one wait of its own length."""
        async def body():
            obs = RuntimeObservatory(ProfConfig(
                enabled=False, lag_slow_s=0.15))
            p = obs.arm_loop("ut-wedge")
            try:
                await asyncio.sleep(0.02)
                time.sleep(0.3)             # wedge the loop
                await asyncio.sleep(0)      # the turn ends: it is booked
            finally:
                p.disarm()
            s = p.summary()
            assert s["max_lag_s"] >= 0.3, s
            assert s["slow_ticks"] >= 1, s
            assert s["busy_s"] >= 0.3, s
            # A role without a recorder keeps the account, stamps nothing.
            assert obs.loop_ring("ut-wedge") is None
            assert s["holds"][0]["note"].startswith("held n=1 "), s

        run_async(body(), timeout=30)

    def test_slow_tick_stamps_running_flights(self):
        rec = flight.FlightRecorder(max_tasks=8)
        rec.task("t-run")
        rec.task("t-done")
        rec.finish_task("t-done", "done")
        obs = RuntimeObservatory(ProfConfig(enabled=False), recorder=rec)
        p = LoopLagProbe(obs, "ut", slow_s=0.25)
        p.note_lag(0.8)
        p.note_lag(0.01)                    # a wait, no hold: no event
        running = rec.get("t-run")
        evs = [e for e in running.events() if e[1] == flight.EV_LOOP_LAG]
        assert len(evs) == 1
        assert evs[0][3] == pytest.approx(0.8)
        done = rec.get("t-done")
        assert not [e for e in done.events()
                    if e[1] == flight.EV_LOOP_LAG]
        # The same hold, once, on the loop's own ring.
        own = _acct_events(rec, "ut", flight.EV_LOOP_LAG)
        assert [e[3] for e in own] == [pytest.approx(0.8)]

    def test_a_hold_names_its_holder(self, run_async):
        """A ``time.sleep`` inside a coroutine is ONE ``held`` hold as long
        as the sleep, of one handle, off the core, and the sampler's pass
        that fell inside it (driven by hand) names the coroutine."""
        rec = flight.FlightRecorder(max_tasks=8)
        obs = RuntimeObservatory(ProfConfig(enabled=False), recorder=rec)
        rec.task("t-run")
        entered = threading.Event()
        sampled: list = []

        def one_pass():
            assert entered.wait(timeout=30)
            with obs.sampler._lock:
                obs.sampler._sample_once()
            sampled.append(True)

        async def the_holder():
            entered.set()
            while not sampled:              # the pass falls inside the turn
                time.sleep(0.001)
            time.sleep(0.05)

        async def body():
            sampler = threading.Thread(target=one_pass, daemon=True,
                                       name="df-ut-one-pass")
            sampler.start()
            p = obs.arm_loop("ut-hold")
            try:
                await asyncio.sleep(0.02)   # woken by ONE due timer
                await the_holder()
                await asyncio.sleep(0)
            finally:
                p.disarm()
                sampler.join(timeout=30)
            assert not sampler.is_alive()
            return p

        p = run_async(body(), timeout=60)
        mine = [e for e in _acct_events(rec, "ut-hold", flight.EV_LOOP_LAG)
                if "the_holder" in e[4]]
        assert len(mine) == 1, _acct_events(rec, "ut-hold",
                                            flight.EV_LOOP_LAG)
        _t, _code, cpu_ms, seconds, note = mine[0]
        assert seconds >= 0.05
        assert note.startswith("held n=1 gc=")
        who = _fields(note)["who"].split(":")
        assert who[:2] == ["test_prof.py", "the_holder"] and int(who[2]) > 0
        assert cpu_ms / 1000.0 < seconds / 2     # it slept: off the core
        # The running task's flight names the same hold, and the probe's
        # longest holds list it.
        stamped = [e for e in rec.get("t-run").events()
                   if e[1] == flight.EV_LOOP_LAG and e[4] == note]
        assert len(stamped) == 1 and stamped[0][3] == seconds
        assert note in [h["note"] for h in p.summary()["holds"]]
        # A hold ends a slice: the slice that holds it is at least as long.
        slices = _acct_events(rec, "ut-hold", flight.EV_LOOP_ACCT)
        assert any(e[3] >= seconds * 1000.0 and e[0] == mine[0][0]
                   for e in slices)

    def test_busy_and_idle_are_the_wall_time_since_arm(self, run_async):
        async def body():
            obs = RuntimeObservatory(ProfConfig(enabled=False))
            before_arm = time.perf_counter_ns()
            p = obs.arm_loop("ut-wall")
            after_arm = time.perf_counter_ns()
            for _ in range(50):
                await asyncio.sleep(0)
            await asyncio.sleep(0.03)
            time.sleep(0.01)
            live = p.summary()              # asked from inside a turn
            before_disarm = time.perf_counter_ns()
            p.disarm()
            after_disarm = time.perf_counter_ns()
            return (p, p.summary(), live, before_disarm - after_arm,
                    after_disarm - before_arm)

        p, s, live, inner_ns, outer_ns = run_async(body(), timeout=30)
        wall_ns = (s["busy_s"] + s["idle_s"]) * 1e9
        assert inner_ns - 1 <= wall_ns <= outer_ns + 1
        assert s["idle_s"] >= 0.029 and s["busy_s"] >= 0.01
        assert 0 < s["cpu_s"] <= s["busy_s"]
        assert s["iterations"] >= 51 and s["handles"] >= 51
        # The live reading is of the same account, a moment earlier.
        assert live["busy_s"] <= s["busy_s"]
        assert live["busy_s"] + live["idle_s"] <= wall_ns / 1e9
        # Once disarmed the account stands still.
        assert p.summary() == s

    def test_slices_sum_to_the_cumulative_busy_time(self, run_async):
        rec = flight.FlightRecorder(max_tasks=8)
        obs = RuntimeObservatory(ProfConfig(enabled=False), recorder=rec)
        busy_c = proflib.LOOP_BUSY_SECONDS.labels("ut-slices")
        busy_before = busy_c._value.get()

        async def body():
            p = obs.arm_loop("ut-slices")
            for _ in range(8):
                time.sleep(0.003)           # turns of 3 ms: 5 ms a slice
                await asyncio.sleep(0)
            await asyncio.sleep(0.01)
            p.disarm()
            return p

        p = run_async(body(), timeout=30)
        slices = _acct_events(rec, "ut-slices", flight.EV_LOOP_ACCT)
        assert len(slices) >= 4             # 24 ms of turns, 5 ms a slice
        assert sum(e[3] for e in slices) == pytest.approx(
            p.busy_ns / 1e6, rel=1e-9)
        assert p.busy_ns >= 24_000_000
        assert sum(e[2] for e in slices) <= p.cpu_ns // 1000
        notes = [_fields(e[4]) for e in slices]
        assert sum(int(f["it"]) for f in notes) == p.iterations
        assert sum(int(f["n"]) for f in notes) == p.handles
        assert sum(float(f["late"]) for f in notes) == pytest.approx(
            p.late_ns / 1e6, abs=0.001 * len(notes))
        assert busy_c._value.get() - busy_before == pytest.approx(
            p.busy_ns / 1e9)

    def test_a_collection_on_the_loops_thread_shows_in_gc(self, run_async):
        rec = flight.FlightRecorder(max_tasks=8)
        obs = RuntimeObservatory(ProfConfig(enabled=False), recorder=rec)

        async def body():
            obs.gc.arm()
            p = obs.arm_loop("ut-gc")
            try:
                await asyncio.sleep(0)
                junk = []
                for _ in range(1000):
                    cycle = [junk]
                    cycle.append(cycle)
                    junk.append(cycle)
                del junk, cycle
                gc.collect()
                await asyncio.sleep(0)
            finally:
                p.disarm()
                obs.gc.disarm()
            return p

        p = run_async(body(), timeout=30)
        assert p.gc_ns > 0
        assert p.gc_ns <= p.busy_ns         # it ran inside the turns
        assert p.summary()["gc_s"] == p.gc_ns / 1e9
        slices = _acct_events(rec, "ut-gc", flight.EV_LOOP_ACCT)
        assert sum(float(_fields(e[4])["gc"]) for e in slices) > 0

    def test_a_late_wake_is_a_hold_of_its_own_kind(self, run_async):
        """A due timer that waits while the loop still sits in ``select``
        (here: a selector that comes back 30 ms late) is ``late``, not
        busy: the loop was due and did not run."""
        rec = flight.FlightRecorder(max_tasks=8)
        obs = RuntimeObservatory(ProfConfig(enabled=False), recorder=rec)

        async def body():
            selector = asyncio.get_running_loop()._selector
            real = selector.select

            def oversleeps(timeout=None):
                events = real(timeout)
                if timeout:
                    time.sleep(0.03)
                return events

            selector.select = oversleeps
            try:
                p = obs.arm_loop("ut-late")
                await asyncio.sleep(0.01)
                p.disarm()
                # An attribute that was there before arm() is there again.
                assert vars(selector)["select"] is oversleeps
            finally:
                del selector.select
            return p

        p = run_async(body(), timeout=30)
        assert p.late_ns >= 30_000_000
        late = [e for e in _acct_events(rec, "ut-late", flight.EV_LOOP_LAG)
                if e[4] == "late"]
        assert len(late) >= 1 and late[0][3] >= 0.03
        assert p.busy_ns < p.late_ns        # nobody held the loop

    def test_an_account_that_raises_takes_itself_off(self, run_async):
        """Whatever the account does wrong it does once: no exception
        reaches the loop, no line a turn reaches the log, ``select`` is the
        selector's own again and the loop turns on."""
        async def body():
            obs = RuntimeObservatory(ProfConfig(enabled=False))
            selector = asyncio.get_running_loop()._selector
            p = obs.arm_loop("ut-raises")
            calls = []

            def broken(end_ns):
                calls.append(end_ns)
                raise ValueError("a negative increment, say")

            p._slice = broken
            time.sleep(0.006)               # a turn that fills a slice
            await asyncio.sleep(0)
            assert len(calls) == 1
            assert "select" not in vars(selector)
            assert threading.get_ident() not in obs.sampler.loops
            time.sleep(0.006)
            await asyncio.sleep(0.001)
            assert len(calls) == 1
            p.disarm()                      # nothing left to do, and no raise

        run_async(body(), timeout=30)

    def test_disarm_leaves_select_as_it_found_it(self, run_async):
        async def body():
            obs = RuntimeObservatory(ProfConfig(enabled=False))
            selector = asyncio.get_running_loop()._selector
            assert "select" not in vars(selector)
            p = obs.arm_loop("ut-arm")
            wrapper = vars(selector)["select"]
            assert p.arm() is p             # armed: a second arm is none
            assert vars(selector)["select"] is wrapper
            assert wrapper.__closure__ is not None
            assert obs.sampler.loops[threading.get_ident()] is p
            # Re-arming the NAME replaces the probe and does not nest: the
            # new wrapper's inner select is the selector's own method.
            q = obs.arm_loop("ut-arm")
            assert q is not p and q._inner.__self__ is selector
            assert obs.sampler.loops[threading.get_ident()] is q
            await asyncio.sleep(0)
            q.disarm()
            assert "select" not in vars(selector)
            assert threading.get_ident() not in obs.sampler.loops
            q.disarm()                      # idempotent
            await asyncio.sleep(0.001)      # the loop turns on, unwrapped
            iterations = q.summary()["iterations"]
            await asyncio.sleep(0.001)
            assert q.summary()["iterations"] == iterations

        run_async(body(), timeout=30)


# --------------------------------------------------------------------- #
# GC observatory
# --------------------------------------------------------------------- #

class TestGCObservatory:
    def test_counts_collections_per_generation(self):
        obs = RuntimeObservatory(ProfConfig(enabled=False))
        g = obs.gc
        g.arm()
        try:
            gc.collect(0)
            gc.collect(2)
        finally:
            g.disarm()
        s = g.summary()
        assert s["collections"][0] >= 1
        assert s["collections"][2] >= 1
        assert s["max_pause_s"] >= 0
        assert len(s["tracked"]) == 3

    def test_slow_pause_stamps_running_flights(self):
        rec = flight.FlightRecorder(max_tasks=8)
        rec.task("t-gc")
        obs = RuntimeObservatory(ProfConfig(enabled=False, gc_slow_s=0.0),
                                 recorder=rec)
        g = obs.gc
        g.arm()
        try:
            gc.collect()        # any pause >= 0.0 counts as slow
        finally:
            g.disarm()
        assert g.slow_pauses >= 1
        evs = [e for e in rec.get("t-gc").events()
               if e[1] == flight.EV_GC_PAUSE]
        assert evs, "slow GC pause not stamped into the running flight"

    def test_disarm_removes_callback(self):
        g = GCObservatory(RuntimeObservatory(ProfConfig(enabled=False)))
        g.arm()
        assert g._cb in gc.callbacks
        g.disarm()
        assert g._cb not in gc.callbacks
        g.disarm()                          # idempotent


# --------------------------------------------------------------------- #
# proc gauges
# --------------------------------------------------------------------- #

def test_proc_stats_reads_linux_gauges():
    s = proc_stats()
    assert s["threads"] >= 1
    if os.path.exists("/proc/self/statm"):
        assert s["rss_bytes"] > 0
        assert s["open_fds"] > 0
        assert s["voluntary_ctx_switches"] > 0


# --------------------------------------------------------------------- #
# install()/release(): the refcounted process singleton
# --------------------------------------------------------------------- #

class TestInstallRelease:
    def test_refcounted_singleton(self):
        assert proflib.observatory() is None, \
            "another test leaked an installed observatory"
        a = proflib.install(ProfConfig(hz=50))
        b = proflib.install(ProfConfig(hz=7))   # second cfg ignored
        try:
            assert a is b
            assert proflib.observatory() is a
            assert a.cfg.hz == 50
            # One sampler thread, not two.
            names = [t.name for t in threading.enumerate()]
            assert names.count("df-prof-sampler") == 1
        finally:
            proflib.release(b)
            assert proflib.observatory() is a   # still one ref held
            proflib.release(a)
        assert proflib.observatory() is None
        names = [t.name for t in threading.enumerate()]
        assert "df-prof-sampler" not in names

    def test_release_of_private_observatory_stops_it(self):
        obs = RuntimeObservatory(ProfConfig())
        obs.start()
        proflib.release(obs)                    # not the singleton
        assert obs.sampler._thread is None


# --------------------------------------------------------------------- #
# loop_lag SLO: the probe kind end to end
# --------------------------------------------------------------------- #

class TestLoopLagSLO:
    def test_probe_kind_breaches_on_wedged_time(self):
        from dragonfly2_tpu.pkg import slo as slolib

        obs = RuntimeObservatory(ProfConfig(enabled=False))
        p = LoopLagProbe(obs, "ut", slow_s=0.25)
        obs.probes["ut"] = p
        p.started_mono = time.monotonic() - 5.0
        p.note_lag(1.5)                     # 1.5 s wedge in ~5 s observed
        eng = slolib.SLOEngine(specs=slolib.RUNTIME_SLOS,
                               probes=obs.slo_probes())
        rep = eng.evaluate()
        ll = [s for s in rep["slos"] if s["name"] == "loop_lag"][0]
        assert ll["kind"] == "probe"
        assert ll["state"] == "breach", ll
        assert "loop_lag" in rep["breached"]
        fast = ll["windows"][0]
        assert fast["burn_rate"] > fast["burn_threshold"]

    def test_unfed_probe_reports_no_data(self):
        from dragonfly2_tpu.pkg import slo as slolib

        eng = slolib.SLOEngine(specs=slolib.RUNTIME_SLOS)
        rep = eng.evaluate()
        ll = [s for s in rep["slos"] if s["name"] == "loop_lag"][0]
        assert ll["state"] == "no_data"
        assert all(w["state"] == "no_data" for w in ll["windows"])

    def test_failing_probe_degrades_to_no_data(self):
        from dragonfly2_tpu.pkg import slo as slolib

        def boom(window, threshold):
            raise RuntimeError("probe exploded")

        eng = slolib.SLOEngine(specs=slolib.RUNTIME_SLOS,
                               probes={"loop_lag": boom})
        rep = eng.evaluate()
        ll = [s for s in rep["slos"] if s["name"] == "loop_lag"][0]
        assert ll["state"] == "no_data"

    def test_default_slos_include_loop_lag(self):
        from dragonfly2_tpu.pkg import slo as slolib

        names = [s.name for s in slolib.DEFAULT_SLOS]
        assert "loop_lag" in names
        assert all(s.kind == "probe" for s in slolib.RUNTIME_SLOS)


# --------------------------------------------------------------------- #
# /debug/prof* endpoints
# --------------------------------------------------------------------- #

class TestProfEndpoints:
    def test_endpoints_serve_armed_observatory(self, run_async):
        import aiohttp

        from dragonfly2_tpu.pkg.metrics_server import MetricsServer

        async def body():
            obs = RuntimeObservatory(ProfConfig(hz=100))
            obs.start()
            probe = obs.arm_loop("ut-endpoint")
            srv = MetricsServer(prof=obs)
            port = await srv.serve("127.0.0.1", 0)
            base = f"http://127.0.0.1:{port}"
            try:
                await asyncio.sleep(0.1)    # a few sampler passes
                async with aiohttp.ClientSession() as sess:
                    async with sess.get(base + "/debug/prof?topn=3") as r:
                        assert r.status == 200
                        rep = await r.json()
                    assert rep["samples"] >= 1
                    assert rep["hz"] == 100
                    for t in rep["threads"].values():
                        assert len(t["top_self"]) <= 3
                    async with sess.get(
                            base + "/debug/prof/runtime") as r:
                        assert r.status == 200
                        rt = await r.json()
                    assert rt["proc"]["threads"] >= 2
                    assert rt["loops"][0]["name"] == "ut-endpoint"
                    async with sess.get(
                            base + "/debug/prof/flame?format=folded") as r:
                        assert r.status == 200
                        assert "json" not in r.headers["Content-Type"]
                        text = await r.text()
                    assert text.strip(), "no folded stacks"
                    # Only the folded collapse format exists.
                    async with sess.get(
                            base + "/debug/prof/flame?format=svg") as r:
                        assert r.status == 400
                    # The runtime_* gauges refreshed on the scrape above.
                    async with sess.get(base + "/metrics") as r:
                        metrics_text = await r.text()
                    assert "dragonfly_tpu_runtime_rss_bytes" in metrics_text
                    assert ("dragonfly_tpu_runtime_profiler_samples_total"
                            in metrics_text)
            finally:
                probe.disarm()
                await srv.close()
                obs.stop()

        run_async(body(), timeout=60)

    def test_endpoints_404_without_observatory(self, run_async):
        import aiohttp

        from dragonfly2_tpu.pkg.metrics_server import MetricsServer

        async def body():
            srv = MetricsServer()
            port = await srv.serve("127.0.0.1", 0)
            try:
                async with aiohttp.ClientSession() as sess:
                    for path in ("/debug/prof", "/debug/prof/flame",
                                 "/debug/prof/runtime"):
                        async with sess.get(
                                f"http://127.0.0.1:{port}{path}") as r:
                            assert r.status == 404, path
            finally:
                await srv.close()

        run_async(body(), timeout=60)


# --------------------------------------------------------------------- #
# Flight integration: advisory line + post-mortem bundle snapshot
# --------------------------------------------------------------------- #

class TestFlightRuntimeIntegration:
    def _report_with_runtime(self):
        tf = flight.TaskFlight("rt-task")
        tf.record(flight.EV_REGISTER)
        tf.record(flight.EV_LOOP_LAG, 12, 0.7,
                  "held n=1 gc=0.0 who=local_store.py:save_metadata:88")
        tf.record(flight.EV_LOOP_LAG, 0, 0.3, "late")
        # Holds under the wedge's 0.25 s name their holder and count as
        # no wedge.
        tf.record(flight.EV_LOOP_LAG, 20, 0.023,
                  "held n=3 gc=0.0 who=device_feed.py:_land:207")
        tf.record(flight.EV_LOOP_LAG, 21, 0.024,
                  "held n=2 gc=1.5 who=device_feed.py:_land:207")
        tf.record(flight.EV_GC_PAUSE, -1, 0.12, "gc_pause")
        tf.finish("done", "")
        return flight.analyze(tf)

    def test_analyze_summarizes_runtime_events(self):
        rep = self._report_with_runtime()
        rt = rep["runtime"]
        assert rt["loop_lag"]["count"] == 2
        assert rt["loop_lag"]["max_s"] == pytest.approx(0.7)
        assert rt["loop_lag"]["total_s"] == pytest.approx(1.0)
        assert rt["gc_pause"]["count"] == 1
        # Every held hold by who ran in it, the longest sum first.
        assert rt["holds"] == 3
        assert list(rt["holders"].items()) == [
            ("local_store.py:save_metadata:88", pytest.approx(0.7)),
            ("device_feed.py:_land:207", pytest.approx(0.047))]

    def test_advisory_renders_in_waterfall(self):
        rep = self._report_with_runtime()
        advisory = flight.runtime_advisory(rep)
        assert ("event loop held 3x, 0.70 s by "
                "local_store.py:save_metadata:88") in advisory
        assert "event loop wedged 2x" in advisory
        assert "gc paused 1x" in advisory
        assert "/debug/prof" in advisory
        text = flight.render_waterfall(rep)
        assert advisory in text

    def test_short_holds_alone_still_name_their_holder(self):
        tf = flight.TaskFlight("rt-short")
        tf.record(flight.EV_REGISTER)
        for _ in range(3):
            tf.record(flight.EV_LOOP_LAG, 22, 0.1,
                      "held n=1 gc=0.0 who=device_feed.py:_land:207")
        tf.finish("done", "")
        rep = flight.analyze(tf)
        assert "loop_lag" not in rep["runtime"]     # no wedge among them
        assert flight.runtime_advisory(rep) == (
            "runtime interference: event loop held 3x, 0.30 s by "
            "device_feed.py:_land:207 during this task — see /debug/prof")

    def test_quiet_runtime_prints_no_advisory(self):
        tf = flight.TaskFlight("quiet")
        tf.record(flight.EV_REGISTER)
        tf.finish("done", "")
        rep = flight.analyze(tf)
        assert flight.runtime_advisory(rep) == ""
        assert "runtime interference" not in flight.render_waterfall(rep)

    def test_postmortem_bundle_embeds_runtime_snapshot(self, tmp_path):
        rec = flight.FlightRecorder(dump_dir=str(tmp_path), max_tasks=8)
        obs = RuntimeObservatory(ProfConfig(enabled=False), recorder=rec)
        rec.runtime = obs
        obs.probes["ut"] = p = LoopLagProbe(obs, "ut")
        rec.task("doomed")
        p.note_lag(0.9)                     # stamped while running
        rec.finish_task("doomed", "failed", "chaos")
        bundles = glob.glob(str(tmp_path / "flight-*.json.gz"))
        assert len(bundles) == 1
        with gzip.open(bundles[0], "rt") as f:
            bundle = json.load(f)
        rt = bundle["runtime"]
        assert "prof" in rt and "loops" in rt and "gc" in rt
        assert rt["loops"][0]["slow_ticks"] == 1
        assert rt["proc"]["threads"] >= 1
        assert bundle["report"]["runtime"]["loop_lag"]["count"] == 1


# --------------------------------------------------------------------- #
# Thread-naming hygiene: every long-lived thread carries a df- prefix
# --------------------------------------------------------------------- #

# Spawn sites allowed to skip the prefix (none today — additions need a
# reason the profiler can live with).
THREAD_NAME_EXEMPT: set = set()


def _literal_prefix(node) -> "str | None":
    """Best-effort leading text of a name expression: plain constants
    and f-strings with a literal head resolve; anything dynamic is
    None (flagged — an unnamed or unprefixed thread is unattributable
    in /debug/prof)."""
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    if isinstance(node, ast.JoinedStr) and node.values:
        head = node.values[0]
        if isinstance(head, ast.Constant) and isinstance(head.value, str):
            return head.value
    return None


def test_every_long_lived_thread_has_df_prefix():
    """AST walk over the whole package: every ``threading.Thread(...)``
    must pass ``name="df-..."`` and every ``ThreadPoolExecutor(...)``
    must pass ``thread_name_prefix="df-..."``. Attribution in the
    sampling profiler is BY THREAD NAME — an anonymous Thread-7 burning
    a core is a mystery; ``df-ioring`` is a diagnosis."""
    violations = []
    for path in glob.glob(os.path.join(PKG_ROOT, "**", "*.py"),
                          recursive=True):
        rel = os.path.relpath(path, PKG_ROOT)
        tree = ast.parse(open(path).read(), filename=rel)
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            func = node.func
            callee = func.attr if isinstance(func, ast.Attribute) \
                else getattr(func, "id", "")
            if callee == "Thread":
                kw = {k.arg: k.value for k in node.keywords}
                name = _literal_prefix(kw.get("name"))
                if name is None or not name.startswith("df-"):
                    violations.append(
                        (rel, node.lineno,
                         f"Thread name {name!r} lacks the df- prefix"))
            elif callee == "ThreadPoolExecutor":
                kw = {k.arg: k.value for k in node.keywords}
                prefix = _literal_prefix(kw.get("thread_name_prefix"))
                if prefix is None or not prefix.startswith("df-"):
                    violations.append(
                        (rel, node.lineno,
                         f"ThreadPoolExecutor prefix {prefix!r} lacks "
                         f"the df- prefix"))
    violations = [v for v in violations
                  if (v[0], v[1]) not in THREAD_NAME_EXEMPT]
    assert not violations, (
        "long-lived threads without a df- name prefix (profiler "
        f"attribution is by thread name): {violations}")


# --------------------------------------------------------------------- #
# Acceptance e2e: runtime interference in a real daemon mid-broadcast
# --------------------------------------------------------------------- #

class TestRuntimeObservatoryE2E:
    def test_interference_attributed_named_and_breached(self, run_async,
                                                        tmp_path):
        """The ISSUE's acceptance drill: during a REAL broadcast (two
        parent daemons serving a conductor download over loopback), a
        seeded CPU-burn thread, forced GC churn, and a wedged event loop
        must surface in every layer at once:

          * /debug/prof names the burn thread (by its df- name) with
            self-time samples;
          * the loop-lag histogram records the wedge and /debug/slo
            breaches ``loop_lag``;
          * the task's flight autopsy carries the typed slow-tick
            events and --explain's waterfall prints the advisory.
        """
        import random

        import aiohttp

        from dataclasses import replace as dc_replace

        from tests.test_flight import _start_parent
        from tests.test_chaos import FakeAnnounceStream, FakeSchedulerClient
        from dragonfly2_tpu.daemon.peer.conductor import PeerTaskConductor
        from dragonfly2_tpu.daemon.peer.piece_manager import PieceManager
        from dragonfly2_tpu.pkg import slo as slolib
        from dragonfly2_tpu.pkg.metrics_server import MetricsServer
        from dragonfly2_tpu.storage import StorageManager, StorageOption
        from dragonfly2_tpu.storage import TaskStoreMetadata

        piece_size = 8192
        n_pieces = 48
        content = bytes(random.Random(99).randbytes(n_pieces * piece_size))
        task_id = "prof-e2e-task"
        rec = flight.recorder()

        async def body():
            cfg = ProfConfig(hz=100, lag_slow_s=0.2, gc_slow_s=0.0)
            # The install below must create the singleton (first cfg
            # wins): a leaked observatory from another test would run
            # this drill with the wrong thresholds.
            assert proflib.observatory() is None, \
                "another test leaked an installed observatory"
            obs = proflib.install(cfg, recorder=rec)
            rec.runtime = obs
            probe = obs.arm_loop("daemon")
            engine = slolib.SLOEngine(
                specs=tuple(dc_replace(s, threshold=cfg.lag_slow_s)
                            for s in slolib.RUNTIME_SLOS),
                probes=obs.slo_probes())

            burn_stop = threading.Event()

            def burn():
                while not burn_stop.is_set():
                    math.sqrt(98765.4321)

            burner = threading.Thread(target=burn, daemon=True,
                                      name="df-e2e-burn")

            parent_a = await _start_parent(tmp_path, "parent-a", task_id,
                                           content, piece_size)
            parent_b = await _start_parent(tmp_path, "parent-b", task_id,
                                           content, piece_size)
            child_storage = StorageManager(
                StorageOption(data_dir=str(tmp_path / "child-data")))
            store = child_storage.register_task(TaskStoreMetadata(
                task_id=task_id, peer_id="child-peer",
                url="http://origin/blob"))
            announce = FakeAnnounceStream([{
                "type": "normal_task",
                "task": {"content_length": len(content),
                         "piece_size": piece_size,
                         "total_piece_count": n_pieces},
                "parents": [parent_a.wire, parent_b.wire],
            }])
            conductor = PeerTaskConductor(
                task_id=task_id, peer_id="child-peer",
                url="http://origin/blob", store=store,
                scheduler_client=FakeSchedulerClient([announce]),
                piece_manager=PieceManager(),
                host_info={"id": "child-host"}, disable_back_source=True)
            try:
                burner.start()
                run = asyncio.ensure_future(conductor.run())
                # Mid-broadcast interference, injected while pieces are
                # in flight on THIS loop: GC churn, then a hard wedge.
                await asyncio.sleep(0.02)
                junk = []
                for _ in range(5):
                    cycle = [junk]
                    cycle.append(cycle)
                    junk.append(cycle)
                    gc.collect(0)
                time.sleep(0.45)            # wedge: blocks loop + pieces
                await asyncio.sleep(0)      # the turn ends: it is booked
                await asyncio.wait_for(run, timeout=60)
                assert store.is_complete()
                rec.finish_task(task_id, "done")

                # A second wedge post-download pushes total wedged wall
                # time to ~1.5 s, so the loop_lag burn rate breaches the
                # slow window regardless of how long this box took to
                # finish the broadcast (burn = 100 * wedged/observed;
                # observed stays well under the 25 s break-even).
                time.sleep(1.0)
                await asyncio.sleep(0)      # booked as its turn ends

                # Give the 100 Hz sampler a beat to catch the burner.
                deadline = time.monotonic() + 5.0
                while time.monotonic() < deadline:
                    if obs.profile_report()["threads"].get(
                            "df-e2e-burn", {}).get("samples", 0) >= 3:
                        break
                    await asyncio.sleep(0.05)

                srv = MetricsServer(flight=rec, prof=obs, slo=engine)
                port = await srv.serve("127.0.0.1", 0)
                base = f"http://127.0.0.1:{port}"
                try:
                    async with aiohttp.ClientSession() as sess:
                        async with sess.get(base + "/debug/prof") as r:
                            assert r.status == 200
                            prof_rep = await r.json()
                        async with sess.get(base + "/debug/slo") as r:
                            assert r.status == 200
                            slo_rep = await r.json()
                        async with sess.get(
                                base + f"/debug/flight/{task_id}") as r:
                            assert r.status == 200
                            autopsy = await r.json()
                        async with sess.get(
                                base + f"/debug/flight/{task_id}"
                                "?format=text") as r:
                            text = await r.text()
                        async with sess.get(
                                base + "/debug/prof/runtime") as r:
                            runtime_rep = await r.json()
                finally:
                    await srv.close()

                # (1) The burn thread is attributed BY NAME.
                burn_prof = prof_rep["threads"].get("df-e2e-burn")
                assert burn_prof and burn_prof["samples"] >= 3, \
                    sorted(prof_rep["threads"])
                assert any("burn" in f["frame"]
                           for f in burn_prof["top_self"]), burn_prof

                # (2) The lag histogram recorded the wedge...
                loop_sum = [l for l in runtime_rep["loops"]
                            if l["name"] == "daemon"][0]
                assert loop_sum["max_lag_s"] >= 0.3, loop_sum
                assert loop_sum["slow_ticks"] >= 1, loop_sum
                # ...and the GC observatory saw the forced churn.
                assert sum(runtime_rep["gc"]["collections"]) >= 5

                # (3) The loop_lag SLO breached.
                ll = [s for s in slo_rep["slos"]
                      if s["name"] == "loop_lag"][0]
                assert ll["state"] == "breach", ll
                assert "loop_lag" in slo_rep["breached"]

                # (4) The task's autopsy carries the typed events and
                # --explain's waterfall prints the advisory.
                rt = autopsy["runtime"]
                assert rt.get("loop_lag", {}).get("count", 0) >= 1, rt
                assert rt["loop_lag"]["max_s"] >= 0.3, rt
                assert rt.get("gc_pause", {}).get("count", 0) >= 1, rt
                assert "runtime interference" in text
                assert "event loop wedged" in text
                assert "/debug/prof" in text
                # ...and names who held the loop: the 100 Hz sampler met
                # this very coroutine inside the 0.45 s wedge.
                assert any(who.startswith("test_prof.py:body:")
                           for who in rt["holders"]), rt
                assert "event loop held" in text and " s by " in text
                # The loop's own ring kept the holds and the slices, and
                # the runtime report the account.
                assert loop_sum["busy_s"] >= 1.45, loop_sum
                assert loop_sum["holds"][0]["seconds"] >= 1.0, loop_sum
                own = rec.get("runtime:loop:daemon")
                assert sum(e[3] for e in own.events()
                           if e[1] == flight.EV_LOOP_ACCT) >= 1450.0
            finally:
                burn_stop.set()
                burner.join(timeout=5)
                probe.disarm()
                obs.probes.pop(probe.name, None)
                rec.runtime = None
                proflib.release(obs)
                await parent_a.close()
                await parent_b.close()
                child_storage.close()

        run_async(body(), timeout=120)
