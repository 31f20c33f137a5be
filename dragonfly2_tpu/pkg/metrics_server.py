"""Standalone metrics + debug HTTP endpoint for any binary.

Reference: Prometheus served per binary (scheduler/scheduler.go:219,
manager/metrics, client daemon metrics) and the --pprof-port runtime
dashboards (cmd/dependency/dependency.go:95-114). The /debug surface is
the Python analog of pprof: live thread stacks and asyncio task dumps.

Routes: GET /metrics (Prometheus text; OpenMetrics via Accept),
        GET /healthy,
        GET /debug/stacks (all thread stacks), GET /debug/tasks (asyncio),
        GET /debug/profile?seconds=N (cProfile sample, pprof's CPU
        profile analog), GET /debug/heap?topn=N (tracemalloc snapshot,
        pprof's heap profile analog; first call arms tracing),
        GET /debug/flight (flight-recorder task index),
        GET /debug/flight/{task_id}[?format=text] (critical-path autopsy:
        phase breakdown + per-piece waterfall, JSON or rendered text),
        GET /debug/pod/{task_id} (scheduler-side per-host straggler
        attribution from piece-report timings),
        GET /debug/pod/{task_id}/timeline[?format=text] (pod lens: the
        merged cross-host broadcast timeline, clock-aligned, slowest
        host + dominant phase named, alignment error bound printed),
        GET /debug/slo (the continuous SLO / burn-rate engine's state),
        GET /debug/prof (runtime observatory: top-N self-time per thread
        from the always-on sampling profiler),
        GET /debug/prof/flame?format=folded (flamegraph-ready folded
        stacks from the same trie),
        GET /debug/prof/runtime (event-loop lag histograms, GC pauses,
        /proc gauges),
        GET /debug/fleet[?window=seconds] (cluster health time-series),
        GET /debug/fleet/hosts (cross-task host scorecards + straggler
        flags), GET /debug/fleet/decisions?host=|task=|kind=|n=|since=|
        before= (the scheduling decision audit log, hard-capped with a
        truncated marker), GET /debug/fleet/info (scheduler uptime /
        build / config snapshot). All fleet routes are backed by the
        bounded pkg/fleet observatory the scheduler passes in.
        GET /debug/cluster[?window=][&format=text] (manager: the merged
        cluster control-tower view — every scheduler's keepalive fleet
        frames folded with per-scheduler attribution),
        GET /debug/cluster/schedulers (per-scheduler state: active /
        inactive / no_data, frames, latest sets),
        GET /debug/cluster/slo (per-scheduler SLO condensate + breached
        union), GET /debug/cluster/events?kind=|scheduler=|n=|since=|
        before= (the edge-triggered cluster event journal). All cluster
        routes are backed by the bounded pkg/cluster series the manager
        passes in.

The route table is a class attribute (``ROUTES``) so tooling and the
docs lint (tests/test_metrics_lint.py) can introspect every registered
``/debug/*`` route without serving.
"""

from __future__ import annotations

import asyncio
import io
import sys
import traceback

from aiohttp import web

from dragonfly2_tpu.pkg import dflog, flight as flightlib, metrics

log = dflog.get("metrics_server")


def _thread_stacks() -> str:
    out = io.StringIO()
    for thread_id, frame in sys._current_frames().items():
        out.write(f"--- thread {thread_id} ---\n")
        traceback.print_stack(frame, file=out)
        out.write("\n")
    return out.getvalue()


def _task_dump() -> str:
    out = io.StringIO()
    try:
        tasks = asyncio.all_tasks()
    except RuntimeError:
        return "no running loop\n"
    for task in tasks:
        out.write(f"--- {task.get_name()} "
                  f"{'cancelled' if task.cancelled() else 'pending'} ---\n")
        task.print_stack(file=out)
        out.write("\n")
    return out.getvalue()


class MetricsServer:
    # The single source of truth for the HTTP surface: (path, handler
    # attribute name). serve() registers exactly this; debug_routes()
    # exposes it so the docs lint can demand every /debug route be
    # documented without hand-listing paths anywhere.
    ROUTES = (
        ("/metrics", "_metrics"),
        ("/healthy", "_healthy"),
        ("/debug/stacks", "_stacks"),
        ("/debug/tasks", "_tasks"),
        ("/debug/profile", "_profile"),
        ("/debug/heap", "_heap"),
        ("/debug/flight", "_flight_index"),
        ("/debug/flight/{task_id}", "_flight_task"),
        ("/debug/pod/{task_id}", "_pod_task"),
        ("/debug/pod/{task_id}/timeline", "_pod_timeline"),
        ("/debug/slo", "_slo"),
        ("/debug/prof", "_prof"),
        ("/debug/prof/flame", "_prof_flame"),
        ("/debug/prof/runtime", "_prof_runtime"),
        ("/debug/fleet", "_fleet_snapshot"),
        ("/debug/fleet/hosts", "_fleet_hosts"),
        ("/debug/fleet/decisions", "_fleet_decisions"),
        ("/debug/fleet/info", "_fleet_info"),
        ("/debug/cluster", "_cluster_view"),
        ("/debug/cluster/schedulers", "_cluster_schedulers"),
        ("/debug/cluster/slo", "_cluster_slo"),
        ("/debug/cluster/events", "_cluster_events"),
    )

    def __init__(self, *, flight: "flightlib.FlightRecorder | None" = None,
                 pod_flight: "flightlib.PodAggregator | None" = None,
                 fleet=None, slo=None, pod_timeline=None, prof=None,
                 cluster=None):
        # Optional providers: the daemon passes its flight recorder, the
        # scheduler its pod aggregator + fleet observatory + SLO engine
        # + pod-timeline assembler (an async callable task_id -> report,
        # so the on-demand FlightReport pulls stay in the scheduler);
        # the manager its cluster control tower (pkg/cluster) behind the
        # /debug/cluster* family; ALL pass the runtime observatory
        # (pkg/prof) behind /debug/prof*; endpoints 404 without one.
        self._flight = flight
        self._pod_flight = pod_flight
        self._fleet = fleet
        self._slo_engine = slo
        self._pod_timeline_provider = pod_timeline
        self._prof_obs = prof
        self._cluster = cluster
        self._runner: web.AppRunner | None = None
        self._port = 0
        self._profiling = False

    @classmethod
    def debug_routes(cls) -> list:
        """Every registered /debug route pattern — what the docs lint
        walks so no endpoint ships undocumented."""
        return [path for path, _name in cls.ROUTES
                if path.startswith("/debug/")]

    async def serve(self, host: str, port: int) -> int:
        app = web.Application()
        for path, name in self.ROUTES:
            app.router.add_get(path, getattr(self, name))
        self._runner = web.AppRunner(app, access_log=None)
        await self._runner.setup()
        site = web.TCPSite(self._runner, host, port)
        await site.start()
        self._port = site._server.sockets[0].getsockname()[1]
        log.info("metrics server up", port=self._port)
        return self._port

    @property
    def port(self) -> int:
        return self._port

    async def close(self) -> None:
        if self._runner is not None:
            await self._runner.cleanup()

    async def _metrics(self, request: web.Request) -> web.Response:
        # Content-negotiated: an OpenMetrics Accept header gets the
        # strict exposition (scrapers that parse strictly — and our own
        # round-trip test — use it).
        body, content_type = metrics.render(request.headers.get("Accept",
                                                                ""))
        # content_type carries params (version/charset); aiohttp's
        # content_type kwarg rejects those — set the raw header.
        return web.Response(body=body, headers={"Content-Type": content_type})

    async def _healthy(self, request: web.Request) -> web.Response:
        return web.json_response({"ok": True})

    async def _stacks(self, request: web.Request) -> web.Response:
        return web.Response(text=_thread_stacks())

    async def _tasks(self, request: web.Request) -> web.Response:
        return web.Response(text=_task_dump())

    async def _profile(self, request: web.Request) -> web.Response:
        """CPU profile of the event-loop thread for ?seconds=N (default 5,
        cap 60): cProfile runs while the loop keeps serving, then pstats
        text comes back — the pprof /debug/pprof/profile analog."""
        import cProfile
        import pstats

        try:
            seconds = min(max(float(request.query.get("seconds", "5")), 0.1),
                          60.0)
        except ValueError:
            return web.Response(text="bad seconds value\n", status=400)
        if self._profiling:
            return web.Response(text="a profile is already running\n",
                                status=409)
        self._profiling = True
        prof = cProfile.Profile()
        try:
            try:
                prof.enable()
            except ValueError as e:  # another profiler is active
                return web.Response(text=f"{e}\n", status=409)
            try:
                await asyncio.sleep(seconds)
            finally:
                prof.disable()
        finally:
            self._profiling = False
        out = io.StringIO()
        stats = pstats.Stats(prof, stream=out)
        stats.sort_stats("cumulative").print_stats(60)
        return web.Response(text=out.getvalue())

    async def _flight_index(self, request: web.Request) -> web.Response:
        if self._flight is None:
            raise web.HTTPNotFound(text="no flight recorder on this binary\n")
        return web.json_response({"tasks": self._flight.summary()})

    async def _flight_task(self, request: web.Request) -> web.Response:
        """The black-box autopsy: phase breakdown folding the task's event
        ring (sums to wall time) + the per-piece waterfall. ``?format=text``
        renders the same waterfall ``dfget --explain`` prints; ``?raw=1``
        answers with the ring's own events instead (``flight.raw``)."""
        if self._flight is None:
            raise web.HTTPNotFound(text="no flight recorder on this binary\n")
        task_id = request.match_info["task_id"]
        self._flight.sync()
        tf = self._flight.get(task_id)
        if tf is None:
            raise web.HTTPNotFound(text=f"no flight data for {task_id}\n")
        if request.query.get("raw") == "1":
            return web.json_response(flightlib.raw(tf))
        report = flightlib.analyze(tf)
        if request.query.get("format") == "text":
            return web.Response(text=flightlib.render_waterfall(report) + "\n")
        return web.json_response(report)

    async def _pod_task(self, request: web.Request) -> web.Response:
        """Pod-level straggler attribution (scheduler binary): slowest
        host, dominant phase, quarantine correlation."""
        if self._pod_flight is None:
            raise web.HTTPNotFound(text="no pod aggregator on this binary\n")
        task_id = request.match_info["task_id"]
        report = self._pod_flight.report(task_id)
        if report is None:
            raise web.HTTPNotFound(text=f"no pod data for {task_id}\n")
        return web.json_response(report)

    async def _pod_timeline(self, request: web.Request) -> web.Response:
        """Pod lens (scheduler binary): the merged cross-host broadcast
        timeline — every host's shipped flight digest aligned onto one
        wall axis by the announce-path clock estimator, slowest host and
        dominant phase named, alignment error bound carried.
        ``?format=text`` renders the per-host phase-colored lag
        waterfall (the same renderer ``dfget --pod`` prints)."""
        if self._pod_timeline_provider is None:
            raise web.HTTPNotFound(
                text="no pod lens on this binary (scheduler-only)\n")
        task_id = request.match_info["task_id"]
        report = await self._pod_timeline_provider(task_id)
        if report is None:
            raise web.HTTPNotFound(
                text=f"no shipped flight digests for {task_id}\n")
        if request.query.get("format") == "text":
            from dragonfly2_tpu.pkg import podlens

            return web.Response(text=podlens.render_timeline(report) + "\n")
        return web.json_response(report)

    async def _slo(self, request: web.Request) -> web.Response:
        """The continuous SLO / burn-rate engine: the scheduler serves
        the full spec set; a daemon serves its runtime-only engine
        (loop_lag) when the observatory is armed."""
        if self._slo_engine is None:
            raise web.HTTPNotFound(
                text="no SLO engine on this binary\n")
        return web.json_response(self._slo_engine.report())

    def _need_prof(self):
        if self._prof_obs is None:
            raise web.HTTPNotFound(
                text="no runtime observatory on this binary "
                     "(prof.enabled=false?)\n")
        return self._prof_obs

    async def _prof(self, request: web.Request) -> web.Response:
        """Runtime observatory (pkg/prof): the always-on sampling
        profiler's top-N self-time frames per thread. ``?topn=`` bounds
        the per-thread list (default 20, cap 200)."""
        obs = self._need_prof()
        try:
            topn = min(max(int(request.query.get("topn", "20")), 1), 200)
        except ValueError:
            return web.Response(text="bad topn value\n", status=400)
        return web.json_response(obs.profile_report(topn))

    async def _prof_flame(self, request: web.Request) -> web.Response:
        """Flamegraph-ready folded stacks (``thread;frame;frame count``
        per line) from the sampler's bounded trie — pipe straight into
        flamegraph.pl / speedscope. ``format=folded`` is the only
        format."""
        obs = self._need_prof()
        if request.query.get("format", "folded") != "folded":
            return web.Response(text="only format=folded is supported\n",
                                status=400)
        return web.Response(text=obs.folded())

    async def _prof_runtime(self, request: web.Request) -> web.Response:
        """Loop-lag histograms per probed loop, GC pause/collection
        summary, and /proc/self gauges (RSS, fds, threads, ctx
        switches) — refreshed at scrape time."""
        return web.json_response(self._need_prof().runtime_report())

    def _need_fleet(self):
        if self._fleet is None:
            raise web.HTTPNotFound(text="no fleet observatory on this "
                                        "binary (scheduler-only)\n")
        return self._fleet

    async def _fleet_snapshot(self, request: web.Request) -> web.Response:
        """Cluster health time-series: counters/gauges over the trailing
        ``?window=`` seconds (default 600, clamped to the ring)."""
        fleet = self._need_fleet()
        try:
            window = max(1.0, float(request.query.get("window", "600")))
        except ValueError:
            return web.Response(text="bad window value\n", status=400)
        return web.json_response(fleet.snapshot(window))

    async def _fleet_hosts(self, request: web.Request) -> web.Response:
        """Cross-task host scorecards: serve/download EWMAs, decayed
        failure counts, upload load, straggler flags with robust z."""
        fleet = self._need_fleet()
        try:
            limit = min(max(int(request.query.get("n", "256")), 1), 4096)
        except ValueError:
            return web.Response(text="bad n value\n", status=400)
        return web.json_response(fleet.hosts_report(limit))

    async def _fleet_decisions(self, request: web.Request) -> web.Response:
        """The scheduling decision audit log, newest first, filterable by
        ?host= / ?task= / ?kind= (handout, quarantine, back_source,
        stripe_handout, stripe_reshuffle, straggler_filter,
        schedule_failed, admission, throttle — the QoS kinds carry the
        TENANT as subject) and bounded in time by ?since=/?before= (wall
        seconds, half-open [since, before)). ?n= caps the page (hard cap
        4096); a page that hit the cap with more matching entries behind
        it carries ``truncated: true`` — page back with
        ``before=<oldest ts>``."""
        fleet = self._need_fleet()
        try:
            limit = min(max(int(request.query.get("n", "256")), 1), 4096)
            since = float(request.query.get("since", "0") or 0)
            before = float(request.query.get("before", "0") or 0)
        except ValueError:
            return web.Response(text="bad n/since/before value\n",
                                status=400)
        return web.json_response(fleet.decisions.query(
            host=request.query.get("host", ""),
            task=request.query.get("task", ""),
            kind=request.query.get("kind", ""),
            limit=limit, since=since, before=before))

    async def _fleet_info(self, request: web.Request) -> web.Response:
        """Scheduler identity card: uptime, build, config snapshot, and
        the observatory's own bounds + resident bytes."""
        return web.json_response(self._need_fleet().info())

    def _need_cluster(self):
        if self._cluster is None:
            raise web.HTTPNotFound(text="no cluster control tower on this "
                                        "binary (manager-only)\n")
        return self._cluster

    async def _cluster_view(self, request: web.Request) -> web.Response:
        """The merged cluster view (manager binary): every scheduler's
        keepalive fleet frames folded into cluster totals with
        per-scheduler straggler/quarantine/breach attribution over the
        trailing ``?window=`` seconds (default 600). ``?format=text``
        renders the same view ``dfget --explain --cluster`` prints."""
        cluster = self._need_cluster()
        try:
            window = max(1.0, float(request.query.get("window", "600")))
        except ValueError:
            return web.Response(text="bad window value\n", status=400)
        report = cluster.report(window)
        if request.query.get("format") == "text":
            from dragonfly2_tpu.pkg.cluster import render_cluster

            return web.Response(text=render_cluster(report))
        return web.json_response(report)

    async def _cluster_schedulers(self, request: web.Request) -> web.Response:
        """Per-scheduler detail: state (active / inactive / no_data —
        no_data = alive keepalive, no fleet frames), frame counts and
        age, latest straggler/quarantine sets and gauges."""
        cluster = self._need_cluster()
        try:
            window = max(1.0, float(request.query.get("window", "600")))
        except ValueError:
            return web.Response(text="bad window value\n", status=400)
        return web.json_response(cluster.schedulers_report(window))

    async def _cluster_slo(self, request: web.Request) -> web.Response:
        """Per-scheduler SLO condensate (worst burn + state per SLO, as
        shipped in the frames) and the cluster-wide breached union."""
        cluster = self._need_cluster()
        try:
            window = max(1.0, float(request.query.get("window", "600")))
        except ValueError:
            return web.Response(text="bad window value\n", status=400)
        return web.json_response(cluster.slo_report(window))

    async def _cluster_events(self, request: web.Request) -> web.Response:
        """The cluster event journal, newest first: keepalive lapse /
        return, slo_breach, straggler, quarantine_storm, admission_burst
        — filterable by ?kind= / ?scheduler= and bounded by ?since= /
        ?before= (wall seconds, half-open [since, before)). ?n= caps the
        page (hard cap 4096); ``truncated: true`` marks a capped page."""
        cluster = self._need_cluster()
        try:
            limit = min(max(int(request.query.get("n", "256")), 1), 4096)
            since = float(request.query.get("since", "0") or 0)
            before = float(request.query.get("before", "0") or 0)
        except ValueError:
            return web.Response(text="bad n/since/before value\n",
                                status=400)
        return web.json_response(cluster.journal.query(
            kind=request.query.get("kind", ""),
            scheduler=request.query.get("scheduler", ""),
            limit=limit, since=since, before=before))

    async def _heap(self, request: web.Request) -> web.Response:
        """Heap allocation snapshot via tracemalloc (armed on first call;
        subsequent calls show current top allocators) — the pprof
        /debug/pprof/heap analog."""
        import tracemalloc

        try:
            topn = min(int(request.query.get("topn", "30")), 200)
        except ValueError:
            return web.Response(text="bad topn value\n", status=400)
        if not tracemalloc.is_tracing():
            tracemalloc.start()
            return web.Response(
                text="tracemalloc armed; call again for a snapshot\n")
        snapshot = tracemalloc.take_snapshot()
        current, peak = tracemalloc.get_traced_memory()
        lines = [f"traced current={current / 1e6:.1f}MB "
                 f"peak={peak / 1e6:.1f}MB", ""]
        for stat in snapshot.statistics("lineno")[:topn]:
            lines.append(str(stat))
        return web.Response(text="\n".join(lines) + "\n")
