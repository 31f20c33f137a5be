"""One cell, one run.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Brings the fabric up on loopback, warms the cell's own shapes with one
untimed operation, measures for ``--seconds``, compares what landed with the
generator's bytes, tears everything down, and prints one JSON object as the
last line of stdout. With ``--trace 0`` the metrics are the cell's
end-to-end metrics; with ``--trace 1`` a shorter window runs under the
profiler and the metrics are the per-layer ones.

The harness is driven by the manifest (``BENCHMARK.json``): a workload names
a configuration file and a traffic file; the traffic file's ``kind`` names a
driver under ``drivers/``, the configuration's ``object.kind`` a generator
under ``objects/``, and each per-layer metric a reader under ``layers/``.

It fails, printing no result, anywhere but on a TPU whose ``device_kind`` is
in ``peaks.json``. ``--manifest chipbench/rehearsal/manifest.json`` is the
CPU rehearsal: the same code at a tiny size, every metric left out.
"""

from __future__ import annotations

T_START = __import__("time").perf_counter()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import time  # noqa: E402
import types  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path[:0] = [HERE, REPO]


def say(msg: str) -> None:
    print(f"[chipbench] {msg}", flush=True)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def find(rows: list[dict], name: str, what: str) -> dict:
    for row in rows:
        if row["name"] == name:
            return row
    raise SystemExit(f"chipbench: no {what} named {name!r} in the manifest")


class CompileMeter:
    """Counts what jax compiles, and for how long, from its own monitoring
    events (a persistent-cache hit is not a compile)."""

    def __init__(self):
        import jax.monitoring

        self.compiles = 0
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, seconds: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.seconds += seconds

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self) -> tuple[int, float, int]:
        return self.compiles, self.seconds, self.cache_hits


def percentile(values: list[float], q: float) -> float:
    """Nearest-rank percentile of all the values."""
    ordered = sorted(values)
    rank = max(1, -(-len(ordered) * q // 100))
    return ordered[int(rank) - 1]


def end_to_end(ops, setup_s: float) -> dict:
    """Every end-to-end metric this harness knows, from the finished
    operations of the window. The manifest says which a cell reports.

    The rate is what the system landed, whatever the number of clients:
    all the content bytes over all the time in which an operation was in
    flight. With one client that is the summed request->ready time; with
    several, the window less the moments in which every client stood
    between two operations, in the benchmark's own work."""
    import reduce_trace

    seconds = [op.seconds for op in ops]
    out = {"setup_s": setup_s}
    if ops:
        out["resident_MBps"] = sum(op.nbytes for op in ops) / 1e6 \
            / reduce_trace.total((op.t0, op.t1) for op in ops)
        out["ttr_p50_ms"] = statistics.median(seconds) * 1000.0
        out["ttr_p95_ms"] = percentile(seconds, 95) * 1000.0
    return out


def metrics_for(manifest: dict, section: str, workload: str) -> list[dict]:
    return [m for m in manifest[section]
            if workload in m.get("workloads", [workload])]


def use_compile_cache(directory: str) -> None:
    """Point jax's persistent compilation cache at ``directory`` from the
    next compile on."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    compilation_cache.reset_cache()
    jax.config.update("jax_compilation_cache_dir", directory)


async def run_cell(args, manifest, workload, config, traffic, devices,
                   peaks, meter, fabric, cache_dir) -> dict:
    import jax

    import reduce_trace
    import spans
    from harness import Cell
    from origin import load_objects

    driver = importlib.import_module("drivers." + traffic["kind"])
    home = fabric.home
    cell = Cell(fabric, config, traffic, load_objects(config, args.seed),
                args.seed)
    traced = bool(args.trace)
    trace_dir = os.path.join(home, "trace")
    try:
        marks = []
        await fabric.start(lambda what: marks.append(
            f"{what} {time.perf_counter() - T_START:.1f}s"))
        if not cell.objects.distinct:
            await cell.facts_for(0)
            marks.append(f"origin's facts {time.perf_counter() - T_START:.1f}s")
        say("set-up, seconds after process start: " + ", ".join(marks))
        await driver.warm_up(cell)
        warm = [op for op in cell.ops if op.warmup]
        say("warm-up: " + ", ".join(
            f"{'cold' if op.cold else 're-land'} {op.seconds:.2f}s"
            + (f" FAILED {op.error}" if op.error else "") for op in warm))
        compiled_before = meter.snapshot()
        # Whatever the program compiles for the first time inside the
        # window (an assembly plan is a static argument, and follows the
        # order pieces arrive in) it compiles in full, in every run: the
        # window's persistent cache is a new empty directory, so a reading
        # never depends on what earlier runs left in the checkout's cache.
        # Set-up and the check keep the checkout's.
        use_compile_cache(os.path.join(home, "window_jax_cache"))
        setup_s = time.perf_counter() - T_START
        if traced:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=options)
        try:
            w0, w1 = await driver.window(cell, args.seconds, traced)
        finally:
            if traced:
                jax.profiler.stop_trace()
            use_compile_cache(cache_dir)
        compiled = [b - a for a, b in zip(compiled_before, meter.snapshot())]
        say(f"window {w1 - w0:.2f}s; compile requests inside it: "
            f"{compiled[0]} in {compiled[1]:.2f}s (persistent-cache hits "
            f"{compiled[2]})")
        memory = devices[0].memory_stats() or {}
        correct, lines = await cell.check()
        for line in lines:
            say("check: " + line)
    except BaseException:
        print(fabric.log_tails(), flush=True)
        raise
    finally:
        await fabric.stop()

    timed = [op for op in cell.ops if not op.warmup]
    done = [op for op in timed if not op.error]
    in_flight = reduce_trace.total((op.t0, op.t1) for op in done)
    failed = sum(1 for op in cell.ops if op.error)
    say(f"operations: {len(timed)} attempted in the window, "
        f"{len(done)} finished; request->ready seconds: "
        + (f"min {min(o.seconds for o in done):.3f} median "
           f"{statistics.median(o.seconds for o in done):.3f} max "
           f"{max(o.seconds for o in done):.3f}" if done else "none"))
    say(f"the benchmark's own work between operations (readings, deletes; "
        f"beside the other clients' timed operations where there are "
        f"several): {sum(o.gap_s for o in done):.2f}s in all, median "
        + (f"{statistics.median(o.gap_s for o in done):.3f}s" if done
           else "-") + f"; in flight {in_flight:.2f}s of the window")
    fifths = [[o.seconds for o in done
               if i * (w1 - w0) / 5 <= o.t0 - w0 < (i + 1) * (w1 - w0) / 5]
              for i in range(5)]
    say("median request->ready by fifth of the window: " + ", ".join(
        f"{statistics.median(f):.3f} ({len(f)})" if f else "-"
        for f in fifths))
    typical = statistics.median(o.seconds for o in done) if done else 0.0
    slow = sorted((op for op in done[1:-1] if op.seconds > 1.3 * typical),
                  key=lambda op: -op.seconds)[:8]
    for op in done:
        if op in (done[0], done[-1]) or op in slow:
            say(f"operation {op.number}: " + spans.timeline(op))
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": memory.get("peak_bytes_in_use")}
    result = {"correct": bool(correct and failed == 0),
              "attempted": len(cell.ops), "failed": failed, "metrics": {},
              "device": device}
    if peaks is None:
        # The rehearsal: no number of a CPU run under a device metric's
        # name, so no metric at all.
        result["rehearsal"] = True
        say(f"rehearsal on {device['platform']}: metrics left out; host "
            f"seconds request->ready {[round(o.seconds, 3) for o in done]}")
        return result

    if not traced:
        values = end_to_end(done, setup_s)
        wanted = metrics_for(manifest, "end_to_end", workload["name"])
    else:
        run = types.SimpleNamespace(
            ops=done, cell=cell, peaks=peaks, trace=None, windows=[],
            busy_s=0.0, window_s=0.0,
            memory_peak_bytes=device["memory_peak_bytes"])
        trace = reduce_trace.read_xplane(trace_dir)
        marks = {f"chipbench:op#{op.number}": op.t0 for op in done}
        offset = reduce_trace.clock_offset(trace, marks)
        run.trace = trace
        run.windows = [(op.t0 + offset, op.t1 + offset) for op in done]
        run.busy_s, run.window_s = reduce_trace.busy_and_window(
            trace, run.windows)
        device["busy_s"], device["window_s"] = run.busy_s, run.window_s
        labelled = [(label, s + offset, e + offset)
                    for op in done for label, s, e in spans.labelled(op)]
        result["breakdown"] = {
            "device_ops": reduce_trace.top_device_ops(trace, run.windows),
            "idle_gaps": reduce_trace.idle_gaps_by_label(
                trace, run.windows, labelled)}
        wanted = metrics_for(manifest, "per_layer", workload["name"])
        values = {}
        for metric in wanted:
            reader = importlib.import_module("layers." + metric["name"])
            value = reader.read(run)
            if value is not None:
                values[metric["name"]] = value
    for metric in wanted:
        if metric["name"] in values:
            result["metrics"][metric["name"]] = {
                "value": values[metric["name"]], "unit": metric["unit"]}
    return result


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--manifest", default=os.path.join(
        REPO, "BENCHMARK.json"))
    args = parser.parse_args(argv)
    if not os.path.isdir(os.path.join(REPO, "dragonfly2_tpu")):
        print("chipbench needs the repository it measures beside it",
              file=sys.stderr)
        return 1
    # The real manifest; another one (the rehearsal's) gives its own
    # configurations and cells and takes every other table from it.
    manifest = {**load_json(os.path.join(REPO, "BENCHMARK.json")),
                **load_json(args.manifest)}
    workload = find(manifest["workloads"], args.workload, "workload")
    config = load_json(os.path.join(REPO, find(
        manifest["configs"], workload["config"], "configuration")["file"]))
    traffic = load_json(os.path.join(HERE, "traffic",
                                     workload["traffic"] + ".json"))

    from fabric import Fabric, scratch_home

    home = scratch_home()
    fabric = Fabric(home, os.path.join(REPO, find(
        manifest["configs"], workload["config"], "configuration")["file"]),
        config, args.seed)
    try:
        # The children that need no chip start first, and come up while
        # jax initialises it.
        fabric.spawn_early()
        import jax

        devices = jax.devices()
        kind = devices[0].device_kind
        peaks = load_json(os.path.join(HERE, "peaks.json")).get(kind)
        if manifest.get("rehearsal"):
            peaks = None
        elif devices[0].platform != "tpu" or peaks is None:
            print(f"chipbench: needs a TPU listed in chipbench/peaks.json; "
                  f"jax found platform {devices[0].platform!r}, device kind "
                  f"{kind!r}", file=sys.stderr)
            return 1
        if len(devices) < workload["chips"]:
            print(f"chipbench: the cell needs {workload['chips']} chip(s); "
                  f"jax found {len(devices)}", file=sys.stderr)
            return 1
        devices = devices[:workload["chips"]]

        from dragonfly2_tpu.ops.compile_cache import place_compile_cache

        cache_dir = place_compile_cache()
        meter = CompileMeter()
        say(f"cell {workload['name']} seed {args.seed} seconds "
            f"{args.seconds} trace {args.trace}; device {kind} "
            f"x{len(devices)}; compile cache {cache_dir}; jax ready "
            f"{time.perf_counter() - T_START:.1f}s after process start")
        result = asyncio.run(run_cell(args, manifest, workload, config,
                                      traffic, devices, peaks, meter, fabric,
                                      cache_dir))
    finally:
        asyncio.run(fabric.stop())
        shutil.rmtree(home, ignore_errors=True)
    say(f"compile requests in all: {meter.compiles} in {meter.seconds:.1f}s, "
        f"persistent-cache hits {meter.cache_hits}; total "
        f"{time.perf_counter() - T_START:.1f}s")
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
