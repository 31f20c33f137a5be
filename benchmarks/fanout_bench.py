"""BASELINE config #2 bench: P2P fan-out, 1 seed + 8 peers, one origin.

Real processes (scheduler + seed + 8 peer daemons spawned via the CLI,
mirroring tests/test_multiprocess_e2e.py); the 8 clients run the dfget
library concurrently against their daemons' unix sockets. Reports:

  - aggregate_gbps      total client bytes delivered / wall time
  - p50_ttfp_s          median time-to-first-piece across clients
  - origin_ratio        origin bytes served / content size (1.0 = one copy)

Usage: python benchmarks/fanout_bench.py [--mb 256] [--peers 8]
Writes a JSON line to stdout and (with --publish) updates
BASELINE.json["published"]["config2_fanout"].

Reference yardstick: test/e2e/v2/dfget_test.go:26-80 (sha-verified
fan-out), SURVEY §6; the reference publishes no numbers (BASELINE.md), so
these become the numbers to beat.
"""

from __future__ import annotations

import argparse
import asyncio
import hashlib
import json
import os
import random
import signal
import socket
import statistics
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _host_hash_gbps(procs: int = 4, mb_each: int = 96) -> "float | None":
    """Aggregate sha256 GB/s across ``procs`` CONCURRENT subprocesses,
    timed over the overlapping hash phase only (interpreter startup
    excluded via in-child wall timestamps). Single-process rates on this
    VM stay flat (~1.1 GB/s) even in windows where multi-process
    throughput collapses several-x, so the window-quality signal must
    itself be multi-process."""
    reps = mb_each // 16
    code = ("import hashlib,os,time;"
            "b=os.urandom(1<<24);"
            "t0=time.time();"
            "h=hashlib.sha256();"
            f"[h.update(b) for _ in range({reps})];"
            "print(t0, time.time())")
    try:
        ps = [subprocess.Popen([sys.executable, "-c", code],
                               stdout=subprocess.PIPE, text=True)
              for _ in range(procs)]
        spans = []
        for p in ps:
            out, _ = p.communicate()
            t0, t1 = (float(x) for x in out.split())
            spans.append((t0, t1))
        wall = max(t1 for _, t1 in spans) - min(t0 for t0, _ in spans)
        return round(procs * reps * (1 << 24) / max(wall, 1e-6) / 1e9, 3)
    except Exception:
        # Auxiliary metric only: a failed calibration child (OOM kill,
        # empty stdout) must never destroy the primary bench result.
        return None

from aiohttp import web  # noqa: E402

from dragonfly2_tpu.pkg.piece import Range  # noqa: E402


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _spawn(args: list[str], log_path: str,
           jax_cpu: bool = False) -> subprocess.Popen:
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO
    env.pop("XLA_FLAGS", None)
    env.pop("JAX_PLATFORMS", None)
    if jax_cpu:
        # Device-sink daemons: a real single-device CPU backend (the
        # jax.Array landing path the TPU sink uses, minus the chip).
        env["JAX_PLATFORMS"] = "cpu"
    logf = open(log_path, "w")
    return subprocess.Popen(
        [sys.executable, "-m", "dragonfly2_tpu.cli.main", *args],
        stdout=logf, stderr=subprocess.STDOUT, env=env)


def _wait_sock(path: str, timeout: float = 120.0) -> bool:
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if os.path.exists(path):
            s = socket.socket(socket.AF_UNIX)
            try:
                s.connect(path)
                return True
            except OSError:
                pass
            finally:
                s.close()
        time.sleep(0.1)
    return False


async def _grab_profile(port: int, seconds: float, out_path: str) -> str:
    """Pull /debug/profile from a daemon's metrics server mid-bench; save
    the full pstats text and return the top cumulative lines."""
    import aiohttp

    try:
        async with aiohttp.ClientSession() as s:
            async with s.get(f"http://127.0.0.1:{port}/debug/profile",
                             params={"seconds": str(seconds)},
                             timeout=aiohttp.ClientTimeout(
                                 total=seconds + 30)) as r:
                text = await r.text()
    except Exception as e:  # noqa: BLE001 - profile is best-effort
        return f"profile failed: {e}"
    with open(out_path, "w") as f:
        f.write(text)
    lines = [ln for ln in text.splitlines() if ln.strip()]
    return "\n".join(lines[4:24])


async def run_bench(total_mb: int, n_peers: int, workdir: str,
                    profile: bool = False,
                    origin_concurrency: int = 4,
                    device_sink: bool = False,
                    warm_seed: bool = False,
                    slices: int = 0,
                    stripe: bool = False,
                    measure_locality: bool = False,
                    host_hash_gbps: "float | None" = None) -> dict:
    measure_locality = measure_locality or stripe
    # randbytes caps at 2^31 bits; build large content from 16 MiB blocks.
    rng = random.Random(99)
    content = b"".join(rng.randbytes(16 << 20)
                       for _ in range(max(1, total_mb // 16)))
    sha = hashlib.sha256(content).hexdigest()
    stats = {"streams": 0, "bytes": 0}

    async def blob(request: web.Request) -> web.Response:
        stats["streams"] += 1
        rng = request.headers.get("Range")
        if rng:
            r = Range.parse_http(rng, len(content))
            data = content[r.start:r.start + r.length]
            stats["bytes"] += len(data)
            return web.Response(status=206, body=data, headers={
                "Accept-Ranges": "bytes",
                "Content-Range":
                    f"bytes {r.start}-{r.start + r.length - 1}/{len(content)}"})
        stats["bytes"] += len(content)
        return web.Response(body=content, headers={"Accept-Ranges": "bytes"})

    app = web.Application()
    app.router.add_get("/model.safetensors", blob)
    runner = web.AppRunner(app, access_log=None)
    await runner.setup()
    site = web.TCPSite(runner, "127.0.0.1", 0)
    await site.start()
    origin_port = site._server.sockets[0].getsockname()[1]

    sched_port = _free_port()
    sched_metrics = _free_port() if slices else 0
    procs: list[subprocess.Popen] = []
    names = ["seed"] + [f"peer{i}" for i in range(n_peers)]
    homes = {n: os.path.join(workdir, n) for n in names}
    try:
        sched_args = ["scheduler", "--host", "127.0.0.1",
                      "--port", str(sched_port)]
        if slices:
            sched_args += ["--metrics-port", str(sched_metrics)]
        procs.append(_spawn(sched_args, os.path.join(workdir, "sched.log")))
        seed_metrics = _free_port() if profile else 0
        peer0_metrics = _free_port() if profile else 0
        seed_args = ["daemon", "--work-home", homes["seed"], "--seed-peer",
                     "--scheduler", f"127.0.0.1:{sched_port}",
                     "--piece-concurrency", str(origin_concurrency)]
        if profile:
            seed_args += ["--metrics-port", str(seed_metrics)]
        if slices:
            # The seed is the cross-slice ingress: its own slice label is
            # outside every peer slice, so every seed-sourced handout
            # counts as cross and intra picks are pure peer↔peer ICI.
            seed_args += ["--tpu-slice", "slice-seed"]
        procs.append(_spawn(seed_args, os.path.join(workdir, "seed.log")))
        if slices and slices > n_peers:
            raise ValueError(f"--slices {slices} > --peers {n_peers}")
        peer_metrics: dict[int, int] = {}
        for i in range(n_peers):
            peer_args = ["daemon", "--work-home", homes[f"peer{i}"],
                         "--scheduler", f"127.0.0.1:{sched_port}"]
            if measure_locality:
                # Per-daemon locality byte counters are the per-host DCN
                # readout; each peer gets its own metrics endpoint.
                peer_metrics[i] = _free_port()
                peer_args += ["--metrics-port", str(peer_metrics[i])]
            if slices:
                # Even partition into EXACTLY `slices` contiguous groups
                # (i*slices//n_peers), so the published "slices" field
                # always matches the real topology.
                sid = i * slices // n_peers
                peer_args += ["--tpu-slice", f"slice-{sid}",
                              "--tpu-worker-index",
                              str(i - (sid * n_peers + slices - 1) // slices)]
            if device_sink:
                peer_args += ["--device-sink"]
            if profile and i == 0:
                if measure_locality:
                    peer0_metrics = peer_metrics[0]  # already serving one
                else:
                    peer_args += ["--metrics-port", str(peer0_metrics)]
            procs.append(_spawn(peer_args,
                                os.path.join(workdir, f"peer{i}.log"),
                                jax_cpu=device_sink))
        for n in names:
            ok = await asyncio.to_thread(
                _wait_sock, os.path.join(homes[n], "run", "dfdaemon.sock"))
            if not ok:
                raise RuntimeError(
                    f"{n} did not come up; tail: "
                    + open(os.path.join(workdir, f"{n}.log")).read()[-1500:])

        from dragonfly2_tpu.client import dfget as dfget_lib
        from dragonfly2_tpu.proto.common import UrlMeta

        url = f"http://127.0.0.1:{origin_port}/model.safetensors"

        if warm_seed:
            # Preheat-then-pull (the checkpoint-distribution pattern):
            # the seed completes and VALIDATES before any peer starts, so
            # children ride pure P2P with the certified digest-skip and
            # no back-source race. Seed time is reported separately.
            t_seed = time.perf_counter()
            r = await dfget_lib.download(dfget_lib.DfgetConfig(
                url=url, output=os.path.join(workdir, "seed_warm.bin"),
                daemon_sock=os.path.join(homes["seed"], "run",
                                         "dfdaemon.sock"),
                meta=UrlMeta(digest=f"sha256:{sha}"),
                allow_source_fallback=False, timeout=600.0))
            if r.get("state") != "done":
                raise RuntimeError(f"seed preheat failed: {r}")
            seed_warm_s = time.perf_counter() - t_seed

        ttfps: list[float] = []
        t0 = time.perf_counter()

        async def one_client(i: int) -> None:
            started = time.perf_counter()
            first_piece = [None]

            def on_progress(frame: dict) -> None:
                if (first_piece[0] is None
                        and frame.get("completed_length", 0) > 0):
                    first_piece[0] = time.perf_counter() - started

            out = os.path.join(workdir, f"out{i}.bin")
            result = await dfget_lib.download(
                dfget_lib.DfgetConfig(
                    url=url, output=out,
                    daemon_sock=os.path.join(homes[f"peer{i}"], "run",
                                             "dfdaemon.sock"),
                    meta=UrlMeta(digest=f"sha256:{sha}"),
                    device="tpu" if device_sink else "",
                    pod_broadcast=stripe,
                    allow_source_fallback=False, timeout=600.0),
                on_progress)
            if result.get("state") != "done":
                raise RuntimeError(f"client {i} failed: {result}")
            if device_sink and not result.get("device_verified"):
                raise RuntimeError(
                    f"client {i}: device sink did not verify: {result}")
            ttfps.append(first_piece[0] if first_piece[0] is not None
                         else time.perf_counter() - started)

        def verify_outputs() -> None:
            # Bench instrumentation, OUTSIDE the timed window: the daemons
            # already digest-verify end to end (validate_digest); an extra
            # n_peers × sha256 on the shared core would bill verification
            # to the delivery plane.
            for i in range(n_peers):
                h = hashlib.sha256()   # file_digest needs 3.11; run on 3.10
                with open(os.path.join(workdir, f"out{i}.bin"), "rb") as f:
                    for chunk in iter(lambda: f.read(4 << 20), b""):
                        h.update(chunk)
                if h.hexdigest() != sha:
                    raise RuntimeError(f"client {i} sha mismatch")

        profiles: dict[str, str] = {}
        clients = asyncio.gather(*[one_client(i) for i in range(n_peers)])
        if profile:
            # Sample both roles while the transfer is actually running.
            async def sample():
                await asyncio.sleep(1.0)
                profiles["seed"] = await _grab_profile(
                    seed_metrics, 10.0,
                    os.path.join(workdir, "profile_seed.txt"))
                profiles["peer0"] = await _grab_profile(
                    peer0_metrics, 10.0,
                    os.path.join(workdir, "profile_peer0.txt"))

            sampler = asyncio.ensure_future(sample())
            await clients
            # Wall stops at transfer completion — the profiler's remaining
            # sampling window must not dilute aggregate_gbps.
            wall = time.perf_counter() - t0
            await sampler
        else:
            await clients
            wall = time.perf_counter() - t0
        verify_outputs()

        total_bytes = n_peers * len(content)
        result = {
            "config": "p2p-fanout",
            "peers": n_peers,
            "seed_peers": 1,
            "content_mb": total_mb,
            "aggregate_gbps": round(total_bytes / wall / 1e9, 3),
            "per_peer_mbps": round(total_bytes / wall / n_peers / 1e6, 1),
            "wall_s": round(wall, 2),
            "p50_ttfp_s": round(statistics.median(ttfps), 3),
            "origin_ratio": round(stats["bytes"] / len(content), 3),
            "origin_streams": stats["streams"],
            "origin_concurrency": origin_concurrency,
            "host_cores": os.cpu_count(),
            # Window-quality calibration: AGGREGATE sha256 GB/s over 4
            # concurrent subprocesses, measured BEFORE the fabric spawned
            # (this VM's schedulable CPU swings several-x between
            # measurement windows; the field lets medians be compared
            # like-for-like instead of mixing fast- and slow-window runs).
            "host_hash_gbps": host_hash_gbps,
            "device_sink": device_sink,
        }
        if warm_seed:
            result["warm_seed"] = True
            result["seed_preheat_s"] = round(seed_warm_s, 2)
        if slices:
            # Real-process validation of the ICI-lexicographic rule: the
            # scheduler's own handout counter, not a sim. The seed carries
            # an out-of-band slice label, so "cross" = seed ingress +
            # genuine cross-slice picks.
            picks = {"intra": 0, "cross": 0, "unlabeled": 0}
            try:
                import aiohttp

                from dragonfly2_tpu.pkg.metrics import parse_labeled_samples

                async with aiohttp.ClientSession() as s:
                    async with s.get(
                            f"http://127.0.0.1:{sched_metrics}/metrics",
                            timeout=aiohttp.ClientTimeout(total=5)) as resp:
                        picks.update(parse_labeled_samples(
                            await resp.text(),
                            "dragonfly_tpu_scheduler_parent_picks_total",
                            "locality"))
            except Exception as e:  # noqa: BLE001 - diagnostics only
                picks["scrape_error"] = str(e)
            result["slices"] = slices
            result["parent_picks"] = picks
            labeled = picks["intra"] + picks["cross"]
            if labeled:
                result["intra_slice_frac"] = round(picks["intra"] / labeled, 3)
        if measure_locality:
            # Per-host DCN bytes from each daemon's own locality counters
            # (conductor PIECE_BYTES): cross = bytes that really crossed
            # slices (the seed carries an out-of-band slice label, so seed
            # ingress counts as cross — exactly the DCN bill).
            import aiohttp

            from dragonfly2_tpu.pkg.metrics import parse_labeled_samples

            per_host: dict[str, dict] = {}
            async with aiohttp.ClientSession() as s:
                for i, mport in peer_metrics.items():
                    try:
                        async with s.get(
                                f"http://127.0.0.1:{mport}/metrics",
                                timeout=aiohttp.ClientTimeout(
                                    total=5)) as resp:
                            samples = parse_labeled_samples(
                                await resp.text(),
                                "dragonfly_tpu_peer_piece_bytes_total",
                                "locality")
                    except Exception as e:  # noqa: BLE001 - diagnostics
                        samples = {"scrape_error": str(e)}
                    per_host[f"peer{i}"] = samples
            result["stripe"] = stripe
            result["per_host_dcn_mb"] = {
                name: round(v.get("cross", 0) / (1 << 20), 2)
                for name, v in per_host.items()}
            dcn = [v.get("cross", 0) for v in per_host.values()
                   if isinstance(v.get("cross", 0), int)]
            intra = [v.get("intra", 0) for v in per_host.values()
                     if isinstance(v.get("intra", 0), int)]
            if dcn:
                result["max_host_dcn_mb"] = round(max(dcn) / (1 << 20), 2)
                result["total_dcn_mb"] = round(sum(dcn) / (1 << 20), 2)
                result["total_intra_mb"] = round(sum(intra) / (1 << 20), 2)
        # The seed is the only origin client; its request fan-in must stay
        # within the configured concurrency (+1 for the initial HEAD-like
        # probe) — against real GCS this is per-task request pressure.
        assert stats["streams"] <= origin_concurrency + 1, (
            f"origin saw {stats['streams']} streams > "
            f"{origin_concurrency} configured")
        if profile:
            result["profiles"] = profiles
        return result
    finally:
        for p in procs:
            p.send_signal(signal.SIGTERM)
        for p in procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()
        await runner.cleanup()


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--mb", type=int, default=256)
    ap.add_argument("--peers", type=int, default=8)
    ap.add_argument("--publish", action="store_true",
                    help="record the result in BASELINE.json['published']")
    ap.add_argument("--profile", action="store_true",
                    help="cProfile the seed and one peer mid-bench "
                         "(saves profile_{seed,peer0}.txt in the workdir)")
    ap.add_argument("--warm-seed", action="store_true",
                    help="preheat the seed (complete + validated) before "
                         "the peers start: the pure-P2P pull phase")
    ap.add_argument("--device-sink", action="store_true",
                    help="daemons run a CPU-backend jax device sink; "
                         "clients request device=tpu and require "
                         "device_verified")
    ap.add_argument("--origin-concurrency", type=int, default=4,
                    help="seed's concurrent origin range streams (asserted "
                         "as the origin's observed request fan-in bound)")
    ap.add_argument("--slices", type=int, default=0,
                    help="label peer daemons with N tpu slices and report "
                         "the scheduler's real intra/cross handout counts")
    ap.add_argument("--stripe", action="store_true",
                    help="paired striped-broadcast run: an unstriped "
                         "control then a pod_broadcast (striped) run on "
                         "the same topology, each reporting per-host DCN "
                         "bytes from the daemons' locality counters; "
                         "implies --warm-seed and --slices 2 unless set")
    ap.add_argument("--workdir", default="")
    args = ap.parse_args()

    import tempfile

    workdir = args.workdir or tempfile.mkdtemp(prefix="df-fanout-")
    # Calibrate BEFORE the fabric exists: ~10 daemon processes contending
    # with the calibration children would depress the reading.
    host_hash_gbps = _host_hash_gbps()
    if args.stripe:
        slices = args.slices or 2
        runs = {}
        for mode in ("unstriped", "striped"):
            mode_dir = os.path.join(workdir, mode)
            os.makedirs(mode_dir, exist_ok=True)
            runs[mode] = asyncio.run(run_bench(
                args.mb, args.peers, mode_dir,
                origin_concurrency=args.origin_concurrency,
                # Cold seed on purpose: the pod registers while the seed
                # is still fetching origin, so stripe membership settles
                # before pieces exist — the "checkpoint lands, pod pulls"
                # shape. (Warm-seed striping works too, but the first
                # registrant of a slice can reserve most pieces before
                # its mates' stripe push arrives, blurring the per-host
                # DCN accounting this bench exists to publish.)
                warm_seed=args.warm_seed,
                slices=slices,
                stripe=(mode == "striped"),
                measure_locality=True,
                host_hash_gbps=host_hash_gbps))
        result = {
            "config": "p2p-fanout-striped",
            "striped": runs["striped"],
            "unstriped": runs["unstriped"],
            "speedup": round(runs["striped"]["aggregate_gbps"]
                             / runs["unstriped"]["aggregate_gbps"], 3),
        }
        if runs["striped"].get("total_dcn_mb") and \
                runs["unstriped"].get("total_dcn_mb"):
            result["dcn_bytes_ratio"] = round(
                runs["striped"]["total_dcn_mb"]
                / runs["unstriped"]["total_dcn_mb"], 3)
        print(json.dumps(result))
        if args.publish:
            path = os.path.join(REPO, "BASELINE.json")
            doc = json.load(open(path))
            doc.setdefault("published", {})["config2_fanout_striped"] = result
            with open(path, "w") as f:
                json.dump(doc, f, indent=2)
                f.write("\n")
        return 0
    result = asyncio.run(run_bench(args.mb, args.peers, workdir,
                                   profile=args.profile,
                                   origin_concurrency=args.origin_concurrency,
                                   device_sink=args.device_sink,
                                   warm_seed=args.warm_seed,
                                   slices=args.slices,
                                   host_hash_gbps=host_hash_gbps))
    if args.profile:
        for role, text in (result.get("profiles") or {}).items():
            sys.stderr.write(f"\n=== {role} profile (top cumulative, "
                             f"{workdir}/profile_{role}.txt) ===\n{text}\n")
        result.pop("profiles", None)
    print(json.dumps(result))

    if args.publish:
        path = os.path.join(REPO, "BASELINE.json")
        doc = json.load(open(path))
        # Device-sink runs publish under their own key: overwriting the
        # canonical fan-out baseline would orphan the README's
        # citations into it.
        key = ("config2_fanout_device_sink" if args.device_sink
               else "config2_fanout_warm" if args.warm_seed
               else "config2_fanout")
        doc.setdefault("published", {})[key] = result
        with open(path, "w") as f:
            json.dump(doc, f, indent=2)
            f.write("\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
