"""Hermetic multi-process E2E: real CLI processes on localhost.

SURVEY §4's kind-replacement harness: one scheduler process, one seed
daemon, N peer daemons — all spawned as ``python -m dragonfly2_tpu.cli.main``
subprocesses against an in-test origin. Verification mirrors
test/e2e/v2/dfget_test.go: sha256 of every output AND of the piece store on
the client + seed by task ID.

Marked ``slow``-ish (process spawns); kept to one scenario battery so the
suite stays CI-friendly.
"""

from __future__ import annotations

import asyncio
import hashlib
import json
import glob
import os
import random
import signal
import socket
import subprocess
import sys
import time

import pytest
from aiohttp import web

from dragonfly2_tpu.pkg.piece import Range

CONTENT = bytes(random.Random(77).randbytes(24 * 1024 * 1024))
SHA = hashlib.sha256(CONTENT).hexdigest()


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


async def _start_origin():
    stats = {"streams": 0, "bytes": 0}

    async def blob(request: web.Request) -> web.Response:
        stats["streams"] += 1
        rng = request.headers.get("Range")
        if rng:
            r = Range.parse_http(rng, len(CONTENT))
            data = CONTENT[r.start:r.start + r.length]
            stats["bytes"] += len(data)
            return web.Response(status=206, body=data, headers={
                "Accept-Ranges": "bytes",
                "Content-Range":
                    f"bytes {r.start}-{r.start + r.length - 1}/{len(CONTENT)}"})
        stats["bytes"] += len(CONTENT)
        return web.Response(body=CONTENT, headers={"Accept-Ranges": "bytes"})

    app = web.Application()
    app.router.add_get("/model.bin", blob)
    runner = web.AppRunner(app, access_log=None)
    await runner.setup()
    site = web.TCPSite(runner, "127.0.0.1", 0)
    await site.start()
    return runner, site._server.sockets[0].getsockname()[1], stats


def _spawn(args: list[str], log_path: str,
           jax_cpu: bool = False) -> subprocess.Popen:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    # Child processes must not inherit the test's virtual-device JAX setup
    # (8 CPU devices per daemon = needless threads/memory in an E2E).
    env.pop("XLA_FLAGS", None)
    env.pop("JAX_PLATFORMS", None)
    if jax_cpu:
        # Device-sink daemon: single-device CPU jax backend.
        env["JAX_PLATFORMS"] = "cpu"
    logf = open(log_path, "w")
    return subprocess.Popen(
        [sys.executable, "-m", "dragonfly2_tpu.cli.main", *args],
        stdout=logf, stderr=subprocess.STDOUT, env=env)


def _wait_sock(path: str, timeout: float = 90.0) -> bool:
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if os.path.exists(path):
            return True
        time.sleep(0.1)
    return False


def _store_sha_by_task(work_home: str, task_id: str) -> str | None:
    """sha256 of the piece store's data file for a task (e2e/v2
    util/task.go CalculateSha256ByTaskID analog)."""
    for meta_path in glob.glob(f"{work_home}/**/metadata.json", recursive=True):
        with open(meta_path) as f:
            meta = json.load(f)
        if meta["task_id"] == task_id and meta.get("done"):
            data = os.path.join(os.path.dirname(meta_path), "data")
            h = hashlib.sha256()
            with open(data, "rb") as df:
                while True:
                    chunk = df.read(1 << 20)
                    if not chunk:
                        break
                    h.update(chunk)
            return h.hexdigest()
    return None


class _Fabric:
    """Spawn/teardown helper for real-process scenarios: scheduler + seed +
    N peers as CLI subprocesses, with exit-code collection on teardown
    (the reference e2e's pod-restart-count analog:
    /root/reference/test/e2e/e2e_test.go:34-75)."""

    def __init__(self, tmp_path, peers=("p1", "p2"), seed_yaml: str = ""):
        self.tmp = tmp_path
        self.peer_names = list(peers)
        self.seed_yaml = seed_yaml
        self.procs: dict[str, subprocess.Popen] = {}
        self.homes: dict[str, str] = {}
        self.exit_codes: dict[str, int] = {}
        self.sched_port = 0

    async def start(self, extra_daemon_args: dict | None = None,
                    extra_scheduler_args: list[str] | None = None) -> None:
        extra = extra_daemon_args or {}
        self.sched_port = _free_port()
        self.procs["sched"] = _spawn(
            ["scheduler", "--host", "127.0.0.1",
             "--port", str(self.sched_port),
             *(extra_scheduler_args or [])],
            str(self.tmp / "sched.log"))
        names = ["seed"] + self.peer_names
        for name in names:
            home = str(self.tmp / name)
            self.homes[name] = home
            args = ["daemon", "--work-home", home,
                    "--scheduler", f"127.0.0.1:{self.sched_port}"]
            if name == "seed":
                args.append("--seed-peer")
                if self.seed_yaml:
                    cfg_path = str(self.tmp / "seed_cfg.yaml")
                    with open(cfg_path, "w") as f:
                        f.write(self.seed_yaml)
                    args += ["--config", cfg_path]
            args += extra.get(name, [])
            self.procs[name] = _spawn(args, str(self.tmp / f"{name}.log"))
        for name in names:
            ok = await asyncio.to_thread(
                _wait_sock, f"{self.homes[name]}/run/dfdaemon.sock")
            assert ok, self.log_tail(name)

    def log_tail(self, name: str, n: int = 2000) -> str:
        try:
            return open(self.tmp / f"{name}.log").read()[-n:]
        except OSError:
            return "<no log>"

    def kill(self, name: str, sig=signal.SIGKILL) -> None:
        self.procs[name].send_signal(sig)
        self.exit_codes[name] = self.procs[name].wait(timeout=15)

    async def restart_daemon(self, name: str) -> None:
        """SIGTERM + respawn on the same work home (store reload path)."""
        if self.procs[name].poll() is None:
            self.procs[name].send_signal(signal.SIGTERM)
        self.exit_codes[name] = await asyncio.to_thread(
            self.procs[name].wait, 20)
        # A fresh-spawn readiness check needs the stale socket gone (the
        # daemon usually unlinks it on clean exit; tolerate either).
        try:
            os.remove(f"{self.homes[name]}/run/dfdaemon.sock")
        except FileNotFoundError:
            pass
        args = ["daemon", "--work-home", self.homes[name],
                "--scheduler", f"127.0.0.1:{self.sched_port}"]
        if name == "seed":
            args.append("--seed-peer")
        self.procs[name] = _spawn(args, str(self.tmp / f"{name}.restart.log"))
        ok = await asyncio.to_thread(
            _wait_sock, f"{self.homes[name]}/run/dfdaemon.sock")
        assert ok, self.log_tail(name)

    def dfget(self, name: str, url: str, out: str,
              extra: list[str] | None = None,
              with_digest: bool = True) -> subprocess.Popen:
        # with_digest=False: the task id must match digestless meta (e.g.
        # a preheat-warmed task — digest is part of the id, reference
        # pkg/idgen/task_id.go:65); integrity still holds via the piece
        # chain, and callers sha-verify the output themselves.
        digest = ["--digest", f"sha256:{SHA}"] if with_digest else []
        return _spawn(
            ["dfget", url, "-O", out, "--work-home", self.homes[name],
             "--no-daemon", *digest, *(extra or [])],
            out + ".log")

    async def await_dfget(self, proc: subprocess.Popen, out: str,
                          timeout: float = 120) -> None:
        rc = await asyncio.to_thread(proc.wait, timeout)
        assert rc == 0, open(out + ".log").read()[-2000:]
        with open(out, "rb") as f:
            assert hashlib.sha256(f.read()).hexdigest() == SHA

    async def teardown(self) -> None:
        for name, p in self.procs.items():
            if p.poll() is None:
                p.send_signal(signal.SIGTERM)
        for name, p in self.procs.items():
            try:
                self.exit_codes.setdefault(name, p.wait(timeout=10))
            except subprocess.TimeoutExpired:
                p.kill()
                self.exit_codes[name] = p.wait()


def _wait_first_piece(homes: list[str], timeout: float = 60.0) -> bool:
    """Block until any task data file under any home has bytes — the
    'transfer is mid-flight' trigger for kill scenarios."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        for home in homes:
            for data in glob.glob(f"{home}/**/data", recursive=True):
                try:
                    if os.path.getsize(data) > 0:
                        return True
                except OSError:
                    pass
        time.sleep(0.05)
    return False


def test_multiprocess_fanout(run_async, tmp_path):
    """scheduler + seed + 2 peer daemon PROCESSES; dfget from both peers:
    outputs sha-verify, stores sha-verify on every node, origin served ~one
    copy through the seed."""

    async def run():
        runner, origin_port, stats = await _start_origin()
        sched_port = _free_port()
        procs: list[subprocess.Popen] = []
        homes = {name: str(tmp_path / name) for name in ("seed", "p1", "p2")}
        try:
            procs.append(_spawn(
                ["scheduler", "--host", "127.0.0.1", "--port", str(sched_port)],
                str(tmp_path / "sched.log")))
            await asyncio.sleep(0)
            procs.append(_spawn(
                ["daemon", "--work-home", homes["seed"], "--seed-peer",
                 "--scheduler", f"127.0.0.1:{sched_port}"],
                str(tmp_path / "seed.log")))
            procs.append(_spawn(
                ["daemon", "--work-home", homes["p1"],
                 "--scheduler", f"127.0.0.1:{sched_port}"],
                str(tmp_path / "p1.log")))
            procs.append(_spawn(
                ["daemon", "--work-home", homes["p2"],
                 "--scheduler", f"127.0.0.1:{sched_port}"],
                str(tmp_path / "p2.log")))
            for name in homes:
                ok = await asyncio.to_thread(
                    _wait_sock, f"{homes[name]}/run/dfdaemon.sock")
                assert ok, open(tmp_path / f"{name}.log").read()[-2000:]

            url = f"http://127.0.0.1:{origin_port}/model.bin"

            def dfget(home: str, out: str) -> subprocess.Popen:
                return _spawn(
                    ["dfget", url, "-O", out, "--work-home", home,
                     "--no-daemon", "--digest", f"sha256:{SHA}"],
                    out + ".log")

            outs = [str(tmp_path / "out1.bin"), str(tmp_path / "out2.bin")]
            downloads = [dfget(homes["p1"], outs[0]),
                         dfget(homes["p2"], outs[1])]
            # Wait OFF the event loop: the origin server lives in this test
            # process, so a blocking Popen.wait would starve it.
            for p, out in zip(downloads, outs):
                rc = await asyncio.to_thread(p.wait, 120)
                assert rc == 0, open(out + ".log").read()[-2000:]

            # Output integrity on both clients (dfget_test.go:26-76 style).
            for out in outs:
                with open(out, "rb") as f:
                    assert hashlib.sha256(f.read()).hexdigest() == SHA

            # Store integrity by task id on every node incl. the seed.
            task_id = None
            for meta_path in glob.glob(f"{homes['p1']}/**/metadata.json",
                                       recursive=True):
                task_id = json.load(open(meta_path))["task_id"]
            assert task_id
            for name, home in homes.items():
                assert _store_sha_by_task(home, task_id) == SHA, name

            # Origin bandwidth: the seed's fetch only (≲1.5 copies allows
            # ranged back-source groups).
            assert stats["bytes"] <= int(len(CONTENT) * 1.5), stats
        finally:
            for p in procs:
                p.send_signal(signal.SIGTERM)
            for p in procs:
                try:
                    p.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    p.kill()
            await runner.cleanup()

    run_async(run())


def test_dfget_cold_host_auto_spawn_joins_p2p(run_async, tmp_path):
    """A COLD host (no daemon running, empty work-home) runs plain dfget
    with --scheduler: the CLI health-checks the socket, forks a daemon
    wired to the scheduler, waits for the handshake, and the download
    rides P2P (p2p=True) off the seed — mirroring
    cmd/dfget/cmd/root.go:251-340 where dfget spawns dfdaemon on demand.
    Direct-source remains the final fallback but must NOT be what happens
    here."""

    async def run():
        runner, origin_port, stats = await _start_origin()
        fab = _Fabric(tmp_path, peers=())
        spawned_home = str(tmp_path / "coldhost")
        try:
            await fab.start()   # scheduler + seed only
            url = f"http://127.0.0.1:{origin_port}/model.bin"
            # Warm the seed so the cold host's pull is served P2P.
            warm = str(tmp_path / "warm.bin")
            await fab.await_dfget(fab.dfget("seed", url, warm,
                                            with_digest=False), warm)
            bytes_warm = stats["bytes"]

            out = str(tmp_path / "cold.bin")
            p = _spawn(
                ["dfget", url, "-O", out, "--work-home", spawned_home,
                 "--scheduler", f"127.0.0.1:{fab.sched_port}"],
                out + ".log")
            rc = await asyncio.to_thread(p.wait, 120)
            log_text = open(out + ".log").read()
            assert rc == 0, log_text[-2000:]
            with open(out, "rb") as f:
                assert hashlib.sha256(f.read()).hexdigest() == SHA
            assert "spawned daemon" in log_text, log_text[-1000:]
            assert "p2p=True" in log_text, log_text[-1000:]
            # Pure P2P: the cold host's pull added no origin traffic.
            assert stats["bytes"] == bytes_warm, stats
        finally:
            subprocess.run(["pkill", "-f", spawned_home],
                           capture_output=True)
            await fab.teardown()
            await runner.cleanup()

    run_async(run(), timeout=240)


def test_multiprocess_seed_death(run_async, tmp_path):
    """SIGKILL the seed PROCESS mid-transfer: both peers still land
    sha-exact (reschedule onto each other + bounded back-source), and the
    collected exit code proves the kill was a real process death."""

    async def run():
        runner, origin_port, stats = await _start_origin()
        # Rate-limit seed serving so the kill lands mid-transfer.
        fab = _Fabric(tmp_path, seed_yaml="upload:\n  rate_limit: 4194304\n")
        try:
            await fab.start()
            url = f"http://127.0.0.1:{origin_port}/model.bin"
            outs = [str(tmp_path / "o1.bin"), str(tmp_path / "o2.bin")]
            dls = [fab.dfget("p1", url, outs[0]),
                   fab.dfget("p2", url, outs[1])]

            hit = await asyncio.to_thread(
                _wait_first_piece, [fab.homes["p1"], fab.homes["p2"]])
            assert hit, "no piece landed on any peer before timeout"
            await asyncio.to_thread(fab.kill, "seed", signal.SIGKILL)
            assert fab.exit_codes["seed"] == -signal.SIGKILL

            for p, out in zip(dls, outs):
                await fab.await_dfget(p, out)
            # Bounded origin re-touch: seed's partial + ≤1 remainder/peer.
            assert stats["bytes"] <= 3 * len(CONTENT) + (1 << 20), stats
        finally:
            await fab.teardown()
            await runner.cleanup()

    run_async(run(), timeout=240)


def test_multiprocess_scheduler_death(run_async, tmp_path):
    """SIGKILL the scheduler PROCESS mid-transfer: with source fallback
    permitted the in-flight download still lands sha-exact (conductor
    demotion), and a FRESH dfget after the death also lands (registration
    ring failover → back-source demotion)."""

    async def run():
        runner, origin_port, stats = await _start_origin()
        fab = _Fabric(tmp_path, peers=("p1",),
                      seed_yaml="upload:\n  rate_limit: 4194304\n")
        try:
            await fab.start()
            url = f"http://127.0.0.1:{origin_port}/model.bin"
            out1 = str(tmp_path / "s1.bin")
            dl = fab.dfget("p1", url, out1)
            hit = await asyncio.to_thread(
                _wait_first_piece, [fab.homes["p1"]])
            assert hit, "no piece landed before timeout"
            await asyncio.to_thread(fab.kill, "sched", signal.SIGKILL)
            assert fab.exit_codes["sched"] == -signal.SIGKILL
            await fab.await_dfget(dl, out1)

            # Schedulerless cold task: a DIFFERENT task id (range variant)
            # from the same daemon must still land via demotion.
            out2 = str(tmp_path / "s2.bin")
            p = _spawn(["dfget", url, "-O", out2,
                        "--work-home", fab.homes["p1"], "--no-daemon",
                        "--range", "0-1048575"], out2 + ".log")
            rc = await asyncio.to_thread(p.wait, 120)
            assert rc == 0, open(out2 + ".log").read()[-2000:]
            with open(out2, "rb") as f:
                got = f.read()
            assert got == CONTENT[:1048576]
        finally:
            await fab.teardown()
            await runner.cleanup()

    run_async(run(), timeout=240)


def test_multiprocess_daemon_restart_reuse(run_async, tmp_path):
    """Restart a peer daemon PROCESS after a download: clean SIGTERM exit
    (code 0 — restart-count hygiene), store reloads from disk, and a second
    dfget is a warm reuse that never touches the origin again."""

    async def run():
        runner, origin_port, stats = await _start_origin()
        fab = _Fabric(tmp_path, peers=("p1",))
        try:
            await fab.start()
            url = f"http://127.0.0.1:{origin_port}/model.bin"
            out1 = str(tmp_path / "r1.bin")
            await fab.await_dfget(fab.dfget("p1", url, out1), out1)
            bytes_before = stats["bytes"]

            await fab.restart_daemon("p1")
            assert fab.exit_codes["p1"] == 0, \
                f"daemon SIGTERM exit {fab.exit_codes['p1']}"

            out2 = str(tmp_path / "r2.bin")
            await fab.await_dfget(fab.dfget("p1", url, out2), out2)
            assert stats["bytes"] == bytes_before, \
                "reuse after restart must not re-touch the origin"
            assert "reuse=True" in open(out2 + ".log").read()
        finally:
            await fab.teardown()
            await runner.cleanup()

    run_async(run(), timeout=240)


def test_multiprocess_device_sink(run_async, tmp_path):
    """A peer daemon PROCESS with a CPU-backend jax device sink: dfget
    --device tpu lands the bytes on disk (sha-exact) AND in the daemon's
    device Array, reported as device_verified; warm reuse re-finalizes
    the sink without touching the origin."""

    async def run():
        runner, origin_port, stats = await _start_origin()
        fab = _Fabric(tmp_path, peers=())
        try:
            await fab.start()
            home = str(tmp_path / "dp")
            fab.homes["dp"] = home
            fab.procs["dp"] = _spawn(
                ["daemon", "--work-home", home, "--device-sink",
                 "--scheduler", f"127.0.0.1:{fab.sched_port}"],
                str(tmp_path / "dp.log"), jax_cpu=True)
            ok = await asyncio.to_thread(_wait_sock, f"{home}/run/dfdaemon.sock")
            assert ok, fab.log_tail("dp")

            url = f"http://127.0.0.1:{origin_port}/model.bin"
            out1 = str(tmp_path / "d1.bin")
            p = _spawn(["dfget", url, "-O", out1, "--work-home", home,
                        "--no-daemon", "--device", "tpu",
                        "--digest", f"sha256:{SHA}"], out1 + ".log")
            await fab.await_dfget(p, out1, timeout=180)
            log1 = open(out1 + ".log").read()
            assert "device_verified=True" in log1, log1[-800:]
            # The daemon names the device that holds the bytes: here the
            # CPU backend's, which must not read as a chip.
            assert "device=cpu/cpu" in log1, log1[-800:]
            bytes_cold = stats["bytes"]

            # Warm: reuse must re-finalize the sink, origin untouched.
            out2 = str(tmp_path / "d2.bin")
            p = _spawn(["dfget", url, "-O", out2, "--work-home", home,
                        "--no-daemon", "--device", "tpu",
                        "--digest", f"sha256:{SHA}"], out2 + ".log")
            await fab.await_dfget(p, out2, timeout=120)
            log2 = open(out2 + ".log").read()
            assert "reuse=True" in log2, log2[-800:]
            assert "device_verified=True" in log2, log2[-800:]
            assert stats["bytes"] == bytes_cold
        finally:
            await fab.teardown()
            await runner.cleanup()

    run_async(run(), timeout=300)


def test_dfget_device_request_without_a_landing_exits_nonzero(run_async,
                                                              tmp_path):
    """dfget --device tpu against a daemon with no device sink: the disk
    result stands (sha-exact), but the request asked for the device, so
    the command exits 1 and says the content did not land there."""

    async def run():
        runner, origin_port, _ = await _start_origin()
        fab = _Fabric(tmp_path, peers=("p1",))
        try:
            await fab.start()
            url = f"http://127.0.0.1:{origin_port}/model.bin"
            out = str(tmp_path / "nodev.bin")
            p = fab.dfget("p1", url, out, extra=["--device", "tpu"])
            rc = await asyncio.to_thread(p.wait, 120)
            log = open(out + ".log").read()
            assert rc == 1, log[-800:]
            assert "device_verified=False" in log, log[-800:]
            assert "did not land in the device sink" in log, log[-800:]
            with open(out, "rb") as f:
                assert hashlib.sha256(f.read()).hexdigest() == SHA

            # A dfget process that imported jax (it never may: the chip
            # belongs to the daemon with the sink) finishes its download
            # and then says so and exits 1 — no traceback over the result.
            out2 = str(tmp_path / "withjax.bin")
            env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=os.path
                       .dirname(os.path.dirname(os.path.abspath(__file__))))
            env.pop("XLA_FLAGS", None)
            p = await asyncio.to_thread(
                subprocess.run,
                [sys.executable, "-c",
                 "import sys, jax; from dragonfly2_tpu.cli.main import main; "
                 "sys.exit(main(sys.argv[1:]))", "dfget", url, "-O", out2,
                 "--work-home", fab.homes["p1"], "--no-daemon",
                 "--digest", f"sha256:{SHA}"],
                env=env, capture_output=True, text=True, timeout=120)
            assert p.returncode == 1, p.stderr[-800:]
            assert "imported jax" in p.stderr and "Traceback" not in p.stderr
            with open(out2, "rb") as f:
                assert hashlib.sha256(f.read()).hexdigest() == SHA
        finally:
            await fab.teardown()
            await runner.cleanup()

    run_async(run(), timeout=240)


def test_multiprocess_manager_preheat(run_async, tmp_path):
    """The full preheat call stack across real PROCESSES (SURVEY §3.4):
    manager REST job -> manager drpc queue -> scheduler job worker ->
    seed-task trigger -> seed daemon back-sources -> store sha-exact.
    Afterwards a peer dfget rides pure P2P: the origin byte count must
    not grow. Reference posture: test/e2e + manager preheat handlers
    (/root/reference/manager/job/preheat.go, scheduler/job/job.go)."""

    async def run():
        from aiohttp import ClientSession

        runner, origin_port, stats = await _start_origin()
        rest_port, drpc_port = _free_port(), _free_port()
        fab = _Fabric(tmp_path, peers=("p1",))
        mgr = _spawn(
            ["manager", "--host", "127.0.0.1", "--port", str(rest_port),
             "--grpc-port", str(drpc_port),
             "--db", str(tmp_path / "manager.db")],
            str(tmp_path / "manager.log"))
        fab.procs["manager"] = mgr
        base = f"http://127.0.0.1:{rest_port}"
        try:
            async with ClientSession() as http:
                for _ in range(300):
                    try:
                        async with http.get(f"{base}/healthy") as r:
                            if r.status == 200:
                                break
                    except Exception:
                        pass
                    await asyncio.sleep(0.1)
                else:
                    raise AssertionError(
                        "manager never healthy: " + fab.log_tail("manager"))

                # Scheduler AFTER the manager: it registers over drpc and
                # its job worker long-polls the cluster queue.
                await fab.start(extra_scheduler_args=[
                    "--manager", f"127.0.0.1:{drpc_port}"])
                url = f"http://127.0.0.1:{origin_port}/model.bin"

                async with http.post(
                        f"{base}/api/v1/users/signin",
                        json={"name": "root", "password": "dragonfly"}) as r:
                    assert r.status == 200, await r.text()
                    hdr = {"Authorization":
                           f"Bearer {(await r.json())['token']}"}
                async with http.post(
                        f"{base}/api/v1/jobs", headers=hdr,
                        json={"type": "preheat",
                              "args": {"type": "file", "url": url}}) as r:
                    assert r.status == 200, await r.text()
                    job_id = (await r.json())["id"]

                state = "PENDING"
                for _ in range(600):
                    async with http.get(f"{base}/api/v1/jobs/{job_id}",
                                        headers=hdr) as r:
                        state = (await r.json())["state"]
                    if state in ("SUCCESS", "FAILURE"):
                        break
                    await asyncio.sleep(0.2)
                assert state == "SUCCESS", (
                    state, fab.log_tail("sched"), fab.log_tail("seed"))

            # The preheat landed on the seed: a done store, sha-exact.
            task_id = None
            for meta_path in glob.glob(
                    f"{fab.homes['seed']}/**/metadata.json", recursive=True):
                meta = json.load(open(meta_path))
                if meta.get("done"):
                    task_id = meta["task_id"]
            assert task_id, fab.log_tail("seed")
            assert _store_sha_by_task(fab.homes["seed"], task_id) == SHA
            bytes_after_preheat = stats["bytes"]
            assert bytes_after_preheat <= int(len(CONTENT) * 1.5), stats

            # A peer pull after the preheat is pure P2P: origin untouched.
            # Digestless meta so the task id matches the preheat's
            # (a digest-pinned request is a DISTINCT task by design —
            # reference pkg/idgen/task_id.go:65).
            out = str(tmp_path / "warm.bin")
            p = fab.dfget("p1", url, out, with_digest=False)
            await fab.await_dfget(p, out, timeout=120)
            assert stats["bytes"] == bytes_after_preheat, stats
        finally:
            await fab.teardown()
            await runner.cleanup()

    run_async(run(), timeout=300)


def test_multiprocess_ici_slice_affinity(run_async, tmp_path):
    """Four peer daemons in two labeled slices + a seed: the scheduler's
    parent_picks counter (scraped from its real /metrics endpoint) must
    record intra-slice handouts — the ICI-lexicographic ranking and the
    warming-relay rule working across real process boundaries, not a sim.
    Every output stays sha-exact and the origin serves ~one copy."""

    async def run():
        import aiohttp

        runner, origin_port, stats = await _start_origin()
        metrics_port = _free_port()
        fab = _Fabric(tmp_path, peers=("p1", "p2", "p3", "p4"),
                      # Rate-limit the seed so transfers overlap: peers
                      # must find each other (and their slice-mates) as
                      # parents rather than all riding the seed.
                      seed_yaml="upload:\n  rate_limit: 16777216\n")
        try:
            await fab.start(
                extra_daemon_args={
                    "seed": ["--tpu-slice", "slice-seed"],
                    "p1": ["--tpu-slice", "slice-a", "--tpu-worker-index", "0"],
                    "p2": ["--tpu-slice", "slice-a", "--tpu-worker-index", "1"],
                    "p3": ["--tpu-slice", "slice-b", "--tpu-worker-index", "0"],
                    "p4": ["--tpu-slice", "slice-b", "--tpu-worker-index", "1"],
                },
                extra_scheduler_args=["--metrics-port", str(metrics_port)])
            url = f"http://127.0.0.1:{origin_port}/model.bin"
            outs = {n: str(tmp_path / f"{n}.bin")
                    for n in ("p1", "p2", "p3", "p4")}
            dls = {n: fab.dfget(n, url, out) for n, out in outs.items()}
            for n, p in dls.items():
                await fab.await_dfget(p, outs[n])

            from dragonfly2_tpu.pkg.metrics import parse_labeled_samples

            picks = {"intra": 0, "cross": 0, "unlabeled": 0}
            async with aiohttp.ClientSession() as s:
                async with s.get(
                        f"http://127.0.0.1:{metrics_port}/metrics",
                        timeout=aiohttp.ClientTimeout(total=10)) as resp:
                    assert resp.status == 200
                    body = await resp.text()
            picks.update(parse_labeled_samples(
                body, "dragonfly_tpu_scheduler_parent_picks_total",
                "locality"))
            # Every daemon carries a slice label, so no handout may be
            # unlabeled; and with two 2-peer slices pulling concurrently
            # at a throttled seed, at least one intra-slice handout must
            # occur (the pairs discover each other).
            assert picks["unlabeled"] == 0, picks
            assert picks["intra"] >= 1, picks
            assert picks["cross"] >= 1, picks  # seed ingress is cross
            # Origin economy holds under the slice labels.
            assert stats["bytes"] <= int(len(CONTENT) * 1.5), stats
        finally:
            await fab.teardown()
            await runner.cleanup()

    run_async(run(), timeout=240)
