"""The fabric on loopback: origin, scheduler and seed peer as children that
never import jax; the peer daemon that holds the device embedded in this
process. Copied from ``chip_smoke.py`` (PR 22), which passed on the v5e, so
that later changes to the smoke cannot move the yardstick.
"""

from __future__ import annotations

import asyncio
import contextlib
import json
import os
import signal
import socket
import subprocess
import sys
import tempfile
import time
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
LOOPBACK = "127.0.0.1"


class BenchFailure(Exception):
    pass


def free_port() -> int:
    with socket.socket() as s:
        s.bind((LOOPBACK, 0))
        return s.getsockname()[1]


def accepts(port: int) -> bool:
    with socket.socket() as s:
        s.settimeout(0.5)
        return s.connect_ex((LOOPBACK, port)) == 0


async def wait_for(what: str, ready, deadline_s: float):
    end = time.monotonic() + deadline_s
    while time.monotonic() < end:
        got = ready()
        if got:
            return got
        await asyncio.sleep(0.05)
    raise BenchFailure(f"wait expired after {deadline_s:.0f}s: {what}")


def scratch_home() -> str:
    """A new directory for this run's DF_HOME: inside the checkout, else
    under the temporary directory, wherever the daemon's unix socket path
    stays inside the 108 bytes such a path may have."""
    parents = (os.path.join(HERE, ".run"), tempfile.gettempdir())
    for parent in parents:
        sock = os.path.join(parent, "r_12345678", "seed", "run",
                            "dfdaemon.sock")
        if len(sock) <= 100:
            os.makedirs(parent, exist_ok=True)
            return tempfile.mkdtemp(prefix="r_", dir=parent)
    raise BenchFailure("the daemon's unix socket path would be too long "
                       f"under any of {parents}")


class Fabric:
    def __init__(self, home: str, config_file: str, config: dict, seed: int):
        self.home = home
        self.config_file = config_file
        self.config = config
        self.seed = seed
        self.children: dict[str, subprocess.Popen] = {}
        self.daemon = None
        self.origin_port = 0
        self.seed_home = os.path.join(home, "seed")

    def log_path(self, name: str) -> str:
        return os.path.join(self.home, f"{name}.log")

    def spawn(self, name: str, argv: list[str]) -> None:
        env = dict(os.environ, PYTHONPATH=REPO, DF_HOME=self.home)
        with open(self.log_path(name), "wb") as logf:
            self.children[name] = subprocess.Popen(
                [sys.executable, *argv], cwd=REPO, env=env, stdout=logf,
                stderr=subprocess.STDOUT, start_new_session=True)

    def alive(self, name: str) -> bool:
        if self.children[name].poll() is not None:
            raise BenchFailure(
                f"{name} exited with code {self.children[name].returncode}")
        return True

    def spawn_early(self) -> None:
        """Origin and scheduler, started before this process imports jax,
        so that they come up while the chip is being initialised."""
        self.port_file = os.path.join(self.home, "origin.port")
        self.spawn("origin", [os.path.join(HERE, "origin.py"),
                              self.config_file, str(self.seed),
                              self.port_file])
        self.sched_port = free_port()
        sched_cfg = os.path.join(self.home, "scheduler.yaml")
        with open(sched_cfg, "w") as f:
            f.write(f"server:\n  advertise_ip: {LOOPBACK}\n")
        self.spawn("scheduler", [
            "-m", "dragonfly2_tpu.cli.main", "scheduler", "--config",
            sched_cfg, "--host", LOOPBACK, "--port", str(self.sched_port)])

    async def start(self, mark=lambda what: None) -> None:
        from dragonfly2_tpu.daemon.config import DaemonConfig
        from dragonfly2_tpu.daemon.daemon import Daemon

        sched_port = self.sched_port
        await wait_for("scheduler port",
                       lambda: self.alive("scheduler") and accepts(sched_port),
                       60)
        mark("scheduler accepts")
        # Every role advertises loopback through config it already reads:
        # the sealed machine has no route for the daemon's UDP-connect
        # guess to find, and no interface worth advertising.
        seed_cfg = os.path.join(self.home, "seed.yaml")
        with open(seed_cfg, "w") as f:
            f.write(f"host:\n  ip: {LOOPBACK}\n  hostname: bench-seed\n")
        self.spawn("seed", [
            "-m", "dragonfly2_tpu.cli.main", "daemon", "--config", seed_cfg,
            "--work-home", self.seed_home, "--seed-peer",
            "--scheduler", f"{LOOPBACK}:{sched_port}"])
        sink = self.config["deployment"]["sink"]
        cfg = DaemonConfig(work_home=os.path.join(self.home, "peer"))
        cfg.host.ip = LOOPBACK
        cfg.host.hostname = "bench-peer"
        cfg.scheduler.addrs = [f"{LOOPBACK}:{sched_port}"]
        cfg.tpu_sink.enabled = True
        cfg.tpu_sink.max_tasks = int(sink["max_tasks"])
        cfg.tpu_sink.batch_pieces = int(sink["batch_pieces"])
        self.daemon = Daemon(cfg)
        await asyncio.wait_for(self.daemon.start(), 60)
        mark("embedded daemon up")
        await wait_for(
            "seed daemon socket",
            lambda: self.alive("seed") and os.path.exists(self.seed_sock), 60)
        mark("seed peer up")
        await wait_for(
            "origin port file",
            lambda: self.alive("origin") and os.path.exists(self.port_file),
            300)
        with open(self.port_file) as f:
            self.origin_port = int(f.read())
        mark("origin serving")

    @property
    def seed_sock(self) -> str:
        return os.path.join(self.seed_home, "run", "dfdaemon.sock")

    def url(self, index: int) -> str:
        return f"http://{LOOPBACK}:{self.origin_port}/o/{index}"

    def origin_json(self, path: str, timeout: float = 300) -> dict:
        with urllib.request.urlopen(
                f"http://{LOOPBACK}:{self.origin_port}{path}",
                timeout=timeout) as r:
            return json.load(r)

    async def delete_everywhere(self, task_id: str) -> None:
        """Drop a finished task from the peer's store and the seed's, so
        the disk does not fill. The seed refuses while it still serves the
        task; a task it keeps is reported, not fatal."""
        from dragonfly2_tpu.pkg.types import NetAddr
        from dragonfly2_tpu.rpc import Client

        await asyncio.to_thread(
            self.daemon.task_manager.storage.delete_task, task_id)
        cli = Client(NetAddr.unix(self.seed_sock))
        try:
            for _ in range(20):
                reply = await cli.call("Daemon.DeleteTask",
                                       {"task_id": task_id}, timeout=10.0)
                if reply.get("ok"):
                    return
                await asyncio.sleep(0.1)
            print(f"[chipbench] seed kept task {task_id[:16]}: {reply}",
                  flush=True)
        finally:
            await cli.close()

    async def stop(self) -> None:
        daemon, self.daemon = self.daemon, None
        if daemon is not None:
            with contextlib.suppress(Exception):
                await asyncio.wait_for(daemon.stop(), 30)
        for proc in self.children.values():
            if proc.poll() is None:
                with contextlib.suppress(ProcessLookupError):
                    os.killpg(proc.pid, signal.SIGTERM)
        for proc in self.children.values():
            try:
                proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                with contextlib.suppress(ProcessLookupError):
                    os.killpg(proc.pid, signal.SIGKILL)
                proc.wait()

    def log_tails(self, n: int = 1500) -> str:
        tails = []
        for name in self.children:
            try:
                with open(self.log_path(name), errors="replace") as f:
                    tails.append(f"--- {name}.log (tail)\n{f.read()[-n:]}")
            except OSError:
                tails.append(f"--- {name}.log: unreadable")
        return "\n".join(tails)
