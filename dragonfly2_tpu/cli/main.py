"""``df`` multi-command CLI.

Reference: cmd/ — one cobra binary per role; we expose one Python entry with
subcommands: dfget, daemon, scheduler, manager, dfcache, dfstore.
``python -m dragonfly2_tpu.cli.main <cmd> ...`` or the ``df`` console script.
"""

from __future__ import annotations

import argparse
import asyncio
import os
import subprocess
import sys
import time

from dragonfly2_tpu.pkg import dflog
from dragonfly2_tpu.pkg.dfpath import Dfpath
from dragonfly2_tpu.pkg.types import format_size

log = dflog.get("cli")


def assert_no_jax(role: str) -> None:
    """A chip belongs to one process. The roles that hold no device sink
    never import jax, so that they never take the chip from the one that
    does; a lazy import that creeps in fails here, not as a hang there."""
    if "jax" in sys.modules:
        raise RuntimeError(f"{role} imported jax without a device sink")


def _add_dfget(sub: argparse._SubParsersAction) -> None:
    p = sub.add_parser("dfget", help="download a file through the P2P fabric")
    p.add_argument("url", help="source URL (http/https/file/gs)")
    p.add_argument("-O", "--output", default="",
                   help="output path (optional with --device tpu)")
    p.add_argument("--device", default="", choices=["", "tpu"],
                   help="also land verified pieces into the daemon's TPU "
                        "HBM sink (requires tpu_sink.enabled in the daemon)")
    p.add_argument("--tag", default="", help="task isolation tag")
    p.add_argument("--application", default="")
    p.add_argument("--tenant", default="",
                   help="QoS attribution tag: every byte this download "
                        "moves is accounted (and rate-shared) under this "
                        "tenant; burning tenants get deprioritized")
    p.add_argument("--priority", type=int, default=3,
                   help="QoS priority 0-6 (>=5 interactive, 3-4 normal, "
                        "<=2 background) — sets the weighted-fair "
                        "dispatch class on every daemon on the path")
    p.add_argument("--digest", default="", help="expected digest algo:hex")
    p.add_argument("--filter", default="", help="'&'-separated query params to ignore")
    p.add_argument("--range", dest="range_", default="", help="byte range a-b")
    p.add_argument("--header", action="append", default=[], help="k:v (repeatable)")
    p.add_argument("--disable-back-source", action="store_true")
    p.add_argument("--pod-broadcast", action="store_true",
                   help="register as a striped slice broadcast: each "
                        "same-slice host DCN-pulls 1/S of the pieces and "
                        "the slice completes the copy internally")
    p.add_argument("--delta-base", default="",
                   help="task id of a locally-landed base version: chunks "
                        "the base already holds are copied (and verified) "
                        "locally, only changed chunks cross the wire as "
                        "ranged P2P tasks (checkpoint-delta plane)")
    p.add_argument("--explain", action="store_true",
                   help="after the download, print the flight recorder's "
                        "critical-path autopsy (phase breakdown + per-piece "
                        "waterfall) — where the wall time went")
    p.add_argument("--pod", action="store_true",
                   help="also fetch the scheduler's merged cross-host pod "
                        "timeline for this task (clock-aligned per-host "
                        "phase bars, slowest host named) — the same "
                        "waterfall /debug/pod/<task_id>/timeline?format="
                        "text renders")
    p.add_argument("--cluster", action="store_true",
                   help="with --explain and --manager: also fetch and "
                        "print the manager's merged cluster control-tower "
                        "view (per-scheduler fleet rollup, stragglers "
                        "attributed to their owning scheduler) — the same "
                        "view /debug/cluster?format=text renders")
    p.add_argument("--recursive", action="store_true")
    p.add_argument("--level", type=int, default=5, help="recursion depth")
    p.add_argument("--timeout", type=float, default=0.0)
    p.add_argument("--work-home", default="")
    p.add_argument("--no-daemon", action="store_true", help="never spawn a daemon")
    p.add_argument("--scheduler", action="append", default=[],
                   help="scheduler host:port handed to an auto-spawned "
                        "daemon (repeatable) — a cold host joins the P2P "
                        "fabric on first dfget (reference "
                        "cmd/dfget/cmd/root.go:251-340)")
    p.add_argument("--manager", default="",
                   help="manager drpc host:port for the auto-spawned daemon")
    p.set_defaults(func=_run_dfget)


def _run_dfget(args: argparse.Namespace) -> int:
    from dragonfly2_tpu.client import dfget as dfget_lib
    from dragonfly2_tpu.proto.common import UrlMeta

    path = Dfpath(args.work_home) if args.work_home else Dfpath()
    header = {}
    for h in args.header:
        k, _, v = h.partition(":")
        header[k.strip()] = v.strip()
    meta = UrlMeta(digest=args.digest, tag=args.tag, filter=args.filter,
                   application=args.application, header=header,
                   range=args.range_, priority=args.priority,
                   tenant=args.tenant)
    cfg = dfget_lib.DfgetConfig(
        url=args.url,
        output=args.output,
        daemon_sock=path.daemon_sock,
        meta=meta,
        disable_back_source=args.disable_back_source,
        recursive=args.recursive,
        level=args.level,
        timeout=args.timeout,
        device=args.device,
        pod_broadcast=args.pod_broadcast,
        explain=args.explain,
        pod=args.pod,
        delta_base=args.delta_base,
    )
    if not args.output and args.device != "tpu":
        sys.stderr.write("dfget: error: -O/--output is required "
                         "(optional only with --device tpu)\n")
        return 2

    async def run() -> int:
        if not args.no_daemon and not await dfget_lib.is_daemon_alive(path.daemon_sock):
            _spawn_daemon(path, device_sink=(args.device == "tpu"),
                          schedulers=args.scheduler, manager=args.manager)
            await _wait_daemon(path.daemon_sock)
        start = time.monotonic()
        state = {"last": 0}

        def on_progress(msg: dict) -> None:
            if msg.get("state") != "running":
                return
            done = msg.get("completed_length", 0)
            total = msg.get("content_length", -1)
            if done - state["last"] >= (8 << 20) or done == total:
                state["last"] = done
                pct = f"{100 * done / total:5.1f}%" if total > 0 else "  ?  "
                sys.stderr.write(f"\r{pct} {format_size(done)}")
                sys.stderr.flush()

        try:
            result = await dfget_lib.download(cfg, on_progress)
        finally:
            # One-shot process: close any source-fallback session pool
            # cleanly instead of leaking it to interpreter exit.
            from dragonfly2_tpu.source.client import default_registry

            await default_registry().close_all()
        elapsed = time.monotonic() - start
        size = result.get("completed_length", 0)
        rate = size / elapsed if elapsed > 0 else 0
        sys.stderr.write(
            f"\rdownloaded {format_size(size)} in {elapsed:.2f}s "
            f"({format_size(int(rate))}/s) task={result.get('task_id', '')[:16]} "
            f"reuse={result.get('from_reuse', False)} p2p={result.get('from_p2p', False)}"
            + (f" device_verified={result.get('device_verified', False)}"
               f" device={result.get('device_platform', '') or '-'}"
               f"/{result.get('device_kind', '') or '-'}"
               if cfg.device else "") + "\n"
        )
        flight_info = result.get("flight") or {}
        if args.explain and flight_info.get("text"):
            from dragonfly2_tpu import qos

            sys.stderr.write(
                f"qos: tenant={qos.normalize_tenant(args.tenant)} "
                f"class={qos.class_of(args.priority)} "
                f"(priority={args.priority})\n")
            sys.stderr.write(flight_info["text"] + "\n")
        pod_info = result.get("pod") or {}
        if args.pod and pod_info.get("text"):
            sys.stderr.write(pod_info["text"] + "\n")
        if args.cluster:
            if not args.manager:
                sys.stderr.write("dfget: --cluster needs --manager "
                                 "host:port\n")
            else:
                try:
                    from dragonfly2_tpu.manager.client import ManagerClient
                    from dragonfly2_tpu.pkg.types import NetAddr

                    mhost, _, mport = args.manager.rpartition(":")
                    mc = ManagerClient(NetAddr.tcp(mhost, int(mport)))
                    try:
                        view = await mc.cluster_view()
                    finally:
                        await mc.close()
                    sys.stderr.write(view.get("text", "") + "\n")
                except Exception as e:
                    sys.stderr.write(f"dfget: cluster view unavailable: "
                                     f"{e}\n")
        if cfg.device and not result.get("device_verified", False):
            # The request asked for the device: a disk-only result is a
            # failure of the request, whatever landed on disk.
            sys.stderr.write(
                "dfget: error: content did not land in the device sink: "
                + (result.get("device_error")
                   or "the daemon reported no sink error (is its "
                      "tpu_sink enabled?)") + "\n")
            return 1
        return 0

    try:
        rc = asyncio.run(run())
    except Exception as e:
        sys.stderr.write(f"\ndfget: error: {e}\n")
        return 1
    if "jax" in sys.modules:
        # dfget is a client of the daemon that holds the chip; see
        # assert_no_jax. Said after the download, not raised over it.
        sys.stderr.write("dfget: error: this process imported jax, which "
                         "only the daemon with the device sink may\n")
        return 1
    return rc


def _spawn_daemon(path: Dfpath, *, device_sink: bool = False,
                  schedulers: list | None = None, manager: str = "") -> None:
    """Fork a daemon like dfget does (reference cmd/dfget/cmd/root.go:313).
    Scheduler/manager addresses thread through so a COLD host's first
    dfget joins the P2P fabric, not just a local-cache daemon."""
    path.ensure()
    cmd = [sys.executable, "-m", "dragonfly2_tpu.cli.main", "daemon",
           "--work-home", path.root]
    for addr in schedulers or []:
        cmd += ["--scheduler", addr]
    if manager:
        cmd += ["--manager", manager]
    if device_sink:
        cmd.append("--device-sink")
    with open(os.path.join(path.log_dir, "daemon-spawn.log"), "ab") as logf:
        subprocess.Popen(cmd, stdout=logf, stderr=logf,
                         start_new_session=True, close_fds=True)
    log.info("spawned daemon", work_home=path.root)


async def _wait_daemon(sock: str, timeout: float = 15.0) -> None:
    from dragonfly2_tpu.client.dfget import is_daemon_alive

    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if await is_daemon_alive(sock):
            return
        await asyncio.sleep(0.1)
    raise RuntimeError(f"daemon did not come up on {sock} within {timeout}s")


def _add_daemon(sub: argparse._SubParsersAction) -> None:
    p = sub.add_parser("daemon", help="run the peer daemon (dfdaemon)")
    p.add_argument("--config", default="", help="YAML config path")
    p.add_argument("--work-home", default="")
    p.add_argument("--seed-peer", action="store_true")
    p.add_argument("--scheduler", action="append", default=[],
                   help="scheduler host:port (repeatable)")
    p.add_argument("--manager", default="",
                   help="manager drpc host:port (dynconfig scheduler resolution)")
    p.add_argument("--proxy-port", type=int, default=-1,
                   help="enable the HTTP proxy on this port (0 = ephemeral)")
    p.add_argument("--registry-mirror", default="",
                   help="remote registry URL to mirror through the proxy")
    p.add_argument("--alive-time", type=float, default=0.0)
    p.add_argument("--object-storage-port", type=int, default=-1,
                   help="enable the S3-like object gateway on this port (0 = ephemeral)")
    p.add_argument("--object-storage-backend", default="fs",
                   help="fs | s3 | gcs | oss | obs")
    p.add_argument("--object-storage-option", action="append", default=[],
                   help="backend kwarg k=v (repeatable), e.g. root=/data/buckets")
    p.add_argument("--pex-port", type=int, default=-1,
                   help="enable gossip peer exchange on this UDP port (0 = ephemeral)")
    p.add_argument("--pex-seed", action="append", default=[],
                   help="PEX bootstrap host:port (repeatable)")
    p.add_argument("--pex-secret", default="",
                   help="shared HMAC secret for gossip datagrams")
    p.add_argument("--prefetch", action="store_true",
                   help="ranged-request misses also prefetch the whole task")
    p.add_argument("--hijack-https", action="store_true",
                   help="TLS-intercept CONNECT tunnels with a CA-forged cert")
    p.add_argument("--device-sink", action="store_true",
                   help="enable the TPU HBM sink (tasks with --device tpu "
                        "land verified pieces in device memory)")
    p.add_argument("--metrics-port", type=int, default=0,
                   help="fixed port for /metrics + /debug endpoints "
                        "(0 = ephemeral, -1 = disabled)")
    p.add_argument("--piece-concurrency", type=int, default=0,
                   help="concurrent origin range streams for back-to-source "
                        "(0 = config default; caps origin request fan-in)")
    p.add_argument("--tpu-slice", default="",
                   help="ICI domain label for this host (e.g. slice-3); "
                        "the scheduler prefers parents inside the same "
                        "slice lexicographically")
    p.add_argument("--tpu-worker-index", type=int, default=-1,
                   help="worker index within the slice")
    p.add_argument("--hostname", default="",
                   help="override this daemon's advertised hostname "
                        "(multi-daemon-per-machine tests; the host id is "
                        "hostname-port)")
    p.add_argument("--clock-offset", type=float, default=0.0,
                   help="chaos/test knob: skew every wall stamp this "
                        "daemon reports by this many seconds — the "
                        "scheduler's clock alignment must recover it")
    p.set_defaults(func=_run_daemon)


def _run_daemon(args: argparse.Namespace) -> int:
    from dragonfly2_tpu.daemon.config import DaemonConfig
    from dragonfly2_tpu.daemon.daemon import Daemon

    if args.config:
        cfg = DaemonConfig.load(args.config)
    else:
        cfg = DaemonConfig()
    if args.work_home:
        cfg.work_home = args.work_home
        cfg.__post_init__()
    if args.seed_peer:
        cfg.seed_peer = True
    if args.scheduler:
        cfg.scheduler.addrs = args.scheduler
    if args.manager:
        cfg.manager_addr = args.manager
    if args.proxy_port >= 0:
        cfg.proxy.enabled = True
        cfg.proxy.port = args.proxy_port
    if args.registry_mirror:
        cfg.proxy.enabled = True
        cfg.proxy.registry_mirror = args.registry_mirror
    if args.alive_time:
        cfg.alive_time = args.alive_time
    if args.tpu_slice:
        cfg.host.tpu_slice = args.tpu_slice
    if args.tpu_worker_index >= 0:
        cfg.host.tpu_worker_index = args.tpu_worker_index
    if args.hostname:
        cfg.host.hostname = args.hostname
    if args.clock_offset:
        cfg.clock_offset_s = args.clock_offset
    if args.object_storage_port >= 0:
        cfg.object_storage.enabled = True
        cfg.object_storage.port = args.object_storage_port
        cfg.object_storage.backend = args.object_storage_backend
        opts = dict(kv.split("=", 1) for kv in args.object_storage_option if "=" in kv)
        if args.object_storage_backend == "fs" and "root" not in opts:
            import os

            opts["root"] = os.path.join(cfg.work_home or ".", "buckets")
        cfg.object_storage.backend_options = opts
    if args.pex_port >= 0 or args.pex_seed:
        cfg.pex.enabled = True
        if args.pex_port >= 0:
            cfg.pex.port = args.pex_port
        cfg.pex.seeds = args.pex_seed
    if args.pex_secret:
        cfg.pex.secret = args.pex_secret
    if args.prefetch:
        cfg.download.prefetch = True
    if args.device_sink:
        cfg.tpu_sink.enabled = True
    if args.metrics_port:
        cfg.metrics_port = args.metrics_port
    if args.piece_concurrency > 0:
        cfg.download.piece_concurrency = args.piece_concurrency
    if args.hijack_https:
        cfg.proxy.enabled = True
        cfg.proxy.hijack_https = True

    async def run() -> int:
        daemon = Daemon(cfg)
        if not cfg.tpu_sink.enabled:
            assert_no_jax("daemon")
        import signal

        loop = asyncio.get_running_loop()
        for sig in (signal.SIGINT, signal.SIGTERM):
            loop.add_signal_handler(sig, lambda: asyncio.ensure_future(daemon.stop()))
        await daemon.serve()
        return 0

    return asyncio.run(run())


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(prog="df", description="TPU-native P2P content fabric")
    sub = parser.add_subparsers(dest="command", required=True)
    _add_dfget(sub)
    _add_daemon(sub)
    # scheduler/manager/dfcache/dfstore subcommands are registered as those
    # stages land (SURVEY.md §7 build order).
    try:
        from dragonfly2_tpu.cli import extra

        extra.register(sub)
    except ImportError:
        pass
    args = parser.parse_args(argv)
    return args.func(args)


if __name__ == "__main__":
    sys.exit(main())
