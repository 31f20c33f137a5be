"""Sharded checkpoint pull with REAL bytes: N embedded daemons each pull
a disjoint slice of one safetensors checkpoint via
client.device.download_sharded (ranged device tasks through a live
scheduler), the sharded-pod pattern of BASELINE config #5.

What it measures (window-independent claims first):
  - origin_copies     total origin bytes / checkpoint size (target ~1.0:
                      each tensor span fetched once pod-wide, headers
                      deduped via the shared ranged task)
  - per-host selected fraction of the checkpoint each host pulled
  - aggregate_gbps    sum of landed bytes / wall (1-core host: both
                      daemons and origin share the core)

Usage: python benchmarks/sharded_bench.py [--hosts 4] [--mb 256] [--publish]

The process re-execs itself with JAX_PLATFORMS=cpu first: the embedded
daemons construct device sinks, and this script's numbers are host-side
counts and host-clock times, not device metrics.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import struct
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _reexec_cpu() -> int:
    env = dict(os.environ)
    env.update({
        "DF_SHARDED_BENCH_CHILD": "1",
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
        "PYTHONPATH": REPO,
    })
    return subprocess.call([sys.executable, os.path.abspath(__file__),
                            *sys.argv[1:]], env=env)


def make_checkpoint(total_mb: int, n_tensors: int) -> tuple[bytes, list[str]]:
    import random

    per = (total_mb << 20) // n_tensors
    rng = random.Random(17)
    header, blobs, off, names = {}, [], 0, []
    for i in range(n_tensors):
        name = f"layer{i}.w"
        names.append(name)
        raw = rng.randbytes(per // 4 * 4)
        header[name] = {"dtype": "F32", "shape": [len(raw) // 4],
                        "data_offsets": [off, off + len(raw)]}
        blobs.append(raw)
        off += len(raw)
    hj = json.dumps(header).encode()
    return struct.pack("<Q", len(hj)) + hj + b"".join(blobs), names


async def run_bench(n_hosts: int, total_mb: int,
                    warm: bool = False) -> dict:
    import numpy as np

    from dragonfly2_tpu.client import device as device_lib
    from dragonfly2_tpu.daemon.config import DaemonConfig
    from dragonfly2_tpu.daemon.daemon import Daemon
    from dragonfly2_tpu.pkg.testing import start_range_origin
    from dragonfly2_tpu.scheduler.config import SchedulerConfig
    from dragonfly2_tpu.scheduler.server import SchedulerServer

    n_tensors = n_hosts * 4          # 4 tensors per host's shard
    ckpt, names = make_checkpoint(total_mb, n_tensors)
    runner, url, stats = await start_range_origin(ckpt)

    scfg = SchedulerConfig()
    scfg.server.port = 0
    scfg.scheduling.retry_interval = 0.05
    sched = SchedulerServer(scfg)
    await sched.start()

    import tempfile

    workdir = tempfile.mkdtemp(prefix="df-sharded-")
    daemons = []
    for i in range(n_hosts + (1 if warm else 0)):
        cfg = DaemonConfig()
        cfg.work_home = os.path.join(workdir, f"h{i}")
        cfg.__post_init__()
        cfg.host.hostname = f"shard-host-{i}"
        cfg.host.ip = "127.0.0.1"
        cfg.scheduler.addrs = [f"127.0.0.1:{sched.port()}"]
        cfg.gc_interval = 3600
        cfg.tpu_sink.enabled = True
        cfg.tpu_sink.max_tasks = 8
        cfg.seed_peer = warm and i == n_hosts   # last daemon = warm seed
        d = Daemon(cfg)
        await d.start()
        daemons.append(d)

    preheat_bytes = 0
    if warm:
        # Preheat the WHOLE checkpoint on the seed; every ranged task the
        # scheduler then triggers on it imports locally — the sharded
        # pull phase must be origin-silent.
        from dragonfly2_tpu.client import dfget as dfget_lib

        r = await dfget_lib.download(dfget_lib.DfgetConfig(
            url=url, output=os.path.join(workdir, "warm.bin"),
            daemon_sock=daemons[-1].config.unix_sock,
            allow_source_fallback=False, timeout=600.0))
        assert r["state"] == "done"
        preheat_bytes = stats["bytes"]

    per_host = n_tensors // n_hosts
    landed_bytes = [0] * n_hosts
    t0 = time.perf_counter()
    try:
        async def pull(i: int) -> None:
            mine = names[i * per_host:(i + 1) * per_host]
            got = await device_lib.download_sharded(
                daemons[i], url, names=mine)
            landed_bytes[i] = sum(
                int(np.prod(a.shape)) * 4 for a in got.values())
            assert set(got) == set(mine)

        await asyncio.gather(*[pull(i) for i in range(n_hosts)])
        wall = time.perf_counter() - t0
    finally:
        for d in daemons:
            await d.stop()
        await sched.stop()
        await runner.cleanup()

    total_landed = sum(landed_bytes)
    out_extra = {}
    if warm:
        out_extra = {
            "warm_seed": True,
            "preheat_bytes": preheat_bytes,
            "origin_bytes_during_pull": stats["bytes"] - preheat_bytes,
        }
    return {
        "config": "sharded-checkpoint-pull",
        **out_extra,
        "hosts": n_hosts,
        "checkpoint_mb": total_mb,
        "tensors": n_tensors,
        "per_host_fraction": round(landed_bytes[0] / len(ckpt), 3),
        "aggregate_gbps": round(total_landed / wall / 1e9, 3),
        "wall_s": round(wall, 2),
        "origin_copies": round(stats["bytes"] / len(ckpt), 3),
        "host_cores": os.cpu_count(),
    }


def main() -> int:
    if os.environ.get("DF_SHARDED_BENCH_CHILD") != "1":
        return _reexec_cpu()
    ap = argparse.ArgumentParser()
    ap.add_argument("--hosts", type=int, default=4)
    ap.add_argument("--mb", type=int, default=256)
    ap.add_argument("--publish", action="store_true")
    ap.add_argument("--warm", action="store_true",
                    help="preheat a seed with the whole file first; the "
                         "pull phase must then be origin-silent")
    args = ap.parse_args()
    result = asyncio.run(run_bench(args.hosts, args.mb, warm=args.warm))
    print(json.dumps(result))
    if args.publish:
        path = os.path.join(REPO, "BASELINE.json")
        doc = json.load(open(path))
        key = ("config5_sharded_real_bytes_warm" if args.warm
               else "config5_sharded_real_bytes")
        doc.setdefault("published", {})[key] = result
        with open(path, "w") as f:
            json.dump(doc, f, indent=2)
            f.write("\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
