"""Checkpoint-delta hot-swap, end to end on one machine.

Serve version N of a checkpoint from the device buffer, publish version
N+1 (a 1%-style scattered edit), watch the delta land — unchanged chunks
copied locally out of version N, only changed chunks fetched — and the
tensors flip atomically to the new generation without a serving gap.

    JAX_PLATFORMS=cpu python examples/checkpoint_hotswap.py
"""

import asyncio
import hashlib
import json
import os
import struct
import sys
import tempfile

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

jax.config.update("jax_platforms", "cpu")

import numpy as np


def make_checkpoint(step: int) -> bytes:
    """A small safetensors checkpoint; version `step+1` is version
    `step` with a few scattered tensor updates (the realistic edit
    pattern — not one contiguous blob)."""
    rng = np.random.RandomState(0)
    tensors = {
        "w1": rng.randn(256, 256).astype(np.float32),
        "w2": rng.randn(256, 128).astype(np.float32),
        "bias": rng.randn(1024).astype(np.float32),
        "step": np.array([0], dtype=np.int32),
    }
    tensors["step"][0] = step
    if step > 1:       # scattered updates on top of version 1
        tensors["bias"][::97] += 0.5
        tensors["w2"][5, :16] *= 1.25
    header, blobs, off = {}, [], 0
    for name, arr in tensors.items():
        raw = arr.tobytes()
        dt = {"float32": "F32", "int32": "I32"}[str(arr.dtype)]
        header[name] = {"dtype": dt, "shape": list(arr.shape),
                        "data_offsets": [off, off + len(raw)]}
        blobs.append(raw)
        off += len(raw)
    hjson = json.dumps(header).encode()
    return struct.pack("<Q", len(hjson)) + hjson + b"".join(blobs)


async def serve_blobs(blobs: dict):
    from aiohttp import web

    from dragonfly2_tpu.pkg.piece import Range

    async def handler(request):
        content = blobs[request.match_info["name"]]
        hdr = request.headers.get("Range")
        if hdr:
            r = Range.parse_http(hdr, len(content))
            data = content[r.start:r.start + r.length]
            return web.Response(status=206, body=data, headers={
                "Content-Range": f"bytes {r.start}-"
                f"{r.start + len(data) - 1}/{len(content)}",
                "Accept-Ranges": "bytes"})
        return web.Response(body=content,
                            headers={"Accept-Ranges": "bytes"})

    app = web.Application()
    app.router.add_get("/{name}", handler)
    runner = web.AppRunner(app, access_log=None)
    await runner.setup()
    site = web.TCPSite(runner, "127.0.0.1", 0)
    await site.start()
    return runner, f"http://127.0.0.1:{site._server.sockets[0].getsockname()[1]}"


async def main() -> int:
    from dragonfly2_tpu.client import device as device_lib
    from dragonfly2_tpu.daemon.config import DaemonConfig
    from dragonfly2_tpu.daemon.daemon import Daemon
    from dragonfly2_tpu.daemon.peer.task_manager import FileTaskRequest
    from dragonfly2_tpu.delta.chunker import CDCParams
    from dragonfly2_tpu.delta.resolver import publish_manifest_for
    from dragonfly2_tpu.ops.hbm_sink import DoubleBuffer
    from dragonfly2_tpu.proto.common import UrlMeta
    from dragonfly2_tpu.scheduler.config import SchedulerConfig
    from dragonfly2_tpu.scheduler.server import SchedulerServer

    v1, v2 = make_checkpoint(1), make_checkpoint(2)
    sha1 = "sha256:" + hashlib.sha256(v1).hexdigest()
    sha2 = "sha256:" + hashlib.sha256(v2).hexdigest()
    params = CDCParams(mask_bits=12, min_size=2 << 10, max_size=32 << 10)

    workdir = tempfile.mkdtemp(prefix="hotswap-example-")
    origin, base_url = await serve_blobs({"v1": v1, "v2": v2})
    scfg = SchedulerConfig()
    scfg.server.port = 0
    sched = SchedulerServer(scfg)
    await sched.start()

    def daemon_cfg(name: str, *, seed=False, sink=False) -> DaemonConfig:
        cfg = DaemonConfig()
        cfg.work_home = os.path.join(workdir, name)
        cfg.__post_init__()
        cfg.host.hostname = name
        cfg.host.ip = "127.0.0.1"
        cfg.scheduler.addrs = [f"127.0.0.1:{sched.port()}"]
        cfg.seed_peer = seed
        cfg.tpu_sink.enabled = sink
        return cfg

    seed = Daemon(daemon_cfg("seed", seed=True))
    pod = Daemon(daemon_cfg("pod", sink=True))
    await seed.start()
    await pod.start()
    try:
        # The publisher side: land both versions on the seed and publish
        # their chunk manifests into the fabric.
        async def land(url, digest):
            final = None
            async for p in seed.task_manager.start_file_task(
                    FileTaskRequest(url=url, output="",
                                    meta=UrlMeta(digest=digest))):
                if p.state == "done":
                    final = p
            return final

        r1 = await land(f"{base_url}/v1", sha1)
        await publish_manifest_for(seed.task_manager, r1.task_id,
                                   params=params)

        # Serve version N from the device buffer.
        result = await device_lib.download_to_device(
            pod, f"{base_url}/v1", digest=sha1)
        hot = DoubleBuffer()
        hot.flip(result.as_words(), result.load_safetensors())
        step = int(np.asarray(hot.tensors()["step"])[0])
        print(f"serving generation {hot.generation} "
              f"(checkpoint step {step}, {len(v1)} bytes in HBM)")

        # Version N+1 appears: publish + manifest.
        r2 = await land(f"{base_url}/v2", sha2)
        await publish_manifest_for(seed.task_manager, r2.task_id,
                                   params=params)

        # The hot swap: delta transfer + device-side reuse + atomic flip.
        swap = await device_lib.download_delta(
            pod, f"{base_url}/v2", base=result.task_id, hot=hot,
            digest=sha2)
        step = int(np.asarray(hot.tensors()["step"])[0])
        st = swap.stats
        print(f"flipped to generation {hot.generation} "
              f"(checkpoint step {step})")
        print(f"  wire:   reused {st['reused_bytes']}B locally, "
              f"fetched {st['fetched_bytes']}B "
              f"({100 * st['fetched_bytes'] / len(v2):.1f}% of the bytes)")
        print(f"  device: {swap.reused_device_bytes}B copied HBM->HBM, "
              f"{swap.staged_bytes}B staged host->device")
        assert step == 2
        return 0
    finally:
        await pod.stop()
        await seed.stop()
        await sched.stop()
        await origin.cleanup()


if __name__ == "__main__":
    sys.exit(asyncio.run(main()))
