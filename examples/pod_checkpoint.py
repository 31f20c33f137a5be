"""Pod checkpoint consumption: fabric-landed safetensors → global mesh.

Simulates the north-star chain on a virtual 8-device mesh: a checkpoint
lands in the HBM sink (in production: `dfstore prefetch --device tpu` or a
manager preheat job with device:"tpu" on every host), then the training
side loads named tensors straight onto a factored dp×tp global mesh.

    python examples/pod_checkpoint.py
"""

import json
import os
import struct
import sys

# Force the virtual CPU mesh regardless of what the environment pins
# (sandboxes may preset JAX_PLATFORMS); on a real pod, drop these two
# lines and the jax.config.update below.
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

jax.config.update("jax_platforms", "cpu")

import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from dragonfly2_tpu.ops.hbm_sink import HBMSink
from dragonfly2_tpu.ops.safetensors import load_from_sink
from dragonfly2_tpu.parallel import multihost


def make_checkpoint() -> tuple[bytes, dict[str, np.ndarray]]:
    rng = np.random.RandomState(0)
    tensors = {"w1": rng.randn(64, 128).astype(np.float32),
               "w2": rng.randn(128, 32).astype(np.float32)}
    header, blobs, off = {}, [], 0
    for name, arr in tensors.items():
        raw = arr.tobytes()
        header[name] = {"dtype": "F32", "shape": list(arr.shape),
                        "data_offsets": [off, off + len(raw)]}
        blobs.append(raw)
        off += len(raw)
    hjson = json.dumps(header).encode()
    return struct.pack("<Q", len(hjson)) + hjson + b"".join(blobs), tensors


def main() -> None:
    multihost.initialize_distributed()       # no-op off-pod
    content, ref = make_checkpoint()

    # The fabric's device sink (what a preheat lands on every host).
    piece = 4096
    sink = HBMSink(len(content), piece, batch_pieces=4)
    for n in range((len(content) + piece - 1) // piece):
        sink.land_piece(n, content[n * piece:(n + 1) * piece])
    assert sink.complete() and sink.verify()

    # Training side: tensors straight onto the pod-global mesh.
    mesh = multihost.global_mesh({"dp": 2, "tp": 4})
    params = load_from_sink(sink, shardings={
        "w1": NamedSharding(mesh, P(None, "tp")),
        "w2": NamedSharding(mesh, P("tp", None)),
    })
    x = np.ones((8, 64), np.float32)
    out = jax.jit(lambda p, x: x @ p["w1"] @ p["w2"])(params, x)
    want = x @ ref["w1"] @ ref["w2"]
    np.testing.assert_allclose(np.asarray(out), want, rtol=1e-4)
    print(f"mesh={dict(mesh.shape)} w1.sharding={params['w1'].sharding.spec} "
          f"forward-pass exact: OK")

    # Global sharded load through the REAL fabric: origin + scheduler +
    # sink daemon in this process, then client.device.download_global
    # pulls only the byte ranges the mesh's devices hold, lands each on
    # the device that keeps it (a range that several devices want lands
    # once and is fanned out chip to chip), and hands back global arrays
    # directly — the production checkpoint-loading API.
    import asyncio

    asyncio.run(fabric_global_load(content, ref, mesh))


async def fabric_global_load(content: bytes, ref, mesh) -> None:
    import socket
    import tempfile

    from dragonfly2_tpu.client import device as device_lib
    from dragonfly2_tpu.daemon.config import DaemonConfig
    from dragonfly2_tpu.daemon.daemon import Daemon
    from dragonfly2_tpu.pkg.testing import start_range_origin
    from dragonfly2_tpu.scheduler.config import SchedulerConfig
    from dragonfly2_tpu.scheduler.server import SchedulerServer

    runner, url, served = await start_range_origin(content)

    scfg = SchedulerConfig()
    scfg.server.port = 0
    scfg.scheduling.retry_interval = 0.05
    sched = SchedulerServer(scfg)
    await sched.start()

    dcfg = DaemonConfig()
    dcfg.work_home = tempfile.mkdtemp(prefix="df-example-")
    dcfg.__post_init__()
    dcfg.host.hostname = socket.gethostname()
    dcfg.host.ip = "127.0.0.1"
    dcfg.scheduler.addrs = [f"127.0.0.1:{sched.port()}"]
    dcfg.tpu_sink.enabled = True
    daemon = Daemon(dcfg)
    await daemon.start()
    try:
        params = await device_lib.download_global(
            daemon, url,
            {"w2": NamedSharding(mesh, P("tp", None))})
        np.testing.assert_array_equal(np.asarray(params["w2"]), ref["w2"])
        print(f"download_global: w2 pulled as per-device row ranges "
              f"({served['bytes']} origin bytes for a "
              f"{len(content)}-byte checkpoint), global sharding "
              f"{params['w2'].sharding.spec} — bit-exact: OK")
    finally:
        await daemon.stop()
        await sched.stop()
        await runner.cleanup()


if __name__ == "__main__":
    main()
