"""Micro-benchmark of the device sink's ops on the chip.

Times the TPU-side work HBMSink does per landed byte (one fused dispatch
assembling staged batches into the flat content while folding per-piece
checksums), host→HBM staging, and the hot-swap verify gate, in GB/s, and
runs a small sink smoke. The host-side baseline is sha256 over the same
bytes (Dragonfly2 verifies digests on CPU; pkg/digest/digest_reader.go),
reported under its own name.

Throughput uses the SLOPE method: run the workload at two iteration
counts with a hard scalar fetch each, and divide the extra work by the
extra time, so fixed overhead (fetch, dispatch warm-up) cancels.

Needs a TPU: without one it exits non-zero and prints no metric. Any
stage that fails fails the run. This is not the benchmark of ROADMAP S0
(end to end, by cell); it times ops in isolation.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...,
"device": {"platform", "kind", "count"}}.
"""

from __future__ import annotations

import hashlib
import json
import sys
import time

import numpy as np


def bench_cpu_sha256(data: bytes, repeats: int = 3) -> float:
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        hashlib.sha256(data).digest()
        best = min(best, time.perf_counter() - t0)
    return len(data) / best


def bench_device_sink(jax, total_mb: int = 512, piece_mb: int = 4,
                      batch_pieces: int = 16) -> float:
    """Steady-state verify+land GB/s: HBMSink's whole device cost per
    landed byte — ONE fused dispatch assembling the staged batches into
    the flat content while folding per-piece checksums from the same read
    (host→HBM staging is excluded here and timed by
    bench_staged_transfer)."""
    import jax.numpy as jnp

    from dragonfly2_tpu.ops.hbm_sink import _assemble_checksum_jit

    piece_words = (piece_mb << 20) // 4
    n_pieces = total_mb // piece_mb
    n_batches = n_pieces // batch_pieces
    rng = np.random.RandomState(0)
    batches = tuple(
        jnp.asarray(rng.randint(0, 2**31, size=(batch_pieces, piece_words),
                                dtype=np.int64).astype(np.uint32))
        for _ in range(n_batches))
    jax.block_until_ready(batches)
    plan = tuple(("b", bi, 0, batch_pieces) for bi in range(n_batches))
    nbytes = n_pieces * piece_words * 4

    def work():
        flat, sums, xors = _assemble_checksum_jit(batches, plan, piece_words)
        return sums, flat

    def run(iters: int) -> float:
        t0 = time.perf_counter()
        r = None
        for _ in range(iters):
            r = work()
        # Hard completion barrier: host scalar fetches.
        _ = int(np.asarray(r[0][0]))
        _ = int(np.asarray(r[1][-1:])[0])
        return time.perf_counter() - t0

    work()  # compile
    run(2)  # warm
    n1, n2 = 8, 32
    slopes = []
    for _ in range(3):
        t1 = run(n1)
        t2 = run(n2)
        if t2 > t1:
            slopes.append((n2 - n1) * nbytes / (t2 - t1))
    if not slopes:
        # Noise beat every slope; fall back to a big sample alone.
        return nbytes * n2 / run(n2)
    slopes.sort()
    return slopes[len(slopes) // 2]


def bench_staged_transfer(jax, total_mb: int = 64, repeats: int = 4) -> float:
    """Host→HBM staging GB/s (jax.device_put of a pageable host buffer —
    the daemon's piece staging path): the transport leg the sink metric
    deliberately excludes. Reported alongside so an end-to-end budget
    (BASELINE config #5's <60 s) can be decomposed into staging + sink and
    neither hides the other's bottleneck."""
    n = (total_mb << 20) // 4
    host = np.random.RandomState(2).randint(
        0, 2**31, size=(n,), dtype=np.int64).astype(np.uint32)

    def run(iters: int) -> float:
        t0 = time.perf_counter()
        staged = None
        for _ in range(iters):
            staged = jax.device_put(host)
        # One hard barrier; the slope below cancels its fixed cost.
        _ = int(np.asarray(staged[:1])[0])
        return time.perf_counter() - t0

    run(1)
    n1, n2 = 2, 6
    slopes = []
    for _ in range(max(1, repeats // 2)):
        t1 = run(n1)
        t2 = run(n2)
        if t2 > t1:
            slopes.append((n2 - n1) * (total_mb << 20) / (t2 - t1))
    if not slopes:
        return (total_mb << 20) * n2 / run(n2)
    slopes.sort()
    return slopes[len(slopes) // 2]


def bench_swap_verify(jax, total_mb: int = 256, piece_mb: int = 4) -> float:
    """Hot-swap gate GB/s: verify_u8_against_host over a resident uint8
    content buffer — the on-device per-piece checksum fold plus the host
    compare a DoubleBuffer flip pays per checkpoint byte before the next
    generation goes live (the delta plane's last on-chip gap: the gate
    had smoke coverage but no throughput number). Each call fetches the
    per-piece checksum vectors to host (np.asarray inside the gate), so
    every iteration carries its own hard completion barrier; the slope
    over two iteration counts cancels the fixed fetch cost like the
    sink measurement above."""
    import jax.numpy as jnp

    from dragonfly2_tpu.ops.hbm_sink import (
        checksum_numpy,
        verify_u8_against_host,
    )

    piece = piece_mb << 20
    total = total_mb << 20
    host = np.random.RandomState(3).bytes(total)
    u8 = jnp.asarray(np.frombuffer(host, dtype=np.uint8))
    jax.block_until_ready(u8)
    checks = {n: checksum_numpy(host[n * piece:(n + 1) * piece])
              for n in range(total // piece)}

    def run(iters: int) -> float:
        t0 = time.perf_counter()
        for _ in range(iters):
            verify_u8_against_host(u8, piece, checks)
        return time.perf_counter() - t0

    run(1)   # compile
    run(2)   # warm
    n1, n2 = 2, 6
    slopes = []
    for _ in range(3):
        t1 = run(n1)
        t2 = run(n2)
        if t2 > t1:
            slopes.append((n2 - n1) * total / (t2 - t1))
    if not slopes:
        return total * n2 / run(n2)
    slopes.sort()
    return slopes[len(slopes) // 2]


def sink_smoke(jax) -> str:
    """Small smoke of the sink ops: HBMSink lands host pieces, verifies
    on device, round-trips the bytes exactly, and passes the hot-swap
    verification gate. The served path at a real size is chip_smoke.py."""
    from dragonfly2_tpu.ops.hbm_sink import HBMSink, verify_u8_against_host

    piece = 1 << 20
    rng = np.random.RandomState(7)
    content = rng.bytes(8 * piece + 12345)   # tail piece
    sink = HBMSink(len(content), piece, batch_pieces=4)
    nums = list(range((len(content) + piece - 1) // piece))
    rng.shuffle(nums)
    for n in nums:
        sink.land_piece(n, content[n * piece:(n + 1) * piece])
    if not sink.complete():
        return "incomplete"
    sink.verify()
    u8 = sink.as_bytes_array()
    try:
        verify_u8_against_host(u8, piece, sink.host_checksums)
    except ValueError as e:
        return f"swap gate failed: {e}"
    out = np.asarray(u8).tobytes()
    return "ok" if out == content else "bytes mismatch"


def main() -> int:
    import jax

    from dragonfly2_tpu.ops.compile_cache import place_compile_cache

    device = jax.devices()[0]
    if device.platform != "tpu":
        print(f"bench.py needs a TPU; jax found platform "
              f"{device.platform!r}", file=sys.stderr)
        return 1
    place_compile_cache()
    cpu_bps = bench_cpu_sha256(np.random.RandomState(1).bytes(64 << 20))
    device_bps = bench_device_sink(jax)
    staged_bps = bench_staged_transfer(jax)
    swap_bps = bench_swap_verify(jax)
    smoke = sink_smoke(jax)
    print(json.dumps({
        "metric": "verify_and_land_throughput",
        "value": round(device_bps / 1e9, 3),
        "unit": "GB/s",
        "vs_baseline": round(device_bps / cpu_bps, 3),
        "staged_host_to_hbm_gbps": round(staged_bps / 1e9, 3),
        "swap_verify_gbps": round(swap_bps / 1e9, 3),
        "cpu_sha256_gbps": round(cpu_bps / 1e9, 3),
        "sink_smoke": smoke,
        "device": {"platform": device.platform, "kind": device.device_kind,
                   "count": len(jax.devices())},
    }))
    return 0 if smoke == "ok" else 1


if __name__ == "__main__":
    sys.exit(main())
