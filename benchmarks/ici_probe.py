"""Which way to every chip: a probe of ``parallel/ici.py``'s ways of placing
one chip's words whole on all four chips of a host, at a checkpoint shard's
size, one child process a candidate (a device's ``peak_bytes_in_use`` is a
high-water mark of its process).

    chiprun --chips 4 -- python3 benchmarks/ici_probe.py
    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \\
        python3 benchmarks/ici_probe.py --words 1048576     # the rehearsal

For each candidate: host seconds from dispatch to every chip's copy ready
(the sharded placement's part of it beside), every chip's peak bytes, and the
device seconds of one traced run, chip by chip. Then the per-chip
verification (``ops/hbm_sink._chip_checksums_jit``) over the result. The
parent never imports jax: a chip belongs to one process at a time. The table
goes to stdout and to ``chiprun_out/ici_probe.json``; PERF.md section 6
(PR 31) holds the reading that put one of them on the normal path.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path[:0] = [REPO, os.path.join(REPO, "chipbench")]

# moonlight-shard-1p7g: 55 pieces of 32 MiB.
PIECE_WORDS = 8 << 20
WORDS = 55 * PIECE_WORDS
CANDIDATES = ("device_put", "all_gather", "chunked_ring_1", "chunked_ring_4",
              "chunked_ring_16", "ring")
REPEATS = 3


def one(name: str, words_n: int) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from jax.sharding import SingleDeviceSharding

    import reduce_trace
    from dragonfly2_tpu.ops.hbm_sink import _chip_checksums_jit
    from dragonfly2_tpu.parallel import ici

    devices = jax.devices()[:4]
    mesh = Mesh(np.array(devices), ("d",))
    piece_words = min(PIECE_WORDS, words_n)

    def stats() -> list[dict]:
        return [d.memory_stats() or {} for d in devices]

    def fill():
        i = jnp.arange(words_n, dtype=jnp.uint32)
        return (i * jnp.uint32(2654435761)) ^ (i >> 7)

    words = jax.block_until_ready(jax.jit(
        fill, out_shardings=SingleDeviceSharding(devices[0]))())
    shard = lambda: jax.device_put(words, NamedSharding(mesh, P("d")))
    ways = {
        "device_put": lambda: ici.replicate_to_mesh(mesh, words),
        "all_gather": lambda: ici.all_gather_shards(mesh, shard()),
        "ring": lambda: ici.ring_all_gather(mesh, shard()),
    }
    if name.startswith("chunked_ring_"):
        chunks = int(name.rsplit("_", 1)[1])
        ways[name] = lambda: ici.chunked_ring_all_gather(
            mesh, shard(), n_chunks=chunks)
    way = ways[name]

    t0 = time.perf_counter()
    out = jax.block_until_ready(way())          # compiles
    first_s = time.perf_counter() - t0
    del out
    seconds, shard_seconds = [], []
    for _ in range(REPEATS):
        if name != "device_put":
            t0 = time.perf_counter()
            jax.block_until_ready(shard())
            shard_seconds.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        out = jax.block_until_ready(way())
        seconds.append(time.perf_counter() - t0)
        del out
    after = stats()

    trace_dir = tempfile.mkdtemp(prefix="ici_probe_")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        out = jax.block_until_ready(way())
    finally:
        jax.profiler.stop_trace()
    device_s, programs, lines = {}, {}, {}
    try:
        trace = reduce_trace.read_xplane(trace_dir)
        for plane, by_line in trace["device"].items():
            # Every line of every device plane: a copy the runtime makes
            # itself runs no program, and may show on a line of its own.
            lines[plane] = {
                line: [len(events), sum(d for _, _, d in events)]
                for line, events in by_line.items() if events}
        for plane in reduce_trace.chip_planes(trace):
            ops = reduce_trace.events_on(trace, plane, reduce_trace.OPS_LINES)
            device_s[plane] = reduce_trace.total(
                (s, s + d) for _, s, d in ops)
            for prog, _, d in reduce_trace.events_on(
                    trace, plane, reduce_trace.MODULE_LINES):
                programs.setdefault(plane, {})
                programs[plane][prog[:60]] = programs[plane].get(
                    prog[:60], 0.0) + d
    except Exception as e:                       # the CPU rehearsal's trace
        device_s = {"error": f"{type(e).__name__}: {e}"[:200]}

    # The peaks are read: now every chip's copy against chip 0's source,
    # compared on the chip that holds it ("ring" returns a stack whose every
    # block is the whole).
    same = [bool(jnp.array_equal(
        s.data[:words_n], jax.device_put(words, s.device)))
        for s in out.addressable_shards]
    verify = {}
    if out.sharding.is_fully_replicated:
        for label in ("first", "second"):
            t0 = time.perf_counter()
            sums = np.asarray(_chip_checksums_jit(
                out, mesh=mesh, axis_name="d", piece_words=piece_words))
            verify[label + "_s"] = time.perf_counter() - t0
        verify["chips_agree"] = bool((sums == sums[0]).all())
    final = stats()
    return {
        "name": name, "words": words_n, "device": devices[0].device_kind,
        "chips": len(devices), "every_copy_equal": same,
        "first_s": first_s, "seconds": seconds,
        "shard_seconds": shard_seconds,
        "bytes_in_use_after": [s.get("bytes_in_use") for s in after],
        "peak_bytes": [s.get("peak_bytes_in_use") for s in after],
        "peak_bytes_after_verify": [s.get("peak_bytes_in_use")
                                    for s in final],
        "device_s": device_s, "programs": programs, "lines": lines,
        "verify": verify,
    }


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--one", choices=CANDIDATES)
    parser.add_argument("--words", type=int, default=WORDS)
    parser.add_argument("--only", nargs="*", default=list(CANDIDATES))
    args = parser.parse_args(argv)
    if args.one:
        print("ICI_PROBE " + json.dumps(one(args.one, args.words)),
              flush=True)
        return 0
    rows = []
    for name in args.only:
        t0 = time.perf_counter()
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--one", name,
             "--words", str(args.words)],
            capture_output=True, text=True, timeout=600)
        line = next((ln for ln in proc.stdout.splitlines()
                     if ln.startswith("ICI_PROBE ")), None)
        if line is None:
            rows.append({"name": name, "error": (proc.stderr or proc.stdout)
                         [-1500:], "rc": proc.returncode})
        else:
            rows.append(json.loads(line[len("ICI_PROBE "):]))
        rows[-1]["child_s"] = time.perf_counter() - t0
        print(f"[ici_probe] {name}: " + json.dumps(rows[-1])[:6000],
              flush=True)
    content = 4 * args.words
    print(f"[ici_probe] content {content} bytes; host seconds dispatch -> "
          f"every chip ready (median of {REPEATS}); peak bytes a chip / "
          "content", flush=True)
    for row in rows:
        if "error" in row:
            print(f"[ici_probe] {row['name']:>16}  FAILED rc={row['rc']}")
            continue
        peaks = ["-" if p is None else f"{p / content:.2f}x"
                 for p in row["peak_bytes"]]
        dev = row["device_s"]
        worst = max(dev.values()) if dev and "error" not in dev else None
        print(f"[ici_probe] {row['name']:>16}  "
              f"{statistics.median(row['seconds']):.4f}s (min "
              f"{min(row['seconds']):.4f}; sharded placement "
              + (f"{statistics.median(row['shard_seconds']):.4f}"
                 if row["shard_seconds"] else "-")
              + f")  device {'-' if worst is None else f'{worst:.4f}s'}  "
              f"peaks {' '.join(peaks)}  copies equal "
              f"{all(row['every_copy_equal'])}", flush=True)
    out_dir = os.path.join(REPO, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "ici_probe.json"), "w") as f:
        json.dump(rows, f, indent=1)
    return 0 if all("error" not in r for r in rows) else 1


if __name__ == "__main__":
    sys.exit(main())
