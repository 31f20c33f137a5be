"""How many views a dispatch should carry: a probe of ``ops/bitview.py``'s
group cap over the benchmark's checkpoint shard (``moonlight-shard-1p7g``:
207 tensors in 1.84 GB of words), one child process a cap, each with no
compile cache, so its first load pays every compile.

    chiprun --chips 1 -- python3 benchmarks/views_probe.py
    chiprun --chips 4 -- python3 benchmarks/views_probe.py --mesh --caps 1 16
    JAX_PLATFORMS=cpu python3 benchmarks/views_probe.py \\
        --config chipbench/rehearsal/tiny-shard-12m.json      # the rehearsal

For each cap: the seconds of the first ``tensor_views`` of the process until
every tensor is ready (the compiles), then over the repeats the median ms
until the call returns (the host's dispatches) and until every tensor is
ready, the dispatches and tensors the counters saw in one load (and how many
of them the rows kernel cut), and the device's bytes in use and at peak.
Then three more loads under the profiler: ``busy_ms``, the time a load keeps
a chip's units busy (the union of its "XLA Ops", a chip's mean: on four chips
"ready" is the host's dispatches, so neither clock says what the programs
cost), and the five operations that take most of it. ``--mesh`` puts the
words on every local chip first, as ``download_to_device(mesh=,
placement="replicated")`` leaves them. The words are a fill, not a
checkpoint's values, in whole pieces as a sink assembles them: the views'
time does not depend on the values. The parent never imports jax: a chip belongs to one
process at a time. The table goes to stdout and to
``chiprun_out/views_probe.json`` (``views_probe_mesh.json`` with ``--mesh``);
PERF.md section 6 (PR 44; PR 32 before it) holds the reading that chose
``_GROUP_CAP``.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import statistics
import subprocess
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path[:0] = [REPO, os.path.join(REPO, "chipbench")]

CONFIG = os.path.join(REPO, "chipbench", "configs", "moonlight-shard-1p7g.json")
CAPS = (1, 4, 8, 16, 32, 64)
REPEATS = 10
TRACED = 3


def one(cap: int, config: str, mesh: bool) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from dragonfly2_tpu.ops import bitview, safetensors as st
    from objects.safetensors_shard import Objects

    jax.config.update("jax_enable_compilation_cache", False)
    bitview._GROUP_CAP = cap
    with open(config) as f:
        widths = json.load(f)
    obj = Objects(widths, seed=1)
    header, data_start = st.parse_header(obj.head)
    # Whole pieces, zero-padded past the content, as the sink assembles.
    piece = widths["object"]["piece_bytes"]
    n_words = -(-obj.length // piece) * piece // 4

    def fill():
        i = jnp.arange(n_words, dtype=jnp.uint32)
        return (i * jnp.uint32(2654435761)) ^ (i >> 7)

    devices = jax.devices()
    words = jax.jit(fill)()
    if mesh:
        words = jax.device_put(
            words, NamedSharding(Mesh(np.array(devices), ("d",)), P()))
    words = jax.block_until_ready(words)

    def counted() -> tuple[float, float, float]:
        if bitview.VIEWS_TENSORS._labelnames:
            rows, flat = (bitview.VIEWS_TENSORS.labels(form)._value.get()
                          for form in ("rows", "flat"))
        else:
            # A tree from before PR 44, probed for comparison: no label.
            rows, flat = 0.0, bitview.VIEWS_TENSORS._value.get()
        return bitview.VIEWS_DISPATCHES._value.get(), rows + flat, rows

    def load() -> tuple[float, float]:
        t0 = time.perf_counter()
        tensors = st.tensor_views(words, header, data_start,
                                  total=obj.length)
        t1 = time.perf_counter()
        jax.block_until_ready(tensors)
        return t1 - t0, time.perf_counter() - t0

    _, first_s = load()
    before = counted()
    returned, ready = zip(*(load() for _ in range(REPEATS)))
    after = counted()
    busy_ms, largest = traced(load, len(devices) if mesh else 1)
    stats = devices[0].memory_stats() or {}
    return {
        "cap": cap, "device": devices[0].device_kind,
        "chips": len(devices) if mesh else 1, "tensors": len(obj.tensors),
        "first_load_s": first_s,
        "returned_ms": statistics.median(returned) * 1e3,
        "ready_ms": statistics.median(ready) * 1e3,
        "ready_ms_all": [r * 1e3 for r in ready],
        "busy_ms": busy_ms, "largest_ops_ms": largest,
        "dispatches": (after[0] - before[0]) / REPEATS,
        "tensors_counted": (after[1] - before[1]) / REPEATS,
        "tensors_rows": (after[2] - before[2]) / REPEATS,
        "bytes_in_use": stats.get("bytes_in_use"),
        "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
        "content_bytes": obj.length,
    }


def traced(load, chips: int) -> tuple[float | None, list]:
    """(ms a load keeps a chip busy, its five largest operations as
    [name, ms a load]) over ``TRACED`` loads under the profiler; (None, [])
    where the trace holds no chip's plane (the CPU rehearsal)."""
    import jax

    import reduce_trace

    where = tempfile.mkdtemp(prefix="views_probe_")
    try:
        with jax.profiler.trace(where):
            for _ in range(TRACED):
                load()
        trace = reduce_trace.read_xplane(where)
    finally:
        shutil.rmtree(where, ignore_errors=True)
    planes = reduce_trace.chip_planes(trace)[:chips]
    if not planes:
        return None, []
    busy, by_name = 0.0, {}
    for plane in planes:
        ops = reduce_trace.events_on(trace, plane, reduce_trace.OPS_LINES)
        busy += reduce_trace.total((s, s + d) for _, s, d in ops)
        for name, _, d in ops:
            by_name[name] = by_name.get(name, 0.0) + d
    share = 1e3 / (TRACED * len(planes))
    largest = sorted(by_name.items(), key=lambda kv: -kv[1])[:5]
    return busy * share, [[name[:120], d * share] for name, d in largest]


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--one", type=int)
    parser.add_argument("--caps", type=int, nargs="*", default=list(CAPS))
    parser.add_argument("--config", default=CONFIG)
    parser.add_argument("--mesh", action="store_true")
    args = parser.parse_args(argv)
    if args.one:
        print("VIEWS_PROBE " + json.dumps(
            one(args.one, args.config, args.mesh)), flush=True)
        return 0
    rows = []
    for cap in args.caps:
        t0 = time.perf_counter()
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--one", str(cap),
             "--config", args.config] + (["--mesh"] if args.mesh else []),
            capture_output=True, text=True, timeout=900)
        line = next((ln for ln in proc.stdout.splitlines()
                     if ln.startswith("VIEWS_PROBE ")), None)
        if line is None:
            rows.append({"cap": cap, "rc": proc.returncode,
                         "error": (proc.stderr or proc.stdout)[-1500:]})
        else:
            rows.append(json.loads(line[len("VIEWS_PROBE "):]))
        rows[-1]["child_s"] = time.perf_counter() - t0
        print(f"[views_probe] cap {cap}: " + json.dumps(rows[-1]),
              flush=True)
    out = os.path.join(REPO, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    name = "views_probe_mesh.json" if args.mesh else "views_probe.json"
    with open(os.path.join(out, name), "w") as f:
        json.dump(rows, f, indent=1)
    return 1 if any("error" in r for r in rows) else 0


if __name__ == "__main__":
    sys.exit(main())
