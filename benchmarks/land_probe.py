"""What a landed piece costs the host: a probe of the pass that reads
pieces from the store into their staging rows and checksums them
(``ops/hbm_sink.py`` "Host passes"), as a re-land through
``DeviceSinkManager.finalize`` alone: no scheduler, no transfer, no views.

    chiprun --chips 1 -- python3 benchmarks/land_probe.py
    JAX_PLATFORMS=cpu python3 benchmarks/land_probe.py \\
        --objects 20000003:4194304                          # the rehearsal

For each object (the benchmark's two geometries, the Moonlight shard's 55
pieces of 32 MiB and a LAION tar's 30 of 8 MiB, and 60 pieces of 16 MiB
between them; random bytes, written to a store of the probe's own and so
in the page cache) and each arrangement,
the median and the range over ``--repeats`` re-lands, after one that is not
counted, of ``sink_finalize`` and of the summed ``sink_read``,
``sink_checksum`` and ``sink_stage`` (which holds the wait for a staging
stack that the runtime is still reading, once the host pass outruns the
link) of the flight, in ms:

  a pass a piece      the tree before PR 40, and a cold pull's path still:
                      one pass and one wait a piece (``HBMSink.free_rows``
                      patched to 1), a helper reads its chunk of the piece
                      and checksums it before it returns
  a pass a stack      the tree as it is: the backfill's pass takes what the
                      open stack has free, every piece cut as it would be
                      alone, ONE wait for all the chunks
  a stack, whole pieces  the same with a piece a chunk (``cuts`` patched):
                      eight hand-overs a stack where the tree makes 32-64
  a pass a stack, h/f the tree's pass with h helpers (1: one thread takes
                      every piece in turn) and a chunk floor of f KiB

Then, for each ``--queued`` geometry (``length:piece_size:sinks``; a LAION
tar's 30 x 8 MiB three times, as ``tar-reland``'s three clients queue them,
and 8 x 4 MiB sixteen times, as ``host-reland-ep4``'s small ranges), that
many sinks of the one geometry asked of ``DeviceSinkManager.finalize`` AT
ONCE through the sink's admission, as ``download_to_device`` asks (three hold
a slot, the others wait there), each discarded as it ends:

  N sinks queued      the round's wall time a sink, and per sink the landing
                      thread's ms (``sink_finalize`` less ``sink_tail``: job
                      start -> the hand-over; the whole ``sink_finalize``
                      where the program stamps no ``sink_tail``), the tail's
                      (``sink_tail``), ``sink_assemble`` inside it,
                      ``sink_wait``, and how many tails had another job
                      started beside them (``sink_tail``'s ``piece`` >= 1)

All but the tree's are the tree's own code under a patch; every
arrangement's host checksums must equal the first's, and the device
verifies each landing. The table goes to stdout and to
``chiprun_out/land_probe.json``; PERF.md section 5 ("The passes, alone")
holds the reading that ``_HELPERS`` and ``_CHUNK_FLOOR`` rest on (PR 35's
rows of the two passes of before it, and of reductions in blocks, are kept
there; their arrangements left this file with PR 40).
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import shutil
import statistics
import sys
import tempfile
import time
from concurrent.futures import ThreadPoolExecutor

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, REPO)

OBJECTS = ("1843431563:33554432", "1000000007:16777216",
           "250000384:8388608")
OTHERS = ("1:2048", "4:2048", "12:2048", "8:1024", "8:4096")
QUEUED = ("250000384:8388608:3", "33554432:4194304:16")


def _store(root: str, name: str, length: int, piece_size: int):
    """A completed store of ``length`` random bytes in ``piece_size``
    pieces."""
    import numpy as np

    from dragonfly2_tpu.storage.local_store import (
        LocalTaskStore,
        TaskStoreMetadata,
    )

    pieces = -(-length // piece_size)
    store = LocalTaskStore(
        os.path.join(root, name),
        TaskStoreMetadata(task_id=name, content_length=length,
                          piece_size=piece_size, total_piece_count=pieces))
    rng = np.random.default_rng(length)
    for n in range(pieces):
        size = min(piece_size, length - n * piece_size)
        store.write_piece(n, rng.integers(0, 256, size, dtype=np.uint8).data)
    return store


def _counted() -> dict:
    from dragonfly2_tpu.ops import hbm_sink

    return {**{k: hbm_sink.SINK_PASSES.labels(k)._value.get()
               for k in ("fused", "checksum")},
            **{k: hbm_sink.SINK_PIECES.labels(k)._value.get()
               for k in ("batched", "split", "whole")}}


def _arrangements(helpers: int, floor: int, others) -> list:
    """(name, helpers, chunk floor, rows a pass may take or None for the
    stack's, whether a piece is one chunk)."""
    rows = [("a pass a piece", helpers, floor, 1, False),
            ("a pass a stack", helpers, floor, None, False),
            ("a stack, whole pieces", helpers, floor, None, True)]
    for other in others:
        h, kib = (int(v) for v in other.split(":"))
        rows.append((f"a pass a stack, {h}/{kib}", h, kib << 10, None,
                     False))
    return rows


async def _reland(mgr, store, repeats: int) -> dict:
    from dragonfly2_tpu.pkg import flight

    task_id = store.metadata.task_id
    runs, checksums = [], None
    counted = {}
    for i in range(repeats + 1):
        before = _counted()
        tf = flight.TaskFlight(task_id)
        sink = await mgr.finalize(task_id, store, tf)
        if sink is None or not sink.verified:
            raise RuntimeError(
                f"{task_id}: no verified landing: "
                f"{mgr.outcome(task_id, False)}")
        checksums = dict(sink.sink.host_checksums)
        counted = {k: n - before[k] for k, n in _counted().items()}
        mgr.discard(task_id)
        del sink
        ms = {"sink_finalize": 0.0, "sink_read": 0.0, "sink_checksum": 0.0,
              "sink_stage": 0.0}
        for _, code, _, aux, _ in tf.events():
            name = flight.EVENT_NAMES[code]
            if name in ms:
                ms[name] += aux
        if i:                               # the first is the warm-up
            runs.append(ms)
    out = {"counted": counted, "checksums": checksums}
    for key, name in (("finalize_ms", "sink_finalize"),
                      ("read_ms", "sink_read"),
                      ("checksum_ms", "sink_checksum"),
                      ("stage_ms", "sink_stage")):
        values = sorted(r[name] for r in runs)
        out[key] = statistics.median(values)
        out[key + "_range"] = [values[0], values[-1]]
    return out


async def _queued(mgr, stores, repeats: int) -> dict:
    """``stores``' sinks landed at once, ``repeats`` rounds after one that
    is not counted: medians over the counted rounds' sinks."""
    from dragonfly2_tpu.pkg import flight

    names = ("sink_finalize", "sink_tail", "sink_assemble", "sink_wait")

    async def land(store, tf):
        task_id = store.metadata.task_id
        async with mgr.admit():
            sink = await mgr.finalize(task_id, store, tf)
            if sink is None or not sink.verified:
                raise RuntimeError(
                    f"{task_id}: no verified landing: "
                    f"{mgr.outcome(task_id, False)}")
            mgr.discard(task_id)

    sinks, rounds = [], []
    for i in range(repeats + 1):
        flights = [flight.TaskFlight(store.metadata.task_id)
                   for store in stores]
        t0 = time.perf_counter()
        await asyncio.gather(*map(land, stores, flights))
        wall_ms = (time.perf_counter() - t0) * 1000.0
        if not i:                           # the first is the warm-up
            continue
        rounds.append(wall_ms / len(stores))
        for tf in flights:
            ms = dict.fromkeys(names, 0.0)
            beside = None
            for _, code, piece, aux, _ in tf.events():
                name = flight.EVENT_NAMES[code]
                if name in ms:
                    ms[name] += aux
                if name == "sink_tail":
                    beside = piece
            sinks.append({**ms, "beside": beside})
    tails = [s["beside"] for s in sinks if s["beside"] is not None]
    return {
        "round_ms_a_sink": statistics.median(rounds),
        "round_ms_a_sink_range": [min(rounds), max(rounds)],
        "thread_ms": statistics.median(
            s["sink_finalize"] - s["sink_tail"] for s in sinks),
        "tail_ms": (statistics.median(s["sink_tail"] for s in sinks)
                    if tails else None),
        "assemble_ms": statistics.median(s["sink_assemble"] for s in sinks),
        "finalize_ms": statistics.median(s["sink_finalize"] for s in sinks),
        "wait_ms": statistics.median(s["sink_wait"] for s in sinks),
        "tails_beside_a_job": (sum(1 for n in tails if n) if tails
                               else None),
        "sinks": len(sinks)}


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--objects", nargs="*", default=list(OBJECTS),
                        help="length:piece_size, bytes")
    parser.add_argument("--repeats", type=int, default=3)
    parser.add_argument("--others", nargs="*", default=list(OTHERS),
                        help="helpers:floor_KiB of further passes a stack")
    parser.add_argument("--queued", nargs="*", default=list(QUEUED),
                        help="length:piece_size:sinks landed at once")
    args = parser.parse_args(argv)

    import jax

    from dragonfly2_tpu.daemon.peer.device_sink import DeviceSinkManager
    from dragonfly2_tpu.ops import hbm_sink

    device = jax.devices()[0]
    tree = (hbm_sink._HELPERS, hbm_sink._CHUNK_FLOOR, hbm_sink._POOL,
            hbm_sink.HBMSink.free_rows, hbm_sink.cuts)

    def restore() -> None:
        (hbm_sink._HELPERS, hbm_sink._CHUNK_FLOOR, hbm_sink._POOL,
         hbm_sink.HBMSink.free_rows, hbm_sink.cuts) = tree

    root = tempfile.mkdtemp(prefix=".land_probe_", dir=REPO)
    rows, queued = [], []
    try:
        for spec in args.objects:
            length, piece_size = (int(v) for v in spec.split(":"))
            t0 = time.perf_counter()
            store = _store(root, f"probe-{length}-{piece_size}", length,
                           piece_size)
            print(f"[land_probe] {spec}: {len(store.metadata.pieces)} pieces "
                  f"stored in {time.perf_counter() - t0:.1f} s", flush=True)
            first = None
            for name, h, floor, rows_a_pass, whole in _arrangements(
                    tree[0], tree[1], args.others):
                pool = ThreadPoolExecutor(
                    max_workers=h, thread_name_prefix="df-sink-helper")
                hbm_sink._HELPERS, hbm_sink._CHUNK_FLOOR = h, floor
                hbm_sink._POOL = pool
                hbm_sink.HBMSink.free_rows = (
                    (lambda sink, n=rows_a_pass: n) if rows_a_pass
                    else tree[3])
                hbm_sink.cuts = ((lambda size: [(0, size)]) if whole
                                 else tree[4])
                mgr = DeviceSinkManager()
                try:
                    row = asyncio.run(_reland(mgr, store, args.repeats))
                finally:
                    mgr.close()
                    pool.shutdown()
                checksums = row.pop("checksums")
                first = first or checksums
                row.update(object=spec, arrangement=name, helpers=h,
                           floor=floor, pieces=len(checksums),
                           device=device.device_kind,
                           same_bits=checksums == first)
                rows.append(row)
                print(f"[land_probe] {spec} {name}: " + json.dumps(row),
                      flush=True)
            store.destroy()
        restore()                           # the tree as it is from here on
        for spec in args.queued:
            length, piece_size, count = (int(v) for v in spec.split(":"))
            stores = [_store(root, f"queued-{length}-{piece_size}-{n}",
                             length + 4 * n, piece_size)
                      for n in range(count)]
            mgr = DeviceSinkManager()
            try:
                row = asyncio.run(_queued(mgr, stores, args.repeats))
            finally:
                mgr.close()
            row.update(geometry=spec, device=device.device_kind)
            queued.append(row)
            print(f"[land_probe] {spec} queued: " + json.dumps(row),
                  flush=True)
            for store in stores:
                store.destroy()
    finally:
        restore()
        shutil.rmtree(root, ignore_errors=True)
    out = os.path.join(REPO, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "land_probe.json"), "w") as f:
        json.dump({"passes": rows, "queued": queued}, f, indent=1)
    print(f"{'object':>22} {'arrangement':>28} {'finalize':>9} {'read':>8} "
          f"{'checksum':>8} {'stage':>7}  ms, median of {args.repeats}")
    for r in rows:
        print(f"{r['object']:>22} {r['arrangement']:>28} "
              f"{r['finalize_ms']:9.1f} {r['read_ms']:8.1f} "
              f"{r['checksum_ms']:8.1f} {r['stage_ms']:7.1f}")
    print(f"{'sinks at once':>26} {'round/sink':>10} {'thread':>8} "
          f"{'tail':>8} {'assemble':>8} {'wait':>8} {'beside':>7}  "
          f"ms a sink, medians over {args.repeats} rounds")
    for r in queued:
        tail = "-" if r["tail_ms"] is None else f"{r['tail_ms']:.1f}"
        beside = ("-" if r["tails_beside_a_job"] is None
                  else f"{r['tails_beside_a_job']}/{r['sinks']}")
        print(f"{r['geometry']:>26} {r['round_ms_a_sink']:10.1f} "
              f"{r['thread_ms']:8.1f} {tail:>8} {r['assemble_ms']:8.1f} "
              f"{r['wait_ms']:8.1f} {beside:>7}")
    return 0 if all(r["same_bits"] for r in rows) else 1


if __name__ == "__main__":
    sys.exit(main())
