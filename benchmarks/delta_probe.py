"""How many piece jobs a delta landing should hold in flight: the probe of
``delta/resolver.py`` ``_JOBS_IN_FLIGHT``, on one TaskManager with no
scheduler and no device. It imports no jax.

    chiprun --chips 1 -- python3 benchmarks/delta_probe.py
    python3 benchmarks/delta_probe.py --pieces 3 --piece-bytes 4194304 --repeats 1  # the rehearsal

Version N is ``--pieces`` pieces of ``--piece-bytes`` random bytes (the
benchmark's shard geometry: 55 of 32 MiB), imported into the store and so
in the page cache, as a serving replica's live version is. Version N+1 is
the same bytes with ``--changed`` of them replaced in ``--runs`` runs (an
expert-specialised checkpoint: a few contiguous runs of new experts), served
by an origin in this process; both are chunked with the benchmark's
parameters (``chipbench/configs/moonlight-esft-swap.json`` ``versions.cdc``)
and the plan is ``plan_delta``'s. One landing that is not counted leaves the
fetched spans' ranged tasks in the store, as the seed's store has them
before a swap asks. Then, for each number of jobs in flight patched over the
constant, ``--repeats`` landings through ``_run_delta`` itself, the landed
task deleted after each: the median of

  landing_s        request -> the final ``done`` frame (pieces, completion
                   digest, announce)
  reuse_union_ms   the union of the landing's ``delta_reuse`` spans: the ms in
                   which at least one job was reading and hashing base chunks
  digest_tail_ms   ``verify_start`` -> ``verified``: what the whole-object
                   sha256 still had to do when the last piece was written
  frontier         pieces the prefix hasher had hashed at ``verify_start``
  how              ``verified``'s note: ``prefix`` or ``rehash``

Every landing is checked byte for byte against version N+1's sha256 (the
task's own completion digest) and ``reused + fetched == content``. The table
goes to stdout and to ``--out`` (``chiprun_out/delta_probe.json``); PERF.md section 5
("The delta landing, alone") holds the reading the constant rests on.
"""

from __future__ import annotations

import argparse
import asyncio
import hashlib
import json
import os
import shutil
import statistics
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, REPO)

IN_FLIGHT = (1, 2, 4, 6, 8)


def _versions(pieces: int, piece_bytes: int, changed: float, runs: int):
    import numpy as np

    rng = np.random.default_rng(pieces)
    total = pieces * piece_bytes
    v1 = rng.integers(0, 256, total, dtype=np.uint8)
    v2 = v1.copy()
    run = int(total * changed / runs)
    for k in range(runs):
        at = (2 * k + 1) * total // (2 * runs)
        v2[at:at + run] = rng.integers(0, 256, run, dtype=np.uint8)
    return v1.tobytes(), v2.tobytes()


def _manifest(content: bytes, params):
    from dragonfly2_tpu.delta.chunker import GearChunker
    from dragonfly2_tpu.delta.manifest import DeltaManifest

    ch = GearChunker(params)
    step = 8 << 20
    for off in range(0, len(content), step):
        ch.feed(content[off:off + step])
    ch.finish()
    return DeltaManifest(name="probe", content_length=len(content),
                         chunks=ch.chunks, params=ch.params)


async def _origin(content: bytes):
    from aiohttp import web

    from dragonfly2_tpu.pkg.piece import Range

    async def blob(request):
        hdr = request.headers.get("Range")
        if not hdr:
            return web.Response(body=content,
                                headers={"Accept-Ranges": "bytes"})
        r = Range.parse_http(hdr, len(content))
        return web.Response(
            status=206, body=content[r.start:r.start + r.length], headers={
                "Content-Range": f"bytes {r.start}-{r.start + r.length - 1}"
                                 f"/{len(content)}",
                "Accept-Ranges": "bytes"})

    app = web.Application()
    app.router.add_get("/v2", blob)
    runner = web.AppRunner(app, access_log=None)
    await runner.setup()
    site = web.TCPSite(runner, "127.0.0.1", 0)
    await site.start()
    port = site._server.sockets[0].getsockname()[1]
    return runner, f"http://127.0.0.1:{port}/v2"


async def _land(tm, req, base_store, new_m, plan) -> dict:
    """One landing through the resolver's own ``_run_delta``; its readings
    from the task's flight."""
    from dragonfly2_tpu.delta import resolver
    from dragonfly2_tpu.pkg import flight as flightlib

    tm.flight = flightlib.FlightRecorder()
    task_id = req.task_id()
    t0 = time.perf_counter()
    final = None
    async for p in resolver._run_delta(tm, req, task_id, base_store, new_m,
                                       plan, 4):
        if p.state == "failed":
            raise RuntimeError(f"landing failed: {p.error}")
        final = p
    seconds = time.perf_counter() - t0
    assert final is not None and final.state == "done"
    stats = tm.delta_stats[task_id]
    assert stats["reused_bytes"] + stats["fetched_bytes"] \
        == new_m.content_length, stats
    assert stats["corrupt_base"] == 0, stats
    store = tm.storage.find_completed_task(task_id)
    assert store is not None and store.metadata.digest == req.meta.digest
    events = [(t, flightlib.EVENT_NAMES.get(code, str(code)), piece, aux, note)
              for t, code, piece, aux, note in tm.flight.get(task_id).events()]
    start = next(e for e in events if e[1] == "verify_start")
    done = next(e for e in events if e[1] == "verified")
    out = {
        "landing_s": seconds,
        "reuse_union_ms": 1000.0 * flightlib._union_s(
            [(t - aux / 1000.0, t) for t, name, _, aux, _ in events
             if name == "delta_reuse"]),
        "digest_tail_ms": done[3],
        "frontier": start[2],
        "how": done[4],
        "fetched_pct": 100.0 * stats["fetched_bytes"] / new_m.content_length,
    }
    tm.storage.delete_task(task_id)
    return out


async def probe(args) -> dict:
    from dragonfly2_tpu.daemon.peer.piece_manager import (
        PieceManager,
        PieceManagerOption,
    )
    from dragonfly2_tpu.daemon.peer.task_manager import (
        FileTaskRequest,
        TaskManager,
    )
    from dragonfly2_tpu.delta import resolver
    from dragonfly2_tpu.delta.chunker import CDCParams
    from dragonfly2_tpu.proto.common import UrlMeta
    from dragonfly2_tpu.source import default_registry
    from dragonfly2_tpu.storage import StorageManager, StorageOption

    with open(os.path.join(REPO, "chipbench", "configs",
                           "moonlight-esft-swap.json")) as f:
        params = CDCParams(**json.load(f)["versions"]["cdc"])
    root = tempfile.mkdtemp(prefix=".delta_probe_", dir=REPO)
    v1, v2 = await asyncio.to_thread(
        _versions, args.pieces, args.piece_bytes, args.changed, args.runs)
    runner, url = await _origin(v2)
    out = {"bytes": len(v2), "pieces": args.pieces, "rows": []}
    try:
        storage = StorageManager(StorageOption(
            data_dir=os.path.join(root, "data")))
        tm = TaskManager(storage, PieceManager(PieceManagerOption()))
        path = os.path.join(root, "v1.bin")
        with open(path, "wb") as f:
            f.write(v1)
        base = await tm.import_task(path, FileTaskRequest(
            url="probe://v1", output="", meta=UrlMeta(tag="delta-probe")))
        base_store = storage.find_completed_task(base["task_id"])
        base_m = await asyncio.to_thread(_manifest, v1, params)
        new_m = await asyncio.to_thread(_manifest, v2, params)
        del v1
        plan = resolver.plan_delta(new_m, base_m)
        req = FileTaskRequest(url=url, output="", meta=UrlMeta(
            tag="delta-probe",
            digest="sha256:" + hashlib.sha256(v2).hexdigest()))
        print(f"{len(v2)} bytes, {new_m.num_chunks} chunks, "
              f"{len(plan.fetch_spans())} fetched spans, "
              f"{100.0 * plan.fetched_bytes / len(v2):.2f} % fetched, "
              f"{os.cpu_count()} cpus", flush=True)
        await _land(tm, req, base_store, new_m, plan)     # not counted
        print(f"{'in flight':>9} {'landing_s':>10} {'range':>15} "
              f"{'reuse_union_ms':>15} {'digest_tail_ms':>15} "
              f"{'frontier':>9} how", flush=True)
        for n in args.in_flight:
            resolver._JOBS_IN_FLIGHT = n
            runs = [await _land(tm, req, base_store, new_m, plan)
                    for _ in range(args.repeats)]
            row = {"in_flight": n, "runs": runs}
            for key in ("landing_s", "reuse_union_ms", "digest_tail_ms",
                        "frontier"):
                row[key] = statistics.median(r[key] for r in runs)
            row["how"] = sorted({r["how"] for r in runs})
            out["rows"].append(row)
            lo = min(r["landing_s"] for r in runs)
            hi = max(r["landing_s"] for r in runs)
            print(f"{n:>9} {row['landing_s']:>10.3f} "
                  f"{f'{lo:.3f}-{hi:.3f}':>15} "
                  f"{row['reuse_union_ms']:>15.1f} "
                  f"{row['digest_tail_ms']:>15.1f} {row['frontier']:>9} "
                  f"{','.join(row['how'])}", flush=True)
        storage.close()
    finally:
        await default_registry().close_all()
        await runner.cleanup()
        shutil.rmtree(root, ignore_errors=True)
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--pieces", type=int, default=55)
    ap.add_argument("--piece-bytes", type=int, default=32 << 20)
    ap.add_argument("--changed", type=float, default=0.075)
    ap.add_argument("--runs", type=int, default=8)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--in-flight", type=lambda s: tuple(
        int(v) for v in s.split(",")), default=IN_FLIGHT)
    ap.add_argument("--out", default=os.path.join(
        REPO, "chiprun_out", "delta_probe.json"))
    args = ap.parse_args()
    out = asyncio.run(probe(args))
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
