"""The checkpoint-delta plane and its hot-swap held to the plain reference of
``delta_reference.py``: the chunker's cut points and digests, the delta plan
and its byte counts, and, over a chain of versions on a real fabric (origin,
scheduler, seed, a daemon with the sink), every swapped tensor bit for bit
with a reader hammering the DoubleBuffer, the spans and counters of the
device half, and one compiled program for every version of a geometry.
"""

from __future__ import annotations

import asyncio
import hashlib
import json
import os
import threading

import numpy as np
import pytest

from dragonfly2_tpu.delta.chunker import CDCParams, chunk_bytes
from dragonfly2_tpu.delta.manifest import build_manifest
from dragonfly2_tpu.delta.resolver import plan_delta
from tests import delta_reference as ref

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GEOMETRIES = [CDCParams(12, 2 << 10, 32 << 10), CDCParams(14, 4 << 10, 64 << 10),
              CDCParams(16, 64 << 10, 256 << 10), CDCParams()]
P = GEOMETRIES[0]
SEED, VERSIONS = 11, 5


def _args(p: CDCParams) -> tuple[int, int, int]:
    return p.mask_bits, p.min_size, p.max_size


@pytest.mark.parametrize("params", GEOMETRIES, ids=lambda p: f"bits{p.mask_bits}")
@pytest.mark.parametrize("seed", [1, 2])
def test_the_chunker_cuts_where_the_reference_cuts(params, seed):
    data = np.random.default_rng(seed).integers(
        0, 256, (6 << 20) + 12345 * seed, dtype=np.uint8).tobytes()
    got = [(c.offset, c.length, c.sha256) for c in chunk_bytes(data, params)]
    assert got == ref.chunks_of(data, *_args(params))


def test_a_stream_with_no_candidate_is_cut_at_the_bound():
    data = bytes(300_000)       # one byte value: the hash never moves
    small = CDCParams(12, 2 << 10, 32 << 10)
    got = [(c.offset, c.length, c.sha256) for c in chunk_bytes(data, small)]
    assert got == ref.chunks_of(data, *_args(small))


@pytest.mark.parametrize("version", range(VERSIONS - 1))
def test_the_plan_is_the_references(version):
    new, base = (ref.safetensors_file(ref.version_tensors(SEED, v))
                 for v in (version + 1, version))
    plan = plan_delta(build_manifest(new, params=P),
                      build_manifest(base, params=P))
    want = ref.delta_plan(ref.chunks_of(new, *_args(P)),
                          ref.chunks_of(base, *_args(P)))
    assert (plan.fetched_bytes, plan.reused_bytes, plan.fetch_spans()) == (
        want["fetched_bytes"], want["reused_bytes"], want["spans"])
    # Two experts of sixteen drawn again: most of a version is the base's.
    assert 0 < want["fetched_bytes"] < 0.25 * len(new)


@pytest.mark.parametrize("name", ["moonlight-esft-swap",
                                  "tiny-shard-swap-12m"])
def test_the_configurations_chunking_is_the_programs_default(name):
    """No caller of ``download_delta`` or ``Daemon.Download`` can change the
    chunking, so the benchmark's reference must chunk as the program does."""
    folder = "configs" if name.startswith("moonlight") else "rehearsal"
    with open(os.path.join(REPO, "chipbench", folder, name + ".json")) as f:
        cdc = json.load(f)["versions"]["cdc"]
    default = CDCParams()
    assert cdc == {"mask_bits": default.mask_bits,
                   "min_size": default.min_size, "max_size": default.max_size}


# (new length, piece size, the runs as (kind, source offset, length), the
# live generation's bytes): what the device half must make of any plan.
LIVE = np.random.default_rng(9).integers(0, 256, 3 << 20, dtype=np.uint8) \
    .tobytes()
ASSEMBLIES = {
    "in place, edges inside words": (
        4096, 2048, [("r", 0, 1001), ("f", 0, 1047), ("r", 2048, 2048)]),
    "shifted by bytes: staged": (
        2560, 2048, [("r", 1030, 1024), ("f", 0, 512), ("r", 6, 1024)]),
    "shifted by whole rows": (
        3 << 20, 1 << 20, [("r", 51200, 1 << 20), ("f", 0, 1 << 20),
                           ("r", 0, 1 << 20)]),
    "a short last piece": (
        (3 << 20) - 5, 1 << 20, [("r", 0, 700001), ("f", 0, 300003),
                                 ("r", 1000004, (3 << 20) - 5 - 1000004)]),
    "nothing live": (1000, 4096, [("f", 0, 1000)]),
    "nothing fetched": (1 << 20, 1 << 19, [("r", 0, 1 << 20)]),
}


@pytest.mark.parametrize("case", list(ASSEMBLIES))
def test_the_assembly_makes_the_new_words_of_any_runs(case):
    import jax
    import jax.numpy as jnp

    from dragonfly2_tpu.ops import hbm_sink
    from dragonfly2_tpu.ops.checksum import checksum_numpy

    total, piece, spec = ASSEMBLIES[case]
    live_bytes = b"" if case == "nothing live" else LIVE
    live = jnp.asarray(np.frombuffer(live_bytes, "<u4"))
    fresh = np.random.default_rng(4)
    want, runs = bytearray(), []
    for kind, src, length in spec:
        runs.append([len(want), src if kind == "r" else len(want), length,
                     kind == "r"])
        want += (live_bytes[src:src + length] if kind == "r" else
                 fresh.integers(0, 256, length, dtype=np.uint8).tobytes())
    assert len(want) == total
    pieces = -(-total // piece)
    plan = hbm_sink.plan_swap(runs, total, pieces * piece // 4,
                              live.shape[0])
    device = jax.devices()[0]

    def read_into(start, length, buf):
        buf[:length] = want[start:start + length]

    words = hbm_sink.assemble_swap_words(
        live, plan, hbm_sink.stage_swap(plan, read_into, device), device)
    got = np.asarray(words).tobytes()
    assert got[:total] == bytes(want) and not any(got[total:])
    hbm_sink.verify_words_against_host(words, piece, {
        i: checksum_numpy(bytes(want[i * piece:(i + 1) * piece]))
        for i in range(pieces)})
    reusable = sum(length for kind, src, length in spec if kind == "r")
    if case in ("shifted by bytes: staged", "nothing live"):
        assert plan.reused_bytes == 0 and not plan.live_segs
    else:
        # A run loses at most the word at either end.
        assert reusable - 8 * len(spec) <= plan.reused_bytes <= reusable


# ------------------------------------------------------------------ #
# A chain of versions through download_delta and a DoubleBuffer
# ------------------------------------------------------------------ #

async def _run_chain(tmp_path) -> dict:
    from aiohttp import web

    from dragonfly2_tpu.client import device as device_lib
    from dragonfly2_tpu.delta.resolver import (
        DELTA_BYTES,
        publish_manifest_for,
    )
    from dragonfly2_tpu.ops import hbm_sink
    from dragonfly2_tpu.pkg import flight as flightlib
    from dragonfly2_tpu.pkg.piece import Range
    from tests import test_p2p_e2e as e2e
    from tests.test_delta import _drain_task, _file_req
    from tests.test_device_sink import _start_sink_daemon

    files = [ref.safetensors_file(ref.version_tensors(SEED, v))
             for v in range(VERSIONS)]
    shas = ["sha256:" + hashlib.sha256(f).hexdigest() for f in files]

    async def blob(request):
        content = files[int(request.match_info["v"])]
        hdr = request.headers.get("Range")
        if not hdr:
            return web.Response(body=content,
                                headers={"Accept-Ranges": "bytes"})
        r = Range.parse_http(hdr, len(content))
        data = content[r.start:r.start + r.length]
        return web.Response(status=206, body=data, headers={
            "Content-Range": f"bytes {r.start}-{r.start + len(data) - 1}"
                             f"/{len(content)}", "Accept-Ranges": "bytes"})

    app = web.Application()
    app.router.add_get("/v{v}", blob)
    runner = web.AppRunner(app, access_log=None)
    await runner.setup()
    site = web.TCPSite(runner, "127.0.0.1", 0)
    await site.start()
    base_url = f"http://127.0.0.1:{site._server.sockets[0].getsockname()[1]}"

    def counters() -> dict:
        return {
            "compiled": hbm_sink.SWAP_ASSEMBLIES.labels("compiled")._value.get(),
            "cached": hbm_sink.SWAP_ASSEMBLIES.labels("cached")._value.get(),
            "flipped": hbm_sink.SWAP_RESULTS.labels("flipped")._value.get(),
            "hbm_reused": hbm_sink.SWAP_BYTES.labels("hbm_reused")._value.get(),
            "staged": hbm_sink.SWAP_BYTES.labels("staged")._value.get(),
            "fetched": DELTA_BYTES.labels("fetched")._value.get(),
            "reused": DELTA_BYTES.labels("reused")._value.get()}

    sched = await e2e.start_scheduler()
    daemons = []
    chain = {"files": files, "swaps": [], "notes": [], "held": []}
    stop = threading.Event()
    try:
        seed = await e2e.start_daemon(tmp_path, "seedc", sched.port(),
                                      seed=True)
        pod = await _start_sink_daemon(tmp_path, "podc", sched.port())
        daemons += [seed, pod]
        pod.task_manager.flight = flightlib.FlightRecorder()
        for v in range(VERSIONS):
            landed = await _drain_task(seed.task_manager,
                                       _file_req(f"{base_url}/v{v}", shas[v]))
            await publish_manifest_for(seed.task_manager, landed.task_id,
                                       params=P)
        first = await device_lib.download_to_device(
            pod, f"{base_url}/v0", digest=shas[0])
        hot = hbm_sink.DoubleBuffer()
        hot.flip(first.as_words(), first.load_safetensors())
        versions_of = {1: 0}

        def reader():
            held = None
            while not stop.is_set():
                snapshot = hot.snapshot()
                generation, _, tensors = snapshot
                # One word of every tensor: whose version is it?
                chain["notes"].append((generation, {
                    name: np.asarray(t.reshape(-1)[:2]).tobytes()
                    for name, t in tensors.items()}))
                if held is not None and held[0] != generation:
                    chain["held"].append((held[0], {
                        name: np.asarray(t).tobytes()
                        for name, t in held[2].items()}))
                held = snapshot

        thread = threading.Thread(target=reader)
        thread.start()
        task_id = first.task_id
        del first
        for v in range(1, VERSIONS):
            before = counters()
            swap = await device_lib.download_delta(
                pod, f"{base_url}/v{v}", base=task_id, hot=hot,
                digest=shas[v])
            after = counters()
            tf = pod.task_manager.flight.get(swap.task_id)
            chain["swaps"].append({
                "version": v, "generation": swap.generation,
                "flipped": swap.flipped, "on_device": swap.on_device,
                "hbm_reused": swap.reused_device_bytes,
                "staged": swap.staged_bytes, "stats": swap.stats,
                "buffer": (str(swap.buffer.dtype), swap.buffer.shape),
                "counted": {k: after[k] - before[k] for k in after},
                "events": [(flightlib.EVENT_NAMES.get(code), piece, aux, note)
                           for _, code, piece, aux, note in tf.events()],
                "report": flightlib.analyze(tf),
                "tensors": {name: np.asarray(t).tobytes()
                            for name, t in hot.tensors().items()}})
            versions_of[swap.generation] = v
            task_id = swap.task_id
            await asyncio.sleep(0.05)     # the reader sees every generation
        chain["versions_of"] = versions_of
    finally:
        stop.set()
        if "thread" in locals():
            thread.join(30)
        for d in daemons:
            await d.stop()
        await sched.stop()
        await runner.cleanup()
    return chain


@pytest.fixture(scope="module")
def chain(tmp_path_factory):
    return asyncio.run(asyncio.wait_for(
        _run_chain(tmp_path_factory.mktemp("chain")), 240))


def _want(version: int) -> dict[str, bytes]:
    return {name: array.tobytes()
            for name, array in ref.version_tensors(SEED, version).items()}


def test_every_swapped_tensor_is_the_references_bit_for_bit(chain):
    assert [s["version"] for s in chain["swaps"]] == list(range(1, VERSIONS))
    for swap in chain["swaps"]:
        assert swap["flipped"] and swap["on_device"]
        assert swap["generation"] == swap["version"] + 1
        assert swap["tensors"] == _want(swap["version"])
        # The generation is words, whole pieces of them, as a landing's.
        assert swap["buffer"][0] == "uint32"


def test_the_byte_counters_are_the_references(chain):
    chunks = [ref.chunks_of(f, *_args(P)) for f in chain["files"]]
    for swap in chain["swaps"]:
        v, total = swap["version"], len(chain["files"][swap["version"]])
        want = ref.delta_plan(chunks[v], chunks[v - 1])
        stats, counted = swap["stats"], swap["counted"]
        assert stats["fetched_bytes"] == counted["fetched"] \
            == want["fetched_bytes"]
        assert stats["reused_bytes"] == counted["reused"] \
            == want["reused_bytes"]
        assert stats["corrupt_base"] == 0
        assert stats["reused_bytes"] + stats["fetched_bytes"] == total
        # The device half: what no run holds is staged, the rest never
        # leaves HBM; a run loses at most a word at either end.
        assert swap["hbm_reused"] + swap["staged"] == total
        assert (counted["hbm_reused"], counted["staged"]) == (
            swap["hbm_reused"], swap["staged"])
        runs = len(want["spans"]) + 1
        assert 0 <= want["reused_bytes"] - swap["hbm_reused"] <= 8 * runs
        assert counted["flipped"] == 1


def test_versions_of_one_geometry_share_one_compiled_program(chain):
    """Different experts change from version to version; the copy program
    and the gate's are compiled for the first swap alone."""
    compiled = [s["counted"]["compiled"] for s in chain["swaps"]]
    cached = [s["counted"]["cached"] for s in chain["swaps"]]
    assert compiled[1:] == [0] * (VERSIONS - 2)
    assert cached[1:] == [1] * (VERSIONS - 2) and compiled[0] + cached[0] == 1
    changed = [frozenset(n for n, b in s["tensors"].items()
                         if b != _want(s["version"] - 1)[n])
               for s in chain["swaps"]]
    assert len(set(changed)) > 1 and all(len(c) == 6 for c in changed)


def test_the_reader_saw_complete_generations_only(chain):
    wants = {g: _want(v) for g, v in chain["versions_of"].items()}
    assert chain["notes"]
    generations = [g for g, _ in chain["notes"]]
    assert generations == sorted(generations)
    assert set(generations) == set(chain["versions_of"])
    for generation, heads in chain["notes"]:
        assert {name: wants[generation][name][:len(head)]
                for name, head in heads.items()} == heads, generation
        assert set(heads) == set(wants[generation])
    # A generation the reader still held when the next one was live reads
    # as it did, every tensor of it.
    assert len(chain["held"]) == VERSIONS - 1
    for generation, tensors in chain["held"]:
        assert tensors == _want(chain["versions_of"][generation])


def test_a_swaps_spans_are_on_the_delta_tasks_flight(chain):
    """Counts and containment, no clock: the resolver's plan, then the
    landing's chunks, then the device half's spans in order, each once."""
    steps = ["admit_wait", "swap_plan", "swap_stage", "swap_assemble",
             "swap_verify", "swap_views", "swap_flip"]
    for swap in chain["swaps"]:
        names = [e[0] for e in swap["events"]]
        assert names[0] == "swap_plan"
        assert names.count("swap_plan") == 2
        assert [n for n in names[names.index("task_done"):] if n in steps] \
            == steps
        landing = names[1:names.index("task_done")]
        assert set(landing) >= {"delta_reuse", "delta_fetch"}
        by_name = {e[0]: e for e in swap["events"]}
        assert by_name["swap_flip"][1] == swap["generation"]
        assert by_name["swap_flip"][3] == ""
        assert by_name["swap_stage"][3] == str(swap["staged"])
        assert by_name["swap_views"][1] == len(swap["tensors"])
        assert swap["events"][0][3] in ("fetched", "built")
        # The spans lie inside the call: each ended before the flip did.
        assert all(aux >= 0 for _, _, aux, _ in swap["events"])
        assert by_name["swap_flip"][2] >= max(
            by_name[n][2] for n in steps[2:6])
        client = swap["report"]["client"]
        assert {"swap_plan_ms", "swap_stage_ms", "swap_assemble_ms",
                "swap_verify_ms", "swap_views_ms"} <= set(client)


def test_explain_prints_the_swaps_steps(chain):
    from dragonfly2_tpu.pkg import flight as flightlib

    text = flightlib.render_waterfall(chain["swaps"][-1]["report"])
    assert "swap_assemble=" in text and "swap_verify=" in text
