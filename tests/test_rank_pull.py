"""One expert-parallel rank's ranged pull (``moonlight-ep4-rank``, the cell
``rank-cold``): ``client.device.download_sharded`` under each rank's selector,
at a small size on the CPU backend (hidden 64, 8 routed experts over 4
ranks, 2 layers, the checkpoint's own naming), held against the plain
reference the benchmark has: the generator's bytes parsed with
``numpy.frombuffer`` at the header's offsets (``chipbench/objects/``, which
imports neither the program nor jax).

What is held: every selected tensor bit for bit and no other name; the four
shares tie to the whole file; the plan (spans, tasks, bytes) equals a plain
recomputation from the header; the result's task records; the three events
and two counters of the layer; no uint8 device array of content; and the
rehearsal of the cell, as a whole run of ``chipbench/run.py``.
"""

from __future__ import annotations

import importlib.util
import os
import sys
import time
import types

import numpy as np
import pytest

from dragonfly2_tpu.client import device as device_lib
from dragonfly2_tpu.pkg import flight
from dragonfly2_tpu.pkg.testing import start_range_origin

import tests.test_p2p_e2e as e2e
from tests.test_device_sink import _start_sink_daemon

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "chipbench")
RANKS = 4
GAP, GUESS = 4096, 16384      # an expert matrix is 5,120 bytes here
CONFIG = {
    "hidden_size": 64, "num_attention_heads": 2, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 8, "kv_lora_rank": 24,
    "n_routed_experts": 2, "moe_intermediate_size": 40,
    "n_shared_experts": 2, "vocab_size": 96,
    "object": {"kind": "safetensors_layers", "layers": [1, 2]},
    "deployment": {"expert_parallel": {"ranks": RANKS, "rank": 0},
                   "coalesce_gap": GAP, "prefix_guess": GUESS}}


def load_bench(names: dict):
    """Modules of chipbench/ loaded from their source under the names they
    import each other by; chipbench/ itself never lands on ``sys.path``,
    where a second ``tests`` package lives. Returns (modules, undo)."""
    before = {name: sys.modules.get(name) for name in names}
    loaded = {}
    for name, path in names.items():
        if path is None:
            module = types.ModuleType(name)
        else:
            spec = importlib.util.spec_from_file_location(
                name, os.path.join(BENCH, *path))
            module = importlib.util.module_from_spec(spec)
        sys.modules[name] = module
        if path is not None:
            spec.loader.exec_module(module)
        loaded[name] = module

    def undo():
        for name, was in before.items():
            if was is None:
                sys.modules.pop(name, None)
            else:
                sys.modules[name] = was

    return loaded, undo


@pytest.fixture(scope="module")
def checkpoint():
    """(the generator's Objects, the file's bytes)."""
    loaded, undo = load_bench({
        "objects": None,
        "objects.safetensors_shard": ("objects", "safetensors_shard.py"),
        "objects.safetensors_layers": ("objects", "safetensors_layers.py")})
    try:
        obj = loaded["objects.safetensors_layers"].Objects(CONFIG, seed=34)
        content = b"".join(bytes(s) for s in obj.segments())
    finally:
        undo()
    assert len(obj.tensors) == 2 * (9 + 3 * 9) and len(content) == obj.length
    assert "jax" not in vars(loaded["objects.safetensors_layers"])
    return obj, content


class Fabric:
    """Origin, scheduler, seed peer and a peer with the sink, in process."""

    async def __aenter__(self):
        return self

    async def start(self, tmp_path, content: bytes):
        self.origin, self.url, self.stats = await start_range_origin(content)
        self.sched = await e2e.start_scheduler()
        self.seed = await e2e.start_daemon(tmp_path, "seed",
                                           self.sched.port(), seed=True)
        self.peer = await _start_sink_daemon(tmp_path, "peer",
                                             self.sched.port())
        return self

    async def __aexit__(self, *exc):
        for d in (getattr(self, "peer", None), getattr(self, "seed", None)):
            if d is not None:
                await d.stop()
        await self.sched.stop()
        await self.origin.cleanup()


def pull(fabric, obj, rank: int, tag: str = ""):
    return device_lib.download_sharded(
        fabric.peer, fabric.url, selector=obj.selector(rank), tag=tag,
        coalesce_gap=GAP, prefix_guess=GUESS)


def as_bytes(tensor) -> np.ndarray:
    got = np.asarray(tensor)
    return got.view(np.uint8).reshape(got.shape[0], -1)


def assert_equals_reference(obj, name: str, tensor) -> None:
    _, dtype, shape = next(t for t in obj.tensors if t[0] == name)
    assert tuple(tensor.shape) == shape, name
    assert str(tensor.dtype) == {"BF16": "bfloat16",
                                 "F32": "float32"}[dtype], name
    assert np.array_equal(as_bytes(tensor), obj.expected(name, None)), name


@pytest.mark.parametrize("rank", range(RANKS))
def test_a_ranks_pull_equals_the_reference_and_the_plain_plan(
        run_async, tmp_path, checkpoint, rank):
    """(a) every selected tensor bit for bit and no other name; (c) the
    spans, the task count and the bytes pulled equal a plain recomputation
    from the header, a tensor inside the header's task is cut from it and
    not pulled again, a span that starts inside it is pulled whole and
    exact; (d) the records cover every selected tensor once, cold."""
    obj, content = checkpoint

    async def body():
        async with Fabric() as fabric:
            await fabric.start(tmp_path, content)
            got = await pull(fabric, obj, rank)
            return got, dict(fabric.stats)

    got, stats = run_async(body(), timeout=180)
    selected = obj.selected(rank)
    assert list(got) == selected and isinstance(got, dict)
    for name in selected:
        assert_equals_reference(obj, name, got[name])
    # The plan, reckoned plainly.
    inside, spans = obj.plan(rank)
    head, ranged = got.tasks[0], got.tasks[1:]
    assert (head.start, head.end, head.names) == (0, GUESS, inside)
    assert "model.layers.1.input_layernorm.weight" in inside
    assert [(t.start, t.end, t.names) for t in ranged] == spans
    assert all(t.content_length == t.end - t.start for t in got.tasks)
    if rank == 0:       # its first experts lie where the header's task ends
        assert spans[0][0] < GUESS < spans[0][1], "no span straddles it"
    assert not any(set(t.names) & set(inside) for t in ranged)
    # Every selected tensor once, and nothing else.
    cut = [n for t in got.tasks for n in t.names]
    assert sorted(cut) == sorted(selected) and len(set(cut)) == len(cut)
    pulled = GUESS + sum(end - start for start, end, _ in spans)
    assert pulled <= stats["bytes"] <= pulled + 8 * len(got.tasks)
    assert stats["bytes"] < 0.6 * len(content)
    # Cold through origin -> seed -> peer: every task, the header's too.
    assert all(t.from_p2p and not t.from_reuse for t in got.tasks)
    assert len({t.task_id for t in got.tasks}) == len(got.tasks)


def test_the_four_shares_tie_to_the_whole(run_async, tmp_path, checkpoint):
    """(b) the ranks' selections partition the routed experts, and their
    union, with every tensor that is not routed counted once, is exactly
    the file's tensor set, byte for byte what a whole ``download_to_device``
    + ``load_safetensors`` gives."""
    obj, content = checkpoint

    async def body():
        async with Fabric() as fabric:
            await fabric.start(tmp_path, content)
            whole = (await device_lib.download_to_device(
                fabric.peer, fabric.url)).load_safetensors()
            return whole, [await pull(fabric, obj, rank)
                           for rank in range(RANKS)]

    whole, shares = run_async(body(), timeout=240)
    names = [set(share) for share in shares]
    routed = [{n for n in share if ".mlp.experts." in n} for share in names]
    assert all(len(r) == 2 * 2 * 3 for r in routed)   # layers x held x 3
    for i in range(RANKS):
        for j in range(i + 1, RANKS):
            assert not routed[i] & routed[j]
    rest = [share - r for share, r in zip(names, routed)]
    assert all(r == rest[0] for r in rest) and len(rest[0]) == 2 * 12
    assert set.union(*names) == set(whole) == {n for n, _, _ in obj.tensors}
    assert sum(map(len, routed)) + len(rest[0]) == len(whole)
    for share in shares:
        for name, tensor in share.items():
            assert tensor.dtype == whole[name].dtype
            assert np.array_equal(as_bytes(tensor), as_bytes(whole[name]))
            assert_equals_reference(obj, name, tensor)


EVENTS = ("admit_wait", "shard_plan", "shard_views")


def counted() -> dict:
    out = {}
    for how in ("pulled", "prefix"):
        out[how] = device_lib.SHARDED_TASKS.labels(how)._value.get()
    for kind in ("selected", "gap", "prefix"):
        out["bytes_" + kind] = device_lib.SHARDED_BYTES.labels(kind)._value.get()
    return out


def ranged_op(peer, got, t0: float, t1: float):
    """What ``chipbench/drivers/closed_loop_ranged.py`` hands the readers."""
    rows = []
    for task in got.tasks:
        tf = peer.task_manager.flight.get(task.task_id)
        start = time.perf_counter() - (flight.anchored_wall()
                                       - tf.start_wall)
        rows.append({"flight": [
            (start + t, flight.EVENT_NAMES[code], piece, aux)
            for t, code, piece, aux, _ in tf.events()
            if t0 <= start + t <= t1]})
    return types.SimpleNamespace(
        t0=t0, t1=t1, ranged=rows, views_span=None,
        nbytes=sum(int(t.nbytes) for t in got.values()),
        flight=sorted(e for row in rows for e in row["flight"]))


def test_events_counters_records_and_the_readers(run_async, tmp_path,
                                                 checkpoint, monkeypatch):
    """The layer's tracing on a cold pull and on a second one: which events
    exist and where, what the fold and ``--explain`` say of them, what the
    counters count, what the benchmark's readers find by name; the second
    pull comes from the store (``from_reuse`` of every task); and neither
    makes a uint8 device array of content."""
    from dragonfly2_tpu.daemon.peer.device_sink import TaskDeviceSink

    obj, content = checkpoint

    def no_bytes(self):
        raise AssertionError("a uint8 device array of content was made")

    monkeypatch.setattr(TaskDeviceSink, "as_bytes_array", no_bytes)

    async def body():
        async with Fabric() as fabric:
            await fabric.start(tmp_path, content)
            before = counted()
            t0 = time.perf_counter()
            cold = await pull(fabric, obj, 1, tag="ev")
            t1 = time.perf_counter()
            after = counted()
            op = ranged_op(fabric.peer, cold, t0, t1)
            reports = {t.task_id: flight.analyze(
                fabric.peer.task_manager.flight.get(t.task_id))
                for t in cold.tasks}
            again = await pull(fabric, obj, 1, tag="ev")
            return cold, again, op, reports, before, after

    cold, again, op, reports, before, after = run_async(body(), timeout=180)
    inside, spans = obj.plan(1)     # four spans through three slots
    head, ranged = cold.tasks[0], cold.tasks[1:]
    assert len(ranged) == len(spans) > 3      # more tasks than sink slots

    # One admit_wait a task, the first event of its flight; the other two
    # on the header task's flight alone, with what they count.
    by_task = [[(name, piece, aux) for _, name, piece, aux in row["flight"]
                if name in EVENTS] for row in op.ranged]
    assert [name for name, _, _ in by_task[0]] == list(EVENTS)
    assert all(row == [("admit_wait", -1, row[0][2])] for row in by_task[1:])
    for row in op.ranged:
        assert row["flight"][0][1] == "admit_wait"
    waits = [row[0][2] for row in by_task]
    assert all(w >= 0 for w in waits) and waits[0] < 50.0
    # Three slots: the header's task and the first three spans do not
    # wait, some later one does.
    assert max(waits[4:]) > max(waits[:4])
    (_, planned, plan_ms), (_, tensors, views_ms) = by_task[0][1:]
    assert planned == len(spans) and tensors == len(cold)
    assert 0 < plan_ms < (op.t1 - op.t0) * 1000.0
    assert 0 < views_ms < (op.t1 - op.t0) * 1000.0

    # The fold and --explain: a block of the report, no phase of the wall.
    client = reports[head.task_id]["client"]
    assert list(client) == ["admit_wait_ms", "shard_plan_ms",
                            "shard_views_ms"]
    assert client["shard_plan_ms"] == pytest.approx(plan_ms, abs=1e-3)
    assert list(reports[ranged[-1].task_id]["client"]) == ["admit_wait_ms"]
    text = flight.render_waterfall(reports[head.task_id])
    assert "client api, ms" in text and "shard_plan=" in text
    assert "client api" in flight.render_waterfall(
        reports[ranged[-1].task_id])
    assert sum(reports[head.task_id]["phases"].values()) <= \
        reports[head.task_id]["wall_s"] + 1e-6

    # The counters: spans pulled and served from the prefix; bytes.
    size = {n: obj.spans[n][1] - obj.spans[n][0] for n in obj.selected(1)}
    held = sum(size[n] for n in inside)
    moved = {k: after[k] - before[k] for k in after}
    assert moved == {
        "pulled": len(spans), "prefix": 1,
        "bytes_selected": sum(size.values()), "bytes_prefix": held,
        "bytes_gap": sum(e - s for s, e, _ in spans)
        - (sum(size.values()) - held)}
    assert moved["bytes_gap"] == 0      # an expert is wider than the gap

    # The benchmark's readers find all of it by name.
    loaded, undo = load_bench({
        "layers": None,
        "layers.ranged_events": ("layers", "ranged_events.py"),
        **{"layers." + m: ("layers", m + ".py") for m in (
            "rank_plan_ms", "rank_admit_wait_ms", "rank_task_fixed_ms",
            "rank_registers_per_GB", "rank_views_ms")}})
    try:
        run = types.SimpleNamespace(ops=[op])
        read = {m: loaded["layers." + m].read(run) for m in (
            "rank_plan_ms", "rank_admit_wait_ms", "rank_task_fixed_ms",
            "rank_registers_per_GB", "rank_views_ms")}
    finally:
        undo()
    assert read["rank_plan_ms"] == pytest.approx(plan_ms)
    assert read["rank_views_ms"] == pytest.approx(views_ms)
    assert read["rank_admit_wait_ms"] == pytest.approx(sum(waits))
    # Seed and peer share this process's recorder, so a task's flight holds
    # the register of each (on the chip the seed is a process of its own).
    assert read["rank_registers_per_GB"] == pytest.approx(
        2 * len(cold.tasks) / (op.nbytes / 1e9))
    # ... and the seed's ``task_done``, which ends the shared flight before
    # the peer's last piece has landed: a task's own tail is read only where
    # the seed is another process (chipbench/tests/test_ranged_layers.py
    # works the reader out on recorded flights).
    assert read["rank_task_fixed_ms"] is None

    # (d) the second pull: the same tasks, every one from the store.
    assert [(t.start, t.end, t.task_id, t.names) for t in again.tasks] == [
        (t.start, t.end, t.task_id, t.names) for t in cold.tasks]
    assert all(t.from_reuse and not t.from_p2p for t in again.tasks)
    assert list(again) == list(cold)
    for name in again:
        assert np.array_equal(as_bytes(again[name]), as_bytes(cold[name]))


def test_a_pull_of_nothing_still_names_the_headers_task(run_async, tmp_path,
                                                        checkpoint):
    obj, content = checkpoint

    async def body():
        async with Fabric() as fabric:
            await fabric.start(tmp_path, content)
            return await device_lib.download_sharded(
                fabric.peer, fabric.url, selector=lambda n, m: False,
                prefix_guess=GUESS)

    got = run_async(body(), timeout=120)
    assert got == {} and len(got.tasks) == 1
    assert (got.tasks[0].start, got.tasks[0].end) == (0, GUESS)
    assert got.tasks[0].names == []


def test_the_three_events_have_names_and_a_place_in_the_docs():
    codes = {flight.EV_ADMIT_WAIT: "admit_wait",
             flight.EV_SHARD_PLAN: "shard_plan",
             flight.EV_SHARD_VIEWS: "shard_views"}
    assert {flight.EVENT_NAMES[c] for c in codes} == set(codes.values())
    assert len(set(flight.EVENT_NAMES.values())) == len(flight.EVENT_NAMES)
    with open(os.path.join(REPO, "docs", "OBSERVABILITY.md")) as f:
        docs = f.read()
    for name in (*codes.values(), "device_sharded_tasks_total",
                 "device_sharded_bytes_total"):
        assert f"`{name}`" in docs, name
