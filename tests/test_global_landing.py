"""A checkpoint file sharded over the four chips of one host
(``moonlight-ep4-host``, the cell ``host-reland-ep4``):
``client.device.download_global`` under the map of four-way expert parallelism
(expert ``e`` of every layer on local device ``e // 16``, everything else on
all four), at a small size on the CPU backend's devices: the 19 MB object of
``chipbench/rehearsal/tiny-ep4-rank-19m.json``, two layers of 64 experts,
held against the plain reference beside this file (``global_reference.py``,
which imports neither the program nor jax) over the generator's own bytes.

One cold call, one re-land of it and one rank's ``download_sharded`` run once
for the module; the tests hold what they left: every shard of every tensor
bit for bit on the device that the sharding names; the tie of the share to
the whole; the plan by destination against a plain recomputation; and counts,
never rates: origin bytes, store bytes read, bytes landed by chip, bytes that
hopped, task ids, the events of a task's chip. Then the manager's rules for
sinks on several chips, one at a time.
"""

from __future__ import annotations

import asyncio
import json
import os

import numpy as np
import pytest

from dragonfly2_tpu.client import device as device_lib
from dragonfly2_tpu.daemon.peer import device_sink
from dragonfly2_tpu.pkg import flight

from tests import global_reference as ref
from tests.test_rank_pull import BENCH, Fabric, as_bytes, load_bench

CHIPS = 4
with open(os.path.join(BENCH, "rehearsal", "tiny-ep4-rank-19m.json")) as f:
    CONFIG = json.load(f)
HELD = CONFIG["n_routed_experts"]                      # experts a chip
GUESS = CONFIG["deployment"]["prefix_guess"]
GAP = CONFIG["deployment"]["coalesce_gap"]


def ep4_shardings(header: dict, devices) -> dict:
    """Expert ``e``: ``SingleDeviceSharding`` of local device ``e // 16``;
    every other tensor replicated over a mesh of the four."""
    from jax.sharding import (Mesh, NamedSharding, PartitionSpec,
                              SingleDeviceSharding)

    everywhere = NamedSharding(Mesh(np.array(devices), ("ep",)),
                               PartitionSpec())
    out = {}
    for name in header:
        if name == "__metadata__":
            continue
        chip = ref.chip_of(name, HELD)
        out[name] = (everywhere if chip is None
                     else SingleDeviceSharding(devices[chip]))
    return out


def counters(devices) -> dict:
    hops = device_sink.SINK_HOP_BYTES
    out = {"store": device_sink.SINK_STORE_READ_BYTES._value.get(),
           "fanout": hops.labels("fanout")._value.get(),
           "device_put": hops.labels("device_put")._value.get()}
    for d in devices:
        out[f"chip{d.id}"] = device_sink.SINK_LANDED_BYTES.labels(
            str(d.id))._value.get()
    return out


def since(before: dict, devices) -> dict:
    return {k: v - before[k] for k, v in counters(devices).items()}


def host_copy(result) -> dict:
    """name -> (sharding, {device id: the shard's bytes as rows})."""
    return {name: (array.sharding,
                   {s.device.id: (tuple(s.data.shape), str(s.data.dtype),
                                  np.asarray(s.data).view(np.uint8).copy())
                    for s in array.addressable_shards})
            for name, array in result.items()}


def events_of(peer, tasks) -> list:
    rows = []
    for task in tasks:
        tf = peer.task_manager.flight.get(task.task_id)
        rows.append([(flight.EVENT_NAMES[code], piece, aux, note)
                     for _, code, piece, aux, note in tf.events()])
    return rows


@pytest.fixture(scope="module")
def landing(tmp_path_factory):
    """Everything the module's one fabric did, brought to the host."""
    import jax

    loaded, undo = load_bench({
        "objects": None,
        "objects.safetensors_shard": ("objects", "safetensors_shard.py"),
        "objects.safetensors_layers": ("objects", "safetensors_layers.py")})
    try:
        obj = loaded["objects.safetensors_layers"].Objects(CONFIG, seed=42)
        content = b"".join(bytes(s) for s in obj.segments())
    finally:
        undo()
    devices = jax.devices()[:CHIPS]
    header, _ = ref.header_of(content)
    shardings = ep4_shardings(header, devices)
    tmp_path = tmp_path_factory.mktemp("global")
    out = {"obj": obj, "content": content, "devices": devices,
           "shardings": shardings}

    async def body():
        async with Fabric() as fabric:
            await fabric.start(tmp_path, content)
            peer, url = fabric.peer, fabric.url
            before = counters(devices)
            cold = await device_lib.download_global(
                peer, url, shardings, tag="g", prefix_guess=GUESS)
            jax.block_until_ready(list(cold.values()))
            out["cold_counts"] = since(before, devices)
            out["origin_bytes"] = fabric.stats["bytes"]
            out["cold"], out["cold_tasks"] = host_copy(cold), cold.tasks
            out["order"] = list(cold)
            out["padded"] = {}
            for task in cold.tasks:
                m = peer.task_manager.storage.find_completed_task(
                    task.task_id).metadata
                pieces = max(1, -(-m.content_length // m.piece_size))
                out["padded"][task.task_id] = pieces * (
                    m.piece_size + (-m.piece_size) % 4)
            del cold
            before = counters(devices)
            again = await device_lib.download_global(
                peer, url, shardings, tag="g", prefix_guess=GUESS)
            jax.block_until_ready(list(again.values()))
            out["reland_counts"] = since(before, devices)
            out["reland_origin_bytes"] = fabric.stats["bytes"]
            out["reland"], out["reland_tasks"] = host_copy(again), again.tasks
            out["reland_events"] = events_of(peer, again.tasks)
            del again
            rank0 = await device_lib.download_sharded(
                peer, url, selector=obj.selector(0), tag="g",
                coalesce_gap=GAP, prefix_guess=GUESS)
            out["rank0"] = {name: as_bytes(t) for name, t in rank0.items()}
            del rank0
            # One range, no chip named and then chip 2 named; another
            # range the other way round.
            sinks = peer.task_manager.device_sinks
            first = await device_lib.download_to_device(
                peer, url, tag="r", range_header="4096-69631", claim=False)
            out["unnamed"] = (first.task_id, first.sink.device,
                              first.as_words().devices())
            named = await device_lib.download_to_device(
                peer, url, tag="r", range_header="4096-69631",
                device=devices[2], claim=False)
            out["named"] = (named.task_id, named.sink.device,
                            named.as_words().devices(),
                            sinks.get(named.task_id) is named.sink,
                            np.asarray(named.as_bytes_array()).tobytes())
            kept = await device_lib.download_to_device(
                peer, url, tag="r", range_header="4096-69631", claim=False)
            out["kept"] = (kept.task_id, kept.sink is named.sink)
            out["named_events"] = events_of(peer, [named])[0]

    asyncio.run(asyncio.wait_for(body(), 600))
    return out


# -- the system against the plain reference, shard by shard ----------------

@pytest.mark.parametrize("which", ["cold", "reland"])
def test_every_tensor_under_exactly_the_sharding_asked(landing, which):
    got, shardings = landing[which], landing["shardings"]
    assert list(got) == list(shardings) == landing["order"]
    assert len(got) == 2 * 204
    for name, (sharding, _) in got.items():
        assert sharding == shardings[name], name


@pytest.mark.parametrize("chip", range(CHIPS))
@pytest.mark.parametrize("which", ["cold", "reland"])
def test_a_chips_shards_equal_the_reference_bit_for_bit(landing, which, chip):
    """Every shard that the sharding gives device ``chip`` is there, in the
    tensor's shape and dtype, and is the reference's bytes."""
    device = landing["devices"][chip]
    want = ref.shards(landing["content"], landing["shardings"])
    seen = 0
    for name, (_, held) in landing[which].items():
        if device not in want[name]:
            assert device.id not in held, name
            continue
        shape, dtype, raw = held[device.id]
        expect = want[name][device]
        assert shape == expect.shape, name
        assert dtype == {"<u2": "bfloat16", "<f4": "float32"}[
            expect.dtype.str], name
        assert np.array_equal(raw.reshape(-1),
                              expect.view(np.uint8).reshape(-1)), name
        seen += 1
    assert seen == 2 * (3 * HELD + 12)      # its experts and the rest


def test_placement_is_exact(landing):
    """An expert's tensors are addressable on its rank's chip and on no
    other; every other tensor is complete on all four."""
    ids = [d.id for d in landing["devices"]]
    for name, (_, held) in landing["reland"].items():
        chip = ref.chip_of(name, HELD)
        assert sorted(held) == (ids if chip is None else [ids[chip]]), name


def test_the_four_shares_tie_to_the_whole(landing):
    """The chips' expert shards are disjoint, and their union with ONE copy
    of the rest is every tensor of the file, bit for bit; the copies of the
    rest are equal."""
    whole = ref.tensors(landing["content"])
    experts = [set() for _ in range(CHIPS)]
    rest: dict = {}
    for name, (_, held) in landing["reland"].items():
        chip = ref.chip_of(name, HELD)
        if chip is not None:
            experts[chip].add(name)
            continue
        copies = [raw for _, _, raw in held.values()]
        assert len(copies) == CHIPS
        assert all(np.array_equal(c, copies[0]) for c in copies), name
        rest[name] = copies[0]
    for i in range(CHIPS):
        for j in range(i + 1, CHIPS):
            assert not experts[i] & experts[j]
    assert set.union(*experts) | set(rest) == set(whole)
    assert sum(map(len, experts)) + len(rest) == len(whole) == 408
    for name, array in whole.items():
        chip = ref.chip_of(name, HELD)
        raw = (rest[name] if chip is None else landing["reland"][name][1][
            landing["devices"][chip].id][2])
        assert np.array_equal(raw.reshape(-1),
                              array.view(np.uint8).reshape(-1)), name


def test_chip_0s_set_is_rank_0s_download_sharded(landing):
    """What chip 0 holds is exactly what ``download_sharded(selector=rank
    0)`` returns: the same names, the same bytes."""
    chip0 = landing["devices"][0].id
    held = {name: held[chip0][2] for name, (_, held)
            in landing["reland"].items() if chip0 in held}
    assert list(held) == list(landing["rank0"])
    for name, rows in landing["rank0"].items():
        assert np.array_equal(held[name].reshape(-1), rows.reshape(-1)), name


# -- the plan by destination ------------------------------------------------

@pytest.mark.parametrize("which", ["cold_tasks", "reland_tasks"])
def test_the_plan_is_the_plain_one(landing, which):
    """The header's task and one ranged task a run of neighbours that the
    same chips want, as the reference reckons them from the header; each
    task's words lie on exactly the chips that want them, landed on the
    first."""
    tasks = landing[which]
    inside, spans = ref.plan(landing["content"], HELD, CHIPS, GUESS)
    ids = [d.id for d in landing["devices"]]
    head, ranged = tasks[0], tasks[1:]
    assert (head.start, head.end) == (0, GUESS)
    assert sorted(head.names) == sorted(inside)
    # What lies inside the header's task is wanted by chips other than the
    # one it landed on: it went to all of them.
    assert head.chips == tuple(ids)
    assert [(t.start, t.end, t.chips, t.names) for t in ranged] == [
        (s, e, tuple(ids[c] for c in chips), names)
        for s, e, chips, names in spans]
    assert len({t.task_id for t in tasks}) == len(tasks)
    assert all(t.content_length == t.end - t.start for t in tasks)
    # No touching neighbours of one destination were left apart, and none
    # of different destinations were merged.
    for a, b in zip(ranged, ranged[1:]):
        assert a.end <= b.start
        assert not (a.end == b.start and a.chips == b.chips)
    one = [t for t in ranged if len(t.chips) == 1]
    assert {t.chips for t in one} == {(i,) for i in ids}
    assert all(ref.chip_of(n, HELD) is not None for t in one
               for n in t.names)


def test_the_paths(landing):
    assert all(t.from_p2p and not t.from_reuse
               for t in landing["cold_tasks"])
    assert all(t.from_reuse and not t.from_p2p
               for t in landing["reland_tasks"])
    assert [t.task_id for t in landing["cold_tasks"]] == [
        t.task_id for t in landing["reland_tasks"]]


# -- counts, never rates ----------------------------------------------------

def test_the_cold_call_reads_the_origin_once(landing):
    size = len(landing["content"])
    pulled = sum(t.content_length for t in landing["cold_tasks"])
    assert size <= pulled <= size + GUESS
    assert pulled <= landing["origin_bytes"] <= 1.1 * size
    assert landing["reland_origin_bytes"] == landing["origin_bytes"]


@pytest.mark.parametrize("which", ["cold", "reland"])
def test_store_bytes_read_are_the_files(landing, which):
    """Every byte of every task is read back from the store once an
    operation, the bytes all four chips want no more often than a chip's
    own."""
    counts = landing[which + "_counts"]
    pulled = sum(t.content_length for t in landing[which + "_tasks"])
    size = len(landing["content"])
    assert counts["store"] == pulled and size <= pulled <= size + GUESS


@pytest.mark.parametrize("which", ["cold", "reland"])
def test_bytes_landed_by_chip(landing, which):
    """A task's bytes are counted on the chip it landed on: a chip's own
    experts on that chip, what all four want on the first of them."""
    counts = landing[which + "_counts"]
    want = {d.id: 0 for d in landing["devices"]}
    for task in landing[which + "_tasks"]:
        want[task.chips[0]] += task.content_length
    assert {d.id: counts[f"chip{d.id}"] for d in landing["devices"]} == want
    assert all(v > 0 for v in want.values())
    assert sum(want.values()) == counts["store"]


@pytest.mark.parametrize("which", ["cold", "reland"])
def test_the_bytes_that_hopped_are_three_copies_of_the_rest(landing, which):
    """No expert byte is copied chip to chip, nothing goes by device_put;
    what hops is what all four want, to the three chips it did not land
    on, as whole padded pieces."""
    counts = landing[which + "_counts"]
    tasks = landing[which + "_tasks"]
    shared = [t for t in tasks if len(t.chips) > 1]
    assert all(len(t.chips) == CHIPS for t in shared)
    moved = (CHIPS - 1) * sum(landing["padded"][t.task_id] for t in shared)
    assert counts["fanout"] == moved
    assert counts["device_put"] == 0
    rest = sum(t.content_length for t in shared)
    # A sink is whole pieces (4 MiB each at this size, the last one zeros
    # past the content), and whole pieces are what the fan-out moves.
    assert (CHIPS - 1) * rest <= moved <= (CHIPS - 1) * (
        rest + len(shared) * (4 << 20))
    # The header's task rides along whole: of the rest proper, three copies.
    proper = sum(raw.size for name, (_, held) in landing["reland"].items()
                 if ref.chip_of(name, HELD) is None
                 for _, _, raw in list(held.values())[:1])
    assert proper <= rest <= proper + GUESS


def test_a_tasks_chip_is_on_its_flight(landing):
    """``device_pull`` names the chip a task landed on (and the others its
    words went to), ``sink_finalize`` the chip of the sink, the fan-out's
    two spans are on the shared tasks' flights alone, and the plan and the
    views on the header's."""
    for task, events in zip(landing["reland_tasks"],
                            landing["reland_events"]):
        pulls = [e for e in events if e[0] == "device_pull"]
        assert pulls and pulls[-1][1] == task.chips[0]
        assert pulls[-1][2] > 0
        # The header's task is pulled before there is a plan: it names no
        # chip, and is fanned out after its pull, by download_global.
        assert pulls[-1][3] == ("chips=" + ",".join(map(str, task.chips))
                                if len(task.chips) > 1 and task.start else "")
        finals = [e for e in events if e[0] == "sink_finalize"]
        assert finals[-1][3] == f"chip={task.chips[0]}"
        fanned = {e[0] for e in events} & {"sink_replicate",
                                           "sink_verify_chips"}
        assert bool(fanned) == (len(task.chips) > 1)
    names = [e[0] for e in landing["reland_events"][0]]
    assert "shard_plan" in names and "shard_views" in names
    plan = [e for e in landing["reland_events"][0] if e[0] == "shard_plan"]
    assert plan[-1][1] == len(landing["reland_tasks"]) - 1
    views = [e for e in landing["reland_events"][0] if e[0] == "shard_views"]
    assert views[-1][1] == 408


# -- the manager's rules for sinks on several chips -------------------------

def test_task_ids_are_equal_whatever_chip_is_named(landing):
    from dragonfly2_tpu.daemon.peer.task_manager import FileTaskRequest
    from dragonfly2_tpu.proto.common import UrlMeta

    assert landing["unnamed"][0] == landing["named"][0] == landing["kept"][0]
    meta = UrlMeta(tag="t", range="bytes=0-99")
    plain = FileTaskRequest(url="http://o/x", output="", meta=meta,
                            device="tpu")
    for device in landing["devices"]:
        assert FileTaskRequest(url="http://o/x", output="", meta=meta,
                               device="tpu",
                               sink_device=device).task_id() == \
            plain.task_id()


def test_a_request_that_names_no_chip_lands_where_it_did(landing):
    import jax

    _, device, holders = landing["unnamed"]
    assert device == jax.local_devices()[0] and holders == {device}


def test_a_named_chip_gets_the_sink_and_an_unnamed_request_keeps_it(landing):
    """A resident sink on another chip than the request names is built
    again on the named one, from the store; a later request that names no
    chip takes what is there."""
    _, device, holders, resident, raw = landing["named"]
    assert device == landing["devices"][2] and holders == {device}
    assert resident
    assert raw == landing["content"][4096:69632]
    assert landing["kept"][1]
    events = landing["named_events"]
    assert [e[3] for e in events if e[0] == "sink_finalize"][-3:] == [
        f"chip={landing['devices'][0].id}", f"chip={device.id}",
        f"chip={device.id}"]


def test_coalesce_by_destination():
    """Touching spans merge only where the same devices want them."""
    import collections

    a, b, c = map(collections.namedtuple("Device", "id"), range(3))
    wanted = {(0, 10): {a}, (10, 20): {a}, (20, 30): {b}, (30, 40): {a},
              (40, 50): {a, b, c}, (50, 60): {c, b, a}, (60, 70): {b},
              (5, 8): {a}, (100, 110): {a}}
    assert device_lib.coalesce_by_destination(wanted) == [
        (0, 20, (a,)), (20, 30, (b,)), (30, 40, (a,)), (40, 60, (a, b, c)),
        (60, 70, (b,)), (100, 110, (a,))]
    assert device_lib.coalesce_by_destination({}) == []


def test_a_new_landing_evicts_a_resident_of_its_own_chip_first(tmp_path):
    """The cap counts the sinks of every chip together; under it a new
    landing takes the slot of a resident on the chip it goes to, where
    there is one, and the oldest resident else."""
    import jax

    from tests.test_device_sink import _stored

    devices = jax.devices()[:3]
    manager = device_sink.DeviceSinkManager(max_tasks=3)
    manager.claim_grace_s = 0.0
    try:
        for n, device in enumerate(devices):
            store, _ = _stored(tmp_path, f"t{n}", 4096, 10000, seed=n)
            sink = manager._finalize_sync(f"t{n}", store, None,
                                          device).result(60)
            assert sink.verified and sink.device == device
        store, content = _stored(tmp_path, "t3", 4096, 10000, seed=3)
        sink = manager._finalize_sync("t3", store, None,
                                      devices[1]).result(60)
        assert sink.device == devices[1]
        assert sorted(manager._sinks) == ["t0", "t2", "t3"]
        assert bytes(np.asarray(sink.as_bytes_array())) == content
        # No resident of chip 1 is left to give way: the oldest goes.
        manager._sinks["t3"].verified_at = 0.0
        store, _ = _stored(tmp_path, "t4", 4096, 10000, seed=4)
        manager.protect("t3")
        assert manager._finalize_sync(
            "t4", store, None, devices[1]).result(60).device == devices[1]
        assert sorted(manager._sinks) == ["t2", "t3", "t4"]
        assert manager.outcome("t4", True) == {
            "device_platform": "cpu",
            "device_kind": devices[1].device_kind}
    finally:
        manager.close()


@pytest.mark.parametrize("chip", [1, 3])
def test_a_sink_runs_its_programs_on_the_chip_it_is_on(tmp_path, chip):
    """Staging, assembly, the checksums and the views of a sink created on
    another chip than the first all lie on that chip, and the bytes are the
    store's."""
    import jax

    from tests.test_device_sink import _stored

    device = jax.devices()[chip]
    manager = device_sink.DeviceSinkManager()
    try:
        store, content = _stored(tmp_path, "t", 4096, 3 * 4096 + 1234)
        sink = manager._finalize_sync("t", store, None, device).result(60)
        assert sink.verified and sink.device == device
        assert sink.as_words().devices() == {device}
        assert sink.as_tensor("uint16", (100,)).devices() == {device}
        assert bytes(np.asarray(sink.as_bytes_array())) == content
        assert manager._finalize_sync("t", store, None,
                                      None).result(60) is sink
    finally:
        manager.close()
