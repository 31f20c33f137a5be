"""A checkpoint shard whole on every chip of a host, at a small size.

``moonlight-shard-4chip`` (chipbench/configs) is four data-parallel replicas
of one pipeline stage on one four-chip host: the host pulls the shard once,
the other three copies travel chip to chip, and every chip's copy is
verified on that chip before ``download_to_device`` returns. Here the same
path runs on the suite's virtual devices, through the client API on a
loopback fabric, on seeded random safetensors objects, and is held to the
plain numpy reference beside this file (``placement_reference.py``), which
imports nothing of the program.

No clock is asserted: counts, equality, order and containment only.
"""

from __future__ import annotations

import hashlib
import json
import struct
import time

import numpy as np
import pytest

from dragonfly2_tpu.client import device as device_lib
from dragonfly2_tpu.pkg import flight
from dragonfly2_tpu.pkg.errors import Code, DfError

from tests import placement_reference as ref
from tests.test_device_sink import _start_sink_daemon, start_content_origin
from tests.test_p2p_e2e import start_scheduler

MIB = 1 << 20


def make_object(seed: int, data_bytes: int) -> bytes:
    """A safetensors object of about ``data_bytes`` from ``seed``: F32,
    BF16 (finite normal values: an exponent that is neither 0 nor all
    ones), I32, U16 and U8 tensors of random bits, odd sizes among them, and
    a header that leaves the data 2 bytes into a word."""
    rng = np.random.default_rng(seed)
    unit = max(64, data_bytes // 16)
    bf16 = rng.integers(0, 1 << 16, (unit // 64, 64), dtype=np.uint16)
    exponent = (bf16 >> 7) & 0xFF
    bf16[exponent == 0] |= 1 << 7
    bf16[exponent == 0xFF] &= np.uint16(~(1 << 7) & 0xFFFF)
    arrays = {
        "embed.weight": ("F32", rng.integers(
            0, 1 << 32, (unit // 16, 8), dtype=np.uint32)),
        "layer.w_bf16": ("BF16", bf16),
        "layer.bias_i32": ("I32", rng.integers(
            -1 << 31, 1 << 31, (unit // 4 + 1,), dtype=np.int64)
            .astype(np.int32)),
        "probe.u16": ("U16", rng.integers(0, 1 << 16, (unit // 2 + 1,),
                                          dtype=np.uint16)),
        "probe.u8": ("U8", rng.integers(0, 256, (unit + 3,),
                                        dtype=np.uint8)),
        "layer.rest_f32": ("F32", rng.integers(
            0, 1 << 32, (max(1, (data_bytes - 7 * unit) // 4),),
            dtype=np.uint32)),
    }
    header, blobs, at = {}, [], 0
    for name, (dtype, array) in arrays.items():
        raw = array.tobytes()
        header[name] = {"dtype": dtype, "shape": list(array.shape),
                        "data_offsets": [at, at + len(raw)]}
        blobs.append(raw)
        at += len(raw)
    text = json.dumps(header).encode()
    text += b" " * ((2 - (8 + len(text))) % 4)
    return struct.pack("<Q", len(text)) + text + b"".join(blobs)


def bits(array) -> np.ndarray:
    """An array's bit patterns, whatever its dtype (bfloat16 included)."""
    array = np.asarray(array)
    return array.view({1: np.uint8, 2: np.uint16, 4: np.uint32}[
        array.dtype.itemsize])


def mesh_of(chips: int):
    import jax
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices()[:chips]), ("d",))


def held_by(array) -> dict:
    """device -> numpy copy of what that device holds of ``array``."""
    return {s.device: np.asarray(s.data) for s in array.addressable_shards}


async def fabric(tmp_path, content: bytes):
    runner, url, stats = await start_content_origin(content)
    sched = await start_scheduler()
    peer = await _start_sink_daemon(tmp_path, "peer", sched.port())

    async def stop():
        await peer.stop()
        await sched.stop()
        await runner.cleanup()

    stop.origin = stats
    return peer, url, stop


def placed(run_async, tmp_path, content: bytes, chips: int, **kwargs):
    """``download_to_device`` of ``content`` whole on ``chips`` chips:
    (result, mesh, the flight's events on this process's perf_counter,
    (t0, t1) of the call)."""

    async def body():
        peer, url, stop = await fabric(tmp_path, content)
        try:
            mesh = mesh_of(chips)
            t0 = time.perf_counter()
            result = await device_lib.download_to_device(
                peer, url, digest="sha256:" + hashlib.sha256(
                    content).hexdigest(),
                mesh=mesh, placement="replicated", **kwargs)
            t1 = time.perf_counter()
            tf = peer.task_manager.flight.get(result.task_id)
            start = time.perf_counter() - (flight.anchored_wall()
                                           - tf.start_wall)
            events = [(start + t, flight.EVENT_NAMES.get(code, code), piece,
                       aux) for t, code, piece, aux, _ in tf.events()]
            return result, mesh, events, (t0, t1), flight.analyze(tf)
        finally:
            await stop()

    return run_async(body(), timeout=180)


def assert_whole_on_every_chip(result, mesh, content: bytes) -> None:
    """The words and every tensor, on every chip of the mesh, bit for bit
    what the reference says."""
    hbm = result.sink.sink
    devices = list(mesh.devices.flat)
    want_words = ref.words_on_every_chip(content, hbm.piece_size,
                                         len(devices))
    words = result.as_words()
    copies = held_by(words)
    assert set(copies) == set(devices)
    for device, want in zip(devices, want_words):
        assert copies[device].shape == want.shape, device
        assert np.array_equal(copies[device], want), device
    assert words.shape == want_words[0].shape
    want_sums = ref.piece_checksums(content, hbm.piece_size)
    assert len(want_sums) == hbm.total_pieces
    for piece, (s, x) in hbm.host_checksums.items():
        assert (s, x) == tuple(int(v) for v in want_sums[piece])
    want_tensors = ref.tensors(content)
    got = result.load_safetensors()
    assert set(got) == set(want_tensors)
    for name, want in want_tensors.items():
        copies = held_by(got[name])
        assert set(copies) == set(devices), name
        for device in devices:
            assert copies[device].shape == want.shape, (name, device)
            assert np.array_equal(bits(copies[device]), bits(want)), (
                name, device)


# -- (a) four chips and one -------------------------------------------------

@pytest.mark.parametrize("chips", [4, 1])
def test_words_and_tensors_on_every_chip_equal_the_reference(
        run_async, tmp_path, chips):
    content = make_object(31 + chips, 9 * MIB + 1234)    # three pieces
    result, mesh, events, _, _ = placed(run_async, tmp_path, content, chips)
    assert result.content_length == len(content)
    assert result.sink.sink.total_pieces == 3
    assert_whole_on_every_chip(result, mesh, content)
    stamped = [name for _, name, _, _ in events
               if name in ("sink_replicate", "sink_verify_chips")]
    # One chip: today's path, and nothing of the fan-out is stamped or run.
    assert stamped == (["sink_replicate", "sink_verify_chips"]
                       if chips > 1 else [])


# -- (b) words that do not divide by the mesh; a single piece ----------------

@pytest.mark.parametrize("data_bytes,chips,pieces",
                         [(300_001, 4, 1), (300_001, 3, 1),
                          (5 * MIB + 6, 3, 2)],
                         ids=["single_piece-4chips", "single_piece-3chips",
                              "two_pieces-3chips"])
def test_a_single_piece_and_words_that_do_not_divide_by_the_mesh(
        run_async, tmp_path, data_bytes, chips, pieces):
    """Through the fabric a piece is whole MiB, so the words divide by
    four chips; by three they do not (the sharded placement's pad path,
    whichever way to every chip takes it)."""
    content = make_object(77, data_bytes)
    result, mesh, _, _, _ = placed(run_async, tmp_path, content, chips,
                                   claim=False)
    hbm = result.sink.sink
    assert hbm.total_pieces == pieces
    assert bool(hbm.padded_words % chips) == (chips == 3)
    assert_whole_on_every_chip(result, mesh, content)


@pytest.mark.parametrize("length,piece,chips",
                         [(1_000_003, 1_000_004, 4), (20 * 4096 + 5, 4096, 3),
                          (12 * 4096, 4096, 4)],
                         ids=["one_piece-4", "21_pieces-3", "divides-4"])
def test_shard_to_mesh_pads_the_last_shard_alone(length, piece, chips):
    """The sharded placement of words that do not divide by the mesh: the
    content in order over the shards, zeros after it, and no second
    content-sized array made for the padding."""
    from dragonfly2_tpu.ops.hbm_sink import HBMSink

    content = np.random.default_rng(length).integers(
        0, 256, length, dtype=np.uint8).tobytes()
    sink = HBMSink(length, piece)
    for n in range(sink.total_pieces):
        sink.land_piece(n, content[n * piece:(n + 1) * piece])
    sink.verify()
    mesh = mesh_of(chips)
    sharded = sink.shard_to_mesh(mesh)
    per = -(-sink.padded_words // chips)
    assert sharded.shape == (per * chips,)
    assert [s.data.shape for s in sharded.addressable_shards] == [
        (per,)] * chips
    want = ref.words_on_every_chip(content, piece, 1)[0]
    whole = np.asarray(sharded)
    assert np.array_equal(whole[:want.size], want)
    assert not whole[want.size:].any()


# -- (c) a copy altered on one chip fails the operation ----------------------

def test_a_copy_altered_on_one_chip_fails_the_operation_and_names_the_chip(
        run_async, tmp_path, monkeypatch):
    import jax

    from dragonfly2_tpu.daemon.peer import device_sink
    from dragonfly2_tpu.parallel import ici

    sound = ici.all_gather_shards
    victim = jax.devices()[2]

    def altered(mesh, sharded, axis_name="d"):
        """The fan-out, then one word of the copy on chip 2 flipped: after
        the fan-out, before the verification."""
        out = jax.block_until_ready(sound(mesh, sharded, axis_name))
        copies = []
        for shard in out.addressable_shards:
            copy = shard.data
            if shard.device == victim:
                copy = copy.at[copy.shape[0] // 3].set(
                    copy[copy.shape[0] // 3] ^ np.uint32(0x10))
            copies.append(copy)
        return jax.make_array_from_single_device_arrays(
            out.shape, out.sharding, copies)

    monkeypatch.setattr(ici, "all_gather_shards", altered)
    counter = device_sink.SINK_CHIP_VERIFY_COUNT
    before = {r: counter.labels(r)._value.get() for r in ("ok", "corrupt")}
    content = make_object(5, 9 * MIB)

    async def body():
        peer, url, stop = await fabric(tmp_path, content)
        try:
            with pytest.raises(DfError) as failed:
                await device_lib.download_to_device(
                    peer, url, mesh=mesh_of(4), placement="replicated",
                    claim=False)
            # The disk result stands; the sink is not left for the next
            # consumer to find unverified copies in.
            assert not peer.task_manager.device_sinks._sinks
            return failed.value
        finally:
            await stop()

    error = run_async(body(), timeout=180)
    assert error.code == Code.ClientPieceDownloadFail
    assert str(victim) in str(error) and "piece 1 corrupt" in str(error)
    after = {r: counter.labels(r)._value.get() for r in ("ok", "corrupt")}
    assert (after["corrupt"] - before["corrupt"],
            after["ok"] - before["ok"]) == (1, 0)


# -- (d) the collective on the path and the plain reference give the same bytes

def _landed(content: bytes, piece: int):
    from dragonfly2_tpu.ops.hbm_sink import HBMSink

    sink = HBMSink(len(content), piece)
    for n in range(sink.total_pieces):
        sink.land_piece(n, content[n * piece:(n + 1) * piece])
    sink.verify()
    return sink


@pytest.mark.parametrize("way", ["on_the_path", "device_put"])
def test_every_way_to_every_chip_gives_the_same_bytes(way):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    piece = 64 * 1024
    content = np.random.default_rng(9).integers(
        0, 256, 11 * piece + 4096 + 3, dtype=np.uint8).tobytes()
    sink = _landed(content, piece)
    mesh = mesh_of(4)
    want = ref.words_on_every_chip(content, piece, 4)
    if way == "on_the_path":
        assert sink.replicate(mesh) == 3
        out = sink.as_words()
    else:
        # The plain reference: the runtime's own placement on every chip.
        out = jax.device_put(sink.as_words(), NamedSharding(mesh, P()))
    copies = held_by(out)
    assert set(copies) == set(mesh.devices.flat)
    for device, words in zip(mesh.devices.flat, want):
        assert np.array_equal(copies[device][:words.size], words), device


# -- (d2) a dispatch carries many views, on every chip ----------------------

@pytest.mark.parametrize("pad", [0, 1, 2, 3])
@pytest.mark.parametrize("form", ["flat", "rows"])
def test_grouped_views_of_words_on_every_chip_equal_the_reference(
        kernel_on_cpu, form, pad):
    """Over words that lie on every chip a group of views is one program
    on every chip: cap + 1 members at two alignments, singles, a BOOL and
    a zero-length tensor, every one whole on every chip and equal to
    ``np.frombuffer``, for the dispatches a single chip's load costs. The
    group is of a shape the flat form cuts, and of one that the rows
    kernel cuts on each chip's own copy of the words."""
    from dragonfly2_tpu.ops import bitview, safetensors as st
    from tests.test_safetensors import (ROWS_SHAPE, _expected_dispatches,
                                        _views_counted, grouped_object)

    members = bitview._GROUP_CAP + 1
    content, want = grouped_object(
        members, pad, seed=40 + pad,
        shape=ROWS_SHAPE if form == "rows" else (7, 9))
    sink = _landed(content, 4096)
    mesh = mesh_of(4)
    assert sink.replicate(mesh) == 3
    was = _views_counted()
    got = st.load_from_sink(sink)
    now = _views_counted()
    assert (now[0] - was[0], now[1] - was[1], now[2] - was[2]) == (
        _expected_dispatches(content), len(want),
        members if form == "rows" else 0)
    assert list(got) == list(want)
    devices = set(mesh.devices.flat)
    for name, reference in want.items():
        copies = held_by(got[name])
        assert set(copies) == devices, name
        for device, copy in copies.items():
            assert copy.dtype == reference.dtype, (name, device)
            assert copy.shape == reference.shape, (name, device)
            assert np.array_equal(bits(copy), bits(reference)), (name, device)


# -- (e) spans and counters ---------------------------------------------------

def test_the_fan_out_and_the_per_chip_verification_are_stamped_once_inside(
        run_async, tmp_path):
    from dragonfly2_tpu.daemon.peer import device_sink

    moved = device_sink.SINK_HOP_BYTES.labels("fanout")._value
    verified = device_sink.SINK_CHIP_VERIFY_COUNT.labels("ok")._value
    before = (moved.get(), verified.get())
    content = make_object(12, 9 * MIB + 8)
    result, mesh, events, (t0, t1), report = placed(
        run_async, tmp_path, content, 4)
    spans = {name: [(t, piece, aux) for t, n, piece, aux in events
                    if n == name]
             for name in ("sink_finalize", "sink_replicate",
                          "sink_verify_chips")}
    assert [len(v) for v in spans.values()] == [1, 1, 1]
    (t_fin, _, _), = spans["sink_finalize"]
    (t_rep, others, rep_ms), = spans["sink_replicate"]
    (t_ver, chips, ver_ms), = spans["sink_verify_chips"]
    assert (others, chips) == (3, 4)
    # One event at the span's end, aux its ms: the landing, then the
    # fan-out, then the verification, none overlapping, all inside the call.
    assert t0 <= t_fin <= t_rep - rep_ms / 1000.0
    assert t_rep <= t_ver - ver_ms / 1000.0 + 1e-6 and t_ver <= t1
    assert moved.get() - before[0] == 3 * 4 * result.sink.sink.padded_words
    assert verified.get() - before[1] == 1
    block = report["hbm"]
    assert block["replicate_ms"] == pytest.approx(rep_ms, abs=0.01)
    assert block["verify_chips_ms"] == pytest.approx(ver_ms, abs=0.01)
    assert list(block)[-3:] == ["replicate_ms", "verify_chips_ms", "wait_ms"]
    text = flight.render_waterfall(report)
    assert "replicate=" in text and "verify_chips=" in text


def test_analyze_books_the_fan_out_under_ici():
    """Hand-made clocks: the two spans of a running task's flight fall under
    ``ici``; the landing before them stays ``hbm``."""
    tf = flight.TaskFlight("synthetic-mesh")
    tf._start_pc = time.perf_counter() - 1.0
    tf._ring[0] = (0.10, flight.EV_HBM_START, 0, 0.0, "")
    tf._ring[1] = (0.30, flight.EV_HBM_LANDED, 0, 0.0, "")
    tf._ring[2] = (0.50, flight.EV_SINK_REPLICATE, 3, 100.0, "")
    tf._ring[3] = (0.55, flight.EV_SINK_VERIFY_CHIPS, 4, 50.0, "")
    report = flight.analyze(tf)
    assert report["phases"]["hbm"] == pytest.approx(0.20, abs=1e-6)
    assert report["phases"]["ici"] == pytest.approx(0.15, abs=1e-6)
    assert report["hbm"] == {"replicate_ms": 100.0, "verify_chips_ms": 50.0}


# -- the request ------------------------------------------------------------

def test_a_placement_that_is_no_placement_is_refused_before_any_pull(
        run_async, tmp_path):
    async def body():
        peer, url, stop = await fabric(tmp_path, b"x" * 4096)
        try:
            with pytest.raises(DfError) as refused:
                await device_lib.download_to_device(
                    peer, url, mesh=mesh_of(4), placement="everywhere")
            assert stop.origin["streams"] == 0
            return refused.value
        finally:
            await stop()

    assert run_async(body(), timeout=60).code == Code.BadRequest


def test_replicate_needs_a_verified_landing_and_devices_on_one_axis():
    import jax
    from jax.sharding import Mesh

    from dragonfly2_tpu.ops.hbm_sink import HBMSink

    content = bytes(range(256)) * 64
    sink = HBMSink(len(content), 4096)
    for n in range(sink.total_pieces):
        sink.land_piece(n, content[n * 4096:(n + 1) * 4096])
    with pytest.raises(ValueError, match="verified"):
        sink.replicate(mesh_of(4))
    sink.verify()
    grid = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("a", "d"))
    with pytest.raises(ValueError, match="one axis"):
        sink.replicate(grid)
    assert sink.replicate(mesh_of(1)) == 0      # the landing device alone
    assert sink.as_words().devices() == {sink.device}
    assert sink.replicate(mesh_of(4)) == 3
    assert sink.replicate(mesh_of(4)) == 0      # placed and verified already

    # The daemon's sink refuses before the device is asked: no fan-out of a
    # landing that nothing has verified.
    from dragonfly2_tpu.daemon.peer.device_sink import (
        DeviceSinkError,
        TaskDeviceSink,
    )

    task = TaskDeviceSink("t-unverified", len(content), 4096)
    for n in range(task.sink.total_pieces):
        task.land(n, content[n * 4096:(n + 1) * 4096])
    with pytest.raises(DeviceSinkError, match="unverified sink"):
        task.replicate(mesh_of(4))
    task.verify()
    task.replicate(mesh_of(4))
    assert len(task.as_words().devices()) == 4
