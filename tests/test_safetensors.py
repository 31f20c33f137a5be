"""safetensors checkpoints straight out of the device sink.

The north-star payload: a safetensors file lands in HBM via the P2P
fabric and becomes named (optionally mesh-sharded) tensors without a
host round trip of the data. The test builds the format by hand
(8-byte LE header length + JSON + raw tensors — the public stable
layout) and round-trips through an HBMSink and the full P2P path.
"""

from __future__ import annotations

import hashlib
import json
import struct

import numpy as np
import pytest

from dragonfly2_tpu.ops.hbm_sink import HBMSink
from dragonfly2_tpu.ops import safetensors as st


def make_safetensors(tensors: dict[str, np.ndarray],
                     dtype_names: dict[str, str]) -> bytes:
    header = {}
    blobs = []
    off = 0
    for name, arr in tensors.items():
        raw = arr.tobytes()
        header[name] = {"dtype": dtype_names[name],
                        "shape": list(arr.shape),
                        "data_offsets": [off, off + len(raw)]}
        blobs.append(raw)
        off += len(raw)
    hjson = json.dumps(header).encode()
    return struct.pack("<Q", len(hjson)) + hjson + b"".join(blobs)


@pytest.fixture
def checkpoint():
    rng = np.random.RandomState(3)
    tensors = {
        "model.embed": rng.randn(64, 32).astype(np.float32),
        "model.w1": (rng.randn(32, 128) * 0.1).astype(np.float32),
        "model.bias": rng.randn(128).astype(np.float32),
        "model.step": np.array([1234], dtype=np.int64),
    }
    dtypes = {"model.embed": "F32", "model.w1": "F32",
              "model.bias": "F32", "model.step": "I64"}
    return tensors, make_safetensors(tensors, dtypes)


def _land(content: bytes, piece: int = 4096) -> HBMSink:
    sink = HBMSink(len(content), piece, batch_pieces=4)
    for n in range((len(content) + piece - 1) // piece):
        sink.land_piece(n, content[n * piece:(n + 1) * piece])
    assert sink.complete() and sink.verify()
    return sink


def test_tensors_from_sink_exact(checkpoint):
    tensors, content = checkpoint
    sink = _land(content)
    loaded = st.load_from_sink(sink)
    assert set(loaded) == set(tensors)
    for name, want in tensors.items():
        got = np.asarray(loaded[name])
        if want.dtype.itemsize == 8:
            # jax x64 disabled: 64-bit tensors canonicalize to 32-bit
            # (low word — exact for values fitting 32 bits).
            assert got.dtype.itemsize == 4, name
            want = want.astype(got.dtype)
        else:
            assert got.dtype == want.dtype, name
        np.testing.assert_array_equal(got, want, err_msg=name)


def test_names_filter_and_shardings(checkpoint):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from dragonfly2_tpu.parallel.ici import make_mesh

    tensors, content = checkpoint
    sink = _land(content)
    mesh = make_mesh(8)
    loaded = st.load_from_sink(
        sink, names=["model.w1"],
        shardings={"model.w1": NamedSharding(mesh, P(None, "d"))})
    assert list(loaded) == ["model.w1"]
    sharded = loaded["model.w1"]
    assert len(sharded.sharding.device_set) == 8
    np.testing.assert_array_equal(np.asarray(sharded), tensors["model.w1"])


def test_corrupt_header_rejected():
    content = struct.pack("<Q", 1 << 40) + b"{}"
    sink = _land(content + b"\x00" * 100)
    with pytest.raises(st.SafetensorsError, match="header length"):
        st.load_from_sink(sink)


def test_span_mismatch_rejected():
    header = {"t": {"dtype": "F32", "shape": [4], "data_offsets": [0, 12]}}
    hj = json.dumps(header).encode()
    content = struct.pack("<Q", len(hj)) + hj + b"\x00" * 16
    sink = _land(content)
    with pytest.raises(st.SafetensorsError, match="data span"):
        st.load_from_sink(sink)


def test_p2p_checkpoint_to_named_tensors(run_async, tmp_path, checkpoint):
    """End to end: safetensors served by an origin, pulled through the
    P2P fabric with --device landing, consumed as named tensors."""
    from aiohttp import web

    from dragonfly2_tpu.client import device as device_lib
    from dragonfly2_tpu.pkg.piece import Range
    from tests.test_device_sink import _start_sink_daemon
    from tests.test_p2p_e2e import start_scheduler

    tensors, content = checkpoint
    sha = "sha256:" + hashlib.sha256(content).hexdigest()

    async def body():
        async def blob(request):
            rng = request.headers.get("Range")
            if rng:
                r = Range.parse_http(rng, len(content))
                return web.Response(
                    status=206, body=content[r.start:r.start + r.length],
                    headers={"Accept-Ranges": "bytes",
                             "Content-Range": f"bytes {r.start}-"
                             f"{r.start + r.length - 1}/{len(content)}"})
            return web.Response(body=content,
                                headers={"Accept-Ranges": "bytes"})

        app = web.Application()
        app.router.add_get("/ckpt.safetensors", blob)
        runner = web.AppRunner(app, access_log=None)
        await runner.setup()
        site = web.TCPSite(runner, "127.0.0.1", 0)
        await site.start()
        oport = site._server.sockets[0].getsockname()[1]

        sched = await start_scheduler()
        peer = await _start_sink_daemon(tmp_path, "ckpt", sched.port())
        try:
            result = await device_lib.download_to_device(
                peer, f"http://127.0.0.1:{oport}/ckpt.safetensors",
                digest=sha)
            loaded = result.load_safetensors()
            for name, want in tensors.items():
                np.testing.assert_array_equal(
                    np.asarray(loaded[name]), want, err_msg=name)
        finally:
            await peer.stop()
            await sched.stop()
            await runner.cleanup()

    run_async(body(), timeout=120)


class TestReviewRegressions:
    def test_bool_tensor_loads(self):
        arr = np.array([1, 0, 1, 1, 0, 0, 1, 0], dtype=np.uint8)
        content = make_safetensors({"mask": arr}, {"mask": "BOOL"})
        sink = _land(content, piece=256)
        loaded = st.load_from_sink(sink)
        np.testing.assert_array_equal(
            np.asarray(loaded["mask"]), arr.astype(bool))

    def test_f64_refused_without_x64(self):
        arr = np.ones(4, dtype=np.float64)
        content = make_safetensors({"w": arr}, {"w": "F64"})
        sink = _land(content, piece=256)
        with pytest.raises(st.SafetensorsError, match="x64"):
            st.load_from_sink(sink)

    def test_out_of_range_offsets_rejected(self):
        header = {"t": {"dtype": "F32", "shape": [64],
                        "data_offsets": [0, 256]}}
        hj = json.dumps(header).encode()
        content = struct.pack("<Q", len(hj)) + hj + b"\x00" * 16  # short
        sink = _land(content, piece=256)
        with pytest.raises(st.SafetensorsError, match="outside content"):
            st.load_from_sink(sink)

    def test_negative_offsets_rejected(self):
        header = {"t": {"dtype": "F32", "shape": [2],
                        "data_offsets": [-8, 0]}}
        hj = json.dumps(header).encode()
        content = struct.pack("<Q", len(hj)) + hj + b"\x00" * 16
        sink = _land(content, piece=256)
        with pytest.raises(st.SafetensorsError, match="outside content"):
            st.load_from_sink(sink)

    def test_missing_requested_name_rejected(self):
        arr = np.ones(4, dtype=np.float32)
        content = make_safetensors({"w": arr}, {"w": "F32"})
        sink = _land(content, piece=256)
        with pytest.raises(st.SafetensorsError, match="not in checkpoint"):
            st.load_from_sink(sink, names=["w_typo"])

    def test_unknown_sharding_name_rejected(self):
        arr = np.ones(4, dtype=np.float32)
        content = make_safetensors({"w": arr}, {"w": "F32"})
        sink = _land(content, piece=256)
        with pytest.raises(st.SafetensorsError, match="not loaded"):
            st.load_from_sink(sink, shardings={"w_typo": None})

    def test_structurally_malformed_headers_raise_schema_error(self):
        cases = [
            b"[1, 2]",                                       # header not object
            b'{"t": "not-an-object"}',                       # entry not object
            b'{"t": {"dtype": "F32", "data_offsets": [0, 4]}}',   # no shape
            b'{"t": {"dtype": "F32", "shape": "x", "data_offsets": [0, 4]}}',
            b'{"t": {"dtype": "F32", "shape": [1], "data_offsets": [0.0, 4]}}',
            b'{"t": {"dtype": "F32", "shape": [-1], "data_offsets": [0, 4]}}',
        ]
        for hj in cases:
            content = struct.pack("<Q", len(hj)) + hj + b"\x00" * 64
            sink = _land(content, piece=256)
            with pytest.raises(st.SafetensorsError):
                st.load_from_sink(sink)

    def test_i64_beyond_32_bits_refused(self):
        arr = np.array([(1 << 40) + 7], dtype=np.int64)
        content = make_safetensors({"big": arr}, {"big": "I64"})
        sink = _land(content, piece=256)
        with pytest.raises(st.SafetensorsError, match="exceed 32 bits"):
            st.load_from_sink(sink)

    def test_i64_negative_within_32_bits_exact(self):
        arr = np.array([-5, 7, -1], dtype=np.int64)
        content = make_safetensors({"ids": arr}, {"ids": "I64"})
        sink = _land(content, piece=256)
        loaded = st.load_from_sink(sink)
        np.testing.assert_array_equal(
            np.asarray(loaded["ids"]), arr.astype(np.int32))


class TestZeroLengthAndMetadata:
    """ISSUE 10 satellites: zero-length tensors load without raising,
    and the ``__metadata__`` entry has a public accessor instead of
    being silently dropped."""

    def _content(self, header: dict, data: bytes = b"") -> bytes:
        hj = json.dumps(header).encode()
        return struct.pack("<Q", len(hj)) + hj + data

    def test_zero_length_tensors_all_dtypes(self):
        header = {
            "f32": {"dtype": "F32", "shape": [0], "data_offsets": [0, 0]},
            "f64": {"dtype": "F64", "shape": [0], "data_offsets": [0, 0]},
            "i64": {"dtype": "I64", "shape": [0, 4], "data_offsets": [0, 0]},
            "bool": {"dtype": "BOOL", "shape": [0], "data_offsets": [0, 0]},
            "mid": {"dtype": "F32", "shape": [2], "data_offsets": [0, 8]},
            "empty_at_end": {"dtype": "F16", "shape": [4, 0],
                             "data_offsets": [8, 8]},
        }
        sink = _land(self._content(header, b"\x11" * 8), piece=256)
        loaded = st.load_from_sink(sink)
        assert loaded["f32"].shape == (0,)
        assert loaded["f64"].shape == (0,)     # no x64 refusal for 0 elems
        assert loaded["i64"].shape == (0, 4)
        assert loaded["bool"].shape == (0,)
        assert bool(loaded["bool"].dtype == np.bool_)
        assert loaded["empty_at_end"].shape == (4, 0)
        assert loaded["mid"].shape == (2,)

    def test_zero_length_span_mismatch_still_rejected(self):
        # A 0-element shape with a NON-empty span is malformed.
        header = {"t": {"dtype": "F32", "shape": [0],
                        "data_offsets": [0, 4]}}
        sink = _land(self._content(header, b"\0" * 4), piece=256)
        with pytest.raises(st.SafetensorsError, match="data span"):
            st.load_from_sink(sink)

    def test_header_metadata_accessor(self):
        header = {"__metadata__": {"format": "pt", "step": "1234"},
                  "w": {"dtype": "F32", "shape": [1],
                        "data_offsets": [0, 4]}}
        content = self._content(header, b"\0" * 4)
        parsed, _ = st.parse_header(content)
        assert st.header_metadata(parsed) == {"format": "pt",
                                              "step": "1234"}
        # tensor_views still skips it.
        sink = _land(content, piece=256)
        assert set(st.load_from_sink(sink)) == {"w"}

    def test_header_metadata_absent_is_empty(self):
        parsed, _ = st.parse_header(self._content(
            {"w": {"dtype": "F32", "shape": [1], "data_offsets": [0, 4]}},
            b"\0" * 4))
        assert st.header_metadata(parsed) == {}

    def test_header_metadata_malformed_rejected(self):
        for bad in ([1, 2], "x", {"k": 3}, {"k": None}, {"k": ["v"]}):
            with pytest.raises(st.SafetensorsError, match="__metadata__"):
                st.header_metadata({"__metadata__": bad})
        with pytest.raises(st.SafetensorsError, match="JSON object"):
            st.header_metadata([])


def test_pod_global_shardings_from_preheated_sink(checkpoint):
    """The north-star consumption chain: a preheat-landed checkpoint loads
    straight into tensors placed on a pod-global factored mesh —
    load_from_sink's shardings hook composes with parallel.multihost
    (single process here; the same NamedSharding spans processes on a
    pod where every host preheated the same content)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from dragonfly2_tpu.ops.safetensors import load_from_sink
    from dragonfly2_tpu.parallel import multihost

    arrays, content = checkpoint
    sink = _land(content)
    mesh = multihost.global_mesh({"dp": 2, "tp": 4})
    name, ref = next((n, a) for n, a in arrays.items() if a.ndim >= 2)
    axis = "tp" if ref.shape[-1] % 4 == 0 else "dp"
    spec = P(*([None] * (ref.ndim - 1) + [axis]))
    tensors = load_from_sink(
        sink, names=[name],
        shardings={name: NamedSharding(mesh, spec)})
    arr = tensors[name]
    assert arr.sharding.mesh.shape == {"dp": 2, "tp": 4}
    np.testing.assert_array_equal(np.asarray(arr), ref)
    # a consumer jit under the same mesh uses it directly
    out = jax.jit(lambda x: x.sum())(arr)
    np.testing.assert_allclose(float(out), float(ref.sum()), rtol=1e-4)


# -- a dispatch carries many views (ops/bitview.typed_views) -----------------

_NUMPY = {"F32": np.float32, "U16": np.uint16, "I16": np.int16,
          "U8": np.uint8, "I8": np.int8, "BOOL": np.bool_, "I32": np.int32}


def _views_counted() -> tuple[float, float, float]:
    """(dispatches, tensors, of them cut by the rows kernel)."""
    from dragonfly2_tpu.ops import bitview

    rows, flat = (bitview.VIEWS_TENSORS.labels(form)._value.get()
                  for form in ("rows", "flat"))
    return bitview.VIEWS_DISPATCHES._value.get(), rows + flat, rows


# The group's shape: two the rows kernel takes (rows of whole 128-word
# groups or half of one; rows of 1.5, whose PAIRS are whole, as the benchmark's
# ``down_proj`` rows of 1,408 items are 5.5) and four it does not: the
# sweep's old shape (under ``_SINGLE_WORDS``), rows of 1,400 items, a 1-D
# norm, and rows of whole groups but fewer than a step cuts.
ROWS_SHAPE = (256, 128)
PAIRED_SHAPE = (258, 384)
FLAT_SHAPES = [(7, 9), (260, 1400), (2048,), (8, 256)]


def grouped_object(members: int, pad: int, seed: int = 5, shape=(7, 9)):
    """A safetensors object whose header mixes one group of ``members``
    I16 tensors of ``shape`` with singles, a BOOL, a zero-length tensor
    and an odd U8 in the middle of the group (so the group's members lie
    at two alignments), its data starting ``pad`` bytes into a word, and
    a tail that keeps the last member a step's rows from the content's
    end: (content, {name: what ``np.frombuffer`` reads}) in header order."""
    rng = np.random.default_rng(seed)
    table = [("a.single", "F32", (3, 5))]
    for i in range(members):
        table.append((f"g.{i}", "I16", shape))
        if i == members // 2:
            table += [("m.odd", "U8", (11,)), ("m.bool", "BOOL", (6,)),
                      ("m.empty", "F32", (0, 4)), ("m.pair", "U16", (2, 3))]
        if i % 5 == 0:
            table.append((f"h.{i}", "I8", (5,)))
    # The kernel reads whole steps of 256 rows: up to 255 rows' words and
    # 9 buffer rows behind the last member's end.
    table += [("z.last", "I32", (4,)),
              ("zz.tail", "U8", (5 * 1024 + 7 + 510 * shape[-1],))]
    header, blobs, want, at = {}, [], {}, 0
    for name, dtype, shape in table:
        kind = np.dtype(_NUMPY[dtype])
        size = int(np.prod(shape)) * kind.itemsize
        raw = rng.integers(0, 2 if dtype == "BOOL" else 256, size,
                           dtype=np.uint8).tobytes()
        header[name] = {"dtype": dtype, "shape": list(shape),
                        "data_offsets": [at, at + size]}
        want[name] = np.frombuffer(raw, kind).reshape(shape)
        blobs.append(raw)
        at += size
    text = json.dumps(header).encode()
    text += b" " * ((pad - (8 + len(text))) % 4)
    return struct.pack("<Q", len(text)) + text + b"".join(blobs), want


def _expected_dispatches(content: bytes, names=None) -> int:
    """One dispatch for each (alignment, dtype, shape) and cap's worth."""
    from dragonfly2_tpu.ops import bitview

    header, data_start = st.parse_header(content)
    groups: dict[tuple, int] = {}
    for name, meta in header.items():
        if names is None or name in names:
            key = ((data_start + meta["data_offsets"][0]) % 4,
                   meta["dtype"], tuple(meta["shape"]))
            groups[key] = groups.get(key, 0) + 1
    return sum(-(-n // bitview._GROUP_CAP) for n in groups.values())


def _assert_equal_to_frombuffer(loaded: dict, want: dict) -> None:
    assert list(loaded) == list(want)                   # header order
    for name, ref in want.items():
        got = np.asarray(loaded[name])
        assert got.dtype == ref.dtype and got.shape == ref.shape, name
        assert got.tobytes() == ref.tobytes(), name     # bit for bit


def _group_sizes():
    from dragonfly2_tpu.ops import bitview

    cap = bitview._GROUP_CAP
    return [cap, cap + 1, 2 * cap + 1]


@pytest.mark.parametrize("pad", [0, 1, 2, 3])
@pytest.mark.parametrize("members", _group_sizes())
def test_grouped_views_equal_frombuffer_at_every_alignment(members, pad):
    content, want = grouped_object(members, pad)
    sink = _land(content, piece=1024)
    was = _views_counted()
    loaded = st.load_from_sink(sink)
    now = _views_counted()
    _assert_equal_to_frombuffer(loaded, want)
    assert len({id(t) for t in loaded.values()}) == len(want)
    assert (now[1] - was[1], now[2] - was[2]) == (len(want), 0)
    assert now[0] - was[0] == _expected_dispatches(content)


def _written_once_cases():
    """Every group size at every alignment over rows of half a word group,
    and a group of cap + 1 over rows of 1.5 with a last step of two."""
    from dragonfly2_tpu.ops import bitview

    return ([(members, ROWS_SHAPE, pad) for members in _group_sizes()
             for pad in (0, 1, 2, 3)]
            + [(bitview._GROUP_CAP + 1, PAIRED_SHAPE, pad) for pad in (1, 2)])


@pytest.mark.parametrize("members,shape,pad", _written_once_cases(), ids=str)
def test_grouped_views_written_once_equal_frombuffer(kernel_on_cpu, members,
                                                     shape, pad):
    """The same sweep over a group the rows kernel cuts: every member of
    the group by the kernel, everything else in the object flat, and all
    of it bit for bit what ``np.frombuffer`` reads."""
    content, want = grouped_object(members, pad, shape=shape)
    sink = _land(content, piece=4096)
    was = _views_counted()
    loaded = st.load_from_sink(sink)
    now = _views_counted()
    _assert_equal_to_frombuffer(loaded, want)
    assert len({id(t) for t in loaded.values()}) == len(want)
    assert (now[1] - was[1], now[2] - was[2]) == (len(want), members)
    assert now[0] - was[0] == _expected_dispatches(content)


@pytest.mark.parametrize("pad", [0, 2, 3])
@pytest.mark.parametrize("shape", FLAT_SHAPES, ids=str)
def test_grouped_views_of_shapes_the_kernel_leaves_stay_flat(kernel_on_cpu,
                                                             shape, pad):
    """Where the kernel may run, a shape it does not take is cut by the
    flat form, and says so."""
    from dragonfly2_tpu.ops import bitview

    content, want = grouped_object(bitview._GROUP_CAP + 1, pad, shape=shape)
    sink = _land(content, piece=4096)
    was = _views_counted()
    loaded = st.load_from_sink(sink)
    now = _views_counted()
    _assert_equal_to_frombuffer(loaded, want)
    assert (now[1] - was[1], now[2] - was[2]) == (len(want), 0)
    assert now[0] - was[0] == _expected_dispatches(content)


@pytest.mark.parametrize("dtype,width,pad", [
    ("I16", 256, 1), ("U16", 512, 2), ("F16", 256, 0), ("BF16", 1024, 2),
    ("BF16", 128, 3), ("U16", 2048, 0), ("BF16", 1408, 2), ("I16", 384, 3)])
def test_the_rows_kernel_at_every_dtype_it_takes(kernel_on_cpu, dtype, width,
                                                 pad):
    """Every 2-byte dtype of the header, rows of half a word group to eight, 5.5 among them,
    a last step that is not whole (300 rows), and the tensor that ends at
    the content's end, which the kernel must leave to the flat form: its
    window would reach past the buffer. 16-bit floats are finite normal
    values (the module's documented exception is the chip's)."""
    import jax.numpy as jnp
    import ml_dtypes

    from dragonfly2_tpu.ops import bitview

    rng = np.random.default_rng(width + pad)
    kind = {"I16": np.int16, "U16": np.uint16, "F16": np.float16,
            "BF16": ml_dtypes.bfloat16}[dtype]
    raw = rng.integers(0, 1 << 16, (3, 300, width), dtype=np.uint16)
    if dtype in ("F16", "BF16"):
        lo, bits = (10, 5) if dtype == "F16" else (7, 8)
        exponent = (raw >> lo) & ((1 << bits) - 1)
        raw[exponent == 0] |= 1 << lo
        raw[exponent == (1 << bits) - 1] &= np.uint16(~(1 << lo) & 0xFFFF)
    data = raw.tobytes()
    size = len(data) // 3
    content = b"\x5a" * (4096 + pad) + data
    content += b"\x00" * (-len(content) % 4096)        # whole tiles
    offsets = [4096 + pad + i * size for i in range(3)]
    words = jnp.asarray(np.frombuffer(content, "<u4"))
    was = _views_counted()
    views = bitview.typed_views(words, offsets, st._DTYPES[dtype], (300, width))
    now = _views_counted()
    # The last of the three may end too near the buffer's end: two steps
    # of 256 rows and 9 buffer rows from its first.
    at_end = offsets[2] // 512 + 2 * width + 9 > len(content) // 512
    assert (now[1] - was[1], now[2] - was[2]) == (3, 3 - at_end)
    assert now[0] - was[0] == 1 + at_end
    for i, view in enumerate(views):
        got = np.asarray(view)
        assert got.dtype == kind and got.shape == (300, width)
        assert got.tobytes() == data[i * size:(i + 1) * size], i


@pytest.mark.parametrize("members", _group_sizes())
def test_grouped_views_from_a_byte_buffer(members):
    """The hot-swap plane's uint8 buffer goes the same way; it knows no
    alignment, so a group is cut whole."""
    import jax.numpy as jnp

    from dragonfly2_tpu.ops import bitview

    content, want = grouped_object(members, 3)
    header, data_start = st.parse_header(content)
    was = _views_counted()
    loaded = st.tensor_views(jnp.asarray(np.frombuffer(content, np.uint8)),
                             header, data_start)
    now = _views_counted()
    _assert_equal_to_frombuffer(loaded, want)
    kinds = {(m["dtype"], tuple(m["shape"])) for m in header.values()}
    assert now[0] - was[0] == len(kinds) - 1 + -(-members
                                                 // bitview._GROUP_CAP)


def test_names_pick_part_of_a_group_in_header_order():
    from dragonfly2_tpu.ops import bitview

    cap = bitview._GROUP_CAP
    content, want = grouped_object(cap + 1, 2)
    sink = _land(content, piece=1024)
    names = ["z.last", f"g.{cap}", "m.bool", "g.1", "g.0", "m.empty"]
    was = _views_counted()
    loaded = st.load_from_sink(sink, names=names)
    now = _views_counted()
    _assert_equal_to_frombuffer(
        loaded, {n: want[n] for n in want if n in names})
    assert (now[0] - was[0], now[1] - was[1]) == (
        _expected_dispatches(content, names), len(names))


_GOOD = '"g.0": {"dtype": "I16", "shape": [2], "data_offsets": [0, 4]}, ' \
        '"g.1": {"dtype": "I16", "shape": [2], "data_offsets": [4, 8]}, '


@pytest.mark.parametrize("bad,match", [
    ('"t": "not-an-object"', "must be an object"),
    ('"t": {"dtype": "F8", "shape": [1], "data_offsets": [8, 9]}',
     "unsupported dtype"),
    ('"t": {"dtype": "F32", "data_offsets": [8, 12]}', "bad shape"),
    ('"t": {"dtype": "F32", "shape": [true], "data_offsets": [8, 12]}',
     "bad shape"),
    ('"t": {"dtype": "F32", "shape": [1], "data_offsets": [8.0, 12]}',
     "bad data_offsets"),
    ('"t": {"dtype": "F32", "shape": [1], "data_offsets": [8]}',
     "bad data_offsets"),
    ('"t": {"dtype": "F32", "shape": [2], "data_offsets": [8, 12]}',
     "data span"),
    ('"t": {"dtype": "F32", "shape": [1], "data_offsets": [-4, 0]}',
     "outside content"),
    ('"t": {"dtype": "F32", "shape": [1], "data_offsets": [4096, 4100]}',
     "outside content"),
    ('"t": {"dtype": "F64", "shape": [1], "data_offsets": [8, 16]}', "x64"),
])
def test_a_malformed_entry_raises_before_anything_is_dispatched(bad, match):
    """The good entries stand before the bad one in the header: nothing
    of them is cut either."""
    text = ("{" + _GOOD + bad + "}").encode()
    content = struct.pack("<Q", len(text)) + text + b"\x01" * 64
    sink = _land(content, piece=256)
    words = sink.as_words()
    header, data_start = st.parse_header(content)
    was = _views_counted()
    with pytest.raises(st.SafetensorsError, match=match):
        st.tensor_views(words, header, data_start, total=len(content))
    with pytest.raises(st.SafetensorsError, match="not in checkpoint"):
        st.tensor_views(words, header, data_start, ["g.0", "absent"],
                        total=len(content))
    assert _views_counted() == was


def test_the_benchmark_shards_207_tensors_are_a_dispatch_a_group():
    """207 entries of ``moonlight-shard-1p7g``'s geometry: its tensor
    table at widths small enough for a test and, as the published ones,
    such that no two of the model's other matrices share a shape. 128
    gate/up and 64 down matrices in groups of the cap, 13 programs of one
    or two tensors. A count, not a time."""
    import importlib.util
    import os

    from dragonfly2_tpu.ops import bitview

    # Loaded from its source: chipbench/ itself never lands on sys.path,
    # where a second ``tests`` package lives.
    spec = importlib.util.spec_from_file_location(
        "safetensors_shard", os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "chipbench", "objects", "safetensors_shard.py"))
    shard = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(shard)
    config = {
        "hidden_size": 128, "num_attention_heads": 2, "qk_nope_head_dim": 16,
        "qk_rope_head_dim": 8, "v_head_dim": 8, "kv_lora_rank": 24,
        "n_routed_experts": 64, "moe_intermediate_size": 40,
        "n_shared_experts": 2, "vocab_size": 96,
        "object": {"probe_u16_items": 1025, "probe_u8_items": 515}}
    obj = shard.Objects(config, seed=11)
    content = b"".join(bytes(s) for s in obj.segments())
    assert len(obj.tensors) == 207 and len(content) == obj.length
    sink = _land(content, piece=256 * 1024)
    was = _views_counted()
    loaded = st.load_from_sink(sink)
    now = _views_counted()
    cap = bitview._GROUP_CAP
    assert now[1] - was[1] == 207
    assert now[0] - was[0] == -(-128 // cap) + -(-64 // cap) + 13
    assert list(loaded) == [name for name, _, _ in obj.tensors]
    for name, rows in obj.sample(np.random.default_rng(3), True):
        got = np.asarray(loaded[name] if rows is None else loaded[name][rows])
        assert np.array_equal(
            got.view(np.uint8).reshape(got.shape[0], -1),
            obj.expected(name, rows)), name
