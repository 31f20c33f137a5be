"""Safetensors views over the device sink's landed bytes.

The north-star payload is a sharded safetensors checkpoint. Once the P2P
fabric lands the file in HBM (ops/hbm_sink.py), this module turns it into
named tensors WITHOUT a host round trip: the 8-byte header length and the
JSON header are fetched to host (tiny), and each tensor is cut from the
device-resident buffer — the sink's uint32 words, as a hot-swapped
generation's are, or a caller's uint8 buffer — by ops/bitview.py, whose forms the TPU compiler accepts
at checkpoint-shard sizes, the tensors of one dtype and shape by one
dispatch.

Format (https://github.com/huggingface/safetensors — stable, public):
  [u64 little-endian header_len][header_len bytes of JSON][tensor data]
  header: {"tensor.name": {"dtype": "BF16", "shape": [..],
                           "data_offsets": [begin, end]}, ...}
  offsets are relative to the end of the header.

No reference analog: Dragonfly2 moves opaque bytes; the TPU build knows
what a checkpoint is.
"""

from __future__ import annotations

import json
import math

import jax
import jax.numpy as jnp
import numpy as np

from dragonfly2_tpu.ops import bitview

_DTYPES = {
    "F64": jnp.float64, "F32": jnp.float32, "F16": jnp.float16,
    "BF16": jnp.bfloat16, "I64": jnp.int64, "I32": jnp.int32,
    "I16": jnp.int16, "I8": jnp.int8, "U8": jnp.uint8, "BOOL": jnp.bool_,
    "U16": jnp.uint16, "U32": jnp.uint32, "U64": jnp.uint64,
}


class SafetensorsError(ValueError):
    pass


def parse_header(head: bytes) -> tuple[dict, int]:
    """(header dict, data_start_offset) from the file's first bytes."""
    if len(head) < 8:
        raise SafetensorsError("file shorter than the length prefix")
    n = int.from_bytes(head[:8], "little")
    if n > len(head) - 8:
        raise SafetensorsError(
            f"header ({n} bytes) longer than provided prefix")
    try:
        header = json.loads(head[8:8 + n])
    except json.JSONDecodeError as e:
        raise SafetensorsError(f"bad header JSON: {e}") from e
    return header, 8 + n


_FILE_DTYPES = {np.dtype(v).name: k for k, v in _DTYPES.items()}


def plan_file(specs: dict, metadata: dict | None = None) -> tuple[bytes, dict, int]:
    """The writer's header for ONE safetensors file of the named tensors:
    ``specs`` maps a name to ``(dtype, shape)``. Returns (the file's first
    bytes: length prefix and header, name -> (byte offset in the file,
    byte length) in the file's order, the file's length).

    The writer's choices, which a reader need not know and a second writer
    must repeat to give the same bytes: the tensors lie by item size, the
    widest first, then by name, one behind the other without a gap (the
    format allows none), so every tensor starts on a multiple of its item
    size; the header is ``json.dumps`` with the separators ``(",", ":")``,
    ``__metadata__`` first where there is any, then the tensors in the
    file's order, padded with spaces so that the data starts on a multiple
    of 8."""
    entries = []
    for name, (dtype, shape) in specs.items():
        dt = np.dtype(dtype)
        code = _FILE_DTYPES.get(dt.name)
        if code is None or name == "__metadata__":
            raise SafetensorsError(f"{name}: cannot write dtype {dt.name!r}")
        shape = [int(d) for d in shape]
        entries.append((-dt.itemsize, name, code,
                        shape, math.prod(shape) * dt.itemsize))
    entries.sort(key=lambda e: e[:2])
    header: dict = {}
    if metadata:
        header["__metadata__"] = {str(k): str(v) for k, v in metadata.items()}
    at = 0
    for _, name, code, shape, nbytes in entries:
        header[name] = {"dtype": code, "shape": shape,
                        "data_offsets": [at, at + nbytes]}
        at += nbytes
    text = json.dumps(header, separators=(",", ":")).encode()
    text += b" " * (-(8 + len(text)) % 8)
    head = len(text).to_bytes(8, "little") + text
    layout = {name: (len(head) + header[name]["data_offsets"][0], nbytes)
              for _, name, _, _, nbytes in entries}
    return head, layout, len(head) + at


def header_metadata(header: dict) -> dict[str, str]:
    """The checkpoint's ``__metadata__`` entry as a plain dict ({} when
    absent). The format allows free-form string-to-string metadata
    (producer, format tags, training step); ``tensor_views`` skips the
    entry when building tensors, and this is the public accessor for it
    — a malformed entry (non-object, non-string values) raises instead
    of being silently dropped, since callers branch on it."""
    if not isinstance(header, dict):
        raise SafetensorsError(
            f"header must be a JSON object, got {type(header).__name__}")
    meta = header.get("__metadata__")
    if meta is None:
        return {}
    if (not isinstance(meta, dict)
            or not all(isinstance(k, str) and isinstance(v, str)
                       for k, v in meta.items())):
        raise SafetensorsError(
            "__metadata__ must be a string-to-string object, got "
            f"{meta!r}")
    return dict(meta)


def _low_words(buffer, name: str, file_dtype: str, at: int, canon, shape):
    """A 64-bit integer tensor with jax x64 disabled, where 64-bit dtypes
    canonicalize to 32-bit. Keeping the low word is exact only when the
    high word is the sign/zero extension; values beyond 32 bits are
    checked on device rather than silently truncated. One tensor a call:
    the check needs its answer on the host."""
    pair = bitview.typed_view(buffer, at, canon, (math.prod(shape), 2))
    low, hi = pair[:, 0], pair[:, 1]
    signed = np.issubdtype(np.dtype(canon), np.signedinteger)
    expect_hi = (jnp.where(low < 0, jnp.asarray(-1, canon),
                           jnp.asarray(0, canon))
                 if signed else jnp.zeros_like(hi))
    if bool(jnp.any(hi != expect_hi)):
        raise SafetensorsError(
            f"{name}: {file_dtype} values exceed 32 bits; "
            "enable jax x64 mode to load exactly")
    return low.reshape(shape)


def tensor_views(buffer: jax.Array, header: dict, data_start: int,
                 names: list[str] | None = None, *,
                 total: int | None = None) -> dict[str, jax.Array]:
    """Named device tensors cut from the landed buffer: flat uint32 words
    (``HBMSink.as_words``; pass the content length as ``total``, since the
    words are padded to whole pieces) or flat uint8 bytes. The whole
    header is validated before anything is dispatched; then the tensors
    of one dtype and shape are cut together, a tuple of arrays a dispatch
    (``bitview.typed_views``). The result is in header order, each tensor
    its own device array in its checkpoint dtype and shape, bit-identical
    to the file except for what a TPU does to 16-bit floats: BF16/F16
    NaN payloads come back as the canonical NaN and denormals as zero
    (ops/bitview.py). Integer and F32 tensors keep every pattern."""
    if total is None:
        total = int(buffer.shape[0]) * buffer.dtype.itemsize
    if not isinstance(header, dict):
        raise SafetensorsError(
            f"header must be a JSON object, got {type(header).__name__}")
    # name -> (byte offset, canonical dtype, shape, the file's dtype where
    # it is wider than the canonical one, else None), in header order.
    plan: dict[str, tuple] = {}
    for name, meta in header.items():
        if name == "__metadata__":
            continue
        if names is not None and name not in names:
            continue
        # Structural validation first: this parses UNTRUSTED downloaded
        # bytes, and every malformation must surface as SafetensorsError,
        # not a raw KeyError/TypeError deep in jax.
        if not isinstance(meta, dict):
            raise SafetensorsError(f"{name}: entry must be an object")
        dtype = _DTYPES.get(meta.get("dtype", ""))
        if dtype is None:
            raise SafetensorsError(
                f"{name}: unsupported dtype {meta.get('dtype')!r}")
        shape_raw = meta.get("shape")
        offsets = meta.get("data_offsets")
        if (not isinstance(shape_raw, list)
                or not all(isinstance(d, int) and not isinstance(d, bool)
                           and d >= 0 for d in shape_raw)):
            raise SafetensorsError(f"{name}: bad shape {shape_raw!r}")
        if (not isinstance(offsets, list) or len(offsets) != 2
                or not all(isinstance(o, int) and not isinstance(o, bool)
                           for o in offsets)):
            raise SafetensorsError(
                f"{name}: bad data_offsets {offsets!r}")
        shape = tuple(shape_raw)
        begin, end = offsets
        itemsize = np.dtype(dtype).itemsize    # FILE item size
        count = int(np.prod(shape)) if shape else 1
        if end - begin != count * itemsize:
            raise SafetensorsError(
                f"{name}: data span {end - begin} != "
                f"{count}x{itemsize} for shape {shape}")
        # Bounds: jax slicing CLAMPS, so an out-of-range (or negative)
        # offset would otherwise read wrong bytes or fail opaquely.
        if begin < 0 or data_start + end > total:
            raise SafetensorsError(
                f"{name}: data_offsets [{begin}, {end}] outside content "
                f"({total - data_start} data bytes)")
        # Zero-length tensors (a 0 dim, data_offsets [s, s]) are legal
        # safetensors and come back empty in the canonical dtype.
        canon = jax.dtypes.canonicalize_dtype(dtype)
        wide = count and canon.itemsize != itemsize
        if wide and meta["dtype"] == "F64":
            # float64 low words are mantissa garbage: refuse.
            raise SafetensorsError(
                f"{name}: F64 requires jax x64 mode "
                "(jax.config.update('jax_enable_x64', True))")
        plan[name] = (data_start + begin, canon, shape,
                      meta["dtype"] if wide else None)
    if names is not None:
        missing = [n for n in names if n not in plan]
        if missing:
            raise SafetensorsError(
                f"tensors not in checkpoint: {missing}")
    out: dict[str, jax.Array] = dict.fromkeys(plan)
    groups: dict[tuple, list[str]] = {}
    for name, (at, canon, shape, wide) in plan.items():
        if wide:
            out[name] = _low_words(buffer, name, wide, at, canon, shape)
        else:
            groups.setdefault((canon, shape), []).append(name)
    for (canon, shape), members in groups.items():
        views = bitview.typed_views(
            buffer, [plan[name][0] for name in members], canon, shape)
        out.update(zip(members, views))
    return out


def load_from_sink(sink, *, names: list[str] | None = None,
                   shardings: dict | None = None) -> dict[str, jax.Array]:
    """Named tensors from a completed, verified HBM sink. ``shardings``
    maps tensor name → jax.sharding.Sharding; matching tensors are
    device_put to their sharding (device-to-device over ICI on a slice),
    the rest stay on the sink's device."""
    return load_from_words(sink.as_words(), sink.content_length,
                           names=names, shardings=shardings)


def load_from_words(words, total: int, *, names: list[str] | None = None,
                    shardings: dict | None = None) -> dict[str, jax.Array]:
    """Named tensors from ``total`` content bytes in a flat uint32 device
    buffer (a sink's ``as_words()``, a hot-swapped generation's words):
    what ``load_from_sink`` does with a sink's."""
    # Header prefix to host: 8 bytes, then exactly the header. Two tiny
    # fetches instead of guessing a prefix size.
    n = int.from_bytes(bitview.host_bytes(words, 0, min(8, total)),
                       "little")
    if 8 + n > total:
        raise SafetensorsError("header length exceeds content")
    header, data_start = parse_header(bitview.host_bytes(words, 0, 8 + n))
    tensors = tensor_views(words, header, data_start, names, total=total)
    if shardings:
        unknown = [n for n in shardings if n not in tensors]
        if unknown:
            # A typo'd sharding would silently leave the tensor the
            # caller believes is mesh-sharded on a single device.
            raise SafetensorsError(
                f"shardings reference tensors not loaded: {unknown}")
        for name, sharding in shardings.items():
            tensors[name] = jax.device_put(tensors[name], sharding)
    return tensors
