"""Typed views of landed bytes that the TPU compiler accepts at real sizes.

The landed content is a flat uint32 word buffer (ops/hbm_sink.py) or, on
the hot-swap path, a flat uint8 buffer. Reinterpreting either as another
width with ``bitcast_convert_type`` gives the array a minor dimension of
2 or 4, which the TPU's tiled layout pads to 128 lanes: 32-128x the
tensor, refused above a few hundred MiB. The forms here keep that padded
intermediate to one block at a time inside a loop, so a view's
temporaries are of the order of the view itself:

  * words -> narrower items (``_words_view``): blocks of ``_BLOCK_WORDS``
    words shaped (rows, 128) are bitcast one per loop iteration. A block
    bitcast OUTSIDE a loop (or in a loop of one iteration, which the
    compiler inlines) compiles in time linear in its size, seconds per
    100 KiB, so anything above ``_SINGLE_WORDS`` goes through loops of at
    least two iterations, with smaller blocks for what the last loop
    left over.
  * bytes -> wider items (``_bytes_view``): strided lane slices combined
    by shifts, block by block (a reshape to (n, itemsize) is hoisted out
    of any loop by the compiler and padded whole).

Byte order is little-endian throughout, as in the safetensors format and
as ``bitcast_convert_type`` defines it, so results are bit-identical to a
NumPy ``frombuffer`` of the same bytes — with one exception that is the
chip's and not this module's: any TPU op that PRODUCES a 16-bit float
array (a standalone uint16 -> bfloat16 bitcast, even a row slice of a
bfloat16 array) rewrites NaN payloads to the canonical NaN and flushes
denormals to zero (measured on a v5e, PR 22: 1 pattern in 128 of random
bytes). Integer, uint8 and float32 views are exact for every pattern
there, and 16-bit floats for every finite normal value.

A dispatch carries many tensors (``typed_views``): the views of one
(alignment, dtype, shape) are cut by one program that takes their offsets
as one int32 vector and returns a tuple of arrays, ``_GROUP_CAP`` at most
a program. The host's cost of a checkpoint load is a dispatch, not a
tensor: 207 tensors of a MoE layer's shard are 37 dispatches.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from dragonfly2_tpu.pkg import metrics

_BLOCK_WORDS = 1 << 18      # 1 MiB of words per loop iteration
_SINGLE_WORDS = 1 << 12     # largest bitcast compiled outside a loop
_BLOCK_BYTES = 1 << 20
_UINT = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32}
# The most views one program cuts. Members are unrolled: each adds its own
# loops to the program, 1.3 MB of code that stays on the device and 0.1-0.3 s
# of compile, and no temporary beyond the single view's, so the cap bounds a
# program's size and compile time. Fixed from a load of the benchmark's shard
# (207 tensors, 192 of them two expert shapes) on the chip (PERF.md section
# 6, PR 32): on one chip the load is the device's 44 ms from 4 up (74 ms at
# 1, the host's); on four chips 221 / 82 / 59 / 45 ms at 1 / 4 / 8 / 16, and
# a cold process compiles 5.8 / 6.6 / 7.9 / 11.0 / 15.9 s at 1 / 4 / 8 / 16 /
# 32. From 8 to 16 a cold process pays 3.1 s and the device 21 MB of code
# for 14 ms of a four-chip load and nothing on one chip: 8.
_GROUP_CAP = 8

VIEWS_DISPATCHES = metrics.counter(
    "device_views_dispatches_total",
    "View programs dispatched: each cuts the typed views of one "
    "(alignment, dtype, shape) from a landed buffer, up to the group cap "
    "of them")
VIEWS_TENSORS = metrics.counter(
    "device_views_tensors_total",
    "Typed views those programs returned; over the dispatches, the group "
    "size met (1.0: no two tensors of a load shared a program)")


def _count(shape) -> int:
    return int(np.prod(shape)) if len(shape) else 1


def _aligned_words(words, start, n: int, shift: int):
    """``n`` words of the byte stream that begins ``shift`` bytes into
    word ``start``. The extra high word is fetched on its own so that the
    fetch clamps at the buffer's end without moving the block."""
    lo = jax.lax.dynamic_slice(words, (start,), (n,))
    if not shift:
        return lo
    last = jax.lax.dynamic_slice(words, (start + n,), (1,))
    hi = jnp.concatenate([lo[1:], last])
    return (lo >> (8 * shift)) | (hi << (32 - 8 * shift))


def _split_block(block, stage, ratio: int):
    """(rows, 128) words -> (rows, 128 * ratio) items of ``stage``. Kept
    two-dimensional: flattened inside a loop it compiles as slowly as a
    block outside one."""
    out = jax.lax.bitcast_convert_type(block, stage)
    return out.reshape(block.shape[0], 128 * ratio)


def _split_words(words, start, n: int, shift: int, stage):
    """``n`` aligned words as 1- or 2-byte items of ``stage``, flat."""
    ratio = 4 // jnp.dtype(stage).itemsize
    parts = []
    while n > _SINGLE_WORDS:
        # At least two blocks per loop; what they leave over (less than a
        # block) goes round again with a smaller block.
        block = min(_BLOCK_WORDS, (n // 2) // 128 * 128)
        full = n // block

        def one(off, block=block, start=start):
            src = _aligned_words(words, start + off, block, shift)
            return _split_block(src.reshape(block // 128, 128), stage, ratio)

        offsets = jnp.arange(full, dtype=jnp.int32) * block
        parts.append(jax.lax.map(one, offsets).reshape(-1))
        start = start + full * block
        n -= full * block
    if n:
        src = _aligned_words(words, start, n, shift)
        padded = -(-n // 128) * 128
        src = jnp.pad(src, (0, padded - n)).reshape(padded // 128, 128)
        parts.append(_split_block(src, stage, ratio).reshape(-1)[: n * ratio])
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts)


def _words_view(words, start, shift: int, dtype, shape):
    count = _count(shape)
    n = -(-count * dtype.itemsize // 4)
    if dtype.itemsize >= 4:
        out = _aligned_words(words, start, n, shift)
        if dtype.itemsize == 8:
            # Only reachable with jax x64 enabled, which no TPU
            # deployment of this sink runs.
            return jax.lax.bitcast_convert_type(
                out.reshape(count, 2), dtype).reshape(shape)
        return jax.lax.bitcast_convert_type(out.reshape(shape), dtype)
    # Floats move as unsigned integers and become ``dtype`` only at the
    # end: a float passing through a loop's copies may have its NaN
    # payload normalised, and the view must be bit-exact. The last
    # bitcast costs a second tensor-sized temporary on the TPU.
    stage = (dtype if jnp.issubdtype(dtype, jnp.integer)
             else _UINT[dtype.itemsize])
    out = _split_words(words, start, n, shift, stage)[:count].reshape(shape)
    return out if stage == dtype else jax.lax.bitcast_convert_type(out, dtype)


def _join_block(block, uint, itemsize: int):
    """(rows, 128 * itemsize) bytes -> (rows * 128,) unsigned items."""
    acc = block[:, 0::itemsize].astype(uint)
    for k in range(1, itemsize):
        acc = acc | (block[:, k::itemsize].astype(uint) << (8 * k))
    return acc.reshape(-1)


def _bytes_view(u8, start, dtype, shape):
    count = _count(shape)
    size = dtype.itemsize
    raw = jax.lax.dynamic_slice(u8, (start,), (count * size,))
    if size == 1:
        return jax.lax.bitcast_convert_type(raw, dtype).reshape(shape)
    if size == 8:
        # x64 only (see _words_view).
        return jax.lax.bitcast_convert_type(
            raw.reshape(count, 8), dtype).reshape(shape)
    uint = _UINT[size]
    row = 128 * size
    full, tail = divmod(raw.shape[0], _BLOCK_BYTES)
    parts = []
    if full:
        main = raw[: full * _BLOCK_BYTES].reshape(
            full, _BLOCK_BYTES // row, row)
        join = functools.partial(_join_block, uint=uint, itemsize=size)
        parts.append((join(main[0]) if full == 1
                      else jax.lax.map(join, main)).reshape(-1))
    if tail:
        padded = -(-tail // row) * row
        rest = jnp.pad(raw[full * _BLOCK_BYTES:], (0, padded - tail))
        parts.append(_join_block(rest.reshape(-1, row), uint,
                                 size)[: tail // size])
    out = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
    return jax.lax.bitcast_convert_type(out.reshape(shape), dtype)


@functools.partial(jax.jit, static_argnames=("shift", "dtype", "shape"))
def _views_jit(buffer, starts, *, shift: int, dtype, shape):
    """One view for each entry of ``starts`` (word offsets into words,
    byte offsets into bytes; ``shift`` is the bytes into the first word,
    0 for bytes), as a tuple. The members share nothing but the buffer."""
    dtype = jnp.dtype(dtype)
    if _count(shape) == 0:
        # Cut from the buffer, so that it lies where the buffer lies.
        empty = buffer[:0].astype(dtype).reshape(shape)
        return (empty,) * starts.shape[0]
    # BOOL is one byte of 0/1; bitcast refuses bool.
    stored = jnp.dtype(jnp.uint8) if dtype == jnp.bool_ else dtype
    if buffer.dtype == jnp.uint32:
        cut = functools.partial(_words_view, shift=shift)
    else:
        cut = _bytes_view
    views = tuple(cut(buffer, starts[i], dtype=stored, shape=shape)
                  for i in range(starts.shape[0]))
    return views if stored == dtype else tuple(v != 0 for v in views)


def typed_views(buffer, byte_offsets, dtype, shape) -> list:
    """For each of ``byte_offsets`` (any alignment), the ``shape`` items
    of ``dtype`` whose bytes start there in a landed buffer: flat uint32
    words or flat uint8 bytes. A list of device arrays in the offsets'
    order, each its own array.

    One compiled program per (alignment, dtype, shape, group size): the
    offsets travel as one int32 vector, an argument of the call, so the
    experts of one layer share a program AND a dispatch, ``_GROUP_CAP``
    of them at most; a larger group goes out in chunks of the cap. No
    device value is made on the host per tensor. Over words that lie on
    every chip of a mesh the program runs on every chip and the views are
    replicated as the words are."""
    dtype = jnp.dtype(dtype)
    shape = tuple(shape)
    if buffer.dtype == jnp.uint32:
        unit = 4
    elif buffer.dtype == jnp.uint8:
        check_u8_indexable(buffer)
        unit = 1
    else:
        raise TypeError(f"landed buffer must be uint32 or uint8, "
                        f"got {buffer.dtype}")
    by_shift: dict[int, list[int]] = {}
    for i, at in enumerate(byte_offsets):
        by_shift.setdefault(at % unit, []).append(i)
    out = [None] * len(byte_offsets)
    for shift, members in by_shift.items():
        for k in range(0, len(members), _GROUP_CAP):
            chunk = members[k:k + _GROUP_CAP]
            starts = np.asarray([byte_offsets[i] // unit for i in chunk],
                                np.int32)
            views = _views_jit(buffer, starts, shift=shift, dtype=dtype,
                               shape=shape)
            if not isinstance(buffer, jax.core.Tracer):
                # Traced into a caller's program it is no dispatch.
                VIEWS_DISPATCHES.inc()
                VIEWS_TENSORS.inc(len(chunk))
            for i, view in zip(chunk, views):
                out[i] = view
    return out


def typed_view(buffer, byte_offset: int, dtype, shape):
    """The one view at ``byte_offset``: ``typed_views`` of one offset."""
    return typed_views(buffer, [byte_offset], dtype, shape)[0]


def check_u8_indexable(u8) -> None:
    """The byte programs index with int32: a uint8 buffer stops at 2 GiB.
    (The word buffer of a landing reaches 8 GiB, past what a 16 GB chip
    can assemble.)"""
    if u8.shape[0] >= 1 << 31:
        raise ValueError(
            f"uint8 device buffer of {u8.shape[0]} bytes: the byte views "
            "and the hot-swap gate index with int32 and stop at 2 GiB")


def host_bytes(buffer, start: int, stop: int) -> bytes:
    """Bytes [start, stop) of a landed buffer, fetched to the host: the
    safetensors length prefix and header. Only the covering words move."""
    if buffer.dtype == jnp.uint8:
        return np.asarray(buffer[start:stop]).tobytes()
    w0, w1 = start // 4, -(-stop // 4)
    raw = np.asarray(buffer[w0:w1]).astype("<u4").tobytes()
    return raw[start - 4 * w0: stop - 4 * w0]
