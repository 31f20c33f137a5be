"""Typed views of landed bytes that the TPU compiler accepts at real sizes.

The landed content is a flat uint32 word buffer (a sink's, a hot-swapped
generation's) or a caller's flat uint8 buffer. Reinterpreting either as another
width with ``bitcast_convert_type`` gives the array a minor dimension of
2 or 4, which the TPU's tiled layout pads to 128 lanes: 32-128x the
tensor, refused above a few hundred MiB. A view is cut in one of two
forms, chosen from what the buffer and the tensor are (``_rows_form``):

  * **rows** (``_rows_kernel``, since PR 44): a 2-byte tensor whose PAIRS
    of rows are whole 128-word groups (a width of a multiple of 128
    items), cut from a word buffer of whole 1,024-word tiles on a TPU. A
    Pallas kernel reads each word once, from the buffer where it lies,
    and writes each item once, into the tensor's own rows, layout and
    dtype: no temporary, no pass after it. The buffer's (n / 128, 128)
    form is the same memory, a pair of tensor rows is a fixed number of
    its rows, and the pipeline brings a step's rows from the multiple of
    8 below the first; inside, the lane a row starts at is a roll and a
    select, the byte shift one sublane of a transposed block, and the
    split of a word into two items, which is a lane interleave that no
    vector instruction does, becomes a sublane interleave between two
    transposes (transposed, a row's words lie along sublanes), done on
    32-bit elements that already hold an even row's item low and the odd
    row's high, as the 16-bit tile packs them. Over words that lie on
    every chip of a mesh it runs under ``shard_map`` on each chip's copy
    (a Mosaic kernel is not partitioned automatically).
  * **flat** (``_words_view`` / ``_bytes_view``): everything else: 1- and
    4-byte items, widths that are no multiple of 128 items, 1-D tensors
    and tensors of fewer rows than a step, the byte buffer, buffers that
    are not whole tiles, the tensor at the buffer's very end, views
    traced into a caller's program, and every backend but the TPU.
    - words -> narrower items: blocks of ``_BLOCK_WORDS`` words shaped
      (rows, 128) are bitcast one per loop iteration. A block bitcast
      OUTSIDE a loop (or in a loop of one iteration, which the compiler
      inlines) compiles in time linear in its size, seconds per 100 KiB,
      so anything above ``_SINGLE_WORDS`` goes through loops of at least
      two iterations, with smaller blocks for what the last loop left
      over; the flatten, the cut to the count, the reshape and a 16-bit
      float's last bitcast are each a pass over the tensor after it.
    - bytes -> wider items: strided lane slices combined by shifts,
      block by block (a reshape to (n, itemsize) is hoisted out of any
      loop by the compiler and padded whole).

What was tried for the rows form and lost, on the chip (PERF.md section 6,
PR 44), so that nobody tries it again: the loop's block cut straight into
rows (``stack([lo, hi], -1).reshape(rows, 2 * words)`` or the bitcast, with
and without the float inside) compiles to two transposing copies in the
loop and runs at 60-70 GB/s whatever the block, 1.5x today's loop and no
more; the same interleave as a product with a permutation matrix on the
MXU (bytes as bfloat16, exact) 75 GB/s; the whole tensor without a loop
holds two tensor-sized temporaries again; ``_aligned_words`` of a whole
tensor ahead of a kernel is itself a 95 GB/s pass. In the kernel a lane
gather and the MXU product run at half the transposes' rate; a dynamic
sublane offset into VMEM, a 1-D DMA at an unaligned word and a reshape of
a 1-D VMEM buffer are refused by Mosaic (jax 0.9), and a pipeline window
must start at a multiple of 8 rows.

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
(alignment, dtype, shape, form) are cut by one program that takes their
offsets as one int32 vector and returns a tuple of arrays, ``_GROUP_CAP``
at most a program. The host's cost of a checkpoint load is a dispatch,
not a tensor.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec

from dragonfly2_tpu.pkg import metrics

_BLOCK_WORDS = 1 << 18      # 1 MiB of words per loop iteration
_SINGLE_WORDS = 1 << 12     # largest bitcast compiled outside a loop
_BLOCK_BYTES = 1 << 20
_UINT = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32}
_ROW_TILE = 256             # tensor rows a step of the rows kernel cuts:
                            # 128 pairs, the lanes of a transposed block
_ROW_GROUPS = 32            # 128-word groups a row may have: 20 MiB of
_ROWS_VMEM = 48 << 20       # windows and scratch, under this scoped limit
_WINDOW_SLACK = 16          # buffer rows a step's window holds beyond its
                            # own: below its first row down to a multiple of
                            # 8 (the pipeline's), and the group behind its last
_KERNEL_PLATFORMS = ("tpu",)
# The most views one program cuts. Members are unrolled: each is its own
# kernel in the program (rows: 56 KB of code that stays on the device, one
# traced and lowered function for all of them; flat: its own loops, 1.3 MB
# and 0.1-0.3 s of compile) and holds no temporary, so the cap bounds a
# program's size. Fixed from a load of the benchmark's shard (207 tensors:
# 200 rows, 192 of them two expert shapes; 7 flat) on the chip
# (benchmarks/views_probe.py; PERF.md section 6, PR 44). Ready ms /
# dispatches / first load of a cold process, s:
#
#   cap        8            16            32            64            128
#   one chip   27.8/37/4.8  23.1/25/6.0   20.2/19/6.0   18.8/16/6.3   21.2/15/6.8
#   four       59.6/37/9.9  46.9/25/5.1   39.1/19/5.3   35.0/16/5.5   -
#   (PR 43, 8: 43.7/37/7.7 on one chip, 56.4/37/8.8 on four, the device busy
#   40.4 ms of it; the column of 8 is from before the members shared one
#   lowered function, which took 1.4 s off a cold process at 16)
#
# The device is busy 12.7 ms a load at any cap, so a load is the host's:
# about 0.5 ms a dispatch and 0.045 a tensor on one chip, 1.2 and 0.073 on
# four. A program's outputs are ready when its last member is, so past 64
# the device's work falls behind the dispatches again (128: 21.2). From 32
# to 64 a load gains 1.4 ms on one chip and 4.1 on four (1 % of a re-land)
# for 3.6 MB more code on the device: 32. (At 8 the flat form's 1.3 MB a
# member was the reason, PR 32; the rows kernel took it away.)
_GROUP_CAP = 32

VIEWS_DISPATCHES = metrics.counter(
    "device_views_dispatches_total",
    "View programs dispatched: each cuts the typed views of one "
    "(alignment, dtype, shape) from a landed buffer, up to the group cap "
    "of them")
VIEWS_TENSORS = metrics.counter(
    "device_views_tensors_total",
    "Typed views those programs returned, by the form that cut them: "
    "written once, block by block into the tensor's own rows, by the rows "
    "kernel (rows), or by the general loops over flat items (flat); over "
    "the dispatches, the group size met (1.0: no two tensors of a load "
    "shared a program)",
    ("form",))
_TENSORS = {form: VIEWS_TENSORS.labels(form) for form in ("rows", "flat")}
VIEWS_BYTES = metrics.counter(
    "device_views_bytes_total",
    "Bytes of the typed views those programs returned, by the form that cut "
    "them: a checkpoint of 2-byte weights is cut by the rows kernel, a "
    "training state's 4-byte tensors by the flat loops", ("form",))
_BYTES = {form: VIEWS_BYTES.labels(form) for form in ("rows", "flat")}


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


def _rows_kernel(start_ref, rows_ref, out_ref, turned_ref, packed_ref, *,
                 words: int, shift: int, window: int, n_rows: int):
    """One grid step: ``_ROW_TILE`` rows of the tensor, as pairs, read from
    the window of 128-word buffer rows that the pipeline brought and written
    into ``out_ref`` in the tensor's own layout and dtype.

    A pair of tensor rows is ``2 * words / 128`` buffer rows. Its even and
    its odd row are cut apart, each beginning at a lane of its own: group
    ``k`` of that row of every pair is one strided load, set straight by a
    lane roll and a select against the group behind it, and transposed, so
    that a row's words lie along sublanes (``turn``); the byte shift's next
    word is then one sublane further. Splitting a word into its two items
    is a lane interleave the vector unit has no instruction for; transposed
    it is a sublane interleave, which two strided stores do, of 32-bit
    elements that hold the even row's item low and the odd row's high, as
    the 16-bit tile packs a pair of rows. One transpose back and the
    element IS the tile's: the reinterpretation costs nothing."""
    half = _ROW_TILE // 2
    pair = 2 * words // 128
    full, rest = divmod(words, 128)
    groups = full + bool(rest)
    start = start_ref[0]
    step = pl.program_id(0) * (half * pair)
    base = jnp.minimum((start // 128 + step) // 8 * 8, n_rows - window)
    lane = jax.lax.broadcasted_iota(jnp.int32, (half, 128), 1)

    def turn(start, slot: int):
        at = start // 128 + step - base
        lanes = start % 128
        back = (128 - lanes) % 128
        ours = lane < 128 - lanes

        def group(k):
            return pltpu.roll(
                rows_ref[pl.ds(at + k, half, stride=pair), :], back, 1)

        def one(k, here):
            behind = group(k + 1)
            turned_ref[slot, pl.ds(pl.multiple_of(k * 128, 128), 128), :] = (
                jnp.where(ours, here, behind).T)
            return behind

        last = jax.lax.fori_loop(0, groups, one, group(0))
        if shift:
            # The byte shift's next word behind the row's last.
            turned_ref[slot, groups * 128:(groups + 1) * 128, :] = last.T

    turn(start, 0)
    turn(start + words, 1)

    def aligned(slot: int, k):
        block = turned_ref[slot, pl.ds(pl.multiple_of(k * 128, 128), 136), :]
        if not shift:
            return block[:128]
        return (block[:128] >> (8 * shift)) | (block[1:129] << (32 - 8 * shift))

    def packed(k):
        even, odd = aligned(0, k), aligned(1, k)
        packed_ref[pl.ds(0, 128, stride=2), :] = (
            (even & 0xFFFF) | (odd << 16))
        packed_ref[pl.ds(1, 128, stride=2), :] = (
            (even >> 16) | (odd & jnp.uint32(0xFFFF0000)))
        return packed_ref[...].T

    def emit(k, _):
        out_ref[:, pl.ds(pl.multiple_of(k * 256, 256), 256)] = pltpu.bitcast(
            packed(k), out_ref.dtype)
        return _

    if full:
        jax.lax.fori_loop(0, full, emit, 0)
    if rest:
        out_ref[:, full * 256:] = pltpu.bitcast(
            packed(full)[:, :2 * rest], out_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "shift", "dtype", "shape", "interpret"))
def _rows_view(buffer, start, *, shift: int, dtype, shape, interpret: bool):
    """The view of ``shape`` at word ``start[0]``, cut by the rows kernel:
    every word read once from the buffer where it lies, every item written
    once where it stays (see ``_rows_form`` for what it takes). A jit of
    its own inside the group's: the members of a group are then one traced
    and lowered function called once a member, and tracing and lowering a
    kernel, 0.07 s of every process's first load that no compilation cache
    keeps, is paid once a program and not once a member."""
    rows, width = _count(shape[:-1]), shape[-1]
    words = width // 2
    n_rows = buffer.shape[0] // 128
    reach = _ROW_TILE * words // 128        # buffer rows of a step
    window = reach + _WINDOW_SLACK

    def brought(i, start_ref):
        first = start_ref[0] // 128 + i * reach
        return pl.multiple_of(
            jnp.minimum(first // 8 * 8, n_rows - window), 8), 0

    # The v5e's vector unit has no float16: Mosaic refuses the kernel's
    # last reinterpretation, so that one dtype leaves it unsigned and XLA
    # makes the float (a pass over the tensor, for a dtype that is rare).
    made = _UINT[2] if dtype == jnp.float16 else dtype
    groups = -(-words // 128)
    out = pl.pallas_call(
        functools.partial(_rows_kernel, words=words, shift=shift,
                          window=window, n_rows=n_rows),
        out_shape=jax.ShapeDtypeStruct((rows, width), made),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(pl.cdiv(rows, _ROW_TILE),),
            in_specs=[pl.BlockSpec((pl.Element(window), pl.Element(128)),
                                   brought)],
            out_specs=pl.BlockSpec((_ROW_TILE, width),
                                   lambda i, start_ref: (i, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, (groups + 2) * 128, _ROW_TILE // 2),
                           jnp.uint32),
                pltpu.VMEM((256, _ROW_TILE // 2), jnp.uint32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_ROWS_VMEM),
        interpret=interpret,
    )(start, buffer.reshape(n_rows, 128))
    if made != dtype:
        out = jax.lax.bitcast_convert_type(out, dtype)
    return out.reshape(shape)


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


@functools.partial(jax.jit, static_argnames=(
    "shift", "dtype", "shape", "form", "mesh", "interpret"))
def _views_jit(buffer, starts, *, shift: int, dtype, shape,
               form: str = "flat", mesh=None, interpret: bool = False):
    """One view for each entry of ``starts`` (word offsets into words,
    byte offsets into bytes; ``shift`` is the bytes into the first word,
    0 for bytes), as a tuple. The members share nothing but the buffer.
    ``form`` is how they are cut (``_rows_form``); the rows kernel is a
    program of one chip, so over words that lie on every chip of ``mesh``
    it runs under ``shard_map`` on each chip's own copy (``interpret``
    runs it off the TPU, for the tests)."""
    dtype = jnp.dtype(dtype)
    if _count(shape) == 0:
        # Cut from the buffer, so that it lies where the buffer lies.
        empty = buffer[:0].astype(dtype).reshape(shape)
        return (empty,) * starts.shape[0]
    # BOOL is one byte of 0/1; bitcast refuses bool.
    stored = jnp.dtype(jnp.uint8) if dtype == jnp.bool_ else dtype
    if form == "rows":
        def cut_all(buffer, starts):
            return tuple(_rows_view(buffer, starts[i:i + 1], shift=shift,
                                    dtype=stored, shape=shape,
                                    interpret=interpret)
                         for i in range(starts.shape[0]))
        if mesh is not None:
            everywhere = PartitionSpec()
            cut_all = jax.shard_map(
                cut_all, mesh=mesh, in_specs=(everywhere, everywhere),
                out_specs=everywhere, check_vma=False)
        return cut_all(buffer, starts)
    if buffer.dtype == jnp.uint32:
        cut = functools.partial(_words_view, shift=shift)
    else:
        cut = _bytes_view
    views = tuple(cut(buffer, starts[i], dtype=stored, shape=shape)
                  for i in range(starts.shape[0]))
    return views if stored == dtype else tuple(v != 0 for v in views)


def _rows_form(buffer, dtype, shape) -> tuple[int, dict] | None:
    """What the rows kernel needs of a buffer and a tensor, all of it read
    from the two: a concrete word buffer of whole 1,024-word tiles (its
    (n / 128, 128) form is then the same memory) on a platform the kernel
    runs on, on one chip or the same on every chip of a mesh; 2-byte
    items; PAIRS of rows that are whole 128-word groups (a width of a
    multiple of 128 items), ``_ROW_GROUPS`` groups a row at most (its
    windows must fit VMEM), and at least one step's rows. Returns (the
    buffer rows a member must find from its first, how ``_views_jit`` is
    to cut it), or None: the flat form."""
    if (isinstance(buffer, jax.core.Tracer) or buffer.dtype != jnp.uint32
            or buffer.shape[0] % 1024 or dtype.itemsize != 2
            or len(shape) < 2 or shape[-1] % 128
            or not 1 <= shape[-1] <= 256 * _ROW_GROUPS
            or _count(shape[:-1]) < _ROW_TILE):
        return None
    reach = _ROW_TILE * shape[-1] // 256
    if buffer.shape[0] // 128 < reach + _WINDOW_SLACK:
        return None
    platform = next(iter(buffer.devices())).platform
    if platform not in _KERNEL_PLATFORMS:
        return None
    mesh = None
    if len(buffer.devices()) > 1:
        if not (buffer.sharding.is_fully_replicated
                and hasattr(buffer.sharding, "mesh")):
            return None
        mesh = buffer.sharding.mesh
    steps = -(-_count(shape[:-1]) // _ROW_TILE)
    return steps * reach + 9, {"form": "rows", "mesh": mesh,
                               "interpret": platform != "tpu"}


def typed_views(buffer, byte_offsets, dtype, shape) -> list:
    """For each of ``byte_offsets`` (any alignment), the ``shape`` items
    of ``dtype`` whose bytes start there in a landed buffer: flat uint32
    words or flat uint8 bytes. A list of device arrays in the offsets'
    order, each its own array.

    One compiled program per (alignment, dtype, shape, form, group size):
    the offsets travel as one int32 vector, an argument of the call, so
    the experts of one layer share a program AND a dispatch,
    ``_GROUP_CAP`` of them at most; a larger group goes out in chunks of
    the cap. No device value is made on the host per tensor. Over words
    that lie on every chip of a mesh the program runs on every chip and
    the views are replicated as the words are."""
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
    rows = _rows_form(buffer, dtype, shape) if _count(shape) else None
    groups: dict[tuple, list[int]] = {}
    for i, at in enumerate(byte_offsets):
        # The kernel reads whole steps and the row behind the last: a
        # tensor that ends nearer the buffer's end than that goes flat.
        form = ("rows" if rows is not None
                and at // 512 + rows[0] <= buffer.shape[0] // 128
                else "flat")
        groups.setdefault((at % unit, form), []).append(i)
    out = [None] * len(byte_offsets)
    for (shift, form), members in groups.items():
        how = rows[1] if form == "rows" else {}
        for k in range(0, len(members), _GROUP_CAP):
            chunk = members[k:k + _GROUP_CAP]
            starts = np.asarray([byte_offsets[i] // unit for i in chunk],
                                np.int32)
            views = _views_jit(buffer, starts, shift=shift, dtype=dtype,
                               shape=shape, **how)
            if not isinstance(buffer, jax.core.Tracer):
                # Traced into a caller's program it is no dispatch.
                VIEWS_DISPATCHES.inc()
                _TENSORS[form].inc(len(chunk))
                _BYTES[form].inc(len(chunk) * _count(shape) * dtype.itemsize)
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
