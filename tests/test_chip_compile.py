"""The served path's device programs, compiled for a described v5e.

Nothing runs here: the TPU compiler that is installed beside jax compiles
each program of the landing and view path for a v5e:2x2 that is described,
not attached, at the sizes the chip smoke lands (1.7 GiB of content,
32 MiB pieces in batches of 8) and at the old 4 MiB geometry. What it
refuses here it refuses on the chip, and ``memory_analysis()`` is what the
memory figures in ops/hbm_sink.py and PERF.md quote.

All cases live in this one file and describe the topology inside a
module-scoped fixture: only one process may load the TPU library, so the
call must not run at import (every xdist worker imports every file).
"""

import functools
import os

import numpy as np
import pytest

MiB = 1 << 20
CONTENT = 1700 * MiB + 4 * 12345      # about one checkpoint shard
PIECES = 55 * 32 * MiB                # the same shard as the sink assembles
                                      # it: whole pieces of 32 MiB
EMBED = (163840, 2048)                # Moonlight-16B-A3B embed_tokens, bf16
EXPERT = (1408, 2048)                 # one routed-expert matrix, 5.5 MiB
DOWN = (2048, 1408)                   # its down_proj: rows of 5.5 word groups


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip; the sink's daemon turns
    # the cache on in this process when another test starts one.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    import jax

    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _memory(fn, *specs):
    """(argument, output, temporary) bytes of ``fn`` compiled for the
    described chip; raises what the chip's compiler would raise."""
    import jax

    m = jax.jit(fn).lower(*specs).compile().memory_analysis()
    return (m.argument_size_in_bytes, m.output_size_in_bytes,
            m.temp_size_in_bytes)


def _batches(n: int, piece_mib: int, sharding, last: int = 8):
    """``n`` staged batches of 8 pieces (the last of ``last``), in the
    shape the sink stages them in."""
    import jax.numpy as jnp

    from dragonfly2_tpu.ops.hbm_sink import _piece_shape

    shape = _piece_shape(piece_mib * MiB // 4)
    return tuple(_spec((8 if i < n - 1 else last, *shape), jnp.uint32,
                       sharding) for i in range(n))


def _assembly(one_chip, piece_mib: int, n_batches: int, last: int = 8,
              missing: int = 0):
    """The assembly program of ``n_batches`` staged batches in a sink of
    ``missing`` more pieces than were staged, compiled for the described
    chip; with it the content's bytes."""
    import jax
    import jax.numpy as jnp

    from dragonfly2_tpu.ops.hbm_sink import _assemble_checksum_jit

    rows = (n_batches - 1) * 8 + last
    compiled = jax.jit(
        functools.partial(_assemble_checksum_jit,
                          total_pieces=rows + missing)).lower(
        _batches(n_batches, piece_mib, one_chip, last),
        _spec((rows,), jnp.int32, one_chip)).compile()
    return compiled, (rows + missing) * piece_mib * MiB


# (piece MiB, batches, pieces in the last one): the smoke's 55 pieces of
# 32 MiB, the tar shards' 30 of 8 MiB, and 512 MiB at the geometry every
# earlier reading used.
GEOMETRIES = [(32, 7, 7), (8, 4, 6), (4, 16, 8)]


@pytest.mark.parametrize("piece_mib,n_batches,last", GEOMETRIES)
def test_assemble_checksum_is_twice_content(one_chip, piece_mib, n_batches,
                                            last):
    """Staged batches in, flat content out, nothing beside them: a staged
    piece is whole tiles, so the copy needs no relayout and the flat view
    of the placed pieces is the same memory (3x before the arrival order
    became an argument: a content-sized temporary for the checksums'
    reshape)."""
    compiled, content = _assembly(one_chip, piece_mib, n_batches, last)
    m = compiled.memory_analysis()
    assert m.argument_size_in_bytes >= content
    assert m.output_size_in_bytes >= content
    assert (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes) <= 2.05 * content
    assert m.temp_size_in_bytes <= 4 * MiB


@pytest.mark.parametrize("piece_mib,n_batches,last", GEOMETRIES)
def test_assembly_program_grows_with_its_operands_not_its_pieces(
        one_chip, piece_mib, n_batches, last):
    """The program itself lives in HBM for the life of the process, one
    for each geometry: a loop a batch, about 150 KB each (one copy a row,
    unrolled, was 3.7 MB at 55 pieces; ``jnp.take`` of 8 rows of 32 MiB
    compiled to 22 MB)."""
    compiled, _ = _assembly(one_chip, piece_mib, n_batches, last)
    code = compiled.memory_analysis().generated_code_size_in_bytes
    assert code <= 192 * 1024 * n_batches + 256 * 1024


@pytest.mark.parametrize("chip", [1, 2, 3])
@pytest.mark.parametrize("n_batches,last", [(1, 5), (2, 7)],
                         ids=["one_expert_17mb", "a_layers_rest_62mb"])
def test_a_sink_on_another_chip_compiles_for_that_chip(topo, chip, n_batches,
                                                       last):
    """A sink that ``download_global`` creates on the chip that keeps its
    bytes (one expert's three matrices, 5 pieces of 4 MiB; what every chip
    keeps of a layer, 15): the assembly program and a group of three views
    compile for that chip as for the first, in the same memory."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from dragonfly2_tpu.ops import bitview

    there = SingleDeviceSharding(topo.devices[chip])
    compiled, content = _assembly(there, 4, n_batches, last)
    m = compiled.memory_analysis()
    assert m.output_size_in_bytes >= content
    assert (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes) <= 2.05 * content + 4 * MiB
    views = jax.jit(functools.partial(
        bitview._views_jit, shift=2, dtype=jnp.dtype(jnp.bfloat16),
        shape=EXPERT)).lower(
        _spec((content // 4,), jnp.uint32, there),
        _starts(3, there)).compile().memory_analysis()
    assert views.output_size_in_bytes >= 3 * 2 * np.prod(EXPERT)


def test_a_partial_sink_assembles_in_the_same_two_contents(one_chip):
    """30 of 55 pieces staged (a ``as_words()`` mid-landing): the output
    is the whole content, zeros where nothing landed, and still nothing
    beside arguments and output."""
    compiled, content = _assembly(one_chip, 32, 4, last=6, missing=25)
    m = compiled.memory_analysis()
    assert m.output_size_in_bytes >= content
    assert m.temp_size_in_bytes <= 4 * MiB


def test_merge_group_of_4mib_batches(one_chip):
    from dragonfly2_tpu.ops.hbm_sink import HBMSink, _merge_jit

    group = HBMSink._MERGE_GROUP
    arg, out, temp = _memory(_merge_jit, _batches(group, 4, one_chip))
    assert out == group * 32 * MiB and temp <= out // 8


def test_merge_group_of_32mib_batches_needs_the_whole_chip(one_chip):
    """32 batches of 256 MiB are 8 GiB in and 8 GiB out. The compiler
    passes it, since it counts the program and not its arguments, but a
    landing at the 32 MiB geometry cannot reach its first merge on 16 GB;
    it is past the 3x limit of the assembly long before (ROADMAP S2)."""
    from dragonfly2_tpu.ops.hbm_sink import HBMSink, _merge_jit

    arg, out, temp = _memory(
        _merge_jit, _batches(HBMSink._MERGE_GROUP, 32, one_chip))
    assert arg + out >= 16 * 1024 * MiB


@pytest.mark.parametrize("piece_mib", [32, 4])
def test_chunk_checksums_relayout_is_one_content(one_chip, piece_mib):
    import jax.numpy as jnp

    from dragonfly2_tpu.ops.checksum import _chunk_checksums_xla

    content = 1728 * MiB
    arg, out, temp = _memory(
        functools.partial(_chunk_checksums_xla,
                          piece_words=piece_mib * MiB // 4),
        _spec((content // 4,), jnp.uint32, one_chip))
    assert temp <= 1.05 * content


def _words(sharding, whole: bool = False):
    """The landed words: the content's, or (``whole``) the whole pieces a
    sink assembles, which the rows kernel reads as 128-word rows."""
    import jax.numpy as jnp

    return _spec((PIECES // 4 if whole else -(-CONTENT // 4),), jnp.uint32,
                 sharding)


def _starts(n: int, sharding):
    """The offsets of ``n`` views, the one vector a view program takes."""
    import jax.numpy as jnp

    return _spec((n,), jnp.int32, sharding)


def test_byte_view_of_the_whole_content(one_chip):
    import jax.numpy as jnp

    from dragonfly2_tpu.ops import bitview

    arg, out, temp = _memory(
        functools.partial(bitview._views_jit, shift=0,
                          dtype=jnp.dtype(jnp.uint8), shape=(CONTENT - 3,)),
        _words(one_chip), _starts(1, one_chip))
    assert out >= CONTENT - 3 and temp <= 1.05 * out


@pytest.mark.parametrize("dtype,shape,shift,factor", [
    ("bfloat16", EMBED, 2, 2.05),   # 640 MiB starting 2 bytes into a word
    ("bfloat16", EMBED, 0, 2.05),   # the same, word-aligned
    ("bfloat16", EXPERT, 3, 1.0),   # 5.5 MiB at an odd byte
    # The chip smoke's two tensors of random bytes: odd counts, so the
    # view ends off a word, after the loops' blocks and their remainder.
    ("uint16", (2048 * 512 + 1,), 2, 1.05),
    ("uint8", (2048 * 256 + 3,), 0, 1.05),
])
def test_typed_view_from_words(one_chip, dtype, shape, shift, factor):
    """The program ``typed_view`` dispatches for a word buffer: the word
    offsets are traced, so one program serves every tensor of a shape and
    alignment. A 16-bit float is its own size again in temporaries twice
    over: the flatten after the loop, and the last bitcast from uint16;
    an integer view once."""
    import jax.numpy as jnp

    from dragonfly2_tpu.ops import bitview

    dtype = jnp.dtype(dtype)
    arg, out, temp = _memory(
        functools.partial(bitview._views_jit, shift=shift, dtype=dtype,
                          shape=shape),
        _words(one_chip), _starts(1, one_chip))
    size = dtype.itemsize * np.prod(shape)
    assert size <= out < size + 4096 and temp <= factor * out


def _group_memory(sharding, n: int, form: str = "flat", shift: int = 2,
                  shape=EXPERT):
    """(output, temporary) bytes per device, the compiled program and the
    seconds its compile took, of the view program for ``n`` bf16 tensors
    of ``shape`` (the benchmark's routed-expert matrices), starting
    ``shift`` bytes into a word, cut by ``form``."""
    import time

    import jax
    import jax.numpy as jnp

    from dragonfly2_tpu.ops import bitview

    how = {}
    if form == "rows":
        how = {"form": "rows",
               "mesh": getattr(sharding, "mesh", None)}
    began = time.perf_counter()
    compiled = jax.jit(functools.partial(
        bitview._views_jit, shift=shift, dtype=jnp.dtype(jnp.bfloat16),
        shape=shape, **how)).lower(
        _words(sharding, whole=form == "rows"),
        _starts(n, sharding)).compile()
    seconds = time.perf_counter() - began
    m = compiled.memory_analysis()
    return m.output_size_in_bytes, m.temp_size_in_bytes, compiled, seconds


@pytest.mark.parametrize("form", ["flat", "rows"])
@pytest.mark.parametrize("where", ["one_chip", "every_chip"])
def test_a_group_of_views_is_its_members_and_no_more(topo, one_chip, where,
                                                     form):
    """One dispatch for ``_GROUP_CAP`` expert matrices, on one chip and
    on words that lie on every chip: the outputs are the members', the
    temporaries at most the cap times the single view's (129,024 bytes
    flat; by the rows kernel nothing but a tile for each member's start),
    nothing of the size of the content or of the group."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from dragonfly2_tpu.ops import bitview

    sharding = one_chip
    if where == "every_chip":
        sharding = NamedSharding(Mesh(np.array(topo.devices), ("d",)), P())
    cap = bitview._GROUP_CAP
    single_out, single_temp, _, _ = _group_memory(sharding, 1, form)
    out, temp, compiled, _ = _group_memory(sharding, cap, form)
    nbytes = 2 * int(np.prod(EXPERT))
    assert single_out == nbytes and single_temp <= MiB // 4
    assert cap * nbytes <= out <= cap * (nbytes + 64)    # + the tuple's table
    assert temp <= cap * (single_temp + 8192)     # + a member's start, a tile or two
    if where == "every_chip":
        assert all(s.is_fully_replicated for s in compiled.output_shardings)


@pytest.mark.parametrize("shift", [0, 2])
@pytest.mark.parametrize("shape,n", [(EMBED, 1), (EXPERT, None), (DOWN, None)],
                         ids=["embed_tokens", "a_group_of_experts",
                              "a_group_of_down_proj"])
def test_a_view_is_written_once(one_chip, shape, n, shift):
    """The rows kernel over 1.7 GiB of landed pieces: ``embed_tokens``, a
    full group of expert matrices and one of their ``down_proj`` (rows of
    704 words: only a PAIR of them is whole 128-word groups) at both
    alignments a 2-byte tensor of the shard meets. The outputs are the members' and no more; what the
    program holds beside them is under 0.1 GB for the embedding (the flat
    form's two tensor-sized temporaries were 1.34 GB) and nothing a
    member; a member is under 128 KiB of code (1.3 MB flat, which held
    the cap at 8); and the group compiles within the seconds the flat
    form's program of 8 takes here, and within a minute in any case."""
    from dragonfly2_tpu.ops import bitview

    n = n or bitview._GROUP_CAP
    out, temp, compiled, seconds = _group_memory(one_chip, n, "rows", shift,
                                                 shape)
    nbytes = 2 * int(np.prod(shape))
    assert n * nbytes <= out <= n * (nbytes + 64)
    assert temp <= min(100 * 10 ** 6, n * MiB // 4)
    code = compiled.memory_analysis().generated_code_size_in_bytes
    assert code <= n * 128 * 1024 + 64 * 1024
    _, _, _, flat_seconds = _group_memory(one_chip, 8, "flat", shift, EXPERT)
    assert seconds <= max(flat_seconds, 2.0) * 2 and seconds <= 60
    assert flat_seconds <= 60


@pytest.mark.parametrize("dtype,shape,shift", [
    ("float16", (300, 256), 3), ("int16", (4, 64, 512), 1),
    ("uint16", (256, 8192), 2)])
def test_the_rows_kernel_at_the_other_dtypes_and_alignments(one_chip, dtype,
                                                            shape, shift):
    """float16 (which the v5e's vector unit lacks: the kernel leaves it
    unsigned and XLA makes the float), an odd byte, a last step that is
    not whole, leading dimensions, and the widest row the kernel takes:
    accepted, with no temporary."""
    import jax
    import jax.numpy as jnp

    from dragonfly2_tpu.ops import bitview

    m = jax.jit(functools.partial(
        bitview._views_jit, shift=shift, dtype=jnp.dtype(dtype), shape=shape,
        form="rows")).lower(
        _words(one_chip, whole=True),
        _starts(2, one_chip)).compile().memory_analysis()
    nbytes = 2 * int(np.prod(shape))
    assert 2 * nbytes <= m.output_size_in_bytes <= 2 * (nbytes + 4096)
    assert m.temp_size_in_bytes <= MiB // 4


def test_typed_view_from_bytes(one_chip):
    """The hot-swap path's uint8 buffer: the embedding within 2.1x."""
    import jax.numpy as jnp

    from dragonfly2_tpu.ops import bitview

    arg, out, temp = _memory(
        functools.partial(bitview._views_jit, shift=0,
                          dtype=jnp.dtype(jnp.bfloat16), shape=EMBED),
        _spec((CONTENT,), jnp.uint8, one_chip), _starts(1, one_chip))
    assert out == 2 * np.prod(EMBED) and temp <= 2.1 * out


def test_byte_buffers_stop_at_2_gib():
    """The byte programs index with int32; past it they say so instead
    of wrapping."""
    import jax
    import jax.numpy as jnp

    from dragonfly2_tpu.ops import bitview

    big = jax.ShapeDtypeStruct((1 << 31,), jnp.uint8)
    with pytest.raises(ValueError, match="2 GiB"):
        bitview.typed_view(big, 1 << 20, jnp.bfloat16, (4, 4))
    with pytest.raises(ValueError, match="2 GiB"):
        bitview.check_u8_indexable(big)


def test_record_batch_view(one_chip):
    """dataset/device_feed.py lands one record per piece, the piece being
    the record rounded up to a word: 6800 records of 256 KiB less 3."""
    import jax.numpy as jnp

    from dragonfly2_tpu.ops.hbm_sink import _record_batch_jit

    records, piece = 6800, 256 * 1024
    arg, out, temp = _memory(
        functools.partial(_record_batch_jit, count=records, piece_size=piece,
                          record_bytes=piece - 3),
        _spec((records * piece // 4,), jnp.uint32, one_chip))
    assert out >= records * (piece - 3) and temp <= 1.05 * out


@pytest.mark.parametrize("n", [4, 60, 204])
def test_a_short_batch_is_a_slice_of_the_full_view(one_chip, n):
    """The one program an epoch's end may compile (DeviceFeed._land_hbm): the
    first ``n`` rows of the feed cell's (256, 256 KiB) batch, whatever ``n``
    is a multiple of, with no temporary."""
    import jax
    import jax.numpy as jnp

    arg, out, temp = _memory(
        lambda batch: jax.lax.slice_in_dim(batch, 0, n),
        _spec((256, 256 * 1024), jnp.uint8, one_chip))
    assert n * 256 * 1024 <= out <= arg and temp == 0


@pytest.mark.parametrize("piece_mib", [32, 4])
def test_hot_swap_gate_reads_words_in_place(one_chip, piece_mib):
    import jax.numpy as jnp

    from dragonfly2_tpu.ops.hbm_sink import _words_checksums_jit

    arg, out, temp = _memory(
        functools.partial(_words_checksums_jit,
                          piece_words=piece_mib * MiB // 4),
        _spec((PIECES // 4,), jnp.uint32, one_chip))
    assert temp <= 2 * piece_mib * MiB


def test_hot_swap_gate_has_no_2_gib_bound(one_chip):
    """The gate of the word buffer takes ``moonlight-ep4-*``'s 4.68 GB file
    (150 pieces of 32 MiB), which the byte gate refused."""
    import jax.numpy as jnp

    from dragonfly2_tpu.ops.hbm_sink import _words_checksums_jit

    arg, out, temp = _memory(
        functools.partial(_words_checksums_jit, piece_words=8 * MiB),
        _spec((150 * 8 * MiB,), jnp.uint32, one_chip))
    assert arg == 150 * 32 * MiB and temp <= 64 * MiB


@pytest.mark.parametrize("source", ["live", "slab"])
def test_hot_swap_assembly_writes_the_new_words_in_place(one_chip, source):
    """The swap's copy program at the benchmark's shard: the new buffer is
    donated and comes back as it lies (no second content), the source (the
    live generation's words, a 32 MiB staging slab) is read where it is,
    and a step's block is the only temporary."""
    import jax
    import jax.numpy as jnp

    from dragonfly2_tpu.ops import hbm_sink

    words = PIECES // 4
    src = words if source == "live" else hbm_sink._SWAP_SLAB_ROWS * 128
    m = jax.jit(functools.partial(hbm_sink._swap_copy_jit,
                                  block=hbm_sink._SWAP_BLOCK_ROWS),
                donate_argnums=(0,)).lower(
        _spec((words,), jnp.uint32, one_chip),
        _spec((src,), jnp.uint32, one_chip),
        _spec((4096, 4), jnp.int32, one_chip),
        _spec((), jnp.int32, one_chip)).compile().memory_analysis()
    assert m.alias_size_in_bytes == m.output_size_in_bytes == PIECES
    assert m.temp_size_in_bytes <= 4 * MiB


def _mesh_words(topo, n_words: int):
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(topo.devices), ("d",))
    return mesh, _spec((n_words,), jnp.uint32, NamedSharding(mesh, P("d")))


def test_all_gather_on_four_chips(topo):
    from dragonfly2_tpu.parallel.ici import _all_gather_jit

    words = 1728 * MiB // 4
    mesh, spec = _mesh_words(topo, words)
    arg, out, temp = _memory(
        functools.partial(_all_gather_jit, mesh=mesh, axis_name="d"), spec)
    assert arg == words and out == 4 * words     # a quarter in, all out


def test_per_chip_checksums_of_a_replicated_content_need_no_temporary(topo):
    """The verification after the fan-out: every chip reads the copy it
    holds, a piece at a time; per chip the content in, 8 bytes a piece out
    and no content-sized temporary."""
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from dragonfly2_tpu.ops.hbm_sink import _chip_checksums_jit

    pieces, piece_words = 55, 32 * MiB // 4
    mesh = Mesh(np.array(topo.devices), ("d",))
    spec = _spec((pieces * piece_words,), jnp.uint32,
                 NamedSharding(mesh, P()))
    arg, out, temp = _memory(
        functools.partial(_chip_checksums_jit, mesh=mesh, axis_name="d",
                          piece_words=piece_words), spec)
    assert arg == 4 * pieces * piece_words
    assert out <= 4096 and temp <= 4 * MiB     # a tile for 55 x 2 words


@pytest.mark.parametrize("form", ["flat", "rows"])
@pytest.mark.parametrize("dtype,shape", [("bfloat16", EMBED),
                                         ("bfloat16", EXPERT)])
def test_typed_view_of_words_that_lie_on_every_chip(topo, dtype, shape, form):
    """A view cut from replicated words is replicated: the same tensor on
    every chip, at the temporaries the one-chip view has (the rows kernel
    runs on each chip's own copy, under ``shard_map``)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(topo.devices), ("d",))
    out, temp, compiled, _ = _group_memory(NamedSharding(mesh, P()), 1, form,
                                           2, shape)
    assert all(s.is_fully_replicated for s in compiled.output_shardings)
    nbytes = 2 * int(np.prod(shape))
    assert out == nbytes
    assert temp <= (2.05 * nbytes + MiB if form == "flat" else MiB)


# A rank's training state as save_from_device packs it: 71 pieces of 32 MiB.
SAVE_WORDS = 71 * 32 * MiB // 4
SAVE_GROUPS = [
    ("float32", EXPERT, 16, 0, False),    # the optimizer's: a bitcast
    ("bfloat16", EXPERT, 16, 0, False),   # the weights': two items a word
    ("bfloat16", DOWN, 16, 0, False),
    ("bfloat16", (2047,), 1, 2, True),    # begins and ends inside a word
    ("uint8", (1000, 3), 1, 1, True),
]


@pytest.mark.parametrize("dtype,shape,members,lead,merge", SAVE_GROUPS)
def test_save_pack_places_a_group_in_the_donated_words(one_chip, dtype, shape,
                                                       members, lead, merge):
    """The way out's pack program (ops/hbm_source.py): a group of tensors
    placed in the file's words, which are donated and come back the same
    memory, with temporaries of the order of a tensor and never of the
    buffer."""
    import jax.numpy as jnp

    from dragonfly2_tpu.ops.hbm_source import _save_pack_jit

    m = _save_pack_jit.lower(
        _spec((SAVE_WORDS,), jnp.uint32, one_chip),
        _spec((members,), jnp.int32, one_chip),
        tuple(_spec(shape, dtype, one_chip) for _ in range(members)),
        lead=lead, merge=merge).compile().memory_analysis()
    assert m.alias_size_in_bytes == m.output_size_in_bytes == 4 * SAVE_WORDS
    assert m.temp_size_in_bytes <= 2 * int(np.prod(shape)) * 4 + MiB


def test_save_pack_sums_and_group_slice_need_no_temporary(one_chip):
    import jax.numpy as jnp

    from dragonfly2_tpu.ops.hbm_source import (
        _save_group_jit,
        _save_pack_sums_jit,
    )

    words = _spec((SAVE_WORDS,), jnp.uint32, one_chip)
    piece_words = 32 * MiB // 4
    m = _save_pack_sums_jit.lower(
        words, piece_words=piece_words).compile().memory_analysis()
    assert m.output_size_in_bytes <= 4096 and m.temp_size_in_bytes <= 4 * MiB
    m = _save_group_jit.lower(words, _spec((), jnp.int32, one_chip),
                              size=4 * piece_words).compile().memory_analysis()
    assert m.output_size_in_bytes == 128 * MiB and m.temp_size_in_bytes == 0
