"""TPU ops + parallel plans on the virtual 8-device CPU mesh."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from dragonfly2_tpu.ops.checksum import _chunk_checksums_xla, checksum_numpy  # noqa: E402
from dragonfly2_tpu.ops.hbm_sink import HBMSink  # noqa: E402
from dragonfly2_tpu.parallel.ici import all_gather_shards, make_mesh  # noqa: E402
from dragonfly2_tpu.parallel.topology import TpuTopology, detect_topology  # noqa: E402


class TestChecksum:
    def test_numpy_reference(self):
        s, x = checksum_numpy(b"\x01\x00\x00\x00\x02\x00\x00\x00")
        assert s == 3 and x == 3
        s, x = checksum_numpy(b"\xff\xff\xff\xff" * 2)
        assert s == (2 * 0xFFFFFFFF) % (1 << 32)
        assert x == 0

    def test_tail_padding_neutral(self):
        # Trailing zero bytes change nothing (HBM sink tail pieces).
        a = checksum_numpy(b"hello world!")
        b = checksum_numpy(b"hello world!" + b"\x00" * 8)
        assert a == b

    # Lengths in bytes by name: around a word, a word short of and over
    # the sizes a pass is cut at (a cache-sized block, the chunk floor), a
    # sum that passes 2^32 four million times, and many random bits.
    LENGTHS = {
        "0": 0, "1": 1, "3": 3, "4": 4, "5": 5,
        **{f"{kib}KiB{step:+d}": (kib << 10) + step
           for kib in (256, 1024, 2048) for step in (-4, 0, 4)},
        "all-ones": (1 << 24) + 12, "64MiB-random": 64 << 20,
    }

    @pytest.mark.parametrize("name", list(LENGTHS))
    def test_numpy_reference_is_the_uint64_definition(self, name):
        """sum32 added at the word's own width (it wraps) against the
        definition written out: every word widened to 64 bits, summed,
        taken mod 2^32; xor32 against the fold of the same words."""
        length = self.LENGTHS[name]
        if name == "all-ones":
            data = b"\xff" * length
        else:
            data = np.random.default_rng(length).integers(
                0, 256, length, dtype=np.uint8).tobytes()
        wide = np.frombuffer(data + bytes(-length % 4), "<u4").astype(
            np.uint64)
        want = (int(wide.sum() % (1 << 32)),
                int(np.bitwise_xor.reduce(wide)) if wide.size else 0)
        if name == "all-ones":
            assert int(wide.sum()) >> 32 > 4_000_000
        assert checksum_numpy(data) == want

    # The kernel's two reshape branches: whole (sublane, lane) tiles of 128
    # words, and a piece that is not a multiple of 128.
    @pytest.mark.parametrize("piece_words", [256, 200])
    def test_device_matches_numpy(self, piece_words):
        rng = np.random.RandomState(0)
        n = 4
        data = rng.randint(0, 2**32, size=(n * piece_words,),
                           dtype=np.uint64).astype(np.uint32)
        sums, xors = _chunk_checksums_xla(jnp.asarray(data), piece_words)
        for i in range(n):
            piece = data[i * piece_words : (i + 1) * piece_words].tobytes()
            want_s, want_x = checksum_numpy(piece)
            assert int(sums[i]) == want_s
            assert int(xors[i]) == want_x


class TestHBMSink:
    def test_land_verify_roundtrip(self):
        rng = np.random.RandomState(1)
        content = rng.bytes(40_000)  # not piece-aligned → tail piece
        sink = HBMSink(len(content), piece_size=16_384, batch_pieces=2)
        piece = 16_384
        nums = list(range((len(content) + piece - 1) // piece))
        rng.shuffle(nums)
        for n in nums:
            sink.land_piece(n, content[n * piece : (n + 1) * piece])
        assert sink.complete()
        assert sink.verify()
        out = np.asarray(sink.as_bytes_array()).tobytes()
        assert out == content

    def test_corruption_detected(self):
        content = np.random.RandomState(2).bytes(16_384 * 2)
        sink = HBMSink(len(content), piece_size=16_384)
        sink.land_piece(0, content[:16_384])
        # Lie about the host checksum → device verify must catch it.
        sink.host_checksums[0] = (123, 456)
        sink.land_piece(1, content[16_384:])
        with pytest.raises(ValueError, match="piece 0 corrupt"):
            sink.verify()

    def test_as_tensor_bitcast(self):
        vals = np.arange(64, dtype=np.float32)
        content = vals.tobytes()
        sink = HBMSink(len(content), piece_size=64)
        for n in range(len(content) // 64):
            sink.land_piece(n, content[n * 64 : (n + 1) * 64])
        t = sink.as_tensor("float32", (8, 8))
        np.testing.assert_array_equal(np.asarray(t).reshape(-1), vals)

    def test_shard_to_mesh(self):
        mesh = make_mesh(8)
        content = np.random.RandomState(3).bytes(8 * 1024)
        sink = HBMSink(len(content), piece_size=1024)
        for n in range(8):
            sink.land_piece(n, content[n * 1024 : (n + 1) * 1024])
        sharded = sink.shard_to_mesh(mesh)
        assert len(sharded.sharding.device_set) == 8
        np.testing.assert_array_equal(
            np.asarray(sharded), np.frombuffer(content, "<u4"))


class TestICI:
    def test_scatter_then_all_gather(self):
        # Fed as the path feeds it (HBMSink.replicate): the landing's own
        # shard_to_mesh, one shard a device.
        mesh = make_mesh(8)
        data = np.arange(8 * 16, dtype=np.uint32)
        sink = HBMSink(data.nbytes, piece_size=64)
        for n in range(8):
            sink.land_piece(n, data[n * 16:(n + 1) * 16].tobytes())
        sharded = sink.shard_to_mesh(mesh)
        assert len(sharded.sharding.device_set) == 8
        full = all_gather_shards(mesh, sharded)
        assert full.sharding.is_fully_replicated
        np.testing.assert_array_equal(np.asarray(full), data)


class TestTopology:
    def test_env_detection(self, monkeypatch):
        monkeypatch.setenv("DF_TPU_SLICE", "v5p-slice-3")
        monkeypatch.setenv("DF_TPU_WORKER", "7")
        monkeypatch.setenv("DF_TPU_POD", "pod-a")
        monkeypatch.setenv("DF_ZONE", "us-east5-a")
        topo = detect_topology()
        assert topo.present
        assert topo.worker_index == 7
        assert topo.location_path() == "us-east5-a|pod-a|v5p-slice-3|w7"

    def test_apply_to_host_config(self, monkeypatch):
        from dragonfly2_tpu.daemon.config import HostOption
        from dragonfly2_tpu.parallel.topology import apply_to_host_config

        monkeypatch.setenv("DF_TPU_SLICE", "s1")
        monkeypatch.setenv("DF_TPU_WORKER", "2")
        host = HostOption()
        apply_to_host_config(host)
        assert host.tpu_slice == "s1"
        assert host.tpu_worker_index == 2
        assert host.idc == "s1"


def test_graft_entry_single_chip():
    import __graft_entry__

    fn, args = __graft_entry__.entry()
    flat, sums, xors = fn(*args)
    all_pieces = np.concatenate([np.asarray(b) for b in args])
    assert flat.shape[0] == all_pieces.size
    np.testing.assert_array_equal(np.asarray(flat),
                                  all_pieces.reshape(-1))
    # Checksums must match the host reference for each landed piece.
    for i in range(all_pieces.shape[0]):
        want_s, want_x = checksum_numpy(all_pieces[i].tobytes())
        assert int(sums[i]) == want_s
        assert int(xors[i]) == want_x


def test_graft_entry_dryrun_multichip():
    import __graft_entry__

    __graft_entry__.dryrun_multichip(8)


def test_hbm_sink_contiguous_runs(tmp_path):
    """flush() collapses contiguous runs into single copies and scatters
    stragglers; landed content and verification stay correct."""
    import numpy as np

    from dragonfly2_tpu.ops.hbm_sink import HBMSink

    piece_size = 4096
    total = 10 * piece_size
    rng = np.random.RandomState(5)
    blobs = [rng.bytes(piece_size) for _ in range(10)]
    sink = HBMSink(total, piece_size, batch_pieces=100)  # manual flush
    # contiguous run 0..4, straggler 7, run 8..9
    for n in (0, 1, 2, 3, 4, 7, 8, 9):
        sink.land_piece(n, blobs[n])
    sink.flush()
    out = np.asarray(sink.as_bytes_array())
    for n in (0, 1, 2, 3, 4, 7, 8, 9):
        assert out[n * piece_size:(n + 1) * piece_size].tobytes() == blobs[n], n
    # remaining pieces
    sink.land_piece(5, blobs[5])
    sink.land_piece(6, blobs[6])
    assert sink.complete()
    assert sink.verify()
    assert np.asarray(sink.as_bytes_array()).tobytes() == b"".join(blobs)


def test_hbm_sink_rejects_out_of_range_piece():
    """A stray out-of-range piece must raise, not poison a (possibly
    already drained) sink — code-review regression r3."""
    sink = HBMSink(4096, 1024)
    with pytest.raises(ValueError, match="out of range"):
        sink.land_piece(4, b"\x00" * 1024)
    with pytest.raises(ValueError, match="out of range"):
        sink.land_piece(-1, b"\x00" * 1024)


def test_hbm_sink_one_piece_batches_scrambled():
    """Every piece a batch of its own, in a scrambled order (64 operands
    of one row each): content and verification stay exact."""
    rng = np.random.RandomState(9)
    piece = 512
    total_pieces = 64
    content = rng.bytes(piece * total_pieces - 123)  # tail piece
    sink = HBMSink(len(content), piece, batch_pieces=1)
    nums = list(range(total_pieces))
    rng.shuffle(nums)
    for n in nums:
        sink.land_piece(n, content[n * piece:(n + 1) * piece])
    assert sink.complete()
    assert sink.verify()
    assert np.asarray(sink.as_bytes_array()).tobytes() == content


@pytest.mark.parametrize("batch_pieces", [1, 4])
def test_hbm_sink_missing_slots_read_zeros(batch_pieces):
    """Slots that no staged row names are zeros in the content, and their
    device checksums are zero (pad-neutral), whatever the batches were."""
    rng = np.random.RandomState(10)
    piece = 512
    content = rng.bytes(piece * 16)
    sink = HBMSink(len(content), piece, batch_pieces=batch_pieces)
    landed = (0, 3, 5, 11, 2, 9)
    for n in landed:
        sink.land_piece(n, content[n * piece:(n + 1) * piece])
    out = np.asarray(sink.as_bytes_array()).tobytes()
    for n in range(16):
        got = out[n * piece:(n + 1) * piece]
        if n in landed:
            assert got == content[n * piece:(n + 1) * piece], n
            assert (int(sink._dev_sums[n]), int(sink._dev_xors[n])
                    ) == sink.host_checksums[n]
        else:
            assert got == b"\x00" * piece, n
            assert (int(sink._dev_sums[n]), int(sink._dev_xors[n])) == (0, 0)
    assert sink.verify() and not sink.complete()


def test_hbm_sink_consolidates_batches_at_scale():
    """Checkpoint-scale staging (many batches) consolidates into
    superbatches so assembly never compiles a 1000-operand concat —
    content and verification stay exact."""
    rng = np.random.RandomState(11)
    piece = 1024
    n_batches = 80            # > 2 merge groups of 32
    total_pieces = n_batches * 4
    content = rng.bytes(piece * total_pieces - 77)   # tail piece
    sink = HBMSink(len(content), piece, batch_pieces=4)
    for n in range(total_pieces):
        sink.land_piece(n, content[n * piece:(n + 1) * piece])
    # 2 supers (64 batches) + 16 recent fulls.
    assert len(sink._batches) <= 2 + 16
    assert sink.complete()
    assert sink.verify()
    assert np.asarray(sink.as_bytes_array()).tobytes() == content


# -- the reused staging stacks (ops/hbm_sink.py "Host staging") ------------

def four_streams(pieces: int) -> list:
    """Arrival as a seed's four ranged streams give it: 0, 14, 28, 42, 1,
    15, ... for 55 pieces."""
    per = -(-pieces // 4)
    return [s * per + i for i in range(per) for s in range(4)
            if s * per + i < pieces]


ARRIVALS = {"in-order": list(range(14)), "reversed": list(range(13, -1, -1)),
            "four-streams": four_streams(14)}


@pytest.fixture
def staging(monkeypatch):
    """A free list of the test's own, so that which stack comes back does
    not depend on what earlier tests of this worker left in the
    process's."""
    from dragonfly2_tpu.ops import hbm_sink
    from dragonfly2_tpu.pkg.bufpool import BufferPool

    pool = BufferPool(1 << 30, name="hbm_stage")
    monkeypatch.setattr(hbm_sink, "_STAGING", pool)
    return pool


def read_into_rows(sink, content: bytes, order) -> None:
    """Land as the daemon does: each piece written into the sink's own
    row, and that row handed over."""
    piece = sink.piece_size
    for n in order:
        data = content[n * piece:(n + 1) * piece]
        row = sink.next_row()
        row[:len(data)] = data
        sink.land_piece(n, row[:len(data)])


def verified_landing(content: bytes, piece: int, batch: int, order) -> tuple:
    """Land ``order`` as the daemon does and verify; the sink, and how
    many programs the assembly compiled on this thread."""
    from dragonfly2_tpu.ops import hbm_sink

    sink = HBMSink(len(content), piece, batch_pieces=batch)
    read_into_rows(sink, content, order)
    sink.flush()
    # The rows lie as they arrived, on the host and on the device.
    assert [int(n) for slots, _ in sink._batches for n in slots] == list(order)
    before, _ = hbm_sink.compiled()
    assert sink.verify()
    return sink, hbm_sink.compiled()[0] - before


@pytest.mark.parametrize("arrival", list(ARRIVALS))
def test_rows_land_in_arrival_order_and_every_order_is_one_program(
        staging, fresh_compiles, arrival):
    hbm_sink = fresh_compiles
    order = ARRIVALS[arrival]
    piece, batch = 4096, 4
    content = np.random.RandomState(21).bytes(piece * len(order) - 1001)
    in_place = hbm_sink._ROWS_IN_PLACE._value.get()
    copied = hbm_sink._ROWS_COPIED._value.get()
    assemblies = {how: hbm_sink.SINK_ASSEMBLIES.labels(how)._value.get()
                  for how in ("compiled", "cached")}
    sink, compiles = verified_landing(content, piece, batch, order)
    assert np.asarray(sink.as_bytes_array()).tobytes() == content
    assert hbm_sink._ROWS_IN_PLACE._value.get() - in_place == len(order)
    assert hbm_sink._ROWS_COPIED._value.get() == copied
    # Every stack is back, and the landing never held more than two.
    stats = staging.stats()
    assert stats["outstanding"] == 0 and 1 <= stats["free_buffers"] <= 2
    # The other orders of the same geometry run the program this one
    # compiled: the order is its argument.
    for other in ARRIVALS.values():
        again, more = verified_landing(content, piece, batch, other)
        assert np.asarray(again.as_bytes_array()).tobytes() == content
        compiles += more
    assert compiles == 1
    assert hbm_sink._assemble_checksum_jit._cache_size() == 1
    moved = {how: hbm_sink.SINK_ASSEMBLIES.labels(how)._value.get() - was
             for how, was in assemblies.items()}
    assert moved == {"compiled": 1, "cached": len(ARRIVALS)}


RANDOM_ORDERS = 20


def test_twenty_random_orders_of_one_geometry_are_one_program(
        staging, fresh_compiles):
    """55 pieces in batches of 8 (8,8,8,8,8,8,7, a shard's geometry at a
    toy piece size), the last piece short: one compile in all, content
    bit-exact and verified for every order."""
    hbm_sink = fresh_compiles
    piece, batch, pieces = 1024, 8, 55
    content = np.random.RandomState(26).bytes(piece * pieces - 333)
    compiles = 0
    for seed in range(RANDOM_ORDERS):
        order = list(np.random.RandomState(seed).permutation(pieces))
        sink, more = verified_landing(content, piece, batch, order)
        compiles += more
        words = np.asarray(sink.as_words()).tobytes()
        assert words[:len(content)] == content, seed
        assert not words[len(content):].strip(b"\x00")
        assert sink.host_checksums[pieces - 1] == checksum_numpy(
            content[(pieces - 1) * piece:])
    assert compiles == 1
    assert hbm_sink._assemble_checksum_jit._cache_size() == 1


@pytest.mark.parametrize("arrival", list(ARRIVALS))
def test_a_flipped_bit_in_a_staged_row_names_its_piece(staging, arrival):
    """The checksums fold from the staged device copy: a row that changed
    on the device after its host checksum was taken fails verification
    under the name of the slot it belongs to, wherever it lies."""
    order = ARRIVALS[arrival]
    piece, batch = 4096, 4
    content = np.random.RandomState(27).bytes(piece * len(order))
    sink = HBMSink(len(content), piece, batch_pieces=batch)
    read_into_rows(sink, content, order)
    sink.flush()
    slots, staged = sink._batches[1]
    bad = staged.at[2].set(staged[2] ^ jnp.uint32(1 << 7))
    sink._batches[1] = (slots, bad)
    with pytest.raises(ValueError, match=f"piece {int(slots[2])} corrupt"):
        sink.verify()


def test_a_short_last_piece_in_a_dirty_stack_reads_zero_padded(staging):
    """A reused stack is not fresh zeros: past a short, non-word-aligned
    last piece lies what an earlier task left there."""
    piece, batch = 4096, 4
    dirty = HBMSink(piece * batch, piece, batch_pieces=batch)
    for n in range(batch):
        dirty.land_piece(n, b"\xff" * piece)
    assert dirty.verify()
    assert staging.stats()["free_buffers"] == 1
    content = np.random.RandomState(22).bytes(piece * 3 + 1001)
    def hand_over_bytes(sink, content: bytes, order) -> None:
        for n in order:
            sink.land_piece(n, content[n * piece:(n + 1) * piece])

    for land in (read_into_rows, hand_over_bytes):
        sink = HBMSink(len(content), piece, batch_pieces=batch)
        land(sink, content, [3, 0, 1, 2])
        assert sink.verify()
        words = np.asarray(sink.as_words())
        assert words.tobytes()[:len(content)] == content
        assert not words.tobytes()[len(content):].strip(b"\x00")
        assert len(words) == batch * piece // 4
        assert sink.host_checksums[3] == checksum_numpy(content[3 * piece:])
    assert staging.stats()["free_buffers"] == 1      # the same stack, thrice


def _passes() -> dict:
    from dragonfly2_tpu.ops import hbm_sink

    return {kind: hbm_sink.SINK_PASSES.labels(kind)._value.get()
            for kind in ("fused", "checksum")}


def _reads(content: bytes, piece: int, n: int):
    """``read_into`` of a run of piece ``n`` of ``content`` alone, as a
    store would give it, and the piece's size."""
    data = content[n * piece:(n + 1) * piece]

    def read_into(_, row, start, stop):
        row[start:stop] = np.frombuffer(data[start:stop], np.uint8)

    return read_into, len(data)


# A sink of 64 KiB pieces under a chunk floor of 16 KiB: the sizes of a last
# piece that ``cuts`` makes 1, 2, 3 and 4 ranges of, whole words and not,
# one of them ending a byte past a cut and one a byte before.
LAST_PIECES = {"whole-4-cuts": 64 * 1024, "4-cuts-odd": 64 * 1024 - 3,
               "3-cuts-odd": 48 * 1024 + 1, "a-byte-before-a-cut": 36863,
               "a-byte-past-a-cut": 36865, "2-cuts": 35_535,
               "1-cut-odd": 30_001, "1-byte": 1}


@pytest.mark.parametrize("name", list(LAST_PIECES))
def test_the_fused_pass_checksums_the_padded_row_however_it_is_cut(
        staging, monkeypatch, name):
    """``read_pieces`` of one piece in a reused stack (an earlier task's 0xff
    everywhere): what it leaves for ``land_piece`` is ``checksum_numpy`` of
    the piece padded to whole words, the pad zeroed before the last range
    was summed; the device agrees, and the helpers were handed one pass."""
    from dragonfly2_tpu.ops import hbm_sink

    monkeypatch.setattr(hbm_sink, "_CHUNK_FLOOR", 16 * 1024)
    piece, batch = 64 * 1024, 2
    dirty = HBMSink(piece * batch, piece, batch_pieces=batch)
    for n in range(batch):
        dirty.land_piece(n, b"\xff" * piece)
    assert dirty.verify()
    last = LAST_PIECES[name]
    content = np.random.RandomState(last % 1000).bytes(piece + last)
    sink = HBMSink(len(content), piece, batch_pieces=batch)
    before = _passes()
    for n in (1, 0):
        read_into, size = _reads(content, piece, n)
        (data,) = sink.read_pieces([(n, size)], read_into)
        assert len(data) == size
        sink.land_piece(n, data)
    assert staging.stats()["free_buffers"] == 0      # the dirty stack, again
    assert sink.host_checksums[1] == checksum_numpy(content[piece:])
    assert sink.host_checksums[0] == checksum_numpy(content[:piece])
    assert len(hbm_sink.cuts(last)) == min(4, max(1, last // (16 * 1024)))
    after = _passes()
    assert (after["fused"] - before["fused"],
            after["checksum"] - before["checksum"]) == (2, 0)
    assert sink.verify()
    words = np.asarray(sink.as_words()).tobytes()
    assert words[:len(content)] == content
    assert not words[len(content):].strip(b"\x00")


@pytest.mark.parametrize("how", ["another-piece", "the-same-piece-altered",
                                 "the-same-row-shorter"])
def test_bytes_that_are_not_the_read_are_checksummed_in_the_row(staging, how):
    """After ``read_pieces`` of piece 3, ``land_piece`` is handed something
    else: another piece's bytes, the benchmark's control (piece 3's bytes
    with a bit flipped, a copy), or less of the row. The host checksum is
    taken from the row as it is put, never the reading left for piece 3."""
    piece, batch = 4096, 4
    content = np.random.RandomState(31).bytes(piece * 6)
    sink = HBMSink(len(content), piece, batch_pieces=batch)
    read_into, size = _reads(content, piece, 3)
    (data,) = sink.read_pieces([(3, size)], read_into)
    before, reading = _passes(), checksum_numpy(bytes(data))
    if how == "another-piece":
        num, given = 5, content[5 * piece:]
    elif how == "the-same-piece-altered":
        altered = bytearray(data)
        altered[len(altered) // 3] ^= 0x10
        num, given = 3, bytes(altered)
    else:
        num, given = 3, data[:size - 8]
    sink.land_piece(num, given)
    assert sink.host_checksums == {num: checksum_numpy(bytes(given))}
    assert sink.host_checksums[num] != reading
    assert _passes()["checksum"] - before["checksum"] == 1
    # And the reading is gone: the same row again is a pass of its own.
    read_into, size = _reads(content, piece, 0)
    row = sink.next_row()
    read_into(0, np.frombuffer(row, np.uint8), 0, size)
    sink.land_piece(0, row[:size])
    assert sink.host_checksums[0] == checksum_numpy(content[:piece])
    assert _passes()["checksum"] - before["checksum"] == 2
    sink.flush()
    assert sink.verify()
    landed = np.asarray(sink.as_words()).tobytes()
    assert landed[num * piece:num * piece + len(given)] == bytes(given)


def _group_reads(content: bytes, piece: int, nums):
    """``read_into`` of a group of pieces ``nums`` of ``content``, as a
    store would give it, the ``(piece, size)`` pairs, and the ranges it
    was asked for."""
    asked = []

    def read_into(i, row, start, stop):
        asked.append((nums[i], start, stop))
        at = nums[i] * piece
        row[start:stop] = np.frombuffer(content[at + start:at + stop],
                                        np.uint8)

    sizes = [len(content[n * piece:(n + 1) * piece]) for n in nums]
    return read_into, list(zip(nums, sizes)), asked


@pytest.mark.parametrize("how", ["as-read", "a-landed-piece-again",
                                 "one-altered"])
def test_a_groups_readings_go_to_its_rows_in_order_and_to_nothing_else(
        staging, how):
    """``read_pieces`` of pieces 4, 1, 5 into three rows, then
    ``land_piece`` of each: handed exactly what was returned, in that
    order, every piece takes the pass's checksum and no other pass runs.
    Anything else between them (a piece that has landed already, a copy
    with a bit flipped) costs the piece at fault, and every piece after
    it, a checksum of its row as it is put: no reading outlives a row it
    may not describe."""
    piece, batch = 4096, 4
    content = np.random.RandomState(37).bytes(piece * 6 - 1_001)
    sink = HBMSink(len(content), piece, batch_pieces=batch)
    sink.land_piece(0, content[:piece])
    assert sink.free_rows() == 3
    nums = [4, 1, 5]
    read_into, pieces, asked = _group_reads(content, piece, nums)
    rows = sink.read_pieces(pieces, read_into)
    assert [len(row) for row in rows] == [piece, piece, piece - 1_001]
    # Every piece's bytes once, no range across two pieces.
    assert sorted(asked) == [(1, 0, piece), (4, 0, piece),
                             (5, 0, piece - 1_001)]
    before = _passes()
    if how == "a-landed-piece-again":
        sink.land_piece(0, content[:piece])     # dropped, and the readings
        again = 3
    elif how == "one-altered":
        altered = bytearray(rows[1])
        altered[7] ^= 0x10
        rows[1] = bytes(altered)
        again = 2
    else:
        again = 0
    given = {n: bytes(row) for n, row in zip(nums, rows)}
    for n, row in zip(nums, rows):
        sink.land_piece(n, row)
    assert sink.host_checksums == {
        0: checksum_numpy(content[:piece]),
        **{n: checksum_numpy(data) for n, data in given.items()}}
    assert _passes()["checksum"] - before["checksum"] == again
    assert sink.free_rows() == batch            # the stack was put
    with pytest.raises(ValueError, match="5 pieces into a stack with 4"):
        sink.read_pieces([(n, 1) for n in range(5)], read_into)
    with pytest.raises(ValueError, match="of 4097 bytes"):
        sink.read_pieces([(2, piece + 1)], read_into)
    assert sink.verify()
    landed = np.asarray(sink.as_words()).tobytes()
    for n, data in given.items():
        assert landed[n * piece:n * piece + len(data)] == data, n


def test_a_second_landing_takes_its_stacks_from_the_free_list(staging):
    from dragonfly2_tpu.pkg import bufpool

    def acquires() -> tuple:
        return tuple(bufpool.BUFPOOL_ACQUIRES.labels("hbm_stage", source)
                     ._value.get() for source in ("fresh", "pooled"))

    piece, batch, pieces = 4096, 4, 14
    content = np.random.RandomState(23).bytes(piece * pieces)
    counts = [acquires()]
    for _ in range(2):
        sink = HBMSink(len(content), piece, batch_pieces=batch)
        read_into_rows(sink, content, four_streams(pieces))
        assert sink.verify()
        counts.append(acquires())
    (f0, p0), (f1, p1), (f2, p2) = counts
    assert 1 <= f1 - f0 <= 2 and (f1 - f0) + (p1 - p0) == 4
    assert f2 == f1 and p2 - p1 == 4


def test_no_staged_batch_aliases_a_stack_that_went_back(monkeypatch):
    """On the CPU backend ``device_put`` of a 64-byte-aligned host array
    copies nothing: the device array is the host memory, for its whole
    life. A stack that went back to the free list while such an array
    lived would change under it."""
    from dragonfly2_tpu.ops import hbm_sink
    from dragonfly2_tpu.pkg.bufpool import BufferPool

    def address(buf) -> int:
        return np.frombuffer(buf, np.uint8).__array_interface__["data"][0]

    class Aligned(BufferPool):
        """Hands out 64-byte-aligned views, and looks at every sink's
        staged batches when one comes back."""

        def acquire(self, size: int) -> memoryview:
            view = super().acquire(size + 64)
            skip = -address(view) % 64
            return view[skip:skip + size]

        def release(self, view) -> None:
            lo = address(view)
            for sink in sinks:
                for _, staged in sink._batches:
                    at = staged.unsafe_buffer_pointer()
                    assert not lo <= at < lo + len(view), "aliased"
            returned.append(lo)
            super().release(view)

    sinks, returned = [], []
    pool = Aligned(1 << 30, name="hbm_stage")
    monkeypatch.setattr(hbm_sink, "_STAGING", pool)
    probe = pool.acquire(4 * 4096)
    words = np.frombuffer(probe, np.uint32).reshape(4, -1)
    if jax.device_put(words).unsafe_buffer_pointer() != address(probe):
        pytest.skip("this backend copies on device_put: nothing to alias")
    pool.release(probe)
    returned.clear()

    piece, batch, pieces = 4096, 4, 22
    content = np.random.RandomState(24).bytes(piece * pieces - 3)
    sink = HBMSink(len(content), piece, batch_pieces=batch)
    sinks.append(sink)
    read_into_rows(sink, content, four_streams(pieces))
    # Six stacks were filled from two buffers: some went back mid-landing,
    # with earlier batches still staged on the device.
    assert len(returned) >= 4 and len(set(returned)) <= 2
    assert sink.verify()
    assert np.asarray(sink.as_bytes_array()).tobytes() == content
    assert pool.stats()["outstanding"] == 0


def test_a_sink_dropped_mid_batch_gives_its_stacks_back(staging):
    piece, batch = 4096, 4
    content = np.random.RandomState(25).bytes(piece * 14)
    sink = HBMSink(len(content), piece, batch_pieces=batch)
    read_into_rows(sink, content, range(6))     # one stack put, one open
    sink.next_row()                             # and a row handed out
    held = staging.stats()["outstanding"]
    assert held in (1, 2)           # the put stack may be back already
    del sink
    assert staging.stats()["outstanding"] == 0
    assert staging.stats()["free_buffers"] == held
    with pytest.raises(ValueError, match="4-byte aligned"):
        HBMSink(10, 6)                          # nothing taken, nothing owed
    assert staging.stats()["outstanding"] == 0


def test_a_piece_larger_than_the_sinks_pieces_is_refused(staging):
    sink = HBMSink(4096 * 2, 4096)
    with pytest.raises(ValueError, match="4097 bytes"):
        sink.land_piece(0, bytes(4097))
    sink.land_piece(0, bytes(4096))
    assert sink.landed == {0}


class TestMultihostAssembly:
    """parallel/multihost.py on the virtual 8-device mesh: the seam from
    per-host fabric landings to one pod-global jax.Array (single-process
    here; make_array_from_single_device_arrays spans processes on a pod)."""

    def test_global_replicated_roundtrip(self):
        import numpy as np

        from dragonfly2_tpu.parallel import multihost

        mesh = multihost.global_mesh()
        content = np.arange(4096, dtype=np.uint32)
        arr = multihost.global_replicated(mesh, content)
        assert arr.shape == content.shape
        assert arr.sharding.is_fully_replicated
        np.testing.assert_array_equal(np.asarray(arr), content)

    def test_global_from_local_shards(self):
        import numpy as np

        from dragonfly2_tpu.parallel import multihost

        mesh = multihost.global_mesh()
        local = np.arange(8 * 16, dtype=np.float32).reshape(8 * 2, 8)
        arr = multihost.global_from_local_shards(mesh, local)
        assert arr.shape == local.shape  # single process: global == local
        np.testing.assert_array_equal(np.asarray(arr), local)
        # downstream consumers can re-shard without surprises
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        out = jax.jit(lambda x: x * 2,
                      out_shardings=NamedSharding(mesh, P()))(arr)
        np.testing.assert_array_equal(np.asarray(out), local * 2)

    def test_factored_mesh_and_validation(self):
        import pytest as _pytest

        from dragonfly2_tpu.parallel import multihost

        mesh = multihost.global_mesh({"dp": 2, "tp": 4})
        assert mesh.shape == {"dp": 2, "tp": 4}
        with _pytest.raises(ValueError):
            multihost.global_mesh({"dp": 3})

    def test_initialize_single_process_noop(self):
        from dragonfly2_tpu.parallel import multihost

        # Single-process runtime: must be a no-op, not an error.
        multihost.initialize_distributed()
        multihost.initialize_distributed()

    def test_global_from_local_shards_factored_mesh(self):
        """P(axis) on a factored mesh: the other axis holds replicated
        copies — the assembly must not try to split rows across it."""
        import numpy as np

        import jax
        from dragonfly2_tpu.parallel import multihost
        from jax.sharding import NamedSharding, PartitionSpec as P

        mesh = multihost.global_mesh({"dp": 2, "tp": 4})
        local = np.arange(8 * 4, dtype=np.float32).reshape(8, 4)
        arr = multihost.global_from_local_shards(mesh, local, axis_name="dp")
        assert arr.shape == local.shape
        np.testing.assert_array_equal(np.asarray(arr), local)
        out = jax.jit(lambda x: x + 1,
                      out_shardings=NamedSharding(mesh, P()))(arr)
        np.testing.assert_array_equal(np.asarray(out), local + 1)


def test_compile_cache_is_placed_from_outside_or_in_the_checkout(monkeypatch):
    import os

    from dragonfly2_tpu.ops import compile_cache

    before = (jax.config.jax_compilation_cache_dir,
              jax.config.jax_persistent_cache_min_compile_time_secs)
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
        assert compile_cache.place_compile_cache() == "/somewhere/else"
        assert jax.config.jax_compilation_cache_dir == before[0]
        # Sub-second view programs are kept wherever the cache is.
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        placed = compile_cache.place_compile_cache()
        assert placed == os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), ".jax_cache")
        assert placed == compile_cache.place_compile_cache()
        assert jax.config.jax_compilation_cache_dir == placed
    finally:
        jax.config.update("jax_compilation_cache_dir", before[0])
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          before[1])
