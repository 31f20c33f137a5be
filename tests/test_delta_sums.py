"""The flip gate's host sums, carried from a piece's commit to the gate.

A delta landing's piece job takes ``checksum_numpy`` of the slice it writes
and the store keeps the pair with the piece, in memory, tied to the bytes;
``client.device._host_piece_checksums`` hands the gate the carried pairs and
walks the store only for the pieces that carry none. The gate itself trusts
no one: a carried pair that is wrong refuses the flip.
"""

from __future__ import annotations

import hashlib
import os

import numpy as np
import pytest

from dragonfly2_tpu.ops.checksum import checksum_numpy
from tests.test_delta import (
    JOB_PIECE,
    _drain_task,
    _file_req,
    _job_versions,
    _make_safetensors,
    _two_blob_origin,
    _with_job_rig,
)


def _sums_by_reading(store) -> dict:
    """The reference: every piece read back from the store as ``bytes`` and
    summed, the last word padded with zeros as the device's buffer is."""
    out = {}
    for rec in store.get_pieces():
        raw = store.read_piece(rec.num)
        out[rec.num] = checksum_numpy(raw + bytes(-len(raw) % 4))
    return out


def _host_sums(store):
    from dragonfly2_tpu.client.device import _host_piece_checksums

    return _host_piece_checksums(store)


def _sums_counted() -> dict:
    from dragonfly2_tpu.ops import hbm_sink

    return {how: hbm_sink.SWAP_HOST_SUMS.labels(how)._value.get()
            for how in ("carried", "walked")}


@pytest.mark.parametrize("tail", [0, 1, 2, 3])
def test_checksum_numpy_pads_the_last_word_without_a_copy(tail):
    """Any bytes-like, a pooled buffer's view included: the tail of 1-3
    bytes is one more word, its high bytes zero."""
    raw = os.urandom(4096 + tail)
    want = checksum_numpy(raw + bytes(-len(raw) % 4))
    words = np.frombuffer(raw + bytes(-len(raw) % 4), "<u4")
    assert want == (int(words.sum(dtype=np.uint64)) & 0xFFFFFFFF,
                    int(np.bitwise_xor.reduce(words)))
    for form in (raw, bytearray(raw), memoryview(bytearray(raw)),
                 np.frombuffer(raw, np.uint8)):
        assert checksum_numpy(form) == want
    assert checksum_numpy(raw[:tail]) == (
        int.from_bytes(raw[:tail], "little"),) * 2


@pytest.mark.parametrize("tail", [0, 1, 2, 3])
def test_every_piece_of_a_landed_delta_carries_its_sums(run_async, tmp_path,
                                                        tail):
    """The carried pair of every piece equals ``checksum_numpy`` of the
    piece read back, the short last piece (no whole words when ``tail``)
    included; the gate's dictionary is the carried one and the counter says
    so."""
    v1, v2 = _job_versions((1 << 20) + 4096 + tail)

    async def body(rig):
        await rig.land()
        assert rig.landed() == v2
        last = rig.store.piece(rig.pieces - 1)
        assert last.size == 4096 + tail
        carried = rig.store.word_sums()
        assert sorted(carried) == list(range(rig.pieces))
        assert carried == _sums_by_reading(rig.store)
        before = _sums_counted()
        assert _host_sums(rig.store) == (carried, rig.pieces)
        after = _sums_counted()
        assert after["carried"] - before["carried"] == rig.pieces
        assert after["walked"] == before["walked"]

    _with_job_rig(run_async, tmp_path, body, v1, v2)


def test_repaired_piece_carries_its_sums_too(run_async, tmp_path):
    """One base chunk rots on disk: the job hands it back, the coroutine
    re-fetches it and commits the piece from a thread of its own, which
    sums what it writes as the job would have. Every pair is carried and
    right."""
    v1 = os.urandom((1 << 20) + 2)
    v2 = bytearray(v1)
    v2[900_000:910_000] = os.urandom(10_000)
    v2 = bytes(v2)

    async def body(rig):
        with open(rig.base_store.data_path, "r+b") as f:
            f.seek(300_000)
            f.write(bytes(x ^ 0xFF for x in v1[300_000:300_016]))
        st = await rig.land()
        assert st["corrupt_base"] == 1
        assert rig.landed() == v2
        sums, carried = _host_sums(rig.store)
        assert carried == rig.pieces == len(sums)
        assert sums == _sums_by_reading(rig.store)

    _with_job_rig(run_async, tmp_path, body, v1, v2)


def test_store_ties_the_sums_to_the_bytes(tmp_path):
    """A pair is kept by the commit that brought it, replaced by one that
    brings another, dropped by a re-record that brings none, by
    ``mark_invalid`` and by ``destroy``; other writers never make one."""
    from dragonfly2_tpu.storage.local_store import (
        LocalTaskStore,
        TaskStoreMetadata,
    )

    store = LocalTaskStore.create(str(tmp_path / "t"), TaskStoreMetadata(
        task_id="t" * 64, piece_size=4096))
    a, b = os.urandom(4096), os.urandom(4096)
    store.write_piece(0, a, word_sums=checksum_numpy(a))
    store.write_piece(1, b)
    store.write_piece_chunks(2, [a[:100], a[100:]])
    assert store.word_sums() == {0: checksum_numpy(a)}
    store.write_piece(0, b, word_sums=checksum_numpy(b))
    assert store.word_sums() == {0: checksum_numpy(b)}
    store.write_piece(0, a)
    assert store.word_sums() == {}
    store.write_piece(1, b, word_sums=checksum_numpy(b))
    # A copy: the caller's to keep, not the store's own dictionary.
    store.word_sums().clear()
    assert store.word_sums() == {1: checksum_numpy(b)}
    store.mark_invalid()
    assert store.word_sums() == {}
    store.write_piece(1, b, word_sums=checksum_numpy(b))
    store.destroy()
    assert store.word_sums() == {}


def test_commits_from_many_threads_keep_every_pair_with_its_bytes(tmp_path):
    """More committing threads than cores, each re-recording pieces of its
    own with and without sums under a shortened switch interval: when they
    are done the store holds exactly the pairs of the pieces whose LAST
    commit brought one, each the sums of the bytes that lie there."""
    import sys
    import threading

    from dragonfly2_tpu.storage.local_store import (
        LocalTaskStore,
        TaskStoreMetadata,
    )

    store = LocalTaskStore.create(str(tmp_path / "t"), TaskStoreMetadata(
        task_id="s" * 64, piece_size=1024))
    workers, each, rounds = 4 * (os.cpu_count() or 4), 4, 25
    last: dict[int, bytes | None] = {}

    def work(k: int):
        for r in range(rounds):
            for num in range(k * each, (k + 1) * each):
                data = os.urandom(1024)
                summed = (r + num) % 3 != 0
                store.write_piece(num, data, word_sums=checksum_numpy(data)
                                  if summed else None)
                last[num] = data if summed else None
                store.word_sums()

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=work, args=(k,))
                   for k in range(workers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(interval)
    assert len(last) == workers * each
    assert store.word_sums() == {num: checksum_numpy(data)
                                 for num, data in last.items()
                                 if data is not None}
    for num, pair in store.word_sums().items():
        assert checksum_numpy(store.read_piece(num)) == pair
    store.destroy()


def test_store_opened_again_from_disk_is_walked(run_async, tmp_path):
    """The pairs are never persisted: the same landing read back from disk
    (a restarted daemon) carries none, and the walk gives the gate the same
    dictionary."""
    from dragonfly2_tpu.storage.local_store import LocalTaskStore

    v1, v2 = _job_versions((1 << 20) + 6)

    async def body(rig):
        await rig.land()
        carried, n = _host_sums(rig.store)
        assert n == rig.pieces
        again = LocalTaskStore.load(rig.store.dir)
        try:
            assert again.metadata.done and again.word_sums() == {}
            before = _sums_counted()
            assert _host_sums(again) == (carried, 0)
            after = _sums_counted()
            assert after["walked"] - before["walked"] == rig.pieces
            assert after["carried"] == before["carried"]
        finally:
            again.close()

    _with_job_rig(run_async, tmp_path, body, v1, v2)


def test_resumed_landing_walks_only_the_pieces_it_skipped(run_async,
                                                          tmp_path):
    """Pieces the store already had when the landing began were written by
    someone who took no sums: their jobs are skipped, they carry none, and
    the gate walks exactly those; the dictionary is the whole landing's."""
    v1, v2 = _job_versions((1 << 20) + 3)

    async def body(rig):
        had = [1, 5, rig.pieces - 1]
        rig.store.update_task(content_length=len(v2),
                              total_piece_count=rig.pieces)
        for num in had:
            rig.store.write_piece(
                num, v2[num * JOB_PIECE:(num + 1) * JOB_PIECE])
        await rig.land()
        assert rig.landed() == v2
        assert sorted(rig.store.word_sums()) == [
            n for n in range(rig.pieces) if n not in had]
        reads = []
        read_into = rig.store.read_into

        def counted(offset, length, buf, at=0):
            reads.append(offset // JOB_PIECE)
            return read_into(offset, length, buf, at=at)

        rig.store.read_into = counted
        try:
            sums, carried = _host_sums(rig.store)
        finally:
            del rig.store.read_into
        assert reads == had and carried == rig.pieces - len(had)
        assert sums == _sums_by_reading(rig.store)

    _with_job_rig(run_async, tmp_path, body, v1, v2)


def test_all_carried_reads_nothing_and_takes_no_buffer(run_async, tmp_path,
                                                       monkeypatch):
    """With every pair carried the gate's host side touches neither the
    store's bytes nor the buffer pool."""
    from dragonfly2_tpu.storage import local_store

    v1, v2 = _job_versions()

    async def body(rig):
        await rig.land()
        want = _sums_by_reading(rig.store)

        def refuse(*args, **kwargs):
            raise AssertionError("the gate read the landing again")

        monkeypatch.setattr(rig.store, "read_into", refuse)
        monkeypatch.setattr(local_store, "acquire_read_buffer", refuse)
        assert _host_sums(rig.store) == (want, rig.pieces)

    _with_job_rig(run_async, tmp_path, body, v1, v2)


@pytest.mark.parametrize("which", ["sum", "xor"])
def test_gate_refuses_a_wrong_carried_pair(run_async, tmp_path, which):
    """The gate trusts no one: the device's sums of the new buffer are
    compared with the host's for every piece, and a carried pair that is
    wrong by one bit raises ``SwapVerifyError`` naming the piece, where the
    honest pairs pass."""
    import jax.numpy as jnp

    from dragonfly2_tpu.ops.hbm_sink import (
        SwapVerifyError,
        verify_words_against_host,
    )

    v1, v2 = _job_versions((1 << 20) + 2)

    async def body(rig):
        await rig.land()
        words = jnp.asarray(np.frombuffer(
            v2 + bytes(rig.pieces * JOB_PIECE - len(v2)), "<u4"))
        sums, carried = _host_sums(rig.store)
        assert carried == rig.pieces
        verify_words_against_host(words, JOB_PIECE, sums)
        for num in (0, rig.pieces - 1):
            s, x = sums[num]
            rig.store._word_sums[num] = (
                (s ^ 1, x) if which == "sum" else (s, x ^ (1 << 31)))
            lying, _ = _host_sums(rig.store)
            with pytest.raises(SwapVerifyError, match=f"piece {num} "):
                verify_words_against_host(words, JOB_PIECE, lying)
            rig.store._word_sums[num] = (s, x)

    _with_job_rig(run_async, tmp_path, body, v1, v2)


@pytest.mark.parametrize("lying", [False, True], ids=["honest", "lying"])
def test_download_delta_gate_on_carried_sums(run_async, tmp_path,
                                             monkeypatch, lying):
    """The whole chain on the CPU backend: a swap whose landing carried
    every pair flips and says so (``stats["host_sums_carried"]``, the
    ``swap_verify`` span's note, the counter); one whose jobs carried wrong
    pairs is refused by the gate, and the old generation stays live."""
    from dragonfly2_tpu.client import device as device_lib
    from dragonfly2_tpu.delta import resolver
    from dragonfly2_tpu.delta.chunker import CDCParams
    from dragonfly2_tpu.ops.hbm_sink import DoubleBuffer
    from dragonfly2_tpu.pkg import flight as flightlib
    from dragonfly2_tpu.pkg.errors import DfError
    from tests import test_p2p_e2e as e2e
    from tests.test_device_sink import _start_sink_daemon

    rng = np.random.RandomState(3)
    tensors_v1 = {"w1": rng.randn(256, 256).astype(np.float32),
                  "bias": rng.randn(515).astype(np.float32)}
    tensors_v2 = {k: v.copy() for k, v in tensors_v1.items()}
    tensors_v2["bias"][7] += 1.0
    v1, v2 = _make_safetensors(tensors_v1), _make_safetensors(tensors_v2)
    sha1 = "sha256:" + hashlib.sha256(v1).hexdigest()
    sha2 = "sha256:" + hashlib.sha256(v2).hexdigest()
    params = CDCParams(mask_bits=12, min_size=2 << 10, max_size=32 << 10)
    if lying:
        monkeypatch.setattr(
            resolver, "checksum_numpy",
            lambda piece: (checksum_numpy(piece)[0] ^ 1, 0))

    async def body():
        origin, base_url, _stats = await _two_blob_origin(v1, v2)
        sched = await e2e.start_scheduler()
        daemons = []
        try:
            seed = await e2e.start_daemon(tmp_path, "seeds", sched.port(),
                                         seed=True)
            pod = await _start_sink_daemon(tmp_path, "pods", sched.port())
            daemons += [seed, pod]
            r1 = await _drain_task(seed.task_manager,
                                   _file_req(f"{base_url}/v1", sha1))
            r2 = await _drain_task(seed.task_manager,
                                   _file_req(f"{base_url}/v2", sha2))
            for r in (r1, r2):
                await resolver.publish_manifest_for(
                    seed.task_manager, r.task_id, params=params)
            result = await device_lib.download_to_device(
                pod, f"{base_url}/v1", digest=sha1)
            hot = DoubleBuffer()
            hot.flip(result.as_words(), result.load_safetensors())
            before = _sums_counted()
            if lying:
                with pytest.raises(DfError, match="hot-swap verify failed"):
                    await device_lib.download_delta(
                        pod, f"{base_url}/v2", base=result.task_id, hot=hot,
                        digest=sha2)
                assert hot.generation == 1
                np.testing.assert_array_equal(
                    np.asarray(hot.tensors()["bias"]), tensors_v1["bias"])
                return
            swap = await device_lib.download_delta(
                pod, f"{base_url}/v2", base=result.task_id, hot=hot,
                digest=sha2)
            assert swap.flipped and swap.on_device and hot.generation == 2
            store = pod.task_manager.storage.find_completed_task(swap.task_id)
            pieces = store.metadata.total_piece_count
            assert swap.stats["host_sums_carried"] == pieces >= 1
            after = _sums_counted()
            assert after["carried"] - before["carried"] == pieces
            assert after["walked"] == before["walked"]
            (verify,) = [e for e in pod.task_manager.flight.get(
                swap.task_id).events()
                if flightlib.EVENT_NAMES.get(e[1]) == "swap_verify"]
            assert (verify[2], verify[4]) == (pieces, str(pieces))
            np.testing.assert_array_equal(
                np.asarray(hot.tensors()["bias"]), tensors_v2["bias"])
        finally:
            for d in daemons:
                await d.stop()
            await sched.stop()
            await origin.cleanup()

    run_async(body(), timeout=120)
