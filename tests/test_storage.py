"""Storage manager + local task store tests."""

import os

import pytest

from dragonfly2_tpu.pkg import digest as pkgdigest
from dragonfly2_tpu.pkg.errors import StorageError
from dragonfly2_tpu.storage import StorageManager, StorageOption, TaskStoreMetadata
from dragonfly2_tpu.storage.local_store import LocalTaskStore


def make_manager(tmp_path, **kw):
    return StorageManager(StorageOption(data_dir=str(tmp_path / "data"), **kw))


def meta(task_id="t1", piece_size=4, content_length=10):
    import math

    return TaskStoreMetadata(
        task_id=task_id,
        peer_id="p1",
        url="http://x/f",
        piece_size=piece_size,
        content_length=content_length,
        total_piece_count=math.ceil(content_length / piece_size) if content_length >= 0 else -1,
    )


class TestLocalStore:
    def test_write_read_roundtrip(self, tmp_path):
        sm = make_manager(tmp_path)
        store = sm.register_task(meta())
        store.write_piece(0, b"aaaa")
        store.write_piece(1, b"bbbb")
        store.write_piece(2, b"cc")
        assert store.read_piece(0) == b"aaaa"
        assert store.read_piece(2) == b"cc"
        assert store.is_complete()
        assert store.downloaded_bytes() == 10

    def test_piece_digest_verified_on_write(self, tmp_path):
        sm = make_manager(tmp_path)
        store = sm.register_task(meta())
        good = str(pkgdigest.hash_bytes("md5", b"aaaa"))
        store.write_piece(0, b"aaaa", expected_digest=good)
        with pytest.raises(StorageError):
            store.write_piece(1, b"bbbb", expected_digest=good)

    def test_out_of_order_writes(self, tmp_path):
        sm = make_manager(tmp_path)
        store = sm.register_task(meta())
        store.write_piece(2, b"cc")
        store.write_piece(0, b"aaaa")
        store.write_piece(1, b"bbbb")
        assert store.is_complete()
        out = tmp_path / "out.bin"
        store.mark_done()
        store.store_to(str(out))
        assert out.read_bytes() == b"aaaabbbbcc"

    def test_store_to_hardlink(self, tmp_path):
        sm = make_manager(tmp_path)
        store = sm.register_task(meta())
        for i, d in enumerate([b"aaaa", b"bbbb", b"cc"]):
            store.write_piece(i, d)
        store.mark_done()
        dest = tmp_path / "out" / "f.bin"
        store.store_to(str(dest))
        assert dest.read_bytes() == b"aaaabbbbcc"
        # hardlink: same inode as the data file
        data_inode = os.stat(os.path.join(store.dir, "data")).st_ino
        assert os.stat(dest).st_ino == data_inode

    def test_store_incomplete_refused(self, tmp_path):
        sm = make_manager(tmp_path)
        store = sm.register_task(meta())
        store.write_piece(0, b"aaaa")
        with pytest.raises(StorageError):
            store.store_to(str(tmp_path / "o"))

    def test_validate_whole_digest(self, tmp_path):
        sm = make_manager(tmp_path)
        store = sm.register_task(meta())
        for i, d in enumerate([b"aaaa", b"bbbb", b"cc"]):
            store.write_piece(i, d)
        want = "sha256:" + pkgdigest.hash_bytes("sha256", b"aaaabbbbcc").encoded
        assert store.validate_digest(want) == want
        with pytest.raises(StorageError):
            store.validate_digest("sha256:" + "0" * 64)

    def test_get_pieces_listing(self, tmp_path):
        sm = make_manager(tmp_path)
        store = sm.register_task(meta(content_length=-1))
        store.update_task(piece_size=4)
        store.write_piece(0, b"aaaa")
        store.write_piece(1, b"bbbb")
        recs = store.get_pieces(0)
        assert [r.num for r in recs] == [0, 1]
        recs = store.get_pieces(1, limit=1)
        assert [r.num for r in recs] == [1]


class TestManager:
    def test_reload_restores_tasks(self, tmp_path):
        sm = make_manager(tmp_path)
        store = sm.register_task(meta())
        for i, d in enumerate([b"aaaa", b"bbbb", b"cc"]):
            store.write_piece(i, d)
        store.mark_done()
        sm.close()
        # New manager over the same dir (daemon restart).
        sm2 = make_manager(tmp_path)
        assert sm2.reload() == 1
        found = sm2.find_completed_task("t1")
        assert found is not None
        assert found.read_piece(1) == b"bbbb"

    def test_reload_sweeps_invalid(self, tmp_path):
        sm = make_manager(tmp_path)
        store = sm.register_task(meta())
        store.mark_invalid()
        sm.close()
        sm2 = make_manager(tmp_path)
        assert sm2.reload() == 0
        assert sm2.try_get("t1") is None

    def test_ttl_gc(self, tmp_path):
        sm = make_manager(tmp_path, task_ttl=0.0)
        store = sm.register_task(meta())
        store.write_piece(0, b"aaaa")
        store.metadata.last_access -= 10
        reclaimed = sm.gc()
        assert reclaimed == ["t1"]
        assert sm.try_get("t1") is None
        assert not os.path.exists(store.dir)

    def test_lru_quota_gc(self, tmp_path):
        import time

        sm = make_manager(tmp_path, disk_gc_threshold=25)
        now = time.time()
        for n in range(3):
            st = sm.register_task(meta(task_id=f"t{n}"))
            for i, d in enumerate([b"aaaa", b"bbbb", b"cc"]):
                st.write_piece(i, d)
            st.metadata.last_access = now - (3 - n)  # t0 oldest
        reclaimed = sm.gc()
        assert "t0" in reclaimed
        assert sm.try_get("t2") is not None

    def test_find_partial(self, tmp_path):
        sm = make_manager(tmp_path)
        store = sm.register_task(meta())
        store.write_piece(0, b"aaaa")
        assert sm.find_completed_task("t1") is None
        assert sm.find_partial_completed_task("t1") is not None


class TestGCPinning:
    def test_pinned_store_survives_gc(self, tmp_path):
        sm = make_manager(tmp_path, task_ttl=0.0)
        store = sm.register_task(meta())
        store.write_piece(0, b"aaaa")
        store.metadata.last_access -= 10
        with store:  # pinned
            assert sm.gc() == []
        assert sm.gc() == ["t1"]  # unpinned → reclaimed

    def test_invalid_store_recreated_on_register(self, tmp_path):
        sm = make_manager(tmp_path)
        store = sm.register_task(meta())
        store.write_piece(0, b"aaaa")
        store.mark_invalid()
        fresh = sm.register_task(meta())
        assert not fresh.metadata.invalid
        assert not fresh.metadata.pieces  # clean slate, no poisoned pieces


def test_concurrent_writes_and_reads_threadsafe(tmp_path):
    """write_piece runs on worker threads (asyncio.to_thread in the piece
    paths) while the event loop reads the piece map — no 'dict changed
    size during iteration', no lost pieces (code-review regression r3)."""
    import threading

    from dragonfly2_tpu.storage.local_store import (
        LocalTaskStore,
        TaskStoreMetadata,
    )

    piece = 4096
    total = 64
    store = LocalTaskStore(
        str(tmp_path / "t"),
        TaskStoreMetadata(task_id="t-threads", content_length=piece * total,
                          piece_size=piece, total_piece_count=total))
    blob = b"\xab" * piece
    errors = []

    def writer(nums):
        try:
            for n in nums:
                store.write_piece(n, blob)
        except Exception as e:  # pragma: no cover
            errors.append(e)

    def reader():
        try:
            for _ in range(300):
                store.get_pieces()
                store.covers_range(0, piece * total)
                store.downloaded_bytes()
        except Exception as e:  # pragma: no cover
            errors.append(e)

    threads = [threading.Thread(target=writer, args=(range(i, total, 4),))
               for i in range(4)] + [threading.Thread(target=reader)
                                     for _ in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors, errors
    assert len(store.metadata.pieces) == total
    assert store.is_complete()


def test_gc_closes_idle_store_fds(tmp_path):
    """Idle (but un-expired) stores drop their data-file fd at GC time and
    reopen lazily — a long-lived daemon must not hold one fd per task it
    ever served."""
    import time as _time

    from dragonfly2_tpu.storage.manager import StorageManager, StorageOption

    mgr = StorageManager(StorageOption(data_dir=str(tmp_path / "d"),
                                       task_ttl=3600.0, gc_interval=10.0))
    store = mgr.register_task(TaskStoreMetadata(
        task_id="fd-task", content_length=8, piece_size=8,
        total_piece_count=1))
    store.write_piece(0, b"12345678")
    assert store._fd is not None
    # Fresh store: GC must keep the fd (recently used).
    mgr.gc()
    assert store._fd is not None
    # Idle past gc_interval but under TTL: fd closes, store survives.
    store.metadata.last_access = _time.time() - 60
    mgr.gc()
    assert store._fd is None
    assert mgr.try_get("fd-task") is store
    # Lazy reopen serves reads.
    assert store.read_piece(0) == b"12345678"
    # Pinned stores are never touched.
    store.metadata.last_access = _time.time() - 60
    with store:
        mgr.gc()
        assert store._fd is not None
    mgr.close()


def test_pieces_all_digest_verified_tracking(tmp_path):
    """The completion-time re-hash skip needs exact provenance: verified
    means 'matched an externally-announced digest at landing', never
    self-computed."""
    from dragonfly2_tpu.pkg import digest as pkgdigest

    mgr = make_manager(tmp_path)
    store = mgr.register_task(meta("t-verified", content_length=9))
    store.update_task(content_length=9, piece_size=4, total_piece_count=3)
    d0 = pkgdigest.hash_bytes(pkgdigest.ALGORITHM_CRC32C, b"aaaa")
    store.write_piece(0, b"aaaa", expected_digest=str(d0))
    assert not store.pieces_all_digest_verified()  # incomplete
    store.write_piece(1, b"bbbb")                  # self-computed digest
    crc2 = int(pkgdigest.hash_bytes(
        pkgdigest.ALGORITHM_CRC32C, b"c").encoded, 16)
    store.record_piece(2, 1, crc2, verified=True)
    assert store.is_complete()
    # Piece 1 was never externally verified -> no skip.
    assert not store.pieces_all_digest_verified()

    store2 = mgr.register_task(meta("t-verified2", content_length=8))
    store2.update_task(content_length=8, piece_size=4, total_piece_count=2)
    d = pkgdigest.hash_bytes(pkgdigest.ALGORITHM_CRC32C, b"xxxx")
    store2.write_piece(0, b"xxxx", expected_digest=str(d))
    crc = int(pkgdigest.hash_bytes(
        pkgdigest.ALGORITHM_CRC32C, b"yyyy").encoded, 16)
    store2.record_piece(1, 4, crc, verified=True)
    # All pieces verified but no completed parent certified the digest
    # map yet -> still no skip.
    assert not store2.pieces_all_digest_verified()
    # Certification is per-piece provenance: the certified map must MATCH
    # what each piece was verified against, or the skip stays off (a
    # corrupt parent's digests cannot be laundered by an honest done).
    good = {0: str(d), 1: f"crc32c:{crc:08x}"}
    store2.certified_digests = {0: str(d), 1: "crc32c:deadbeef"}
    assert not store2.pieces_all_digest_verified()
    store2.certified_digests = good
    assert store2.pieces_all_digest_verified()

    # apply_certification tries every done parent's map: a corrupt early
    # finisher cannot mask an honest one.
    corrupt = {0: str(d), 1: "crc32c:deadbeef"}
    store2.certified_digests = None
    assert store2.apply_certification([corrupt, good]) is True
    assert store2.certified_digests == good
    assert store2.pieces_all_digest_verified()
    # An installed verifying map is never downgraded by later candidates.
    assert store2.apply_certification([corrupt]) is True
    assert store2.certified_digests == good
    # Only corrupt candidates from scratch: nothing installed — the
    # completion decision re-hashes either way.
    store2.certified_digests = None
    assert store2.apply_certification([corrupt]) is False
    assert store2.certified_digests is None
    assert not store2.pieces_all_digest_verified()
    # Empty candidate list: nothing installed, nothing clobbered.
    assert store2.apply_certification([]) is False
    assert store2.certified_digests is None


class TestPrefixHasher:
    """Hash-as-you-backsource: the contiguous-prefix hasher must produce
    the same completion digest as the full re-hash, and any anomaly must
    poison it into the fallback path, never a wrong digest."""

    def _content(self, n=3 * 65536 + 123):
        import random
        return bytes(random.Random(5).randbytes(n))

    def test_out_of_order_pieces_match_full_hash(self, tmp_path):
        import hashlib

        content = self._content()
        piece = 65536
        store = LocalTaskStore(str(tmp_path / "s1"),
                               meta("t-ph1", piece_size=piece,
                                    content_length=len(content)))
        want = "sha256:" + hashlib.sha256(content).hexdigest()
        store.start_prefix_hasher(want)
        assert store._prefix_hasher is not None
        order = [2, 0, 3, 1]
        for n in order:
            store.write_piece(n, content[n * piece:(n + 1) * piece])
        assert store.is_complete()
        assert store.validate_digest(want) == want
        assert store._prefix_hasher is None  # consumed

    def test_mismatch_still_raises(self, tmp_path):
        content = self._content()
        piece = 65536
        store = LocalTaskStore(str(tmp_path / "s2"),
                               meta("t-ph2", piece_size=piece,
                                    content_length=len(content)))
        want = "sha256:" + "0" * 64
        store.start_prefix_hasher(want)
        for n in range(4):
            store.write_piece(n, content[n * piece:(n + 1) * piece])
        with pytest.raises(StorageError):
            store.validate_digest(want)

    def test_rerecorded_piece_poisons_to_fallback(self, tmp_path):
        import hashlib
        import time as _time

        content = self._content()
        piece = 65536
        store = LocalTaskStore(str(tmp_path / "s3"),
                               meta("t-ph3", piece_size=piece,
                                    content_length=len(content)))
        want = "sha256:" + hashlib.sha256(content).hexdigest()
        store.start_prefix_hasher(want)
        store.write_piece(0, content[:piece])
        # Let the hasher pass piece 0, then re-record it behind the
        # frontier: the hasher must poison, and validate_digest must
        # fall back to the (still correct) full re-hash.
        deadline = _time.monotonic() + 5
        while (store._prefix_hasher._next < 1
               and _time.monotonic() < deadline):
            _time.sleep(0.01)
        assert store._prefix_hasher._next >= 1
        for n in range(4):
            store.write_piece(n, content[n * piece:(n + 1) * piece])
        assert store.validate_digest(want) == want

    def test_unknown_algorithm_is_noop(self, tmp_path):
        store = LocalTaskStore(str(tmp_path / "s4"),
                               meta("t-ph4", piece_size=4, content_length=8))
        store.start_prefix_hasher("whirlpool999:beef")
        assert store._prefix_hasher is None


class _GatedHasher:
    """sha256 whose ``update`` stands at a gate: holds the hashing thread
    so that the read frontier runs ahead of the hash frontier."""

    def __init__(self, gate):
        import hashlib

        self._h = hashlib.sha256()
        self._gate = gate

    def update(self, data):
        assert self._gate.wait(timeout=10)
        self._h.update(data)

    def hexdigest(self):
        return self._h.hexdigest()


def _wait_for(cond, what, timeout=10.0):
    import time

    deadline = time.monotonic() + timeout
    while not cond():
        assert time.monotonic() < deadline, f"timed out: {what}"
        time.sleep(0.005)


def _prefix_threads():
    import threading

    return [t for t in threading.enumerate() if t.name.startswith("df-prefix-")]


class TestDigestReadAhead:
    """The read-ahead in front of the prefix hasher (PR 37): a reader fills
    a ring of pooled chunks, the hashing thread never reads, and the
    digest is ``hashlib``'s over the content in piece order in every
    interleaving of commits, feeds and reads."""

    CHUNK = 16 * 1024

    @pytest.fixture(autouse=True)
    def small_chunks(self, monkeypatch):
        from dragonfly2_tpu.storage import local_store

        # Pieces of a few chunks each without gigabytes of content.
        monkeypatch.setattr(local_store, "_CHUNK", self.CHUNK)

    def _filled(self, tmp_path, name, pieces, piece=40 * 1024 + 17, tail=1234,
                seed=0):
        import hashlib
        import random

        content = bytes(random.Random(seed).randbytes(
            (pieces - 1) * piece + tail))
        store = LocalTaskStore(str(tmp_path / name),
                               meta(f"t-{name}", piece_size=piece,
                                    content_length=len(content)))
        return store, content, "sha256:" + hashlib.sha256(content).hexdigest()

    @staticmethod
    def _land(store, content, n, fed):
        """Commit piece ``n``: through memory (``write_piece``: the Python
        receive paths, which feed) or as the native engine does (bytes
        straight into the file, then ``record_piece``: read back)."""
        piece = store.metadata.piece_size
        data = content[n * piece:(n + 1) * piece]
        if fed:
            store.write_piece(n, data)
        else:
            os.pwrite(store.data_fd(), data, n * piece)
            store.record_piece(n, len(data), crc=0)

    @pytest.mark.parametrize("readers", [1, 2])
    @pytest.mark.parametrize("pieces", [1, 2, 55])
    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_digest_is_hashlibs_in_any_order_and_mix(
            self, tmp_path, monkeypatch, seed, pieces, readers):
        import random

        from dragonfly2_tpu.storage import local_store

        monkeypatch.setattr(local_store, "_RING_READERS", readers)
        rnd = random.Random(seed * 100 + pieces)
        store, content, want = self._filled(
            tmp_path, "mix", pieces, tail=rnd.randrange(1, 40 * 1024),
            seed=seed)
        store.start_prefix_hasher(want)
        ph = store._prefix_hasher
        order = list(range(pieces))
        rnd.shuffle(order)
        fed = 0
        for n in order:
            by_memory = rnd.random() < 0.5
            fed += by_memory
            self._land(store, content, n, by_memory)
        assert store.validate_digest(want) == want
        how, read_back, (ready, waited) = store.digest_pass
        assert how == "prefix"
        # Every piece went into the digest once: read back, or fed.
        assert read_back == ph.disk_reads and pieces - fed <= read_back <= pieces
        assert not _prefix_threads()

    def test_concurrent_commits_under_a_short_switch_interval(self, tmp_path):
        import sys
        import threading

        store, content, want = self._filled(tmp_path, "stress", 96)
        store.start_prefix_hasher(want)
        workers = 2 * (os.cpu_count() or 4)
        kept = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            threads = [
                threading.Thread(target=lambda w=w: [
                    self._land(store, content, n, fed=(n + w) % 2 == 0)
                    for n in range(w, 96, workers)])
                for w in range(workers)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=30)
                assert not t.is_alive()
        finally:
            sys.setswitchinterval(kept)
        assert store.validate_digest(want) == want
        assert store.digest_pass[0] == "prefix"

    @pytest.mark.parametrize("offset,how", [(-1, "rehash"), (0, "rehash"),
                                            (1, "prefix")])
    def test_rerecord_at_or_behind_the_read_frontier_poisons(
            self, tmp_path, monkeypatch, offset, how):
        import threading

        from dragonfly2_tpu.storage import local_store

        gate = threading.Event()
        monkeypatch.setattr(local_store.pkgdigest, "new_hasher",
                            lambda algorithm: _GatedHasher(gate))
        store, content, want = self._filled(tmp_path, f"poison{offset + 1}", 8,
                                            piece=self.CHUNK, tail=self.CHUNK)
        store.start_prefix_hasher(want)
        ph = store._prefix_hasher
        for n in range(8):
            self._land(store, content, n, fed=False)
        # The hasher stands at piece 0 (a chunk a piece); the reader has
        # copied a ring's worth ahead of it.
        _wait_for(lambda: ph._read_next == local_store._RING_DEPTH,
                  "reader a ring ahead of the hasher")
        assert ph._next == 0
        again = ph._read_next + offset
        assert again > ph._next + 1   # the hash frontier's rule let it pass
        self._land(store, content, again, fed=False)
        assert ph._ring.closed == (how == "rehash")
        gate.set()
        assert store.validate_digest(want) == want
        assert store.digest_pass[0] == how
        _wait_for(lambda: not _prefix_threads(), "threads gone")

    @pytest.mark.parametrize("repaired", [True, False])
    def test_short_data_file_poisons_and_falls_back(self, tmp_path, repaired):
        store, content, want = self._filled(tmp_path, f"short{repaired}", 4)
        for n in range(4):
            self._land(store, content, n, fed=False)
        os.truncate(store.data_path, len(content) // 2)
        store.start_prefix_hasher(want)
        ph = store._prefix_hasher
        _wait_for(lambda: ph._ring.closed, "short read poisons")
        assert "short read" in ph._ring.err
        if repaired:
            os.pwrite(store.data_fd(), content, 0)
            assert store.validate_digest(want) == want
            assert store.digest_pass[:2] == ("rehash", 4)
        else:
            with pytest.raises(StorageError, match="short read"):
                store.validate_digest(want)
        _wait_for(lambda: not _prefix_threads(), "threads gone")

    @pytest.mark.parametrize("recorded", [(0, 1, 2, 3), (0, 1, 3), (2,), ()])
    def test_full_rehash_gives_the_digest_it_gave(self, tmp_path, recorded):
        """``validate_digest`` without a prefix hasher: sha256 over the
        RECORDED pieces in piece order (gaps and all), as the serial loop
        gave it."""
        import hashlib

        store, content, _ = self._filled(tmp_path, f"re{len(recorded)}", 4)
        piece = store.metadata.piece_size
        for n in reversed(recorded):
            self._land(store, content, n, fed=n % 2 == 0)
        want = "sha256:" + hashlib.sha256(b"".join(
            content[n * piece:(n + 1) * piece] for n in recorded)).hexdigest()
        assert store.validate_digest() == want
        how, pieces, (ready, waited) = store.digest_pass
        assert (how, pieces) == ("rehash", len(recorded))
        assert ready + waited == sum(
            -(-len(content[n * piece:(n + 1) * piece]) // self.CHUNK)
            for n in recorded)
        assert not _prefix_threads()

    @pytest.mark.parametrize("ending", ["stop", "mark_invalid", "destroy",
                                        "finish"])
    def test_endings_leave_no_thread_and_no_buffer_out(
            self, tmp_path, monkeypatch, ending):
        import threading

        from dragonfly2_tpu.storage import local_store

        gate = threading.Event()
        monkeypatch.setattr(local_store.pkgdigest, "new_hasher",
                            lambda algorithm: _GatedHasher(gate))

        def out():
            return local_store.read_buffer_stats()["outstanding"]

        before = out()
        store, content, want = self._filled(tmp_path, ending, 12)
        store.start_prefix_hasher(want)
        ph = store._prefix_hasher
        for n in range(12):
            self._land(store, content, n, fed=False)
        # A full ring: every buffer the hasher may hold is out of the pool.
        _wait_for(lambda: out() - before == local_store._RING_DEPTH,
                  "ring full")
        if ending == "finish":
            gate.set()
            assert "sha256:" + ph.finish() == want
        else:
            ph.stop() if ending == "stop" else getattr(store, ending)()
            assert ph._ring.closed and ph.finish(timeout=0.1) is None
            gate.set()
        _wait_for(lambda: not _prefix_threads(), "threads gone")
        assert out() == before


def test_open_data_files_stay_inside_the_managers_budget(tmp_path,
                                                         monkeypatch):
    """A daemon that lands thousands of one-piece tasks (a dataset's samples,
    a ranged task each) holds at most ``MAX_OPEN_FILES`` data files open,
    beside the pinned and the just-touched: the oldest opened close theirs
    and reopen lazily, with their bytes."""
    import time as _time

    from dragonfly2_tpu.storage import manager
    from dragonfly2_tpu.storage.manager import StorageManager, StorageOption

    monkeypatch.setattr(manager, "MAX_OPEN_FILES", 16)
    mgr = StorageManager(StorageOption(data_dir=str(tmp_path / "d")))
    stores = []
    for i in range(200):
        store = mgr.register_task(TaskStoreMetadata(
            task_id=f"sample-{i:04d}", content_length=8, piece_size=8,
            total_piece_count=1))
        store.write_piece(0, b"%08d" % i)
        # As if written more than a second ago.
        store.metadata.last_access = _time.time() - 5
        stores.append(store)
    assert sum(s._fd is not None for s in stores) <= 16
    assert stores[0]._fd is None and stores[-1]._fd is not None
    assert stores[0].read_piece(0) == b"00000000"      # lazy reopen
    # A pinned store and one touched within the second keep theirs.
    with stores[1]:
        stores[1].read_piece(0)
        stores[2].read_piece(0)
        stores[2].touch()
        for s in stores[3:40]:
            s.read_piece(0)
            s.metadata.last_access = _time.time() - 5
        assert stores[1]._fd is not None and stores[2]._fd is not None
    assert sum(s._fd is not None for s in stores) <= 16 + 2
    mgr.close()
