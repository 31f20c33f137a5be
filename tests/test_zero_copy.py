"""Zero-copy data plane: hash-while-receiving, buffer reuse, and the
no-re-read guarantee.

The acceptance bar for the single-pass pipeline: piece verification on the
download path performs ZERO re-reads of landed bytes — digests stream over
the bytes as they arrive (reference Dragonfly2 pkg/digest/digest_reader.go
hashes in the reader, not off a landed copy), and the completion-time
whole-content digest is fed from the same in-memory bytes, never from a
disk read-back.
"""

from __future__ import annotations

import hashlib
import os
import random

import pytest

from dragonfly2_tpu.daemon.peer.piece_downloader import assemble_piece
from dragonfly2_tpu.daemon.peer.piece_manager import (
    PieceManager,
    PieceManagerOption,
)
from dragonfly2_tpu.pkg import digest as pkgdigest
from dragonfly2_tpu.pkg.bufpool import BufferPool
from dragonfly2_tpu.pkg.errors import DfError
from dragonfly2_tpu.source import Request, ResourceClient, Response
from dragonfly2_tpu.source import default_registry
from dragonfly2_tpu.storage.local_store import (
    LocalTaskStore,
    StorageError,
    TaskStoreMetadata,
)

PIECE = 256 * 1024
CONTENT = bytes(random.Random(5).randbytes(4 * PIECE + 12345))


class _ReadTap:
    """Counts every path that could re-read landed bytes during landing."""

    def __init__(self, monkeypatch):
        self.preads = 0
        self.piece_reads = 0
        real_pread = os.pread
        real_preadv = os.preadv

        def pread(fd, n, off):
            self.preads += 1
            return real_pread(fd, n, off)

        def preadv(fd, bufs, off):
            self.preads += 1
            return real_preadv(fd, bufs, off)

        real_read_piece = LocalTaskStore.read_piece

        def read_piece(store, num):
            self.piece_reads += 1
            return real_read_piece(store, num)

        monkeypatch.setattr(os, "pread", pread)
        monkeypatch.setattr(os, "preadv", preadv)
        monkeypatch.setattr(LocalTaskStore, "read_piece", read_piece)

    @property
    def total(self) -> int:
        return self.preads + self.piece_reads


async def _chunks(data, chunk=64 * 1024):
    view = memoryview(data)
    for off in range(0, len(data), chunk):
        yield bytes(view[off:off + chunk])


def _store(tmp_path, name, piece_size=PIECE) -> LocalTaskStore:
    return LocalTaskStore.create(
        str(tmp_path / name),
        TaskStoreMetadata(task_id=f"zc-{name}", piece_size=piece_size))


def test_p2p_verified_landing_performs_zero_store_reads(
        tmp_path, monkeypatch, run_async):
    """The peer download path: parent-advertised digests verify against
    the hash computed WHILE the body streamed — landing touches the
    store's write path only."""

    async def run():
        piece_count = (len(CONTENT) + PIECE - 1) // PIECE
        digests = [
            f"crc32c:{pkgdigest.crc32c(CONTENT[n * PIECE:(n + 1) * PIECE]):08x}"
            for n in range(piece_count)]
        store = _store(tmp_path, "p2p")
        store.update_task(content_length=len(CONTENT),
                          total_piece_count=piece_count)
        tap = _ReadTap(monkeypatch)
        for n in range(piece_count):
            piece = CONTENT[n * PIECE:(n + 1) * PIECE]
            chunks, size, received = await assemble_piece(
                _chunks(piece), len(piece), digests[n])
            rec = store.write_piece_chunks(n, chunks, received,
                                           expected_digest=digests[n])
            assert rec.size == size == len(piece)
            assert rec.digest == digests[n]
        assert tap.total == 0, \
            f"verified landing re-read landed bytes {tap.total} times"
        # Every piece carries its verified-against digest: the certified
        # completion skip engages with zero additional reads.
        store.certified_digests = dict(enumerate(digests))
        assert store.pieces_all_digest_verified()
        assert tap.total == 0
        # Sanity OUTSIDE the landing window: the bytes on disk are real.
        monkeypatch.undo()
        assert store.read_range(0, len(CONTENT)) == CONTENT
        store.destroy()

    run_async(run())


def test_p2p_wrong_body_rejected_before_commit(tmp_path, run_async):
    """Hash-while-receiving must still fail a corrupt body exactly like
    the in-store verify did: coded error, nothing recorded."""

    async def run():
        store = _store(tmp_path, "bad")
        good = CONTENT[:PIECE]
        want = f"crc32c:{pkgdigest.crc32c(good):08x}"
        corrupt = bytearray(good)
        corrupt[100] ^= 0xFF
        chunks, _size, received = await assemble_piece(
            _chunks(bytes(corrupt)), PIECE, want)
        with pytest.raises(StorageError):
            store.write_piece_chunks(0, chunks, received,
                                     expected_digest=want)
        assert 0 not in store.metadata.pieces
        # Non-crc algorithms stream their digest during receive and are
        # refused by comparison at the same commit point.
        md5_want = str(pkgdigest.hash_bytes("md5", good))
        chunks, _size, received = await assemble_piece(
            _chunks(bytes(corrupt)), PIECE, md5_want)
        assert received and received != md5_want
        with pytest.raises(StorageError):
            store.write_piece_chunks(0, chunks, received,
                                     expected_digest=md5_want)
        assert 0 not in store.metadata.pieces
        # Undersized and oversized bodies are coded failures too.
        with pytest.raises(DfError):
            await assemble_piece(_chunks(good[:100]), PIECE, want)
        with pytest.raises(DfError):
            await assemble_piece(_chunks(good + b"x"), PIECE, want)
        store.destroy()

    run_async(run())


class _MemClient(ResourceClient):
    def __init__(self, content):
        self.content = content

    async def download(self, request: Request) -> Response:
        data = self.content
        status = 200
        rng = request.header.get("Range")
        if rng:
            from dragonfly2_tpu.pkg.piece import Range

            r = Range.parse_http(rng, len(data))
            data = data[r.start:r.start + r.length]
            status = 206
        return Response(_chunks(data), status=status,
                        content_length=len(data), support_range=True)

    async def get_content_length(self, request):
        return len(self.content)

    async def is_support_range(self, request):
        return True

    async def probe(self, request):
        return len(self.content), True


def test_backsource_completion_digest_needs_no_disk_readback(
        tmp_path, monkeypatch, run_async):
    """Sequential back-to-source: per-piece digests stream over the wire
    chunks, and the completion whole-content sha256 is fed the same
    in-memory bytes at commit time — download + validate_digest with ZERO
    reads of the data file (the old pipeline re-read every committed
    piece through the prefix hasher)."""

    async def run():
        default_registry().register("memzc", _MemClient(CONTENT))
        sha = hashlib.sha256(CONTENT).hexdigest()
        store = _store(tmp_path, "origin")
        pm = PieceManager(PieceManagerOption(concurrency=1))
        tap = _ReadTap(monkeypatch)
        store.start_prefix_hasher(f"sha256:{sha}")
        ph = store._prefix_hasher
        assert ph is not None
        await pm.download_source(store, "memzc://origin/blob")
        assert store.validate_digest(f"sha256:{sha}") == f"sha256:{sha}"
        assert tap.total == 0 and ph.disk_reads == 0, \
            (tap.preads, tap.piece_reads, ph.disk_reads)
        monkeypatch.undo()
        assert store.read_range(0, len(CONTENT)) == CONTENT
        store.destroy()

    run_async(run())


def test_buffer_pool_recycles_and_refuses_double_release():
    pool = BufferPool(max_retained_bytes=1 << 20)
    a = pool.acquire(1000)
    a[:4] = b"abcd"
    backing = a.obj
    pool.release(a)
    b = pool.acquire(500)
    assert b.obj is backing, "pool did not recycle the buffer"
    with pytest.raises(ValueError):
        a[0]   # released view must not be readable
    pool.release(b)
    # Oversized buffers beyond the retention cap are dropped, not leaked.
    big = pool.acquire(2 << 20)
    pool.release(big)
    assert pool.stats()["retained_bytes"] <= 1 << 20


@pytest.mark.parametrize("through", ["prefix", "rehash"])
def test_completion_digest_reads_only_on_the_reader_threads(
        tmp_path, monkeypatch, through):
    """The thread that hashes never reads (PR 37): every ``preadv`` of the
    completion digest is a ``df-prefix-read-*`` thread's, whether the
    digest comes from the prefix hasher or from the full re-hash, and
    ``disk_reads`` and ``store_digest_chunks_total`` account for every
    chunk read."""
    import threading

    from dragonfly2_tpu.storage import local_store

    chunk = 64 * 1024
    monkeypatch.setattr(local_store, "_CHUNK", chunk)
    store = _store(tmp_path, f"readers-{through}")
    pieces = -(-len(CONTENT) // PIECE)
    store.update_task(content_length=len(CONTENT))
    want = "sha256:" + hashlib.sha256(CONTENT).hexdigest()
    # Native-engine landings: bytes straight into the file, no memory feed.
    for n in range(pieces):
        data = CONTENT[n * PIECE:(n + 1) * PIECE]
        os.pwrite(store.data_fd(), data, n * PIECE)
        store.record_piece(n, len(data), crc=0)
    readers: list[str] = []
    real_preadv = os.preadv

    def preadv(fd, bufs, off):
        readers.append(threading.current_thread().name)
        return real_preadv(fd, bufs, off)

    def counted():
        return sum(local_store.DIGEST_CHUNKS.labels(how)._value.get()
                   for how in ("ready", "waited"))

    before = counted()
    monkeypatch.setattr(os, "preadv", preadv)
    if through == "prefix":
        store.start_prefix_hasher(want)
    assert store.validate_digest(want) == want
    how, read_back, (ready, waited) = store.digest_pass
    assert (how, read_back) == (through, pieces)
    assert all(name.startswith("df-prefix-read-") for name in readers), \
        set(readers)
    chunks = sum(-(-min(PIECE, len(CONTENT) - n * PIECE) // chunk)
                 for n in range(pieces))
    assert len(readers) == chunks == counted() - before
    assert ready + waited <= chunks   # the tail's, or the whole pass's
    monkeypatch.undo()
    store.destroy()
