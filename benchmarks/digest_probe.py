"""What the completion digest of a stored object costs the host: a probe
of the read-ahead in front of the whole-object hash
(``storage/local_store.py`` ``_ReadAhead``), on one store, with no
scheduler, no transfer and no device. It imports no jax.

    chiprun --chips 1 -- python3 benchmarks/digest_probe.py
    python3 benchmarks/digest_probe.py --pieces 5 --piece-bytes 4194304  # the rehearsal

A store of ``--pieces`` pieces of ``--piece-bytes`` random bytes (the
benchmark's shard geometry: 55 of 32 MiB), written by the probe and so in
the page cache, as a seed's store is when its last piece has landed. For
each arrangement the median and the range over ``--repeats`` whole passes,
after one that is not counted, in GB/s (1e9 bytes a second):

  sha256 from memory   ``hashlib`` over bytes already in memory, 4 MiB an
                       update: what the hash alone can do on one thread
  preadv alone         one thread copying the store through one 4 MiB
                       buffer, no hash: what one read stream can do
  serial               the tree before PR 37: ``preadv`` of 4 MiB, then
                       ``update`` of it, in turn on one thread
  ring d/r             the tree's loop (``validate_digest``'s full pass:
                       ``_ReadAhead.hash_into``) with ``_RING_DEPTH`` d and
                       ``_RING_READERS`` r patched over the constants; d 1
                       is the hand-over alone, nothing read ahead
  prefix hasher        ``_PrefixHasher`` itself over the complete store,
                       with the tree's constants: what a seed's tail runs

and each again while eight threads copy 32 MiB arrays in memory without
pause (the landing's eight helpers, ``ops/hbm_sink.py``, which share the
host's cores and memory with a seed's tail in ``shard-cold``). Every
arrangement that hashes must give the same digest; the ring rows also give
the share of chunks the hashing thread found ready
(``store_digest_chunks_total``). The table goes to stdout and to
``chiprun_out/digest_probe.json``; PERF.md section 5 ("The digest, alone")
holds the reading that ``_RING_DEPTH`` and ``_RING_READERS`` rest on.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import shutil
import statistics
import sys
import tempfile
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, REPO)

RINGS = ((1, 1), (2, 1), (4, 1), (1, 2), (2, 2), (4, 2))
CHUNK = 4 << 20
HELPERS = 8


def _store(root: str, pieces: int, piece_size: int):
    """A completed store of ``pieces`` pieces of random bytes."""
    import numpy as np

    from dragonfly2_tpu.storage.local_store import (
        LocalTaskStore,
        TaskStoreMetadata,
    )

    store = LocalTaskStore(
        os.path.join(root, "probe"),
        TaskStoreMetadata(task_id="digest-probe",
                          content_length=pieces * piece_size,
                          piece_size=piece_size, total_piece_count=pieces))
    rng = np.random.default_rng(pieces)
    for n in range(pieces):
        store.write_piece(
            n, rng.integers(0, 256, piece_size, dtype=np.uint8).data)
    return store


def _from_memory(nbytes: int):
    """sha256 over ``nbytes`` (at most 512 MiB, gone over again) in memory."""
    import numpy as np

    held = np.random.default_rng(1).integers(
        0, 256, min(nbytes, 512 << 20), dtype=np.uint8).data

    def run() -> str:
        h = hashlib.sha256()
        done = 0
        while done < nbytes:
            at = done % len(held)
            take = min(CHUNK, len(held) - at, nbytes - done)
            h.update(held[at:at + take])
            done += take
        return ""   # other bytes than the store's: no digest to compare

    return run


def _preadv_alone(store):
    def run() -> str:
        fd = os.open(store.data_path, os.O_RDONLY)
        buf = memoryview(bytearray(CHUNK))
        try:
            off, end = 0, store.metadata.content_length
            while off < end:
                off += os.preadv(fd, [buf[:min(CHUNK, end - off)]], off)
        finally:
            os.close(fd)
        return ""

    return run


def _serial(store):
    """``validate_digest``'s loop as it was before PR 37."""
    def run() -> str:
        fd = os.open(store.data_path, os.O_RDONLY)
        buf = memoryview(bytearray(CHUNK))
        h = hashlib.sha256()
        try:
            off, end = 0, store.metadata.content_length
            while off < end:
                n = os.preadv(fd, [buf[:min(CHUNK, end - off)]], off)
                h.update(buf[:n])
                off += n
        finally:
            os.close(fd)
        return "sha256:" + h.hexdigest()

    return run


def _ring(store, depth: int, readers: int, shares: list):
    from dragonfly2_tpu.storage import local_store

    def run() -> str:
        kept = local_store._RING_DEPTH, local_store._RING_READERS
        local_store._RING_DEPTH, local_store._RING_READERS = depth, readers
        try:
            got = store.validate_digest()
        finally:
            local_store._RING_DEPTH, local_store._RING_READERS = kept
        shares.append(store.digest_pass[2])
        return got

    return run


def _prefix(store, shares: list):
    def run() -> str:
        store.start_prefix_hasher("sha256:" + "0" * 64)
        ph = store._prefix_hasher
        got = ph.finish()
        store._prefix_hasher = None
        shares.append(ph.tail_chunks)
        return "sha256:" + got

    return run


class _Load:
    """``HELPERS`` threads copying 32 MiB arrays in memory until stopped."""

    def __enter__(self):
        import numpy as np

        self._stop = False

        def stream():
            src = np.ones(8 << 20, dtype=np.uint32)
            dst = np.empty_like(src)
            while not self._stop:
                np.copyto(dst, src)

        self._threads = [threading.Thread(target=stream, daemon=True)
                         for _ in range(HELPERS)]
        for t in self._threads:
            t.start()
        return self

    def __exit__(self, *exc):
        self._stop = True
        for t in self._threads:
            t.join()


def _measure(run, nbytes: int, repeats: int) -> dict:
    run()
    rates, digest = [], ""
    for _ in range(repeats):
        t0 = time.perf_counter()
        digest = run()
        rates.append(nbytes / (time.perf_counter() - t0) / 1e9)
    return {"GBps": round(statistics.median(rates), 3),
            "min": round(min(rates), 3), "max": round(max(rates), 3),
            "digest": digest}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--pieces", type=int, default=55)
    ap.add_argument("--piece-bytes", type=int, default=32 << 20)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--out", default=os.path.join(
        REPO, "chiprun_out", "digest_probe.json"))
    args = ap.parse_args()
    nbytes = args.pieces * args.piece_bytes
    # In the checkout, where the benchmark's stores lie (chipbench/.run).
    root = tempfile.mkdtemp(prefix=".digest_probe_", dir=REPO)
    rows: list[dict] = []
    try:
        store = _store(root, args.pieces, args.piece_bytes)
        shares: dict[str, list] = {}
        arrangements = [("sha256 from memory", _from_memory(nbytes)),
                        ("preadv alone", _preadv_alone(store)),
                        ("serial", _serial(store))]
        for depth, readers in RINGS:
            name = f"ring {depth}/{readers}"
            arrangements.append(
                (name, _ring(store, depth, readers,
                             shares.setdefault(name, []))))
        arrangements.append(
            ("prefix hasher", _prefix(store, shares.setdefault(
                "prefix hasher", []))))
        digests = set()
        for loaded in (False, True):
            for name, run in arrangements:
                if loaded:
                    with _Load():
                        row = _measure(run, nbytes, args.repeats)
                else:
                    row = _measure(run, nbytes, args.repeats)
                if row["digest"]:
                    digests.add(row.pop("digest"))
                else:
                    del row["digest"]
                got = shares.get(name)
                if got:
                    ready = sum(r for r, _ in got)
                    row["ready_share"] = round(
                        ready / max(1, ready + sum(w for _, w in got)), 3)
                    got.clear()
                row.update(arrangement=name, helpers_streaming=loaded)
                rows.append(row)
                print(f"{name:20s} {'loaded' if loaded else 'quiet':6s} "
                      f"{row['GBps']:6.3f} GB/s ({row['min']:.3f}-"
                      f"{row['max']:.3f})"
                      + (f"  ready {row['ready_share']:.3f}"
                         if "ready_share" in row else ""), flush=True)
        if len(digests) != 1:
            print(f"digests differ: {sorted(digests)}", file=sys.stderr)
            return 1
    finally:
        shutil.rmtree(root, ignore_errors=True)
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump({"pieces": args.pieces, "piece_bytes": args.piece_bytes,
                   "repeats": args.repeats, "cpus": os.cpu_count(),
                   "rows": rows}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
