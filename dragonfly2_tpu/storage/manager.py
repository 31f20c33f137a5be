"""Storage manager: registry of task stores + reload + quota GC.

Reference: client/daemon/storage/storage_manager.go — RegisterTask (:253),
WritePiece (:311), FindCompletedTask (:529), ReloadPersistentTask (:703),
TTL+LRU disk-quota GC (:871-1068).
"""

from __future__ import annotations

import collections
import os
import threading
import time
from dataclasses import dataclass

from dragonfly2_tpu.pkg import dflog
from dragonfly2_tpu.pkg.errors import Code, StorageError
from dragonfly2_tpu.storage.local_store import (
    METADATA_FILE,
    LocalTaskStore,
    TaskStoreMetadata,
)

log = dflog.get("storage")

# Data files a manager holds open at once, beside those of pinned stores and
# of stores touched within the last second: a daemon that serves thousands
# of small tasks a minute (a dataset's samples, a ranged task each) would
# else hold one fd a task until the idle sweep of gc(), a gc_interval or two
# later, and run out of them first. The oldest opened go first; a closed
# store reopens lazily.
MAX_OPEN_FILES = 512


@dataclass
class StorageOption:
    data_dir: str
    task_ttl: float = 3 * 60 * 60.0          # reference DataExpireTime default
    disk_gc_threshold: int = 0               # bytes; 0 = unlimited
    keep_storage: bool = False               # survive daemon exit without GC
    gc_interval: float = 60.0
    # Idle time before an un-expired store drops its data-file fd (lazily
    # reopened). 0 = follow gc_interval; decoupled so operators can speed
    # up TTL sweeps without making warm stores thrash open()/close().
    fd_idle_close: float = 0.0


class StorageManager:
    def __init__(self, opt: StorageOption):
        self.opt = opt
        self._stores: dict[str, LocalTaskStore] = {}
        # Optional serving-index observer (duck-typed): task_updated(store),
        # piece_recorded(task_id, rec), task_deleted(task_id). The native
        # upload server mirrors the piece map through these callbacks so it
        # can serve without consulting Python per request. piece_recorded
        # arrives from worker threads; implementations must be thread-safe.
        self.observer = None
        # Stores in the order they opened their data file, oldest first
        # (a store that was closed and reopened is in it again).
        self._opened: "collections.deque[LocalTaskStore]" = \
            collections.deque()
        self._opened_lock = threading.Lock()
        os.makedirs(opt.data_dir, exist_ok=True)

    def set_observer(self, observer) -> None:
        """Attach the observer and replay current state (tasks + pieces)
        so an index attached after reload starts complete."""
        self.observer = observer
        for store in self._stores.values():
            store.observer = observer
            observer.task_updated(store)
            for rec in store.metadata.pieces.values():
                observer.piece_recorded(store.metadata.task_id, rec)

    def clear_observer(self) -> None:
        """Detach the observer from the manager AND every store (each store
        holds its own reference — clearing only the manager's would leave
        piece commits calling a dead index)."""
        self.observer = None
        for store in self._stores.values():
            store.observer = None

    # -- paths -------------------------------------------------------------

    def _task_dir(self, task_id: str) -> str:
        return os.path.join(self.opt.data_dir, "tasks", task_id[:3], task_id)

    # -- registration ------------------------------------------------------

    def register_task(self, metadata: TaskStoreMetadata) -> LocalTaskStore:
        store = self._stores.get(metadata.task_id)
        if store is not None:
            if store.metadata.invalid:
                # A failed attempt poisoned this store; retries must start
                # clean rather than resume over untrusted pieces.
                self.delete_task(metadata.task_id)
            else:
                store.touch()
                return store
        store = LocalTaskStore.create(self._task_dir(metadata.task_id), metadata)
        store.on_open = self._note_open
        self._stores[metadata.task_id] = store
        if self.observer is not None:
            store.observer = self.observer
            self.observer.task_updated(store)
        return store

    def _note_open(self, store: LocalTaskStore) -> None:
        """A store opened its data file (any thread). Over the budget, the
        few that opened theirs longest ago close them, but for those in
        use: pinned, or touched within the last second (the GC's own rule
        for an idle fd, at a shorter idle), which go to the back."""
        with self._opened_lock:
            self._opened.append(store)
            over = len(self._opened) - MAX_OPEN_FILES
            oldest = [self._opened.popleft() for _ in range(min(over, 8))]
        now = time.time()
        for old in oldest:
            if old._fd is None:
                continue        # closed since (gc, destroy)
            if old.pinned or now - old.metadata.last_access < 1.0:
                with self._opened_lock:
                    self._opened.append(old)
            else:
                old.close()

    def get(self, task_id: str) -> LocalTaskStore:
        store = self._stores.get(task_id)
        if store is None:
            raise StorageError(f"task {task_id} not registered", Code.StorageTaskNotFound)
        return store

    def try_get(self, task_id: str) -> LocalTaskStore | None:
        return self._stores.get(task_id)

    def delete_task(self, task_id: str) -> None:
        store = self._stores.pop(task_id, None)
        if store is not None:
            store.destroy()
            if self.observer is not None:
                self.observer.task_deleted(task_id)

    def tasks(self) -> list[LocalTaskStore]:
        return list(self._stores.values())

    # -- unified read path (serve-side zero-copy) --------------------------
    # Task-id-addressed shapes over LocalTaskStore's preadv primitives for
    # serving layers that hold only an id (upload server, gateway). Both
    # pin the store for the duration of the read so GC cannot rmtree the
    # data file mid-preadv.

    def read_piece_into(self, task_id: str, num: int, buf):
        """Read one piece into ``buf``; returns its PieceRecord."""
        with self.get(task_id) as store:
            return store.read_piece_into(num, buf)

    def read_spans_into(self, task_id: str, spans, buf) -> int:
        """Pack byte spans of ``task_id``'s data file into ``buf``;
        returns the total byte count."""
        with self.get(task_id) as store:
            return store.read_spans_into(spans, buf)

    # -- reuse lookups (reference storage_manager.go:529-698) --------------

    def find_completed_task(self, task_id: str) -> LocalTaskStore | None:
        store = self._stores.get(task_id)
        if store is not None and store.metadata.done and not store.metadata.invalid:
            store.touch()
            return store
        return None

    def find_partial_completed_task(self, task_id: str) -> LocalTaskStore | None:
        store = self._stores.get(task_id)
        if store is not None and not store.metadata.invalid and store.metadata.pieces:
            store.touch()
            return store
        return None

    # -- reload (reference storage_manager.go:703-869) ---------------------

    def reload(self) -> int:
        """Restore task stores from disk after a daemon restart. Invalid or
        unreadable dirs are swept. Returns the number of restored tasks."""
        root = os.path.join(self.opt.data_dir, "tasks")
        if not os.path.isdir(root):
            return 0
        restored = 0
        for prefix in os.listdir(root):
            pdir = os.path.join(root, prefix)
            if not os.path.isdir(pdir):
                continue
            for task_id in os.listdir(pdir):
                tdir = os.path.join(pdir, task_id)
                meta_path = os.path.join(tdir, METADATA_FILE)
                try:
                    store = LocalTaskStore.load(tdir)
                except Exception as e:
                    log.warning("sweeping unreadable task dir", dir=tdir, error=str(e))
                    import shutil

                    shutil.rmtree(tdir, ignore_errors=True)
                    continue
                if store.metadata.invalid:
                    store.destroy()
                    continue
                store.on_open = self._note_open
                self._stores[store.metadata.task_id] = store
                restored += 1
        if restored:
            log.info("reloaded task stores", count=restored)
        return restored

    # -- GC (reference storage_manager.go:871-1068) ------------------------

    def gc(self) -> list[str]:
        """TTL sweep + LRU eviction under the disk quota. Returns reclaimed
        task IDs."""
        now = time.time()
        reclaimed: list[str] = []
        for task_id, store in list(self._stores.items()):
            if store.pinned:
                continue  # active download/upload; never yank mid-flight
            m = store.metadata
            if m.invalid or (now - m.last_access) > self.opt.task_ttl:
                self.delete_task(task_id)
                reclaimed.append(task_id)
                continue
            # Idle stores drop their data-file fd (reopened lazily on the
            # next read): without this, a long-lived daemon holds one fd
            # per task it has EVER served until the TTL delete
            # (tests/test_storage.py::test_gc_closes_idle_store_fds). The
            # native upload server is unaffected: it opens per request.
            idle_close = self.opt.fd_idle_close or self.opt.gc_interval
            if now - m.last_access > idle_close:
                store.close()
        if self.opt.disk_gc_threshold > 0:
            usage = sum(s.disk_usage() for s in self._stores.values())
            if usage > self.opt.disk_gc_threshold:
                # Oldest-access first until under quota.
                by_lru = sorted(self._stores.values(), key=lambda s: s.metadata.last_access)
                for store in by_lru:
                    if usage <= self.opt.disk_gc_threshold:
                        break
                    if store.pinned:
                        continue
                    usage -= store.disk_usage()
                    reclaimed.append(store.metadata.task_id)
                    self.delete_task(store.metadata.task_id)
        if reclaimed:
            log.info("storage gc reclaimed", count=len(reclaimed))
        return reclaimed

    def total_disk_usage(self) -> int:
        return sum(s.disk_usage() for s in self._stores.values())

    def close(self) -> None:
        for store in self._stores.values():
            store.close()
        if not self.opt.keep_storage:
            pass  # data kept on disk; reload() restores on next boot
