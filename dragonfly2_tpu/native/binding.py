"""ctypes binding over libdfnative.so (see src/dfnative.cc).

Importing this module raises if the library can't be built/loaded; callers
(pkg/digest, storage) catch and fall back to pure Python, mirroring how the
reference loads optional plugins (internal/dfplugin/dfplugin.go:53-55).
ctypes calls release the GIL, so piece hashing/writing runs truly parallel
under the daemon's worker threads.

HANDLE OWNERSHIP CONTRACT (dfhttp connections, dfupload servers): the C
layer resolves a handle to a raw object pointer under its registry mutex
and then RELEASES the mutex for the call's duration — a concurrent
``http_close``/``upload_stop`` on the SAME handle would free the object
under a live call. Each handle therefore has exactly one owner that
sequences its calls and invokes close/stop last, never concurrently with
another call on that handle (connection pool slots in
daemon/peer/piece_downloader; the UploadManager's server handle).
Cross-HANDLE concurrency is unrestricted.
"""

from __future__ import annotations

import array as _array
import ctypes
import os

from dragonfly2_tpu.native import build as _build

if os.environ.get("DF_DISABLE_NATIVE"):
    raise ImportError("native library disabled via DF_DISABLE_NATIVE")

# Import contract: failure to produce/load the library is ALWAYS a clean
# ImportError with a one-line reason — never a CalledProcessError or OSError
# traceback — so the backend ladders (pkg/digest, delta/chunker,
# storage/io_ring) can catch ImportError and fall through.
try:
    _lib = ctypes.CDLL(_build.build())
except _build.BuildUnavailable as e:
    raise ImportError(f"native library unavailable: {e.reason}") from None
except OSError as e:
    raise ImportError(f"native library unavailable: {e}") from None

_lib.df_crc32c.argtypes = [ctypes.c_char_p, ctypes.c_size_t, ctypes.c_uint32]
_lib.df_crc32c.restype = ctypes.c_uint32

_lib.df_write_piece_crc.argtypes = [
    ctypes.c_int, ctypes.c_uint64, ctypes.c_char_p, ctypes.c_size_t,
    ctypes.POINTER(ctypes.c_uint32),
]
_lib.df_write_piece_crc.restype = ctypes.c_int

_lib.df_write_chunk_crc.argtypes = [
    ctypes.c_int, ctypes.c_uint64, ctypes.c_char_p, ctypes.c_size_t,
    ctypes.c_uint32, ctypes.POINTER(ctypes.c_uint32),
]
_lib.df_write_chunk_crc.restype = ctypes.c_int

_lib.df_read_piece_crc.argtypes = [
    ctypes.c_int, ctypes.c_uint64, ctypes.c_char_p, ctypes.c_size_t,
    ctypes.POINTER(ctypes.c_uint32),
]
_lib.df_read_piece_crc.restype = ctypes.c_int64

_lib.df_hash_pieces_crc.argtypes = [
    ctypes.c_int,
    ctypes.POINTER(ctypes.c_uint64), ctypes.POINTER(ctypes.c_uint64),
    ctypes.POINTER(ctypes.c_uint32), ctypes.c_size_t, ctypes.c_int,
]
_lib.df_hash_pieces_crc.restype = ctypes.c_int

_lib.df_copy_range.argtypes = [ctypes.c_int, ctypes.c_int, ctypes.c_uint64]
_lib.df_copy_range.restype = ctypes.c_int

_lib.df_has_hw_crc.argtypes = []
_lib.df_has_hw_crc.restype = ctypes.c_int

_lib.df_http_connect.argtypes = [ctypes.c_char_p, ctypes.c_int, ctypes.c_int]
_lib.df_http_connect.restype = ctypes.c_int64

_lib.df_http_start.argtypes = [
    ctypes.c_int64, ctypes.c_char_p, ctypes.POINTER(ctypes.c_int),
    ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int),
]
_lib.df_http_start.restype = ctypes.c_int64

_lib.df_http_read_to_file.argtypes = [
    ctypes.c_int64, ctypes.c_int, ctypes.c_uint64, ctypes.c_uint64,
    ctypes.POINTER(ctypes.c_uint32),
]
_lib.df_http_read_to_file.restype = ctypes.c_int64

_lib.df_http_fetch_to_file.argtypes = [
    ctypes.c_int64, ctypes.c_char_p, ctypes.c_int, ctypes.c_uint64,
    ctypes.c_int64, ctypes.POINTER(ctypes.c_int),
    ctypes.POINTER(ctypes.c_uint32), ctypes.POINTER(ctypes.c_int),
]
_lib.df_http_fetch_to_file.restype = ctypes.c_int64

_lib.df_http_reusable.argtypes = [ctypes.c_int64]
_lib.df_http_reusable.restype = ctypes.c_int

_lib.df_http_close.argtypes = [ctypes.c_int64]
_lib.df_http_close.restype = None

_lib.df_upload_start.argtypes = [ctypes.c_char_p, ctypes.c_int, ctypes.c_int,
                                 ctypes.c_int]
_lib.df_upload_start.restype = ctypes.c_int64

_lib.df_upload_port.argtypes = [ctypes.c_int64]
_lib.df_upload_port.restype = ctypes.c_int

_lib.df_upload_register_task.argtypes = [
    ctypes.c_int64, ctypes.c_char_p, ctypes.c_char_p, ctypes.c_int64,
    ctypes.c_uint64,
]
_lib.df_upload_register_task.restype = ctypes.c_int

_lib.df_upload_register_piece.argtypes = [
    ctypes.c_int64, ctypes.c_char_p, ctypes.c_uint32, ctypes.c_uint64,
    ctypes.c_uint64,
]
_lib.df_upload_register_piece.restype = ctypes.c_int

_lib.df_upload_unregister_task.argtypes = [ctypes.c_int64, ctypes.c_char_p]
_lib.df_upload_unregister_task.restype = ctypes.c_int

_lib.df_upload_counters.argtypes = [ctypes.c_int64,
                                    ctypes.POINTER(ctypes.c_uint64)]
_lib.df_upload_counters.restype = None

_lib.df_upload_drain.argtypes = [ctypes.c_int64, ctypes.c_char_p,
                                 ctypes.c_int64]
_lib.df_upload_drain.restype = ctypes.c_int64

_lib.df_upload_stop.argtypes = [ctypes.c_int64]
_lib.df_upload_stop.restype = None

_lib.df_chunk_scan.argtypes = [
    ctypes.c_char_p, ctypes.c_uint64, ctypes.c_char_p, ctypes.c_int32,
    ctypes.c_uint64, ctypes.POINTER(ctypes.c_uint32), ctypes.c_uint64,
    ctypes.POINTER(ctypes.c_uint64),
]
_lib.df_chunk_scan.restype = ctypes.c_int64

# Output pointers are typed c_void_p, not POINTER(...): report_decode
# passes raw addresses into one reused scratch buffer (see
# _report_scratch_for), and int -> void* is the cheapest conversion
# ctypes has.
_lib.df_report_decode.argtypes = (
    [ctypes.c_char_p, ctypes.c_uint64, ctypes.c_char_p, ctypes.c_uint64,
     ctypes.c_uint64, ctypes.c_uint64] + [ctypes.c_void_p] * 12)
_lib.df_report_decode.restype = ctypes.c_int64

_lib.df_ring_create.argtypes = [ctypes.c_uint32]
_lib.df_ring_create.restype = ctypes.c_int64

_lib.df_ring_depth.argtypes = [ctypes.c_int64]
_lib.df_ring_depth.restype = ctypes.c_int

_lib.df_ring_read_batch.argtypes = [
    ctypes.c_int64, ctypes.c_int, ctypes.c_uint64,
    ctypes.POINTER(ctypes.c_uint64), ctypes.POINTER(ctypes.c_uint64),
    ctypes.c_char_p, ctypes.POINTER(ctypes.c_uint64),
]
_lib.df_ring_read_batch.restype = ctypes.c_int64

_lib.df_ring_write_batch.argtypes = [
    ctypes.c_int64, ctypes.c_int, ctypes.c_uint64,
    ctypes.POINTER(ctypes.c_uint64), ctypes.POINTER(ctypes.c_uint64),
    ctypes.POINTER(ctypes.c_void_p),
]
_lib.df_ring_write_batch.restype = ctypes.c_int64

_lib.df_ring_close.argtypes = [ctypes.c_int64]
_lib.df_ring_close.restype = None

_lib.df_batch_read.argtypes = [
    ctypes.c_int, ctypes.c_uint64,
    ctypes.POINTER(ctypes.c_uint64), ctypes.POINTER(ctypes.c_uint64),
    ctypes.c_char_p, ctypes.POINTER(ctypes.c_uint64),
]
_lib.df_batch_read.restype = ctypes.c_int64

_lib.df_batch_write.argtypes = [
    ctypes.c_int, ctypes.c_uint64,
    ctypes.POINTER(ctypes.c_uint64), ctypes.POINTER(ctypes.c_uint64),
    ctypes.POINTER(ctypes.c_void_p),
]
_lib.df_batch_write.restype = ctypes.c_int64


def _as_char_buf(data):
    """(arg, nbytes) for a bytes-like without copying: bytes pass through;
    writable buffers (bytearray, memoryview from the receive pool) wrap in
    a ctypes char array sharing their memory — ctypes accepts either where
    a char pointer is declared. Read-only non-bytes views (rare) fall back
    to one copy."""
    if isinstance(data, bytes):
        return data, len(data)
    mv = data if isinstance(data, memoryview) else memoryview(data)
    if mv.readonly:
        b = bytes(mv)
        return b, len(b)
    return (ctypes.c_char * mv.nbytes).from_buffer(mv), mv.nbytes


def crc32c(data, crc: int = 0) -> int:
    buf, n = _as_char_buf(data)
    return _lib.df_crc32c(buf, n, crc)


def has_hw_crc() -> bool:
    return bool(_lib.df_has_hw_crc())


def write_piece_crc(fd: int, offset: int, data) -> int:
    """Fused checksum+pwrite; returns the crc32c of ``data`` (any
    bytes-like; pooled receive buffers land without a bytes() copy)."""
    out = ctypes.c_uint32(0)
    buf, n = _as_char_buf(data)
    rc = _lib.df_write_piece_crc(fd, offset, buf, n, ctypes.byref(out))
    if rc < 0:
        raise OSError(-rc, os.strerror(-rc))
    return out.value


def write_chunk_crc(fd: int, offset: int, data, crc: int = 0) -> int:
    """Seeded fused checksum+pwrite for chunk streams: continues ``crc``
    across calls, so a piece digest assembles while its wire chunks land —
    one memory walk per byte, no separate hash pass."""
    out = ctypes.c_uint32(0)
    buf, n = _as_char_buf(data)
    rc = _lib.df_write_chunk_crc(fd, offset, buf, n, crc, ctypes.byref(out))
    if rc < 0:
        raise OSError(-rc, os.strerror(-rc))
    return out.value


def read_piece_crc_into(fd: int, offset: int, buf) -> tuple[int, int]:
    """Fused pread+checksum into a caller-owned (usually pooled) writable
    buffer — the native half of the unified read path: no per-piece
    allocation, bytes land straight in the recycled view. Returns
    (bytes_read, crc32c)."""
    mv = buf if isinstance(buf, memoryview) else memoryview(buf)
    arr = (ctypes.c_char * mv.nbytes).from_buffer(mv)
    out = ctypes.c_uint32(0)
    n = _lib.df_read_piece_crc(fd, offset, arr, mv.nbytes, ctypes.byref(out))
    if n < 0:
        raise OSError(-n, os.strerror(-n))
    return n, out.value


def read_piece_crc(fd: int, offset: int, size: int) -> tuple[bytes, int]:
    """Fused pread+checksum; returns (data, crc32c). Compatibility shape —
    hot paths use read_piece_crc_into with a pooled buffer."""
    buf = bytearray(size)
    n, crc = read_piece_crc_into(fd, offset, buf)
    return bytes(buf[:n]), crc


def hash_pieces_crc(fd: int, offsets: list[int], sizes: list[int],
                    threads: int = 0) -> list[int]:
    """Parallel per-piece crc32c table over an open file."""
    n = len(offsets)
    if n != len(sizes):
        raise ValueError("offsets/sizes length mismatch")
    if n == 0:
        return []
    off_arr = (ctypes.c_uint64 * n)(*offsets)
    size_arr = (ctypes.c_uint64 * n)(*sizes)
    crc_arr = (ctypes.c_uint32 * n)()
    rc = _lib.df_hash_pieces_crc(fd, off_arr, size_arr, crc_arr, n, threads)
    if rc < 0:
        raise OSError(-rc, os.strerror(-rc))
    return list(crc_arr)


def copy_range(in_fd: int, out_fd: int, length: int) -> None:
    """copy_file_range loop with read/write fallback."""
    rc = _lib.df_copy_range(in_fd, out_fd, length)
    if rc < 0:
        raise OSError(-rc, os.strerror(-rc))


# -- native HTTP engine (src/dfhttp.cc) -------------------------------------

HTTP_E_RESOLVE = -100001
HTTP_E_TIMEOUT = -100002
HTTP_E_CLOSED = -100003
HTTP_E_PROTO = -100004
HTTP_E_UNSUPPORTED = -100005
HTTP_E_BADHANDLE = -100006
HTTP_E_TOOBIG = -100007
HTTP_E_LENMISMATCH = -100008

_HTTP_E_NAMES = {
    HTTP_E_RESOLVE: "resolve failed",
    HTTP_E_TIMEOUT: "timed out",
    HTTP_E_CLOSED: "connection closed",
    HTTP_E_PROTO: "malformed response",
    HTTP_E_UNSUPPORTED: "unsupported encoding",
    HTTP_E_BADHANDLE: "bad handle",
    HTTP_E_TOOBIG: "response head too large",
    HTTP_E_LENMISMATCH: "length mismatch",
}


class NativeHttpError(OSError):
    """A df_http_* call failed; .code is the DF_HTTP_E_* or -errno value."""

    def __init__(self, code: int, where: str):
        self.code = code
        detail = _HTTP_E_NAMES.get(code) or os.strerror(-code)
        super().__init__(-code, f"native http {where}: {detail}")


def _http_check(rc: int, where: str) -> int:
    if rc < 0:
        raise NativeHttpError(rc, where)
    return rc


def http_connect(host: str, port: int, timeout_ms: int = 30000) -> int:
    """TCP connect; returns a connection handle for the df_http_* calls."""
    return _http_check(
        _lib.df_http_connect(host.encode(), port, timeout_ms), "connect")


def http_start(handle: int, head: bytes) -> tuple[int, int, bool]:
    """Send a request head, parse the response head; body left unread.
    Returns (status, content_length, keep_alive); content_length -1 means
    read-until-close (the handle is then single-use)."""
    status = ctypes.c_int(0)
    clen = ctypes.c_int64(-1)
    keep = ctypes.c_int(0)
    _http_check(_lib.df_http_start(handle, head, ctypes.byref(status),
                                   ctypes.byref(clen), ctypes.byref(keep)),
                "start")
    return status.value, clen.value, bool(keep.value)


def http_read_to_file(handle: int, fd: int, offset: int, length: int) -> int:
    """Land exactly `length` body bytes at fd/offset, crc32c fused into the
    single memory walk. Returns the crc."""
    crc = ctypes.c_uint32(0)
    _http_check(_lib.df_http_read_to_file(handle, fd, offset, length,
                                          ctypes.byref(crc)), "read")
    return crc.value


def http_fetch_to_file(handle: int, head: bytes, fd: int, offset: int,
                       expected_len: int = -1) -> tuple[int, int, int, bool]:
    """One request→file exchange. Returns (status, body_len, crc,
    keep_alive); body_len is 0 (nothing landed) for non-200/206 statuses."""
    status = ctypes.c_int(0)
    crc = ctypes.c_uint32(0)
    keep = ctypes.c_int(0)
    n = _http_check(
        _lib.df_http_fetch_to_file(handle, head, fd, offset, expected_len,
                                   ctypes.byref(status), ctypes.byref(crc),
                                   ctypes.byref(keep)), "fetch")
    return status.value, n, crc.value, bool(keep.value)


def http_reusable(handle: int) -> bool:
    return bool(_lib.df_http_reusable(handle))


def http_close(handle: int) -> None:
    """Must be the handle owner's LAST call, never concurrent with another
    call on the same handle (see module HANDLE OWNERSHIP CONTRACT)."""
    _lib.df_http_close(handle)


# -- native upload server (src/dfupload.cc) ---------------------------------

def upload_start(ip: str, port: int, workers: int = 32,
                 concurrent_limit: int = 0) -> int:
    """Start the native piece-serving HTTP server; returns a handle."""
    h = _lib.df_upload_start(ip.encode(), port, workers, concurrent_limit)
    if h < 0:
        raise OSError(-h, os.strerror(-h))
    return h


def upload_port(handle: int) -> int:
    return _lib.df_upload_port(handle)


def upload_register_task(handle: int, task_id: str, data_path: str,
                         content_length: int, piece_size: int) -> None:
    _lib.df_upload_register_task(handle, task_id.encode(),
                                 data_path.encode(), content_length,
                                 piece_size)


def upload_register_piece(handle: int, task_id: str, num: int, offset: int,
                          size: int) -> None:
    _lib.df_upload_register_piece(handle, task_id.encode(), num, offset, size)


def upload_unregister_task(handle: int, task_id: str) -> None:
    _lib.df_upload_unregister_task(handle, task_id.encode())


def upload_counters(handle: int) -> dict:
    out = (ctypes.c_uint64 * 6)()
    _lib.df_upload_counters(handle, out)
    return {"bytes_served": out[0], "ok": out[1], "not_found": out[2],
            "piece_missing": out[3], "throttled": out[4],
            "bad_request": out[5]}


def upload_drain(handle: int) -> list:
    """The sends finished since the last call, oldest first:
    ``(task_id, piece, bytes, end_s, send_ms, wait_ms)`` each, ``end_s`` on
    ``time.perf_counter()``'s clock (CLOCK_MONOTONIC), ``piece`` -1 for a
    Range request."""
    out: list = []
    buf = ctypes.create_string_buffer(1 << 16)
    while True:
        n = _lib.df_upload_drain(handle, buf, len(buf))
        if n <= 0:
            return out
        for line in buf.raw[:n].decode().splitlines():
            task_id, piece, nbytes, end_ns, send_us, wait_us = line.split()
            out.append((task_id, int(piece), int(nbytes), int(end_ns) / 1e9,
                        int(send_us) / 1000.0, int(wait_us) / 1000.0))


def upload_stop(handle: int) -> None:
    """Must be the handle owner's LAST call, never concurrent with another
    call on the same handle (see module HANDLE OWNERSHIP CONTRACT)."""
    _lib.df_upload_stop(handle)


# -- native gear-CDC candidate scanner (src/dfchunk.cc) ----------------------

_CHUNK_OUT_CAP = 65536
_CHUNK_WINDOW = 32


def chunk_scan(region, gear: bytes, mask_bits: int, ctx: int) -> list:
    """Candidate cut positions in ``region`` (any bytes-like): indices of
    bytes whose gear hash has its top ``mask_bits`` zero, skipping the first
    ``ctx`` context bytes. ``gear`` is the 256-entry uint32 table as
    little-endian bytes (delta/chunker owns its derivation). Matches
    delta/chunker._window_hashes bit for bit, including partial windows at
    region start; loops internally when the candidate buffer fills."""
    mv = region if isinstance(region, memoryview) else memoryview(region)
    total = mv.nbytes
    out = (ctypes.c_uint32 * _CHUNK_OUT_CAP)()
    consumed = ctypes.c_uint64(0)
    results: list[int] = []
    base = 0          # offset of the slice passed to C within region
    cur_ctx = ctx
    while True:
        buf, n = _as_char_buf(mv[base:] if base else mv)
        rc = _lib.df_chunk_scan(buf, n, gear, mask_bits, cur_ctx, out,
                                _CHUNK_OUT_CAP, ctypes.byref(consumed))
        if rc < 0:
            raise OSError(-rc, os.strerror(-rc))
        results.extend(base + out[i] for i in range(rc))
        done = base + consumed.value
        if done >= total:
            return results
        # Candidate buffer filled: resume from `done` with a fresh
        # WINDOW-1-byte context replay (hashes only look back 32 bytes).
        start = done - min(done, _CHUNK_WINDOW - 1)
        cur_ctx = done - start
        base = start


# -- packed piece-report batch decoder (src/dfreport.cc) ---------------------

_REPORT_DECODE_ERRORS = {
    -1: "piece-num varint stream truncated",
    -2: "trailing bytes after piece-num stream",
    -3: "negative piece number",
    -4: "column block length mismatch",
    -5: "peer intern index out of range",
}


# One grow-only scratch buffer for all report decodes: creating twelve
# ctypes array TYPES per call ((ctype * n) is a class construction) cost
# more than the decode itself at announce-storm batch sizes. The C side
# fully writes every region it reports (aggs are memset there), so reuse
# is safe; the buffer only ever grows.
_report_scratch: "tuple | None" = None


def _report_scratch_for(n: int, n_peers: int) -> tuple:
    global _report_scratch
    if (_report_scratch is not None and _report_scratch[0] >= n
            and _report_scratch[1] >= n_peers):
        return _report_scratch
    cap_n = max(64, 1 << (n - 1).bit_length()) if n else 64
    cap_p = max(16, 1 << (n_peers - 1).bit_length()) if n_peers else 16
    # 8-byte sections first, then 4-byte, then 2-byte: every column start
    # stays aligned for the memoryview casts below.
    size = 16 * cap_n + 24 * cap_p + 48 + 24 * cap_n + 4 * cap_n
    buf = bytearray(size)
    cbuf = (ctypes.c_char * size).from_buffer(buf)
    _report_scratch = (cap_n, cap_p, buf, ctypes.addressof(cbuf), cbuf)
    return _report_scratch


def report_decode(nums: bytes, cols: bytes, n: int, n_peers: int):
    """Decode a packed pieces_finished batch (proto/reportcodec layout) in
    one native call. Returns (nums, costs, starts, sizes, peer_idx, flags,
    dcn, stall, store, crcs, parent_aggs, totals) — the first ten are
    per-piece lists, parent_aggs is [[count, cost_sum, bytes], ...] per
    interned peer, totals is [cost_total, bytes_total, dcn_ms, stall_ms,
    store_ms, min_cost]. Raises ValueError on malformed input (the ladder
    maps it to reportcodec.CodecError)."""
    cap_n, cap_p, buf, base, _keep = _report_scratch_for(n, n_peers)
    o_nums = 0
    o_start = 8 * cap_n
    o_aggs = o_start + 8 * cap_n
    o_tot = o_aggs + 24 * cap_p
    o_cost = o_tot + 48
    o_size = o_cost + 4 * cap_n
    o_dcn = o_size + 4 * cap_n
    o_stall = o_dcn + 4 * cap_n
    o_store = o_stall + 4 * cap_n
    o_crc = o_store + 4 * cap_n
    o_peer = o_crc + 4 * cap_n
    o_flags = o_peer + 2 * cap_n
    rc = _lib.df_report_decode(
        nums, len(nums), cols, len(cols), n, n_peers,
        base + o_nums, base + o_cost, base + o_start, base + o_size,
        base + o_peer, base + o_flags, base + o_dcn, base + o_stall,
        base + o_store, base + o_crc, base + o_aggs, base + o_tot)
    if rc < 0:
        raise ValueError(_REPORT_DECODE_ERRORS.get(
            rc, f"packed report decode failed ({rc})"))
    mv = memoryview(buf)
    agg_flat = mv[o_aggs:o_aggs + 24 * n_peers].cast("Q").tolist()
    aggs = [agg_flat[3 * p:3 * p + 3] for p in range(n_peers)]

    def col(off: int, width: int, fmt: str):
        # Cold columns (everything the scheduler's bulk apply never
        # touches) come back as int-indexable memoryviews over private
        # snapshots — one memcpy instead of materializing n Python ints
        # that the hot path would throw away. The snapshot matters: the
        # scratch is overwritten by the next decode.
        return memoryview(bytes(mv[off:off + width * n])).cast(fmt)

    out = (mv[o_nums:o_nums + 8 * n].cast("q").tolist(),
           mv[o_cost:o_cost + 4 * n].cast("I").tolist(),
           col(o_start, 8, "Q"),
           col(o_size, 4, "I"),
           col(o_peer, 2, "H"),
           col(o_flags, 2, "H"),
           col(o_dcn, 4, "I"),
           col(o_stall, 4, "I"),
           col(o_store, 4, "I"),
           col(o_crc, 4, "I"),
           aggs,
           mv[o_tot:o_tot + 48].cast("Q").tolist())
    mv.release()
    return out


# -- batched-IO submission ring (src/dfring.cc) ------------------------------

RING_E_SHORT_READ = -200101


class RingShortRead(OSError):
    """A ring read hit EOF inside a requested span (same condition the
    serial read path reports as a StorageError short read)."""

    def __init__(self):
        super().__init__(5, "ring read: EOF inside requested span")


def ring_create(entries: int = 64) -> int:
    """Create an io_uring submission ring; returns a handle. Raises OSError
    (commonly ENOSYS/EPERM) when the kernel refuses io_uring — callers fall
    back down the ladder."""
    h = _lib.df_ring_create(entries)
    if h < 0:
        raise OSError(-h, os.strerror(-h))
    return h


def ring_depth(handle: int) -> int:
    return _lib.df_ring_depth(handle)


def _u64s(values) -> "_array.array":
    """A uint64 array ctypes can pass where POINTER(c_uint64) is declared
    (via from_buffer, no copy) — ~4x cheaper to build than a ctypes array
    for the span-table sizes the submission ring sends per batch."""
    return _array.array("Q", values)


def _u64_arg(arr: "_array.array"):
    return (ctypes.c_uint64 * len(arr)).from_buffer(arr)


def _marshal_read(spans, buf, buf_offsets):
    n = len(spans)
    mv = buf if isinstance(buf, memoryview) else memoryview(buf)
    arr = (ctypes.c_char * mv.nbytes).from_buffer(mv)
    offs = _u64_arg(_u64s(o for o, _ in spans))
    lens = _u64_arg(_u64s(ln for _, ln in spans))
    boffs = _u64_arg(_u64s(buf_offsets))
    return n, offs, lens, arr, boffs


def _check_read_rc(rc: int) -> int:
    if rc == RING_E_SHORT_READ:
        raise RingShortRead()
    if rc < 0:
        raise OSError(-rc, os.strerror(-rc))
    return rc


def _marshal_write(chunks, offsets):
    n = len(chunks)
    # Keep the ctypes views alive for the call's duration.
    kept = [_as_char_buf(c) for c in chunks]
    ptrs = (ctypes.c_void_p * n)()
    lens = (ctypes.c_uint64 * n)()
    for i, (cb, ln) in enumerate(kept):
        if isinstance(cb, bytes):
            ptrs[i] = ctypes.cast(ctypes.c_char_p(cb), ctypes.c_void_p)
        else:
            ptrs[i] = ctypes.cast(cb, ctypes.c_void_p)
        lens[i] = ln
    offs = _u64_arg(_u64s(offsets))
    return n, offs, lens, ptrs, kept


def ring_read_batch(handle: int, fd: int, spans, buf, buf_offsets) -> int:
    """Read ``spans`` ([(offset, length), ...]) of ``fd`` into the writable
    buffer ``buf`` at ``buf_offsets`` with one submission per wave. Returns
    total bytes; raises RingShortRead on EOF inside a span, OSError on IO
    errors. The destination views stay caller-owned (pooled-buffer
    discipline: bytes land in place, nothing is allocated here)."""
    if not spans:
        return 0
    n, offs, lens, arr, boffs = _marshal_read(spans, buf, buf_offsets)
    return _check_read_rc(
        _lib.df_ring_read_batch(handle, fd, n, offs, lens, arr, boffs))


def batch_read(fd: int, spans, buf, buf_offsets) -> int:
    """Same contract as ring_read_batch, but completion is the stateless
    syscall loop in C (df_batch_read) — no ring handle. Fast path for
    page-cache-hot stores (see dfring.cc header)."""
    if not spans:
        return 0
    n, offs, lens, arr, boffs = _marshal_read(spans, buf, buf_offsets)
    return _check_read_rc(_lib.df_batch_read(fd, n, offs, lens, arr, boffs))


def ring_write_batch(handle: int, fd: int, chunks, offsets) -> int:
    """Write each bytes-like in ``chunks`` at its offset in ``fd`` with one
    submission per wave; returns total bytes written. ``offsets`` is one
    file offset per chunk."""
    if not len(chunks):
        return 0
    n, offs, lens, ptrs, _kept = _marshal_write(chunks, offsets)
    rc = _lib.df_ring_write_batch(handle, fd, n, offs, lens, ptrs)
    if rc < 0:
        raise OSError(-rc, os.strerror(-rc))
    return rc


def batch_write(fd: int, chunks, offsets) -> int:
    """Same contract as ring_write_batch via the stateless syscall loop
    (df_batch_write) — no ring handle."""
    if not len(chunks):
        return 0
    n, offs, lens, ptrs, _kept = _marshal_write(chunks, offsets)
    rc = _lib.df_batch_write(fd, n, offs, lens, ptrs)
    if rc < 0:
        raise OSError(-rc, os.strerror(-rc))
    return rc


def ring_close(handle: int) -> None:
    """Must be the handle owner's LAST call, never concurrent with another
    call on the same handle (see module HANDLE OWNERSHIP CONTRACT)."""
    _lib.df_ring_close(handle)
