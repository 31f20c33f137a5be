"""Digest type, parser and hashing readers.

Reference: pkg/digest/digest.go:58-158 (algorithm:encoded string form,
parser, validation) and pkg/digest/digest_reader.go (readers that hash as
they stream). We additionally expose crc32c — used by piece verification on
the TPU-sidecar path — with backend selection in strict preference order
(``crc32c_backend()`` names the one in use):

  1. ``native``  — the C++ engine's SIMD kernel (dragonfly2_tpu/native,
     hardware CRC32C instructions); accepts any buffer zero-copy and
     releases the GIL for the call.
  2. ``google-crc32c`` — the C extension's SIMD kernel; its converter
     only takes read-only bytes, so writable pooled views pay one bounded
     slice-copy.
  3. ``python`` — table-driven pure Python, one table lookup a byte
     (correctness backstop only).

Large buffers hash in bounded slices (``_CRC_SLICE``) so no single C call
holds memory/GIL attention for tens of MB, and the per-slice copies of
backend 2 stay allocator-friendly.
"""

from __future__ import annotations

import hashlib
import re
from dataclasses import dataclass
from typing import BinaryIO, Iterable

ALGORITHM_MD5 = "md5"
ALGORITHM_SHA1 = "sha1"
ALGORITHM_SHA256 = "sha256"
ALGORITHM_SHA512 = "sha512"
ALGORITHM_CRC32C = "crc32c"

_ALGORITHMS = (ALGORITHM_MD5, ALGORITHM_SHA1, ALGORITHM_SHA256, ALGORITHM_SHA512, ALGORITHM_CRC32C)

_ENCODED_RE = {
    ALGORITHM_MD5: re.compile(r"^[a-f0-9]{32}$"),
    ALGORITHM_SHA1: re.compile(r"^[a-f0-9]{40}$"),
    ALGORITHM_SHA256: re.compile(r"^[a-f0-9]{64}$"),
    ALGORITHM_SHA512: re.compile(r"^[a-f0-9]{128}$"),
    ALGORITHM_CRC32C: re.compile(r"^[a-f0-9]{8}$"),
}


class InvalidDigestError(ValueError):
    pass


@dataclass(frozen=True)
class Digest:
    """A digest in ``algorithm:encoded`` string form (reference digest.go:58-76)."""

    algorithm: str
    encoded: str

    def __post_init__(self):
        if self.algorithm not in _ALGORITHMS:
            raise InvalidDigestError(f"unsupported digest algorithm {self.algorithm!r}")
        if not _ENCODED_RE[self.algorithm].match(self.encoded):
            raise InvalidDigestError(f"invalid {self.algorithm} encoded value {self.encoded!r}")

    def __str__(self) -> str:
        return f"{self.algorithm}:{self.encoded}"


def parse(value: str) -> Digest:
    """Parse ``algorithm:encoded`` (reference digest.go:120-158)."""
    algorithm, sep, encoded = value.partition(":")
    if not sep:
        raise InvalidDigestError(f"digest {value!r} missing ':' separator")
    return Digest(algorithm, encoded.lower())


def _crc32c_py(data: bytes, crc: int = 0) -> int:
    """Pure-python CRC-32C (Castagnoli), table-driven fallback."""
    table = _crc32c_table()
    crc ^= 0xFFFFFFFF
    for b in data:
        crc = table[(crc ^ b) & 0xFF] ^ (crc >> 8)
    return crc ^ 0xFFFFFFFF


_CRC32C_TABLE: list[int] | None = None


def _crc32c_table() -> list[int]:
    global _CRC32C_TABLE
    if _CRC32C_TABLE is None:
        poly = 0x82F63B78
        table = []
        for i in range(256):
            crc = i
            for _ in range(8):
                crc = (crc >> 1) ^ poly if crc & 1 else crc >> 1
            table.append(crc)
        _CRC32C_TABLE = table
    return _CRC32C_TABLE


def _native_crc32c():
    try:
        from dragonfly2_tpu.native import binding

        return binding.crc32c
    except Exception:
        return None


def _google_crc32c():
    """google-crc32c's C kernel, adapted to arbitrary buffers. Its argument
    converter only accepts read-only bytes-likes (bytes, not bytearray or
    memoryview), so non-bytes input pays one copy per slice — still ~GB/s
    where the pure-Python table is ~MB/s."""
    try:
        import google_crc32c

        if google_crc32c.implementation != "c":
            return None   # the package's own Python fallback is no faster
        google_crc32c.extend(0, b"probe")
    except Exception:
        return None

    def _impl(data, crc: int = 0) -> int:
        if not isinstance(data, bytes):
            data = bytes(data)
        return google_crc32c.extend(crc, data)

    return _impl


_crc32c_impl = None
_crc32c_backend_name = ""
_CRC_SLICE = 4 << 20


def _select_crc32c():
    global _crc32c_impl, _crc32c_backend_name
    impl = _native_crc32c()
    if impl is not None:
        _crc32c_backend_name = "native"
    else:
        impl = _google_crc32c()
        if impl is not None:
            _crc32c_backend_name = "google-crc32c"
        else:
            impl = _crc32c_py
            _crc32c_backend_name = "python"
    _crc32c_impl = impl
    return impl


def crc32c_backend() -> str:
    """Name of the selected CRC-32C backend (see module docstring for the
    preference order): ``native`` | ``google-crc32c`` | ``python``."""
    if _crc32c_impl is None:
        _select_crc32c()
    return _crc32c_backend_name


def crc32c(data, crc: int = 0) -> int:
    """CRC-32C over any bytes-like, buffer-sliced through the best
    available backend (module docstring: native SIMD > google-crc32c >
    Python table)."""
    impl = _crc32c_impl or _select_crc32c()
    n = data.nbytes if isinstance(data, memoryview) else len(data)
    if n <= _CRC_SLICE:
        return impl(data, crc)
    mv = data if isinstance(data, memoryview) else memoryview(data)
    for off in range(0, n, _CRC_SLICE):
        crc = impl(mv[off:off + _CRC_SLICE], crc)
    return crc


class _Crc32cHasher:
    """hashlib-like interface over crc32c."""

    name = ALGORITHM_CRC32C
    digest_size = 4

    def __init__(self):
        self._crc = 0

    def update(self, data: bytes) -> None:
        self._crc = crc32c(data, self._crc)

    def hexdigest(self) -> str:
        return f"{self._crc:08x}"

    def digest(self) -> bytes:
        return self._crc.to_bytes(4, "big")


def preferred_piece_algorithm() -> str:
    """Per-piece digest algorithm for newly produced pieces: crc32c
    whenever a C-speed backend exists — the native library (fused
    checksum+write, and cheap enough to re-verify on-device —
    ops/checksum.py) or google-crc32c (~11 GB/s vs md5's ~0.6) — else md5
    like the reference (local_storage.go WritePiece)."""
    if crc32c_backend() != "python":
        return ALGORITHM_CRC32C
    return ALGORITHM_MD5


def new_hasher(algorithm: str):
    if algorithm == ALGORITHM_CRC32C:
        return _Crc32cHasher()
    if algorithm in (ALGORITHM_MD5, ALGORITHM_SHA1, ALGORITHM_SHA256, ALGORITHM_SHA512):
        return hashlib.new(algorithm)
    raise InvalidDigestError(f"unsupported digest algorithm {algorithm!r}")


def hash_bytes(algorithm: str, data: bytes) -> Digest:
    h = new_hasher(algorithm)
    h.update(data)
    return Digest(algorithm, h.hexdigest())


def sha256_from_strings(*values: str) -> str:
    """SHA256 over concatenated strings (reference pkg/digest SHA256FromStrings,
    used by idgen task IDs — pkg/idgen/task_id.go:50,81,100)."""
    h = hashlib.sha256()
    for v in values:
        h.update(v.encode("utf-8"))
    return h.hexdigest()


def hash_file(algorithm: str, path: str, chunk_size: int = 4 * 1024 * 1024) -> Digest:
    h = new_hasher(algorithm)
    with open(path, "rb") as f:
        while True:
            chunk = f.read(chunk_size)
            if not chunk:
                break
            h.update(chunk)
    return Digest(algorithm, h.hexdigest())


class HashingReader:
    """Wraps a binary stream, hashing while reading
    (reference pkg/digest/digest_reader.go)."""

    def __init__(self, raw: BinaryIO, algorithm: str = ALGORITHM_MD5):
        self._raw = raw
        self._hasher = new_hasher(algorithm)
        self._algorithm = algorithm

    def read(self, n: int = -1) -> bytes:
        data = self._raw.read(n)
        if data:
            self._hasher.update(data)
        return data

    def digest(self) -> Digest:
        return Digest(self._algorithm, self._hasher.hexdigest())


def verify_chunks(algorithm: str, expected: Digest, chunks: Iterable[bytes]) -> bool:
    h = new_hasher(algorithm)
    for c in chunks:
        h.update(c)
    return h.hexdigest() == expected.encoded
