"""The way out of HBM: typed tensors -> the words of ONE safetensors file on
the device -> the host, a group of pieces at a time.

``ops/hbm_sink.py`` lands a file's pieces as flat uint32 words and
``ops/bitview.py`` cuts typed tensors from them; this module is their
inverse, for ``client/device.py`` ``save_from_device``. ``snapshot`` places
every tensor's bytes where the writer's header (``ops/safetensors.plan_file``)
says they lie, in a word buffer of whole pieces that is made on the device
(zeros past the content, as a landing's is), and takes the per-piece
(sum32, xor32) of those words with the checksum a landing uses
(``ops/checksum.py``). From then on the caller's tensors are its own again:
what is saved is the words. ``Snapshot.fetch`` brings a group of pieces to
the host; the host's sums of the bytes it commits are held equal to the
device's by the importer (``daemon/peer/piece_manager.import_pieces``).

The programs. The tensors of one (dtype, shape, alignment) are placed by one
program that takes their word offsets as one int32 vector and the buffer
donated, ``_GROUP_CAP`` of them at most (a training state is a few shapes,
many times each: a rank of a Moonlight layer is 250 tensors of 13 shapes).
A 4-byte tensor's words are its items: a bitcast and the flat form. A 1- or
2-byte tensor's items are joined 4 or 2 to a word by strided slices of its
rows and shifts (``bitview._join_block``'s form: a reshape to (n, 2) has a
minor dimension the TPU pads to 128 lanes). A tensor that begins or ends
inside a word (an odd count of 2-byte items before it, a 1-byte tensor)
shares that word with its neighbour: its program ORs into the zeroed buffer
and does not overwrite. jax has no copy from the device into a caller's
buffer (0.9: ``np.asarray`` of an array allocates), so a group's bytes lie
where the runtime put them and a piece is committed from there: a pooled
buffer would be one more pass over the object.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from dragonfly2_tpu.ops import hbm_sink
from dragonfly2_tpu.pkg import metrics

_GROUP_CAP = 16             # tensors one pack program places
_UINT = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32}

SAVE_BYTES = metrics.counter(
    "device_save_bytes_total",
    "Bytes of device saves (save_from_device): the file's content, what the "
    "device -> host copies brought (d2h), what was copied host to host "
    "between them and the store (copied: none, a piece is committed from "
    "where the runtime put it), what was written into the store (stored)",
    ("kind",))
SAVE_SECONDS = metrics.counter(
    "device_save_seconds",
    "Seconds of device saves: the call until its handle is returned, the "
    "snapshot taken (snapshot: the caller's stall), and the call until the "
    "replicas' acknowledgement (ack)", ("stage",))
SAVE_FAILURES = metrics.counter(
    "device_save_failures_total",
    "Device saves that failed: a piece whose host sums differ from the "
    "device's (mismatch), no acknowledgement from the replicas in time "
    "(replica), anything else (error)", ("reason",))


class SaveError(ValueError):
    """The tensors cannot be saved as they are."""


def _as_words(x, lead: int):
    """The bytes of tensor ``x`` as flat uint32 words, the first byte
    ``lead`` bytes into the first word, zeros before it and behind the
    last."""
    if x.dtype == jnp.bool_:
        x = x.astype(jnp.uint8)
    size = x.dtype.itemsize
    if size == 4:
        return jax.lax.bitcast_convert_type(x, jnp.uint32).reshape(-1)
    ratio = 4 // size
    items = jax.lax.bitcast_convert_type(x, _UINT[size])
    if not lead and x.ndim and x.shape[-1] % ratio == 0:
        # Whole words a row: the rows keep their tiles.
        items = items.reshape(-1, x.shape[-1])
    else:
        items = jnp.pad(items.reshape(-1), (
            lead // size, -(lead // size + items.size) % ratio))
    words = items[..., 0::ratio].astype(jnp.uint32)
    for k in range(1, ratio):
        words = words | (items[..., k::ratio].astype(jnp.uint32)
                         << (8 * size * k))
    return words.reshape(-1)


@functools.partial(jax.jit, static_argnames=("lead", "merge"),
                   donate_argnums=(0,))
def _save_pack_jit(buffer, starts, tensors: tuple, *, lead: int,
                   merge: bool):
    """``buffer`` with each of ``tensors`` placed at word ``starts[i]``.
    ``merge``: the tensors share their first or last word with a
    neighbour, and are ORed into the (zeroed) buffer."""
    for i, x in enumerate(tensors):
        words = _as_words(x, lead)
        if merge:
            words = words | jax.lax.dynamic_slice(
                buffer, (starts[i],), words.shape)
        buffer = jax.lax.dynamic_update_slice(buffer, words, (starts[i],))
    return buffer


@functools.partial(jax.jit, static_argnames=("piece_words",))
def _save_pack_sums_jit(words, *, piece_words: int):
    """Per-piece (sum32[n], xor32[n]) of the packed words: the swap gate's
    program (``hbm_sink._words_checksums_jit``) under the save's name."""
    return hbm_sink._words_checksums_jit(words, piece_words=piece_words)


@functools.partial(jax.jit, static_argnames=("size",))
def _save_group_jit(words, start, *, size: int):
    return jax.lax.dynamic_slice(words, (start,), (size,))


class Snapshot:
    """A save's words on the device, with the device's sums of each piece.
    ``fetch`` is called from one thread at a time."""

    def __init__(self, words, sums: dict, content_length: int,
                 piece_size: int):
        self.words = words
        self.sums = sums                  # piece -> (sum32, xor32)
        self.content_length = content_length
        self.piece_size = piece_size
        self.pieces = len(sums)
        self._ahead: dict = {}

    def _group(self, first: int, count: int):
        got = self._ahead.pop((first, count), None)
        if got is None:
            words = self.piece_size // 4
            got = _save_group_jit(self.words, np.int32(first * words),
                                  size=count * words)
        return got

    def prefetch(self, first: int, count: int) -> None:
        """Start the copy of pieces [first, first + count) to the host."""
        group = self._group(first, count)
        group.copy_to_host_async()
        self._ahead[(first, count)] = group

    def fetch(self, first: int, count: int) -> memoryview:
        """The bytes of pieces [first, first + count) on the host, cut to
        the content."""
        raw = np.asarray(self._group(first, count))
        nbytes = min((first + count) * self.piece_size,
                     self.content_length) - first * self.piece_size
        SAVE_BYTES.labels("d2h").inc(raw.nbytes)
        return memoryview(raw).cast("B")[:nbytes]

    def release(self) -> None:
        self.words = None
        self._ahead.clear()


def _device_of(tensors: dict):
    devices = set()
    for name, x in tensors.items():
        if not isinstance(x, jax.Array) or isinstance(x, jax.core.Tracer):
            raise SaveError(f"{name}: not a jax.Array")
        if x.dtype.itemsize not in _UINT:
            raise SaveError(f"{name}: items of {x.dtype.itemsize} bytes "
                            f"({x.dtype}) cannot be saved")
        devices |= x.devices()
    if len(devices) != 1:
        raise SaveError("the tensors of one save lie on one device; these "
                        f"lie on {sorted(d.id for d in devices)}")
    return next(iter(devices))


def snapshot(tensors: dict, head: bytes, layout: dict, total: int,
             piece_size: int) -> Snapshot:
    """The file ``head`` + the tensors at ``layout``'s offsets (both
    ``safetensors.plan_file``'s) as words on the tensors' device, and the
    device's sums of each piece of ``piece_size``; returns once they are
    taken. ``total`` is the file's length."""
    device = _device_of(tensors)
    piece_words = piece_size // 4
    pieces = -(-total // piece_size)
    with jax.default_device(device):
        buffer = jnp.zeros((pieces * piece_words,), jnp.uint32)
    groups: dict[tuple, list[str]] = {
        (jnp.dtype(jnp.uint32), (len(head) // 4,), 0, False): [""]}
    placed = {"": (0, len(head)), **layout}
    for name, (at, nbytes) in layout.items():
        if nbytes:
            x = tensors[name]
            merge = bool(at % 4 or nbytes % 4)
            groups.setdefault((x.dtype, x.shape, at % 4, merge),
                              []).append(name)
    sources = {"": jax.device_put(np.frombuffer(head, "<u4"), device),
               **tensors}
    for (_, _, lead, merge), members in groups.items():
        for k in range(0, len(members), _GROUP_CAP):
            chunk = members[k:k + _GROUP_CAP]
            starts = np.asarray([placed[n][0] // 4 for n in chunk], np.int32)
            buffer = _save_pack_jit(
                buffer, starts, tuple(sources[n] for n in chunk),
                lead=lead, merge=merge)
    sums, xors = (np.asarray(c) for c in _save_pack_sums_jit(
        buffer, piece_words=piece_words))
    SAVE_BYTES.labels("content").inc(total)
    return Snapshot(buffer, {i: (int(sums[i]), int(xors[i]))
                             for i in range(pieces)}, total, piece_size)
