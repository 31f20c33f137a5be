"""Batch landing: loader samples → fixed-size record batches on device.

The last hop of the dataset plane: instead of a host-side copy loop
(bytes → np.stack → device_put), fixed-size records land through
``ops.hbm_sink.HBMSink`` piece-per-record — each record stages into a
device batch exactly like a P2P piece, the batch is verified ON DEVICE
against host checksums (the same verify-on-land contract as the
``--device=tpu`` sink, daemon/peer/device_sink.py), and the batch
materializes as a ``(batch, record_bytes)`` uint8 device array in one
fused assembly dispatch.

A feed has ONE landing geometry for its whole life: ``batch_size`` rows of
``record_bytes`` rounded up to words, staged ``min(batch_size, 64)`` rows a
stack. Every batch's sink is built for that, whatever the batch holds, so
the assembly and the record view are the programs the first batch
compiled. The epoch's short last batch of ``n`` records lands them in slots
``0..n-1`` and the slots ``n..batch_size-1`` as empty records (all-zero
rows, the host checksum of nothing; ``verify()`` covers them too), and its
array is the first ``n`` rows of the full view, cut on the device by a
plain XLA slice: the one program an epoch's end may still compile, and a
small one. A record is handed to the sink at its own length and padded
where it is written, in its row of the reused staging stack (one copy of
the record, one zero fill of the row's rest, a checksum of the record's own
words: zeros add nothing to sum32 / xor32); the NumPy path pads where it
builds its array, so both give rows that are zero beyond the record.

On a CPU-only JAX backend (``JAX_PLATFORMS=cpu``) — or with no usable
jax at all — the feed degrades to plain NumPy batches (``force_hbm=True``
keeps the sink path for tests and CPU-backend verification). A device
path that FAILS degrades the same way, for the rest of the feed's life;
a feed that was told its device (``device=`` or ``force_hbm=True``) says
so: ``dataset_device_fallbacks_total{cause}``, one error line, and
``DeviceFeed.fell_back`` names the cause, so a caller that needs device
batches can stop (``DeviceBatch.on_device`` is false from then on).

Spans, on the ring handed in as ``flight`` (the loader's,
``PodShardedLoader.flight``; pkg/flight.py says what each carries):
``feed_wait`` a batch, the consumer side's wait for its samples;
``feed_batch`` a batch, first record staged -> as_record_batch
dispatched, its note naming the records (``n=``) beside the rows that were
landed and put (``rows=``, ``put=``: the geometry's); and the batch's
HBMSink stamps its own ``sink_*`` steps there with ``batch=<k>`` leading the
note. ``dataset_device_short_batches_total`` counts the device batches with
``n < rows``: one an epoch whose samples are no multiple of ``batch_size``.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field

from dragonfly2_tpu.dataset.shard_reader import DATASET_BYTES
from dragonfly2_tpu.pkg import dflog, metrics
from dragonfly2_tpu.pkg import flight as flightlib

log = dflog.get("dataset.device_feed")

DEVICE_BATCHES = metrics.counter(
    "dataset_device_batches_total",
    "Record batches produced by the device feed", ("path",))
DEVICE_FALLBACKS = metrics.counter(
    "dataset_device_fallbacks_total",
    "Device feeds that fell to NumPy batches after the device path failed, "
    "by the exception that ended it", ("cause",))
DEVICE_SHORT_BATCHES = metrics.counter(
    "dataset_device_short_batches_total",
    "Device batches of fewer records than the feed's landing geometry has "
    "rows (an epoch's last): landed through the full batch's programs, the "
    "rest of the rows empty, and cut to their records on the device")


class DeviceFeedError(Exception):
    pass


@dataclass
class DeviceBatch:
    """One landed batch: ``array`` is (n, record_bytes) uint8 — a device
    array on the HBM path, np.ndarray on the fallback."""

    keys: list[str]
    array: object
    on_device: bool
    # The shard each record came from (a sample's ``__shard__``): keys
    # are unique within a shard only.
    shards: list[str] = field(default_factory=list)


def _hbm_available() -> bool:
    try:
        import jax

        return jax.default_backend() != "cpu"
    except Exception:
        return False


class DeviceFeed:
    """Consumes a sample iterator (``PodShardedLoader.epoch()``) and
    yields fixed-size record batches of one member extension.

    ``record_bytes``: every record must be exactly this long, unless
    ``pad=True`` (shorter records are zero-padded; longer ones always
    raise — silent truncation would corrupt training data). The final
    short batch is yielded unless ``drop_last``. On the device path it
    costs a full batch's landing (``batch_size`` rows put, the rows past its
    records empty) and one slice program for its row count: no assembly or
    view compile, those are the full batch's (the module docstring).
    """

    def __init__(self, ext: str, record_bytes: int, batch_size: int, *,
                 pad: bool = False, drop_last: bool = False,
                 device=None, force_hbm: bool = False, flight=None):
        if record_bytes <= 0 or batch_size <= 0:
            raise DeviceFeedError("record_bytes and batch_size must be > 0")
        self.ext = ext
        self.record_bytes = record_bytes
        self.batch_size = batch_size
        self.pad = pad
        self.drop_last = drop_last
        self.device = device
        self.use_hbm = force_hbm or _hbm_available()
        # The caller named its device: a fall to NumPy is then loud.
        self.device_asked = force_hbm or device is not None
        self.fell_back = ""     # the cause, once the device path has failed
        self.flight = flight
        self.batch_no = 0       # batches landed so far: the next one's number

    def _record(self, sample: dict) -> bytes:
        data = sample.get(self.ext)
        if data is None:
            raise DeviceFeedError(
                f"sample {sample.get('__key__')!r} has no {self.ext!r} member")
        if len(data) > self.record_bytes:
            raise DeviceFeedError(
                f"sample {sample.get('__key__')!r}: {self.ext} is "
                f"{len(data)}B > record_bytes={self.record_bytes}")
        if len(data) < self.record_bytes and not self.pad:
            raise DeviceFeedError(
                f"sample {sample.get('__key__')!r}: {self.ext} is "
                f"{len(data)}B != record_bytes={self.record_bytes} "
                "(pass pad=True to zero-pad)")
        # At its own length: the row it lands in is where it is padded.
        return data

    def _land_hbm(self, records: list[bytes]) -> "tuple[object, str]":
        import jax

        from dragonfly2_tpu.ops.hbm_sink import HBMSink

        # The feed's one geometry, whatever this batch holds.
        rows, n = self.batch_size, len(records)
        padded = self.record_bytes + ((-self.record_bytes) % 4)
        ring, lead = self.flight, f"batch={self.batch_no}"
        sink = HBMSink(
            padded * rows, padded, device=self.device,
            batch_pieces=min(rows, 64),
            stamp=None if ring is None else (
                lambda code, piece, ms, note="": ring.record(
                    code, piece, ms, f"{lead} {note}" if note else lead)))
        t0 = time.perf_counter()
        for i, rec in enumerate(records):
            sink.land_piece(i, rec)
        for i in range(n, rows):
            sink.land_piece(i, b"")     # an empty record: an all-zero row
        sink.flush()
        t1 = time.perf_counter()
        sink.verify()   # on-device checksums vs host values, every slot
        t2 = time.perf_counter()
        arr = sink.as_record_batch(rows, self.record_bytes)
        if n < rows:
            arr = jax.lax.slice_in_dim(arr, 0, n)
            DEVICE_SHORT_BATCHES.inc()
        t3 = time.perf_counter()
        DATASET_BYTES.labels("device").inc(padded * rows)
        return arr, (f"put={padded * rows} "
                     f"stage={(t1 - t0) * 1e3:.3f} "
                     f"verify={(t2 - t1) * 1e3:.3f} view={(t3 - t2) * 1e3:.3f}")

    def _land_numpy(self, records: list[bytes]):
        import numpy as np

        arr = np.zeros((len(records), self.record_bytes), np.uint8)
        for row, rec in zip(arr, records):
            row[:len(rec)] = np.frombuffer(rec, np.uint8)
        return arr

    def _fall_back(self, e: Exception) -> None:
        """The device path failed: host batches from here on, the input
        pipeline must outlive a sink hiccup. Once, and loudly where the
        caller had named its device."""
        self.use_hbm = False
        self.fell_back = type(e).__name__
        DEVICE_FALLBACKS.labels(self.fell_back).inc()
        (log.error if self.device_asked else log.warning)(
            "HBM batch landing failed; NumPy batches for the rest of this "
            "feed", batch=self.batch_no, cause=self.fell_back,
            error=str(e)[:200], device_asked=self.device_asked)

    def _land(self, keys: list[str], shards: list[str],
              records: list[bytes], payload: int) -> DeviceBatch:
        t0 = time.perf_counter()
        arr = None
        if self.use_hbm:
            try:
                arr, steps = self._land_hbm(records)
            except DeviceFeedError:
                raise
            except Exception as e:
                self._fall_back(e)
        path = "numpy" if arr is None else "hbm"
        if arr is None:
            arr, steps = self._land_numpy(records), "put=0"
        DEVICE_BATCHES.labels(path).inc()
        if self.flight is not None:
            self.flight.record(
                flightlib.EV_FEED_BATCH, self.batch_no,
                (time.perf_counter() - t0) * 1000.0,
                f"path={path} n={len(records)} "
                f"rows={self.batch_size if path == 'hbm' else len(records)} "
                f"payload={payload} {steps}")
        self.batch_no += 1
        return DeviceBatch(keys=keys, array=arr, on_device=path == "hbm",
                           shards=shards)

    async def batches(self, samples):
        """Async generator: sample dicts in → DeviceBatch out."""
        keys: list[str] = []
        shards: list[str] = []
        records: list[bytes] = []
        payload = 0         # the records' bytes before padding
        waited = 0.0
        it = samples.__aiter__()
        while True:
            t0 = time.perf_counter()
            try:
                sample = await it.__anext__()
            except StopAsyncIteration:
                break
            waited += time.perf_counter() - t0
            keys.append(sample.get("__key__", ""))
            shards.append(sample.get("__shard__", ""))
            records.append(self._record(sample))
            payload += len(records[-1])
            if len(records) == self.batch_size:
                self._stamp_wait(waited, len(records))
                yield self._land(keys, shards, records, payload)
                keys, shards, records = [], [], []
                payload, waited = 0, 0.0
        if records and not self.drop_last:
            self._stamp_wait(waited, len(records))
            yield self._land(keys, shards, records, payload)

    def _stamp_wait(self, waited: float, n: int) -> None:
        if self.flight is not None:
            self.flight.record(flightlib.EV_FEED_WAIT, self.batch_no,
                               waited * 1000.0, str(n))
