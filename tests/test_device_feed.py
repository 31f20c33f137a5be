"""``DeviceFeed``'s one landing geometry and the record written once, on the
CPU backend with ``force_hbm=True`` (the one ``HBMSink``): the epoch's short
last batch lands through the full batch's compiled programs, a record is
padded in its row of the reused staging stack, and the NumPy path gives the
same rows.
"""

from __future__ import annotations

import asyncio
import random

import numpy as np
import pytest

from dragonfly2_tpu.dataset import device_feed
from dragonfly2_tpu.dataset.device_feed import DeviceFeed, DeviceFeedError
from dragonfly2_tpu.ops import hbm_sink
from dragonfly2_tpu.pkg import flight

RECORD = 37         # no whole words: the row is 40 bytes


def make_samples(count: int, *, seed: int = 3, least: int = 1,
                 most: int = RECORD, fill: int | None = None) -> list[dict]:
    rng = random.Random(seed)
    return [{"__key__": f"k{i}", "__shard__": "s",
             "jpg": (rng.randbytes(rng.randint(least, most)) if fill is None
                     else bytes([fill]) * rng.randint(least, most))}
            for i in range(count)]


def expected(samples: list[dict], record_bytes: int = RECORD) -> np.ndarray:
    want = np.zeros((len(samples), record_bytes), np.uint8)
    for row, sample in zip(want, samples):
        row[:len(sample["jpg"])] = np.frombuffer(sample["jpg"], np.uint8)
    return want


def batches_of(feed: DeviceFeed, samples: list[dict],
               each=lambda batch: batch) -> list:
    """``each(batch)`` of every batch of one epoch, taken as it is yielded."""
    async def aiter():
        for sample in samples:
            yield sample

    async def run():
        return [each(b) async for b in feed.batches(aiter())]

    return asyncio.run(run())


@pytest.fixture
def sinks(monkeypatch):
    """The ``HBMSink`` a feed builds while the test runs: ``made`` keeps
    every one, each stack is handed to it full of 0xFF (what an earlier batch
    of such records leaves), and ``spoil`` is slot -> the byte of its staged
    row to flip after the host's checksum was taken, before the row is put."""

    class Watched(hbm_sink.HBMSink):
        made: list = []
        spoil: dict = {}

        def __init__(self, *args, **kwargs):
            super().__init__(*args, **kwargs)
            self.made.append(self)

        def _open_stack(self):
            super()._open_stack()
            self._stack[:] = 0xFF

        def flush(self):
            for row, slot in enumerate(self._rows):
                if slot in self.spoil:
                    self._stack[row, self.spoil[slot]] ^= 0x04
            super().flush()

    monkeypatch.setattr(hbm_sink, "HBMSink", Watched)
    return Watched


# -- (a) the short last batch compiles neither assembly nor view ------------

@pytest.mark.parametrize("batch_size, n", [(8, 1), (8, 3), (8, 5), (8, 7),
                                           (72, 37)])
def test_the_short_last_batch_lands_through_the_full_batchs_programs(
        batch_size, n):
    samples = make_samples(batch_size + n, seed=batch_size * 100 + n)
    feed = DeviceFeed("jpg", RECORD, batch_size, pad=True, force_hbm=True)
    (full, *programs_full), (short, *programs_short) = batches_of(
        feed, samples, lambda batch: (
            batch, hbm_sink._assemble_checksum_jit._cache_size(),
            hbm_sink._record_batch_jit._cache_size()))
    assert programs_short == programs_full
    assert full.on_device and short.on_device and not feed.fell_back
    assert full.array.shape == (batch_size, RECORD)
    assert short.array.shape == (n, RECORD)
    assert short.array.dtype == np.uint8
    assert short.array.devices() == full.array.devices()
    assert short.keys == [s["__key__"] for s in samples[batch_size:]]
    np.testing.assert_array_equal(np.asarray(full.array),
                                  expected(samples[:batch_size]))
    np.testing.assert_array_equal(np.asarray(short.array),
                                  expected(samples[batch_size:]))


def test_every_batch_of_a_feeds_life_builds_the_same_sink(sinks):
    """Two stacks a batch (72 rows, 64 a stack), epoch after epoch."""
    feed = DeviceFeed("jpg", RECORD, 72, pad=True, force_hbm=True)
    for epoch in range(2):
        batches_of(feed, make_samples(72 + 5 + epoch, seed=epoch))
    assert len(sinks.made) == 4
    assert {(s.total_pieces, s.piece_size, s.batch_pieces, s.complete(),
             len(s.host_checksums))
            for s in sinks.made} == {(72, 40, 64, True, 72)}


# -- (b) the row is where a record is padded --------------------------------

def test_a_record_is_padded_in_its_row_of_a_dirty_stack(sinks):
    """A batch of full-length 0xFF records, then short ones through the same
    staging stacks: every tail is zero, the device's checksums equal the
    host's, and the host's are of the record's own words."""
    dirty = make_samples(8, least=RECORD, fill=0xFF)
    short = make_samples(8 + 3, seed=11, most=RECORD - 5)
    feed = DeviceFeed("jpg", RECORD, 8, pad=True, force_hbm=True)
    first, = batches_of(feed, dirty)
    assert np.asarray(first.array).min() == 0xFF
    full, last = batches_of(feed, short)
    assert full.on_device and last.on_device and not feed.fell_back
    np.testing.assert_array_equal(np.asarray(full.array), expected(short[:8]))
    np.testing.assert_array_equal(np.asarray(last.array), expected(short[8:]))
    for sink, records in zip(sinks.made[1:], (short[:8], short[8:])):
        assert sink._verified
        for slot in range(8):
            own = records[slot]["jpg"] if slot < len(records) else b""
            words = own + b"\0" * (-len(own) % 4)
            assert sink.host_checksums[slot] == hbm_sink.checksum_numpy(
                np.frombuffer(words, np.uint8))
    assert sinks.made[2].host_checksums[7] == (0, 0)     # an empty record's


# -- (c) verify() covers every slot of a short batch ------------------------

@pytest.mark.parametrize("slot", [1, 6], ids=["a_records_slot",
                                              "an_empty_slot"])
def test_a_corrupted_staged_row_of_a_short_batch_fails_verify(sinks, slot):
    feed = DeviceFeed("jpg", RECORD, 8, pad=True, force_hbm=True)
    records = [s["jpg"] for s in make_samples(3)]
    sinks.spoil[slot] = 9
    with pytest.raises(ValueError, match=f"piece {slot} corrupt in HBM"):
        feed._land_hbm(records)
    # Through the feed the same batch falls to NumPy, loudly, and is right.
    short, = batches_of(feed, make_samples(3))
    assert feed.fell_back == "ValueError" and not short.on_device
    np.testing.assert_array_equal(short.array, expected(make_samples(3)))


# -- (d) the NumPy path gives the same rows; the errors and drop_last stand -

@pytest.mark.parametrize("path", ["hbm", "numpy", "fell_back_mid_epoch"])
def test_every_path_gives_the_same_rows_and_shapes(path, monkeypatch):
    samples = make_samples(8 + 8 + 5, seed=23)
    feed = DeviceFeed("jpg", RECORD, 8, pad=True, force_hbm=path != "numpy")
    if path == "fell_back_mid_epoch":
        sound = DeviceFeed._land_hbm

        def land_hbm(self, records):
            if self.batch_no >= 1:
                raise MemoryError("out of HBM")
            return sound(self, records)

        monkeypatch.setattr(DeviceFeed, "_land_hbm", land_hbm)
    batches = batches_of(feed, samples)
    assert [b.on_device for b in batches] == {
        "hbm": [True] * 3, "numpy": [False] * 3,
        "fell_back_mid_epoch": [True, False, False]}[path]
    assert [b.array.shape for b in batches] == [(8, RECORD), (8, RECORD),
                                                (5, RECORD)]
    for k, batch in enumerate(batches):
        np.testing.assert_array_equal(np.asarray(batch.array),
                                      expected(samples[8 * k:8 * k + 8]))


@pytest.mark.parametrize("force_hbm", [True, False], ids=["hbm", "numpy"])
@pytest.mark.parametrize("member, pad", [("short", False), ("long", False),
                                         ("long", True)])
def test_a_member_of_the_wrong_length_still_raises(force_hbm, member, pad):
    samples = make_samples(3, least=RECORD)
    samples[1]["jpg"] = b"x" * (RECORD - 1 if member == "short"
                                else RECORD + 1)
    feed = DeviceFeed("jpg", RECORD, 2, pad=pad, force_hbm=force_hbm)
    with pytest.raises(DeviceFeedError, match="k1"):
        batches_of(feed, samples)


@pytest.mark.parametrize("force_hbm", [True, False], ids=["hbm", "numpy"])
def test_drop_last_yields_no_short_batch(force_hbm):
    feed = DeviceFeed("jpg", RECORD, 4, pad=True, drop_last=True,
                      force_hbm=force_hbm)
    batches = batches_of(feed, make_samples(4 + 4 + 3))
    assert [b.array.shape for b in batches] == [(4, RECORD)] * 2


# -- (e) the tracing says the geometry engaged ------------------------------

@pytest.mark.parametrize("force_hbm", [True, False], ids=["hbm", "numpy"])
def test_the_ring_names_records_and_rows_and_the_counter_rises_once_an_epoch(
        force_hbm):
    ring = flight.TaskFlight("dataset-feed:test")
    feed = DeviceFeed("jpg", RECORD, 8, pad=True, force_hbm=force_hbm,
                      flight=ring)
    short_before = device_feed.DEVICE_SHORT_BATCHES._value.get()
    put_before = device_feed.DATASET_BYTES.labels("device")._value.get()
    epochs = [make_samples(8 + 8 + 3, seed=e) for e in range(2)]
    for samples in epochs:
        batches_of(feed, samples)
    notes = [dict(part.split("=") for part in note.split())
             for _, code, _, _, note in ring.events()
             if code == flight.EV_FEED_BATCH]
    assert [int(f["n"]) for f in notes] == [8, 8, 3] * 2
    payload = [sum(len(s["jpg"]) for s in samples[at:at + 8])
               for samples in epochs for at in (0, 8, 16)]
    assert [int(f["payload"]) for f in notes] == payload
    if force_hbm:
        # What was landed and put is the geometry's, whatever the batch held.
        assert [f["path"] for f in notes] == ["hbm"] * 6
        assert [int(f["rows"]) for f in notes] == [8] * 6
        assert [int(f["put"]) for f in notes] == [8 * 40] * 6
    else:
        assert [f["path"] for f in notes] == ["numpy"] * 6
        assert [int(f["rows"]) for f in notes] == [8, 8, 3] * 2
        assert [int(f["put"]) for f in notes] == [0] * 6
    assert device_feed.DEVICE_SHORT_BATCHES._value.get() - short_before \
        == (2 if force_hbm else 0)
    assert device_feed.DATASET_BYTES.labels("device")._value.get() \
        - put_before == (6 * 8 * 40 if force_hbm else 0)
