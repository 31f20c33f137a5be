"""Traffic kind ``closed_loop_feed``: ONE consumer taking record batches from
``dataset.device_feed.DeviceFeed`` fed by ``dataset.PodShardedLoader`` on the
embedded daemon, its next batch asked for when the last one is ready; an
operation is one batch.

Parameters of a traffic file of this kind:
  clients          1: the feed is one consumer's
  mode             "feed" (the harness wants the key; neither of its modes)
  epoch            the epoch the window starts in, from its first sample
  warm_up_batches  batches taken before the window (the compiles)
  kept_batches     batches the consumer keeps on the device, the newest
  fetch_share      share of the window's batches fetched back whole for the
                   comparison, drawn from the cell's seeded generator, the
                   first and the last always, at most ``fetch_most``
  trace            how much of the window a traced run covers: {"seconds": s}
The shards, the loader's options and the feed's are the configuration's
(``feed``), handed to the program as they stand.

Set-up (``warm_up``): once the scheduler is seen to send the seed for a
ranged task (``seed_known``), ``PodShardedLoader.over_daemon(task manager, the
shards' URLs, tag).prepare()`` pulls every shard cold through the fabric,
once, streaming it into the indexer and leaving it whole in the peer's store;
the origin's ``/stats`` are read; then the warm-up's batches. Timed: the
consumer's request (``__anext__`` of ``feed.batches(loader.epoch(e))``) -> the
``(batch_size, record_bytes)`` array ready on the chip
(``block_until_ready``). ``op.nbytes`` is the jpg payload of the batch, the
bytes asked for, not the padding. Untimed, after each operation: the events
the feed's flight ring gained (``op.feed``, with their notes; ``op.flight`` the
same without, for the harness's lines); the benchmark's own (sum32, xor32) of
every row, taken on the device in plain jax.numpy; where the draw says so the
whole batch fetched back.

The comparison is the driver's own (``check``, put in ``Cell.check``'s place:
the harness's compares whole objects by piece), against the objects module's
reference alone: every row's checksum and every fetched batch byte for byte
against ``expected_batch``; every batch's (shard, key) in the reference's
order; no sample twice in an epoch; every batch ``on_device``, on the cell's
chip, from a feed that never fell to NumPy; the origin's bytes between set-up's
end and the window's, and a shard over its length.

A program whose loader cannot be handed shards by URL on an embedded daemon
cannot run the cell: this module refuses to load there, before the fabric's
daemon starts, and ``warm_up`` raises when set-up or a batch of its own fails,
so such a run ends at once with no last line.
"""

from __future__ import annotations

import asyncio
import collections
import functools
import time

import numpy as np

import harness
from dragonfly2_tpu.dataset import LoaderOptions, PodShardedLoader
from dragonfly2_tpu.dataset.device_feed import DeviceFeed
from layers import feed_events
from objects.tar_shard_feed import record_checksums

if not hasattr(PodShardedLoader, "over_daemon"):
    raise RuntimeError(
        "closed_loop_feed: this program's PodShardedLoader knows a shard only "
        "as (Dfstore, bucket, key) behind an object gateway; it cannot be "
        "handed URLs on the embedded daemon that holds the chip")


@functools.lru_cache(maxsize=None)
def _rows_checksum_program(records: int, record_bytes: int):
    """The benchmark's own (sum32, xor32) of every row as little-endian
    uint32 words, on the device, in plain jax.numpy: byte ``j`` of each word
    summed and xor-ed apart (a reshape to (.., 4) would pad the minor
    dimension to 128 lanes), then put together; the sums wrap as uint32."""
    import jax
    import jax.numpy as jnp

    def chipbench_rows_checksum(rows):
        sums = jnp.zeros((records,), jnp.uint32)
        xors = jnp.zeros((records,), jnp.uint32)
        for j in range(4):
            lane = rows[:, j::4].astype(jnp.uint32)
            sums = sums + (jnp.sum(lane, axis=1, dtype=jnp.uint32) << (8 * j))
            xors = xors | (jax.lax.reduce(
                lane, jnp.uint32(0), jax.lax.bitwise_xor, (1,)) << (8 * j))
        return jnp.stack([sums, xors], axis=1)

    return jax.jit(chipbench_rows_checksum)


def read_ring(cell, op) -> None:
    """What the feed's ring gained since the last reading, on this
    process's perf_counter clock."""
    from dragonfly2_tpu.pkg import flight as flightlib

    tf = cell.loader.flight
    if tf is None:
        return
    events, cell.ring_seen = tf.tail(cell.ring_seen)
    start = time.perf_counter() - (flightlib.anchored_wall() - tf.start_wall)
    names = flightlib.EVENT_NAMES
    op.feed = sorted((start + t, names.get(code, str(code)), piece, aux, note)
                     for t, code, piece, aux, note in events)
    op.flight = [event[:4] for event in op.feed]


async def batches_of(cell):
    """(epoch, batch number in it, DeviceBatch), epoch after epoch."""
    epoch = int(cell.traffic["epoch"])
    while True:
        k = 0
        samples = cell.loader.epoch(epoch)
        records = cell.feed.batches(samples)
        try:
            async for batch in records:
                yield epoch, k, batch
                k += 1
        finally:
            # Closed with the stream: the read-ahead's tasks end here, not
            # when the collector finds the generators.
            await records.aclose()
            await samples.aclose()
        harness.say(f"the consumer outran epoch {epoch} ({k} batches) and "
                    f"goes on to epoch {epoch + 1}")
        epoch += 1


async def operation(cell, number: int, *, warmup: bool = False,
                    closing=lambda: False) -> harness.Op:
    """One batch, timed by the host clock until its array is ready on the
    chip; then, untimed, the benchmark's readings."""
    import jax
    from jax.profiler import TraceAnnotation

    op = harness.Op(number=number, client=0, object_index=0,
                    tag=cell.tag, warmup=warmup, cold=False)
    op.feed = []
    batch = None
    op.t0 = time.perf_counter()
    try:
        with TraceAnnotation(f"chipbench:op#{number}"):
            op.epoch, op.batch, batch = await asyncio.wait_for(
                cell.stream.__anext__(), 600)
            jax.block_until_ready(batch.array)
        op.t1 = time.perf_counter()
    except Exception as e:  # a failed operation is counted, not fatal
        op.t1 = time.perf_counter()
        op.error = f"{type(e).__name__}: {e}"[:500]
        harness.say(f"operation {number} failed: {op.error}")
    cell.ops.append(op)
    if op.error:
        return op
    read_ring(cell, op)
    op.keys = list(zip(batch.shards, batch.keys))
    op.nbytes = cell.objects.payload_bytes(op.epoch, op.batch) \
        if len(op.keys) == len(cell.objects.planned(op.epoch, op.batch)) \
        else 0
    array = batch.array
    op.on_chip = bool(batch.on_device) and not cell.feed.fell_back \
        and getattr(array, "devices", lambda: set())() == {cell.chip}
    op.shape = tuple(array.shape)
    if op.on_chip:
        op.device_checksums = await asyncio.to_thread(
            lambda: np.asarray(_rows_checksum_program(*op.shape)(array))
            .view(np.uint32))
    else:
        # Host rows: nothing of the device to read a checksum from; the
        # rows themselves are compared.
        op.device_checksums = None
    always = warmup or number == 0 or closing() or not op.on_chip
    drawn = cell.rng.random() < float(cell.traffic["fetch_share"]) \
        and cell.drawn < int(cell.traffic["fetch_most"]) - 2
    if always or drawn:
        op.fetched = [await asyncio.to_thread(np.asarray, array)]
        cell.drawn += not always
    # The consumer's own: the newest batches stay on the device, as a
    # training step's input and the one being prepared do.
    cell.kept.append(array)
    del batch, array
    op.gap_s = time.perf_counter() - op.t1
    return op


async def seed_known(cell) -> None:
    """Set-up's race, as ``closed_loop.warm_up`` has it: the seed's socket is
    up before the scheduler has its announce, and a pull asked for in that
    moment is sent back to the source. A shard that the peer pulled so is
    whole in the peer's store and not in the seed's, and the scheduler still
    sends the seed for every sample of it: the seed takes each from the origin
    (seed 2147484107 of PR 46's first set: four shards, 40.6 MB of sample
    reads). So set-up first asks for 512 bytes of shard 0 as a ranged task of
    its own tag until one comes from the seed."""
    from dragonfly2_tpu.dataset import DaemonRangeFetcher

    buf = memoryview(bytearray(512))
    for attempt in range(40):
        src, _, _ = await DaemonRangeFetcher(
            cell.fabric.daemon.task_manager, cell.urls[0],
            tag=f"{cell.tag}-seed-known-{attempt}").fetch_into(0, 512, buf)
        if src == "peer":
            if attempt:
                harness.say(f"set-up: the seed was known to the scheduler at "
                            f"the {attempt + 1}th asking")
            return
        await asyncio.sleep(0.25)
    raise RuntimeError("closed_loop_feed: the scheduler never sent the seed "
                       "for a ranged task")


async def warm_up(cell) -> None:
    """Set-up's cold pull and index of every shard, the origin's count after
    it, then the warm-up's batches. Anything that fails here ends the run."""
    import jax

    feed = cell.config["feed"]
    objects = cell.objects
    daemon = cell.fabric.daemon
    cell.check = functools.partial(check, cell)
    cell.tag = f"s{cell.seed}-feed"
    cell.chip = jax.devices()[0]
    cell.ring_seen = 0
    cell.drawn = 0
    cell.kept = collections.deque(maxlen=int(cell.traffic["kept_batches"]))
    cell.urls = [cell.fabric.url(i) for i in range(objects.shards)]
    cell.loader = PodShardedLoader.over_daemon(
        daemon.task_manager, cell.urls, tag=cell.tag,
        options=LoaderOptions(
            seed=cell.seed, num_hosts=int(feed["num_hosts"]),
            host_id=int(feed["host_id"]), interleave=int(feed["interleave"]),
            readahead=int(feed["readahead"]),
            extensions=(None if feed["extensions"] is None
                        else tuple(feed["extensions"]))),
        coalesce_gap=int(feed["coalesce_gap"]))
    await seed_known(cell)
    t0 = time.perf_counter()
    await asyncio.wait_for(cell.loader.prepare(), 900)
    cell.stats_after_setup = await asyncio.to_thread(
        cell.fabric.origin_json, "/stats")
    harness.say(
        f"set-up: {objects.shards} shards pulled cold and indexed in "
        f"{time.perf_counter() - t0:.1f}s: {cell.loader.num_samples} samples "
        f"({[i.num_samples for i in cell.loader.indexes]}), a host's epoch "
        f"{len(cell.loader.plan(int(cell.traffic['epoch'])))} samples in "
        f"{objects.batches_an_epoch()} batches of {feed['batch_size']}")
    cell.feed = DeviceFeed(
        feed["ext"], int(feed["record_bytes"]), int(feed["batch_size"]),
        pad=bool(feed["pad"]), device=cell.chip, flight=cell.loader.flight,
        # The rehearsal's backend is the CPU, where a feed takes the sink's
        # path only when told to.
        force_hbm=cell.chip.platform == "cpu")
    cell.stream = batches_of(cell)
    for n in range(int(cell.traffic["warm_up_batches"])):
        op = await operation(cell, n - int(cell.traffic["warm_up_batches"]),
                             warmup=True)
        if op.error:
            raise RuntimeError("closed_loop_feed: the warm-up's batch "
                               f"failed: {op.error}")


async def window(cell, seconds: float, traced: bool) -> tuple[float, float]:
    """As ``closed_loop.window``, one client."""
    limit = cell.traffic.get("trace", {}) if traced else {}
    seconds = min(seconds, limit.get("seconds", seconds))
    start = time.perf_counter()
    number = 0
    while time.perf_counter() - start < seconds:
        await operation(
            cell, number,
            closing=lambda: time.perf_counter() - start >= seconds)
        number += 1
    end = time.perf_counter()
    # The read-ahead must not go on pulling while the check reads the
    # origin's count.
    await cell.stream.aclose()
    cell.kept.clear()
    done = [op for op in cell.ops if not op.warmup and not op.error]
    if done:
        harness.say(describe(done))
        harness.say(last_tasks(cell))
    return start, end


def last_tasks(cell) -> str:
    """Inside a sample's task, for a person: the recorder still holds the
    rings of the last hundred-odd ranged tasks; the median ms between their
    events, in the order they fall."""
    from dragonfly2_tpu.pkg import flight as flightlib

    steps = ("register", "scheduled", "request", "landed", "task_done")
    rows = []
    for row in cell.fabric.daemon.task_manager.flight.summary():
        tf = cell.fabric.daemon.task_manager.flight.get(row["task_id"])
        if tf is None or tf.state != "done" or tf is cell.loader.flight:
            continue
        at = {}
        for t, code, _, _, _ in tf.events():
            at.setdefault(flightlib.EVENT_NAMES.get(code), t * 1000.0)
        if all(name in at for name in steps):
            rows.append([at[steps[0]]] + [at[b] - at[a] for a, b
                                          in zip(steps, steps[1:])])
    if not rows:
        return "no finished ranged task's ring is left to read"
    medians = [feed_events.median(col) for col in zip(*rows)]
    return (f"inside a sample's task, medians of the last {len(rows)}, ms: "
            f"start->register {medians[0]:.1f}, " + ", ".join(
                f"{a}->{b} {ms:.1f}"
                for a, b, ms in zip(steps, steps[1:], medians[1:])))


def describe(done) -> str:
    """Where a batch's time went, for a person: medians over the window's
    operations and samples, from the ring's notes."""
    run = type("Run", (), {"ops": done})
    med = feed_events.median

    def of(name: str, field: str) -> float:
        found = med(float(f[field]) for _, _, f, _ in
                    feed_events.events(run, name) if field in f)
        return float("nan") if found is None else found

    samples = feed_events.events(run, "feed_sample")
    if not samples:
        return "the feed's ring holds no feed_sample event"
    return (
        f"a batch, medians of {len(done)}: request->ready "
        f"{med(op.seconds for op in done) * 1e3:.1f} ms = waiting for "
        f"samples {med(a for _, a, _, _ in feed_events.events(run, 'feed_wait')):.1f}"
        f" + landing {med(a for _, a, _, _ in feed_events.events(run, 'feed_batch')):.1f}"
        f" (stage {of('feed_batch', 'stage'):.1f}, verify "
        f"{of('feed_batch', 'verify'):.1f}, view {of('feed_batch', 'view'):.1f})"
        f"; a sample's read, medians of {len(samples)}: "
        f"{med(a for _, a, _, _ in samples):.2f} ms (moving its bytes in the "
        f"task {of('feed_sample', 'move'):.2f}, store->buffer "
        f"{of('feed_sample', 'read'):.2f}); {sum(op.nbytes for op in done)} "
        f"payload bytes in {sum(len(op.keys) for op in done)} samples; "
        f"sources {sources_of(done)}")


def sources_of(ops) -> dict:
    """src -> sample reads, from the ring's ``feed_sample`` notes."""
    run = type("Run", (), {"ops": ops})
    return dict(collections.Counter(
        f.get("src") for _, _, f, _ in feed_events.events(run, "feed_sample")))


async def check(cell) -> tuple[bool, list[str]]:
    """Every number compared, beside its limit; ``correct`` is all of them
    inside their limits."""
    objects = cell.objects
    done = [op for op in cell.ops if not op.error]
    lines: list[str] = []
    bad_rows = rows = bad_fetched = fetched = out_of_order = 0
    seen: dict = {}
    twice = 0
    # In the reference's order, so that it makes each shard's bytes once.
    for op in sorted(done, key=lambda op: (op.epoch, op.batch)):
        want_keys = objects.expected_keys(op.epoch, op.batch)
        got_keys = [(cell.urls.index(shard) if shard in cell.urls else -1, key)
                    for shard, key in op.keys]
        out_of_order += sum(a != b for a, b in zip(got_keys, want_keys)) \
            + abs(len(got_keys) - len(want_keys))
        for sample in got_keys:
            where = (op.epoch, *sample)
            twice += where in seen
            seen[where] = True
        want = await asyncio.to_thread(objects.expected_batch, op.epoch,
                                       op.batch)
        rows += len(want)
        for whole in op.fetched:
            fetched += 1
            bad_fetched += not (whole.shape == want.shape
                                and np.array_equal(whole, want))
        if op.device_checksums is not None:
            sums = record_checksums(want)
            bad_rows += len(want) if op.device_checksums.shape != sums.shape \
                else int((op.device_checksums != sums).any(axis=1).sum())
        elif not op.fetched or op.fetched[0].shape != want.shape:
            bad_rows += len(want)
        else:
            bad_rows += int((op.fetched[0] != want).any(axis=1).sum())
    lines.append(f"rows whose checksum, taken on the device, differs from the "
                 f"reference's: {bad_rows} of {rows} in {len(done)} batches "
                 "(limit 0)")
    lines.append(f"batches fetched back whole that differ from the "
                 f"reference's expected_batch: {bad_fetched} of {fetched} "
                 "(limit 0)")
    lines.append(f"keys out of the planned order: {out_of_order} of {rows} "
                 "(limit 0)")
    lines.append(f"samples seen twice in an epoch: {twice} of {len(seen)} "
                 "(limit 0)")
    off_chip = sum(not op.on_chip for op in done)
    lines.append(f"batches not on the device (on_device, the sink's verify, "
                 f"the array on {cell.chip}): {off_chip} of {len(done)} "
                 f"(limit 0); the feed fell back: "
                 f"{cell.feed.fell_back or 'never'}")
    stats = await asyncio.to_thread(cell.fabric.origin_json, "/stats")
    before = sum(s["bytes"] for s in cell.stats_after_setup.values())
    sample_reads = sum(s["bytes"] for s in stats.values()) - before
    sources = sources_of(done)
    lines.append(f"origin bytes for sample reads (the origin's count after "
                 f"the window less after set-up): {sample_reads} (limit 0); "
                 f"sample reads by source: {sources}")
    shares = [stats.get(str(i), {}).get("bytes", 0) / objects.size(i)
              for i in range(objects.shards)]
    limit = cell.config["guarantees"]["origin_amplification_max"]
    lines.append(f"origin bytes a shard over its length: least "
                 f"{min(shares):.4f}, most {max(shares):.4f} (limits 1, "
                 f"{limit})")
    ok = (bool(done) and bad_rows == 0 and bad_fetched == 0 and fetched > 0
          and out_of_order == 0 and twice == 0 and off_chip == 0
          and sample_reads == 0 and 1.0 <= min(shares)
          and max(shares) <= limit)
    return ok, lines

