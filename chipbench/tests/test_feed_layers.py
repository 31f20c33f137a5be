"""The feed cell's names and readers: every name of the cell leads to a file,
the configuration keeps the source's shapes and states the program's own
defaults, the objects module's reference agrees with itself where it can be
checked without the program, the seven readers of the layer "dataset feed" are
worked out by hand on hand-made flight events, they read nothing from an
operation of another driver or from a program that stamps none of their events,
and the rehearsal of the cell on the CPU runs the new driver end to end, with
``correct`` true when nothing is broken and false under each control."""

import importlib
import inspect
import io
import json
import os
import subprocess
import sys
import tarfile
import types

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
sys.path[:0] = [BENCH]

FEED_LAYERS = ("feed_sample_read_ms", "feed_task_fixed_ms",
               "feed_batch_land_ms", "feed_consumer_wait_pct",
               "feed_tasks_per_sample", "feed_pad_x", "feed_batch_roofline")


def config_of(path: str) -> dict:
    with open(os.path.join(REPO, path)) as f:
        return json.load(f)


def test_the_cells_names_resolve():
    m = config_of("BENCHMARK.json")
    cell = next(w for w in m["workloads"] if w["name"] == "feed-records")
    assert cell["chips"] == 1 and cell["traffic"] == "feed-1consumer"
    entry = next(c for c in m["configs"] if c["name"] == cell["config"])
    assert entry["name"] == "laion-feed-b256" and entry["reduced"] == ["shards"]
    on_file = config_of(entry["file"])
    assert set(on_file["reduced"]) == {"shards"}
    # The source's shapes: laion-tar-250m's object block but for the kind and
    # the clip, each stated under ``assumed``.
    source = config_of("chipbench/configs/laion-tar-250m.json")["object"]
    differs = {k for k in source if on_file["object"][k] != source[k]}
    assert differs == {"kind", "jpg_max_bytes"}
    assert on_file["object"]["jpg_max_bytes"] == on_file["feed"]["record_bytes"]
    assert {"jpg_max_bytes", "batch_size", "record_bytes", "shard_bytes",
            "first_sample"} <= set(on_file["assumed"])
    assert on_file["deployment"]["sink"] == config_of(
        "chipbench/configs/laion-tar-250m.json")["deployment"]["sink"]
    assert {"verified_on_device", "bit_identical", "order", "at_most_once",
            "origin_bytes_for_sample_reads", "origin_amplification_max"} <= \
        set(on_file["guarantees"])
    traffic = config_of("chipbench/traffic/" + cell["traffic"] + ".json")
    assert (traffic["kind"], traffic["clients"]) == ("closed_loop_feed", 1)
    assert traffic["trace"] == {"seconds": 6}
    driver = importlib.import_module("drivers." + traffic["kind"])
    assert hasattr(driver, "warm_up") and hasattr(driver, "window")
    listed = {p["name"] for p in m["per_layer"]
              if p.get("workloads") == ["feed-records"]}
    assert listed == set(FEED_LAYERS)
    for p in m["per_layer"]:
        if p["name"] in FEED_LAYERS:
            assert p["layer"] == "dataset feed"
            assert p["moves"] == "resident_MBps"
            assert hasattr(importlib.import_module("layers." + p["name"]),
                           "read")


def test_the_feed_block_states_the_programs_own_defaults():
    from dragonfly2_tpu.dataset import LoaderOptions, PodShardedLoader

    feed = config_of("chipbench/configs/laion-feed-b256.json")["feed"]
    assert feed["interleave"] == LoaderOptions().interleave
    assert feed["extensions"] is LoaderOptions().extensions is None
    assert feed["coalesce_gap"] == inspect.signature(
        PodShardedLoader.__init__).parameters["coalesce_gap"].default
    # README.md's sample.
    assert (feed["ext"], feed["record_bytes"], feed["pad"],
            feed["readahead"]) == ("jpg", 256 * 1024, True, 32)
    assert (feed["num_hosts"], feed["host_id"], feed["shards"],
            feed["batch_size"]) == (1, 0, 12, 256)


# -- the reference, against Python's tarfile and against itself ------------

def tiny_objects(seed: int = 11):
    from origin import load_objects

    return load_objects(config_of("chipbench/rehearsal/tiny-feed-2m.json"),
                        seed)


def test_a_shard_is_a_tar_whose_samples_lie_where_the_reference_says():
    """The generator's bytes read by ``tarfile``, which knows nothing of
    either: every sample has its three members under one key, and a jpg's
    bytes are where tar arithmetic put them."""
    objects = tiny_objects()
    from objects import tar_shard_feed as ref

    for shard in (0, 1):
        content = objects.content(shard)
        assert content.size == objects.size(shard)
        samples = ref.samples_of(objects.members(shard))
        with tarfile.open(fileobj=io.BytesIO(content.tobytes())) as tar:
            members = tar.getmembers()
            assert [m.name for m in members] == [
                name for name, _ in objects.members(shard)]
            by_name = {m.name: m for m in members}
            for number, (key, parts) in enumerate(samples):
                assert set(parts) == {"jpg", "txt", "json"}
                assert key == objects.key(shard, number)
                offset, size = parts["jpg"]
                member = by_name[key + ".jpg"]
                assert (member.offset_data, member.size) == (offset, size)
                assert tar.extractfile(member).read() == \
                    content[offset:offset + size].tobytes()
        assert samples[0][0] == f"shard{shard:06d}-000000000"
        assert len(samples) == objects.samples_a_shard()


def test_an_epoch_is_every_sample_once_and_a_batch_is_its_rows():
    objects = tiny_objects()
    from objects import tar_shard_feed as ref

    count = objects.samples_a_shard()
    plan = objects.plan(0)
    assert sorted(plan) == [(s, k) for s in (0, 1) for k in range(count)]
    assert plan != objects.plan(1) and plan == tiny_objects().plan(0)
    assert plan != tiny_objects(seed=12).plan(0)
    # Two hosts: disjoint, and together the one-host epoch's samples.
    halves = [ref.epoch_plan([count, count], 11, 0, 2, h, 4) for h in (0, 1)]
    assert sorted(halves[0] + halves[1]) == sorted(plan)
    assert not set(halves[0]) & set(halves[1])
    # Interleaving deals from two open shards in turn while both last.
    assert [s for s, _ in plan[:6]] in ([0, 1] * 3, [1, 0] * 3)
    batches = objects.batches_an_epoch()
    assert batches == -(-2 * count // 8)
    last = objects.expected_batch(0, batches - 1)
    assert last.shape == (2 * count - 8 * (batches - 1), 65536)
    rows = objects.expected_batch(0, 0)
    for row, (shard, sample) in zip(rows, plan[:8]):
        offset, size = objects._samples[sample][1]["jpg"]
        assert row[:size].tobytes() == \
            objects.content(shard)[offset:offset + size].tobytes()
        assert not row[size:].any()
    assert objects.payload_bytes(0, 0) == sum(
        objects._samples[k][1]["jpg"][1] for _, k in plan[:8])
    sums = ref.record_checksums(rows)
    words = rows.view("<u4").astype(np.uint64)
    assert sums[:, 0].tolist() == (words.sum(axis=1) & 0xFFFFFFFF).tolist()
    assert sums[3, 1] == np.bitwise_xor.reduce(rows[3].view("<u4"))


# -- the readers, on hand-made flight events --------------------------------

def sample(t, ms, *, src="peer", tasks=1, nbytes=25000, move=1.0, read=0.5):
    return (t, "feed_sample", 0, ms,
            f"src={src} tasks={tasks} bytes={nbytes} task={ms - read:.3f} "
            f"move={move:.3f} read={read:.3f}")


def feed_op(t0, *, wait_ms, land_ms, ready_ms, reads, payload=5_000_000,
            put=64 << 20, n=256, record_bytes=262144, batch=0):
    """An operation: the consumer waits ``wait_ms`` for its samples, the
    batch lands in ``land_ms`` and is ready ``ready_ms`` after that; ``reads``
    are (ms, move, read) of the samples whose events it found."""
    staged = t0 + wait_ms / 1000.0
    landed = staged + land_ms / 1000.0
    feed = [sample(t0 + 0.001 * i, ms, move=move, read=read)
            for i, (ms, move, read) in enumerate(reads)]
    feed += [(staged, "feed_wait", batch, wait_ms, str(n)),
             (staged + 0.001, "sink_stage", 0, 0.2, f"batch={batch}"),
             (landed, "feed_batch", batch, land_ms,
              f"path=hbm n={n} payload={payload} put={put} stage=10.000 "
              "verify=5.000 view=0.100")]
    return types.SimpleNamespace(
        t0=t0, t1=landed + ready_ms / 1000.0, nbytes=payload,
        shape=(n, record_bytes), feed=sorted(feed),
        flight=[e[:4] for e in sorted(feed)], views_span=None)


# Sample reads (ms, move, read): fixed = ms - move - read
#   op A: (10, 2, 1) (20, 4, 1) (30, 3, 2)   -> fixed 7, 15, 25
#   op B: (40, 5, 5) (50, 10, 2)             -> fixed 30, 38
# reads 10 20 30 40 50 -> median 30; fixed 7 15 25 30 38 -> median 25.
# Landing, first record staged -> ready: A 100 + 20, B 200 + 40 -> median 180.
# Wait: (900 + 700) ms of (1020 + 940) ms of operations.
# put / payload: 2 * 64 MiB over 5 MB + 3 MB.
OPS = [feed_op(10.0, wait_ms=900.0, land_ms=100.0, ready_ms=20.0,
               reads=[(10.0, 2.0, 1.0), (20.0, 4.0, 1.0), (30.0, 3.0, 2.0)]),
       feed_op(20.0, wait_ms=700.0, land_ms=200.0, ready_ms=40.0, batch=1,
               payload=3_000_000, reads=[(40.0, 5.0, 5.0), (50.0, 10.0, 2.0)])]
OPS[0].feed[1] = sample(OPS[0].feed[1][0], 20.0, move=4.0, read=1.0, tasks=3)


def run_of(ops, trace=None):
    return types.SimpleNamespace(
        ops=ops, trace=trace, windows=[(op.t0, op.t1) for op in ops],
        peaks={"hbm_bytes_per_s": 819e9})


def read(name, run):
    return importlib.import_module("layers." + name).read(run)


@pytest.mark.parametrize("name, want", [
    ("feed_sample_read_ms", 30.0),
    ("feed_task_fixed_ms", 25.0),
    ("feed_batch_land_ms", 180.0),
    ("feed_consumer_wait_pct", 100.0 * 1.6 / 1.96),
    ("feed_tasks_per_sample", 7 / 5),          # one sample took three tasks
    ("feed_pad_x", 2 * (64 << 20) / 8_000_000),
])
def test_reader_on_hand_made_flights(name, want):
    got = read(name, run_of(OPS))
    assert got is not None and got == pytest.approx(want, rel=1e-9)


def test_the_roofline_counts_the_staged_words_once_and_the_batch_once():
    """Two batches whose programs ran 0.4 ms and 0.6 ms on the chip, inside
    their operations; a run outside any operation is not the feed's."""
    plane = {"XLA Modules": [
        ["jit__assemble_checksum_jit(1)", 10.95, 0.0003],
        ["jit__record_batch_jit(2)", 11.0, 0.0001],
        ["jit__assemble_checksum_jit(1)", 20.8, 0.0005],
        ["jit__record_batch_jit(2)", 20.91, 0.0001],
        ["jit_chipbench_rows_checksum(3)", 11.5, 0.002],
        ["jit__assemble_checksum_jit(1)", 15.0, 0.0004]]}
    trace = {"device": {"/device:TPU:0": plane}, "host": []}
    least = 2 * ((64 << 20) + 256 * 262144) / 819e9
    got = read("feed_batch_roofline", run_of(OPS, trace))
    assert got == pytest.approx(100.0 * least / 0.001, rel=1e-9)
    assert 0 < got < 100
    from layers import feed_batch_roofline

    assert feed_batch_roofline.least_bytes(64 << 20, 64 << 20) == 128 << 20
    assert read("feed_batch_roofline", run_of(OPS)) is None     # no trace


@pytest.mark.parametrize("name", FEED_LAYERS)
@pytest.mark.parametrize("what", ["another_driver", "older_program",
                                  "no_operation"])
def test_reader_reads_nothing_where_nothing_is_stamped(name, what):
    """An operation of ``Cell.operation`` (no ``op.feed``), a program older
    than the events (a feed whose ring holds the sink's steps alone), or no
    operation at all: the line leaves the metric out, and nothing raises."""
    plane = {"XLA Modules": [["jit__assemble_checksum_jit(1)", 50.1, 0.001]]}
    trace = {"device": {"/device:TPU:0": plane}, "host": []}
    ops = {"another_driver": [types.SimpleNamespace(
               t0=50.0, t1=50.6, nbytes=10, views_span=None,
               flight=[(50.1, "register", -1, 0.0),
                       (50.45, "sink_finalize", 0, 450.0)])],
           "older_program": [types.SimpleNamespace(
               t0=50.0, t1=50.6, nbytes=10, views_span=None, shape=(8, 64),
               flight=[(50.2, "sink_stage", 0, 0.1)],
               feed=[(50.2, "sink_stage", 0, 0.1, "")])],
           "no_operation": []}[what]
    assert read(name, run_of(ops, trace)) is None


# -- the rehearsal ---------------------------------------------------------

def rehearse(script: str, *extra: str) -> dict:
    """One whole run of the cell's rehearsal in a process of its own."""
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, script), *extra,
         "--manifest", os.path.join(BENCH, "rehearsal", "manifest-feed.json"),
         "--workload", "tiny-feed-records", "--seed", "2147484031",
         "--seconds", "1", "--trace", "0"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=600, cwd=REPO)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("script, extra, correct", [
    ("run.py", (), True),
    ("tests/control_feed.py", ("--break", "flip"), False),
    ("tests/control_feed.py", ("--break", "swap"), False),
    ("tests/control_feed.py", ("--break", "numpy"), False),
], ids=["sound", "one_byte_of_one_record_flipped", "two_keys_swapped",
        "the_feed_fell_to_numpy"])
def test_the_rehearsal_and_the_controls(script, extra, correct):
    line = rehearse(script, *extra)
    assert line["failed"] == 0 and line["attempted"] >= 3
    assert line["correct"] is correct, line
    assert line["metrics"] == {} and line["device"]["platform"] == "cpu"
