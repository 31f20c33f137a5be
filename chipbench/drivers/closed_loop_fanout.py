"""Traffic kind ``closed_loop_fanout``: one job, closed loop; an operation is
ALL the hosts of the deployment asking at once for one fresh task.

Parameters of a traffic file of this kind:
  clients   1: the job
  mode      "cold": every operation a fresh task (new tag), deleted from
            every store afterwards
  trace     how much of the window a traced run covers:
            {"operations": n} or {"seconds": s}
The hosts, their slice and their pod are the configuration's
(``deployment.hosts``, ``.tpu_slice``, ``.idc``).

The layout. ``warm_up`` puts the deployment's labels on host 0 and starts the
other hosts. Host 0 is the embedded daemon that holds the chip: the fabric's
own peer carries no slice (``fabric.py`` builds it, and is no file of this
cell's to edit), so it is stopped and a daemon with the deployment's
``host.tpu_slice`` / ``tpu_worker_index`` / ``idc`` takes its place as
``fabric.daemon``, built the same way otherwise. Hosts 1.. are plain
``python -m dragonfly2_tpu.cli.main daemon`` children (``Fabric.spawn``, so
``Fabric.stop`` and ``log_tails`` cover them) that never import jax, each with
its own config file, work home, store, socket and ports. The hosts' work
homes lie on the machine's memory-backed scratch where it has room
(``hosts_scratch``): one machine's one disk is no part of the deployment.

An operation here is the driver's own, built from the harness's parts and
appended to ``cell.ops``, so that ``Cell.check`` runs unchanged
(``Cell.operation`` cannot be host 0's part: it stops the clock at host 0's
tensors and deletes the task from the seed's store while other hosts may
still pull from it). Timed: all the requests issued in one turn of the event
loop (host 0's ``download_to_device`` -> words ready -> ``load_safetensors``
-> every tensor ready; the others' ``Daemon.Download``, what dfget sends, with
an output path under their own work home, which the store hard-links) -> the
LAST host done. ``op.nbytes`` is the content ONCE: the rate is the shard over
the time until the last host has it, what a job behind a barrier waits for.
Untimed: the flights of every daemon for the task (host 0's ring read in
place; the others' and the seed's through ``Daemon.FlightReport`` with
``raw``), each ring's ``start_wall`` put on this process's clock, kept apart
in ``op.hosts`` with ``aux`` and ``note`` for the readers of the layer
"fan-out between peers" (``layers/fanout_events.py``), host 0's also in
``op.flight`` as the harness keeps a flight; host 0's readings as
``Cell.operation`` takes them; of the other hosts ``Daemon.StatTask`` (done,
the digest the daemon verified) and the stream's ``from_p2p``, and the
benchmark's own per-piece (sum32, xor32) of ONE seeded host's output file
(every host's in a warm-up and in the window's last operation) against the
origin's facts; then the task deleted from every store. A host that fails a
reading puts an entry into ``op.fetched`` that ``objects.matches`` refuses.
``cell.pulls[0]`` grows by ONE an operation, so the check's origin line reads
bytes served over one content an operation.

A program whose ``Daemon.FlightReport`` knows no ``raw`` still runs the cell:
the other daemons' flights are then missing and the ``fanout_*`` readers read
nothing.
"""

from __future__ import annotations

import asyncio
import atexit
import contextlib
import itertools
import os
import shutil
import statistics
import tempfile
import time

import numpy as np

import harness
from fabric import LOOPBACK, wait_for
from origin import piece_checksums


MEMORY_SCRATCH = "/dev/shm"


def hosts_scratch(cell) -> str:
    """Where the hosts' work homes (their stores) live: a directory of its
    own on the machine's memory-backed scratch, removed when the process
    exits; the run's own directory where there is no such scratch with room
    for the operation twice over. One machine's disk stands under every
    store here, where each host of the deployment has its own, and an
    operation writes the content once a host: the disk of the benchmark's
    machine took 16.6 GB an operation at 0.3 GB/s, and ends a command that
    has made its backing file grow by 45 GiB, deleted files included
    (PERF.md section 6, PR 38). The seed's store stays where the fabric
    put it."""
    need = 2 * int(cell.config["deployment"]["hosts"]) * cell.objects.size(0)
    try:
        if shutil.disk_usage(MEMORY_SCRATCH).free >= need:
            path = tempfile.mkdtemp(prefix="chipbench_", dir=MEMORY_SCRATCH)
            atexit.register(shutil.rmtree, path, ignore_errors=True)
            return path
    except OSError:
        pass
    return cell.fabric.home


class Host:
    """One of the hosts 1..: a daemon child, reached over its socket."""

    def __init__(self, scratch: str, index: int):
        self.index = index
        self.name = f"h{index}"
        self.home = os.path.join(scratch, self.name)
        self.sock = os.path.join(self.home, "run", "dfdaemon.sock")
        self.client = None

    def output(self, tag: str) -> str:
        return os.path.join(self.home, "out", tag)

    async def call(self, method: str, body: dict, timeout: float = 30.0):
        return await self.client.call(method, body, timeout=timeout)

    async def download(self, url: str, digest: str, tag: str,
                       pod_broadcast: bool = False) -> dict:
        """What dfget sends; the stream's last message (state ``done``)."""
        from dragonfly2_tpu.proto.common import UrlMeta

        stream = await self.client.open_stream("Daemon.Download", {
            "url": url, "output": self.output(tag),
            "meta": UrlMeta(digest=digest, tag=tag).to_wire(),
            "pod_broadcast": pod_broadcast})
        final = None
        while True:
            msg = await stream.recv(timeout=600)
            if msg is None:
                break
            if msg.get("state") in ("done", "failed"):
                final = msg
        if final is None or final["state"] != "done":
            raise RuntimeError(f"host {self.index}: {final}")
        return final


def labels(cell, index: int) -> dict:
    deployment = cell.config["deployment"]
    return {"ip": LOOPBACK, "hostname": f"bench-host-{index}",
            "idc": deployment["idc"], "tpu_slice": deployment["tpu_slice"],
            "tpu_worker_index": index}


async def relabel_host0(cell) -> None:
    """The embedded daemon again, as the fabric builds it, with the
    deployment's labels."""
    from dragonfly2_tpu.daemon.config import DaemonConfig
    from dragonfly2_tpu.daemon.daemon import Daemon

    fabric = cell.fabric
    old, fabric.daemon = fabric.daemon, None
    await asyncio.wait_for(old.stop(), 30)
    sink = cell.config["deployment"]["sink"]
    cfg = DaemonConfig(work_home=os.path.join(cell.scratch, "h0"))
    for key, value in labels(cell, 0).items():
        setattr(cfg.host, key, value)
    cfg.scheduler.addrs = [f"{LOOPBACK}:{fabric.sched_port}"]
    cfg.tpu_sink.enabled = True
    cfg.tpu_sink.max_tasks = int(sink["max_tasks"])
    cfg.tpu_sink.batch_pieces = int(sink["batch_pieces"])
    fabric.daemon = Daemon(cfg)
    await asyncio.wait_for(fabric.daemon.start(), 60)


async def start_hosts(cell) -> list:
    from dragonfly2_tpu.pkg.types import NetAddr
    from dragonfly2_tpu.rpc import Client

    fabric = cell.fabric
    hosts = [Host(cell.scratch, i)
             for i in range(1, int(cell.config["deployment"]["hosts"]))]
    for host in hosts:
        config = os.path.join(fabric.home, host.name + ".yaml")
        with open(config, "w") as f:
            f.write("host:\n" + "".join(
                f"  {key}: {value}\n"
                for key, value in labels(cell, host.index).items()))
        fabric.spawn(host.name, [
            "-m", "dragonfly2_tpu.cli.main", "daemon", "--config", config,
            "--work-home", host.home,
            "--scheduler", f"{LOOPBACK}:{fabric.sched_port}"])
    for host in hosts:
        await wait_for(
            f"host {host.index}'s daemon socket",
            lambda h=host: fabric.alive(h.name) and os.path.exists(h.sock),
            90)
        host.client = Client(NetAddr.unix(host.sock))
        await host.call("Daemon.Health", {})
    return hosts


def on_this_clock(raw: dict, t0: float, t1: float) -> list:
    """A ``flight.raw`` reply's events inside the operation, on this
    process's perf_counter clock: [(t, name, piece, aux, note)]. Every
    process of the machine anchors its wall clock to the same
    ``time.time()``."""
    from dragonfly2_tpu.pkg import flight as flightlib

    start = time.perf_counter() - (flightlib.anchored_wall()
                                   - raw["start_wall"])
    return [(start + t, name, piece, aux, note)
            for t, name, piece, aux, note in raw["events"]
            if t0 <= start + t <= t1]


async def read_flights(cell, op) -> None:
    """``op.hosts``: a row a daemon, host 0 first, the seed last:
    ``{"host": index or "seed", "flight": events or None,
    "events_dropped": n}``. ``op.flight``: host 0's, as the harness keeps
    one (no notes)."""
    from dragonfly2_tpu.pkg import flight as flightlib
    from dragonfly2_tpu.pkg.types import NetAddr
    from dragonfly2_tpu.rpc import Client

    own = cell.fabric.daemon.task_manager.flight
    if hasattr(own, "sync"):
        own.sync()
    tf = own.get(op.task_id)
    rows = [{"host": 0, "flight": None, "events_dropped": 0}]
    if tf is not None:
        names = flightlib.EVENT_NAMES
        rows[0] = {"host": 0, "events_dropped": tf.events_dropped,
                   "flight": on_this_clock(
                       {"start_wall": tf.start_wall, "events": [
                           (t, names.get(code, str(code)), piece, aux, note)
                           for t, code, piece, aux, note in tf.events()]},
                       op.t0, op.t1)}
        op.flight = [(t, name, piece, aux)
                     for t, name, piece, aux, _ in rows[0]["flight"]]

    async def ask(who, call) -> dict:
        row = {"host": who, "flight": None, "events_dropped": 0}
        try:
            reply = await call("Daemon.FlightReport",
                               {"task_id": op.task_id, "raw": True})
        except Exception as e:
            harness.say(f"no flight from {who}: {type(e).__name__}: {e}")
            return row
        raw = reply.get("raw")
        if raw is not None:
            row["flight"] = on_this_clock(raw, op.t0, op.t1)
            row["events_dropped"] = raw["events_dropped"]
        return row

    seed = Client(NetAddr.unix(cell.fabric.seed_sock))
    try:
        rows += await asyncio.gather(
            *(ask(h.index, h.call) for h in cell.hosts),
            ask("seed", lambda m, b: seed.call(m, b, timeout=30.0)))
    finally:
        await seed.close()
    op.hosts = rows


def file_checksums(path: str, piece_bytes: int) -> np.ndarray:
    """The benchmark's own per-piece (sum32, xor32) of a file, by NumPy."""
    sums = []
    with open(path, "rb") as f:
        while True:
            raw = f.read(piece_bytes)
            if not raw:
                break
            raw += b"\0" * (-len(raw) % 4)
            sums.append(piece_checksums(np.frombuffer(raw, "<u4")))
    return np.asarray(sums, np.uint64).astype(np.uint32).reshape(-1, 2)


async def check_hosts(cell, op, finals: list, every_file: bool) -> list:
    """What hosts 1.. hold, against the origin's facts: one line a fault."""
    facts = await cell.facts_for(0)
    want = np.asarray(facts["checksums"], np.uint64).astype(np.uint32)
    faults = []
    stats = await asyncio.gather(
        *(h.call("Daemon.StatTask", {"task_id": op.task_id})
          for h in cell.hosts), return_exceptions=True)
    for host, final, stat in zip(cell.hosts, finals, stats):
        if isinstance(stat, Exception):
            faults.append(f"host {host.index}: StatTask {stat}")
        elif not (stat["done"] and stat["digest"] == facts["digest"]
                  and stat["content_length"] == facts["length"]):
            faults.append(f"host {host.index}: not done and verified: {stat}")
        if not final.get("from_p2p") or final.get("from_reuse"):
            faults.append(f"host {host.index}: off the P2P path "
                          f"(from_p2p {final.get('from_p2p')}, from_reuse "
                          f"{final.get('from_reuse')})")
    files = cell.hosts if every_file else [
        cell.hosts[int(cell.rng.integers(len(cell.hosts)))]]
    # Side by side: NumPy's reductions release the GIL.
    sums = await asyncio.gather(
        *(asyncio.to_thread(file_checksums, h.output(op.tag),
                            facts["piece_bytes"]) for h in files),
        return_exceptions=True)
    for host, got in zip(files, sums):
        if isinstance(got, OSError):
            faults.append(f"host {host.index}: {got}")
            continue
        if isinstance(got, BaseException):
            raise got
        size = os.path.getsize(host.output(op.tag))
        if size != facts["length"] or got.shape != want.shape:
            faults.append(f"host {host.index}: {size} bytes in its file")
        elif (got != want).any():
            faults.append(
                f"host {host.index}: pieces "
                f"{np.flatnonzero((got != want).any(axis=1)).tolist()} of "
                "its file differ from the generator's")
    op.files_checked = len(files)
    return faults


async def delete_everywhere(cell, op) -> None:
    """The task out of all the stores, the hosts' output links too."""
    async def drop(host) -> None:
        with contextlib.suppress(FileNotFoundError):
            os.unlink(host.output(op.tag))
        for _ in range(20):
            reply = await host.call("Daemon.DeleteTask",
                                    {"task_id": op.task_id}, 10.0)
            if reply.get("ok"):
                return
            await asyncio.sleep(0.1)
        harness.say(f"host {host.index} kept task {op.task_id[:16]}: {reply}")

    await asyncio.gather(*(drop(h) for h in cell.hosts))
    # Last: the seed refuses while a child still reads from it.
    await cell.fabric.delete_everywhere(op.task_id)


async def operation(cell, number: int, *, warmup: bool = False,
                    closing=lambda: False,
                    pod_broadcast: bool = False) -> harness.Op:
    import jax
    from jax.profiler import TraceAnnotation

    from dragonfly2_tpu.client.device import download_to_device

    tag = f"s{cell.seed}-op{number}"
    op = harness.Op(number=number, client=0, object_index=0, tag=tag,
                    warmup=warmup, cold=True)
    digest = (await cell.facts_for(0))["digest"]
    url = cell.fabric.url(0)

    async def host0() -> tuple:
        with TraceAnnotation(f"chipbench:op#{number}"):
            result = await asyncio.wait_for(download_to_device(
                cell.fabric.daemon, url, digest=digest, tag=tag), 600)
            words = jax.block_until_ready(result.as_words())
            v0 = time.perf_counter()
            with TraceAnnotation(f"chipbench:views#{number}"):
                tensors = result.load_safetensors()
                jax.block_until_ready(list(tensors.values()))
            op.views_span = (v0, time.perf_counter())
        return result, words, tensors, time.perf_counter()

    op.t0 = time.perf_counter()
    # One turn of the event loop: no await between the hosts' requests.
    asks = [asyncio.ensure_future(host0())] + [
        asyncio.ensure_future(h.download(url, digest, tag, pod_broadcast))
        for h in cell.hosts]
    done = await asyncio.gather(*asks, return_exceptions=True)
    op.t1 = time.perf_counter()
    errors = [f"host {i}: {type(e).__name__}: {e}"
              for i, e in enumerate(done) if isinstance(e, BaseException)]
    cell.ops.append(op)
    if errors:
        op.error = "; ".join(errors)[:500]
        harness.say(f"operation {number} failed: {op.error}")
        return op
    (result, words, tensors, host0_t1), *finals = done
    # The futures hold host 0's arrays too: let go, so that the readings
    # below free the tensors before the checksums run.
    asks = done = None
    op.host0_s = host0_t1 - op.t0
    op.nbytes = result.content_length
    op.task_id = result.task_id
    op.from_p2p = result.from_p2p and all(f.get("from_p2p") for f in finals)
    op.from_reuse = result.from_reuse or any(f.get("from_reuse")
                                             for f in finals)
    op.piece_bytes = result.sink.sink.piece_size
    piece_words = result.sink.sink.piece_words
    cell.pulls[0] = cell.pulls.get(0, 0) + 1
    del result
    await read_flights(cell, op)
    op.fetched = await asyncio.to_thread(
        cell.objects.fetch, tensors, cell.rng, cell.fetch_whole_first)
    cell.fetch_whole_first = False
    tensors = None
    op.device_checksums = await asyncio.to_thread(
        lambda: np.asarray(harness._checksum_program(piece_words)(words))
        .view(np.uint32))
    del words
    for fault in await check_hosts(cell, op, finals, warmup or closing()):
        harness.say(f"operation {number}: {fault}")
        op.fetched.append(("", None, None, [fault]))
    await delete_everywhere(cell, op)
    op.gap_s = time.perf_counter() - op.t1
    return op


def describe(op) -> str:
    """A row a daemon for a person: seconds after the request of its first
    and last ``landed`` and its ``task_done``; the bytes it took from the
    seed / from fellow hosts / from the origin and its parents
    (``task_sources``); its ``cert_wait`` ms; pieces it served."""
    from layers import fanout_events

    rows = []
    for row in getattr(op, "hosts", None) or []:
        flight = row["flight"]
        if flight is None:
            rows.append(f"{row['host']}: no flight")
            continue
        at = {}
        for t, name, *_ in flight:
            at.setdefault(name, [t, t])[1] = t
        src = fanout_events.sources(flight) or {}
        cert = sum(aux for _, name, _, aux, _ in flight
                   if name == "cert_wait")
        sends = [aux for _, name, _, aux, _ in flight
                 if name == "upload_serve"]
        costs = [aux for _, name, _, aux, _ in flight if name == "landed"]

        def sec(name, last=False):
            return f"{at[name][last] - op.t0:.2f}" if name in at else "-"

        first = "source_landed" if row["host"] == "seed" else "landed"
        rows.append(
            f"{row['host']}: {sec(first)}..{sec(first, True)} done "
            f"{sec('task_done', True)} seed/peer/origin MB "
            f"{src.get('seed_bytes', 0) / 1e6:.0f}/"
            f"{src.get('peer_bytes', 0) / 1e6:.0f}/"
            f"{src.get('origin_bytes', 0) / 1e6:.0f} parents "
            f"{src.get('parents', '-')} cert {cert:.0f}ms piece "
            f"{statistics.median(costs) if costs else 0:.0f}ms served "
            f"{len(sends)}"
            + (f" x {statistics.median(sends):.0f}ms" if sends else ""))
    return "; ".join(rows)


async def warm_up(cell) -> None:
    """The deployment's hosts, then untimed operations until one went the
    cell's path (set-up's race, as ``closed_loop.warm_up`` has it). An
    operation that fails here ends the run."""
    cell.scratch = hosts_scratch(cell)
    usage = shutil.disk_usage(cell.scratch)
    harness.say(f"the hosts' stores under {cell.scratch}: "
                f"{usage.free / 1e9:.1f} GB free of {usage.total / 1e9:.1f}")
    t0 = time.perf_counter()
    await relabel_host0(cell)
    cell.hosts = await start_hosts(cell)
    harness.say(f"host 0 relabelled and hosts 1-{len(cell.hosts)} up in "
                f"{time.perf_counter() - t0:.1f}s")
    for attempt in range(4):
        op = await operation(cell, -1 - attempt, warmup=True)
        if op.error:
            raise RuntimeError("closed_loop_fanout: the warm-up's operation "
                               f"failed: {op.error}")
        harness.say(f"warm-up {-1 - attempt}: {op.seconds:.2f}s (host 0 "
                    f"{op.host0_s:.2f}s), a row a daemon: " + describe(op))
        if op.from_p2p:
            return
        op.raced = True


async def window(cell, seconds: float, traced: bool) -> tuple[float, float]:
    """As ``closed_loop.window``, one client."""
    limit = cell.traffic.get("trace", {}) if traced else {}
    seconds = min(seconds, limit.get("seconds", seconds))
    most = limit.get("operations")
    start = time.perf_counter()
    for n in itertools.count():
        if time.perf_counter() - start >= seconds \
                or (most is not None and n >= most):
            break
        await operation(
            cell, n, closing=lambda n=n: (
                time.perf_counter() - start >= seconds
                or (most is not None and n + 1 >= most)))
    end = time.perf_counter()
    for host in cell.hosts:
        await host.client.close()
    done = [op for op in cell.ops if not op.warmup and not op.error]
    for op in done[-1:]:
        harness.say(f"operation {op.number}: {op.seconds:.2f}s (host 0 "
                    f"{op.host0_s:.2f}s; {op.files_checked} host file(s) "
                    "summed), a row a daemon (first..last landed, done, MB "
                    "by source, parents, cert_wait, a piece's median cost, "
                    "pieces served x a send's median ms): "
                    + describe(op))
    return start, end
