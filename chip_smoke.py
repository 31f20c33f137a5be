"""Chip smoke: the served path once, on the TPU, at one real shard size.

    python chip_smoke.py            # one chip: phases A (client API) and B (CLI)
    python chip_smoke.py --chips 4  # four chips: mesh, ring and all-gather only

What is pulled: a safetensors file made from a seed inside the run —
``model.embed_tokens`` plus the whole first MoE layer of Moonlight-16B-A3B at
its published widths in bf16, one tensor per expert matrix, DeepSeek-V3
names: about 1.7 GiB in 205 tensors, and behind them two small tensors of
random bytes that are not the model's (every bit pattern, through the
integer views). Through what: a byte-counting HTTP origin, a scheduler and
a seed-peer daemon, each a child process that never imports jax, and the
chip-holding peer daemon embedded in THIS process (the only one that
touches jax). What is checked: the content came over P2P with the origin
serving it about once, every piece verified on device, every tensor is a
TPU array of its published dtype and shape, sampled tensors are bit-exact
against the generator (the weights are finite normal numbers: the chip
canonicalises NaNs and flushes denormals in 16-bit floats), and ``dfget
--device tpu`` exits 0 on a landing and non-zero when the sink cannot land.

The last line of stdout is one JSON object; ``"ok": true`` only on a TPU.
The phases are plain functions taking the object's widths, so the tests
run them at a tiny size on the CPU backend (tests/test_chip_smoke.py).
"""

from __future__ import annotations

import argparse
import asyncio
import contextlib
import dataclasses
import functools
import gc
import hashlib
import json
import os
import shutil
import signal
import socket
import struct
import subprocess
import sys
import time
import urllib.request

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
SEED = 20260926
LOOPBACK = "127.0.0.1"


@dataclasses.dataclass(frozen=True)
class Widths:
    """The widths of one checkpoint. Depth is not here: the object is the
    embedding and ONE MoE layer, whatever the model's depth."""

    hidden: int
    vocab: int
    routed: int          # routed experts
    shared: int          # shared experts (one fused MLP of shared * expert)
    expert: int          # moe_intermediate_size
    kv_lora: int
    heads: int
    nope: int
    rope: int
    v: int


# moonshotai/Moonlight-16B-A3B config.json (model-configs catalog entry).
MOONLIGHT = Widths(hidden=2048, vocab=163840, routed=64, shared=2,
                   expert=1408, kv_lora=512, heads=16, nope=128, rope=64,
                   v=128)
REDUCED = {"layers": "27 -> 1 (layer 1, the first MoE layer; layer 0 and "
                     "layers 2-26, the final norm and lm_head are left out)",
           "widths": "published, untouched",
           "weights": f"random finite normal values, seed {SEED}; after "
                      "them two tensors of random BYTES (U16, U8) that are "
                      "not the model's, so that every bit pattern passes "
                      "through a view that is exact on the chip"}


def tensor_table(w: Widths) -> list[tuple[str, str, tuple[int, ...]]]:
    """(name, safetensors dtype, shape), name-sorted as the file stores
    them."""
    layer = "model.layers.1."
    rows = [
        ("model.embed_tokens.weight", "BF16", (w.vocab, w.hidden)),
        (layer + "input_layernorm.weight", "BF16", (w.hidden,)),
        (layer + "post_attention_layernorm.weight", "BF16", (w.hidden,)),
        (layer + "self_attn.q_proj.weight", "BF16",
         (w.heads * (w.nope + w.rope), w.hidden)),
        (layer + "self_attn.kv_a_proj_with_mqa.weight", "BF16",
         (w.kv_lora + w.rope, w.hidden)),
        (layer + "self_attn.kv_a_layernorm.weight", "BF16", (w.kv_lora,)),
        (layer + "self_attn.kv_b_proj.weight", "BF16",
         (w.heads * (w.nope + w.v), w.kv_lora)),
        (layer + "self_attn.o_proj.weight", "BF16",
         (w.hidden, w.heads * w.v)),
        (layer + "mlp.gate.weight", "BF16", (w.routed, w.hidden)),
        (layer + "mlp.gate.e_score_correction_bias", "F32", (w.routed,)),
    ]
    mlps = [(f"mlp.experts.{e}.", w.expert) for e in range(w.routed)]
    mlps.append(("mlp.shared_experts.", w.shared * w.expert))
    for prefix, width in mlps:
        rows += [
            (layer + prefix + "gate_proj.weight", "BF16", (width, w.hidden)),
            (layer + prefix + "up_proj.weight", "BF16", (width, w.hidden)),
            (layer + prefix + "down_proj.weight", "BF16", (w.hidden, width)),
        ]
    # Not the model's. The weights above are finite normal numbers, as
    # weights are; these two carry every bit pattern, NaN payloads and
    # denormals included, through the integer views, which are exact on
    # the chip (see CheckpointObject.tensor_bytes). Odd counts, so that
    # the views end off a word; they sort after the model, at the end of
    # the file.
    rows += [("smoke.random_bits.u16", "U16", (w.hidden * 512 + 1,)),
             ("smoke.random_bits.u8", "U8", (w.hidden * 256 + 3,))]
    return sorted(rows)


ITEM_BYTES = {"BF16": 2, "F32": 4, "U16": 2, "U8": 1}


class CheckpointObject:
    """The safetensors file, never held whole by this process: each
    tensor's bytes come back from (seed, index) on demand."""

    def __init__(self, w: Widths, seed: int = SEED):
        self.seed = seed
        self.tensors = tensor_table(w)
        self.spans: dict[str, tuple[int, int]] = {}
        header = {}
        at = 0
        for name, dtype, shape in self.tensors:
            size = int(np.prod(shape)) * ITEM_BYTES[dtype]
            header[name] = {"dtype": dtype, "shape": list(shape),
                            "data_offsets": [at, at + size]}
            self.spans[name] = (at, at + size)
            at += size
        raw = json.dumps(header, separators=(",", ":")).encode()
        # Trailing spaces are legal header padding. Writers that pad align
        # the data to 8; writers that do not leave it anywhere. Start the
        # data 2 bytes into a word, so that every view here is cut at an
        # offset that is aligned for bf16 and not for the word buffer.
        raw += b" " * ((2 - (8 + len(raw))) % 4)
        self.head = struct.pack("<Q", len(raw)) + raw
        self.data_start = len(self.head)
        self.length = self.data_start + at

    def tensor_bytes(self, name: str) -> bytes:
        """Float tensors are random weights that are weights: finite,
        normal numbers of magnitude 2**-27 .. 2**5, every sign and
        mantissa bit random. Not random bytes: one 16-bit pattern in 128
        is a NaN or a denormal, and the TPU rewrites both (NaN payloads
        to 0x7fc0, denormals to zero) in any op that produces a 16-bit
        float, even a row slice — seen on the chip, PR 22. The integer
        tensors are random bytes, every pattern among them."""
        index, (_, dtype, _) = next(
            (i, t) for i, t in enumerate(self.tensors) if t[0] == name)
        begin, end = self.spans[name]
        raw = np.random.default_rng([self.seed, index]).bytes(end - begin)
        if dtype not in ("BF16", "F32"):
            return raw
        uint, mantissa = {"BF16": (np.uint16, 7), "F32": (np.uint32, 23)}[dtype]
        bits = np.frombuffer(raw, np.dtype(uint).newbyteorder("<"))
        exponent = uint(100) + ((bits >> uint(mantissa)) & uint(0x1F))
        keep = uint(~(0xFF << mantissa) & np.iinfo(uint).max)
        return ((bits & keep) | (exponent << uint(mantissa))).tobytes()

    def chunks(self):
        yield self.head
        for name, _, _ in self.tensors:
            yield self.tensor_bytes(name)

    @functools.cached_property
    def sha256(self) -> str:
        h = hashlib.sha256()
        for chunk in self.chunks():
            h.update(chunk)
        return "sha256:" + h.hexdigest()


@functools.lru_cache(maxsize=2)
def checkpoint(widths: Widths) -> CheckpointObject:
    """One object per widths: its digest is a pass over every byte."""
    return CheckpointObject(widths)


def say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


# ---------------------------------------------------------------------- #
# Origin child: range-serving HTTP on loopback, counting bytes served.
# ---------------------------------------------------------------------- #

def origin_main(argv: list[str]) -> int:
    """Entry of the origin child (``python -c 'import chip_smoke; ...'``):
    builds the object from the widths it is given and serves it at
    /model.safetensors, a small second object at /small.bin, and its
    counters at /stats."""
    from aiohttp import web

    from dragonfly2_tpu.pkg.piece import Range

    widths, port_file = Widths(**json.loads(argv[0])), argv[1]
    obj = checkpoint(widths)
    content = bytearray(obj.length)
    at = 0
    for chunk in obj.chunks():
        content[at:at + len(chunk)] = chunk
        at += len(chunk)
    blobs = {"/model.safetensors": bytes(content),
             "/small.bin": small_object()}
    del content
    stats = {path: {"bytes": 0, "requests": 0} for path in blobs}

    async def blob(request):
        body, stat = blobs[request.path], stats[request.path]
        stat["requests"] += 1
        hdr = request.headers.get("Range")
        if not hdr:
            stat["bytes"] += len(body)
            return web.Response(body=body, headers={"Accept-Ranges": "bytes"})
        r = Range.parse_http(hdr, len(body))
        data = body[r.start:r.start + r.length]
        stat["bytes"] += len(data)
        return web.Response(status=206, body=data, headers={
            "Accept-Ranges": "bytes",
            "Content-Range":
                f"bytes {r.start}-{r.start + len(data) - 1}/{len(body)}"})

    async def serve():
        app = web.Application()
        for path in blobs:
            app.router.add_get(path, blob)
        app.router.add_get("/stats", lambda _: web.json_response(stats))
        runner = web.AppRunner(app, access_log=None)
        await runner.setup()
        site = web.TCPSite(runner, LOOPBACK, 0)
        await site.start()
        port = site._server.sockets[0].getsockname()[1]
        with open(port_file + ".tmp", "w") as f:
            f.write(str(port))
        os.replace(port_file + ".tmp", port_file)
        if "jax" in sys.modules:
            raise RuntimeError("the origin child imported jax")
        await asyncio.Event().wait()

    asyncio.run(serve())
    return 0


def small_object() -> bytes:
    return np.random.default_rng([SEED, 1 << 20]).bytes((1 << 20) + 13)


# ---------------------------------------------------------------------- #
# The fabric: origin, scheduler and seed peer as children; the peer that
# holds the device embedded in this process.
# ---------------------------------------------------------------------- #

class SmokeFailure(Exception):
    pass


def free_port() -> int:
    with socket.socket() as s:
        s.bind((LOOPBACK, 0))
        return s.getsockname()[1]


def accepts(port: int) -> bool:
    with socket.socket() as s:
        s.settimeout(0.5)
        return s.connect_ex((LOOPBACK, port)) == 0


async def wait_for(what: str, ready, deadline_s: float):
    """Poll ``ready()`` until it returns something true; a wait that
    expires says which one it was."""
    end = time.monotonic() + deadline_s
    while time.monotonic() < end:
        got = ready()
        if got:
            return got
        await asyncio.sleep(0.1)
    raise SmokeFailure(f"wait expired after {deadline_s:.0f}s: {what}")


class Fabric:
    def __init__(self, home: str, widths: Widths):
        self.home = home
        self.widths = widths
        self.children: dict[str, subprocess.Popen] = {}
        self.daemon = None
        self.origin_port = 0

    def log_path(self, name: str) -> str:
        return os.path.join(self.home, f"{name}.log")

    def spawn(self, name: str, argv: list[str]) -> None:
        env = dict(os.environ, PYTHONPATH=HERE, DF_HOME=self.home)
        with open(self.log_path(name), "wb") as logf:
            self.children[name] = subprocess.Popen(
                [sys.executable, *argv], cwd=HERE, env=env, stdout=logf,
                stderr=subprocess.STDOUT, start_new_session=True)

    def alive(self, name: str) -> bool:
        if self.children[name].poll() is not None:
            raise SmokeFailure(
                f"{name} exited with code {self.children[name].returncode}")
        return True

    async def start(self) -> None:
        from dragonfly2_tpu.daemon.config import DaemonConfig
        from dragonfly2_tpu.daemon.daemon import Daemon

        os.makedirs(self.home, exist_ok=True)
        port_file = os.path.join(self.home, "origin.port")
        self.spawn("origin", [
            "-c", "import sys, chip_smoke; "
                  "sys.exit(chip_smoke.origin_main(sys.argv[1:]))",
            json.dumps(dataclasses.asdict(self.widths)), port_file])
        sched_port = free_port()
        sched_cfg = os.path.join(self.home, "scheduler.yaml")
        with open(sched_cfg, "w") as f:
            f.write(f"server:\n  advertise_ip: {LOOPBACK}\n")
        self.spawn("scheduler", [
            "-m", "dragonfly2_tpu.cli.main", "scheduler", "--config",
            sched_cfg, "--host", LOOPBACK, "--port", str(sched_port)])
        await wait_for("scheduler port",
                       lambda: self.alive("scheduler") and accepts(sched_port),
                       60)
        # Every role advertises loopback through config it already reads:
        # the sealed machine has no route for the daemon's UDP-connect
        # guess to find, and no interface worth advertising.
        seed_home = os.path.join(self.home, "seed")
        seed_cfg = os.path.join(self.home, "seed.yaml")
        with open(seed_cfg, "w") as f:
            f.write(f"host:\n  ip: {LOOPBACK}\n  hostname: smoke-seed\n")
        self.spawn("seed", [
            "-m", "dragonfly2_tpu.cli.main", "daemon", "--config", seed_cfg,
            "--work-home", seed_home, "--seed-peer",
            "--scheduler", f"{LOOPBACK}:{sched_port}"])
        await wait_for(
            "seed daemon socket",
            lambda: self.alive("seed") and os.path.exists(
                os.path.join(seed_home, "run", "dfdaemon.sock")), 60)
        # The origin builds the whole object before it binds.
        await wait_for(
            "origin port file (the origin generates the object first)",
            lambda: self.alive("origin") and os.path.exists(port_file), 300)
        with open(port_file) as f:
            self.origin_port = int(f.read())

        cfg = DaemonConfig(work_home=os.path.join(self.home, "peer"))
        cfg.host.ip = LOOPBACK
        cfg.host.hostname = "smoke-peer"
        cfg.scheduler.addrs = [f"{LOOPBACK}:{sched_port}"]
        cfg.tpu_sink.enabled = True
        # One sink slot: what bounds the HBM this run can hold, and what
        # phase B fills to make a landing fail.
        cfg.tpu_sink.max_tasks = 1
        self.daemon = Daemon(cfg)
        await asyncio.wait_for(self.daemon.start(), 60)

    def url(self, path: str) -> str:
        return f"http://{LOOPBACK}:{self.origin_port}{path}"

    def origin_stats(self) -> dict:
        with urllib.request.urlopen(self.url("/stats"), timeout=10) as r:
            return json.load(r)

    async def dfget(self, url: str, *extra: str, deadline_s: float = 600):
        """The CLI as a user runs it, against the embedded daemon's socket
        (same work home, --no-daemon). Returns (exit code, stderr)."""
        proc = await asyncio.create_subprocess_exec(
            sys.executable, "-m", "dragonfly2_tpu.cli.main", "dfget", url,
            "--device", "tpu", "--no-daemon",
            "--work-home", self.daemon.config.work_home, *extra,
            cwd=HERE, env=dict(os.environ, PYTHONPATH=HERE),
            stdout=asyncio.subprocess.PIPE, stderr=asyncio.subprocess.STDOUT)
        try:
            out, _ = await asyncio.wait_for(proc.communicate(), deadline_s)
        except asyncio.TimeoutError:
            proc.kill()
            raise SmokeFailure(f"wait expired after {deadline_s:.0f}s: "
                               f"dfget {url}") from None
        return proc.returncode, out.decode(errors="replace")

    async def stop(self) -> None:
        if self.daemon is not None:
            with contextlib.suppress(Exception):
                await asyncio.wait_for(self.daemon.stop(), 30)
        for proc in self.children.values():
            if proc.poll() is None:
                with contextlib.suppress(ProcessLookupError):
                    os.killpg(proc.pid, signal.SIGTERM)
        for proc in self.children.values():
            try:
                proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                with contextlib.suppress(ProcessLookupError):
                    os.killpg(proc.pid, signal.SIGKILL)
                proc.wait()

    def log_tails(self, n: int = 1500) -> str:
        tails = []
        for name in self.children:
            try:
                with open(self.log_path(name), errors="replace") as f:
                    tails.append(f"--- {name}.log (tail)\n{f.read()[-n:]}")
            except OSError:
                tails.append(f"--- {name}.log: unreadable")
        return "\n".join(tails)


def require(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def device_memory(device) -> dict:
    stats = device.memory_stats() or {}
    return {k: stats.get(k) for k in ("bytes_in_use", "peak_bytes_in_use")}


class CompileMeter:
    """Counts what jax compiles, and for how long, from its own monitoring
    events (a persistent-cache hit is not a compile)."""

    def __init__(self):
        import jax.monitoring

        self.compiles = 0
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, seconds: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.seconds += seconds

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def line(self) -> str:
        return (f"compile requests: {self.compiles} in {self.seconds:.1f}s, "
                f"of which persistent-cache hits: {self.cache_hits} (a hit "
                f"costs its read)")


# ---------------------------------------------------------------------- #
# Phases
# ---------------------------------------------------------------------- #

async def land_object(fabric: Fabric, obj: CheckpointObject, digest: str):
    """Pull the object origin -> seed -> this peer -> device sink."""
    from dragonfly2_tpu.client.device import download_to_device

    t0 = time.monotonic()
    result = await asyncio.wait_for(
        download_to_device(fabric.daemon, fabric.url("/model.safetensors"),
                           digest=digest), 900)
    seconds = time.monotonic() - t0
    served = fabric.origin_stats()["/model.safetensors"]["bytes"]
    sink = result.sink
    say(f"landed {result.content_length} bytes in {seconds:.1f}s "
        f"(request to verified-resident): from_p2p={result.from_p2p} "
        f"from_reuse={result.from_reuse} origin_bytes_served={served} "
        f"({served / obj.length:.3f}x content) pieces={len(sink.landed)} x "
        f"{sink.sink.piece_size} B on {sink.sink.platform}/"
        f"{sink.sink.device_kind}")
    require(result.content_length == obj.length, "landed length differs")
    require(result.from_p2p, "content did not come over P2P (from_p2p false)")
    require(obj.length <= served <= 1.1 * obj.length,
            f"origin served {served} bytes for a {obj.length}-byte object: "
            "not about once")
    require(sink.verified and sink.sink.verify()
            and len(sink.landed) == sink.sink.total_pieces,
            "HBMSink.verify() did not pass for every piece")
    return result


async def phase_a(fabric: Fabric, widths: Widths, device) -> None:
    """Client API: download_to_device -> DeviceResult -> load_safetensors."""
    import jax

    obj = checkpoint(widths)
    digest = await asyncio.to_thread(getattr, obj, "sha256")
    result = await land_object(fabric, obj, digest)
    require(result.sink.sink.device == device,
            f"sink landed on {result.sink.sink.device}, not {device}")

    t0 = time.monotonic()
    tensors = result.load_safetensors()
    jax.block_until_ready(list(tensors.values()))
    say(f"load_safetensors: {len(tensors)} tensors resident in "
        f"{time.monotonic() - t0:.1f}s; {device_memory(device)}")
    dtypes = {"BF16": "bfloat16", "F32": "float32", "U16": "uint16",
              "U8": "uint8"}
    require(set(tensors) == {n for n, _, _ in obj.tensors},
            "tensor names differ from the file's")
    for name, dtype, shape in obj.tensors:
        t = tensors[name]
        require(t.devices() == {device} and t.dtype == dtypes[dtype]
                and t.shape == shape,
                f"{name}: {t.dtype}{t.shape} on {t.devices()}, expected "
                f"{dtypes[dtype]}{shape} on {device}")

    def exact(name: str, rows: slice | None = None) -> None:
        _, _, shape = next(t for t in obj.tensors if t[0] == name)
        want = np.frombuffer(obj.tensor_bytes(name), np.uint8).reshape(
            shape[0], -1)
        got = tensors[name] if rows is None else tensors[name][rows]
        got = np.asarray(got)
        got = got.view(np.uint8).reshape(got.shape[0], -1)
        require(np.array_equal(got, want if rows is None else want[rows]),
                f"{name}: bytes on the device differ from the generator's")

    by_offset = sorted((n for n in obj.spans if n.startswith("model.")),
                       key=lambda n: obj.spans[n][0])
    layer = "model.layers.1.mlp.experts."
    exact("model.embed_tokens.weight", slice(0, 1))
    exact("model.embed_tokens.weight", slice(widths.vocab - 1, widths.vocab))
    sampled = [layer + "0.gate_proj.weight",
               layer + f"{widths.routed // 2}.down_proj.weight",
               layer + f"{widths.routed - 1}.up_proj.weight",
               "model.layers.1.mlp.gate.e_score_correction_bias",
               by_offset[0], by_offset[-1],
               "smoke.random_bits.u16", "smoke.random_bits.u8"]
    for name in sampled:
        await asyncio.to_thread(exact, name)
    say(f"bit-exact: embedding rows 0 and {widths.vocab - 1}, and whole: "
        + ", ".join(sampled) + f" (the model's smallest offset "
        f"{by_offset[0]}, largest {by_offset[-1]}; data starts at byte "
        f"{obj.data_start}, {obj.data_start % 4} into a word)")


async def phase_b(fabric: Fabric, widths: Widths, device) -> None:
    """CLI: dfget --device tpu against the embedded daemon's socket. The
    reuse path backfills a fresh sink from the store and verifies it; then
    an object whose sink cannot land must fail the command."""
    t0 = time.monotonic()
    rc, out = await fabric.dfget(fabric.url("/model.safetensors"),
                                 "--digest", checkpoint(widths).sha256)
    line = out.strip().splitlines()[-1] if out.strip() else ""
    say(f"dfget exit {rc} in {time.monotonic() - t0:.1f}s: {line}")
    # The daemon's final progress names the device; dfget prints it.
    require(rc == 0 and "reuse=True" in line
            and "device_verified=True "
                f"device={device.platform}/{device.device_kind}" in line,
            f"dfget --device tpu on a landed object: rc={rc}\n{out[-1500:]}")

    # One sink slot, held by the landing above and claimed for a consumer
    # (protect): the next landing finds the cap reached and nothing it may
    # evict, so its result is disk-only — which a device request must
    # report as a failure.
    sinks = fabric.daemon.task_manager.device_sinks
    resident = list(sinks._sinks)
    require(len(resident) == sinks.max_tasks == 1,
            f"expected one resident sink in one slot, found {resident}")
    sinks.protect(resident[0])
    try:
        rc, out = await fabric.dfget(fabric.url("/small.bin"))
    finally:
        sinks.unprotect(resident[0])
    line = out.strip().splitlines()[-1] if out.strip() else ""
    say(f"dfget exit {rc} for an object whose sink cannot land: {line}")
    require(rc != 0 and "sink cap reached" in out,
            f"dfget --device tpu with no sink slot: rc={rc}\n{out[-1500:]}")
    require(out.count("device_verified=False") == 1,
            "the failed landing did not print device_verified=False")
    sinks.discard(resident[0])


def require_checksums_on_every_device(label: str, arr, hbm, devices) -> None:
    """Each device's whole copy of ``arr`` has the per-piece checksums the
    host recorded at landing, computed on that device."""
    from dragonfly2_tpu.ops.checksum import _chunk_checksums_xla

    copies = {s.device: s.data for s in arr.addressable_shards}
    require(set(copies) == set(devices),
            f"{label}: copies on {sorted(map(str, copies))}")
    for dev, copy in copies.items():
        if copy.shape[0] != hbm.padded_words:   # shard_to_mesh's pad
            copy = copy[: hbm.padded_words]
        sums, xors = (np.asarray(c) for c in _chunk_checksums_xla(
            copy, hbm.piece_words))
        bad = [n for n, (s, x) in hbm.host_checksums.items()
               if (int(sums[n]), int(xors[n])) != (s, x)]
        require(not bad, f"{label} on {dev}: pieces {bad[:5]} differ "
                         "from the host's checksums")


async def phase_four_chips(fabric: Fabric, widths: Widths, devices) -> None:
    """shard_to_mesh, the chunked ring (ici_broadcast) and, as what it is
    compared with, all_gather_shards, over four local devices; then one
    landing on the last device, which must not pass through the first."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from dragonfly2_tpu.daemon.peer.device_sink import DeviceSinkManager
    from dragonfly2_tpu.parallel.ici import all_gather_shards

    obj = checkpoint(widths)
    digest = await asyncio.to_thread(getattr, obj, "sha256")
    result = await land_object(fabric, obj, digest)
    hbm = result.sink.sink
    content = 4 * hbm.padded_words
    mesh = Mesh(np.array(devices[:4]), ("d",))

    # The CPU backend of the rehearsal reports no memory statistics; the
    # chip does, and there every check on them is made.
    metered = bool(devices[0].memory_stats())

    def in_use() -> list[int]:
        return [device_memory(d)["bytes_in_use"] or 0 for d in devices[:4]]

    base = in_use()
    t0 = time.monotonic()
    sharded = jax.block_until_ready(result.shard_to_mesh(mesh))
    shard_devices = [s.device for s in sharded.addressable_shards]
    require(len(set(shard_devices)) == 4,
            f"shards sit on {shard_devices}, not on four devices")
    ring = jax.block_until_ready(result.sink.ici_broadcast(mesh))
    gathered = jax.block_until_ready(all_gather_shards(mesh, sharded))
    say(f"shard_to_mesh + chunked ring + all_gather in "
        f"{time.monotonic() - t0:.1f}s over {shard_devices}")
    require(bool(jnp.array_equal(ring, gathered)),
            "chunked ring and all_gather differ")
    for label, arr in (("ring", ring), ("all_gather", gathered)):
        require_checksums_on_every_device(label, arr, hbm, devices[:4])
    grown = [b - a for a, b in zip(base, in_use())]
    say(f"per-piece checksums equal the host's on all four devices, both "
        f"ways; bytes_in_use grew by {grown} (content {content})")
    # Each device now holds its shard and two whole copies. Device 0 may
    # hold that and no more: the landed source was there before.
    require(not metered or all(g >= 2 * content for g in grown[1:]),
            "devices 1-3 do not show the content-sized allocations")
    require(not metered or grown[0] <= max(grown[1:]) + content // 8,
            "device 0 holds extra copies")
    del sharded, ring, gathered, arr
    gc.collect()

    last = devices[3]
    before = (device_memory(devices[0]), device_memory(last))
    store = fabric.daemon.task_manager.storage.find_completed_task(
        result.task_id)
    manager = DeviceSinkManager(device=last)
    try:
        with store:
            sink = await manager.finalize("smoke-last-device", store)
    finally:
        manager.close()
    require(sink is not None and sink.verified,
            "landing on the last device: "
            + manager.outcome("smoke-last-device", False).get(
                "device_error", "no sink"))
    after = (device_memory(devices[0]), device_memory(last))
    say(f"landing on {last}: device 0 {before[0]} -> {after[0]}; "
        f"last device {before[1]} -> {after[1]}")
    require(sink.as_words().devices() == {last},
            f"landed on {sink.as_words().devices()}, not {last}")
    if metered:
        require((after[1]["bytes_in_use"] - before[1]["bytes_in_use"])
                >= obj.length, "the last device does not hold the content")
        for key in ("bytes_in_use", "peak_bytes_in_use"):
            require(after[0][key] - before[0][key] < content // 8,
                    f"device 0's {key} grew during a landing on {last}")


# ---------------------------------------------------------------------- #
# Driver
# ---------------------------------------------------------------------- #

async def run(widths: Widths, chips: int, home: str, devices) -> None:
    fabric = Fabric(home, widths)
    try:
        t0 = time.monotonic()
        await fabric.start()
        say(f"fabric up in {time.monotonic() - t0:.1f}s: origin "
            f":{fabric.origin_port}, scheduler, seed peer (children), peer "
            f"daemon with the sink embedded here, all on {LOOPBACK}")
        if chips == 4:
            await timed("four chips", phase_four_chips(fabric, widths,
                                                       devices))
            return
        await timed("A (client API)", phase_a(fabric, widths, devices[0]))
        # Phase A's arrays go before Phase B lands the content again
        # (3x content while its assembly runs).
        gc.collect()
        await timed("B (CLI)", phase_b(fabric, widths, devices[0]))
    except BaseException:
        print(fabric.log_tails(), flush=True)
        raise
    finally:
        await fabric.stop()


async def timed(name: str, phase) -> None:
    t0 = time.monotonic()
    try:
        await phase
    except SmokeFailure as e:
        raise SmokeFailure(f"phase {name}: {e}") from None
    except Exception as e:
        raise SmokeFailure(
            f"phase {name}: {type(e).__name__}: {e}") from e
    say(f"phase {name} passed in {time.monotonic() - t0:.1f}s")


def scratch_home() -> str:
    """A new directory for this run's DF_HOME, which no other run shares:
    in the checkout, else under the temporary directory — wherever the
    daemon's unix socket stays inside the 108 bytes a socket path may
    have."""
    import tempfile

    parents = (HERE, tempfile.gettempdir())
    for parent in parents:
        sock = os.path.join(parent, ".chip_smoke_12345678", "peer", "run",
                            "dfdaemon.sock")
        if len(sock) <= 100:
            return tempfile.mkdtemp(prefix=".chip_smoke_", dir=parent)
    raise SmokeFailure("the daemon's unix socket path would be too long "
                       f"under any of {parents}")


def describe(devices) -> dict:
    """The device as jax reports it, for the last line."""
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def entries(directory: str) -> int:
    return len(os.listdir(directory)) if os.path.isdir(directory) else 0


def native_rungs() -> str:
    from dragonfly2_tpu.pkg import digest

    try:
        from dragonfly2_tpu.native import binding

        loaded = f"loaded (hardware crc32c: {binding.has_hw_crc()})"
    except ImportError as e:
        loaded = f"not loaded ({e})"
    return f"native data plane {loaded}; crc32c rung: {digest.crc32c_backend()}"


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1,
                        help="4: run only the four-chip path and what it "
                             "is compared with")
    args = parser.parse_args(argv)
    if not os.path.isdir(os.path.join(HERE, "dragonfly2_tpu")):
        print("chip_smoke.py needs the repository it was written for "
              "beside it", file=sys.stderr)
        return 1
    import jax

    devices = jax.devices()
    found = describe(devices)
    if found["platform"] != "tpu" or len(devices) < args.chips:
        print(json.dumps({"ok": False, "device": found, "error":
                          f"needs {args.chips} TPU chip(s); jax found "
                          f"platform {found['platform']!r} with "
                          f"{found['count']} device(s)"}))
        return 1

    from dragonfly2_tpu.ops.compile_cache import place_compile_cache

    cache_dir = place_compile_cache()
    meter = CompileMeter()
    obj = checkpoint(MOONLIGHT)
    say(f"device: {found}; compile cache: {cache_dir} "
        f"({entries(cache_dir)} entries before the run)")
    say(f"object: Moonlight-16B-A3B embed_tokens + MoE layer 1, "
        f"{len(obj.tensors)} tensors, {obj.length} bytes "
        f"({obj.length / 2**30:.3f} GiB); reduced: {json.dumps(REDUCED)}")
    say(native_rungs())
    t0 = time.monotonic()
    home = None
    try:
        home = scratch_home()
        asyncio.run(run(MOONLIGHT, args.chips, home, devices))
    except Exception as e:
        say(meter.line())
        print(json.dumps({"ok": False, "device": found,
                          "error": f"{type(e).__name__}: {e}"[:2000]}))
        return 1
    finally:
        if home is not None:
            shutil.rmtree(home, ignore_errors=True)
    say(meter.line())
    say(f"cache entries after the run: {entries(cache_dir)}; "
        f"memory: {device_memory(devices[0])}; "
        f"total {time.monotonic() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": found}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
