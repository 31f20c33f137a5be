"""One cell's run: what an operation is, what is kept from it, and the
comparison that decides ``correct``.

A traffic driver (``drivers/<kind>.py``) decides which operations run and
when; this module times one, reads the peer's flight ring for it, takes the
benchmark's own device checksums of what landed and fetches a seeded sample
of tensors back, all outside the timed region. The comparison runs once the
window has closed, against the generator's bytes: the program supplies only
what it landed.
"""

from __future__ import annotations

import asyncio
import dataclasses
import functools
import time

import numpy as np

from fabric import Fabric


def say(msg: str) -> None:
    print(f"[chipbench] {msg}", flush=True)


@dataclasses.dataclass
class Op:
    """One operation, as the driver and the readers see it. Times are
    ``time.perf_counter()`` seconds."""

    number: int
    client: int
    object_index: int
    tag: str
    t0: float = 0.0
    t1: float = 0.0
    nbytes: int = 0
    error: str = ""
    task_id: str = ""
    from_p2p: bool = False
    from_reuse: bool = False
    views_span: tuple[float, float] | None = None
    flight: list = dataclasses.field(default_factory=list)
    piece_bytes: int = 0
    device_checksums: np.ndarray | None = None   # (pieces, 2) uint32
    fetched: list = dataclasses.field(default_factory=list)
    kept_words: object = None   # whole words: on the device until the check
    gap_s: float = 0.0          # the benchmark's own work after the timed part
    warmup: bool = False
    cold: bool = True
    raced: bool = False       # a warm-up that ran before the seed was known

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


@functools.lru_cache(maxsize=None)
def _checksum_program(piece_words: int):
    """The benchmark's own per-piece (sum32, xor32) on the device, in
    plain jax.numpy: int32 lanes wrap exactly as uint32 does."""
    import jax
    import jax.numpy as jnp

    def chipbench_piece_checksums(words):
        # One piece at a time, so the temporary is a piece and not a copy
        # of the content: the reading must not raise the device's peak.
        def one(i):
            w = jax.lax.dynamic_slice(words, (i * piece_words,),
                                      (piece_words,))
            w = jax.lax.bitcast_convert_type(w, jnp.int32)
            if piece_words % 128 == 0:
                w = w.reshape(piece_words // 128, 128)
            return jnp.stack([
                jnp.sum(w, dtype=jnp.int32),
                jax.lax.reduce(w, jnp.int32(0), jax.lax.bitwise_xor,
                               tuple(range(w.ndim)))])

        return jax.lax.map(one, jnp.arange(words.shape[0] // piece_words))

    return jax.jit(chipbench_piece_checksums)


class Cell:
    """The fabric plus the cell's configuration, traffic and objects."""

    def __init__(self, fabric: Fabric, config: dict, traffic: dict,
                 objects, seed: int):
        self.fabric = fabric
        self.config = config
        self.traffic = traffic
        self.objects = objects
        self.seed = seed
        self.mode = traffic["mode"]            # "cold" | "reland"
        self.facts: dict[int, dict] = {}
        self.ops: list[Op] = []
        self.pulls: dict[int, int] = {}        # object index -> cold pulls
        self.rng = np.random.default_rng([seed, 0xc4ec])
        self.next_object = 0
        self.turns: dict[int, int] = {}        # client -> re-lands so far
        # Objects that a re-land cell keeps in the peer's store.
        self.stored = int(traffic.get("objects", 1))
        self.fetch_whole_first = True          # once per run

    # -- set-up ------------------------------------------------------------

    async def facts_for(self, index: int) -> dict:
        if index not in self.facts:
            self.facts[index] = await asyncio.to_thread(
                self.fabric.origin_json, f"/facts/{index}")
        return self.facts[index]

    def claim_object(self, client: int) -> int:
        """The object of a client's next operation. One object: always it.
        Distinct objects: in a cold cell every operation a new one; in a
        re-land cell the ``objects`` that set-up left in the store, each
        client going round a share of its own, so that no two clients
        ever land the same task."""
        if not self.objects.distinct:
            return 0
        if self.mode == "reland":
            clients = int(self.traffic["clients"])
            turn = self.turns.get(client, 0)
            self.turns[client] = turn + 1
            return client + clients * (turn % (self.stored // clients))
        self.next_object += 1
        return self.next_object - 1

    # -- one operation -----------------------------------------------------

    async def operation(self, number: int, client: int, *,
                        warmup: bool = False, cold: bool | None = None,
                        index: int | None = None,
                        closing=lambda: False) -> Op:
        """Request -> verified-resident (and typed tensors ready, where the
        object is a checkpoint), timed by the host clock; then, untimed,
        the benchmark's own readings and the clean-up the mode asks for.
        ``closing()`` says, once the timed part is over, whether this was
        the client's last operation of the window."""
        import jax
        from jax.profiler import TraceAnnotation

        from dragonfly2_tpu.client.device import download_to_device

        if index is None:
            index = self.claim_object(client)
        if cold is None:
            cold = self.mode == "cold"
        # A cold pull of a re-land cell is the one that fills the store.
        fresh = cold and self.mode == "cold"
        tag = f"s{self.seed}-op{number}" if fresh else f"s{self.seed}-reland"
        op = Op(number=number, client=client, object_index=index, tag=tag,
                warmup=warmup, cold=cold)
        digest = ""
        if self.config["object"].get("digest"):
            digest = (await self.facts_for(index))["digest"]
        url = self.fabric.url(index)
        daemon = self.fabric.daemon
        result = words = tensors = None
        op.t0 = time.perf_counter()
        try:
            with TraceAnnotation(f"chipbench:op#{number}"):
                result = await asyncio.wait_for(
                    download_to_device(daemon, url, digest=digest, tag=tag),
                    600)
                words = jax.block_until_ready(result.as_words())
                if self.objects.typed:
                    v0 = time.perf_counter()
                    with TraceAnnotation(f"chipbench:views#{number}"):
                        tensors = result.load_safetensors()
                        jax.block_until_ready(list(tensors.values()))
                    op.views_span = (v0, time.perf_counter())
            op.t1 = time.perf_counter()
        except Exception as e:  # a failed operation is counted, not fatal
            op.t1 = time.perf_counter()
            op.error = f"{type(e).__name__}: {e}"[:500]
            say(f"operation {number} failed: {op.error}")
        self.ops.append(op)
        if op.error:
            return op
        op.nbytes = result.content_length
        op.task_id = result.task_id
        op.from_p2p, op.from_reuse = result.from_p2p, result.from_reuse
        op.piece_bytes = result.sink.sink.piece_size
        piece_words = result.sink.sink.piece_words
        if cold:
            self.pulls[index] = self.pulls.get(index, 0) + 1
        self._read_flight(op)
        del result
        # Untimed readings, in an order that keeps the device's peak the
        # program's: the sampled tensors first, then the tensors go, then
        # the benchmark's checksums over the words, a piece at a time.
        if tensors is not None:
            op.fetched = await asyncio.to_thread(
                self.objects.fetch, tensors, self.rng, self.fetch_whole_first)
            self.fetch_whole_first = False
            tensors = None
        # With several clients what follows runs beside the others' timed
        # operations, so it is kept small: a few milliseconds of device
        # time, 8 bytes a piece to the host, and the deletes. Whole word
        # buffers stay on the device until the window has closed, and only
        # those that raise no peak: the warm-up's, fetched now, and each
        # client's last.
        op.device_checksums = await asyncio.to_thread(
            lambda: np.asarray(_checksum_program(piece_words)(words))
            .view(np.uint32))
        if not self.objects.typed:
            if warmup:
                op.kept_words = await asyncio.to_thread(np.asarray, words)
            elif closing():
                op.kept_words = words
        del words
        if fresh:
            await self.fabric.delete_everywhere(op.task_id)
        op.gap_s = time.perf_counter() - op.t1
        return op

    def _read_flight(self, op: Op) -> None:
        """The peer's flight events that fall inside the operation, on
        this process's perf_counter clock."""
        from dragonfly2_tpu.pkg import flight as flightlib

        tf = self.fabric.daemon.task_manager.flight.get(op.task_id)
        if tf is None:
            return
        # The flight's clock is perf_counter since its start; its start
        # as perf_counter follows from its anchored wall start.
        start = time.perf_counter() - (flightlib.anchored_wall()
                                       - tf.start_wall)
        names = flightlib.EVENT_NAMES
        op.flight = [(start + t, names.get(code, str(code)), piece, aux)
                     for t, code, piece, aux, _ in tf.events()
                     if op.t0 <= start + t <= op.t1]

    # -- the comparison ----------------------------------------------------

    async def check(self, sample_cap: int = 48) -> tuple[bool, list[str]]:
        """Every number compared, beside its limit. ``correct`` is all of
        them inside their limits. Exact comparisons have the limit 0."""
        done = [op for op in self.ops if not op.error]
        lines: list[str] = []
        # A seeded sample of the finished operations, the first and the
        # last always among them.
        if len(done) > sample_cap:
            pick = set(self.rng.choice(len(done), sample_cap - 2,
                                       replace=False).tolist())
            pick |= {0, len(done) - 1}
            sample = [done[i] for i in sorted(pick)]
        else:
            sample = done
        bad_pieces = pieces = 0
        for op in sample:
            facts = await self.facts_for(op.object_index)
            want = np.asarray(facts["checksums"], np.uint64).astype(np.uint32)
            got = op.device_checksums
            pieces += len(want)
            if (op.piece_bytes != facts["piece_bytes"]
                    or op.nbytes != facts["length"]
                    or got.shape != want.shape):
                bad_pieces += len(want)
            else:
                bad_pieces += int((got != want).any(axis=1).sum())
        lines.append(f"pieces whose device checksum differs from the "
                     f"generator's: {bad_pieces} of {pieces} in "
                     f"{len(sample)} of {len(done)} objects (limit 0)")
        bad_tensors = tensors = 0
        for op in done:
            for item in op.fetched:
                tensors += 1
                bad_tensors += not await asyncio.to_thread(
                    self.objects.matches, item)
            if op.kept_words is not None:
                tensors += 1
                want = await asyncio.to_thread(self.objects.content,
                                               op.object_index)
                got = (await asyncio.to_thread(np.asarray, op.kept_words)) \
                    .view(np.uint8)
                op.kept_words = None
                bad_tensors += not (np.array_equal(got[:want.size], want)
                                    and not got[want.size:].any())
        lines.append(f"fetched tensors or word buffers that differ from the "
                     f"generator's bytes: {bad_tensors} of {tensors} "
                     "(limit 0)")
        wrong_path = sum(
            (op.from_p2p, op.from_reuse) != ((True, False) if op.cold
                                             else (False, True))
            for op in done if not op.raced)
        lines.append(f"operations off the path the cell names (from_p2p, "
                     f"from_reuse): {wrong_path} of {len(done)} (limit 0)")
        stats = await asyncio.to_thread(self.fabric.origin_json, "/stats")
        worst = 0.0
        for index, n in self.pulls.items():
            served = stats.get(str(index), {}).get("bytes", 0)
            worst = max(worst, served / (n * self.objects.size(index)))
        limit = self.config["guarantees"]["origin_amplification_max"]
        lines.append(f"origin bytes per distinct task over content, worst "
                     f"object: {worst:.4f} (limit {limit}, at least 1)")
        ok = (bool(done) and bad_pieces == 0 and bad_tensors == 0
              and wrong_path == 0 and tensors > 0
              and (not self.pulls or 1.0 <= worst <= limit))
        return ok, lines
