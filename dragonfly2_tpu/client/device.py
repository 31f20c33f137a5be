"""Device-landing client API: fetch content through the P2P fabric and
hand it back as a JAX array in TPU HBM.

The north-star flow (BASELINE.json): a JAX training/serving process embeds
a dfdaemon (`daemon.daemon.Daemon` is pure asyncio — it runs on the
process's loop), and checkpoint shards arrive as device buffers without an
intermediate file export:

    d = Daemon(cfg_with_tpu_sink_enabled)
    await d.start()
    arr = await device.download_to_device(d, url, digest="sha256:...",
                                          dtype="bfloat16", shape=[8192, 4096])

No reference analog: Dragonfly2's dfget terminates at the filesystem
(client/dfget/dfget.go:47 Download → file output); ours can terminate in HBM.
"""

from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass, field

from dragonfly2_tpu.pkg import dflog, flight as flightlib, metrics
from dragonfly2_tpu.pkg.errors import Code, DfError
from dragonfly2_tpu.proto.common import UrlMeta

log = dflog.get("client.device")

CACHE_SCHEME = "dfcache://"

SHARDED_TASKS = metrics.counter(
    "device_sharded_tasks_total",
    "Spans of sharded pulls (download_sharded) by how they were served: "
    "pulled as a ranged task of their own, or cut from the surplus of the "
    "header's ranged task", ("how",))
SHARDED_BYTES = metrics.counter(
    "device_sharded_bytes_total",
    "Bytes of sharded pulls: of the selected tensors, of what rode along "
    "in the gaps of coalesced spans, and of the selected tensors that the "
    "header's ranged task already held", ("kind",))


@dataclass
class DeviceResult:
    """A completed device landing: the verified sink plus task facts."""

    task_id: str
    content_length: int
    from_p2p: bool
    from_reuse: bool
    sink: object  # TaskDeviceSink

    def as_words(self):
        return self.sink.as_words()

    def as_bytes_array(self):
        return self.sink.as_bytes_array()

    def as_tensor(self, dtype, shape):
        return self.sink.as_tensor(dtype, shape)

    def shard_to_mesh(self, mesh, axis_name: str = "d"):
        return self.sink.shard_to_mesh(mesh, axis_name)

    def load_safetensors(self, *, names: list[str] | None = None,
                         shardings: dict | None = None):
        """The landed content as named checkpoint tensors (the content
        must be a safetensors file): typed device arrays cut from the HBM
        buffer, optionally device_put to per-tensor shardings. On a TPU,
        BF16/F16 denormals come back as zero and NaN payloads as the
        canonical NaN (ops/bitview.py); every other value is exact."""
        from dragonfly2_tpu.ops import safetensors as st

        return st.load_from_sink(self.sink, names=names,
                                 shardings=shardings)


@dataclass
class RangedTask:
    """One ranged task of a sharded pull: bytes [start, end) of the object
    as the fabric landed them, and the tensors that were cut from it."""

    start: int
    end: int
    task_id: str
    content_length: int
    from_p2p: bool
    from_reuse: bool
    names: list[str] = field(default_factory=list)
    # Ids of the local devices the landed words lie on, the chip they
    # landed on first.
    chips: tuple = ()


class ShardedTensors(dict):
    """What ``download_sharded`` and ``download_global`` return: name ->
    device array, a plain dict to every caller, and in ``tasks`` every
    ranged task the pull made, the header's first."""

    def __init__(self, tensors=(), tasks=()):
        super().__init__(tensors)
        self.tasks: list[RangedTask] = list(tasks)


@dataclass
class HeaderPrefix:
    """The first bytes of a checkpoint as ``fetch_safetensors_header``
    landed them: ``nbytes`` content bytes in the sink's ``words`` (uint32,
    zero-padded), and the ranged task (two, for a header longer than the
    guess) that brought them."""

    words: object
    nbytes: int
    tasks: list[RangedTask]
    sink: object = None     # the guess's TaskDeviceSink, for a later fan-out


def _chips(landed_on, words) -> tuple:
    return (landed_on.id, *sorted(
        d.id for d in words.devices() if d != landed_on))


def _ranged_task(start: int, result: "DeviceResult") -> RangedTask:
    return RangedTask(start, start + result.content_length, result.task_id,
                      result.content_length, result.from_p2p,
                      result.from_reuse,
                      chips=_chips(result.sink.device, result.as_words()))


def _put(array, sharding):
    """``jax.device_put`` of a landed array to ``sharding``, the bytes that
    thereby reach a device from another one counted
    (``device_sink_hop_bytes_total``)."""
    import jax

    from dragonfly2_tpu.daemon.peer.device_sink import SINK_HOP_BYTES

    held = array.devices()
    out = jax.device_put(array, sharding)
    SINK_HOP_BYTES.labels("device_put").inc(sum(
        s.data.nbytes for s in out.addressable_shards
        if s.device not in held))
    return out


async def download_to_device(daemon, url: str, *, digest: str = "",
                             tag: str = "", application: str = "",
                             header: dict | None = None,
                             range_header: str = "",
                             dtype=None, shape=None,
                             mesh=None, axis_name: str = "d",
                             placement: str = "sharded",
                             device=None,
                             claim: bool = True):
    """Download ``url`` through the embedded daemon's P2P machinery and
    land it in the device sink. Returns a jax.Array when ``dtype``+
    ``shape`` (bitcast tensor) or ``mesh`` (sharded uint32 words) is
    given, else a DeviceResult exposing the sink.

    ``mesh`` with ``placement="replicated"``: the content whole on every
    chip of the mesh (the host's local devices on one axis). The landing is
    what it always is, up to the verified words on the sink's device; then
    the copies travel chip to chip, and every chip's per-piece checksums of
    its own copy must equal the host's before this returns. The result is a
    DeviceResult whose ``as_words()`` is one replicated array and whose
    ``load_safetensors()`` gives every tensor on every chip. A chip whose
    copy differs fails the call (DfError, counted in
    ``device_sink_chip_verify_total``). A mesh of the sink's device alone is
    the plain landing.

    ``device``: the local device (a ``jax.Device`` of this process) the
    bytes land on: its sink is created there, and staging, assembly, the
    checksums on the device and every view cut from ``as_words()`` run
    there. None lands where the daemon's sink manager lands, the first
    local device. The destination rides with the request and is no part of
    the task id: hosts that land the same range on different chips still
    issue byte-identical tasks. With ``mesh`` and ``placement=
    "replicated"`` it is the chip the fan-out starts from.

    ``claim``: take ownership of the sink (the manager forgets it — HBM is
    released when the caller drops the arrays). With ``claim=False`` the
    sink stays resident for other consumers until its TTL.

    ``range_header`` ("a-b" or "bytes=a-b"): land only that byte slice of
    the object — a distinct ranged task (P2P-deduped among peers pulling
    the SAME range). Ranged landings verify by the per-piece digest chain
    only; a whole-content ``digest`` cannot apply to a slice.

    A ``dfcache://<cache_id>`` URL (what ``save_from_device`` stores) is
    pulled P2P-only: the request never goes back to a source, because there
    is none, and where no host holds the entry it fails with the
    scheduler's code. A range of it is cut by a host that holds it whole
    (the scheduler's ``_trigger_range_holder``).

    A pod-wide pull (every host of a slice asking for the same object at
    once) is, on this path, plain P2P: each host registers for the task, the
    scheduler hands it up to ``candidate_parent_limit`` parents, slice-mates
    ranked before the seed (``scheduling.find_candidate_parents``), and its
    pieces come from whichever of them holds one. This call has no way to
    ask for the striped broadcast (each slice-mate pulling a disjoint
    stripe across the DCN and trading the rest inside the slice): that is
    ``FileTaskRequest.pod_broadcast``, reachable through ``Daemon.Download``
    (``dfget --pod-broadcast``) alone, and ``stripe_min_slice_peers`` is 0,
    so the scheduler stripes nothing unasked (ROADMAP S5).
    """
    from dragonfly2_tpu.daemon.peer.task_manager import FileTaskRequest
    from dragonfly2_tpu.pkg.piece import Range

    tm = daemon.task_manager
    if tm.device_sinks is None:
        raise DfError(Code.BadRequest,
                      "daemon has no device sink (set tpu_sink.enabled)")
    if placement not in ("sharded", "replicated"):
        raise DfError(Code.BadRequest,
                      f"placement {placement!r}: sharded or replicated")
    replicated = mesh is not None and placement == "replicated"
    rng = Range.normalize_header(range_header) if range_header else ""
    req = FileTaskRequest(
        url=url, output="",
        meta=UrlMeta(digest=digest, tag=tag, application=application,
                     header=header or {}, range=rng),
        device="tpu", sink_device=device,
        # A cache entry has no origin: its pull is P2P-only by construction
        # (this is every device entry point's one task request, the header
        # fetch and the ranged tasks of download_sharded among them).
        disable_back_source=url.startswith(CACHE_SCHEME),
    )
    if rng:
        req.range = Range.parse_http(rng)
    sink = None
    # The task id is deterministic: announce the imminent claim so the
    # verify→take window can never lose the sink to cap-pressure
    # eviction (protect), only to a concurrent claimer of the same task.
    expected_id = req.task_id()
    tm.device_sinks.protect(expected_id)
    try:
        for attempt in range(2):
            final = None
            asked = time.perf_counter()
            async with tm.device_sinks.admit():
                # The flight begins with the task, so the wait lies before
                # its first event and inside no phase of its wall time.
                admitted = time.perf_counter()
                tm.flight.task(expected_id).record(
                    flightlib.EV_ADMIT_WAIT, -1, (admitted - asked) * 1000.0)
                async for progress in tm.start_file_task(req):
                    if progress.state == "failed":
                        raise DfError.from_wire(progress.error or {})
                    if progress.state == "done":
                        final = progress
            if final is None:
                raise DfError(Code.UnknownError,
                              "download ended without a result")
            if not final.device_verified:
                raise DfError(Code.ClientPieceDownloadFail,
                              "content did not land in the device sink: "
                              + (final.device_error
                                 or "no sink error was recorded"))
            task_id = final.task_id
            sink = (tm.device_sinks.take(task_id) if claim
                    else tm.device_sinks.get(task_id))
            if sink is not None:
                break
            # Claim raced away: concurrent callers of the SAME task
            # (dedup) share one landing, and another claimer took it
            # first. The task is complete on disk, so one re-run rides
            # the reuse path, which backfills and re-verifies a fresh
            # sink from the store.
            if attempt == 0:
                log.info("device sink claimed by a concurrent caller; "
                         "rebuilding from store", task=task_id[:16])
    finally:
        tm.device_sinks.unprotect(expected_id)
    if sink is None:
        raise DfError(Code.UnknownError, "device sink vanished after verify")
    if replicated:
        await _fan_out(tm, sink, mesh, axis_name)
    chips = _chips(sink.device, sink.as_words())
    tm.flight.task(task_id).record(
        flightlib.EV_DEVICE_PULL, chips[0],
        (time.perf_counter() - admitted) * 1000.0,
        "chips=" + ",".join(map(str, chips)) if len(chips) > 1 else "")
    result = DeviceResult(task_id=task_id,
                          content_length=final.content_length,
                          from_p2p=final.from_p2p,
                          from_reuse=final.from_reuse, sink=sink)
    if dtype is not None and shape is not None:
        return result.as_tensor(dtype, shape)
    if mesh is not None and not replicated:
        return result.shard_to_mesh(mesh, axis_name)
    return result


async def _fan_out(tm, sink, mesh, axis_name: str = "d") -> None:
    """``sink`` whole on every chip of ``mesh``, each copy verified on its
    chip (``DeviceSinkManager.replicate``); a copy that differs fails the
    call as a landing that fails its verification does."""
    from dragonfly2_tpu.daemon.peer.device_sink import DeviceSinkError

    try:
        await tm.device_sinks.replicate(sink, mesh, axis_name,
                                        tm.flight.task(sink.task_id))
    except DeviceSinkError as e:
        tm.device_sinks.discard(sink.task_id)
        raise DfError(Code.ClientPieceDownloadFail,
                      f"device sink verification failed: {e}")


async def fetch_safetensors_header(daemon, url: str, *, tag: str = "",
                                   application: str = "",
                                   header: dict | None = None,
                                   prefix_guess: int = 256 << 10):
    """The checkpoint's parsed safetensors header via ONE guessed-size
    ranged pull (length prefix + header almost always fit in the guess;
    a second exact pull covers the rare huge header). Ranged tasks are
    byte-identical pod-wide, so a 256-host pod fetching the same header
    costs ~one origin touch and ONE fabric round trip per host instead
    of two. Returns ``(header_dict, data_start_abs, prefix)``: the landed
    guess as a ``HeaderPrefix``, whose surplus beyond the header is real
    tensor data that callers carve tensors from. The guess stays what the
    sink landed, uint32 words on the device; only the words that cover
    the length prefix and the header come to the host."""
    from dragonfly2_tpu.ops import bitview
    from dragonfly2_tpu.ops import safetensors as st

    first = await download_to_device(
        daemon, url, tag=tag, application=application, header=header,
        range_header=f"0-{prefix_guess - 1}")
    words, plen = first.as_words(), first.content_length
    tasks = [_ranged_task(0, first)]
    if plen < 8:
        raise st.SafetensorsError(f"file shorter ({plen}B) than the "
                                  "safetensors length prefix")
    n = int.from_bytes(bitview.host_bytes(words, 0, 8), "little")
    if n <= 0 or n > (1 << 27):
        raise st.SafetensorsError(f"implausible header length {n}")
    got = bitview.host_bytes(words, 0, min(8 + n, plen))
    if 8 + n > plen:
        rest = await download_to_device(
            daemon, url, tag=tag, application=application, header=header,
            range_header=f"{plen}-{8 + n - 1}")
        tasks.append(_ranged_task(plen, rest))
        got += bitview.host_bytes(rest.as_words(), 0, rest.content_length)
    header_dict, _ = st.parse_header(got[:8 + n])
    return header_dict, 8 + n, HeaderPrefix(words, plen, tasks, first.sink)


def _mesh_of(devices):
    """The devices on one axis ``d``, as a landing's fan-out takes them."""
    import numpy as np
    from jax.sharding import Mesh

    return Mesh(np.array(list(devices)), ("d",))


async def _pull_ranges(daemon, url: str, ranges, *, tag: str = "",
                       application: str = "",
                       header: dict | None = None) -> dict:
    """Pull each ``(start, end)`` byte range as its own ranged device
    task, concurrently under the daemon's shared sink admission; returns
    ``{(start, end): (words, task)}``: the sink's uint32 buffer, zero-padded
    past the range, and the ``RangedTask`` that brought it. A range may
    come as ``(start, end, devices)``, the local devices that want it: it
    lands on the first of them and, where there are more, is placed whole
    on each and verified there (``download_to_device`` with ``device`` and a
    replicated ``mesh``), so the words come back on exactly those devices.
    The destination changes no task id. The single pull engine for
    download_sharded and download_global — their task ids and coalesce
    behavior must never fork. A failed range CANCELS its siblings
    (orphaned pulls would keep downloading against a dead result), and
    the first real failure re-raises UNWRAPPED so callers keep the plain
    DfError/SafetensorsError contract rather than an ExceptionGroup."""
    import asyncio

    landed: dict = {}

    async def pull(s0: int, s1: int, devices=()) -> None:
        placed = {}
        if devices:
            placed["device"] = devices[0]
        if len(devices) > 1:
            placed.update(mesh=_mesh_of(devices), placement="replicated")
        result = await download_to_device(
            daemon, url, tag=tag, application=application, header=header,
            range_header=f"{s0}-{s1 - 1}", **placed)
        landed[(s0, s1)] = (result.as_words(), _ranged_task(s0, result))

    # First failure cancels the sibling pulls and re-raises plain (the
    # TaskGroup/ExceptionGroup shape needs 3.11; this runs on 3.10 too).
    tasks = [asyncio.ensure_future(pull(*r)) for r in ranges]
    try:
        await asyncio.gather(*tasks)
    except BaseException:
        for t in tasks:
            t.cancel()
        await asyncio.gather(*tasks, return_exceptions=True)
        raise
    return landed


def _carve(words, base: int, nbytes: int, header_dict: dict,
           data_start: int, names: list[str]) -> dict:
    """The ``names`` tensors cut from ``words``, which hold ``nbytes``
    content bytes from byte ``base`` of the object on: ``tensor_views``
    validates and converts exactly as for a full-content landing, over
    entries whose offsets are rebased onto the slice."""
    from dragonfly2_tpu.ops import safetensors as st

    sub = {n: {**header_dict[n], "data_offsets": [
        data_start + header_dict[n]["data_offsets"][0] - base,
        data_start + header_dict[n]["data_offsets"][1] - base]}
        for n in names}
    return st.tensor_views(words, sub, 0, names, total=nbytes)


def coalesce_spans(spans) -> list[tuple[int, int]]:
    """Touching/overlapping ``(start, end)`` spans merged into
    super-ranges (sorted). The one merge rule for download_global's
    ranged-task planning — unit-testable without a daemon."""
    merged: list[list[int]] = []
    for s0, s1 in sorted(spans):
        if merged and s0 <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], s1)
        else:
            merged.append([s0, s1])
    return [(s0, s1) for s0, s1 in merged]


def covering_span(coverage, a: int, b: int) -> tuple[int, int]:
    """The first span of ``coverage`` that fully contains [a, b); a miss
    is a planner bug surfaced as SafetensorsError, never a silent wrong
    carve."""
    from dragonfly2_tpu.ops import safetensors as st

    for s0, s1 in coverage:
        if s0 <= a and b <= s1:
            return (s0, s1)
    raise st.SafetensorsError(
        f"internal: span [{a}, {b}) not covered by any landed range")


def _validated_span(name: str, meta, data_start: int) -> tuple[int, int]:
    """(absolute_start, absolute_end) of a tensor's bytes, with the
    malformed-header failure modes surfaced as SafetensorsError."""
    from dragonfly2_tpu.ops import safetensors as st

    if not isinstance(meta, dict):
        raise st.SafetensorsError(f"{name}: entry must be an object")
    offsets = meta.get("data_offsets")
    if (not isinstance(offsets, list) or len(offsets) != 2
            or not all(isinstance(o, int) and not isinstance(o, bool)
                       for o in offsets)
            or offsets[1] < offsets[0] or offsets[0] < 0):
        raise st.SafetensorsError(f"{name}: bad data_offsets {offsets!r}")
    return data_start + offsets[0], data_start + offsets[1]


async def download_sharded(daemon, url: str, *,
                           names: list[str] | None = None,
                           selector=None,
                           shardings: dict | None = None,
                           tag: str = "", application: str = "",
                           header: dict | None = None,
                           coalesce_gap: int = 4 << 20,
                           prefix_guess: int = 256 << 10):
    """Pull ONLY this host's tensors of a safetensors checkpoint through
    the fabric, landing straight in HBM: the sharded-pod pattern where a
    host needs its pipeline stage / expert shard and not the whole file
    (an expert-parallel rank of four needs 29 % of a Moonlight checkpoint
    file: its 16 of each layer's 64 experts and what is not routed).

    Every host in the same shard group issues byte-identical ranged tasks
    (same task ids), so the fabric dedupes origin traffic per RANGE, not
    per object — with 16 pipeline stages, origin serves ~1/16th of the
    checkpoint once per stage group instead of the whole file per host.
    Upstream Dragonfly2 has ranged tasks (``dfget --range``) and ends each
    in a file; the plan over a checkpoint's header and the landing in HBM
    are this build's.

    ``names``: explicit tensor list, or ``selector(name, meta) -> bool``
    over header entries. ``shardings``: tensor name → jax Sharding,
    applied via device_put after landing on the daemon's one landing chip
    (a second, chip-to-chip copy, counted in ``device_sink_hop_bytes_total``;
    ``download_global`` lands each shard on the chip that keeps it).
    Adjacent selected spans closer
    than ``coalesce_gap`` bytes merge into one ranged task (fewer tasks;
    the gap bytes ride along). A tensor that lies whole inside the
    header's ranged task (``prefix_guess`` bytes) is cut from it and not
    pulled again.

    Returns a ``ShardedTensors``: a dict of name → device array in header
    order, whose ``tasks`` name every ranged task of the pull (the header's
    first) with its byte range, task id, content length, ``from_p2p``,
    ``from_reuse`` and the tensors cut from it.

    Ranged landings verify by the per-piece digest chain (announced by
    serving parents, anchored at the range seed's self-hash); a
    whole-content digest cannot apply to slices.
    """
    from dragonfly2_tpu.ops import safetensors as st

    called = time.perf_counter()
    header_dict, data_start, prefix = await fetch_safetensors_header(
        daemon, url, tag=tag, application=application, header=header,
        prefix_guess=prefix_guess)
    plen = prefix.nbytes

    picked: list[tuple[int, int, str]] = []
    for name, meta in header_dict.items():
        if name == "__metadata__":
            continue
        if names is not None and name not in names:
            continue
        if selector is not None and not selector(name, meta):
            continue
        start, end = _validated_span(name, meta, data_start)
        picked.append((start, end, name))
    if names is not None:
        missing = set(names) - {n for _, _, n in picked}
        if missing:
            raise st.SafetensorsError(
                f"tensors not in checkpoint: {sorted(missing)}")
    if shardings:
        # Validate BEFORE any early return: a selector typo plus a
        # shardings dict must fail loudly, not hand back {} silently.
        unknown = [n for n in shardings
                   if n not in {t[2] for t in picked}]
        if unknown:
            raise st.SafetensorsError(
                f"shardings reference tensors not loaded: {unknown}")

    # Three kinds of tensor: without bytes (legal: a 0 dim, data_offsets
    # [s, s]; synthesized below), whole inside the header's landing (cut
    # from it for free), and the rest, whose spans coalesce into ranged
    # tasks. A tensor that straddles the landing's end is pulled whole:
    # splitting it would need a carve from two sources.
    empty: list[str] = []
    in_prefix: list[str] = []
    spans: list[list] = []  # [start, end, [names...]]
    selected = held = gap = 0
    for start, end, name in sorted(picked):
        selected += end - start
        if end == start:
            empty.append(name)
        elif end <= plen:
            in_prefix.append(name)
            held += end - start
        elif spans and start - spans[-1][1] <= coalesce_gap:
            gap += max(0, start - spans[-1][1])
            spans[-1][1] = max(spans[-1][1], end)
            spans[-1][2].append(name)
        else:
            spans.append([start, end, [name]])
    head = prefix.tasks[0]
    head.names = in_prefix
    SHARDED_TASKS.labels("pulled").inc(len(spans))
    if in_prefix:
        SHARDED_TASKS.labels("prefix").inc()
    SHARDED_BYTES.labels("selected").inc(selected)
    SHARDED_BYTES.labels("gap").inc(gap)
    SHARDED_BYTES.labels("prefix").inc(held)
    tf = daemon.task_manager.flight.task(head.task_id)
    tf.record(flightlib.EV_SHARD_PLAN, len(spans),
              (time.perf_counter() - called) * 1000.0)

    # Independent spans pull concurrently (scattered shards — e.g. MoE
    # expert weights — are max-of-spans, not sum-of-spans), bounded by
    # the daemon's shared sink admission inside _pull_ranges.
    landed = await _pull_ranges(daemon, url, [(s, e) for s, e, _ in spans],
                                tag=tag, application=application,
                                header=header)
    viewing = time.perf_counter()
    cut: dict = {}
    if empty:
        import jax.numpy as jnp

        sub = {name: {**header_dict[name], "data_offsets": [0, 0]}
               for name in empty}
        cut.update(st.tensor_views(jnp.zeros((0,), dtype="uint32"),
                                   sub, 0, empty))
    if in_prefix:
        cut.update(_carve(prefix.words, 0, plen, header_dict, data_start,
                          in_prefix))
    tasks = list(prefix.tasks)
    for start, end, span_names in spans:
        words, task = landed.pop((start, end))
        task.names = span_names
        tasks.append(task)
        cut.update(_carve(words, start, task.content_length, header_dict,
                          data_start, span_names))
    tf.record(flightlib.EV_SHARD_VIEWS, len(cut),
              (time.perf_counter() - viewing) * 1000.0)
    if shardings:  # unknown names already rejected above, pre-download
        for name, sharding in shardings.items():
            cut[name] = _put(cut[name], sharding)
    return ShardedTensors(((name, cut[name]) for _, _, name in picked),
                          tasks)


def coalesce_by_destination(wanted: dict) -> list[tuple]:
    """``wanted`` maps a ``(start, end)`` span to the devices that want it;
    returns ``[(start, end, devices)]`` sorted by offset, the devices in id
    order: touching or overlapping spans merge into one super-range only
    where the SAME devices want them (``coalesce_spans`` within each set of
    devices), so no byte lands on a chip that has no use for it. The one
    merge rule of download_global's plan — unit-testable without a
    daemon."""
    by_devices: dict[tuple, list] = {}
    for span, devices in wanted.items():
        key = tuple(sorted(devices, key=lambda d: d.id))
        by_devices.setdefault(key, []).append(span)
    return sorted(((s0, s1, key) for key, spans in by_devices.items()
                   for s0, s1 in coalesce_spans(spans)),
                  key=lambda pull: pull[:2])


def _held_by(array, device):
    """What ``device`` holds of ``array`` (its copy, for an array that lies
    whole on several devices), as an array of that device alone; nothing
    is copied."""
    if array.devices() == {device}:
        return array
    return next(s.data for s in array.addressable_shards
                if s.device == device)


async def download_global(daemon, url: str,
                          shardings: dict, *,
                          tag: str = "", application: str = "",
                          header: dict | None = None,
                          prefix_guess: int = 256 << 10):
    """Global sharded checkpoint load through the fabric: for each tensor,
    pull ONLY the byte ranges this process's devices actually hold under
    its jax Sharding, land each range ON the device that keeps it, and
    assemble true global ``jax.Array``s, each under exactly the sharding
    asked, with ``make_array_from_single_device_arrays``.

    The pod pattern this completes: every host computes the same plan
    from (header x shardings); hosts holding the same shard issue
    byte-identical ranged tasks, so origin traffic dedupes per shard
    RANGE across the pod — a TP=16 row-sharded matrix costs the origin
    one copy TOTAL, each 1/16th fetched once and fanned over P2P.

    The plan is by DESTINATION. Leading-axis shards (a slice on axis 0,
    all trailing axes full) map to contiguous byte ranges, and touching
    ranges coalesce into one ranged task only where the same set of local
    devices wants them (``coalesce_by_destination``): under four-way expert
    parallelism a chip's experts are its own tasks, and what every chip
    holds is a task of all four. A range that one device wants lands on
    that device (``download_to_device(device=)``): staging, assembly, the
    checksums on the device and the typed views all run there, and no
    shard is copied from another chip. A range that several want is read
    from the store and passed over by the host ONCE, lands on the first of
    them, and reaches the others chip to chip (``placement="replicated"``
    over a mesh of exactly those devices: the fan-out of
    ``HBMSink.replicate``), each copy's per-piece checksums computed on the
    chip that holds it and compared with the host's before this returns.
    The destination is no part of a task id. Any other layout (a shard that
    is no leading-axis slice) falls back to landing that tensor whole on
    the first of its devices, slicing there and ``jax.device_put`` of each
    slice (counted in ``device_sink_hop_bytes_total``), as does what lies inside
    the header's ranged task where another chip than that task's wants it:
    that task names no chip, and is fanned out to the chips that do.
    ``shardings``: tensor name -> jax.sharding.Sharding (tensors not named
    are not loaded).

    Returns a ``ShardedTensors``: name -> global array in the order of
    ``shardings``, and in ``tasks`` every ranged task (the header's first)
    with the local devices its words lie on (``chips``). The spans
    ``shard_plan`` and ``shard_views`` go on the header task's flight, as
    ``download_sharded`` stamps them; every task stamps ``device_pull`` with
    its chip.
    """
    import numpy as np

    import jax

    from dragonfly2_tpu.ops import safetensors as st

    called = time.perf_counter()
    header_dict, data_start, prefix = await fetch_safetensors_header(
        daemon, url, tag=tag, application=application, header=header,
        prefix_guess=prefix_guess)
    plen = prefix.nbytes

    missing = [n for n in shardings if n not in header_dict]
    if missing:
        raise st.SafetensorsError(
            f"tensors not in checkpoint: {sorted(missing)}")

    # Plan: per (tensor, local device) -> the absolute byte span it needs
    # plus how to carve the shard out of that span once landed.
    #   (name, dev, span_start, span_end, shard_shape, idx | None)
    # and per span the devices that want its bytes ON them.
    plan = []
    wanted: dict[tuple[int, int], set] = {}
    for name, sharding in shardings.items():
        meta = header_dict[name]
        begin, end = _validated_span(name, meta, data_start)
        shape_raw = meta.get("shape")
        if (not isinstance(shape_raw, list)
                or not all(isinstance(d, int) and not isinstance(d, bool)
                           and d >= 0 for d in shape_raw)):
            raise st.SafetensorsError(f"{name}: bad shape {shape_raw!r}")
        shape = tuple(shape_raw)
        nbytes = end - begin
        count = int(np.prod(shape)) if shape else 1
        itemsize = nbytes // max(1, count)
        row_bytes = (int(np.prod(shape[1:])) if len(shape) > 1 else 1) * itemsize
        idx_map = sharding.devices_indices_map(shape)
        if not sharding.addressable_devices:
            # A sub-mesh of other hosts' devices: assembly below would
            # KeyError; fail with the tensor named like every other
            # malformed-input path here.
            raise st.SafetensorsError(
                f"{name}: sharding has no addressable devices in this "
                "process")
        devices = sorted(sharding.addressable_devices, key=lambda d: d.id)
        for dev in devices:
            idx = idx_map[dev]

            def _dim(sl, size):
                start, stop, step = sl.indices(size)
                return max(0, -(-(stop - start) // step))

            shard_shape = tuple(
                _dim(sl, dim) if isinstance(sl, slice) else 1
                for sl, dim in zip(idx, shape))
            lead = idx[0] if idx else slice(None)
            contiguous = (
                len(shape) >= 1 and nbytes > 0
                and isinstance(lead, slice) and lead.step in (None, 1)
                and all(isinstance(s, slice)
                        and s == slice(None) for s in idx[1:]))
            if contiguous:
                r0 = lead.start or 0
                r1 = shape[0] if lead.stop is None else lead.stop
                span = (begin + r0 * row_bytes, begin + r1 * row_bytes)
                plan.append((name, dev, span[0], span[1], shard_shape, None))
                wants = dev
            else:
                span = (begin, end)   # whole tensor; sliced where it lands
                plan.append((name, dev, begin, end, shard_shape, idx))
                wants = devices[0]
            if span[1] > span[0]:
                wanted.setdefault(span, set()).add(wants)

    # Ranges the header-guess landing already covers carve from it free;
    # the others coalesce, by destination, into one ranged task each.
    pull_list = coalesce_by_destination(
        {span: devs for span, devs in wanted.items() if span[1] > plen})
    head = prefix.tasks[0]
    tf = daemon.task_manager.flight.task(head.task_id)
    tf.record(flightlib.EV_SHARD_PLAN, len(pull_list),
              (time.perf_counter() - called) * 1000.0)
    pulled = await _pull_ranges(daemon, url, pull_list, tag=tag,
                                application=application, header=header)
    viewing = time.perf_counter()
    tasks = list(prefix.tasks)
    landed: dict = {}
    for s0, s1, _ in pull_list:
        landed[(s0, s1)], task = pulled.pop((s0, s1))
        tasks.append(task)
    coverage = [pull[:2] for pull in pull_list]
    if plen:
        landed[(0, plen)] = prefix.words
        coverage.append((0, plen))
        # The header's task named no chip: where others want what lies
        # inside it, it goes whole to them too, verified there.
        inside = set().union(*(devs for span, devs in wanted.items()
                               if span[1] <= plen))
        here = prefix.sink.device
        if inside - {here}:
            await _fan_out(daemon.task_manager, prefix.sink, _mesh_of(
                [here, *sorted(inside - {here}, key=lambda d: d.id)]))
            landed[(0, plen)] = prefix.sink.as_words()
            head.chips = _chips(here, landed[(0, plen)])

    # Carve: every distinct (tensor, byte range, shard shape) ONCE from the
    # words of the range that covers it, the cuts of one range by one
    # ``tensor_views`` (a dispatch a group of equal dtype and shape), on
    # the device(s) those words lie on; a device's shard is then what it
    # holds of the cut.
    cuts: dict[tuple[int, int], dict] = {}
    for name, dev, a, b, shard_shape, idx in plan:
        if b > a:
            s0, s1 = covering_span(coverage, a, b)
            meta = header_dict[name]
            cuts.setdefault((s0, s1), {})[(name, a, idx is not None)] = {
                **meta, "data_offsets": [a - s0, b - s0],
                "shape": meta["shape"] if idx is not None
                else list(shard_shape)}
    of_range = {(task.start, task.end): task for task in tasks}
    carved: dict = {}
    for (s0, s1), sub in cuts.items():
        of_range[(s0, s1)].names = list(dict.fromkeys(
            name for name, _, _ in sub))
        carved.update(st.tensor_views(landed.pop((s0, s1)), sub, 0,
                                      total=s1 - s0))
    landed.clear()
    by_name: dict[str, list] = {}
    for name, dev, a, b, shard_shape, idx in plan:
        if b <= a:
            # Zero-element shard: synthesize through the same validated
            # dtype path as real carves (tensor_views rejects unknown
            # dtypes as SafetensorsError, never a bare KeyError).
            sub = {name: {**header_dict[name], "shape": list(shard_shape),
                          "data_offsets": [0, 0]}}
            shard = jax.device_put(st.tensor_views(
                jax.numpy.zeros((0,), dtype="uint32"), sub, 0, [name])[name],
                dev)
        elif idx is not None:
            # Fallback: the whole tensor landed on the first of its
            # devices; the (possibly non-contiguous) shard is cut there
            # and copied to its own.
            shard = _put(carved[(name, a, True)][idx], dev)
        else:
            shard = _held_by(carved[(name, a, False)], dev)
        by_name.setdefault(name, []).append(shard)
    out = {}
    for name, sharding in shardings.items():
        shape = tuple(header_dict[name].get("shape") or ())
        out[name] = jax.make_array_from_single_device_arrays(
            shape, sharding, by_name[name])
    tf.record(flightlib.EV_SHARD_VIEWS, len(out),
              (time.perf_counter() - viewing) * 1000.0)
    return ShardedTensors(out, tasks)


# ------------------------------------------------------------------ #
# The way out: a checkpoint leaves HBM through the fabric
# ------------------------------------------------------------------ #

@dataclass
class SaveResult:
    """An acknowledged save: ``holders`` are the ids of the hosts that hold
    a verified copy, this host's first."""

    task_id: str
    cache_id: str
    digest: str             # sha256:<hex> of the stored file
    content_length: int
    pieces: int
    holders: list[str]


class Save:
    """A save whose snapshot is taken: the caller's tensors are its own
    again (it may overwrite or donate them), and the rest runs behind it.
    ``stall_s`` is what the call took."""

    def __init__(self, task_id: str, cache_id: str, content_length: int,
                 stall_s: float, running):
        self.task_id = task_id
        self.cache_id = cache_id
        self.content_length = content_length
        self.stall_s = stall_s
        self._running = running

    async def acked(self) -> SaveResult:
        """Returns when ``replicas`` hosts, this one among them, hold a copy
        whose pieces and sha256 verified; raises DfError where the save
        failed or the replicas could not be made in time (the task is then
        reported ``Failed`` to the scheduler)."""
        import asyncio

        return await asyncio.shield(self._running)


async def save_from_device(daemon, tensors: dict, cache_id: str, *,
                           tag: str = "", application: str = "",
                           replicas: int = 2, metadata: dict | None = None,
                           ack_timeout: float = 120.0) -> Save:
    """Save ``tensors`` (name -> jax.Array, all on one local device) into
    the fabric as ONE safetensors file, the persistent cache task
    ``dfcache://<cache_id>``, replicated to ``replicas`` hosts. Any host
    resumes it with ``download_to_device`` / ``download_sharded`` of that
    URL, from the replicas alone.

    Returns a ``Save`` as soon as the snapshot is taken, which is the
    caller's stall (ByteCheckpoint's "checkpoint stall"): the writer's
    header is laid out (``ops/safetensors.plan_file``), the file's words
    are built on the device from the typed tensors and checksummed a piece
    there (``ops/hbm_source.snapshot``). Behind the caller, until
    ``Save.acked()``: the words come to the host a group of pieces at a
    time, each piece is committed to this daemon's store on a worker
    thread with its digest and its host (sum, xor) held equal to the
    device's (a mismatch fails the save: what is stored is what was in
    HBM), the whole-content sha256 follows the pieces on a thread of its
    own (``PieceManager.import_pieces``); the task is registered with the
    scheduler as a persistent cache task with its geometry, so the host the
    scheduler's ``_replica_order`` names is asked to pull its replica as the
    save starts and is served each piece from its commit on, and
    ``Finished``, which waits only for that pull's tail, is answered only
    when ``replicas`` hosts hold a verified copy: each piece held against
    this host's piece digest, the replica's own sha256 of what it stored
    against the one this host took (``TaskManager.import_source``). No file
    is written outside the store.

    ``dfcache import --persistent`` is the other form of the same task: it
    reads a file and is answered BEFORE replication."""
    import asyncio

    from dragonfly2_tpu.daemon.peer.task_manager import FileTaskRequest
    from dragonfly2_tpu.ops import hbm_sink, hbm_source
    from dragonfly2_tpu.ops import safetensors as st
    from dragonfly2_tpu.pkg.piece import compute_piece_size

    called = time.perf_counter()
    tm = daemon.task_manager
    if not cache_id:
        raise DfError(Code.BadRequest, "cache_id required")
    req = FileTaskRequest(
        url=CACHE_SCHEME + cache_id, output="",
        meta=UrlMeta(tag=tag, application=application))
    task_id = req.task_id()
    tf = tm.flight.task(task_id)
    try:
        head, layout, total = st.plan_file(
            {name: (x.dtype, x.shape) for name, x in tensors.items()},
            metadata)
        piece_size = compute_piece_size(total)
        with hbm_sink.span(tf.record, flightlib.EV_SAVE_PACK,
                           len(tensors)) as step:
            snap = await asyncio.to_thread(
                hbm_source.snapshot, tensors, head, layout, total, piece_size)
            step.note = str(total)
    except (st.SafetensorsError, hbm_source.SaveError) as e:
        hbm_source.SAVE_FAILURES.labels("error").inc()
        raise DfError(Code.BadRequest, f"save_from_device: {e}")
    stall = time.perf_counter() - called
    tf.record(flightlib.EV_SAVE_SNAPSHOT, snap.pieces, stall * 1000.0,
              str(total))
    hbm_source.SAVE_SECONDS.labels("snapshot").inc(stall)

    async def run() -> SaveResult:
        try:
            result = await tm.import_source(
                snap, req, replica_count=replicas,
                wait_replicas_s=ack_timeout, stamp=tf.record)
        except BaseException as e:
            # By the code of who refused: the importer's piece gate, the
            # scheduler's awaited Finished.
            reason = {Code.ClientPieceDownloadFail: "mismatch",
                      Code.SchedError: "replica"}.get(
                          getattr(e, "code", None), "error")
            hbm_source.SAVE_FAILURES.labels(reason).inc()
            tm.flight.finish_task(task_id, "failed", note=str(e)[:200])
            raise
        finally:
            snap.release()
        hbm_source.SAVE_BYTES.labels("stored").inc(total)
        hbm_source.SAVE_SECONDS.labels("ack").inc(
            time.perf_counter() - called)
        tm.flight.finish_task(task_id, "done")
        return SaveResult(task_id=task_id, cache_id=cache_id,
                          digest=result["digest"], content_length=total,
                          pieces=snap.pieces, holders=result["holders"])

    running = asyncio.ensure_future(run())
    # A save nobody awaits still reports its failure.
    running.add_done_callback(
        lambda f: f.cancelled() or f.exception() is None or log.warning(
            "save failed", task=task_id[:16], error=str(f.exception())[:200]))
    return Save(task_id, cache_id, total, stall, running)


# ------------------------------------------------------------------ #
# Checkpoint-delta hot-swap (delta plane + ops/hbm_sink.DoubleBuffer)
# ------------------------------------------------------------------ #

@dataclass
class HotSwapResult:
    """One hot-swapped checkpoint generation: the verified device buffer
    plus its named tensor views and the delta accounting that produced
    it. ``buffer``/``tensors`` are also installed into the caller's
    DoubleBuffer (when given) by an atomic flip."""

    task_id: str
    content_length: int
    generation: int
    buffer: object                  # uint32 device words, whole pieces
                                    # (a uint8 np array on fallback)
    tensors: dict
    on_device: bool
    flipped: bool
    reused_device_bytes: int        # HBM->HBM copied from the live buffer
    staged_bytes: int               # host->device staged from the landing
    stats: dict                     # delta resolver accounting (may be {})


def _host_piece_checksums(store) -> tuple[dict[int, tuple[int, int]], int]:
    """checksum_numpy of every piece of the verified landing, the host side
    of the hot-swap verify gate, and how many of them were carried. A piece
    a delta landing's job committed carries the pair the job took of the
    bytes it wrote (``LocalTaskStore.word_sums``) and is not read again;
    every other piece (a store read back from disk, a piece a resumed
    landing found there, a version pulled whole) is walked here: read into
    ONE pooled buffer (a fresh 32 MiB ``bytes`` a piece, ``read_piece``,
    pays its page faults again or not by the allocator's state, 0.9 to
    5.9 s for a shard's 55 pieces on the chip's host: PERF.md section 6,
    PR 49) and summed on this thread. With every pair carried nothing is
    read and no buffer taken."""
    from dragonfly2_tpu.ops import hbm_sink
    from dragonfly2_tpu.ops.checksum import checksum_numpy
    from dragonfly2_tpu.storage.local_store import (
        acquire_read_buffer,
        release_read_buffer,
    )

    with store:
        pieces = store.get_pieces()
        sums = store.word_sums()
        out = {rec.num: sums[rec.num] for rec in pieces if rec.num in sums}
        walked = [rec for rec in pieces if rec.num not in out]
        if walked:
            buf = acquire_read_buffer(max(rec.size for rec in walked))
            try:
                for rec in walked:
                    store.read_into(rec.offset, rec.size, buf)
                    out[rec.num] = checksum_numpy(buf[:rec.size])
            finally:
                release_read_buffer(buf)
    carried = len(pieces) - len(walked)
    hbm_sink.SWAP_HOST_SUMS.labels("carried").inc(carried)
    hbm_sink.SWAP_HOST_SUMS.labels("walked").inc(len(walked))
    return out, carried


def _swap_runs(new_m, base_m) -> list:
    """The new version's content as runs ``[dst, src, length, reused]`` in
    offset order, for ``hbm_sink.plan_swap``: reused chunks that follow one
    another in the new version AND in the base are one run (versions that
    replace tensors in place: a dozen runs, not 1,400 chunks), and so are
    fetched chunks that follow one another."""
    from dragonfly2_tpu.delta.resolver import plan_delta

    base_of = {c.offset: b.offset for c, b in
               plan_delta(new_m, base_m).reused}
    runs: list = []
    for c in new_m.chunks:
        src = base_of.get(c.offset)
        last = runs[-1] if runs else None
        if (last is not None and last[3] == (src is not None)
                and (src is None or last[1] + last[2] == src)):
            last[2] += c.length
        else:
            runs.append([c.offset, c.offset if src is None else src,
                         c.length, src is not None])
    return runs


def _swap_on_device(store, live, plan, names, shardings, tf):
    """The device half of a hot-swap, on a thread of its own: the words no
    run holds staged from the verified landing, the new word buffer
    assembled beside the live one, EVERY piece of it checked on the device
    against the host's sums of the landing (the flip gate: a mismatch
    raises ValueError; the sums are the ones the landing's piece jobs
    carried, or ``_host_piece_checksums``'s walk), then the typed tensors
    cut from it and ready. Each step is a span on the delta task's flight.
    Returns the words, the tensors and how many pieces' sums were carried."""
    import jax

    from dragonfly2_tpu.ops import hbm_sink
    from dragonfly2_tpu.ops import safetensors as st

    meta = store.metadata
    device = (next(iter(live.devices())) if live is not None
              else jax.local_devices()[0])
    span = hbm_sink.span
    hbm_sink.watch_compiles()
    with store:
        with span(tf.record, flightlib.EV_SWAP_STAGE,
                  len(plan.slabs)) as step:
            slabs = hbm_sink.stage_swap(plan, store.read_into, device)
            step.note = str(meta.content_length - plan.reused_bytes)
        count, _ = hbm_sink.compiled()
        with span(tf.record, flightlib.EV_SWAP_ASSEMBLE,
                  bool(plan.live_segs) + len(slabs)):
            words = hbm_sink.assemble_swap_words(live, plan, slabs, device)
            del slabs
        with span(tf.record, flightlib.EV_SWAP_VERIFY,
                  meta.total_piece_count) as step:
            sums, carried = _host_piece_checksums(store)
            step.note = str(carried)
            hbm_sink.verify_words_against_host(words, meta.piece_size, sums)
        # The swap's two programs (the copy, the checksums) are one for a
        # geometry: a compile here is a geometry's first swap.
        hbm_sink.SWAP_ASSEMBLIES.labels(
            "compiled" if hbm_sink.compiled()[0] > count else "cached").inc()
    with span(tf.record, flightlib.EV_SWAP_VIEWS) as step:
        tensors = st.load_from_words(words, meta.content_length,
                                     names=names, shardings=shardings)
        jax.block_until_ready(list(tensors.values()))
        step.piece = len(tensors)
    return words, tensors, carried


async def download_delta(daemon, url: str, *, base, hot=None,
                         digest: str = "", tag: str = "",
                         application: str = "", header: dict | None = None,
                         names: list[str] | None = None,
                         shardings: dict | None = None):
    """Land version N+1 of a checkpoint as a delta against version N and
    hot-swap the device tensors without a serving gap.

    ``base``: the live generation — a DeviceResult/HotSwapResult from the
    previous download, or a bare base task id (then the live buffer, if
    any, comes from ``hot``). ``hot``: an ops.hbm_sink.DoubleBuffer;
    when given, the verified new generation is installed with one atomic
    flip, so a reader thread iterating ``hot.snapshot()`` only ever sees
    complete old-or-new tensor sets. The live buffer is the generation's
    uint32 words (``DeviceResult.as_words()``, a HotSwapResult's
    ``buffer``); anything else is no source, and the new generation is
    staged whole.

    The wire side rides the delta plane (TaskManager.start_delta_task):
    only changed chunks cross DCN, and the patched disk landing is
    digest-verified and served to peers. The device side then asks the
    sink manager's admission as every client pull does, copies the reused
    runs HBM->HBM out of the live words, stages only what no run holds
    from the disk landing (``hbm_sink.plan_swap``: one compiled program a
    geometry, whichever tensors a version changed), and verifies EVERY
    piece of the assembled buffer on-device against the host's piece
    checksums of the verified landing BEFORE the flip. Those are the sums
    the landing's piece jobs took of the bytes they committed, read only
    now that the task is done and its whole-object digest verified; a piece
    that carries none is read back from the store and summed
    (``_host_piece_checksums``; ``stats["host_sums_carried"]`` counts the
    carried). The tensors are cut from the new words as a landing's are
    (``ops/safetensors.load_from_words``) and are ready when it flips.
    """
    import asyncio

    import numpy as np

    from dragonfly2_tpu.daemon.peer.task_manager import FileTaskRequest
    from dragonfly2_tpu.delta.manifest import manifest_from_store
    from dragonfly2_tpu.delta.resolver import fetch_manifest
    from dragonfly2_tpu.ops import hbm_sink
    from dragonfly2_tpu.ops import safetensors as st

    called = time.perf_counter()
    tm = daemon.task_manager
    base_task_id = base if isinstance(base, str) else base.task_id
    live = None
    if hot is not None and hot.generation > 0:
        live = hot.buffer()
    elif not isinstance(base, str):
        live = (base.buffer if isinstance(base, HotSwapResult)
                else base.as_words())
    if isinstance(live, np.ndarray) or str(getattr(live, "dtype", "")) \
            != "uint32":
        live = None

    req = FileTaskRequest(
        url=url, output="",
        meta=UrlMeta(digest=digest, tag=tag, application=application,
                     header=header or {}))
    final = None
    async for progress in tm.start_delta_task(req, base_task_id):
        if progress.state == "failed":
            raise DfError.from_wire(progress.error or {})
        if progress.state == "done":
            final = progress
    if final is None:
        raise DfError(Code.UnknownError, "delta download ended silently")
    store = tm.storage.find_completed_task(final.task_id)
    if store is None:
        raise DfError(Code.UnknownError, "delta task has no store")
    total = store.metadata.content_length
    tf = tm.flight.task(final.task_id)

    asked = time.perf_counter()
    admission = (tm.device_sinks.admit() if tm.device_sinks is not None
                 else contextlib.nullcontext())
    async with admission:
        planning = time.perf_counter()
        tf.record(flightlib.EV_ADMIT_WAIT, -1, (planning - asked) * 1000.0)
        # Device plan: the reused runs out of the live words when they and
        # both manifests are at hand, whole-buffer staging otherwise.
        runs, how = [[0, 0, total, False]], "whole"
        if live is not None:
            new_m = await fetch_manifest(tm, final.task_id)
            base_store = tm.storage.find_completed_task(base_task_id)
            base_m = (await fetch_manifest(tm, base_task_id)
                      if base_store is not None else None)
            built = (base_m is None and base_store is not None
                     and new_m is not None)
            if built:
                base_m = await asyncio.to_thread(
                    manifest_from_store, base_store,
                    base_store.metadata.url, new_m.params)
            if new_m is not None and base_m is not None \
                    and base_m.params == new_m.params:
                runs = _swap_runs(new_m, base_m)
                how = "built" if built else "fetched"
        on_device = True
        stats = dict(tm.delta_stats.get(final.task_id, {}))
        try:
            plan = hbm_sink.plan_swap(
                runs, total, max(1, store.metadata.total_piece_count)
                * (store.metadata.piece_size // 4),
                0 if live is None else int(live.shape[0]))
            tf.record(flightlib.EV_SWAP_PLAN, plan.runs,
                      (time.perf_counter() - planning) * 1000.0, how)
            words, tensors, carried = await asyncio.to_thread(
                _swap_on_device, store, live, plan, names, shardings, tf)
            stats["host_sums_carried"] = carried
            reused, staged = plan.reused_bytes, total - plan.reused_bytes
        except (st.SafetensorsError, DfError):
            raise
        except hbm_sink.SwapVerifyError as e:
            # The flip gate: a verify MISMATCH is corruption, never a
            # fallback — handing back a bad buffer would defeat
            # verify-on-land exactly like the device sink path. The old
            # generation stays live.
            hbm_sink.SWAP_RESULTS.labels("refused").inc()
            raise DfError(Code.ClientPieceDownloadFail,
                          f"hot-swap verify failed: {e}")
        except Exception as e:
            # Device trouble (OOM, runtime errors) degrades to a host
            # buffer over the verified disk landing — the device_feed
            # discipline: the pipeline must outlive a sink hiccup.
            log.warning("delta device assembly failed; numpy fallback",
                        task=final.task_id[:16], error=str(e)[:200])
            on_device = False
            reused, staged = 0, total

    if not on_device:
        words = np.empty((total,), np.uint8)
        with store:
            await asyncio.to_thread(store.read_into, 0, total,
                                    memoryview(words))
        header_dict, data_start = st.parse_header(bytes(
            words[:8 + int.from_bytes(bytes(words[:8]), "little")]))
        tensors = _numpy_views(words, header_dict, data_start, names)

    generation = 1
    flipped = False
    if hot is not None:
        generation = hot.flip(words, tensors)
        flipped = True
    tf.record(flightlib.EV_SWAP_FLIP, generation,
              (time.perf_counter() - called) * 1000.0,
              "" if on_device else "fallback")
    hbm_sink.SWAP_RESULTS.labels(
        "flipped" if on_device else "fallback").inc()
    hbm_sink.SWAP_BYTES.labels("hbm_reused").inc(reused)
    hbm_sink.SWAP_BYTES.labels("staged").inc(staged)
    return HotSwapResult(
        task_id=final.task_id, content_length=total, generation=generation,
        buffer=words, tensors=tensors, on_device=on_device, flipped=flipped,
        reused_device_bytes=reused, staged_bytes=staged, stats=stats)


_NP_DTYPES = {
    "F64": "f8", "F32": "f4", "F16": "f2", "I64": "i8", "I32": "i4",
    "I16": "i2", "I8": "i1", "U8": "u1", "U16": "u2", "U32": "u4",
    "U64": "u8", "BOOL": "?", "BF16": "u2",   # numpy has no bfloat16
}


def _numpy_views(u8, header: dict, data_start: int,
                 names: list[str] | None) -> dict:
    """CPU fallback tensor views over a host uint8 buffer (BF16 surfaces
    as raw uint16 words — numpy has no bfloat16)."""
    import numpy as np

    from dragonfly2_tpu.ops import safetensors as st

    out: dict = {}
    for name, meta in header.items():
        if name == "__metadata__":
            continue
        if names is not None and name not in names:
            continue
        begin, end = _validated_span(name, meta, 0)
        dt = _NP_DTYPES.get(meta.get("dtype", ""))
        shape = meta.get("shape")
        if dt is None or not isinstance(shape, list):
            raise st.SafetensorsError(f"{name}: bad entry for numpy views")
        out[name] = np.frombuffer(
            u8, dtype=np.dtype("<" + dt),
            count=(end - begin) // np.dtype(dt).itemsize,
            offset=data_start + begin).reshape(shape)
    if names is not None:
        missing = [k for k in names if k not in out]
        if missing:
            raise st.SafetensorsError(f"tensors not in checkpoint: {missing}")
    return out
