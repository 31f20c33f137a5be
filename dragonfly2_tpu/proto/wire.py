"""The drpc wire contract: every method's request shape, in one module.

Reference: the entire RPC surface of Dragonfly2 is a single versioned
protobuf module (``d7y.io/api/v2`` — /root/reference/go.mod:6) that every
role compiles against. This module plays that role for the msgpack drpc
surface: a declarative schema per method (unary requests, stream opens,
and client→server stream messages), validated at the SERVER boundary
(rpc/server.py) so malformed or mistyped bodies fail fast with
Code.BadRequest instead of surfacing as deep KeyErrors/TypeErrors — the
class of bug per-handler tests can't exhaustively cover.

Semantics follow protobuf's spirit: unknown fields pass through
(forward compatibility), missing optional fields take their defaults,
required fields and type mismatches reject the call. Handlers keep
reading plain dicts — the schema is enforcement, not a codegen layer.
"""

from __future__ import annotations

from typing import Any

__all__ = [
    "F", "Msg", "SchemaError",
    "validate_unary", "validate_stream_open", "validate_stream_msg",
    "UNARY", "STREAM_OPEN", "STREAM_MSGS",
]


class SchemaError(ValueError):
    """A body failed validation; message names the method+field."""


class F:
    """One field: type, requiredness, optional nested/list schema."""

    __slots__ = ("type", "required", "spec", "item")

    def __init__(self, type_: type | tuple, required: bool = False,
                 spec: "Msg | None" = None, item: "F | None" = None):
        self.type = type_
        self.required = required
        self.spec = spec      # nested Msg for dict fields
        self.item = item      # element spec for list fields


class Msg:
    """A message shape: field name → F. Unknown fields are allowed."""

    __slots__ = ("name", "fields")

    def __init__(self, name: str, **fields: F):
        self.name = name
        self.fields = fields

    def validate(self, body: Any, where: str) -> None:
        if body is None:
            body = {}
        if not isinstance(body, dict):
            raise SchemaError(f"{where}: body must be a map, got "
                              f"{type(body).__name__}")
        for fname, f in self.fields.items():
            if fname not in body:
                if f.required:
                    raise SchemaError(f"{where}: missing required field "
                                      f"{fname!r}")
                continue
            value = body[fname]
            if value is None and not f.required:
                continue
            self._check(fname, f, value, where)

    def _check(self, fname: str, f: F, value: Any, where: str) -> None:
        ok = isinstance(value, f.type)
        # bools are ints in Python; don't let a bool satisfy an int field
        # unless the field is bool itself.
        if ok and isinstance(value, bool) and f.type is not bool:
            types = f.type if isinstance(f.type, tuple) else (f.type,)
            ok = bool in types
        # ints satisfy float fields (msgpack preserves the distinction) —
        # but bools, despite being ints, satisfy neither.
        if (not ok and f.type is float and isinstance(value, int)
                and not isinstance(value, bool)):
            ok = True
        if not ok:
            raise SchemaError(
                f"{where}: field {fname!r} must be "
                f"{getattr(f.type, '__name__', f.type)}, got "
                f"{type(value).__name__}")
        if f.spec is not None and isinstance(value, dict):
            f.spec.validate(value, f"{where}.{fname}")
        if f.item is not None and isinstance(value, (list, tuple)):
            for i, item in enumerate(value):
                self._check(f"{fname}[{i}]", f.item, item, where)


# --------------------------------------------------------------------- #
# Shared shapes
# --------------------------------------------------------------------- #

HOST = Msg(
    "Host",
    id=F(str), hostname=F(str), ip=F(str), port=F(int), upload_port=F(int),
    type=F(int), idc=F(str), location=F(str), tpu_slice=F(str),
    tpu_worker_index=F(int), telemetry=F(dict),
)

URL_META = Msg(
    "UrlMeta",
    digest=F(str), tag=F(str), range=F(str), filter=F(str),
    header=F(dict), application=F(str), priority=F(int),
    # QoS attribution tag (dragonfly2_tpu/qos): rides with the request
    # but stays OUT of task identity — two tenants pulling the same
    # content share one task.
    tenant=F(str),
)

PIECE = Msg(
    "Piece",
    piece_num=F(int, required=True), range_start=F(int), range_size=F(int),
    digest=F(str), download_cost_ms=F(int), dst_peer_id=F(str),
    # Flight-recorder per-phase split of download_cost_ms ({dcn_ms,
    # stall_ms, store_ms}): the scheduler's PodAggregator folds these into
    # per-host straggler attribution (/debug/pod/<task_id>). Optional —
    # origin/imported pieces report without it.
    timings=F(dict),
)

# Packed piece-report batch (proto/reportcodec): the negotiated compact
# alternative to a PIECE dict list — delta-coded piece nums, fixed-width
# columns, interned dst_peer_id table. Only sent after the scheduler
# advertised ``packed_reports`` on a stamped answer; structural decode
# validation (column length, varint bounds, intern indices) lives in
# reportcodec.decode_packed — the schema only pins the envelope types.
PACKED_PIECES = Msg(
    "PackedPieces",
    v=F(int, required=True), n=F(int, required=True),
    peers=F(list, required=True, item=F(str)),
    nums=F(bytes, required=True), cols=F(bytes, required=True),
    digests=F(dict),
)

_PERSISTENT_COMMON = dict(
    task_id=F(str, required=True), peer_id=F(str), host=F(dict, spec=HOST),
)

# Clock-alignment round-trip sample (pkg/podlens.ClockEstimator): the
# daemon stamped t0/t1 (its anchored monotonic wall clock) around a prior
# announce whose response echoed the scheduler's ``sched_wall``; the NTP
# midpoint (t0+t1)/2 - echo estimates the host's offset with a
# guaranteed |error| <= (t1-t0)/2 bound.
CLOCK_SAMPLE = Msg(
    "ClockSample",
    t0=F(float, required=True), t1=F(float, required=True),
    echo=F(float, required=True),
)

# Resume state on a (re-)register: the daemon's full local task state —
# landed piece bitset, task geometry, contiguous-prefix digest, stripe
# membership — so a failover ring member or a restarted scheduler can
# rebuild Task/Peer FSMs from re-registrations instead of treating the
# peer as fresh (no re-download of landed pieces, no spurious
# back-to-source). piece_nums is the compact form; digests ride the
# idempotent re-report that follows.
RESUME = Msg(
    "Resume",
    piece_nums=F(list, item=F(int)),
    # Packed alternative to piece_nums (bit i of byte i>>3 = piece i
    # landed, proto/reportcodec.nums_to_bitmap): a 64k-host restart storm
    # re-registers with one bit per piece instead of a msgpack int list.
    # Negotiated like packed reports; an old scheduler ignores it and the
    # idempotent recovery re-report rebuilds the same state.
    piece_bitmap=F(bytes),
    content_length=F(int), piece_size=F(int), total_piece_count=F(int),
    prefix_digest=F(str), pod_broadcast=F(bool), stripe=F(dict),
)

# Compact bounded flight digest (pkg/flight.digest): phase totals +
# merged phase segments + truncated waterfall + clock samples, shipped on
# the terminal announce message so the scheduler's pod lens can merge
# cross-host timelines. Validated loosely (dict) — the digest is
# forward-evolving and byte-capped at the source.
FLIGHT_DIGEST = Msg(
    "FlightDigest",
    v=F(int), task_id=F(str), state=F(str), start_wall=F(float),
    wall_s=F(float), phases=F(dict), segments=F(list),
    pieces=F(list), events=F(list), clock=F(list),
)

# --------------------------------------------------------------------- #
# Unary request schemas, keyed by method
# --------------------------------------------------------------------- #

UNARY: dict[str, Msg] = {
    # Scheduler (reference schedulerv2 + persistent-cache family)
    "Scheduler.AnnounceHost": Msg(
        "AnnounceHost",
        id=F(str, required=True), hostname=F(str), ip=F(str), port=F(int),
        upload_port=F(int), type=F(int), idc=F(str), location=F(str),
        tpu_slice=F(str), tpu_worker_index=F(int), telemetry=F(dict),
        # Previous announce's round-trip clock sample (the response
        # carries ``sched_wall`` to echo back) — feeds the pod lens's
        # per-host clock alignment.
        clock=F(dict, spec=CLOCK_SAMPLE)),
    # Merged cross-host broadcast timeline (pkg/podlens): the scheduler
    # assembles shipped flight digests (+ on-demand Daemon.FlightReport
    # pulls) into one wall-aligned pod view — dfget --pod's data source.
    "Scheduler.PodTimeline": Msg(
        "PodTimeline", task_id=F(str, required=True)),
    "Scheduler.LeaveHost": Msg("LeaveHost", id=F(str, required=True)),
    "Scheduler.LeavePeer": Msg("LeavePeer", id=F(str, required=True)),
    "Scheduler.AnnounceTask": Msg(
        "AnnounceTask",
        task_id=F(str, required=True), peer_id=F(str, required=True),
        url=F(str), tag=F(str), application=F(str),
        host=F(dict, required=True, spec=HOST),
        content_length=F(int), piece_size=F(int), total_piece_count=F(int),
        piece_nums=F(list, item=F(int))),
    "Scheduler.StatTask": Msg("StatTask", task_id=F(str, required=True)),
    "Scheduler.StatPeer": Msg("StatPeer", peer_id=F(str, required=True)),
    "Scheduler.ListHosts": Msg("ListHosts"),
    "Scheduler.UploadPersistentCacheTaskStarted": Msg(
        "UploadPersistentCacheTaskStarted",
        **_PERSISTENT_COMMON,
        url=F(str), tag=F(str), application=F(str), piece_size=F(int),
        content_length=F(int), total_piece_count=F(int),
        replica_count=F(int), ttl=F(float), digest=F(str)),
    # ``digest``: the content's digest as the uploader took it, given to
    # the replicas to verify against. ``wait_replicas_s`` > 0 is the awaited
    # form: the answer comes once ``replica_count`` hosts hold a verified
    # copy and names them (``holders``), or is an error after that long;
    # 0 answers at once and replicates behind the answer.
    "Scheduler.UploadPersistentCacheTaskFinished": Msg(
        "UploadPersistentCacheTaskFinished",
        **_PERSISTENT_COMMON,
        content_length=F(int), piece_size=F(int), total_piece_count=F(int),
        digest=F(str), wait_replicas_s=F(float)),
    # ``unreplicated``: the uploader asked for verified replicas and got no
    # answer: the task is kept as failed, whatever copies exist.
    "Scheduler.UploadPersistentCacheTaskFailed": Msg(
        "UploadPersistentCacheTaskFailed", **_PERSISTENT_COMMON,
        unreplicated=F(bool)),
    "Scheduler.StatPersistentCacheTask": Msg(
        "StatPersistentCacheTask", task_id=F(str, required=True)),
    "Scheduler.ListPersistentCacheTasks": Msg("ListPersistentCacheTasks"),
    "Scheduler.DeletePersistentCacheTask": Msg(
        "DeletePersistentCacheTask", task_id=F(str, required=True)),

    # Daemon download service (unix socket — dfget/dfcache attach)
    "Daemon.StatTask": Msg("DaemonStatTask", task_id=F(str, required=True)),
    "Daemon.ImportTask": Msg(
        "ImportTask",
        path=F(str, required=True), cache_id=F(str, required=True),
        tag=F(str), application=F(str), digest=F(str),
        persistent=F(bool), replica_count=F(int), ttl=F(float)),
    "Daemon.DeleteTask": Msg("DeleteTask", task_id=F(str, required=True)),
    "Daemon.Health": Msg("Health"),
    # Flight-recorder autopsy: the phase breakdown + waterfall for a task
    # this daemon ran (dfget --explain, tooling; also served on the PEER
    # service so the scheduler can pull digests on demand for the pod
    # timeline).
    "Daemon.FlightReport": Msg("FlightReport",
                               task_id=F(str, required=True)),
    # dfget --pod: the daemon proxies the merged cross-host timeline from
    # the scheduler (Scheduler.PodTimeline) over its own ring client.
    "Daemon.PodTimeline": Msg("DaemonPodTimeline",
                              task_id=F(str, required=True)),

    # Peer service (TCP — other daemons + scheduler triggers)
    "Peer.GetPieceTasks": Msg(
        "GetPieceTasks", task_id=F(str, required=True)),
    "Peer.TriggerDownloadTask": Msg(
        "TriggerDownloadTask",
        url=F(str, required=True), task_id=F(str), tag=F(str),
        application=F(str), digest=F(str), header=F(dict),
        filters=F(list, item=F(str)), seed=F(bool),
        disable_back_source=F(bool),
        # preheat-to-device: "tpu" additionally lands the content in the
        # triggered daemon's HBM sink (north-star pod-wide warm-up)
        device=F(str),
        # sharded preheat: warm only this byte range ("bytes=a-b") — a
        # distinct ranged task; stage groups preheat their own spans
        range=F(str),
        # pod-wide preheat: register the triggered pull as a striped
        # slice broadcast (scheduler answers with a stripe plan)
        pod_broadcast=F(bool),
        # QoS plane: the triggering caller's tenant tag + priority class
        # carry into the seed task so preheats are attributable and
        # dispatched fairly like any other pull
        tenant=F(str), priority=F(int)),
    "Peer.StatTask": Msg("PeerStatTask", task_id=F(str, required=True)),
    "Peer.DeleteTask": Msg("PeerDeleteTask", task_id=F(str, required=True)),

    # Manager (reference managerv2)
    "Manager.GetScheduler": Msg(
        "GetScheduler", hostname=F(str), ip=F(str),
        scheduler_cluster_id=F(int)),
    "Manager.ListSchedulers": Msg(
        "ListSchedulers", hostname=F(str), ip=F(str), idc=F(str),
        location=F(str)),
    "Manager.UpdateScheduler": Msg(
        "UpdateScheduler",
        hostname=F(str, required=True), ip=F(str, required=True),
        scheduler_cluster_id=F(int),   # omitted → seeded default cluster
        port=F(int), idc=F(str), location=F(str), state=F(str),
        features=F(list)),
    "Manager.GetSchedulerClusterConfig": Msg(
        "GetSchedulerClusterConfig",
        scheduler_cluster_id=F(int, required=True)),
    "Manager.ListSeedPeers": Msg(
        "ListSeedPeers", scheduler_cluster_id=F(int, required=True)),
    "Manager.UpdateSeedPeer": Msg(
        "UpdateSeedPeer",
        hostname=F(str, required=True), ip=F(str, required=True),
        seed_peer_cluster_id=F(int),   # omitted → seeded default cluster
        port=F(int), download_port=F(int), object_storage_port=F(int),
        type=F(str), idc=F(str), location=F(str), state=F(str)),
    "Manager.DeleteSeedPeer": Msg(
        "DeleteSeedPeer", hostname=F(str), ip=F(str),
        seed_peer_cluster_id=F(int)),
    "Manager.ListApplications": Msg("ListApplications"),
    "Manager.ListBuckets": Msg("ListBuckets"),
    "Manager.UpsertPeer": Msg(
        "UpsertPeer", hostname=F(str), ip=F(str), port=F(int),
        idc=F(str), location=F(str), state=F(str)),
    "Manager.PollJob": Msg(
        "PollJob", queue=F(str, required=True), timeout=F(float)),
    "Manager.CompleteJob": Msg(
        "CompleteJob",
        group_id=F(str, required=True), task_uuid=F(str, required=True),
        state=F(str), result=F(dict)),
    "Manager.TakeJobTokens": Msg(
        "TakeJobTokens", cluster_ids=F(list, required=True), tokens=F(int)),
}

# --------------------------------------------------------------------- #
# Stream open schemas
# --------------------------------------------------------------------- #

STREAM_OPEN: dict[str, Msg] = {
    "Scheduler.AnnouncePeer": Msg(
        "AnnouncePeerOpen",
        host=F(dict, required=True, spec=HOST),
        peer_id=F(str, required=True), task_id=F(str, required=True),
        url=F(str), tag=F(str), application=F(str), digest=F(str),
        filters=F(list, item=F(str)), header=F(dict), priority=F(int),
        # QoS attribution tag — carried into the scheduler's Task so
        # completions feed the per-tenant burn book (qos/admission)
        tenant=F(str),
        range=F(str), is_seed=F(bool), disable_back_source=F(bool),
        # striped slice broadcast: the task fans to >=2 same-slice hosts;
        # the scheduler answers with a stripe plan (piece%S ownership)
        pod_broadcast=F(bool)),
    "Daemon.Download": Msg(
        "DownloadOpen",
        url=F(str, required=True), output=F(str),
        meta=F(dict, spec=URL_META), disable_back_source=F(bool),
        device=F(str), pod_broadcast=F(bool),
        # checkpoint-delta plane: task id of the locally-landed base
        # version; chunks the base already holds are copied locally and
        # only changed chunks cross the wire (dfget --delta-base)
        delta_base=F(str)),
    "Daemon.ExportTask": Msg(
        "ExportTaskOpen",
        cache_id=F(str, required=True), output=F(str, required=True),
        tag=F(str), application=F(str), digest=F(str)),
    "Peer.SyncPieceTasks": Msg(
        "SyncPieceTasksOpen",
        task_id=F(str, required=True), peer_id=F(str)),
    "Manager.KeepAlive": Msg(
        "KeepAliveOpen",
        source_type=F(str), hostname=F(str), ip=F(str), cluster_id=F(int)),
}

# --------------------------------------------------------------------- #
# Client→server stream message schemas, by method and "type" discriminator
# --------------------------------------------------------------------- #

STREAM_MSGS: dict[str, dict[str, Msg]] = {
    "Scheduler.AnnouncePeer": {
        "register": Msg("Register", resume=F(dict, spec=RESUME)),
        "download_started": Msg(
            "DownloadStarted", content_length=F(int), piece_size=F(int),
            total_piece_count=F(int)),
        "piece_finished": Msg(
            "PieceFinished", piece=F(dict, required=True, spec=PIECE)),
        "pieces_finished": Msg(
            "PiecesFinished",
            # Exactly one of the two forms rides a message: the legacy
            # per-piece dict list, or the negotiated packed batch.
            pieces=F(list, item=F(dict, spec=PIECE)),
            packed=F(dict, spec=PACKED_PIECES)),
        "piece_failed": Msg(
            "PieceFailed", piece_num=F(int), parent_id=F(str),
            temporary=F(bool),
            # Typed failure reason (pkg/quarantine.REASON_WEIGHTS
            # vocabulary): feeds the scheduler-side parent demotion.
            reason=F(str)),
        "reschedule": Msg(
            "Reschedule", blocklist=F(list, item=F(str)),
            description=F(str)),
        "download_finished": Msg(
            "DownloadFinished", content_length=F(int), piece_size=F(int),
            total_piece_count=F(int),
            # Compact bounded flight digest (pkg/flight.digest) — the
            # "flight shipping" half of the pod lens: named events +
            # phase segments + per-piece waterfall + clock samples, one
            # per task, byte-capped at the source.
            flight=F(dict, spec=FLIGHT_DIGEST)),
        "download_failed": Msg("DownloadFailed", reason=F(str),
                               flight=F(dict, spec=FLIGHT_DIGEST)),
    },
}


# --------------------------------------------------------------------- #
# Boundary hooks (called by rpc/server.py)
# --------------------------------------------------------------------- #

def validate_unary(method: str, body: Any) -> None:
    """Raises SchemaError when ``body`` violates the method's schema.
    Unknown methods pass (plugins can register methods the core schema
    does not know — same posture as proto unknown fields)."""
    schema = UNARY.get(method)
    if schema is not None:
        schema.validate(body, method)


def validate_stream_open(method: str, body: Any) -> None:
    schema = STREAM_OPEN.get(method)
    if schema is not None:
        schema.validate(body, method)


def validate_stream_msg(method: str, body: Any) -> None:
    """Validate one client→server stream message. Messages with an
    unknown discriminator pass (server dispatch already warns), but on a
    schema'd method the body must at least be a map — a raw scalar would
    otherwise surface as an AttributeError deep in the handler."""
    kinds = STREAM_MSGS.get(method)
    if kinds is None:
        return
    if not isinstance(body, dict):
        raise SchemaError(f"{method}: stream message must be a map, got "
                          f"{type(body).__name__}")
    schema = kinds.get(body.get("type", ""))
    if schema is not None:
        schema.validate(body, f"{method}/{body.get('type')}")
