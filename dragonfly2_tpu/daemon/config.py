"""Daemon configuration tree.

Reference: client/config/peerhost.go:46-85 (DaemonOption: scheduler, host,
download, upload, proxy, objectStorage, storage, announcer...) with YAML
loading (:91-110). Kept as nested dataclasses with a YAML/dict loader.
"""

from __future__ import annotations

import os
import socket
from dataclasses import dataclass, field

import yaml

from dragonfly2_tpu.pkg.dfpath import Dfpath
from dragonfly2_tpu.pkg.prof import ProfConfig
from dragonfly2_tpu.pkg.types import HostType, parse_size


def _local_ip() -> str:
    # UDP connect trick: no traffic actually sent.
    try:
        s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        s.connect(("10.255.255.255", 1))
        ip = s.getsockname()[0]
        s.close()
        return ip
    except OSError:
        return "127.0.0.1"


@dataclass
class HostOption:
    hostname: str = field(default_factory=socket.gethostname)
    ip: str = field(default_factory=_local_ip)
    idc: str = ""               # for TPU: the pod/cluster identifier
    location: str = ""          # "zone|pod|slice|host" affinity path
    tpu_slice: str = ""         # slice name within the pod (ICI domain)
    tpu_worker_index: int = -1  # worker index within the slice


@dataclass
class SchedulerOption:
    addrs: list[str] = field(default_factory=list)  # "host:port" drpc
    schedule_timeout: float = 30.0
    disable_auto_back_source: bool = False
    max_schedule_attempts: int = 5


@dataclass
class DownloadOption:
    rate_limit: int = 0             # bytes/sec, 0 = unlimited
    traffic_shaper: str = "plain"   # plain | sampling (reference trafficShaperType)
    piece_concurrency: int = 4      # origin range-group concurrency
    parent_concurrency: int = 4     # concurrent parent piece workers
    unix_sock: str = ""             # download gRPC analog (dfget attach)
    peer_port: int = 0              # TCP drpc for other peers (sync pieces)
    calculate_digest: bool = True
    prefetch: bool = False          # prefetch whole task on ranged requests
    concurrent_min_length: int = 32 << 20
    # Max pieces per coalesced pieces_finished announce message. The cap
    # is adaptive at the conductor: idle traffic still flushes single
    # reports immediately (latency path), backlog grows batches toward
    # this knob and recovery re-reports drain in knob-sized messages.
    report_batch: int = 32


@dataclass
class UploadOption:
    port: int = 0                   # HTTP piece upload server, 0 = ephemeral
    rate_limit: int = 0


@dataclass
class StorageOpt:
    task_ttl: float = 3 * 3600.0
    disk_gc_threshold: int = 0
    keep_storage: bool = True
    write_buffer_size: int = 4 << 20
    # Idle seconds before an un-expired store drops its data-file fd
    # (lazily reopened). 0 = follow gc_interval.
    fd_idle_close: float = 0.0


@dataclass
class ProxyOption:
    enabled: bool = False
    port: int = 0
    registry_mirror: str = ""       # remote registry URL to mirror
    rules: list[dict] = field(default_factory=list)  # {regex, use_dragonfly, direct}
    white_list_ports: list[int] = field(default_factory=lambda: [443, 80])
    max_concurrency: int = 0
    # HTTPS interception (reference proxy.go:471 handleHTTPS +
    # proxy_sni.go): terminate CONNECT tunnels with CA-forged leaf certs
    # so HTTPS registry pulls ride P2P. With empty cert paths a CA is
    # generated and persisted under the daemon work home ("ca/").
    hijack_https: bool = False
    ca_cert: str = ""               # PEM path of operator-supplied CA cert
    ca_key: str = ""                # PEM path of its private key
    hijack_hosts: list[str] = field(default_factory=list)  # regexes, [] = all
    sni_enabled: bool = False       # direct-TLS SNI listener
    sni_port: int = 0
    sni_hijack: bool = False        # terminate+serve instead of splice


@dataclass
class ObjectStorageOption:
    enabled: bool = False
    port: int = 0
    max_replicas: int = 3
    backend: str = "fs"             # fs | s3 | gcs | oss | obs
    # Backend constructor kwargs: fs {root}, s3/oss/obs {endpoint,
    # access_key, secret_key, region}, gcs {endpoint, project}.
    backend_options: dict = field(default_factory=dict)


@dataclass
class PexOption:
    """Gossip peer exchange (reference client/daemon/pex,
    peerExchange option peerhost.go:84)."""

    enabled: bool = False
    port: int = 0                   # UDP gossip port, 0 = ephemeral
    seeds: list[str] = field(default_factory=list)  # "host:port" bootstrap
    # Shared cluster secret: when set, every gossip datagram carries an
    # HMAC and unauthenticated packets are dropped (the role memberlist's
    # cluster encryption key plays in the reference).
    secret: str = ""


@dataclass
class QoSOption:
    """Tenant QoS plane (dragonfly2_tpu/qos): weighted-fair piece
    dispatch across concurrent tasks + per-tenant upload buckets under
    the daemon-wide cap. Off by default — with it on, piece serving
    stays on the aiohttp path (per-tenant accounting and limiting live
    there, same posture as ``upload.rate_limit > 0``)."""

    enabled: bool = False
    # WFQ gate slots shared by ALL tasks' piece workers; 0 = 2x
    # download.parent_concurrency, so a single task never feels the gate.
    dispatch_capacity: int = 0
    # Floor share of upload.rate_limit any one tenant keeps when many
    # are active (the traffic shaper's MIN_SHARE_FRACTION idiom).
    upload_min_share_fraction: float = 0.1


@dataclass
class TPUSinkOption:
    """--device=tpu sink: land verified pieces into TPU HBM as they
    verify (daemon/peer/device_sink.DeviceSinkManager; no reference
    analog — BASELINE.json north star). Requests opt in per task with
    ``device="tpu"`` (dfget --device tpu)."""

    enabled: bool = False
    batch_pieces: int = 8       # pieces staged per device dispatch
    max_tasks: int = 4          # concurrent HBM-resident tasks


@dataclass
class DaemonConfig:
    host: HostOption = field(default_factory=HostOption)
    scheduler: SchedulerOption = field(default_factory=SchedulerOption)
    download: DownloadOption = field(default_factory=DownloadOption)
    upload: UploadOption = field(default_factory=UploadOption)
    storage: StorageOpt = field(default_factory=StorageOpt)
    proxy: ProxyOption = field(default_factory=ProxyOption)
    object_storage: ObjectStorageOption = field(default_factory=ObjectStorageOption)
    pex: PexOption = field(default_factory=PexOption)
    tpu_sink: TPUSinkOption = field(default_factory=TPUSinkOption)
    qos: QoSOption = field(default_factory=QoSOption)
    # Runtime observatory (pkg/prof): always-on sampling profiler +
    # loop-lag probe + GC observatory behind /debug/prof*, plus the
    # daemon-side loop_lag SLO at /debug/slo.
    prof: ProfConfig = field(default_factory=ProfConfig)
    work_home: str = ""
    host_type: str = "normal"       # normal|super|strong|weak (seed tiers)
    alive_time: float = 0.0         # 0 = forever
    gc_interval: float = 60.0
    metrics_port: int = 0
    manager_addr: str = ""          # manager drpc for dynconfig (stage 4)
    seed_peer: bool = False
    # Flight-recorder post-mortem bundles kept on disk (newest-N rotation
    # in pkg/flight; a crash-looping task must not fill the log volume).
    flight_keep_bundles: int = 32
    # Chaos/test knob: skew every wall stamp this daemon reports (flight
    # start_wall, announce clock samples) by this many seconds — the pod
    # lens's clock alignment must then RECOVER the skew, and the e2e pins
    # that the reported error bound covers it.
    clock_offset_s: float = 0.0

    def __post_init__(self):
        if not self.work_home:
            self.work_home = Dfpath().root

    @property
    def dfpath(self) -> Dfpath:
        return Dfpath(self.work_home)

    @property
    def unix_sock(self) -> str:
        """Resolved lazily so work_home changes after construction move the
        socket with them."""
        return self.download.unix_sock or self.dfpath.daemon_sock

    @property
    def host_type_enum(self) -> HostType:
        if self.seed_peer and self.host_type == "normal":
            return HostType.SUPER_SEED
        return HostType.parse(self.host_type)

    @classmethod
    def from_dict(cls, d: dict) -> "DaemonConfig":
        cfg = cls()
        _merge_dataclass(cfg, d)
        cfg.__post_init__()
        return cfg

    @classmethod
    def load(cls, path: str) -> "DaemonConfig":
        with open(path) as f:
            data = yaml.safe_load(f) or {}
        return cls.from_dict(data)


def _merge_dataclass(obj, d: dict) -> None:
    """Recursive dict→dataclass merge; size strings like '100MiB' accepted
    for int fields ending in _limit/_size/_threshold."""
    for key, value in d.items():
        if not hasattr(obj, key):
            continue
        current = getattr(obj, key)
        if hasattr(current, "__dataclass_fields__") and isinstance(value, dict):
            _merge_dataclass(current, value)
        elif isinstance(current, int) and not isinstance(current, bool) and isinstance(value, str):
            setattr(obj, key, parse_size(value))
        else:
            setattr(obj, key, value)
