"""Scheduler configuration and tuning constants.

Reference: scheduler/config/config.go + constants.go:26-107 (the numbers
that shape scheduling behavior). TPU addition: topology affinity weights for
ICI/DCN-aware parent selection (BASELINE.json north star).
"""

from __future__ import annotations

from dataclasses import dataclass, field

import yaml

from dragonfly2_tpu.pkg.prof import ProfConfig

# Reference scheduler/config/constants.go values.
SEED_PEER_CONCURRENT_UPLOAD_LIMIT = 2000   # :26-28
PEER_CONCURRENT_UPLOAD_LIMIT = 200         # :29-31
CANDIDATE_PARENT_LIMIT = 4                 # :32-34
FILTER_PARENT_LIMIT = 15                   # :35-37
TASK_BACK_TO_SOURCE_PEER_COUNT = 200       # :59-61
RETRY_LIMIT = 5                            # :64-65
RETRY_BACK_TO_SOURCE_LIMIT = 4             # :66-67
RETRY_INTERVAL = 0.5                       # :68-70 (500ms)
PIECE_DOWNLOAD_TIMEOUT = 30 * 60.0         # :71-73
PEER_TTL = 24 * 3600.0                     # :77-79
HOST_TTL = 3600.0                          # :86-88 (reference 1h)
TASK_TTL = 24 * 3600.0


@dataclass
class SchedulerServerConfig:
    host: str = "127.0.0.1"
    port: int = 8002                       # reference DefaultPort (constants.go:42)
    advertise_ip: str = ""


@dataclass
class SchedulingConfig:
    # "default" = built-in weighted evaluator; any other name is resolved
    # through the plugin registry (reference evaluator plugin.go:39
    # LoadPlugin when algorithm == "plugin").
    algorithm: str = "default"
    candidate_parent_limit: int = CANDIDATE_PARENT_LIMIT
    filter_parent_limit: int = FILTER_PARENT_LIMIT
    retry_limit: int = RETRY_LIMIT
    retry_back_to_source_limit: int = RETRY_BACK_TO_SOURCE_LIMIT
    retry_interval: float = RETRY_INTERVAL
    back_to_source_count: int = TASK_BACK_TO_SOURCE_PEER_COUNT
    # How long to hold a peer that refuses back-to-source (dfcache export)
    # in the schedule loop waiting for a parent to appear.
    no_source_patience: float = 30.0
    # Striped slice broadcast (scheduling/stripe.py): peers that register
    # with pod_broadcast=true always stripe once >= 2 same-slice
    # broadcast peers share the task. Setting this >= 2 additionally
    # auto-stripes ANY task with that many alive same-slice peers — off
    # by default so plain fan-outs keep the classic full-copy semantics
    # unless the deployment opts in.
    stripe_min_slice_peers: int = 0
    # Evaluator weights (reference evaluator_base.go:28-46); topology terms
    # replace IDC/location weighting when TPU topology metadata is present.
    weight_finished_pieces: float = 0.2
    weight_upload_success: float = 0.2
    weight_free_upload: float = 0.15
    weight_host_type: float = 0.15
    weight_idc_affinity: float = 0.15
    weight_location_affinity: float = 0.15


@dataclass
class FleetConfig:
    """Fleet observatory bounds (pkg/fleet): the continuous scheduler-side
    cluster view. All structures are preallocated/bounded — these knobs
    size them; ``enabled=False`` removes the per-event hooks entirely."""

    enabled: bool = True
    bucket_s: float = 5.0          # time-series bucket width
    buckets: int = 720             # ring length (5s x 720 = 1h)
    decision_cap: int = 1024       # audit-log ring length
    scorecard_hosts: int = 1024    # per-host scorecards kept (LRU past it)
    straggler_z: float = 3.0       # robust z-score flag threshold
    min_serve_samples: int = 8     # serve EWMA samples before scoring
    min_population: int = 8        # scored hosts before anyone is flagged
    # Advisory candidate filter: flagged stragglers are dropped from
    # parent candidate sets (each drop is recorded in the decision log).
    straggler_filter: bool = True
    # Cluster control tower (pkg/cluster): hard byte cap on the fleet
    # frame each manager keepalive carries (halving-until-fit past it).
    frame_max_bytes: int = 8192


@dataclass
class PodLensConfig:
    """Pod lens (pkg/podlens) + SLO engine (pkg/slo) bounds: the merged
    cross-host timeline store, the per-host clock estimator, and the
    continuous burn-rate evaluation. All bounded; ``enabled=False``
    removes the digest-ingest hooks entirely."""

    enabled: bool = True
    slo_enabled: bool = True
    max_tasks: int = 256           # task digests kept (LRU past it)
    clock_hosts: int = 4096        # per-host clock sample slots
    pull_missing: int = 16         # on-demand FlightReport pulls/timeline
    max_completions: int = 4096    # SLO completion ring length


@dataclass
class HAConfig:
    """Crash-recovery (scheduler HA): a bounded, periodically-flushed
    snapshot of live task/peer/host state in the same embedded-sqlite
    backend as the persistent-cache rows, so a restarted scheduler serves
    correct stripe plans and parent sets immediately — before every host
    has re-announced. Snapshot load and live resume re-registration
    converge to the same state (property-tested in
    tests/test_scheduler_ha.py)."""

    enabled: bool = True
    # Snapshot db path; "" reuses ``persistent_cache_db`` (one durable
    # file per scheduler). ":memory:" keeps the machinery live for tests
    # without durability.
    snapshot_db: str = ""
    snapshot_interval: float = 5.0
    # Bounds: newest tasks win; peers are capped per flush (terminal
    # peers are never written, so these bound live state only).
    max_tasks: int = 1024
    max_peers: int = 65536


@dataclass
class GCConfig:
    peer_ttl: float = PEER_TTL
    host_ttl: float = HOST_TTL
    task_ttl: float = TASK_TTL
    interval: float = 60.0


@dataclass
class SchedulerConfig:
    server: SchedulerServerConfig = field(default_factory=SchedulerServerConfig)
    scheduling: SchedulingConfig = field(default_factory=SchedulingConfig)
    gc: GCConfig = field(default_factory=GCConfig)
    fleet: FleetConfig = field(default_factory=FleetConfig)
    podlens: PodLensConfig = field(default_factory=PodLensConfig)
    ha: HAConfig = field(default_factory=HAConfig)
    # Runtime observatory (pkg/prof): /debug/prof* on the scheduler's
    # metrics server + the loop_lag SLO probe wired into the engine.
    prof: ProfConfig = field(default_factory=ProfConfig)
    manager_addr: str = ""                 # manager drpc for registration
    # Advertised hostname for manager registration and cluster-frame
    # attribution; "" = socket.gethostname(). Multi-scheduler tests on
    # one machine need distinct identities (the manager keys schedulers
    # by hostname+ip+cluster).
    hostname: str = ""
    manager_keepalive_interval: float = 5.0
    cluster_id: int = 1
    # Durable persistent-cache state (reference: Redis-backed
    # scheduler/resource/persistentcache); ":memory:" = tests/dev.
    persistent_cache_db: str = ":memory:"
    metrics_port: int = 0
    seed_peer_enabled: bool = True

    @classmethod
    def load(cls, path: str) -> "SchedulerConfig":
        with open(path) as f:
            data = yaml.safe_load(f) or {}
        cfg = cls()
        from dragonfly2_tpu.daemon.config import _merge_dataclass

        _merge_dataclass(cfg, data)
        return cfg
