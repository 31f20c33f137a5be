"""Daemon bootstrap: wire every sub-service and serve.

Reference: client/daemon/daemon.go — New (:108) builds storage, peer task
manager, rpc servers, upload server, proxy, object storage, gc, announcer;
Serve (:400-710) starts them; Stop (:711) tears down.
"""

from __future__ import annotations

import asyncio
import os

from dragonfly2_tpu.daemon.announcer import Announcer
from dragonfly2_tpu.daemon.config import DaemonConfig
from dragonfly2_tpu.daemon.peer.conductor import PeerTaskConductor
from dragonfly2_tpu.daemon.peer.piece_manager import PieceManager, PieceManagerOption
from dragonfly2_tpu.daemon.peer.task_manager import TaskManager
from dragonfly2_tpu.daemon.rpcserver import DaemonRpcServer
from dragonfly2_tpu.daemon.schedulerclient import SchedulerClient
from dragonfly2_tpu.daemon.upload import UploadManager
from dragonfly2_tpu.pkg import dflog
from dragonfly2_tpu.pkg.cache import GC, GCTask
from dragonfly2_tpu.pkg.ratelimit import Limiter
from dragonfly2_tpu.pkg.types import NetAddr
from dragonfly2_tpu.storage import StorageManager, StorageOption

log = dflog.get("daemon")


class Daemon:
    def __init__(self, config: DaemonConfig):
        self.config = config
        path = config.dfpath.ensure()
        dflog.configure(log_dir=path.log_dir)

        # TPU topology autodetection feeds the scheduler's ICI/DCN-aware
        # evaluator (env-based; never initializes JAX unless opted in).
        from dragonfly2_tpu.parallel.topology import apply_to_host_config

        apply_to_host_config(config.host)

        self.storage = StorageManager(
            StorageOption(
                data_dir=path.data_dir,
                task_ttl=config.storage.task_ttl,
                disk_gc_threshold=config.storage.disk_gc_threshold,
                keep_storage=config.storage.keep_storage,
                gc_interval=config.gc_interval,
                fd_idle_close=config.storage.fd_idle_close,
            )
        )
        self.storage.reload()

        rate = config.download.rate_limit
        self.piece_manager = PieceManager(
            PieceManagerOption(
                concurrency=config.download.piece_concurrency,
                compute_digest=config.download.calculate_digest,
                concurrent_min_length=config.download.concurrent_min_length,
            ),
            limiter=Limiter(rate if rate > 0 else float("inf")),
        )

        self.scheduler_client: SchedulerClient | None = None
        if config.scheduler.addrs:
            self.scheduler_client = SchedulerClient(config.scheduler.addrs)

        # Tenant QoS plane (dragonfly2_tpu/qos): one DWRR dispatch gate
        # shared by every conductor's piece workers + per-tenant upload
        # buckets under the daemon-wide cap. Gated off by default; with
        # it on, piece serving stays on the aiohttp path (attribution
        # and per-tenant limiting live there).
        self.qos_gate = None
        qos_buckets = None
        if config.qos.enabled:
            from dragonfly2_tpu import qos as qoslib

            capacity = config.qos.dispatch_capacity or (
                2 * max(1, config.download.parent_concurrency))
            self.qos_gate = qoslib.WFQGate(capacity)
            qos_buckets = qoslib.TenantBuckets(
                float(config.upload.rate_limit),
                min_share_fraction=config.qos.upload_min_share_fraction)
        self.upload = UploadManager(self.storage,
                                    rate_limit=config.upload.rate_limit,
                                    qos_buckets=qos_buckets)
        device_sinks = None
        if config.tpu_sink.enabled:
            from dragonfly2_tpu.daemon.peer.device_sink import DeviceSinkManager
            from dragonfly2_tpu.ops.compile_cache import place_compile_cache

            log.info("jax compile cache", dir=place_compile_cache())
            device_sinks = DeviceSinkManager(
                batch_pieces=config.tpu_sink.batch_pieces,
                max_tasks=config.tpu_sink.max_tasks)
        self.task_manager = TaskManager(
            self.storage,
            self.piece_manager,
            host_ip=config.host.ip,
            scheduler_client=self.scheduler_client,
            conductor_factory=self._make_conductor if self.scheduler_client else None,
            total_rate_limit=rate,
            host_wire=self._host_wire,
            traffic_shaper=config.download.traffic_shaper,
            prefetch=config.download.prefetch,
            device_sinks=device_sinks,
        )
        self.rpc = DaemonRpcServer(self.task_manager)
        self.proxy = None
        if config.proxy.enabled:
            from dragonfly2_tpu.daemon.proxy import Proxy
            from dragonfly2_tpu.daemon.transport import P2PTransport, rules_from_config

            rules = rules_from_config(config.proxy.rules)
            ca = None
            if config.proxy.hijack_https or config.proxy.sni_hijack:
                from dragonfly2_tpu.pkg.certify import CertAuthority

                ca = CertAuthority.load_or_generate(
                    config.proxy.ca_cert, config.proxy.ca_key,
                    persist_dir=os.path.join(config.work_home or ".", "ca"))
            self.proxy = Proxy(
                P2PTransport(self.task_manager, rules=rules),
                registry_mirror=config.proxy.registry_mirror,
                max_concurrency=config.proxy.max_concurrency,
                white_list_ports=config.proxy.white_list_ports,
                cert_authority=ca,
                hijack_hosts=config.proxy.hijack_hosts)
        self.object_storage = None
        if config.object_storage.enabled:
            from dragonfly2_tpu.daemon.objectstorage import ObjectStorageService
            from dragonfly2_tpu.daemon.transport import P2PTransport
            from dragonfly2_tpu.pkg.objectstorage import new_client

            backend = new_client(config.object_storage.backend,
                                 **config.object_storage.backend_options)
            self.object_storage = ObjectStorageService(
                backend, P2PTransport(self.task_manager),
                get_seed_peers=self._known_seed_peers,
                trigger_seed=self._trigger_seed_peer)
        self.announcer: Announcer | None = None
        self.dynconfig = None  # manager-source scheduler resolution
        self.pex = None        # gossip peer exchange (started in start())
        self.metrics = None    # Prometheus + /debug endpoint
        self.prof_obs = None   # runtime observatory (pkg/prof)
        self._prof_probe = None
        self._runtime_slo = None
        self._started = False
        self._peer_port = 0
        self.gc = GC(log)
        self.gc.add(GCTask("storage", config.gc_interval, 30.0, self._gc_storage))
        self._stopped = asyncio.Event()

    def _host_wire(self) -> dict:
        """Canonical host identity, {} before the announcer exists."""
        if self.announcer is None:
            return {}
        return self.announcer.host_wire()

    # -- object-storage replication hooks ----------------------------------

    def _known_seed_peers(self) -> list[dict]:
        """Seed peers from dynconfig (manager mode); empty otherwise —
        replication then degrades to backend-only writes."""
        if self.dynconfig is not None and hasattr(self.dynconfig, "cached_seed_peers"):
            return self.dynconfig.cached_seed_peers()
        return []

    async def _trigger_seed_peer(self, seed: dict, spec: dict) -> bool:
        """Fire Peer.TriggerDownloadTask at a seed daemon (same RPC the
        scheduler uses — seed_client.py)."""
        from dragonfly2_tpu.rpc import Client

        addr = NetAddr.tcp(seed.get("ip", ""), int(seed.get("port", 0)))
        cli = Client(addr)
        try:
            resp = await cli.call("Peer.TriggerDownloadTask", spec, timeout=10.0)
            return bool(resp and resp.get("ok"))
        except Exception:
            return False
        finally:
            await cli.close()

    # -- conductor factory (P2P path) --------------------------------------

    def _make_conductor(self, *, task_id: str, peer_id: str, request, store,
                        on_piece, is_seed: bool = False,
                        limiter=None) -> PeerTaskConductor:
        disable_back_source = getattr(request, "disable_back_source", False)
        if self.announcer is None:
            raise RuntimeError("conductor requires a started daemon (announcer missing)")
        # Single source of truth for the host record: the announcer's wire
        # form (minus telemetry) — scheduler must see ONE identity per host.
        host_info = self.announcer.host_wire()
        host_info.pop("telemetry", None)
        meta = {
            "tag": request.meta.tag,
            "application": request.meta.application,
            "digest": request.meta.digest,
            "filters": request.meta.filter.split("&") if request.meta.filter else [],
            "header": dict(request.meta.header),
            "priority": request.meta.priority,
            "tenant": request.meta.tenant,
            "range": request.meta.range,
            "pod_broadcast": getattr(request, "pod_broadcast", False),
            "digest_from_parent": getattr(request, "digest_from_parent", ""),
        }
        return PeerTaskConductor(
            task_id=task_id,
            peer_id=peer_id,
            url=request.url,
            store=store,
            scheduler_client=self.scheduler_client,
            piece_manager=self.piece_manager,
            host_info=host_info,
            meta=meta,
            flight=self.task_manager.flight.task(task_id),
            quarantine=self.task_manager.quarantine,
            is_seed=is_seed or (self.config.seed_peer
                                and not getattr(request, "as_peer", False)),
            piece_parallelism=self.config.download.parent_concurrency,
            report_batch=self.config.download.report_batch,
            limiter=limiter if limiter is not None else self.task_manager.limiter,
            on_piece=on_piece,
            wfq=self.qos_gate,
            disable_back_source=disable_back_source,
            local_range_source=(
                lambda s, cb, _req=request:
                self.task_manager.import_range_from_local_parent(s, _req, cb)),
        )

    async def _resolve_schedulers_from_manager(self) -> None:
        """Manager-source dynconfig: resolve (and keep fresh) the scheduler
        set; static config addrs stay as fallback (reference
        client/config/dynconfig_manager.go). The refresh loop always runs, so
        a daemon started before any scheduler registers picks one up on the
        next refresh instead of staying sourceless forever."""
        from dragonfly2_tpu.daemon.dynconfig import DaemonDynconfig

        h = self.config.host
        self.dynconfig = DaemonDynconfig(
            local_addrs=self.config.scheduler.addrs,
            manager_addr=self.config.manager_addr,
            host_info={"hostname": h.hostname, "ip": h.ip, "idc": h.idc,
                       "location": h.location, "pod": h.tpu_slice},
            cache_dir=self.config.dfpath.cache_dir)
        addrs = await self.dynconfig.scheduler_addrs()
        if addrs:
            self._apply_scheduler_addrs(addrs)
        else:
            log.warning("manager returned no schedulers yet; will keep polling")

        def _on_change(data: dict) -> None:
            fresh = [f"{s['ip']}:{s['port']}" for s in data.get("schedulers", [])
                     if s.get("state") == "active"]
            if fresh:
                self._apply_scheduler_addrs(fresh)

        self.dynconfig.register(_on_change)
        self.dynconfig.serve()

    def _apply_scheduler_addrs(self, addrs: list[str]) -> None:
        if self.scheduler_client is None:
            self.scheduler_client = SchedulerClient(addrs)
            self.task_manager.scheduler_client = self.scheduler_client
            self.task_manager.conductor_factory = self._make_conductor
            # Late discovery (daemon already serving): bring the announcer up
            # now so the scheduler learns this host.
            if self._started and self.announcer is None:
                self.announcer = Announcer(
                    self.config, self.scheduler_client,
                    peer_port=self._peer_port, upload_port=self.upload.port,
                    recorder=self.task_manager.flight)
                asyncio.create_task(self.announcer.start())
        else:
            self.scheduler_client.update_addrs(addrs)

    async def _gc_storage(self) -> None:
        self.storage.gc()
        if self.task_manager.device_sinks is not None:
            # TTL sweep of unclaimed device sinks: content-sized HBM must
            # not stay resident for the daemon's lifetime.
            self.task_manager.device_sinks.gc()

    # -- lifecycle ---------------------------------------------------------

    async def start(self) -> None:
        """Bring every service up (non-blocking)."""
        # Retain FIRST: services go live mid-start, and a sibling
        # daemon's stop() must not close the shared origin sessions under
        # a request that raced in. A failed start releases in the
        # except — both hygiene properties hold.
        from dragonfly2_tpu.source.client import default_registry

        self._source_registry = default_registry().retain()
        try:
            await self._start_inner()
        except BaseException:
            registry, self._source_registry = self._source_registry, None
            if registry is not None:
                await registry.release()
            raise

    async def _start_inner(self) -> None:
        # Chaos fabric: armed ONLY when DF_CHAOS is set (benches/e2e fault
        # drills). The guard keeps pkg/chaos entirely unimported — and the
        # data plane hook-free — in normal operation.
        if os.environ.get("DF_CHAOS"):
            from dragonfly2_tpu.pkg import chaos

            chaos.maybe_enable_from_env()
        # Warm the native data-plane probe off-loop: a cold first import
        # compiles the C++ library (seconds of g++), which must not freeze
        # the event loop at the first piece write on the hot path.
        from dragonfly2_tpu.storage import local_store

        await asyncio.get_running_loop().run_in_executor(None, local_store._native)
        if self.config.manager_addr:
            await self._resolve_schedulers_from_manager()
        self.task_manager.shaper.serve()
        # Flight recorder: post-mortem bundles land next to the logs so a
        # failed task's autopsy survives the process (pkg/flight).
        recorder = self.task_manager.flight
        if not recorder.dump_dir:
            recorder.dump_dir = self.config.dfpath.log_dir
        recorder.keep_bundles = self.config.flight_keep_bundles
        if self.config.clock_offset_s:
            recorder.wall_offset = self.config.clock_offset_s
        if self.config.prof.enabled:
            # Runtime observatory: always-on sampler + the loop's account
            # + GC observatory (pkg/prof). Holds of the loop and slow
            # pauses stamp typed events into every running flight, the
            # account's slices its own ring (runtime:loop:daemon); the
            # probe feeds a daemon-side loop_lag SLO engine at /debug/slo.
            from dataclasses import replace as _dc_replace

            from dragonfly2_tpu.pkg import prof as proflib
            from dragonfly2_tpu.pkg import slo as slolib

            self.prof_obs = proflib.install(self.config.prof,
                                            recorder=recorder)
            self._prof_probe = self.prof_obs.arm_loop("daemon")
            recorder.runtime = self.prof_obs
            self._runtime_slo = slolib.SLOEngine(
                specs=tuple(
                    _dc_replace(s, threshold=self.config.prof.lag_slow_s)
                    for s in slolib.RUNTIME_SLOS),
                probes=self.prof_obs.slo_probes())
        if self.config.metrics_port >= 0:
            from dragonfly2_tpu.pkg.metrics_server import MetricsServer

            # Loopback by default: /debug exposes live stacks; operators
            # who want network scraping front it deliberately.
            self.metrics = MetricsServer(flight=recorder,
                                         prof=self.prof_obs,
                                         slo=self._runtime_slo)
            await self.metrics.serve("127.0.0.1", self.config.metrics_port)
        await self.rpc.serve_download(NetAddr.unix(self.config.unix_sock))
        if self.config.download.peer_port >= 0:  # -1 disables the peer service
            await self.rpc.serve_peer(
                NetAddr.tcp(self.config.host.ip, self.config.download.peer_port))
        await self.upload.serve(self.config.host.ip, self.config.upload.port)
        if self.proxy is not None:
            await self.proxy.serve(self.config.host.ip, self.config.proxy.port)
            if self.config.proxy.sni_enabled:
                await self.proxy.serve_sni(
                    self.config.host.ip, self.config.proxy.sni_port,
                    hijack=self.config.proxy.sni_hijack)
        if self.object_storage is not None:
            await self.object_storage.serve(self.config.host.ip,
                                            self.config.object_storage.port)
        if self.config.pex.enabled:
            from dragonfly2_tpu.daemon.pex import PeerExchange

            self.pex = PeerExchange(
                ip=self.config.host.ip,
                peer_port=self.rpc.peer_server.port() if self.rpc.peer_server._servers else 0,
                upload_port=self.upload.port,
                secret=self.config.pex.secret)
            await self.pex.start(self.config.pex.port, self.config.pex.seeds)
            self.task_manager.pex = self.pex
            # Gossip everything already complete on disk (restart recovery).
            for store in self.storage.tasks():
                if store.metadata.done and not store.metadata.invalid:
                    self.pex.add_task(store.metadata.task_id)
        peer_port = self.rpc.peer_server.port() if self.rpc.peer_server._servers else 0
        self._peer_port = peer_port
        self._started = True
        if self.scheduler_client is not None:
            self.announcer = Announcer(
                self.config, self.scheduler_client,
                peer_port=peer_port,
                upload_port=self.upload.port,
                recorder=self.task_manager.flight,
            )
            await self.announcer.start()
        self.gc.serve()
        log.info(
            "daemon up",
            sock=self.config.unix_sock,
            peer_port=peer_port,
            upload_port=self.upload.port,
            seed=self.config.seed_peer,
        )

    async def serve(self) -> None:
        await self.start()
        if self.config.alive_time > 0:
            try:
                await asyncio.wait_for(self._stopped.wait(), self.config.alive_time)
            except asyncio.TimeoutError:
                log.info("alive time reached, exiting")
        else:
            await self._stopped.wait()

    async def stop(self) -> None:
        self.gc.stop()
        self.task_manager.shaper.stop()
        if self.pex is not None:
            await self.pex.stop()
        if self.metrics is not None:
            await self.metrics.close()
        if self.prof_obs is not None:
            from dragonfly2_tpu.pkg import prof as proflib

            if self._prof_probe is not None:
                self._prof_probe.disarm()
                self.prof_obs.probes.pop(self._prof_probe.name, None)
            self.task_manager.flight.runtime = None
            proflib.release(self.prof_obs)
            self.prof_obs = None
        if self.dynconfig is not None:
            await self.dynconfig.stop()
        if self.announcer is not None:
            await self.announcer.stop()
        if self.scheduler_client is not None:
            await self.scheduler_client.close()
        if self.proxy is not None:
            await self.proxy.close()
        if self.object_storage is not None:
            await self.object_storage.close()
        await self.upload.close()
        await self.rpc.close()
        if self.task_manager.device_sinks is not None:
            self.task_manager.device_sinks.close()
        registry = getattr(self, "_source_registry", None)
        if registry is not None:
            self._source_registry = None
            await registry.release(close_when_idle=True)
        self.storage.close()
        self._stopped.set()

    def peer_port(self) -> int:
        return self.rpc.peer_server.port()
