"""Daemon bootstrap: assemble storage, piece engine, servers; serve.

Role parity: reference ``client/daemon/daemon.go`` ``New``/``Serve`` — wires
the listeners (local API gRPC on unix socket, peer gRPC on TCP, upload HTTP,
optional proxy/object-gateway HTTP), the GC loop, the announcer, and the
scheduler client.
"""

from __future__ import annotations

import asyncio
import json
import logging
import os
import socket
from typing import Any

from ..common.dfpath import DFPath
from ..common.errors import Code, DFError
from ..common.gc import GC, GCTask
from ..idl.messages import DeviceSink, Host, HostType
from ..storage.manager import StorageConfig, StorageManager
from ..tpu import topology
from .config import DaemonConfig
from .peertask_manager import PeerTaskManager
from .piece_manager import PieceManager
from ..rpc.client import ChannelPool
from .piece_downloader import PieceDownloader
from .piece_engine import PieceEngine
from .rpcserver import DaemonService, build_service
from .scheduler_session import SchedulerConnector
from .traffic_shaper import TrafficShaper
from .upload_server import UploadServer
from ..rpc.server import RPCServer

log = logging.getLogger("df.core.daemon")


def _local_ip() -> str:
    try:
        s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        s.connect(("10.255.255.255", 1))
        ip = s.getsockname()[0]
        s.close()
        return ip
    except OSError:
        return "127.0.0.1"


class Daemon:
    def __init__(self, cfg: DaemonConfig, *, scheduler_factory: Any = None,
                 p2p_engine_factory: Any = None):
        self.cfg = cfg
        self.hostname = cfg.hostname or socket.gethostname()
        self.host_ip = cfg.host_ip or _local_ip()
        self.paths = DFPath(cfg.workdir) if cfg.workdir else DFPath()
        self.paths.ensure()
        self.topology = topology.detect()
        self.storage_mgr = StorageManager(StorageConfig(
            data_dir=os.path.join(self.paths.data_dir, "tasks"),
            task_ttl_s=cfg.storage.task_ttl_s,
            disk_gc_high_ratio=cfg.storage.disk_gc_high_ratio,
            disk_gc_low_ratio=cfg.storage.disk_gc_low_ratio,
            capacity_bytes=cfg.storage.capacity_bytes,
            gc_interval_s=cfg.storage.gc_interval_s,
            dedupe_enabled=cfg.storage.dedupe_enabled,
            reload_verify=cfg.storage.reload_verify,
            popularity_halflife_s=cfg.storage.popularity_halflife_s))
        self.piece_mgr = PieceManager(cfg.download)
        self.shaper = TrafficShaper(
            total_rate_bps=cfg.download.total_rate_limit_bps,
            kind=cfg.download.traffic_shaper_kind)
        # multi-tenant QoS: class-aware admission + brownout shed
        # (daemon/qos.py); the shaper rides along for /debug/qos's
        # per-class rate readout
        from .qos import QosGovernor
        self.qos = QosGovernor(cfg.qos, shaper=self.shaper)
        # per-parent verdict ledger (daemon/verdicts.py): the local half
        # of the swarm immune system — typed failure memory consulted by
        # the engine's parent admission, the PEX rung, and self-quarantine
        from .verdicts import VerdictLedger
        self.verdicts = VerdictLedger()
        if self.storage_mgr.castore is not None:
            self.storage_mgr.castore.on_rot = lambda tid: \
                self.verdicts.self_quarantine(
                    f"cas placement re-verify failed (task {tid[:12]})")
        from .flight_recorder import FlightRecorder
        self.flight_recorder = FlightRecorder(
            enabled=cfg.flight.enabled, max_tasks=cfg.flight.max_tasks,
            max_events=cfg.flight.max_events,
            max_serves=cfg.flight.max_serves)
        # PEX gossip plane (daemon/pex.py): swarm index + gossiper exist
        # before the upload server so its routes mount at start; ports and
        # topology resolve lazily through host_info()
        # cut-through relay hub (daemon/relay.py): in-flight landing spans
        # the upload server serves to the watermark; exists before the
        # upload server and the engine factory so both share it
        self.relay = None
        if cfg.download.relay_enabled:
            from .relay import RelayHub
            self.relay = RelayHub()
        self.pex = None
        if cfg.pex.enabled:
            from .pex import PexGossiper
            from .swarm_index import SwarmIndex
            self.pex = PexGossiper(
                storage_mgr=self.storage_mgr,
                host_info=self.host_info,
                index=SwarmIndex(ttl_s=cfg.pex.ttl_s),
                interval_s=cfg.pex.interval_s, fanout=cfg.pex.fanout,
                max_digest_tasks=cfg.pex.max_digest_tasks,
                bootstrap=cfg.pex.bootstrap, relay=self.relay,
                verdicts=self.verdicts,
                pod_scope=cfg.pex.pod_scope,
                pod_seed=cfg.pex.pod_seed,
                federation_peers=cfg.pex.federation_peers)
        self.upload_server = UploadServer(
            self.storage_mgr, port=cfg.upload.port,
            rate_limit_bps=cfg.upload.rate_limit_bps,
            debug_endpoints=cfg.upload.debug_endpoints,
            concurrent_limit=cfg.upload.concurrent_limit,
            bulk_concurrent_limit=cfg.upload.bulk_concurrent_limit,
            host=cfg.listen_ip, flight_recorder=self.flight_recorder,
            pex=self.pex, relay=self.relay,
            relay_stall_s=cfg.download.relay_stall_s, qos=self.qos,
            verdicts=self.verdicts)
        # scopes the upload.serve faultgate key (byzantine chaos) to THIS
        # daemon even when several share one process (the test pod)
        self.upload_server.host_id = f"{self.hostname}-{self.host_ip}"
        self._scheduler_factory = scheduler_factory
        self._p2p_engine_factory = p2p_engine_factory
        self.scheduler: Any = None
        self.ptm: PeerTaskManager | None = None
        self.rpc: RPCServer | None = None
        self.local_rpc: RPCServer | None = None
        self.gc = GC()
        self.proxy_server: Any = None
        self.object_gateway: Any = None
        self.announcer: Any = None
        self.prober: Any = None
        self.manager: Any = None
        self.health: Any = None
        # this host's jax devices; None until a device sink is first asked
        # for (device_runtime) — the process holds no chip before that
        self._devices: list | None = None
        self._devices_lock = asyncio.Lock()

    # ------------------------------------------------------------------

    def host_info(self) -> Host:
        return Host(
            id=f"{self.hostname}-{self.host_ip}",
            ip=self.host_ip, hostname=self.hostname,
            port=self.rpc.port if self.rpc else 0,
            download_port=self.upload_server.port,
            type=HostType.SUPER_SEED if self.cfg.is_seed else HostType.NORMAL,
            os=os.uname().sysname.lower(), platform=os.uname().machine,
            topology=self.topology,
            concurrent_upload_limit=self.cfg.upload.concurrent_limit,
            # self-quarantine rides every register AND announce: the
            # scheduler's quarantine registry treats the flag as hard
            # evidence (this daemon verified its own bit-rot)
            quarantined=self.verdicts.self_quarantined)

    async def device_runtime(self) -> list:
        """This host's devices, bringing JAX up on first use — the moment
        this process takes the chip. Constructing or starting a daemon
        never comes here; only a device-sink request does. The cold init
        (8-13 s on a v5e) runs in a worker thread, once; one native call
        inside it still holds the GIL for seconds (0.3-5.6 s seen on one
        chip), so an embedding process that knows it will open sinks can
        await this right after start() and pay that stall before traffic
        does. What the devices
        tell about the host's position rides the next announce and
        register."""
        if self._devices is None:
            async with self._devices_lock:
                if self._devices is None:
                    from ..tpu import runtime
                    try:
                        devices = await asyncio.to_thread(runtime.bring_up)
                    except Exception as exc:  # noqa: BLE001 - jax raises a zoo
                        raise DFError(
                            Code.CLIENT_DEVICE_SINK_ERROR,
                            f"no device runtime: {type(exc).__name__}: "
                            f"{exc}") from exc
                    self.topology = topology.with_devices(self.topology,
                                                          devices)
                    if hasattr(self.scheduler, "refresh_host"):
                        self.scheduler.refresh_host(self.host_info())
                    self._devices = devices
        return self._devices

    async def device_sink_builder(self, spec: DeviceSink):
        """Returns a factory(content_length[, shard_specs]) -> DeviceIngest
        honoring the request's sink spec; raises when this process cannot
        bring a device runtime up. ``shard_specs`` (sharded tasks,
        common/sharding.py) switches the sink to manifest mode: named
        uneven shards that each become a device array the moment their
        bytes are covered. ``factory.chips`` is how many chips the sink
        is opened over: a manifest that places a shard beyond them is
        refused by the conductor before a byte moves."""
        devices = await self.device_runtime()

        def factory(content_length: int, shard_specs: list | None = None):
            from ..tpu.hbm_sink import DeviceIngest
            if shard_specs:
                return DeviceIngest(content_length, dtype=spec.dtype,
                                    devices=devices,
                                    shard_specs=shard_specs)
            spd = spec.pipeline_shards
            if spd <= 0:
                # auto: one shard per DMA unit (32 MiB; the unit size
                # against smaller ones is not measured). The overlap comes
                # from back-source's front-to-back work-queue coverage
                # completing these units progressively.
                from ..common.piece import INGEST_DMA_UNIT_BYTES
                per_dev = -(-content_length // len(devices))
                spd = max(1, min(32, per_dev // INGEST_DMA_UNIT_BYTES))
            return DeviceIngest(content_length, dtype=spec.dtype,
                                devices=devices, shards_per_device=spd)
        factory.chips = len(devices)
        return factory

    async def _enroll_security(self):
        from ..rpc.security import obtain_certificate
        from ..rpc.server import TLSOptions

        sec = self.cfg.security
        token = sec.issue_token
        if not token and sec.issue_token_path:
            # dflint: disable=DF001 — one-shot KB token read during startup enrollment, before the daemon serves traffic
            with open(sec.issue_token_path, encoding="utf-8") as f:
                # dflint: disable=DF001 — see above: startup enrollment
                token = f.read().strip()
        if not sec.ca_cert:
            log.warning(
                "security: enrolling over a channel with NO pinned fleet "
                "CA — the issuance token travels unprotected and the CA is "
                "trust-on-first-use; set security.ca_cert (and a TLS "
                "manager port) for untrusted networks")
        cert, key, ca = await obtain_certificate(
            self.cfg.manager_addresses,
            hosts=[self.host_ip, self.hostname],
            token=token, out_dir=os.path.join(self.paths.cache_dir, "tls"),
            validity_s=sec.cert_validity_s, tls_ca=sec.ca_cert)
        self.fleet_ca = sec.ca_cert or ca
        # peer channels verify the CA AND present our leaf; the server
        # REQUIRES client certs — that is the mutual half of mTLS
        self._peer_tls_ca = self.fleet_ca
        self._peer_tls_cert = cert
        self._peer_tls_key = key
        loop = asyncio.get_running_loop()
        self._cert_renewal = loop.create_task(self._renew_certs_loop())
        return TLSOptions(cert, key, ca_path=self.fleet_ca,
                          require_client_cert=True)

    async def _renew_certs_loop(self) -> None:
        """Re-enroll at 2/3 validity (reference: certify re-issues on
        demand). Outbound material rotates live; see SecurityConfig NOTE
        for the listener restart window."""
        from ..rpc.security import obtain_certificate
        sec = self.cfg.security
        while True:
            await asyncio.sleep(max(sec.cert_validity_s * 2 / 3, 60))
            try:
                token = sec.issue_token
                if not token and sec.issue_token_path:
                    # dflint: disable=DF001 — KB token reread at 2/3 cert validity (hours apart)
                    with open(sec.issue_token_path, encoding="utf-8") as f:
                        # dflint: disable=DF001 — see above: hours-apart renewal
                        token = f.read().strip()
                await obtain_certificate(
                    self.cfg.manager_addresses,
                    hosts=[self.host_ip, self.hostname], token=token,
                    out_dir=os.path.join(self.paths.cache_dir, "tls"),
                    validity_s=sec.cert_validity_s, tls_ca=sec.ca_cert)
                log.info("fleet certificate renewed")
            except Exception as exc:  # noqa: BLE001 - retry next cycle
                log.error("fleet certificate renewal failed: %s", exc)

    _active_in_process = 0   # daemons started but not yet stopped (this proc)

    async def start(self) -> None:
        # health plane FIRST: the watchdog must already be sweeping when
        # the earliest download section opens (refcounted process-wide,
        # like the metrics REGISTRY — co-resident daemons share it)
        from ..common import health
        self.health = health.PLANE
        self.health.acquire(self.cfg.health.to_plane())
        self.health.attach_recorder(self.flight_recorder)
        if self.cfg.plugin_dir:
            from ..common.plugins import load_source_plugins
            load_source_plugins(self.cfg.plugin_dir)
        if self.storage_mgr.reloaded_tasks:
            # warm restart: re-verify the reloaded pieces (crc32c, fanned
            # across the storage pool — never this loop) BEFORE anything
            # serves or advertises them; what fails verification is
            # dropped here, so the swarm only ever hears bytes that
            # re-hashed
            stats = await self.storage_mgr.verify_reloaded_async()
            log.info("warm restart: %d task(s) reloaded, %d piece(s) "
                     "verified, %d dropped", self.storage_mgr.reloaded_tasks,
                     stats.get("pieces_ok", 0),
                     stats.get("pieces_dropped", 0))
            if stats.get("pieces_rot", 0):
                # ROT only — pieces of COMPLETED tasks that once verified
                # and now hash wrong: the disk is lying, so self-
                # quarantine (stop advertising in PEX, flag every
                # announce) until an operator/restart re-verifies clean.
                # Pulling still works: quarantine is about not SERVING.
                # Drops from PARTIAL tasks are ordinary crash-torn writes
                # (data is not fsynced per write) and heal silently —
                # every unclean restart would otherwise sideline a
                # healthy daemon pod-wide.
                self.verdicts.self_quarantine(
                    f"boot re-verify found {stats['pieces_rot']} "
                    f"rotted piece(s) in completed tasks")
        if self.cfg.tracing.enabled:
            from ..common import tracing
            tracing.configure(
                service=f"dfdaemon/{self.hostname}",
                jsonl_path=self.cfg.tracing.jsonl_path or os.path.join(
                    self.paths.log_dir, "traces.jsonl"),
                otlp_endpoint=self.cfg.tracing.otlp_endpoint,
                sample_ratio=self.cfg.tracing.sample_ratio)
        # mTLS enrollment FIRST: the peer channel pool and the rpc server
        # both depend on the issued material
        self._rpc_tls = None
        self._peer_tls_ca = ""
        self._peer_tls_cert = ""
        self._peer_tls_key = ""
        if self.cfg.security.enabled:
            self._rpc_tls = await self._enroll_security()
        if self._peer_tls_cert:
            self.upload_server.tls = (self._peer_tls_cert,
                                      self._peer_tls_key, self._peer_tls_ca)
            # rollout knob applies to BOTH planes; must be set before
            # upload_server.start() decides whether to front a mux
            self.upload_server.tls_policy = self.cfg.security.tls_policy
        if self.cfg.download.source_ca or self.cfg.download.source_insecure:
            # the source client is a process singleton: remember the prior
            # trust setting so stop() restores it (co-resident daemons in
            # one process — the test suite — must not inherit this one's)
            from ..source.client import client_for
            http = client_for("https://")
            self._prev_source_tls = http._ssl
            http.set_tls(insecure=self.cfg.download.source_insecure,
                         ca_file=self.cfg.download.source_ca)
        await self.upload_server.start()
        self._peer_channels = ChannelPool(
            tls_ca=self._peer_tls_ca, tls_cert=self._peer_tls_cert,
            tls_key=self._peer_tls_key)
        tls_triple = ((self._peer_tls_cert, self._peer_tls_key,
                       self._peer_tls_ca)
                      if self._peer_tls_cert else None)
        self.upload_server.tls = tls_triple
        self._piece_downloader = PieceDownloader(
            timeout_s=self.cfg.download.piece_timeout_s, tls=tls_triple)
        engine_factory = self._p2p_engine_factory
        if engine_factory is None:
            def engine_factory() -> PieceEngine:
                return PieceEngine(
                    parallelism=self.cfg.download.piece_parallelism,
                    schedule_timeout_s=self.cfg.scheduler.schedule_timeout_s,
                    piece_timeout_s=self.cfg.download.piece_timeout_s,
                    downloader=self._piece_downloader,
                    channel_pool=self._peer_channels,
                    slice_name=(self.topology.slice_name
                                if self.topology else ""),
                    peer_observer=(self.pex.observe_parent
                                   if self.pex is not None else None),
                    relay=self.relay,
                    verdicts=self.verdicts)
        if self.pex is not None:
            # the pex rung builds a FRESH engine per pull (the scheduler
            # path may already have consumed the conductor's), and gossip
            # exchanges present the fleet client leaf under mTLS
            self.pex.engine_factory = engine_factory
            self.pex.tls = tls_triple
        self.shaper.start()
        self.ptm = PeerTaskManager(
            storage_mgr=self.storage_mgr, piece_mgr=self.piece_mgr,
            hostname=self.hostname, host_ip=self.host_ip,
            scheduler=None,
            p2p_engine_factory=engine_factory,
            device_sink_builder=self.device_sink_builder,
            is_seed=self.cfg.is_seed, shaper=self.shaper,
            prefetch_whole_file=self.cfg.download.prefetch_whole_file,
            flight_recorder=self.flight_recorder, pex=self.pex,
            relay=self.relay, qos=self.qos)
        svc = DaemonService(self.ptm,
                            upload_addr=f"{self.host_ip}:{self.upload_server.port}")
        # fleet mTLS: enroll with the manager, serve the peer RPC port with
        # the issued leaf, dial other peers trusting the fleet CA
        # peer-facing TCP server: bind the listen address, advertise host_ip
        self.rpc = RPCServer(f"{self.cfg.listen_ip}:{self.cfg.rpc_port}",
                             tls=self._rpc_tls,
                             tls_policy=self.cfg.security.tls_policy)
        for sdef in build_service(svc):
            self.rpc.register(sdef)
        await self.rpc.start()
        # scheduler connector needs the resolved rpc/upload ports for register
        if self._scheduler_factory is not None:
            self.scheduler = self._scheduler_factory(self)
        elif self.cfg.scheduler.addresses:
            self.scheduler = SchedulerConnector(
                self.cfg.scheduler.addresses, self.host_info(),
                register_timeout_s=self.cfg.scheduler.register_timeout_s,
                failover_n=self.cfg.scheduler.failover_n,
                demote_s=self.cfg.scheduler.demote_s)
        elif self.cfg.manager_addresses:
            await self._attach_manager()
        self.ptm.scheduler = self.scheduler
        # S2: demotion memory survives the daemon process (next to the
        # rest of the daemon's on-disk metadata) — covers every boot path
        # above (configured addresses, factory, manager discovery)
        await asyncio.to_thread(self._restore_scheduler_demotions)
        # local API over unix socket (dfget/dfcache/dfstore)
        sock = self.cfg.unix_sock or self.paths.daemon_sock()
        # dflint: disable=DF001 — stale-socket cleanup during start(), nothing is served yet
        if os.path.exists(sock):
            # dflint: disable=DF001 — see above: startup path
            os.unlink(sock)
        self.local_rpc = RPCServer(f"unix:{sock}")
        for sdef in build_service(svc):
            self.local_rpc.register(sdef)
        await self.local_rpc.start()
        self.unix_sock = sock
        # optional HTTP surfaces
        if self.cfg.proxy.enabled:
            from .proxy import ProxyServer
            self.proxy_server = ProxyServer(self, self.cfg.proxy)
            await self.proxy_server.start()
        if self.cfg.object_storage.enabled:
            from .objectstorage import ObjectGateway
            self.object_gateway = ObjectGateway(self, self.cfg.object_storage)
            await self.object_gateway.start()
        self.gc.add(GCTask("storage", self.cfg.storage.gc_interval_s,
                           self.storage_mgr.try_gc))
        self.gc.start()
        await self._wire_scheduler_extras()
        if self.pex is not None:
            self.pex.scheduler = self.scheduler
            # a warm-restarted daemon re-seeds its PEX digests from disk
            # NOW (one immediate push-pull round against bootstrap/known
            # peers) instead of after the first jittered interval — the
            # swarm learns the holder is back within one gossip round
            await self.pex.start(
                initial_round=bool(self.storage_mgr.reloaded_tasks))
        # counted only after everything above succeeded, consumed exactly
        # once by stop(): a failed start() or a double stop() must neither
        # strand the count high (leak fix disabled) nor drive it to zero
        # early (shared sessions yanked from a still-running daemon)
        self._counted_active = True
        Daemon._active_in_process += 1
        log.info("daemon up: host=%s ip=%s rpc=%s upload=%d sock=%s seed=%s",
                 self.hostname, self.host_ip, self.rpc.port,
                 self.upload_server.port, sock, self.cfg.is_seed)

    async def _attach_manager(self) -> None:
        """Discover schedulers via the manager (dynconfig role); seed
        daemons also register themselves as seed peers + keepalive."""
        from ..idl.messages import (GetSchedulersRequest,
                                    RegisterSeedPeerRequest)
        from ..rpc.manager_link import ManagerLink

        self.manager = ManagerLink(self.cfg.manager_addresses)
        try:
            if self.cfg.is_seed:
                await self.manager.register_seed_peer(RegisterSeedPeerRequest(
                    hostname=self.hostname, ip=self.host_ip,
                    port=self.rpc.port,
                    download_port=self.upload_server.port,
                    seed_peer_cluster_id=1, topology=self.topology))
                self.manager.start_keepalive(source_type="seed_peer",
                                             hostname=self.hostname,
                                             ip=self.host_ip,
                                             port=self.rpc.port)
            resp = await self.manager.get_schedulers(GetSchedulersRequest(
                hostname=self.hostname, ip=self.host_ip,
                topology=self.topology))
            addrs = [f"{s.ip}:{s.port}" for s in (resp.schedulers or [])]
            if addrs:
                self.scheduler = SchedulerConnector(
                    addrs, self.host_info(),
                    register_timeout_s=self.cfg.scheduler.register_timeout_s,
                    failover_n=self.cfg.scheduler.failover_n,
                    demote_s=self.cfg.scheduler.demote_s)
            else:
                log.info("manager knows no active schedulers; back-source "
                         "only until the refresh loop finds one")
        except Exception as exc:  # noqa: BLE001 - manager optional
            log.warning("manager attach failed (%s); back-source only", exc)
        if self.cfg.scheduler.refresh_interval_s > 0:
            self._sched_refresh = asyncio.get_running_loop().create_task(
                self._scheduler_refresh_loop())

    async def _wire_scheduler_extras(self) -> None:
        """Announcer + topology prober ride the scheduler connection; wired
        at boot AND when the refresh loop adopts a late scheduler — a
        healed daemon must announce itself and probe like one that booted
        after the scheduler."""
        if self.scheduler is None:
            return
        if self.pex is not None:
            # a late-adopted scheduler must also get the ticker's demoted-
            # member revival probe
            self.pex.scheduler = self.scheduler
        if self.announcer is None and hasattr(self.scheduler,
                                              "announce_host"):
            from .announcer import Announcer
            self.announcer = Announcer(self)
            await self.announcer.start()
        if (self.prober is None and self.cfg.probe_enabled
                and hasattr(self.scheduler, "sync_probes")):
            from .networktopology import NetworkTopologyProber
            self.prober = NetworkTopologyProber(self)
            await self.prober.start()

    async def _scheduler_refresh_loop(self) -> None:
        """Track the manager's scheduler set (reference daemon dynconfig
        refresh): a replaced scheduler reaches the ring, and a daemon that
        booted before ANY scheduler registered heals out of back-source-
        only the moment one appears. An empty/failed fetch keeps the last
        known set — a manager blip must not strand live schedulers."""
        from ..idl.messages import GetSchedulersRequest

        while True:
            await asyncio.sleep(self.cfg.scheduler.refresh_interval_s)
            try:
                resp = await self.manager.get_schedulers(GetSchedulersRequest(
                    hostname=self.hostname, ip=self.host_ip,
                    topology=self.topology))
                addrs = [f"{s.ip}:{s.port}"
                         for s in (resp.schedulers or [])]
                if not addrs:
                    continue
                if self.scheduler is None:
                    self.scheduler = SchedulerConnector(
                        addrs, self.host_info(),
                        register_timeout_s=self.cfg.scheduler
                        .register_timeout_s,
                        failover_n=self.cfg.scheduler.failover_n,
                        demote_s=self.cfg.scheduler.demote_s)
                    if self.ptm is not None:
                        self.ptm.scheduler = self.scheduler
                    await asyncio.to_thread(
                        self._restore_scheduler_demotions)
                    await self._wire_scheduler_extras()
                    log.info("schedulers appeared: %s", addrs)
                elif set(addrs) != set(self.scheduler.addresses):
                    log.info("scheduler set changed: %s -> %s",
                             self.scheduler.addresses, addrs)
                    self.scheduler.update_addresses(addrs)
            except Exception as exc:  # noqa: BLE001 - manager flaky is fine
                log.debug("scheduler refresh failed: %s", exc)

    def _demotions_path(self) -> str:
        return os.path.join(self.paths.data_dir, "scheduler_demotions.json")

    def _restore_scheduler_demotions(self) -> None:
        """S2: re-arm the connector's sticky demotion memory from the
        previous process — a restarted daemon must not re-probe every
        known-dead scheduler through the full register-timeout ladder."""
        if self.scheduler is None or not hasattr(self.scheduler,
                                                 "restore_demotions"):
            return
        try:
            with open(self._demotions_path(), "rb") as f:
                state = json.loads(f.read())
        except FileNotFoundError:
            return
        except (OSError, ValueError) as exc:
            log.debug("demotion state unreadable (%s); starting clean", exc)
            return
        self.scheduler.restore_demotions(state)

    def _persist_scheduler_demotions(self) -> None:
        """Counterpart of ``_restore_scheduler_demotions`` on the stop
        path (tmp+fsync+rename, the TaskMetadata.save idiom). Best
        effort: shutdown must not fail on a full disk."""
        if self.scheduler is None or not hasattr(self.scheduler,
                                                 "export_demotions"):
            return
        path = self._demotions_path()
        tmp = path + ".tmp"
        try:
            payload = json.dumps(self.scheduler.export_demotions(),
                                 sort_keys=True).encode()
            f = open(tmp, "wb")
            try:
                f.write(payload)
                f.flush()
                os.fsync(f.fileno())
            finally:
                f.close()          # fd released even on a torn write
            os.replace(tmp, path)
        except OSError as exc:
            log.debug("demotion persist failed: %s", exc)

    async def stop(self) -> None:
        renewal = getattr(self, "_cert_renewal", None)
        if renewal is not None:
            renewal.cancel()
        refresh = getattr(self, "_sched_refresh", None)
        if refresh is not None:
            refresh.cancel()
        if self.cfg.tracing.enabled:
            from ..common import tracing
            tracing.TRACER.flush()
        if hasattr(self, "_prev_source_tls"):
            from ..source.client import client_for
            client_for("https://")._ssl = self._prev_source_tls
            del self._prev_source_tls
        if getattr(self, "manager", None) is not None:
            await self.manager.close()
        if getattr(self, "prober", None) is not None:
            await self.prober.stop()
        await self.shaper.stop()
        if self.pex is not None:
            await self.pex.stop()
        if self.announcer is not None:
            await self.announcer.stop()
        await self.gc.stop()
        if self.ptm is not None:
            await self.ptm.shutdown()
        if self.proxy_server is not None:
            await self.proxy_server.stop()
        if self.object_gateway is not None:
            await self.object_gateway.stop()
        if self.local_rpc is not None:
            await self.local_rpc.stop(0.2)
        if self.rpc is not None:
            await self.rpc.stop(0.2)
        await self.upload_server.stop()
        if getattr(self, "_piece_downloader", None) is not None:
            await self._piece_downloader.close()
        if getattr(self, "_peer_channels", None) is not None:
            await self._peer_channels.close()
        if self.scheduler is not None:
            await asyncio.to_thread(self._persist_scheduler_demotions)
            if hasattr(self.scheduler, "leave_host"):
                await self.scheduler.leave_host()
            if hasattr(self.scheduler, "close"):
                await self.scheduler.close()
        # source-client sessions are process singletons shared by every
        # co-resident daemon: close them only when the LAST daemon leaves,
        # or asyncio reports them leaked on loop close (bench tpu phase)
        if getattr(self, "_counted_active", False):
            self._counted_active = False
            Daemon._active_in_process -= 1
            if Daemon._active_in_process == 0:
                from ..source.client import close_clients
                await close_clients()
        if getattr(self, "health", None) is not None:
            self.health.release()
            self.health = None
