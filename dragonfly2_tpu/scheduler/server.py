"""Scheduler bootstrap: wire resource, scheduling, seed client, GC, gRPC.

Role parity: reference ``scheduler/scheduler.go`` ``New``/``Serve``
(:110-299, :302) minus manager/Redis (dynconfig + keepalive attach in the
manager stage; job queues ride the manager's queue, not Redis).
"""

from __future__ import annotations

import asyncio
import logging

from ..common.gc import GC, GCTask
from ..rpc.server import RPCServer
from .config import SchedulerConfig
from .evaluator import make_evaluator
from .resource import Resource
from .scheduling import Scheduling
from .seed_client import SeedPeerClient
from .service import SchedulerService, build_service
from .topology_store import TopologyStore

log = logging.getLogger("df.sched.server")


class Scheduler:
    def __init__(self, cfg: SchedulerConfig, *, records=None, infer=None):
        self.cfg = cfg
        self.resource = Resource(peer_ttl_s=cfg.peer_ttl_s,
                                 task_ttl_s=cfg.task_ttl_s,
                                 host_ttl_s=cfg.host_ttl_s,
                                 peer_upload_limit=cfg.peer_upload_limit,
                                 seed_upload_limit=cfg.seed_upload_limit)
        self.topo = TopologyStore()
        evaluator = make_evaluator(cfg.algorithm, topo_store=self.topo,
                                   infer=infer, plugin_dir=cfg.plugin_dir)
        self.scheduling = Scheduling(cfg, evaluator)
        self.seed_client = SeedPeerClient(self.resource, cfg.seed_peers)
        if records is None and (cfg.records_dir or cfg.trainer_address):
            from .records import DownloadRecords
            records = DownloadRecords(cfg.records_dir)
        # decision ledger: every find/refresh ruling explained — live ring
        # at GET /debug/decisions, kind=decision rows into records (when
        # records are on) for the outcome join + dfbench --pr8 replay
        from .decision_ledger import DecisionLedger
        self.ledger = DecisionLedger(records=records)
        self.scheduling.decision_sink = self.ledger.on_decision
        # pod-wide quarantine registry: corrupt verdicts + self-flags in,
        # offer/relay/seed exclusion out, every transition a ledger row
        self.quarantine = None
        if cfg.quarantine_enabled:
            from .quarantine import QuarantineRegistry
            self.quarantine = QuarantineRegistry(
                corrupt_threshold=cfg.quarantine_corrupt_threshold,
                halflife_s=cfg.quarantine_halflife_s,
                probation_delay_s=cfg.quarantine_probation_delay_s,
                probe_successes=cfg.quarantine_probe_successes,
                probe_children=cfg.quarantine_probe_children,
                min_reporters=cfg.quarantine_min_reporters,
                sink=self.ledger.on_decision)
            self.scheduling.quarantine = self.quarantine
            self.seed_client.quarantine = self.quarantine
        # cross-pod federation view: fed from register/announce, consulted
        # by the scheduling filter; off (None) = exact pre-federation path
        self.federation = None
        if cfg.federation_enabled:
            from .federation import PodFederation
            self.federation = PodFederation(
                seeds_per_pod=cfg.federation_seeds_per_pod,
                quarantine=self.quarantine,
                sink=self.ledger.on_decision)
            self.scheduling.federation = self.federation
            # evicted hosts/tasks leave the election electorate too —
            # without this a GC'd (silently dead) pod seed would keep
            # winning elections it can never serve
            self.resource.on_host_evict = self.federation.forget_host
            self.resource.on_task_evict = self.federation.drop_task
        # sharded-checkpoint shard affinity: disjoint tree-fetch subsets
        # ruled at register for requests carrying UrlMeta.shards; the
        # eviction hooks CHAIN with federation's (both views must forget)
        self.sharded = None
        if cfg.shard_affinity_enabled:
            from .shard_affinity import ShardAffinity
            self.sharded = ShardAffinity(sink=self.ledger.on_decision)
            self.scheduling.sharded = self.sharded
            prev_host, prev_task = (self.resource.on_host_evict,
                                    self.resource.on_task_evict)

            def _evict_host(hid, _prev=prev_host, _sh=self.sharded):
                _sh.forget_host(hid)
                if _prev is not None:
                    _prev(hid)

            def _evict_task(tid, _prev=prev_task, _sh=self.sharded):
                _sh.drop_task(tid)
                if _prev is not None:
                    _prev(tid)

            self.resource.on_host_evict = _evict_host
            self.resource.on_task_evict = _evict_task
        # crash-survivable control plane (scheduler/statestore.py): the
        # slow-moving ruling state — quarantine ladder, shard-affinity
        # memos, seed elections, tenant quotas — journals to one
        # versioned snapshot. Event-driven cadence rides the components'
        # existing decision sinks (every covered transition already
        # emits a ledger row), so durability costs one dirty-flag store
        # per ruling and zero new wiring inside the components.
        self.statestore = None
        if cfg.statestore_dir:
            from .statestore import SchedulerStateStore
            self.statestore = SchedulerStateStore(
                cfg.statestore_dir, interval_s=cfg.statestore_interval_s)
            if self.quarantine is not None:
                self.statestore.register("quarantine",
                                         self.quarantine.export_state,
                                         self.quarantine.restore)
                self.quarantine.sink = self.statestore.wrap_sink(
                    self.quarantine.sink)
            if self.federation is not None:
                self.statestore.register("federation",
                                         self.federation.export_state,
                                         self.federation.restore)
                self.federation.sink = self.statestore.wrap_sink(
                    self.federation.sink)
            if self.sharded is not None:
                self.statestore.register("shard_affinity",
                                         self.sharded.export_state,
                                         self.sharded.restore)
                self.sharded.sink = self.statestore.wrap_sink(
                    self.sharded.sink)
        # fleet pulse plane (scheduler/fleetpulse.py): announce-borne
        # telemetry rings + EWMA anomaly detector + incident capture.
        # Anomaly firings ride the decision ledger (decision_kind=anomaly)
        # and the rings register with the statestore so incident history
        # survives a scheduler crash/failover.
        self.fleetpulse = None
        if cfg.fleetpulse_enabled:
            from .fleetpulse import FleetPulse
            self.fleetpulse = FleetPulse(
                sink=self.ledger.on_decision,
                quarantine=self.quarantine,
                federation=self.federation,
                statestore=self.statestore)
            if self.statestore is not None:
                self.statestore.register("fleetpulse",
                                         self.fleetpulse.export_state,
                                         self.fleetpulse.restore)
        self.service = SchedulerService(cfg, self.resource, self.scheduling,
                                        self.seed_client, self.topo,
                                        records=records, ledger=self.ledger,
                                        quarantine=self.quarantine,
                                        federation=self.federation,
                                        fleetpulse=self.fleetpulse)
        if self.statestore is not None:
            svc = self.service

            def _export_tenants() -> dict:
                return {"tenants": svc.tenants,
                        "applications": svc.applications}

            def _restore_tenants(sub: dict) -> int:
                # restored quotas hold until the first manager dynconfig
                # refresh overwrites them — a recovered brain enforces
                # tenant limits from ruling one instead of running
                # quota-blind for a refresh interval
                svc.tenants = dict(sub.get("tenants") or {})
                svc.applications = {k: int(v) for k, v in
                                    (sub.get("applications") or {}).items()}
                return len(svc.tenants)

            def _export_meta() -> dict:
                return {"epoch": svc.epoch}

            def _restore_meta(sub: dict) -> int:
                # strictly-increasing epoch across durable restarts: the
                # daemons' change detection must never see a restart
                # land on the same epoch value
                svc.epoch = max(svc.epoch, int(sub.get("epoch", 0)) + 1)
                return 1

            self.statestore.register("tenants", _export_tenants,
                                     _restore_tenants)
            self.statestore.register("meta", _export_meta, _restore_meta)
        self.announcer = None
        self.rpc: RPCServer | None = None
        self.gc = GC()
        self.port: int | None = None
        self.manager = None

    @property
    def address(self) -> str:
        return f"{self.cfg.advertise_ip}:{self.port}"

    async def start(self) -> None:
        if self.cfg.tracing_jsonl or self.cfg.tracing_otlp:
            from ..common import tracing
            tracing.configure(service="dfscheduler",
                              jsonl_path=self.cfg.tracing_jsonl,
                              otlp_endpoint=self.cfg.tracing_otlp)
        if self.statestore is not None:
            # restore BEFORE the first RPC can land: a ruling made on an
            # amnesiac view and then "corrected" by a late restore would
            # be exactly the half-applied state the store exists to
            # prevent. A refused/missing snapshot degrades to the cold
            # path — recovery must never block boot.
            prov = await asyncio.to_thread(self.statestore.restore)
            if prov.get("recovered") and self.ledger is not None:
                self.service._recovery_seq += 1
                self.ledger.on_decision({
                    "kind": "decision",
                    "decision_kind": "recovery",
                    "decision_id":
                        f"r{self.service._recovery_seq:08d}.snapshot",
                    "host_id": "",
                    "source": "snapshot",
                    "gap_s": prov.get("gap_s", 0.0),
                    "components": {
                        k: v.get("restored", 0)
                        for k, v in (prov.get("components") or {}).items()},
                    "scheduler_epoch": self.service.epoch,
                    "task_id": "",
                    "peer_id": "",
                    "candidates": [],
                    "excluded": [],
                    "chosen": [],
                })
        self.rpc = RPCServer(f"{self.cfg.listen_ip}:{self.cfg.port}")
        self.rpc.register(build_service(self.service))
        await self.rpc.start()
        self.port = self.rpc.port
        if self.cfg.manager_addresses:
            await self._attach_manager()
        if self.cfg.security_issue_token and self.cfg.manager_addresses:
            await self._enroll_security()
        self.gc.add(GCTask("resource", self.cfg.gc_interval_s,
                           self.resource.gc))
        if self.statestore is not None:
            # snapshot ticker rides the GC runner (periodic + dirty):
            # maybe_save never raises, so a sick disk shows up as an
            # error-result counter, not a dead sweeper
            store = self.statestore
            self.gc.add(GCTask("statestore",
                               min(self.cfg.statestore_interval_s, 5.0),
                               lambda: int(store.maybe_save())))
        if self.fleetpulse is not None:
            # silent-daemon detection + series aging ride the GC runner:
            # a daemon that stops announcing can't push its own absence
            fp = self.fleetpulse
            self.gc.add(GCTask("fleetpulse", self.cfg.gc_interval_s,
                               lambda: fp.tick()))
        self.gc.start()
        # records → trainer upload + model → evaluator refresh (ML loop)
        from .announcer import SchedulerAnnouncer
        self.announcer = SchedulerAnnouncer(
            self, upload_interval_s=self.cfg.train_upload_interval_s,
            refresh_interval_s=self.cfg.model_refresh_interval_s)
        self.announcer.start()
        log.info("scheduler up on %s (cluster=%d, algorithm=%s, seeds=%d)",
                 self.address, self.cfg.cluster_id, self.cfg.algorithm,
                 len(self.seed_client.seed_peers))

    async def _enroll_security(self) -> None:
        """Obtain fleet TLS material so seed triggers can reach
        security-enabled seed daemons (their rpc ports require client
        certs)."""
        import os

        from ..rpc.security import obtain_certificate
        try:
            cert, key, ca = await obtain_certificate(
                self.cfg.manager_addresses,
                hosts=[self.cfg.advertise_ip],
                token=self.cfg.security_issue_token,
                out_dir=os.path.join(self.cfg.workdir or ".",
                                     "scheduler-tls"),
                tls_ca=self.cfg.security_ca_cert)
        except Exception as exc:  # noqa: BLE001 - seeds then unreachable
            log.error("fleet TLS enrollment failed (%s): seed triggers to "
                      "mTLS seed daemons WILL fail", exc)
            return
        tls = (cert, key, self.cfg.security_ca_cert or ca)
        await self.seed_client.close()
        self.seed_client = SeedPeerClient(
            self.resource, list(self.seed_client.seed_peers.values()),
            tls=tls, quarantine=self.quarantine)
        self.service.seed_client = self.seed_client

    async def _attach_manager(self) -> None:
        """Register with the manager, keep alive, and adopt its seed-peer
        set when none is configured statically (reference scheduler boots
        the same way off dynconfig)."""
        import socket

        from ..idl.messages import RegisterSchedulerRequest
        from ..rpc.manager_link import ManagerLink
        from ..tpu import topology
        from .config import SeedPeerAddr
        from .seed_client import SeedPeerClient

        hostname = socket.gethostname()
        self.manager = ManagerLink(
            self.cfg.manager_addresses,
            keepalive_interval_s=self.cfg.keepalive_interval_s)
        try:
            await self.manager.register_scheduler(RegisterSchedulerRequest(
                hostname=hostname, ip=self.cfg.advertise_ip, port=self.port,
                scheduler_cluster_id=self.cfg.cluster_id,
                # environment only: a scheduler never takes the chip
                topology=topology.detect()))
            self.manager.start_keepalive(source_type="scheduler",
                                         hostname=hostname,
                                         ip=self.cfg.advertise_ip,
                                         cluster_id=self.cfg.cluster_id,
                                         port=self.port)
            if not self.cfg.seed_peers:
                resp = await self.manager.get_seed_peers()
                seeds = [SeedPeerAddr(host_id=f"{e.hostname}-{e.ip}",
                                      ip=e.ip, rpc_port=e.port,
                                      download_port=e.download_port)
                         for e in (resp.seed_peers or [])]
                if seeds:
                    self.seed_client = SeedPeerClient(
                        self.resource, seeds, quarantine=self.quarantine)
                    self.service.seed_client = self.seed_client
        except Exception as exc:  # noqa: BLE001 - manager optional at boot
            log.warning("manager attach failed (%s); running standalone", exc)
            return
        if self.cfg.statestore_handoff:
            await self._import_handoff()
        # applications are OPTIONAL (an older manager may lack the verb):
        # a failed first fetch must neither mislabel the attach as failed
        # nor disable refresh — the loop keeps retrying and recovers when
        # the manager catches up
        self._app_refresh = asyncio.get_running_loop().create_task(
            self._app_refresh_loop())

    def _handoff_signature(self, blob: bytes) -> str:
        import hashlib
        import hmac
        token = self.cfg.security_issue_token
        if not token:
            return ""
        return hmac.new(token.encode(), blob, hashlib.sha256).hexdigest()

    async def _export_handoff(self) -> None:
        """Graceful stop/demotion: park the quarantine/affinity summary
        with the manager (config plane of record) so the ring successor
        can warm itself — sealed with the PEX envelope codec, HMAC'd
        with the cluster issuance token when security is on."""
        if (self.manager is None or self.statestore is None
                or not self.cfg.statestore_handoff):
            return
        from ..daemon.pex import DIGEST_VERSION, seal
        from ..idl.messages import SetSchedulerStateRequest
        body: dict = {"v": DIGEST_VERSION}
        if self.quarantine is not None:
            body["quarantine"] = self.quarantine.export_state()
        if self.sharded is not None:
            body["shard_affinity"] = self.sharded.export_state()
        if len(body) == 1:
            return
        blob = seal(body)
        try:
            await self.manager.set_scheduler_state(SetSchedulerStateRequest(
                scheduler_id=self.address,
                cluster_id=self.cfg.cluster_id,
                blob=blob,
                signature=self._handoff_signature(blob)))
        except Exception as exc:  # noqa: BLE001 - handoff is best-effort
            log.debug("handoff export failed: %s", exc)

    async def _import_handoff(self) -> None:
        """Ring-failover successor: import the demoted member's parked
        summary. The PR 12 anti-slander rule is structural, not
        advisory: imported verdicts land as CIRCUMSTANTIAL (relayed)
        mass via ``QuarantineRegistry.import_summary``, which tops out
        at `suspect` — only fresh first-hand corrupt reports arriving
        HERE can quarantine. Affinity memos import whole (the split is a
        pure observable function, so adopting them only preserves
        stickiness)."""
        if self.manager is None:
            return
        import hmac as _hmac

        from ..daemon.pex import unseal
        from ..idl.messages import GetSchedulerStateRequest
        try:
            resp = await self.manager.get_scheduler_state(
                GetSchedulerStateRequest(cluster_id=self.cfg.cluster_id,
                                         exclude=self.address))
        except Exception as exc:  # noqa: BLE001 - older manager: no verb
            log.debug("handoff import unavailable: %s", exc)
            return
        if resp is None or not resp.blob or resp.scheduler_id == self.address:
            return
        want = self._handoff_signature(resp.blob)
        if want and not _hmac.compare_digest(want, resp.signature or ""):
            log.warning("handoff blob from %s refused: bad signature",
                        resp.scheduler_id)
            return
        body = unseal(resp.blob)
        if body is None:
            log.warning("handoff blob from %s refused: torn/version-skewed",
                        resp.scheduler_id)
            return
        imported = 0
        if self.quarantine is not None \
                and isinstance(body.get("quarantine"), dict):
            imported += self.quarantine.import_summary(
                body["quarantine"], source=resp.scheduler_id)
        if self.sharded is not None \
                and isinstance(body.get("shard_affinity"), dict):
            imported += self.sharded.restore(body["shard_affinity"])
        log.info("handoff import from %s: %d entries warmed",
                 resp.scheduler_id, imported)
        if self.ledger is not None and imported:
            self.service._recovery_seq += 1
            self.ledger.on_decision({
                "kind": "decision",
                "decision_kind": "recovery",
                "decision_id": f"r{self.service._recovery_seq:08d}.handoff",
                "host_id": "",
                "source": "handoff",
                "from_scheduler": resp.scheduler_id,
                "entries_imported": imported,
                "scheduler_epoch": self.service.epoch,
                "task_id": "",
                "peer_id": "",
                "candidates": [],
                "excluded": [],
                "chosen": [],
            })

    async def _refresh_applications(self) -> None:
        """Pull the application priority table into the service (reference
        dynconfig.GetApplications feeding Peer.CalculatePriority), plus
        the tenant quota table (multi-tenant QoS) on the same cadence —
        both optional verbs, each failing independently so an older
        manager serving only applications still feeds them."""
        resp = await self.manager.list_applications()
        self.service.applications = {
            e.name: int(e.priority) for e in (resp.applications or [])}
        try:
            tresp = await self.manager.list_tenants()
        except Exception as exc:  # noqa: BLE001 - older manager: no verb
            log.debug("tenant refresh failed: %s", exc)
            return
        self.service.tenants = {
            t.name: {"qos_class": t.qos_class,
                     "max_running": int(t.max_running),
                     "shed_retry_after_ms": int(t.shed_retry_after_ms)}
            for t in (tresp.tenants or [])}

    async def _app_refresh_loop(self) -> None:
        while True:
            try:
                await self._refresh_applications()
            except Exception as exc:  # noqa: BLE001 - manager flaky is fine
                log.debug("application refresh failed: %s", exc)
            await asyncio.sleep(self.cfg.keepalive_interval_s * 6)

    async def stop(self) -> None:
        if getattr(self, "_app_refresh", None) is not None:
            self._app_refresh.cancel()
        if self.announcer is not None:
            await self.announcer.stop()
        if self.statestore is not None:
            # final snapshot + manager handoff BEFORE the manager link
            # closes; both swallow failures — shutdown never wedges on a
            # sick disk or an absent manager
            await asyncio.to_thread(self.statestore.save,
                                    reason="shutdown")
            await self._export_handoff()
        if self.service.records is not None:
            await self.service.records.aclose()
        if getattr(self, "manager", None) is not None:
            await self.manager.close()
        await self.gc.stop()
        for t in list(self.service._seed_tasks):
            t.cancel()
        await self.seed_client.close()
        if self.rpc is not None:
            await self.rpc.stop(0.5)
