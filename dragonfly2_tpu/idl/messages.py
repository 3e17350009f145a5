"""All wire messages for the four services.

Service surface parity (reference ``pkg/rpc/*`` client wrappers, SURVEY §2.6):
scheduler (register/report/announce/probes), daemon (download/piece sync/cache
ops/seeding), manager (entities/keepalive/dynconfig), trainer (dataset upload).
TPU-native additions: ``TopologyInfo`` carries ICI slice coordinates so the
scheduler can score parents by link locality, and ``DeviceSink`` describes an
HBM placement target for a download.
"""

from __future__ import annotations

import enum

from .base import message


# ---------------------------------------------------------------- enums

class SizeScope(enum.IntEnum):
    NORMAL = 0   # many pieces, full P2P
    SMALL = 1    # exactly one piece: skip piece sync, single parent
    TINY = 2     # <=128 KiB: content returned inline in register result
    EMPTY = 3    # zero bytes


class TaskType(enum.IntEnum):
    STANDARD = 0       # downloaded file, GC-able
    PERSISTENT = 1     # dfcache import: pinned until deleted
    PERSISTENT_CACHE = 2


class Priority(enum.IntEnum):
    LEVEL0 = 0  # highest
    LEVEL1 = 1
    LEVEL2 = 2
    LEVEL3 = 3
    LEVEL4 = 4
    LEVEL5 = 5
    LEVEL6 = 6  # lowest


# The multi-tenant QoS service-class vocabulary, pinned here the way
# ``EXCLUSION_REASONS`` pins the scheduling filter's reasons: every surface
# that carries a class (dfget/proxy/object-gateway requests, shaper/upload
# admission, scheduler rulings, ``df_qos_*`` metric labels) must use one of
# these strings, and each must be backticked in docs/RESILIENCE.md /
# docs/OBSERVABILITY.md (dflint DF006 priority-class-vocabulary).
#
#   ``critical`` — latency-sensitive foreground (a serving host pulling a
#                  hot model): holds its SLO under contention, may preempt
#                  ``bulk`` dispatch slots;
#   ``standard`` — the default class; everything pre-QoS behaved as;
#   ``bulk``     — background batch (dataset prefetch, image layers):
#                  first to be throttled, queued, and shed under brownout.
PRIORITY_CLASSES = ("critical", "standard", "bulk")
DEFAULT_PRIORITY_CLASS = "standard"

# numeric Priority a class resolves to when the request carries none:
# ``bulk`` sinks to LEVEL6 so priority-ordered surfaces that predate the
# class vocabulary (storage GC eviction, the per-class back-source budget)
# order it behind foreground traffic without any new plumbing
CLASS_DEFAULT_PRIORITY = {"critical": 0, "standard": 0, "bulk": 6}


def resolve_class(qos_class: str) -> str:
    """Clamp a wire-supplied class onto the pinned vocabulary ("" and
    unknown strings resolve to the default class, never an error — an old
    client must keep working against a QoS-aware pod)."""
    return qos_class if qos_class in PRIORITY_CLASSES \
        else DEFAULT_PRIORITY_CLASS


# Typed piece-failure vocabulary, pinned the way ``PRIORITY_CLASSES`` and
# the scheduler's ``EXCLUSION_REASONS`` are: every failed piece report
# (``PieceResult.fail_code``), flight-journal failure event, ``kind=piece``
# record row, and per-parent verdict-ledger counter uses one of these
# strings, each backticked in docs/OBSERVABILITY.md. ``ok=False`` alone
# told the scheduler nothing about *why* — and a swarm immune system needs
# the why: ``corrupt`` is hard evidence of a lying parent (quarantinable),
# the other three are congestion/liveness shapes that only deprioritize.
#
#   ``corrupt`` — the bytes landed but failed digest verification:
#                 the parent served wrong bytes (bit-rot, bad NIC, or a
#                 byzantine daemon);
#   ``stall``   — the transfer died mid-body (short read, connection
#                 reset): the parent wedged or churned away;
#   ``timeout`` — the per-piece deadline fired before the body finished;
#   ``refused`` — the parent answered with an error (or never accepted
#                 the connection) before any payload moved.
FAIL_CODES = ("corrupt", "stall", "timeout", "refused")


class HostType(enum.IntEnum):
    NORMAL = 0       # ordinary peer
    SUPER_SEED = 1   # seed peer, first to back-source
    STRONG_SEED = 2
    WEAK_SEED = 3


class LinkType(enum.IntEnum):
    """Locality class between two hosts, best to worst."""

    LOCAL = 0  # same host
    ICI = 1    # same TPU slice: wired inter-chip interconnect
    DCN = 2    # same zone, data-center network between slices/hosts
    WAN = 3    # cross-zone / unknown


# ---------------------------------------------------------------- core types

@message
class UrlMeta:
    """Download-relevant metadata; participates in the task id."""

    digest: str = ""                 # "sha256:..." expected digest of whole file
    tag: str = ""                    # task isolation tag
    range: str = ""                  # "bytes=a-b" sub-range request
    filtered_query_params: list[str] | None = None
    header: dict | None = None       # extra origin request headers
    application: str = ""
    priority: Priority = Priority.LEVEL0
    # multi-tenant QoS: who this request belongs to and which service
    # class it rides (PRIORITY_CLASSES; "" = standard). NOT part of the
    # task id — two tenants pulling the same URL share the task and the
    # content store dedupes across them; what differs is admission,
    # shaping, and eviction treatment.
    tenant: str = ""
    qos_class: str = ""
    # sharded tasks (common/sharding.py): comma-joined names of the
    # manifest shards THIS host's mesh position needs ("" = whole task).
    # NOT part of the task id — every host pulling any subset of the
    # same checkpoint joins the same task/swarm and shares pieces; what
    # differs is which pieces each host fetches and which shards become
    # ready arrays. The scheduler reads this at register to assign the
    # host its disjoint tree-fetch subset (RegisterResult.assigned_shards).
    shards: str = ""


@message
class TopologyInfo:
    """Where a host sits in the TPU pod fabric.

    This replaces the reference's IDC/location strings
    (``scheduler/scheduling/evaluator/evaluator_base.go:28-46`` scores) with
    coordinates the evaluator can compute real link classes from.
    """

    slice_name: str = ""             # e.g. "v5p-256-slice-0"; "" = not a TPU host
    worker_index: int = -1           # TPU VM worker number within the slice
    ici_coords: tuple | None = None  # chip-mesh coords of this host's chips, e.g. (x, y, z)
    num_chips: int = 0
    zone: str = ""                   # cloud zone (DCN domain)
    cluster_id: int = 0
    # explicit pod identity (cross-pod federation, ROADMAP item 2): the
    # ICI bandwidth domain this host belongs to. "" = derive from slice
    # identity (``tpu.topology.pod_id``: one slice == one ICI domain ==
    # one pod); set explicitly (DF_POD_ID) only when a deployment groups
    # hosts differently from slice boundaries. Rides every register/
    # announce so the scheduler can route cross-pod pulls through the
    # pod's elected seeds instead of letting the whole fleet cross DCN.
    pod: str = ""


@message
class CPUStat:
    logical_count: int = 0
    percent: float = 0.0


@message
class MemoryStat:
    total: int = 0
    available: int = 0
    used_percent: float = 0.0


@message
class NetworkStat:
    download_rate: int = 0       # bytes/s current
    download_rate_limit: int = 0
    upload_rate: int = 0
    upload_rate_limit: int = 0


@message
class DiskStat:
    total: int = 0
    free: int = 0
    used_percent: float = 0.0


@message
class Host:
    """A daemon instance's identity + address, carried in every register."""

    id: str = ""
    ip: str = ""
    hostname: str = ""
    port: int = 0                  # peer gRPC port
    download_port: int = 0         # piece upload (HTTP) port
    type: HostType = HostType.NORMAL
    os: str = ""
    platform: str = ""
    topology: TopologyInfo | None = None
    cpu: CPUStat | None = None
    memory: MemoryStat | None = None
    network: NetworkStat | None = None
    disk: DiskStat | None = None
    # 0 = "auto": the scheduler applies its per-host-type default (peers
    # serve few children each so fan-outs form trees, not stars)
    concurrent_upload_limit: int = 0
    build_version: str = ""
    # self-quarantine flag (daemon/verdicts.py): the daemon detected its
    # OWN storage bit-rot (boot re-verify or content-store placement
    # re-hash failed) and asks to be excluded as a parent pod-wide. Rides
    # every register/AnnounceHost; the scheduler's quarantine registry
    # treats it as hard evidence (state ``quarantined``, reason self).
    quarantined: bool = False


@message
class PieceInfo:
    piece_num: int = 0
    range_start: int = 0
    range_size: int = 0
    digest: str = ""               # per-piece "crc32c:..." / "md5:..."
    download_cost_ms: int = 0      # filled by downloader when reporting


@message
class PiecePacket:
    """Answer to "which pieces does peer X have" — also carries dst address."""

    task_id: str = ""
    dst_peer_id: str = ""
    dst_addr: str = ""             # "ip:download_port" to fetch pieces from
    piece_infos: list[PieceInfo] | None = None
    total_piece_count: int = -1    # -1: unknown yet
    content_length: int = -1
    piece_size: int = 0
    extend_attribute: dict | None = None
    # the holder's advertised landing watermark: pieces landed so far
    # (-1 = not reported). Rides every announcement so a child can see
    # how complete the partial holder it is pulling from is.
    progress: int = -1
    # cut-through announce-ahead (daemon/relay.py): piece numbers in
    # ``piece_infos`` that are IN-FLIGHT at the holder right now — the
    # upload server serves them to the landing watermark, so a child may
    # begin pulling before the holder finishes receiving them
    relay_nums: list[int] | None = None


@message
class ShardInfo:
    """One named array shard of a sharded task: a contiguous byte range of
    the content plus the array geometry a serving host reassembles it
    with. Integrity rides the existing per-piece digest machinery (every
    piece of the shard verifies at landing); ``digest`` is an OPTIONAL
    whole-shard digest checked at task finalize, not on the incremental
    shard-ready path."""

    name: str = ""                   # e.g. "layers.17.mlp.w1"
    range_start: int = 0             # byte offset within the content
    range_size: int = 0
    dtype: str = "uint8"             # numpy dtype string for the array view
    shape: list[int] | None = None   # array shape; None = flat bytes
    digest: str = ""                 # optional "sha256:..." of the shard
    # the chip this array is to be on: its ordinal among the chips the
    # holder's sink is opened over (``Daemon.device_runtime()``'s list, in
    # its order); -1 is unplaced (the sink spreads such shards round-robin)
    device: int = -1


@message
class ShardManifest:
    """A sharded task's shard table (task -> named shards). Shards are
    disjoint contiguous ranges; gaps are legal (unnamed bytes still ride
    the task, they just never become named ready arrays). Identical
    shards across checkpoint versions dedupe in the CA store via the
    ordinary piece-digest/content_key machinery — a rollout that reuses
    unchanged layers transfers only the delta (docs/STORAGE.md)."""

    shards: list[ShardInfo] | None = None


@message
class DeviceSink:
    """TPU-native: optional terminal sink describing how verified bytes land
    in device HBM (which mesh axis shard this host holds, dtype, etc.)."""

    enabled: bool = False
    dtype: str = "uint8"
    shard_index: int = 0
    shard_count: int = 1
    donate: bool = True
    pipeline_shards: int = 0       # DMA units per device; 0 = auto (~32MiB each)


# ---------------------------------------------------------------- scheduler service

@message
class RegisterPeerTaskRequest:
    url: str = ""
    url_meta: UrlMeta | None = None
    task_id: str = ""
    peer_id: str = ""
    peer_host: Host | None = None
    is_migrating: bool = False


@message
class SinglePiece:
    dst_peer_id: str = ""
    dst_addr: str = ""
    piece_info: PieceInfo | None = None


@message
class RegisterResult:
    task_id: str = ""
    size_scope: SizeScope = SizeScope.NORMAL
    direct_content: bytes = b""           # TINY: whole file inline
    single_piece: SinglePiece | None = None  # SMALL
    content_length: int = -1
    piece_size: int = 0
    # the scheduler's resolved priority (explicit > application table >
    # default) echoed back so the daemon's storage GC can order eviction
    # by it even when the request itself carried no explicit priority
    resolved_priority: Priority = Priority.LEVEL0
    # sharded tasks: the disjoint tree-fetch subset of the request's
    # ``UrlMeta.shards`` this peer was assigned (scheduler shard
    # affinity, ``decision_kind=shard``). The daemon fetches these from
    # the distribution tree and waits for co-located replicas to supply
    # the rest over ICI-near P2P (tree fallback after a bounded hold).
    # None = no affinity ruling (scheduler arm disabled / whole-file
    # task): every needed piece is tree-eligible immediately.
    assigned_shards: list[str] | None = None
    # the answering scheduler's boot epoch (crash resilience): a daemon
    # that sees this CHANGE knows the brain restarted and re-announces
    # its held content so the recovered scheduler relearns who holds
    # what within one announce interval. 0 = pre-epoch scheduler.
    scheduler_epoch: int = 0


@message
class HostLoad:
    cpu_ratio: float = 0.0
    mem_ratio: float = 0.0
    disk_ratio: float = 0.0


@message
class PieceResult:
    """Peer -> scheduler, one per finished/failed piece (the report stream)."""

    task_id: str = ""
    src_peer_id: str = ""           # downloader
    dst_peer_id: str = ""           # parent it fetched from ("" = back-source)
    piece_info: PieceInfo | None = None
    begin_ms: int = 0
    end_ms: int = 0
    success: bool = False
    code: int = 0                   # errors.Code
    # typed failure verdict (FAIL_CODES; "" on success): the *kind* of
    # failure, which ``code`` alone collapsed — the scheduler's quarantine
    # registry promotes ``corrupt`` verdicts into pod-wide exclusion while
    # stall/timeout/refused stay congestion-shaped (blocklist only)
    fail_code: str = ""
    # the failed transfer rode the parent's cut-through relay path
    # (X-DF-Relay): corrupt bytes then originated UPSTREAM of the named
    # parent, so the evidence is circumstantial — it may deprioritize /
    # mark the relay suspect, never shun or quarantine it (the
    # relay-plane form of the anti-slander rule; one poisoner must not
    # get every honest relay below it evicted)
    relayed: bool = False
    host_load: HostLoad | None = None
    finished_count: int = 0         # pieces this peer now holds


@message
class PeerAddr:
    peer_id: str = ""
    ip: str = ""
    rpc_port: int = 0
    download_port: int = 0
    link: LinkType = LinkType.DCN   # scheduler-computed locality to the child
    is_seed: bool = False           # seed/super-seed host (dispatcher steers
                                    # demand to mesh peers when they can serve)


@message
class PeerPacket:
    """Scheduler -> peer: current parent assignment set."""

    task_id: str = ""
    src_peer_id: str = ""
    parallel_count: int = 4
    main_peer: PeerAddr | None = None
    candidate_peers: list[PeerAddr] | None = None
    code: int = 0                   # e.g. SCHED_NEED_BACK_SOURCE
    # advisory packets ADD parents without pruning the current assignment
    # (PEX swarm-index pre-population, daemon/pex.py): the scheduler's own
    # packets stay authoritative — only they replace the assignment set
    advisory: bool = False


@message
class PeerResult:
    """Final report when a peer's task ends."""

    task_id: str = ""
    peer_id: str = ""
    src_ip: str = ""
    url: str = ""
    success: bool = False
    traffic: int = 0                # bytes downloaded P2P
    cost_ms: int = 0
    code: int = 0
    total_piece_count: int = 0
    content_length: int = -1
    # compact flight-recorder summary (daemon/flight_recorder.py
    # ``compact_summary``): per-parent throughput, tail latencies,
    # back-to-source ratio — feeds the scheduler's cluster view and the
    # trainer's record stream; None when the recorder is disabled
    flight_summary: dict | None = None


# Fleet-pulse digest schema version. Bumped when the field semantics
# change incompatibly; the scheduler's ingest (scheduler/fleetpulse.py)
# refuses mismatched versions WHOLESALE (the PEX schema-refusal rule) —
# a half-understood telemetry stream is worse than none, because it
# looks like knowledge.
PULSE_VERSION = 1


@message
class PulseDigest:
    """One daemon's health counters, folded compact and piggybacked on
    the ``AnnounceHost`` heartbeat it already sends (daemon/pulse.py
    builds it; scheduler/fleetpulse.py ingests it). Zero new
    connections; dfbench --pr18 gates the encoded overhead at <= 512 B
    per announce.

    All ``*_total``-style fields are since-boot monotonic counters (the
    scheduler differentiates them; a restart's reset clamps to zero) —
    gauges are instantaneous. Unknown fields from a NEWER daemon are
    dropped by the codec (idl/base.py forward-compat rule); an unknown
    ``v`` rejects the whole digest at ingest, never crashes it."""

    v: int = PULSE_VERSION
    seq: int = 0                    # per-daemon announce counter
    flight_tasks: int = 0           # flight-ring occupancy (gauge)
    flight_evicted: int = 0         # flights dropped oldest (counter)
    served_rungs: dict | None = None    # ladder rung -> entries (counter)
    loop_lag_max_ms: float = 0.0    # event-loop lag high-water (gauge)
    loop_stalls: int = 0            # stall-threshold crossings (counter)
    slo_breaches: int = 0           # per-stage budget breaches (counter)
    corrupt_verdicts: int = 0       # first-hand corrupt verdicts (counter)
    shunned_parents: int = 0        # parents currently shunned (gauge)
    self_quarantined: bool = False  # the daemon pulled itself out
    qos_state: str = "normal"       # QoS governor state (gauge)
    qos_shed: int = 0               # admissions shed (counter)
    storage_tasks: int = 0          # tasks held by the storage manager


@message
class AnnounceHostRequest:
    host: Host | None = None
    interval_s: float = 30.0
    # fleet-pulse piggyback (daemon/pulse.py): None from a pre-pulse
    # daemon — the scheduler treats absence as "no telemetry", never
    # as an anomaly by itself (silent-daemon keys off missed announces)
    pulse: PulseDigest | None = None


@message
class AnnounceHostResponse:
    """Scheduler -> daemon heartbeat answer. Carries the scheduler's
    boot epoch so the announce plane doubles as restart detection (the
    register path carries it too — whichever lands first wins). Old
    schedulers answered Empty; the codec is self-describing, so a
    daemon treats anything without an epoch as epoch 0 (unknown)."""

    scheduler_epoch: int = 0


@message
class HeldContentEntry:
    """One task's holdings in a daemon's recovery re-announce — the PEX
    digest entry shape (daemon/pex.py build_digest), typed for the
    scheduler RPC plane."""

    task_id: str = ""
    url: str = ""
    total_piece_count: int = -1
    content_length: int = -1
    piece_size: int = 0
    done: bool = False
    pieces: list[int] | None = None     # partial holdings (done=False)


@message
class AnnounceContentRequest:
    """Daemon -> scheduler after an epoch change / register failover:
    re-announce held content so a freshly restarted (or newly elected)
    brain rebuilds its resource view from the swarm instead of sending
    the herd back to origin. ``digest`` is the daemon's sealed PEX
    envelope (sha256 + canonical JSON, daemon/pex.py seal) over the
    same entries — the scheduler verifies the seal and refuses torn or
    version-skewed blobs wholesale."""

    host: Host | None = None
    entries: list[HeldContentEntry] | None = None
    digest: bytes = b""
    # same piggyback as AnnounceHostRequest: the recovery re-announce is
    # a heartbeat too, and a freshly restarted brain wants telemetry
    # history started on the FIRST contact, not one interval later
    pulse: PulseDigest | None = None


@message
class AnnounceContentResponse:
    scheduler_epoch: int = 0
    tasks_adopted: int = 0


@message
class LeaveHostRequest:
    host_id: str = ""


@message
class LeavePeerRequest:
    task_id: str = ""
    peer_id: str = ""


@message
class StatTaskRequest:
    task_id: str = ""


@message
class TaskStat:
    id: str = ""
    type: TaskType = TaskType.STANDARD
    content_length: int = -1
    total_piece_count: int = -1
    state: str = ""
    peer_count: int = 0
    has_available_peer: bool = False


@message
class ProbeTarget:
    host_id: str = ""
    ip: str = ""
    port: int = 0


@message
class SyncProbesRequest:
    """Daemon -> scheduler: either asking for targets or reporting results."""

    host: Host | None = None
    probes: list[Probe] | None = None
    failed_host_ids: list[str] | None = None


@message
class Probe:
    target_host_id: str = ""
    rtt_us: int = 0
    created_at_ms: int = 0


@message
class SyncProbesResponse:
    targets: list[ProbeTarget] | None = None
    probe_interval_s: float = 20.0


# ---------------------------------------------------------------- daemon service

@message
class DownloadRequest:
    url: str = ""
    output: str = ""                # abs path; "" = stream/cache only
    url_meta: UrlMeta | None = None
    timeout_s: float = 0.0
    rate_limit_bps: int = 0
    disable_back_source: bool = False
    recursive: bool = False
    recursive_concurrency: int = 8
    keep_original_offset: bool = False
    device_sink: DeviceSink | None = None
    task_type: TaskType = TaskType.STANDARD
    # sharded tasks: the checkpoint's shard table. With a manifest the
    # daemon maps pieces -> shards as they verify, emits ``shard_ready``
    # flight events, hands each complete shard to the HBM sink
    # incrementally, and — when ``url_meta.shards`` names a subset —
    # pulls only the pieces that cover it.
    shard_manifest: ShardManifest | None = None


@message
class DownloadResponse:
    task_id: str = ""
    peer_id: str = ""
    completed_length: int = 0
    content_length: int = -1
    done: bool = False
    output: str = ""                # echo of where this entry landed (recursive)
    code: int = 0
    message: str = ""
    # sharded tasks: a ``shard_ready`` progress frame — this named shard's
    # bytes all verified and (when a device sink rides the request) its
    # HBM handoff is enqueued. ``shard_src`` says how its bytes arrived:
    # ``tree`` (this host's assigned tree-fetch subset) or ``swap``
    # (supplied by co-located replicas over ICI-near P2P). dfget prints
    # one per-shard ready timestamp per frame.
    shard: str = ""
    shard_src: str = ""
    shards_ready: int = 0
    shards_total: int = 0


@message
class PieceTaskRequest:
    task_id: str = ""
    src_peer_id: str = ""           # requester
    dst_peer_id: str = ""           # owner being asked
    start_num: int = 0
    limit: int = 32
    src_slice: str = ""             # requester's TPU slice: super-seeds
                                    # spread reveals one-per-slice so each
                                    # slice gets a local first-tier copy
                                    # that ICI then fans out


@message
class StatTaskDaemonRequest:
    url: str = ""
    url_meta: UrlMeta | None = None
    task_id: str = ""
    local_only: bool = False


@message
class ImportTaskRequest:
    path: str = ""
    url: str = ""                   # cache key url (d7y cache scheme)
    url_meta: UrlMeta | None = None
    task_type: TaskType = TaskType.PERSISTENT


@message
class ExportTaskRequest:
    url: str = ""
    output: str = ""
    url_meta: UrlMeta | None = None
    timeout_s: float = 0.0
    local_only: bool = False


@message
class DeleteTaskRequest:
    url: str = ""
    url_meta: UrlMeta | None = None
    task_id: str = ""


@message
class ObtainSeedsRequest:
    url: str = ""
    url_meta: UrlMeta | None = None
    task_id: str = ""


@message
class PieceSeed:
    peer_id: str = ""
    host_id: str = ""
    piece_info: PieceInfo | None = None
    done: bool = False
    content_length: int = -1
    total_piece_count: int = -1


@message
class Empty:
    pass


# ---------------------------------------------------------------- manager service

@message
class SchedulerEntity:
    id: int = 0
    hostname: str = ""
    ip: str = ""
    port: int = 0
    state: str = "inactive"         # active | inactive
    scheduler_cluster_id: int = 0
    features: list[str] | None = None
    topology: TopologyInfo | None = None


@message
class SeedPeerEntity:
    id: int = 0
    hostname: str = ""
    ip: str = ""
    port: int = 0
    download_port: int = 0
    object_storage_port: int = 0
    type: str = "super"
    state: str = "inactive"
    seed_peer_cluster_id: int = 0
    topology: TopologyInfo | None = None


@message
class ClusterConfig:
    """Scheduler-cluster tunables served via dynconfig."""

    candidate_parent_limit: int = 4
    filter_parent_limit: int = 15
    job_rate_limit: int = 10
    seed_peer_load_limit: int = 300
    peer_load_limit: int = 50
    piece_parallel_count: int = 4


@message
class GetSchedulersRequest:
    hostname: str = ""
    ip: str = ""
    topology: TopologyInfo | None = None
    version: str = ""


@message
class GetSchedulersResponse:
    schedulers: list[SchedulerEntity] | None = None
    cluster_config: ClusterConfig | None = None


@message
class GetSeedPeersRequest:
    cluster_id: int = 0


@message
class GetSeedPeersResponse:
    seed_peers: list[SeedPeerEntity] | None = None


@message
class KeepAliveRequest:
    source_type: str = ""           # "scheduler" | "seed_peer"
    hostname: str = ""
    ip: str = ""
    port: int = 0                   # instance identity is (hostname, ip, port)
    cluster_id: int = 0


@message
class RegisterSchedulerRequest:
    hostname: str = ""
    ip: str = ""
    port: int = 0
    scheduler_cluster_id: int = 0
    topology: TopologyInfo | None = None


@message
class RegisterSeedPeerRequest:
    hostname: str = ""
    ip: str = ""
    port: int = 0
    download_port: int = 0
    object_storage_port: int = 0
    type: str = "super"
    seed_peer_cluster_id: int = 0
    topology: TopologyInfo | None = None


@message
class PreheatRequest:
    """Manager/operator -> scheduler: warm a URL into the seed layer."""

    url: str = ""
    url_meta: UrlMeta | None = None
    wait: bool = True               # block until the seed finishes


@message
class PreheatResponse:
    task_id: str = ""
    state: str = ""                 # pending | running | succeeded | failed
    content_length: int = -1
    total_piece_count: int = -1


# ---------------------------------------------------------------- trainer service

@message
class TrainRequest:
    """Client-stream chunk: schedulers upload CSV datasets for model fitting."""

    hostname: str = ""
    ip: str = ""
    cluster_id: int = 0
    dataset: str = ""               # "download" | "networktopology"
    chunk: bytes = b""
    done: bool = False


@message
class TrainResponse:
    ok: bool = True
    message: str = ""
    model_version: str = ""


@message
class ModelInferRequest:
    model_name: str = "bandwidth_mlp"
    features: list[list] | None = None   # batch of feature rows


@message
class ModelInferResponse:
    outputs: list[float] | None = None
    model_version: str = ""


# ---------------------------------------------------------------- model registry

@message
class ModelEntity:
    """A versioned trained model (reference ``manager/models/model.go:36``)."""

    id: int = 0
    name: str = ""                  # bandwidth_mlp | topology_gnn
    version: str = ""               # content hash of the blob
    state: str = "active"
    scheduler_cluster_id: int = 0
    metrics: dict | None = None     # loss curve, rows, train time...
    data: bytes = b""               # npz param archive ("" in listings)
    created_at: float = 0.0


@message
class CreateModelRequest:
    name: str = ""
    version: str = ""
    scheduler_cluster_id: int = 0
    metrics: dict | None = None
    data: bytes = b""


@message
class GetModelRequest:
    name: str = ""
    version: str = ""               # "" = latest active version
    scheduler_cluster_id: int = 0
    if_none_match: str = ""         # client's current version: matching
                                    # reply omits the blob (poll cheaply)


@message
class GetModelResponse:
    model: ModelEntity | None = None


@message
class CertificateRequest:
    """Fleet cert issuance (reference security_server_v1.go IssueCertificate
    + pkg/issuer): the requester keeps its private key and submits only the
    public half plus the identities to certify."""

    public_key_pem: bytes = b""
    hosts: list[str] | None = None       # DNS names / IPs for the SAN
    validity_s: int = 0                  # 0 = issuer default; server-capped
    token: str = ""                      # issuance token (manager workdir
                                         # issuer.token; distributed to the
                                         # fleet out of band)


@message
class CertificateResponse:
    cert_pem: bytes = b""
    ca_cert_pem: bytes = b""


@message
class ApplicationEntry:
    """One manager-registered application with its download priority
    (reference ``manager/models/application.go:24`` Priority JSONMap —
    the scheduler's CalculatePriority consults this when a request
    carries no explicit priority)."""

    name: str = ""
    url: str = ""
    priority: Priority = Priority.LEVEL0


@message
class ListApplicationsResponse:
    applications: list[ApplicationEntry] | None = None


@message
class TenantEntry:
    """One manager-registered tenant with its quota and default service
    class — the per-tenant half of the QoS plane. Schedulers pull this
    table over dynconfig (``ListTenants``, same cadence as applications)
    and enforce ``max_running`` at register with a 429-shaped
    RESOURCE_EXHAUSTED + retry-after that the common/retry.py ladder
    already honors."""

    name: str = ""
    qos_class: str = ""              # default class for the tenant's
                                     # requests that carry none
    max_running: int = 0             # concurrent running downloads
                                     # cluster-wide (0 = unlimited)
    shed_retry_after_ms: int = 0     # hint stamped on quota sheds
                                     # (0 = scheduler default)


@message
class ListTenantsResponse:
    tenants: list[TenantEntry] | None = None


@message
class SetSchedulerStateRequest:
    """Demoting/stopping scheduler -> manager: park this member's last
    exported quarantine/affinity summary with the config plane of
    record, so the failover successor can import it. ``signature`` is
    an HMAC over ``blob`` with the cluster's issuance token when
    security is on ("" = unsigned, accepted only by managers that hold
    no token either)."""

    scheduler_id: str = ""           # exporter identity (host:port)
    cluster_id: int = 0
    blob: bytes = b""                # sealed summary (pex.seal envelope)
    signature: str = ""


@message
class GetSchedulerStateRequest:
    cluster_id: int = 0
    exclude: str = ""                # don't hand a member its own blob


@message
class GetSchedulerStateResponse:
    scheduler_id: str = ""           # "" = nothing parked
    blob: bytes = b""
    signature: str = ""


@message
class SyncPeersRequest:
    """Manager -> scheduler: dump your live host set (reference
    scheduler/job/job.go:224 syncPeers consumed by manager/job/sync_peers)."""

    cluster_id: int = 0


@message
class SyncPeersResponse:
    hosts: list[Host] | None = None
