"""PeerTaskConductor: the per-(task, peer) download state machine.

Role parity: reference ``client/daemon/peer/peertask_conductor.go`` — one
conductor per running task in the daemon: registers with the scheduler, pulls
pieces (P2P or back-source), lands them in storage (and optionally straight
into TPU HBM via the DeviceIngest sink), broadcasts progress to subscribers
(file/stream façades), reports results, and finalizes with digest check.

Stage layout: the back-source ladder and storage/sink/subscriber machinery
live here; P2P pulling attaches through ``set_p2p_engine`` (piece_engine.py)
and the scheduler stream through ``scheduler_session.py``.
"""

from __future__ import annotations

import asyncio
import logging
import time
from typing import Any, AsyncIterator

from ..common import digest as digestlib
from ..common import tracing
from ..common.errors import Code, DFError
from ..common.logging import with_fields
from ..common.metrics import REGISTRY
from ..common.piece import Range, compute_piece_size, piece_count
from ..idl.messages import PieceInfo, TaskType, UrlMeta
from ..storage.io_executor import run_io
from ..storage.manager import StorageManager
from ..storage.metadata import TaskMetadata
from ..storage.store import TaskStorage
from . import flight_recorder as fr

log = logging.getLogger("df.core.conductor")

# which landing path served each downloaded span: "native" (fused
# pwrite+crc32c, one traversal), "python" (one pwrite + off-loop hashing),
# or "per_piece" (storage without a span entry point) — the dfbench --pr5
# smoke gate fails when per_piece shows up on the normal P2P path
_span_lands = REGISTRY.counter(
    "df_span_land_total", "downloaded spans landed in storage, by landing "
    "path", ("path",))

# sharded-task delivery (common/sharding.py): per-shard readiness +
# tree-vs-swap byte attribution — the numbers behind "time-to-serving"
_shard_ready = REGISTRY.counter(
    "df_shard_ready_total", "manifest shards whose bytes all verified, "
    "by supply path (tree = this host's assigned fetch subset, swap = "
    "co-located replicas over ICI-near P2P)", ("src",))
_shard_ready_s = REGISTRY.histogram(
    "df_shard_ready_seconds", "time from task start to each shard "
    "becoming ready",
    buckets=(0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0,
             120.0, 300.0))
_shard_fallbacks = REGISTRY.counter(
    "df_shard_fallback_total", "swap-class pieces re-pulled from the "
    "tree after the bounded swap hold expired (the ICI swap partner "
    "died or stalled)")
_shard_bytes = REGISTRY.counter(
    "df_shard_bytes_total", "bytes landed into manifest shards, by the "
    "piece's supply class", ("src",))

# bound on waiting out a finished download's last device transfers
SINK_DRAIN_TIMEOUT_S = 600.0


def _stamped(fn, *args, **kwargs):
    """A landing call as the storage thread runs it: its result with the
    ``time.monotonic()`` stamps of its start and end, taken on that thread
    (``run_io`` itself knows nothing of them), under a ``df:land`` span in
    the profiler's trace."""
    t0 = time.monotonic()
    with tracing.annotate("land"):
        out = fn(*args, **kwargs)
    return out, t0, time.monotonic()


class PeerTaskConductor:
    # terminal states
    PENDING, RUNNING, SUCCESS, FAILED = "pending", "running", "success", "failed"

    def __init__(self, *, task_id: str, peer_id: str, url: str,
                 url_meta: UrlMeta | None, storage_mgr: StorageManager,
                 piece_mgr: Any, scheduler: Any = None,
                 content_range: Range | None = None,
                 disable_back_source: bool = False,
                 task_type: TaskType = TaskType.STANDARD,
                 device_sink_factory: Any = None,
                 ordered: bool = False,
                 trace: Any = None,
                 flight: Any = None,
                 pex: Any = None,
                 relay: Any = None,
                 shard_manifest: Any = None,
                 requested_shards: list[str] | None = None):
        self.task_id = task_id
        self.peer_id = peer_id
        self.url = url
        self.url_meta = url_meta or UrlMeta()
        # scheduler may refine this at register (application-table lookup);
        # storage GC eviction ordering reads the refined value
        self.resolved_priority = int(self.url_meta.priority)
        # multi-tenant QoS: the service class rides the whole download —
        # shaper registration, piece GETs (upload-slot admission at the
        # parent), storage metadata (class-weighted eviction), the flight
        # summary (per-class SLO budgets) — on EVERY rung including
        # back-source and the scheduler-less pex path, because it lives on
        # the conductor rather than any one session
        from ..idl.messages import resolve_class
        self.qos_class = resolve_class(self.url_meta.qos_class)
        self.tenant = self.url_meta.tenant
        self.storage_mgr = storage_mgr
        self.piece_mgr = piece_mgr
        self.scheduler = scheduler
        self.content_range = content_range
        self.disable_back_source = disable_back_source
        self.task_type = task_type
        self.device_sink_factory = device_sink_factory
        self.ordered = ordered       # stream consumers want low pieces first
        self.trace = trace
        self.flight = flight         # TaskFlight journal (None = disabled)
        self.pex = pex               # PexGossiper (None = plane disabled)
        self.relay = relay           # RelayHub (None = cut-through off)
        self._relay_tracked = False
        # sharded-task delivery (common/sharding.py): the manifest's shard
        # table, the subset this host needs, and — once piece geometry is
        # known (_init_shards) — the tracker that turns verified piece
        # landings into per-shard readiness. Ranged requests keep the
        # whole-file path: a manifest's offsets are content-absolute and
        # a sub-range task's pieces are range-relative.
        shards = getattr(shard_manifest, "shards", shard_manifest)
        self.shard_manifest = (list(shards) if shards
                               and content_range is None
                               and not self.url_meta.range else None)
        self.requested_shards = (list(requested_shards)
                                 if requested_shards else None)
        self.shard_tracker: Any = None
        # piece numbers this download actually needs (None = all): the
        # requested-shard subset's coverage — the dispatcher, back-source
        # hole computation, and the finish check all read this
        self.needed_pieces: set[int] | None = None
        # scheduler shard affinity: the disjoint tree-fetch subset this
        # peer was assigned (RegisterResult.assigned_shards); pieces of
        # every OTHER requested shard are swap-class — held off the seed
        # for a bounded window so co-located replicas supply them over
        # ICI-near P2P (piece_dispatcher swap hold)
        self.affinity_shards: list[str] | None = None
        self.swap_piece_nums: set[int] = set()
        self._swap_shard_names: set[str] = set()
        self._fallback_noted: set[int] = set()
        # completion commit point: set SYNCHRONOUSLY with the final
        # needed-coverage check (engine loop / back-source / finalize) —
        # a widen that loses this race is refused, so a finishing subset
        # task can never be widened into "incomplete" (raising for both
        # requesters) or into a success that silently lacks the
        # joiner's shards
        self._finishing = False
        # True when register failed at the TRANSPORT level (every ring
        # member unreachable) rather than by scheduler verdict — only then
        # may the pex rung second-guess the missing control plane
        self._sched_unreachable = False

        self.state = self.PENDING
        self.fail_code = Code.OK
        self.fail_message = ""
        self.content_length = -1
        self.piece_size = 0
        self.total_pieces = -1
        self.completed_length = 0
        self.traffic_p2p = 0          # bytes from peers (for egress-saved stats)
        self.traffic_source = 0       # bytes from origin
        self.traffic_placed = 0       # bytes placed from the content store
        self._adopted = False         # whole task materialized by digest
        self.start_ms = int(time.time() * 1000)

        # QoS admission release hook (PeerTaskManager): fired exactly once
        # when the run ends, success or failure — an unreleased admission
        # would wedge the bulk gate shut for the rest of the process
        self.qos_release: Any = None
        self.storage: TaskStorage | None = None
        self.device_ingest: Any = None
        # why a requested device sink is gone (refused at open, a failed
        # write/flush/transfer): the bytes still finish landing on disk —
        # the swarm can be fed from them and a retry re-stages without
        # the wire — but the task ends FAILED with this reason
        self.sink_error = ""
        self.ready: set[int] = set()          # piece numbers landed
        self._landing: set[int] = set()       # pieces mid-write (dedup race)
        self.done_event = asyncio.Event()
        self._piece_cond = asyncio.Condition()
        self._subscribers: list[asyncio.Queue] = []
        self._run_task: asyncio.Task | None = None
        self._p2p_engine: Any = None
        self._session: Any = None      # scheduler PeerSession once registered
        self.shaper: Any = None
        self.rate_limiter: Any = None  # per-task bucket from the shaper
        self.log = with_fields("df.core.conductor",
                               task=task_id[:12], peer=peer_id[-12:])

    # ------------------------------------------------------------------
    # lifecycle
    # ------------------------------------------------------------------

    def start(self) -> None:
        if self._run_task is None:
            self.state = self.RUNNING
            self._run_task = asyncio.get_running_loop().create_task(self._run())

    def set_p2p_engine(self, engine: Any) -> None:
        self._p2p_engine = engine

    def attach_shaper(self, shaper: Any) -> None:
        self.shaper = shaper
        self.rate_limiter = shaper.register(
            self.task_id, qos_class=self.qos_class, tenant=self.tenant)

    async def _run(self) -> None:
        from ..common import tracing
        with tracing.span("peertask", task_id=self.task_id[:16],
                          peer_id=self.peer_id[-16:], url=self.url) as sp:
            await self._run_traced(sp)

    def _refuse_unplaceable_manifest(self) -> None:
        """A manifest that places a shard on a chip this host's sink is
        not opened over can never be honoured: the task fails here, at
        open, before register and before a byte moves (the sink itself
        would refuse it only once the content length is known)."""
        chips = getattr(self.device_sink_factory, "chips", None)
        if chips is None or not self.shard_manifest:
            return
        worst = max(self.shard_manifest, key=lambda s: s.device)
        if worst.device >= chips:
            self.sink_error = (
                f"device sink refused: shard {worst.name} is placed on "
                f"device {worst.device}, this host's sink is open over "
                f"{chips}")
            raise DFError(Code.CLIENT_DEVICE_SINK_ERROR, self.sink_error)

    async def _run_traced(self, sp) -> None:
        try:
            used_p2p = False
            self._refuse_unplaceable_manifest()
            if await self._try_adopt_content():
                # the whole task's bytes were already on disk under another
                # task id (content-digest hit): placed, not transferred —
                # no scheduler, no parents, no origin
                await self._finish_success()
                return
            if self.scheduler is not None:
                self._session = await self._register()
                if self.flight is not None and self._session is not None:
                    self.flight.event(fr.REGISTERED)
                if self._session is not None:
                    assigned = getattr(self._session.result,
                                       "assigned_shards", None)
                    if assigned is not None:
                        self.set_affinity(list(assigned))
                if self._session is not None and self._p2p_engine is not None:
                    if self.flight is not None:
                        self.flight.rung(fr.RUNG_P2P)
                    if self.pex is not None:
                        # opportunistic: swarm-known holders ride an
                        # advisory packet so hot tasks have parents before
                        # the scheduler's assignment lands
                        self.pex.prime(self, self._session)
                    used_p2p = await self._p2p_engine.pull(self, self._session)
            if (not used_p2p and self.pex is not None
                    and (self.scheduler is None or self._sched_unreachable)):
                # the pex rung (docs/RESILIENCE.md): every scheduler is
                # unreachable (or none was ever configured) but gossip
                # knows mesh holders — serve P2P instead of stampeding
                # the origin. Scheduler VERDICTS (NeedBackSource) are
                # respected: this rung only replaces a control plane that
                # is absent, never one that answered.
                used_p2p = await self.pex.try_pull(self)
            if not used_p2p:
                if self.disable_back_source:
                    raise DFError(Code.CLIENT_BACK_SOURCE_ERROR,
                                  "no P2P path and back-source disabled")
                if self.flight is not None:
                    self.flight.rung(fr.RUNG_BACK_SOURCE)
                self.log.info("back-source: %s", self.url)
                await self.piece_mgr.download_source(self)
            await self._finish_success()
        except asyncio.CancelledError:
            await self._finish_fail(Code.CLIENT_CONTEXT_CANCELED, "canceled")
        except DFError as exc:
            await self._finish_fail(exc.code, exc.message)
        except Exception as exc:  # noqa: BLE001
            self.log.exception("task failed")
            await self._finish_fail(Code.UNKNOWN, str(exc))
        finally:
            sp.set(state=self.state, pieces=len(self.ready),
                   traffic_p2p=self.traffic_p2p,
                   traffic_source=self.traffic_source)
            # closed only after finalize so the PeerResult carries the real
            # outcome — a half-pulled peer must never be advertised complete
            if self._session is not None:
                await self._session.close(success=self.state == self.SUCCESS)
            if self.shaper is not None:
                self.shaper.unregister(self.task_id)
            if self.qos_release is not None:
                release, self.qos_release = self.qos_release, None
                release()
            if self._relay_tracked:
                # wakes any streaming serve parked on this task's progress
                # so it winds down now instead of riding out its deadline
                self._relay_tracked = False
                self.relay.untrack(self.task_id)

    async def _register(self):
        """Register with the scheduler; None means "go to origin" (the
        reference's fallback ladder: register-fail / NeedBackSource)."""
        try:
            return await self.scheduler.register(self)
        except DFError as exc:
            if exc.code in (Code.UNAVAILABLE, Code.DEADLINE_EXCEEDED):
                # transport exhaustion, not a verdict: the pex rung may
                # still find mesh parents before origin
                self._sched_unreachable = True
                self.log.info("register unreachable: %s", exc.message)
                return None
            if exc.code == Code.SCHED_NEED_BACK_SOURCE:
                self.log.info("register says back-source: %s", exc.message)
                return None
            raise
        except Exception as exc:  # scheduler unreachable entirely
            self._sched_unreachable = True
            self.log.warning("scheduler unreachable (%s); falling back", exc)
            return None

    def _sink_lost(self, why: str) -> None:
        """The requested device sink is gone: remember why (the task will
        end FAILED with it, _finish_sink) and release the sink."""
        self.log.error("device sink lost: %s", why, exc_info=True)
        if not self.sink_error:
            self.sink_error = why
        if self.device_ingest is not None:
            self.device_ingest.close()
            self.device_ingest = None

    def _open_device_sink(self, content_length: int) -> None:
        if (self.device_sink_factory is None or content_length <= 0
                or self.device_ingest is not None or self.sink_error):
            return
        t0 = time.monotonic()
        try:
            with tracing.annotate("sink_open"):
                self.device_ingest = self._make_device_ingest(content_length)
        except Exception as exc:  # noqa: BLE001 - reported at finish
            self._sink_lost(f"device sink refused: {type(exc).__name__}: "
                            f"{exc}")
            return
        if self.flight is not None:
            # the content-sized host buffer is leased here, on the loop;
            # parent: whether the pool had a released one for it
            hit = getattr(self.device_ingest, "pool_hit", None)
            self.flight.event(fr.SINK_OPEN, nbytes=content_length,
                              parent=("" if hit is None
                                      else "hit" if hit else "miss"),
                              dur_ms=(time.monotonic() - t0) * 1000.0)

    def _stage_lease(self):
        """A landing's hold on the device sink's host buffer, handed to
        the storage call as ``stage=`` so that the staging copy of each
        verified piece runs on the storage thread, in the landing's own
        hop. None for a task with no sink (such a landing carries no
        extra argument). The caller releases it once the landing has
        returned: until then the sink keeps the buffer, even if the sink
        is lost or closed meanwhile."""
        ingest = self.device_ingest
        return ingest.lease() if ingest is not None else None

    def _ingest_to_device(self, num: int, offset: int, nbytes: int,
                          lease) -> bool:
        """Account one piece that a landing staged into the device sink
        through ``lease``; False once the sink is lost. The ONE copy of
        the commit/journal/loss sequence — every landing path (pieces,
        spans, adoption, placement) comes through here, on the loop, after
        its landing has returned and so after the staging copy has."""
        if self.device_ingest is None:
            return False
        t0 = time.monotonic()
        try:
            if lease.error is not None:
                raise lease.error
            self.device_ingest.commit(offset, nbytes)
        except Exception as exc:  # noqa: BLE001 - reported at finish
            self._sink_lost(f"device ingest write failed at piece {num}: "
                            f"{type(exc).__name__}: {exc}")
            return False
        if self.flight is not None:
            # dur_ms: the loop's seconds in here, which is the sink's
            # bookkeeping (coverage map, spec scan, enqueue); the staging
            # copy ran in the landing (flight ``staged``)
            t1 = time.monotonic()
            self.flight.event(fr.HBM_DONE, num, nbytes=nbytes,
                              dur_ms=(t1 - t0) * 1000.0,
                              t_ms=self.flight.ms_at(t1))
        return True

    async def _stage_from_disk(self, num: int, offset: int,
                               size: int) -> bool:
        """Stage a piece whose VERIFIED bytes are on disk and came off no
        wire in this conductor (adoption, placement, a piece an earlier
        conductor recorded): read and copied on the storage thread
        (journaled as a landing of path ``disk``), then accounted. False
        with no sink, or once it is lost."""
        lease = self._stage_lease()
        if lease is None:
            return False

        def read_and_stage(stage) -> None:
            stage.copy(offset, self.storage.read_piece(num))

        try:
            await self._land(num, size, "disk", read_and_stage, stage=lease)
            return self._ingest_to_device(num, offset, size, lease)
        finally:
            lease.release()

    async def _land(self, num: int, nbytes: int, path: str | None,
                    fn, *args, stage=None, **kwargs):
        """One landing on the storage executor, journaled: ``landed`` is
        the thread's write + verify pass, ``staged`` the staging copies it
        made beside that into the device sink (``stage``: the sink's
        lease, handed on to ``fn`` where there is one), ``land_wait`` what
        the landing waited, for a storage thread and then for the loop to
        resume this coroutine. ``path`` None: ``fn`` is ``write_span`` and
        names the path it took in its result."""
        if stage is not None:
            kwargs["stage"] = stage
        t_submit = time.monotonic()
        out, t_begin, t_end = await run_io(_stamped, fn, *args, **kwargs)
        flight = self.flight
        if flight is not None:
            t_back = time.monotonic()
            if path is None:
                path = out[2]
            copy_s = stage.seconds if stage is not None else 0.0
            flight.event(fr.LANDED, num, path, nbytes,
                         dur_ms=(t_end - t_begin - copy_s) * 1000.0,
                         t_ms=flight.ms_at(t_begin))
            if copy_s:
                flight.event(fr.STAGED, num, path, stage.nbytes,
                             dur_ms=copy_s * 1000.0,
                             t_ms=flight.ms_at(t_end))
            flight.event(fr.LAND_WAIT, num, path, dur_ms=(
                (t_begin - t_submit) + (t_back - t_end)) * 1000.0,
                t_ms=flight.ms_at(t_back))
        return out

    # ------------------------------------------------------------------
    # content-addressed dedupe (storage/castore.py)
    # ------------------------------------------------------------------

    async def _try_adopt_content(self) -> bool:
        """Whole-task dedupe: when the request names a content digest the
        store already holds complete, materialize this task as a hardlink
        of the canonical copy (zero transfers, shared bytes on disk) and
        adopt its piece table. False = no hit; the normal ladder runs."""
        if (not self.url_meta.digest or self.content_range is not None
                or self.url_meta.range
                # url_meta.range is checked SEPARATELY from content_range:
                # a ranged request's content_range is still None here (it
                # resolves against the origin's real total later, in
                # download_source) — adopting on the raw flag alone would
                # materialize the WHOLE file under the ranged task id
                or getattr(self.storage_mgr, "castore", None) is None):
            return False
        md = TaskMetadata(
            task_id=self.task_id, task_type=self.task_type, url=self.url,
            tag=self.url_meta.tag, application=self.url_meta.application,
            digest=self.url_meta.digest, priority=self.resolved_priority,
            qos_class=self.qos_class)
        ts = await run_io(self.storage_mgr.adopt_content, md)
        if ts is None or not (ts.md.done and ts.md.success):
            return False
        self._adopted = True
        self.storage = ts
        self.content_length = ts.md.content_length
        self.piece_size = ts.md.piece_size
        self.total_pieces = ts.md.total_piece_count
        self._init_shards()
        self.storage_mgr.castore.note_hit("content", ts.md.content_length)
        self._open_device_sink(self.content_length)
        for num in sorted(ts.md.pieces):
            p = ts.md.pieces[num]
            await self._stage_from_disk(num, p.start, p.size)
            async with self._piece_cond:
                self.ready.add(num)
                self.completed_length += p.size
                self._piece_cond.notify_all()
            self.traffic_placed += p.size
            if self.flight is not None:
                self.flight.event(fr.PLACED, num, "cas", p.size)
            self._note_shard_progress(num, p.start, p.size)
            self._publish({"type": "piece", "num": num, "size": p.size,
                           "completed": self.completed_length,
                           "total": self.content_length})
        self.log.info("content dedupe: task adopted from the store "
                      "(%d pieces, %d bytes, zero transferred)",
                      len(ts.md.pieces), self.completed_length)
        return True

    async def place_from_store(self, infos: list[PieceInfo]) -> set[int]:
        """Piece-level dedupe: land any of ``infos`` whose bytes are
        already on disk — recorded under THIS task (warm restart / retry
        over surviving storage) or under any task sharing the digest
        (cross-task placement via the content store) — without touching
        the wire. Returns the piece numbers landed so the engine never
        dispatches a pull for them."""
        if self.storage is None:
            return set()
        castore = getattr(self.storage_mgr, "castore", None)
        placed: set[int] = set()
        reports: list = []
        for info in infos:
            num = info.piece_num
            if num in self.ready or num in self._landing:
                continue
            meta = self.storage.md.pieces.get(num)
            if meta is None and (castore is None or not info.digest
                                 or castore.find_piece(
                                     info.digest, info.range_size,
                                     exclude_task=self.task_id) is None):
                continue
            self._landing.add(num)
            try:
                if meta is not None:
                    # verified at its original landing (or at the boot
                    # re-verify): adopt in place, no copy
                    offset, size, landed = meta.start, meta.size, True
                    if castore is not None:
                        castore.note_hit("task", size)
                else:
                    offset, size = info.range_start, info.range_size
                    landed = await run_io(
                        castore.place_piece, self.storage, num,
                        offset, size, info.digest)
            finally:
                self._landing.discard(num)
            if not landed or num in self.ready:
                continue
            await self._stage_from_disk(num, offset, size)
            async with self._piece_cond:
                if num in self.ready:
                    continue
                self.ready.add(num)
                self.completed_length += size
                self._piece_cond.notify_all()
            self.traffic_placed += size
            placed.add(num)
            if self.flight is not None:
                self.flight.event(fr.PLACED, num, "cas", size)
            self._note_shard_progress(num, offset, size)
            if self._relay_tracked:
                self.relay.pulse(self.task_id)
            self._publish({"type": "piece", "num": num, "size": size,
                           "completed": self.completed_length,
                           "total": self.content_length})
            if self._session is not None:
                # announce the placement so the scheduler counts this
                # daemon a holder — same shape as a back-source landing
                # (dst ""): the bytes came off no peer's upload slot.
                # Collected and fired CONCURRENTLY below — a warm restart
                # adopts hundreds of pieces, and one sequential RPC round
                # trip per piece would stall the hole-filling download
                # behind pieces x RTT of scheduler chatter
                from ..idl.messages import PieceResult
                now = int(time.time() * 1000)
                reports.append(PieceResult(
                    task_id=self.task_id, src_peer_id=self.peer_id,
                    dst_peer_id="", success=True,
                    piece_info=PieceInfo(piece_num=num, range_start=offset,
                                         range_size=size,
                                         digest=info.digest),
                    begin_ms=now, end_ms=now,
                    finished_count=len(self.ready)))
        if reports:
            await asyncio.gather(*(self._session.report_piece(r)
                                   for r in reports))
        return placed

    # ------------------------------------------------------------------
    # content metadata + piece arrival (called by piece manager / engine)
    # ------------------------------------------------------------------

    # ------------------------------------------------------------------
    # sharded delivery (common/sharding.py)
    # ------------------------------------------------------------------

    def _init_shards(self) -> None:
        """Build the shard tracker once piece geometry is known. A
        malformed manifest demotes the task to the whole-file path (the
        download still completes; nothing becomes a named ready array)."""
        if (self.shard_manifest is None or self.shard_tracker is not None
                or self.piece_size <= 0):
            return
        from ..common import sharding
        try:
            sharding.validate_manifest(self.shard_manifest,
                                       self.content_length)
            tracker = sharding.ShardTracker(self.shard_manifest,
                                            self.requested_shards)
        except ValueError:
            self.log.exception("bad shard manifest; whole-file fallback")
            self.shard_manifest = None
            self.requested_shards = None
            return
        self.shard_tracker = tracker
        if self.flight is not None:
            self.flight.shards_total = tracker.total
        if self.requested_shards is not None and self.total_pieces >= 0:
            self.needed_pieces = tracker.needed_pieces(self.piece_size,
                                                       self.total_pieces)
        self._classify_affinity()
        self.log.info("sharded task: %d/%d shards requested (%s pieces "
                      "needed, %d swap-class)", tracker.total,
                      len(self.shard_manifest),
                      "all" if self.needed_pieces is None
                      else len(self.needed_pieces),
                      len(self.swap_piece_nums))

    def set_affinity(self, names: list[str]) -> None:
        """Scheduler shard-affinity ruling: these requested shards are
        THIS peer's to fetch from the tree; the rest arrive by swap."""
        self.affinity_shards = names
        self._classify_affinity()

    def _classify_affinity(self) -> None:
        tracker = self.shard_tracker
        if tracker is None or self.affinity_shards is None \
                or self.piece_size <= 0:
            return
        from ..common.sharding import pieces_for_shards
        mine = set(self.affinity_shards)
        self._swap_shard_names = {s.name for s in tracker.shards
                                  if s.name not in mine}
        swap_shards = [s for s in tracker.shards
                       if s.name in self._swap_shard_names]
        swap = pieces_for_shards(swap_shards, self.piece_size,
                                 self.total_pieces)
        tree_shards = [s for s in tracker.shards if s.name in mine]
        tree = pieces_for_shards(tree_shards, self.piece_size,
                                 self.total_pieces)
        # a boundary piece shared by a tree shard and a swap shard is
        # tree-class: this host must fetch it anyway, and holding it
        # back would stall the tree shard behind the swap window
        self.swap_piece_nums = swap - tree

    def pieces_remaining(self) -> int:
        """Pieces still to land before this download is DONE — the
        requested-subset count for sharded tasks, total otherwise
        (-1 = unknown geometry)."""
        if self.total_pieces < 0:
            return -1
        if self.needed_pieces is not None:
            return len(self.needed_pieces - self.ready)
        return self.total_pieces - len(self.ready)

    def needed_piece_nums(self, total: int) -> list[int]:
        """Sorted piece numbers this task needs out of ``total`` — the
        back-source hole universe (piece_manager.download_source)."""
        if self.needed_pieces is not None:
            return sorted(n for n in self.needed_pieces if n < total)
        return list(range(total))

    def _note_shard_progress(self, num: int, offset: int, size: int,
                             replay: bool = False) -> None:
        """One verified piece landed: advance shard coverage, journal +
        publish any shard that just completed. Cheap (interval merge) —
        rides every landing path including placements and adoption.
        ``replay`` (the widen path re-feeding already-landed pieces into
        a fresh tracker) skips the byte counters: those bytes were
        counted, with their true tree/swap class, when they landed."""
        tracker = self.shard_tracker
        if tracker is None:
            return
        if not replay:
            # count only the bytes that fall INSIDE tracked shards:
            # manifest-gap pieces (and the non-shard halves of boundary
            # pieces) must not inflate the tree/swap split the metric
            # exists to report
            in_shards = tracker.shard_bytes_in(offset, offset + size)
            if in_shards:
                swap = num in self.swap_piece_nums
                _shard_bytes.labels("swap" if swap else "tree").inc(
                    in_shards)
        t = self.flight.now_ms() if self.flight is not None else 0.0
        for name in tracker.on_span(offset, offset + size, t):
            shard = tracker.shard_for(name)
            src = (fr.SHARD_SRC_SWAP if name in self._swap_shard_names
                   else fr.SHARD_SRC_TREE)
            _shard_ready.labels(fr.SHARD_SRC_NAMES[src]).inc()
            _shard_ready_s.observe(max(t, 0.0) / 1000.0)
            if self.flight is not None:
                self.flight.event(fr.SHARD_READY, src, name,
                                  shard.range_size, t_ms=t)
            self._publish({"type": "shard", "name": name,
                           "src": fr.SHARD_SRC_NAMES[src],
                           "bytes": shard.range_size,
                           "ready": len(tracker.ready),
                           "total": tracker.total})

    def note_shard_fallback(self, num: int, parent_id: str) -> None:
        """A swap-class piece is being served by the TREE after its swap
        hold expired (engine hook): journal it once per piece so dfdiag
        can tell a healthy swap from a died-partner fallback."""
        if num in self._fallback_noted:
            return
        self._fallback_noted.add(num)
        _shard_fallbacks.inc()
        if self.flight is not None:
            self.flight.event(fr.SHARD_FALLBACK, num, parent_id)

    def widen_to_whole_file(self) -> bool:
        """A joiner needs shards (or the whole file) outside this subset
        download: widen to the full piece set mid-flight. Landed coverage
        is replayed into a full-manifest tracker so already-complete
        shards stay ready and partially-covered ones keep their bytes —
        nothing re-fetches. Returns False when this download has already
        COMMITTED to finishing (the engine's/back-source's final
        coverage check, or finalize itself): widening then could fail a
        complete subset as "incomplete" or hand the joiner a success
        missing its shards — the caller starts a fresh conductor over
        the same task storage instead (it adopts the landed pieces and
        fetches only the gap). Runs on the event loop, so the refusal
        check and the mutation are atomic w.r.t. the commit points."""
        if self.requested_shards is None:
            return True
        if self._finishing or self.done_event.is_set():
            return False
        self.log.info("sharded task widened to the whole file by a joiner")
        self.requested_shards = None
        self.needed_pieces = None
        self.swap_piece_nums = set()
        self._swap_shard_names = set()
        if (self.shard_tracker is not None and self.piece_size > 0
                and self.shard_manifest):
            from ..common.sharding import ShardTracker
            fresh = ShardTracker(self.shard_manifest)
            fresh.ready.update(self.shard_tracker.ready)
            self.shard_tracker = fresh
            if self.flight is not None:
                self.flight.shards_total = fresh.total
            if self.storage is not None:
                for num in sorted(self.ready):
                    meta = self.storage.md.pieces.get(num)
                    if meta is not None:
                        self._note_shard_progress(num, meta.start,
                                                  meta.size, replay=True)
        engine = self._p2p_engine
        if engine is not None:
            engine.apply_shard_state(self)
        return True

    def _device_shard_specs(self) -> list[tuple] | None:
        tracker = self.shard_tracker
        if tracker is None:
            return None
        return [(s.name, s.range_start, s.range_size, s.dtype,
                 list(s.shape) if s.shape else None,
                 s.device)
                for s in tracker.shards]

    def _make_device_ingest(self, content_length: int):
        specs = self._device_shard_specs()
        if specs:
            return self.device_sink_factory(content_length,
                                            shard_specs=specs)
        return self.device_sink_factory(content_length)

    def set_content_info(self, content_length: int,
                         piece_size: int = 0) -> int:
        """Fix piece geometry; register storage + device sink. Returns the
        piece size. ``content_length`` is the EFFECTIVE length this task
        stores (the sub-range length for ranged tasks — piece offsets are
        range-relative). Safe to call more than once with identical values."""
        if self.piece_size:
            return self.piece_size
        effective_len = content_length
        self.content_length = effective_len
        self.piece_size = piece_size or compute_piece_size(max(effective_len, 0))
        if effective_len >= 0:
            self.total_pieces = piece_count(effective_len, self.piece_size)
        md = TaskMetadata(
            task_id=self.task_id, task_type=self.task_type, url=self.url,
            tag=self.url_meta.tag, application=self.url_meta.application,
            content_length=effective_len, total_piece_count=self.total_pieces,
            piece_size=self.piece_size, digest=self.url_meta.digest,
            priority=self.resolved_priority, qos_class=self.qos_class)
        self.storage = self.storage_mgr.register_task(md)
        self._init_shards()
        if self.relay is not None and not self._relay_tracked:
            # cut-through: from here until finish, the upload server may
            # serve this task's bytes up to the landing watermark
            self._relay_tracked = True
            self.relay.track(self.task_id, total_pieces=self.total_pieces,
                             on_open=self._on_relay_span)
        self._open_device_sink(effective_len)
        return self.piece_size

    def _on_relay_span(self, span) -> None:
        """A new in-flight span opened for this task: publish its piece
        numbers so the rpcserver's sync streams can announce-ahead —
        children may begin pulling these pieces NOW and the upload
        server's streaming path serves them to the watermark."""
        self._publish({"type": "relay",
                       "nums": [p.piece_num for p in span.pieces]})

    async def on_piece_from_source(self, num: int, offset: int, data: bytes,
                                   cost_ms: int) -> None:
        # timestamp taken BEFORE landing (wire_done must precede the
        # hbm_done _land_piece emits), recorded only AFTER the piece
        # verified and landed (a digest-failed or duplicate piece must not
        # count as delivered bytes in the summary); back-source pieces
        # skip the dispatcher stages, so the duration back-dates the start
        t_wire = self.flight.now_ms() if self.flight is not None else 0.0
        if not await self._land_piece(num, offset, data, cost_ms, source=""):
            return
        self.traffic_source += len(data)
        if self.flight is not None:
            self.flight.event(fr.WIRE_DONE, num, fr.ORIGIN, len(data),
                              dur_ms=cost_ms, t_ms=t_wire)
        if self._session is not None:
            # a back-source peer announces its pieces so the scheduler can
            # make it a parent — this is where origin egress gets saved
            from ..idl.messages import PieceInfo, PieceResult
            now = int(time.time() * 1000)
            await self._session.report_piece(PieceResult(
                task_id=self.task_id, src_peer_id=self.peer_id,
                dst_peer_id="", success=True,
                piece_info=PieceInfo(piece_num=num, range_start=offset,
                                     range_size=len(data),
                                     download_cost_ms=cost_ms),
                begin_ms=now - cost_ms, end_ms=now,
                finished_count=len(self.ready)))

    async def on_piece_from_peer(self, num: int, offset: int, data: bytes,
                                 cost_ms: int, parent_id: str,
                                 piece_digest: str = "") -> bool:
        """Returns True when this call landed the piece (the flight
        recorder and traffic stats count only landed pieces). The normal
        P2P path lands through ``on_span_from_peer``; this remains for
        TINY direct-content tasks and per-piece callers."""
        # the downloader no longer hashes on the loop: verification happens
        # in the storage write pass (a mismatch raises DIGEST_MISMATCH)
        landed = await self._land_piece(num, offset, data, cost_ms,
                                        source=parent_id,
                                        piece_digest=piece_digest)
        if landed:
            # endgame-raced duplicates are dropped at landing and must not
            # inflate the traffic accounting (egress-saved stats)
            self.traffic_p2p += len(data)
        return landed

    async def on_span_from_peer(self, parent_id: str,
                                pieces: list[PieceInfo], data,
                                cost_ms_per_piece: int,
                                ) -> tuple[list[int], list[int], list[int]]:
        """Land a whole contiguous downloaded span in ONE pass: one
        storage-executor hop, one buffer traversal (digest verification
        fused with the write — ``TaskStorage.write_span``), one condition
        round for all pieces. This replaces the per-piece landing loop
        that cost a ``to_thread`` hop, a hash pass, and a write per 4-16
        MiB piece.

        ``pieces`` are contiguous ascending; ``data`` holds their bytes
        from ``pieces[0].range_start``. Returns ``(placed, corrupt,
        raced)`` piece-number lists. ``raced`` pieces were CLAIMED BY AN
        IN-FLIGHT RACER (endgame duplicate mid-landing) whose outcome is
        unknown — the caller must report them neither completed nor
        corrupt (the racer's own report settles them); now that
        verification happens at landing, treating a still-landing
        duplicate as done would orphan the piece for good if the racer's
        copy turns out corrupt. Already-LANDED duplicates appear in none
        of the three lists: those verified at landing and are safely
        reportable as complete. The caller owns ``data`` and may release
        it to the buffer pool as soon as this returns: the storage write
        and, inside the same storage hop, the staging copy into the device
        sink are complete when the landing returns, and nothing here reads
        ``data`` after it (the pool's reuse-safety contract).
        """
        if self.storage is None:
            raise DFError(Code.CLIENT_STORAGE_ERROR,
                          "span before content info")
        base = pieces[0].range_start
        raced = [p.piece_num for p in pieces
                 if p.piece_num in self._landing]
        claim = [p for p in pieces
                 if p.piece_num not in self.ready
                 and p.piece_num not in self._landing]
        if not claim:
            return [], [], raced
        lease = self._stage_lease()
        for p in claim:             # same dedup-race claim as _land_piece
            self._landing.add(p.piece_num)
        try:
            return await self._land_span(parent_id, claim, data, base,
                                         cost_ms_per_piece, raced, lease)
        finally:
            if lease is not None:
                lease.release()

    async def _land_span(self, parent_id: str, claim: list[PieceInfo], data,
                         base: int, cost_ms_per_piece: int, raced: list[int],
                         lease) -> tuple[list[int], list[int], list[int]]:
        """``on_span_from_peer`` once the pieces are claimed; ``lease`` is
        the device sink's (None with no sink), held by the caller until
        this returns."""
        try:
            write_span = getattr(self.storage, "write_span", None)
            if write_span is not None:
                spec = [(p.piece_num, p.range_start, p.range_size, p.digest)
                        for p in claim]
                metas, corrupt, path = await self._land(
                    claim[0].piece_num,
                    sum(p.range_size for p in claim), None,
                    write_span, spec, data, base=base,
                    cost_ms=cost_ms_per_piece, source=parent_id,
                    stage=lease)
                _span_lands.labels(path).inc()
                landed_nums = [m.num for m in metas]
            else:
                # storage without a span entry point (ranged sub-task
                # views): per-piece landing, still off-loop
                _span_lands.labels("per_piece").inc()
                landed_nums, corrupt = [], []
                mv = memoryview(data)
                try:
                    for p in claim:
                        lo = p.range_start - base
                        staged = lease.nbytes if lease is not None else 0
                        try:
                            await self._land(
                                p.piece_num, p.range_size, "per_piece",
                                self.storage.write_piece, p.piece_num,
                                p.range_start, mv[lo:lo + p.range_size],
                                p.digest, cost_ms=cost_ms_per_piece,
                                source=parent_id, stage=lease)
                        except DFError as exc:
                            if exc.code == Code.CLIENT_DIGEST_MISMATCH:
                                corrupt.append(p.piece_num)
                                continue
                            raise
                        if lease is None or lease.took(staged):
                            landed_nums.append(p.piece_num)
                        # else write_piece found it recorded and skipped
                        # it: one of the on_disk pieces below
                finally:
                    mv.release()
        finally:
            for p in claim:
                self._landing.discard(p.piece_num)
        by_num = {p.piece_num: p for p in claim}
        landed_set = set(landed_nums)
        corrupt_set = set(corrupt)
        # claimed pieces that are neither landed nor corrupt were ALREADY
        # on disk: md-recorded by an earlier conductor over this same
        # TaskStorage (retry after a failed download — the ready set died
        # with the old conductor, the storage did not). Their disk bytes
        # were verified when first landed, so count them placed here too;
        # not doing so would report them complete meshside while this
        # conductor never reaches total_pieces — a silent forever-hang.
        on_disk = set(p.piece_num for p in claim
                      if p.piece_num not in landed_set
                      and p.piece_num not in corrupt_set
                      and p.piece_num not in self.ready)
        placed = [n for n in landed_nums if n not in self.ready]
        placed += sorted(on_disk)
        if not placed:
            return [], corrupt, raced
        if lease is not None:
            # the landing staged every piece it recorded, on its storage
            # thread; what is left for the loop is the sink's bookkeeping
            for n in placed:
                p = by_num[n]
                if n in on_disk:
                    # this span's copy of an already-recorded piece was
                    # never digest-checked, and write_span skipped it:
                    # stage the VERIFIED bytes from disk instead
                    ok = await self._stage_from_disk(n, p.range_start,
                                                     p.range_size)
                else:
                    ok = self._ingest_to_device(n, p.range_start,
                                                p.range_size, lease)
                if not ok:
                    break
        events = []
        counted = []
        async with self._piece_cond:
            for n in placed:
                if n in self.ready:
                    # lost a race decided during the awaits above (an
                    # endgame duplicate re-claimed a just-landed piece in
                    # the _landing-discard → ready-add window): the winner
                    # already accounted it — counting twice would inflate
                    # completed_length past content_length
                    continue
                counted.append(n)
                size = by_num[n].range_size
                self.ready.add(n)
                self.completed_length += size
                self.traffic_p2p += size
                if self.shaper is not None:
                    self.shaper.record(self.task_id, size)
                events.append({"type": "piece", "num": n, "size": size,
                               "completed": self.completed_length,
                               "total": self.content_length})
            self._piece_cond.notify_all()
        for n in counted:
            p = by_num[n]
            self._note_shard_progress(n, p.range_start, p.range_size)
        if self._relay_tracked:
            # landed bytes are now disk-covered: move relay readers along
            self.relay.pulse(self.task_id)
        for ev in events:
            self._publish(ev)
        return counted, corrupt, raced

    async def _land_piece(self, num: int, offset: int, data: bytes,
                          cost_ms: int, source: str,
                          piece_digest: str = "",
                          pre_verified: bool = False) -> bool:
        """Returns True when THIS call landed the piece (duplicates from
        endgame racing return False and change nothing)."""
        if self.storage is None:
            raise DFError(Code.CLIENT_STORAGE_ERROR, "piece before content info")
        if num in self.ready or num in self._landing:
            # _landing claims the piece BEFORE the await below: endgame
            # duplicate racers land near-simultaneously, and a ready-only
            # check would let both through (double-counted progress, double
            # device-ingest writes, duplicate scheduler success reports)
            return False
        self._landing.add(num)
        lease = self._stage_lease()
        try:
            # hashing+write can take ms at 16MiB — runs on the DEDICATED
            # storage executor (io_executor.py), not the shared default
            # pool, so piece landing never queues behind TLS handshakes.
            # The staging copy into the device sink rides the same hop,
            # after the piece has verified (write_piece's ``stage``)
            await self._land(num, len(data), "per_piece",
                             self.storage.write_piece, num, offset,
                             data, piece_digest, cost_ms=cost_ms,
                             source=source, pre_verified=pre_verified,
                             stage=lease)
        finally:
            self._landing.discard(num)
            if lease is not None:
                lease.release()
        if num in self.ready:     # lost a race decided elsewhere
            return False
        if lease is not None:
            # what is left for the loop is the sink's bookkeeping and the
            # transfer-queue enqueue — the DMA itself runs on the sink's
            # own thread and is never awaited here
            if lease.took(0):
                self._ingest_to_device(num, offset, len(data), lease)
            else:
                # write_piece found the piece recorded (an earlier
                # conductor over this storage) and skipped it: ``data``
                # was never digest-checked, the bytes on disk were
                await self._stage_from_disk(num, offset, len(data))
        if self.shaper is not None:
            self.shaper.record(self.task_id, len(data))
        async with self._piece_cond:
            self.ready.add(num)
            self.completed_length += len(data)
            self._piece_cond.notify_all()
        self._note_shard_progress(num, offset, len(data))
        if self._relay_tracked:
            self.relay.pulse(self.task_id)
        self._publish({"type": "piece", "num": num, "size": len(data),
                       "completed": self.completed_length,
                       "total": self.content_length})
        return True

    def on_source_complete(self, total: int) -> None:
        if self.content_length < 0:
            self.content_length = total
            self.total_pieces = len(self.ready)
            if self.storage is not None:
                self.storage.md.content_length = total
                self.storage.md.total_piece_count = self.total_pieces

    # ------------------------------------------------------------------
    # finalize
    # ------------------------------------------------------------------

    async def _verify_digest(self) -> None:
        if not self.url_meta.digest or self.storage is None:
            return
        if self._adopted:
            # the canonical copy verified this digest when IT completed,
            # and adoption is a hardlink of that same inode — a second
            # full-content hash here would re-pay the cost dedupe removed
            return
        if self.content_range is not None:
            # the digest describes the whole file; a sub-range can't check it
            return
        algo, want = digestlib.parse(self.url_meta.digest)

        def compute() -> str:
            def chunks():
                with open(self.storage.data_path(), "rb") as f:
                    remaining = self.content_length
                    while remaining > 0:
                        b = f.read(min(4 << 20, remaining))
                        if not b:
                            return
                        remaining -= len(b)
                        yield b
            return digestlib.hash_stream(algo, chunks())

        # default executor ON PURPOSE (not run_io): this is a full-content
        # hash — minutes at multi-GB — and the storage pool is 4 threads
        # sized for piece landings; parking it there would queue every
        # in-flight span write behind a finalizing task
        got = await asyncio.to_thread(compute)
        if got != want:
            raise DFError(Code.CLIENT_DIGEST_MISMATCH,
                          f"content digest mismatch: {algo}:{got[:12]}..")

    async def _verify_shard_digests(self) -> None:
        """Optional whole-shard digests (ShardInfo.digest) checked at
        finalize over the landed bytes; per-piece digests already
        verified every piece at landing, so this is belt-and-braces for
        manifests that carry them."""
        tracker = self.shard_tracker
        if tracker is None or self.storage is None:
            return
        to_check = [s for s in tracker.shards
                    if s.digest and s.name in tracker.ready]
        if not to_check:
            return
        path = self.storage.data_path()

        def compute() -> list[str]:
            bad: list[str] = []
            with open(path, "rb") as f:
                for s in to_check:
                    algo, want = digestlib.parse(s.digest)
                    hasher = digestlib.Hasher(algo)
                    f.seek(s.range_start)
                    remaining = s.range_size
                    while remaining > 0:
                        b = f.read(min(4 << 20, remaining))
                        if not b:
                            break
                        remaining -= len(b)
                        hasher.update(b)
                    if remaining or hasher.hexdigest() != want:
                        bad.append(s.name)
            return bad

        # default executor, same rationale as _verify_digest: multi-GB
        # hashing must not queue span landings on the 4-thread storage pool
        bad = await asyncio.to_thread(compute)
        if bad:
            raise DFError(Code.CLIENT_DIGEST_MISMATCH,
                          f"shard digest mismatch: {bad}")

    async def _finish_success(self) -> None:
        # a requested-shard subset finishes when ITS pieces are all in;
        # the task's storage then stays a warm PARTIAL (never marked
        # done): peers see exactly the pieces it holds, a later request
        # for other shards adopts them via place_from_store, and the
        # complete-task reuse path can never serve the partial file as
        # whole content
        self._finishing = True      # widen refused from here on
        subset_done = (self.needed_pieces is not None
                       and self.total_pieces >= 0
                       and len(self.ready) < self.total_pieces
                       and not (self.needed_pieces - self.ready))
        if (self.total_pieces >= 0 and len(self.ready) < self.total_pieces
                and not subset_done):
            raise DFError(Code.CLIENT_STORAGE_ERROR,
                          f"incomplete: {len(self.ready)}/{self.total_pieces} pieces")
        await self._verify_shard_digests()
        if subset_done:
            if self.storage is not None:
                await run_io(self.storage.persist)
        else:
            await self._verify_digest()
            if self.storage is not None:
                await run_io(self.storage.mark_done, success=True,
                             content_length=self.content_length,
                             total_piece_count=self.total_pieces)
        if self.device_sink_factory is not None:
            await self._finish_sink()
        self.state = self.SUCCESS
        if self.flight is not None:
            self.flight.finish(self.SUCCESS)
            # count this task's stage-budget breaches into
            # df_slo_breach_total (once, here — summaries themselves only
            # carry the annotation)
            from ..common.health import PLANE
            PLANE.slo.observe_summary(self.flight.summarize())
        self._publish({"type": "done", "success": True,
                       "completed": self.completed_length,
                       "total": self.content_length})
        self.done_event.set()
        async with self._piece_cond:
            self._piece_cond.notify_all()
        self.log.info("task success: %d bytes, %d pieces (p2p=%d src=%d "
                      "placed=%d)", self.completed_length, len(self.ready),
                      self.traffic_p2p, self.traffic_source,
                      self.traffic_placed)

    async def _finish_sink(self) -> None:
        """A task that asked for a device sink succeeds only with every
        shard ON the device: wait out the last transfers (off-loop) and
        raise, with the reason, when the sink was refused, lost on the
        way, or a transfer failed."""
        ingest = self.device_ingest
        if ingest is not None:
            try:
                ingest.flush()
                await asyncio.to_thread(ingest.drain, SINK_DRAIN_TIMEOUT_S)
            except Exception as exc:  # noqa: BLE001 - becomes the verdict
                cause = exc.__cause__ or exc
                self._sink_lost(f"device transfer failed: "
                                f"{type(cause).__name__}: {cause}")
        if self.device_ingest is None:
            raise DFError(Code.CLIENT_DEVICE_SINK_ERROR, self.sink_error or
                          "no device sink was opened (content length "
                          "unknown)")
        # inside the peertask span context: the HBM landing joins the
        # task's trace (schedule decision -> piece fetch -> HBM)
        from ..common import tracing
        spans = list(ingest.transfer_spans)
        chips = list(ingest.transfer_chips)    # drained: one a span
        with tracing.span("hbm.ingest", task_id=self.task_id[:16]) as hsp:
            hsp.set(transfers=len(spans),
                    done_fraction=ingest.done_fraction(),
                    dma_ms=round(sum(b - a for a, b in spans) * 1e3, 3))
        if self.flight is not None:
            self.flight.hbm_spans(spans, chips)

    async def _finish_fail(self, code: Code, message: str) -> None:
        if self.state in (self.SUCCESS, self.FAILED):
            return
        self.state = self.FAILED
        self.fail_code = code
        self.fail_message = message
        if self.flight is not None:
            # ladder exhausted: the fail rung makes the terminal verdict
            # part of the journal, not just the PeerResult code
            self.flight.rung(fr.RUNG_FAIL)
            self.flight.finish(self.FAILED, message)
            from ..common.health import PLANE
            PLANE.slo.observe_summary(self.flight.summarize())
        if self.device_ingest is not None:
            self.device_ingest.close()
            self.device_ingest = None
        if self.storage is not None and not self.sink_error:
            # (a lost sink fails the REQUEST, not the bytes: the verified
            # disk copy keeps the state _finish_success gave it)
            try:
                await run_io(self.storage.mark_done, success=False)
            except Exception:  # noqa: BLE001
                pass
        self._publish({"type": "done", "success": False, "code": int(code),
                       "message": message})
        self.done_event.set()
        async with self._piece_cond:
            self._piece_cond.notify_all()
        self.log.warning("task failed: %s %s", code.name, message)

    async def wait_done(self, timeout: float | None = None) -> bool:
        if timeout:
            try:
                await asyncio.wait_for(self.done_event.wait(), timeout)
            except asyncio.TimeoutError:
                return False
        else:
            await self.done_event.wait()
        return self.state == self.SUCCESS

    def cancel(self) -> None:
        if self._run_task is not None:
            self._run_task.cancel()

    # ------------------------------------------------------------------
    # progress fan-out
    # ------------------------------------------------------------------

    def subscribe(self) -> asyncio.Queue:
        q: asyncio.Queue = asyncio.Queue()
        self._subscribers.append(q)
        if self.done_event.is_set():
            q.put_nowait({"type": "done", "success": self.state == self.SUCCESS,
                          "code": int(self.fail_code),
                          "completed": self.completed_length,
                          "total": self.content_length,
                          "message": self.fail_message})
        return q

    def unsubscribe(self, q: asyncio.Queue) -> None:
        try:
            self._subscribers.remove(q)
        except ValueError:
            pass

    def _publish(self, event: dict) -> None:
        for q in list(self._subscribers):
            q.put_nowait(event)

    # ------------------------------------------------------------------
    # ordered byte stream (stream tasks, proxy, object gateway)
    # ------------------------------------------------------------------

    async def read_ordered(self) -> AsyncIterator[bytes]:
        """Yield content bytes in order as pieces become ready."""
        num = 0
        while True:
            async with self._piece_cond:
                while (num not in self.ready
                       and not self.done_event.is_set()):
                    await self._piece_cond.wait()
            if num in self.ready:
                assert self.storage is not None
                data = await run_io(self.storage.read_piece, num)
                yield data
                num += 1
                if self.total_pieces >= 0 and num >= self.total_pieces:
                    return
                continue
            # done without the piece -> task ended
            if self.state == self.FAILED:
                raise DFError(self.fail_code or Code.UNKNOWN,
                              self.fail_message or "task failed")
            if self.total_pieces >= 0 and num >= self.total_pieces:
                return
            if self.total_pieces < 0:
                return
