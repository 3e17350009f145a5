"""P2P piece engine: pulls a task's pieces from parent peers.

Role parity: reference ``client/daemon/peer/peertask_conductor.go`` P2P half —
``pullPiecesWithP2P`` (:544), ``receivePeerPacket`` (:659), the 4 piece
workers (:976-1010) — plus ``peertask_piecetask_synchronizer.go`` (one
``SyncPieceTasks`` bidi stream per parent feeding the dispatcher).

``pull`` returns:
  * True  — task completed via P2P (conductor verifies + finalizes)
  * False — fall back to origin (the back-source ladder: NeedBackSource from
    the scheduler, no parents within the schedule timeout, or all parents
    dying without replacement)
and raises DFError for hard failures.
"""

from __future__ import annotations

import asyncio
import logging
import random
import time
from typing import TYPE_CHECKING

from ..common import health
from ..common.bufpool import POOL
from ..common.errors import Code, DFError
from ..common.metrics import BYTES_BUCKETS, REGISTRY
from ..idl.messages import (PeerAddr, PeerPacket, PieceInfo, PieceResult,
                            PieceTaskRequest, SizeScope)
from ..rpc.client import ChannelPool, ServiceClient
from . import flight_recorder as fr
from .piece_dispatcher import ENDGAME_PIECES, Dispatch, PieceDispatcher
from .piece_downloader import PieceDownloader

if TYPE_CHECKING:  # pragma: no cover
    from .conductor import PeerTaskConductor
    from .scheduler_session import PeerSession

log = logging.getLogger("df.flow.engine")

DAEMON_SERVICE = "df.daemon.Daemon"

_p2p_pieces = REGISTRY.counter("df_p2p_piece_total",
                               "pieces fetched from peers", ("result",))
_p2p_piece_bytes = REGISTRY.histogram(
    "df_p2p_piece_bytes", "size of each piece landed from a peer",
    buckets=BYTES_BUCKETS)


class _Synchronizer:
    """One SyncPieceTasks stream against one parent daemon."""

    def __init__(self, engine: "PieceEngine", conductor: "PeerTaskConductor",
                 parent: PeerAddr):
        self.engine = engine
        self.conductor = conductor
        self.parent = parent
        self.task: asyncio.Task | None = None
        self.stream = None              # live SyncPieceTasks stream
        self._seen: set[int] = set()    # piece nums this parent announced

    def start(self) -> None:
        self.task = asyncio.get_running_loop().create_task(self._run())

    def exhausted(self) -> bool:
        """Parent has announced every piece of the task — pinging it cannot
        reveal anything new."""
        total = self.conductor.total_pieces
        return total >= 0 and len(self._seen) >= total

    async def ping(self) -> None:
        """Starvation signal: ask the parent for more work (super-seeding
        parents respond by revealing more pieces; others re-announce)."""
        if self.exhausted():
            return
        stream = self.stream
        if stream is None:
            return
        try:
            await stream.write(PieceTaskRequest(
                task_id=self.conductor.task_id,
                src_peer_id=self.conductor.peer_id,
                dst_peer_id=self.parent.peer_id,
                start_num=0, limit=1 << 20,
                src_slice=self.engine.slice_name))
        except Exception:  # noqa: BLE001 - stream may be closing
            pass

    async def _run(self) -> None:
        addr = f"{self.parent.ip}:{self.parent.rpc_port}"
        try:
            client = self.engine.peer_client(addr)
            stream = client.stream_stream("SyncPieceTasks")
            self.stream = stream
            await stream.write(PieceTaskRequest(
                task_id=self.conductor.task_id,
                src_peer_id=self.conductor.peer_id,
                dst_peer_id=self.parent.peer_id,
                start_num=0, limit=1 << 20,
                src_slice=self.engine.slice_name))
            try:
                while True:
                    packet = await stream.read()
                    if packet is None:
                        break
                    await self._on_packet(packet)
            finally:
                self.stream = None
                stream.cancel()
        except asyncio.CancelledError:
            raise
        except DFError as exc:
            log.debug("sync with %s ended: %s", self.parent.peer_id, exc)
            await self.engine.dispatcher.remove_parent(self.parent.peer_id)
        except Exception as exc:  # noqa: BLE001 - parent went away
            log.debug("sync with %s failed: %s", self.parent.peer_id, exc)
            await self.engine.dispatcher.remove_parent(self.parent.peer_id)

    async def _on_packet(self, packet) -> None:
        if packet.content_length >= 0 and self.conductor.piece_size == 0:
            self.conductor.set_content_info(packet.content_length,
                                            packet.piece_size)
            self.engine.apply_shard_state(self.conductor)
        if self.conductor.piece_size == 0:
            # parent itself doesn't know the geometry yet (unknown-length
            # origin mid-flight): skip — the done-refresh re-announces all
            return
        dst_addr = packet.dst_addr or f"{self.parent.ip}:{self.parent.download_port}"
        if not self.engine._admissible(self.parent.peer_id, dst_addr):
            # locally-shunned address: its announcements must not grow a
            # dispatcher slot, however it got a sync stream
            return
        await self.engine.dispatcher.add_parent(self.parent.peer_id, dst_addr,
                                                is_seed=self.parent.is_seed,
                                                link=self.parent.link)
        for p in packet.piece_infos or []:
            self._seen.add(p.piece_num)
        infos = [p for p in (packet.piece_infos or [])
                 if p.piece_num not in self.conductor.ready]
        if infos:
            # content-store consult BEFORE dispatch: announced pieces whose
            # digests are already on disk (this task's surviving pieces, or
            # any task's under the same digest) are placed locally — the
            # dispatcher never even queues a pull for them
            placed = await self.conductor.place_from_store(infos)
            if placed:
                infos = [p for p in infos if p.piece_num not in placed]
        if infos:
            await self.engine.dispatcher.announce(self.parent.peer_id, infos)

    def stop(self) -> None:
        if self.task is not None:
            self.task.cancel()


class _SpanHandle:
    """Engine-side relay-span lifecycle: called by the downloader with the
    pooled buffer once acquired (registers the in-flight span), retired by
    the engine once the span's pieces have landed — always before the
    buffer returns to the pool. A no-op when the relay plane is off."""

    __slots__ = ("relay", "task_id", "pieces", "span")

    def __init__(self, relay, task_id: str, pieces: list[PieceInfo]):
        self.relay = relay
        self.task_id = task_id
        self.pieces = pieces
        self.span = None

    def __call__(self, buf):
        if self.relay is None:
            return None
        base = self.pieces[0].range_start
        size = sum(p.range_size for p in self.pieces)
        self.span = self.relay.open_span(self.task_id, base, size, buf,
                                         self.pieces)
        return self.span

    def retire(self) -> None:
        if self.span is not None and self.relay is not None:
            self.relay.retire(self.span)
            self.span = None


class PieceEngine:
    def __init__(self, *, parallelism: int = 4,
                 schedule_timeout_s: float = 30.0,
                 piece_timeout_s: float = 60.0,
                 downloader: PieceDownloader | None = None,
                 channel_pool: ChannelPool | None = None,
                 slice_name: str = "",
                 peer_observer=None,
                 relay=None,
                 verdicts=None):
        self.parallelism = parallelism
        self.slice_name = slice_name    # advertised to super-seeding parents
        # PEX membership hook (daemon/pex.py): every parent the scheduler
        # assigns is observed so the gossip plane knows the mesh
        self.peer_observer = peer_observer
        # per-parent verdict ledger (daemon/verdicts.py): typed failure
        # verdicts recorded here; parents the ledger shuns on local
        # corrupt evidence are never admitted to the dispatcher — even
        # when the scheduler (or the PEX rung) keeps offering them
        self.verdicts = verdicts
        # cut-through relay hub (daemon/relay.py): every in-flight span
        # this engine downloads becomes readable by the upload server's
        # streaming range path while its bytes are still arriving
        self.relay = relay
        self.schedule_timeout_s = schedule_timeout_s
        self.piece_timeout_s = piece_timeout_s
        self.downloader = downloader or PieceDownloader(timeout_s=piece_timeout_s)
        self._own_downloader = downloader is None
        # channel pool may be shared daemon-wide so parent connections persist
        self._channels = channel_pool if channel_pool is not None else ChannelPool()
        self._own_channels = channel_pool is None
        self.dispatcher = PieceDispatcher()
        self._busy_s = 0.0          # summed over workers, in _download_one
        self._synchronizers: dict[str, _Synchronizer] = {}
        self._current_parents: dict[str, PeerAddr] = {}  # latest assignment
        self._need_back_source = False
        self._first_parent = asyncio.Event()
        self._last_ping = 0.0
        # starvation-ping pacing: per-engine jittered base so a fan-out's
        # children never ping in phase, exponential while pings produce no
        # new announcements (a struggling swarm must not spend its one core
        # on 100s of control messages/s — the r04 16-leecher convoy),
        # reset to base on progress
        self._ping_base = 0.1 * random.uniform(0.9, 1.5)
        self._ping_interval = self._ping_base
        self._announced_at_ping = -1
        self._shards_applied = False

    def peer_client(self, addr: str) -> ServiceClient:
        return ServiceClient(self._channels.get(addr), DAEMON_SERVICE)

    def _relay_opener(self, conductor, pieces: list[PieceInfo]) -> _SpanHandle:
        return _SpanHandle(self.relay, conductor.task_id, pieces)

    def apply_shard_state(self, conductor) -> None:
        """Push the conductor's sharded-task piece classes into the
        dispatcher once geometry is known: the needed subset (pieces
        outside it are never dispatched) and the swap-class set (held
        off seed parents for the bounded swap window so co-located
        replicas supply them over ICI-near P2P). Idempotent; re-applied
        on widen (a joiner requesting other shards)."""
        if conductor.shard_tracker is None or conductor.piece_size <= 0:
            return
        if self._shards_applied \
                and self.dispatcher.needed == conductor.needed_pieces \
                and self.dispatcher.swap_nums == conductor.swap_piece_nums:
            return
        self._shards_applied = True
        self.dispatcher.set_shard_state(conductor.needed_pieces,
                                        conductor.swap_piece_nums)

    # ------------------------------------------------------------------

    async def pull(self, conductor: "PeerTaskConductor",
                   session: "PeerSession") -> bool:
        self.dispatcher.ordered = conductor.ordered
        result = session.result
        try:
            if result.size_scope == SizeScope.EMPTY:
                conductor.set_content_info(0)
                return True
            if result.size_scope == SizeScope.TINY and result.direct_content:
                data = result.direct_content
                conductor.set_content_info(len(data))
                await conductor.on_piece_from_peer(0, 0, data, 0, "scheduler")
                return True
            if (result.size_scope == SizeScope.SMALL
                    and result.single_piece is not None
                    and result.single_piece.piece_info is not None):
                # a single-piece task has no workers: this call is its one
                # download, and counts as their busy seconds
                t_busy = time.monotonic()
                try:
                    ok = await self._pull_single(conductor, session,
                                                 result.single_piece)
                finally:
                    self._busy_s += time.monotonic() - t_busy
                if ok:
                    return True
                # fall through to the normal path: scheduler may still help
            return await self._pull_normal(conductor, session)
        finally:
            self._journal_workers(conductor.flight)
            await self._teardown()

    def _journal_workers(self, flight) -> None:
        """What the piece workers did with their time, into the flight
        journal before its ``done``: the dispatcher's wait buckets (parked
        with nothing to fetch, by why) against the seconds inside
        ``_download_one``. Starved workers are the protocol's doing; busy
        ones on a slow task are the loop's."""
        if flight is None:
            return
        for bucket, secs in self.dispatcher.wait_stats.items():
            if secs > 0:
                flight.event(fr.WORKER_WAIT, parent=bucket,
                             dur_ms=secs * 1000.0)
        flight.event(fr.WORKER_BUSY, dur_ms=self._busy_s * 1000.0)

    @staticmethod
    def _journal_wire(flight, num: int, parent_id: str, nbytes: int,
                      meta: dict) -> None:
        """One dispatch's ``wire_copy``, and its reads and direct bytes:
        what ``_read_body`` left in the meta dict that rode the download."""
        if flight is not None:
            flight.event(fr.WIRE_COPY, num, parent_id, nbytes,
                         dur_ms=meta.get("copy_s", 0.0) * 1000.0)
            flight.wire_chunks += meta.get("chunks", 0)
            flight.wire_direct_bytes += meta.get("direct", 0)

    async def _pull_single(self, conductor, session, single) -> bool:
        info: PieceInfo = single.piece_info
        if session.result.content_length >= 0:
            conductor.set_content_info(session.result.content_length,
                                       session.result.piece_size)
        else:
            conductor.set_content_info(info.range_size)
        t0 = int(time.time() * 1000)
        flight = conductor.flight
        on_first = None
        if flight is not None:
            flight.event(fr.DISPATCHED, info.piece_num, single.dst_peer_id)

            def on_first(_num=info.piece_num, _pid=single.dst_peer_id):
                flight.event(fr.FIRST_BYTE, _num, _pid)
        span = self._relay_opener(conductor, [info])
        try:
            with health.PLANE.watchdog.section(
                    "piece.wire", health.PLANE.slo.section_deadline_s(),
                    stage="wire"):
                wire_meta: dict = {}
                data, cost = await self.downloader.download_piece(
                    dst_addr=single.dst_addr, task_id=conductor.task_id,
                    src_peer_id=conductor.peer_id, piece=info,
                    on_first_byte=on_first, relay_open=span,
                    qos_class=getattr(conductor, "qos_class", ""),
                    meta=wire_meta)
        except DFError as exc:
            _p2p_pieces.labels("fail").inc()
            # backpressure is not a failure VERDICT (parity with the
            # span path's requeue-without-strike): a busy 503 earns no
            # typed code, no flight failure event, no ledger entry
            busy = exc.code == Code.CLIENT_PEER_BUSY
            fcode = "" if busy else self._fail_code(exc)
            if not busy:
                self._note_fail(conductor, info, single.dst_peer_id,
                                single.dst_addr, fcode)
            await session.report_piece(self._piece_result(
                conductor, info, single.dst_peer_id, t0, ok=False,
                code=exc.code, fail_code=fcode))
            return False
        self._journal_wire(flight, info.piece_num, single.dst_peer_id,
                           info.range_size, wire_meta)
        t_wire = flight.now_ms() if flight is not None else 0.0
        try:
            placed, corrupt, raced = await conductor.on_span_from_peer(
                single.dst_peer_id, [info], data, cost)
        finally:
            # retire BEFORE the pool release: a relay reader must never
            # copy from a recycled buffer (landed bytes serve from disk)
            span.retire()
            POOL.release(data)
        if corrupt:
            self._note_corrupt(conductor, info, single.dst_peer_id,
                               addr=single.dst_addr,
                               relayed=wire_meta.get("relayed", False))
            await session.report_piece(self._piece_result(
                conductor, info, single.dst_peer_id, t0, ok=False,
                code=Code.CLIENT_DIGEST_MISMATCH, fail_code="corrupt",
                relayed=wire_meta.get("relayed", False)))
            return False
        if raced:
            # an endgame racer is mid-landing: its outcome is unknown, so
            # report NOTHING for this piece — the racer's own path settles
            # it (reporting ok here would orphan the piece if the racer's
            # copy fails verification)
            return True
        if flight is not None and placed:
            flight.event(fr.WIRE_DONE, info.piece_num, single.dst_peer_id,
                         info.range_size, dur_ms=cost, t_ms=t_wire)
        if placed:
            _p2p_piece_bytes.observe(info.range_size)
        _p2p_pieces.labels("ok").inc()
        if self.verdicts is not None:
            self.verdicts.record_ok(single.dst_addr)
        await session.report_piece(self._piece_result(
            conductor, info, single.dst_peer_id, t0, ok=True, cost_ms=cost))
        return True

    def _note_corrupt(self, conductor, info: PieceInfo, parent_id: str,
                      addr: str = "", relayed: bool = False) -> bool:
        """A transfer failed digest verification at landing: count it
        (df_p2p_piece_total{result="corrupt"}), journal a flight event
        so dfdiag can name the corrupting parent, and record the hard
        verdict in the daemon-wide ledger — enough decayed corrupt
        verdicts locally shun the address for EVERY task on this daemon
        (scheduler reachable or not), journaled as a ``quarantine``
        flight event at the flip."""
        _p2p_pieces.labels("corrupt").inc()
        log.warning("piece %d from %s: digest mismatch (requeued)",
                    info.piece_num, parent_id[-12:])
        if conductor.flight is not None:
            conductor.flight.event(fr.CORRUPT, info.piece_num, parent_id,
                                   info.range_size)
        if self.verdicts is not None and addr:
            flipped = self.verdicts.record(addr, "corrupt",
                                           peer_id=parent_id,
                                           relayed=relayed)
            if flipped and conductor.flight is not None:
                conductor.flight.event(fr.QUARANTINE, info.piece_num, addr)
            return flipped
        return False

    @staticmethod
    def _fail_code(exc: DFError) -> str:
        """Typed verdict for a failed fetch (idl.FAIL_CODES): the
        downloader classifies transport failures at the raise site;
        digest mismatches are corrupt by definition."""
        code = getattr(exc, "fail_code", "")
        if code:
            return code
        return "corrupt" if exc.code == Code.CLIENT_DIGEST_MISMATCH \
            else "stall"

    _FAIL_EVENTS = {"stall": fr.STALL, "timeout": fr.TIMEOUT,
                    "refused": fr.REFUSED}

    def _note_fail(self, conductor, info: PieceInfo, parent_id: str,
                   addr: str, code: str) -> None:
        """Journal + ledger one NON-corrupt typed failure (corrupt goes
        through _note_corrupt): soft evidence — the ledger decays it for
        ordering, never shuns on it."""
        if conductor.flight is not None:
            kind = self._FAIL_EVENTS.get(code)
            if kind is not None:
                conductor.flight.event(kind, info.piece_num, parent_id)
        if self.verdicts is not None and addr and code != "corrupt":
            self.verdicts.record(addr, code, peer_id=parent_id)

    def _admissible(self, parent_id: str, addr: str) -> bool:
        """Parent admission gate: a locally-shunned address is refused a
        dispatcher slot no matter who offers it (scheduler packet, sync
        announcement, PEX rung) — the round trip of pulling, verifying,
        and requeuing a poisoned piece is exactly the waste the ledger
        exists to stop."""
        if self.verdicts is None or not self.verdicts.shunned(addr):
            return True
        log.info("refusing shunned parent %s (%s): local corrupt "
                 "verdicts", parent_id[-12:], addr)
        return False

    async def _pull_normal(self, conductor, session) -> bool:
        if session.result.content_length >= 0:
            conductor.set_content_info(session.result.content_length,
                                       session.result.piece_size)
        self.apply_shard_state(conductor)

        packet_task = asyncio.get_running_loop().create_task(
            self._consume_packets(conductor, session))
        workers = [asyncio.get_running_loop().create_task(
            self._worker(conductor, session)) for _ in range(self.parallelism)]
        try:
            # first gate: a parent must show up within the schedule timeout
            try:
                await asyncio.wait_for(self._first_parent.wait(),
                                       self.schedule_timeout_s)
            except asyncio.TimeoutError:
                log.info("no parents within %.1fs; back-source",
                         self.schedule_timeout_s)
                return False
            if self._need_back_source:
                return False

            # sessions without a scheduler behind them (the pex rung's
            # synthetic session, rescuable=False) must self-abort when the
            # swarm stops producing: with live-but-incomplete parents no
            # packet, verdict, or re-assignment is ever coming, so a stall
            # would otherwise tick forever (and a seed stuck here while
            # its leechers wait on IT is a pod-wide deadlock)
            rescuable = getattr(session, "rescuable", True)
            last_ready = len(conductor.ready)
            last_progress = time.monotonic()

            while True:
                if self._need_back_source:
                    return False
                if (conductor.total_pieces >= 0
                        and conductor.pieces_remaining() == 0):
                    # done = every NEEDED piece landed (the requested-shard
                    # subset for sharded tasks, all pieces otherwise). The
                    # commit flag is set in the SAME synchronous block as
                    # the coverage check: a widen (also loop-synchronous)
                    # either ran before it — and this check then saw the
                    # widened needed set and kept pulling — or is refused
                    # after it, so a completing subset can never be
                    # widened into "incomplete"
                    conductor._finishing = True
                    return True
                if not rescuable:
                    if len(conductor.ready) != last_ready:
                        last_ready = len(conductor.ready)
                        last_progress = time.monotonic()
                    elif (time.monotonic() - last_progress
                            > self.schedule_timeout_s):
                        log.info("scheduler-less pull stalled %.1fs at "
                                 "%d/%d pieces; returning to the ladder",
                                 self.schedule_timeout_s, last_ready,
                                 conductor.total_pieces)
                        return False
                # endgame gate: duplicate-request racing only for the task's
                # actual tail (see dispatcher._pick_endgame)
                remaining = conductor.pieces_remaining()
                self.dispatcher.endgame = (0 <= remaining <= ENDGAME_PIECES)
                if not self.dispatcher.has_live_parent():
                    # parents gone: give the scheduler a grace period to
                    # re-assign, then fall back to origin — the reschedule
                    # rung journals that this task is riding out an outage
                    if conductor.flight is not None:
                        conductor.flight.rung(fr.RUNG_RESCHEDULE)
                    try:
                        await asyncio.wait_for(
                            self._wait_parent_change(),
                            self.schedule_timeout_s)
                    except asyncio.TimeoutError:
                        log.info("parents exhausted; back-source for the rest")
                        return False
                    if conductor.flight is not None:
                        conductor.flight.rung(fr.RUNG_P2P)
                    continue
                # progress tick: piece arrivals notify the conductor's cond.
                # The acquire and the wait live in ONE wrapped coroutine so
                # wait_for's cancellation unwinds them atomically — a bare
                # wait_for(cond.wait(), t) splits them across tasks, and the
                # orphaned waiter can die holding the condition lock (the
                # same 3.10 hazard documented at the teardown below)
                try:
                    await asyncio.wait_for(self._piece_tick(conductor), 0.25)
                except asyncio.TimeoutError:
                    pass
        finally:
            # close the dispatcher BEFORE cancelling the workers, not just
            # before gathering them. Two distinct 3.10 asyncio hazards meet
            # here:
            #   * a cancel delivered in the same loop tick as a cond notify
            #     (the last piece's report) is swallowed by asyncio.wait_for
            #     (lost-cancellation), and the unbounded gather below then
            #     waits forever on an undead worker — with the dispatcher
            #     closed, such a worker's next get() returns None and it
            #     exits via the closed path;
            #   * cancelling a worker PARKED in get()'s wait_for(cond.wait)
            #     orphans the inner Condition.wait task, which re-acquires
            #     the condition lock in its finally and can die HOLDING it —
            #     a close() issued after that cancel then queues on the
            #     poisoned lock forever (the fake-pod silent-hang: conductor
            #     stuck in dispatcher.close, zero log output). Closing first
            #     lets close() take the lock while it is still healthy;
            #     workers then wake via the notify and exit cleanly, and the
            #     dispatcher's closed short-circuits keep any late caller
            #     off the lock entirely.
            await self.dispatcher.close()
            packet_task.cancel()
            for w in workers:
                w.cancel()
            await asyncio.gather(packet_task, *workers, return_exceptions=True)

    @staticmethod
    async def _piece_tick(conductor) -> None:
        async with conductor._piece_cond:
            await conductor._piece_cond.wait()

    async def _wait_parent_change(self) -> None:
        cond = self.dispatcher._cond
        async with cond:
            while (not self.dispatcher.has_live_parent()
                   and not self._need_back_source):
                await cond.wait()

    # ------------------------------------------------------------------

    async def _consume_packets(self, conductor, session) -> None:
        """Apply scheduler parent assignments as they arrive."""
        while True:
            packet: PeerPacket = await session.packets.get()
            code = Code(packet.code or 0)
            if code == Code.SCHED_NEED_BACK_SOURCE:
                self._need_back_source = True
                self._first_parent.set()
                async with self.dispatcher._cond:
                    self.dispatcher._cond.notify_all()
                return
            if code in (Code.SCHED_PEER_GONE, Code.SCHED_REREGISTER,
                        Code.SCHED_TASK_STATUS_ERROR, Code.UNAVAILABLE):
                # stream ended or scheduler lost us; workers drain what they
                # have, the main loop decides on fallback
                self._first_parent.set()
                continue
            parents = list(packet.candidate_peers or [])
            if packet.main_peer is not None:
                parents.insert(0, packet.main_peer)
            for parent in parents:
                if parent.peer_id == conductor.peer_id:
                    continue
                dl_addr = f"{parent.ip}:{parent.download_port}"
                if not self._admissible(parent.peer_id, dl_addr):
                    continue
                await self.dispatcher.add_parent(parent.peer_id, dl_addr,
                                                 resurrect=True,
                                                 is_seed=parent.is_seed,
                                                 link=parent.link)
                self._current_parents[parent.peer_id] = parent
                if self.peer_observer is not None:
                    self.peer_observer(parent)
                sync = self._synchronizers.get(parent.peer_id)
                if sync is None or (sync.task is not None and sync.task.done()):
                    sync = _Synchronizer(self, conductor, parent)
                    self._synchronizers[parent.peer_id] = sync
                    sync.start()
            if parents and not packet.advisory:
                # the packet is the scheduler's CURRENT parent assignment —
                # dropped parents release their upload slot server-side, so
                # continuing to pull from them would overload hosts the
                # scheduler is actively shedding (the round-robin that keeps
                # a loaded seed from serving every child rides on this).
                # Advisory packets (PEX swarm pre-population) skip the
                # prune: they add opportunistic parents without overriding
                # the scheduler's assignment.
                assigned = {p.peer_id for p in parents}
                for peer_id in list(self._synchronizers):
                    if peer_id not in assigned:
                        self._synchronizers.pop(peer_id).stop()
                        self._current_parents.pop(peer_id, None)
                        await self.dispatcher.remove_parent(peer_id)
            if parents:
                self._first_parent.set()

    async def _worker(self, conductor, session) -> None:
        while True:
            d = await self.dispatcher.get(timeout=0.1)
            if d is None:
                if self.dispatcher.closed:
                    return
                # idle worker with nothing dispatchable: pull-signal the
                # parents (super-seeding seeds ration announcements and
                # grow them on starvation pings — see rpcserver._SuperSeed)
                await self._maybe_ping()
                continue
            t_busy = time.monotonic()
            try:
                await self._download_one(conductor, session, d)
            finally:
                self._busy_s += time.monotonic() - t_busy

    async def _maybe_ping(self) -> None:
        if not self.dispatcher.starving():
            return
        now = time.monotonic()
        if now - self._last_ping < self._ping_interval:
            return
        self._last_ping = now
        announced = sum(p.announced
                        for p in self.dispatcher.parents.values())
        if announced > self._announced_at_ping:
            self._ping_interval = self._ping_base      # progress: re-arm
        else:
            self._ping_interval = min(self._ping_interval * 1.7, 1.2)
        self._announced_at_ping = announced
        for sync in list(self._synchronizers.values()):
            await sync.ping()
        # resurrect dead sync streams for parents the scheduler still
        # assigns us: a stream that failed at setup (connect refused under a
        # load spike) otherwise stays dead until the scheduler pushes a NEW
        # packet — and the sticky refresh only pushes on set-change, so a
        # stable assignment means no retry ever. This divergence is the
        # 100%-seed-sourced straggler: a child that lost its mesh at t=0 and
        # never got it back. Paced by the starvation gate above.
        for peer_id, parent in list(self._current_parents.items()):
            sync = self._synchronizers.get(peer_id)
            if sync is not None and sync.task is not None and sync.task.done():
                if not self._admissible(
                        peer_id, f"{parent.ip}:{parent.download_port}"):
                    continue
                if self.dispatcher.hard_removed(peer_id):
                    # lifetime fail cap: stays dead until the SCHEDULER
                    # re-offers it in a packet (its blocklists are the
                    # authority); auto-resurrecting here would loop a child
                    # against a corrupt parent forever
                    continue
                # the stream's failure path marked the parent removed in the
                # dispatcher — this is an explicit assignment-backed retry
                await self.dispatcher.add_parent(
                    peer_id, f"{parent.ip}:{parent.download_port}",
                    resurrect=True, is_seed=parent.is_seed,
                    link=parent.link)
                fresh = _Synchronizer(self, sync.conductor, parent)
                self._synchronizers[peer_id] = fresh
                fresh.start()

    async def _download_one(self, conductor, session, d: Dispatch) -> None:
        if conductor.swap_piece_nums and d.parent.is_seed:
            # a swap-class piece (a co-located replica's tree assignment)
            # riding the SEED: its swap hold expired — the partner died or
            # stalled and the tree is covering the hole (journaled so
            # dfdiag can tell this from a healthy swap)
            for info in d.pieces:
                if info.piece_num in conductor.swap_piece_nums:
                    conductor.note_shard_fallback(info.piece_num,
                                                  d.parent.peer_id)
        flight = conductor.flight
        if flight is not None:
            # worker pickup: queue_ms then measures the rate-limiter wait;
            # parent-side queueing lands in ttfb_ms (dispatched->first_byte)
            for info in d.pieces:
                flight.event(fr.SCHEDULED, info.piece_num, d.parent.peer_id)
        if conductor.rate_limiter is not None:
            await conductor.rate_limiter.acquire(d.size())
        t0 = int(time.time() * 1000)
        on_first = None
        if flight is not None:
            for info in d.pieces:
                flight.event(fr.DISPATCHED, info.piece_num, d.parent.peer_id)

            def on_first(_num=d.piece.piece_num, _pid=d.parent.peer_id):
                flight.event(fr.FIRST_BYTE, _num, _pid)
        from ..common import tracing
        try:
            with tracing.span("piece.download",
                              piece=d.piece.piece_num,
                              n_pieces=len(d.pieces),
                              parent=None,   # inherit the task span
                              ) as psp:
                psp.set(dst=d.parent.peer_id[-16:], link=int(d.parent.link))
                # watchdog section: a parent that wedges mid-transfer
                # self-reports (await-chain dump + SLO wire breach) well
                # before the hard per-piece deadline cancels the read
                # (no-op context while the plane is off); the deadline
                # scales with the group so healthy spans don't trip it
                with health.PLANE.watchdog.section(
                        "piece.wire",
                        health.PLANE.slo.section_deadline_s(len(d.pieces)),
                        stage="wire"):
                    span = self._relay_opener(conductor, d.pieces)
                    wire_meta: dict = {}
                    buf, cost = await self.downloader.download_span(
                        dst_addr=d.parent.addr, task_id=conductor.task_id,
                        src_peer_id=conductor.peer_id, pieces=d.pieces,
                        on_first_byte=on_first, relay_open=span,
                        qos_class=getattr(conductor, "qos_class", ""),
                        meta=wire_meta)
        except DFError as exc:
            if exc.code == Code.CLIENT_PEER_BUSY:
                # backpressure, not failure: requeue; no scheduler report
                # (a busy seed must not land on the blocklist)
                _p2p_pieces.labels("busy").inc()
                await self.dispatcher.report_busy(
                    d, retry_after_ms=getattr(exc, "retry_after_ms", 0))
                return
            _p2p_pieces.labels("fail").inc()
            log.debug("pieces %s from %s failed: %s",
                      [p.piece_num for p in d.pieces],
                      d.parent.peer_id[-12:], exc)
            fcode = self._fail_code(exc)
            # one transfer, one typed verdict (however many pieces rode
            # it) — per-piece ledger strikes would triple-count a single
            # dead connection
            self._note_fail(conductor, d.piece, d.parent.peer_id,
                            d.parent.addr, fcode)
            await self.dispatcher.report(d, ok=False)
            if d.parent.removed:
                # permanently removed (hard fail cap): its sync stream dies
                # too, or a dead parent keeps the engine looking alive
                # forever. Cooldown ejections keep their stream — the parent
                # keeps announcing and gets retried when the window expires.
                sync = self._synchronizers.get(d.parent.peer_id)
                if sync is not None:
                    sync.stop()
            for info in d.pieces:   # every group member failed, report each
                await session.report_piece(self._piece_result(
                    conductor, info, d.parent.peer_id, t0, ok=False,
                    code=exc.code, fail_code=fcode))
            return
        per_piece_cost = max(1, cost // len(d.pieces))
        self._journal_wire(flight, d.piece.piece_num, d.parent.peer_id,
                           d.size(), wire_meta)
        # timestamp before the landing await, journaled only for pieces
        # that actually land — an endgame duplicate must not overwrite the
        # real deliverer's attribution
        t_wire = flight.now_ms() if flight is not None else 0.0
        try:
            # ONE landing hop for the whole span (storage write + verify
            # and the HBM staging copy, fused off-loop) — pre-PR5 this was one
            # to_thread + one hash pass + one write PER piece
            placed, corrupt, raced = await conductor.on_span_from_peer(
                d.parent.peer_id, d.pieces, buf, per_piece_cost)
        finally:
            # landing (with the sink's staging copy, made inside it on the
            # storage thread) has completed: the buffer is recyclable —
            # this kills the 4-16 MiB
            # alloc/free churn per download at fan-out. The relay span is
            # retired FIRST: its bytes now serve from storage (or, if a
            # piece failed verification, stop being servable at all)
            span.retire()
            POOL.release(buf)
        placed_set, corrupt_set = set(placed), set(corrupt)
        raced_set = set(raced)
        shun_flipped = False
        for info in d.pieces:
            if info.piece_num in corrupt_set:
                shun_flipped |= self._note_corrupt(
                    conductor, info, d.parent.peer_id, addr=d.parent.addr,
                    relayed=wire_meta.get("relayed", False))
                await session.report_piece(self._piece_result(
                    conductor, info, d.parent.peer_id, t0, ok=False,
                    code=Code.CLIENT_DIGEST_MISMATCH, fail_code="corrupt",
                    relayed=wire_meta.get("relayed", False)))
                continue
            if info.piece_num in raced_set:
                # an endgame racer is mid-landing: outcome unknown — say
                # nothing; the racer's own report settles the piece
                continue
            if info.piece_num in placed_set:
                if flight is not None:
                    flight.event(fr.WIRE_DONE, info.piece_num,
                                 d.parent.peer_id, info.range_size,
                                 dur_ms=per_piece_cost, t_ms=t_wire)
                _p2p_piece_bytes.observe(info.range_size)
            _p2p_pieces.labels("ok").inc()
            if self.verdicts is not None:
                self.verdicts.record_ok(d.parent.addr)
            await session.report_piece(self._piece_result(
                conductor, info, d.parent.peer_id, t0, ok=True,
                cost_ms=per_piece_cost, finished=len(conductor.ready)))
        if shun_flipped:
            # the ledger just shunned this address on local corrupt
            # evidence: sever it for THIS task immediately (permanent
            # removal + dead sync stream) — the admission gate keeps it
            # out of every later task, and the scheduler's pod-wide
            # quarantine follows from the corrupt reports above
            await self.dispatcher.remove_parent(d.parent.peer_id)
            sync = self._synchronizers.get(d.parent.peer_id)
            if sync is not None:
                sync.stop()
        await self.dispatcher.report(
            d, ok=True, cost_ms=cost,
            # a raced piece must NOT be marked done (the racer may yet
            # fail verification — it would be orphaned forever); leaving
            # it out requeues it, and the winner's report retires it
            completed=[info.piece_num for info in d.pieces
                       if info.piece_num not in corrupt_set
                       and info.piece_num not in raced_set])

    @staticmethod
    def _piece_result(conductor, info: PieceInfo, parent_id: str, t0: int, *,
                      ok: bool, cost_ms: int = 0, code: Code = Code.OK,
                      finished: int = 0, fail_code: str = "",
                      relayed: bool = False) -> PieceResult:
        reported = PieceInfo(piece_num=info.piece_num,
                             range_start=info.range_start,
                             range_size=info.range_size, digest=info.digest,
                             download_cost_ms=cost_ms)
        return PieceResult(
            task_id=conductor.task_id, src_peer_id=conductor.peer_id,
            dst_peer_id=parent_id, piece_info=reported, begin_ms=t0,
            end_ms=t0 + cost_ms, success=ok, code=int(code),
            fail_code=fail_code, relayed=relayed, finished_count=finished)

    # ------------------------------------------------------------------

    async def _teardown(self) -> None:
        for sync in self._synchronizers.values():
            sync.stop()
        await asyncio.gather(
            *(s.task for s in self._synchronizers.values() if s.task),
            return_exceptions=True)
        await self.dispatcher.close()
        if self._own_channels:
            await self._channels.close()
        if self._own_downloader:
            await self.downloader.close()
