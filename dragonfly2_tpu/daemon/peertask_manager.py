"""PeerTaskManager: deduplicates conductors per task, serves file/stream
façades, and the reuse fast path.

Role parity: reference ``client/daemon/peer/peertask_manager.go`` +
``peertask_file.go`` / ``peertask_stream.go`` / ``peertask_reuse.go``.
"""

from __future__ import annotations

import asyncio
import logging
from dataclasses import replace
from typing import Any, AsyncIterator

from ..common import ids
from ..common.errors import Code, DFError
from ..common.piece import Range, parse_http_range
from ..idl.messages import (DownloadRequest, DownloadResponse, TaskStat,
                            TaskType, UrlMeta)
from ..storage.manager import StorageManager
from .conductor import PeerTaskConductor
from .piece_manager import PieceManager

log = logging.getLogger("df.core.peertask")


class PeerTaskManager:
    def __init__(self, *, storage_mgr: StorageManager, piece_mgr: PieceManager,
                 hostname: str, host_ip: str, scheduler: Any = None,
                 p2p_engine_factory: Any = None,
                 device_sink_builder: Any = None, is_seed: bool = False,
                 shaper: Any = None, prefetch_whole_file: bool = False,
                 flight_recorder: Any = None, pex: Any = None,
                 relay: Any = None, qos: Any = None):
        self.storage_mgr = storage_mgr
        self.piece_mgr = piece_mgr
        self.hostname = hostname
        self.host_ip = host_ip
        self.scheduler = scheduler
        self.p2p_engine_factory = p2p_engine_factory
        self.device_sink_builder = device_sink_builder
        self.is_seed = is_seed
        self.shaper = shaper
        self.prefetch_whole_file = prefetch_whole_file
        self.flight_recorder = flight_recorder
        self.pex = pex
        self.relay = relay            # RelayHub (None = cut-through off)
        self.qos = qos                # QosGovernor (None = admission off)
        self._conductors: dict[str, PeerTaskConductor] = {}
        self._prefetching: set[str] = set()
        # strong refs: the loop only weak-refs tasks, and a GC'd prefetch
        # would strand its id in _prefetching forever
        self._prefetch_tasks: set[asyncio.Task] = set()
        self._lock = asyncio.Lock()

    # ------------------------------------------------------------------

    def _task_id(self, url: str, meta: UrlMeta) -> str:
        return ids.task_id(
            url, tag=meta.tag, application=meta.application, digest=meta.digest,
            piece_range=meta.range,
            filtered_query_params=list(meta.filtered_query_params or []))

    async def get_or_create_conductor(
            self, url: str, meta: UrlMeta, *,
            task_type: TaskType = TaskType.STANDARD,
            disable_back_source: bool = False,
            device_sink_factory: Any = None,
            ordered: bool = False,
            shard_manifest: Any = None) -> PeerTaskConductor:
        task_id = self._task_id(url, meta)
        content_range: Range | None = None
        requested_shards = None
        if meta.shards:
            from ..common.sharding import parse_shard_names
            requested_shards = parse_shard_names(meta.shards) or None
        existing = await self._join_existing(
            task_id, ordered, requested_shards=requested_shards)
        if existing is not None:
            return existing
        # QoS admission happens OUTSIDE the manager lock: a bulk request
        # riding the brownout queue must never hold the lock critical
        # traffic needs to create ITS conductor (priority inversion by
        # lock). May raise RESOURCE_EXHAUSTED (+retry_after_ms) — the
        # 429-shaped shed the proxy/gateway/rpc surfaces forward.
        from ..idl.messages import resolve_class
        qos_cls = qos_ruling = None
        if self.qos is not None:
            qos_cls, qos_ruling = await self.qos.admit(
                resolve_class(meta.qos_class), meta.tenant)
        # the class stored on the flight is CLAMPED ("" stays classless):
        # it becomes a df_qos_slo_breach_total label via observe_summary,
        # and a raw wire string there would be unbounded client-
        # controlled metric cardinality
        flight_cls = resolve_class(meta.qos_class) if meta.qos_class \
            else ""
        async with self._lock:
            conductor = self._conductors.get(task_id)
            if (conductor is not None
                    and conductor.state != PeerTaskConductor.FAILED):
                # lost the creation race while queued at admission: the
                # winner's admission is the accounted one. A FINISHED or
                # finishing subset conductor that doesn't cover this
                # request falls through to a fresh conductor instead
                # (same task storage; only the gap transfers).
                gap = self._subset_gap(conductor, requested_shards)
                if not gap or (not conductor.done_event.is_set()
                               and conductor.widen_to_whole_file()):
                    if qos_cls is not None:
                        self.qos.release(qos_cls)
                    return conductor
            peer_id = ids.peer_id(self.hostname, self.host_ip,
                                  seed=self.is_seed)
            flight = (self.flight_recorder.begin(
                task_id, peer_id, url=url,
                qos_class=flight_cls, tenant=meta.tenant)
                if self.flight_recorder is not None else None)
            conductor = PeerTaskConductor(
                task_id=task_id, peer_id=peer_id,
                url=url, url_meta=meta, storage_mgr=self.storage_mgr,
                piece_mgr=self.piece_mgr, scheduler=self.scheduler,
                content_range=content_range,
                disable_back_source=disable_back_source, task_type=task_type,
                device_sink_factory=device_sink_factory, ordered=ordered,
                flight=flight, pex=self.pex, relay=self.relay,
                shard_manifest=shard_manifest,
                requested_shards=requested_shards)
            if qos_cls is not None:
                conductor.qos_release = (
                    lambda c=qos_cls: self.qos.release(c))
                if flight is not None:
                    # journal the admission ruling: a bulk task that rode
                    # the brownout queue carries the wait in its journal
                    from . import flight_recorder as fr
                    flight.event(fr.QOS, parent=(
                        "brownout" if qos_ruling == "queued"
                        else self.qos.state))
            if self.p2p_engine_factory is not None:
                conductor.set_p2p_engine(self.p2p_engine_factory())
            if self.shaper is not None:
                conductor.attach_shaper(self.shaper)
            self._conductors[task_id] = conductor
            conductor.start()
            return conductor

    async def _join_existing(self, task_id: str, ordered: bool,
                             requested_shards: list[str] | None = None,
                             ) -> PeerTaskConductor | None:
        """Join a live conductor for this task if one exists (subscribers
        share one download — joining costs no QoS admission; the original
        admission already accounts the work)."""
        async with self._lock:
            conductor = self._conductors.get(task_id)
            if conductor is None \
                    or conductor.state == PeerTaskConductor.FAILED:
                return None
            if ordered and not conductor.ordered:
                # a stream consumer joined a running file task: switch to
                # in-order fetching so read_ordered() doesn't stall
                conductor.ordered = True
                engine = conductor._p2p_engine
                if engine is not None:
                    engine.dispatcher.ordered = True
            if self._subset_gap(conductor, requested_shards):
                # the joiner needs shards (or the whole file) the live
                # subset download would never fetch: widen to the full
                # piece set so its done_event covers both. A FINISHED
                # (or finishing — widen refuses) subset download can't
                # grow: a fresh conductor over the same task storage
                # adopts its pieces (place_from_store) and fetches only
                # the gap.
                if (conductor.done_event.is_set()
                        or not conductor.widen_to_whole_file()):
                    return None
            return conductor

    @staticmethod
    def _subset_gap(conductor: PeerTaskConductor,
                    requested_shards: list[str] | None) -> bool:
        """True when ``conductor`` is a requested-subset download that
        does NOT cover this request's needs (other shards, or the whole
        file)."""
        if conductor.requested_shards is None:
            return False
        if requested_shards is None:
            return True
        return bool(set(requested_shards)
                    - set(conductor.requested_shards))

    def conductor(self, task_id: str) -> PeerTaskConductor | None:
        return self._conductors.get(task_id)

    def _start_prefetch(self, url: str, meta: UrlMeta) -> None:
        """Fire-and-forget whole-file download backing a ranged request."""
        whole = replace(meta, range="")
        task_id = self._task_id(url, whole)
        if (task_id in self._prefetching
                or self.storage_mgr.find_completed_task(task_id) is not None):
            return
        self._prefetching.add(task_id)

        async def run() -> None:
            try:
                conductor = await self.get_or_create_conductor(url, whole)
                await conductor.wait_done()
            except Exception:  # noqa: BLE001 - prefetch is best-effort
                log.exception("whole-file prefetch of %s failed", url)
            finally:
                self._prefetching.discard(task_id)

        t = asyncio.get_running_loop().create_task(run())
        self._prefetch_tasks.add(t)
        t.add_done_callback(self._prefetch_tasks.discard)

    # ------------------------------------------------------------------
    # file task: download -> progress events -> land at output path
    # ------------------------------------------------------------------

    async def start_file_task(
            self, req: DownloadRequest) -> AsyncIterator[DownloadResponse]:
        meta = req.url_meta or UrlMeta()
        task_id = self._task_id(req.url, meta)

        # reuse fast path: completed task (or a whole-file parent covering a
        # ranged request) already on disk
        reuse = self.storage_mgr.find_completed_task(task_id)
        rng: Range | None = None
        if meta.range and reuse is None:
            # ranged request: serve from the whole-file parent when present
            parent_id = ids.parent_task_id(
                req.url, tag=meta.tag, application=meta.application,
                digest=meta.digest,
                filtered_query_params=list(meta.filtered_query_params or []))
            parent = self.storage_mgr.get(parent_id)
            parent_done = (parent is not None
                           and getattr(parent.md, "done", False)
                           and parent.md.content_length >= 0)
            if self.prefetch_whole_file and not parent_done:
                # warm the whole file in the background so later ranged
                # requests are local subtask reads (reference
                # ``client/daemon/peer/peertask_manager.go:262-287``)
                self._start_prefetch(req.url, meta)
            if parent_done:
                total = parent.md.content_length
                try:
                    rng = parse_http_range(meta.range, total)
                except ValueError as exc:
                    raise DFError(Code.INVALID_ARGUMENT, str(exc)) from None
                reuse = self.storage_mgr.find_partial_completed_task(
                    parent_id, rng.start, rng.length)
                if reuse is None:
                    rng = None
        # never plain success without the sink that was asked for
        want_sink = req.device_sink is not None and req.device_sink.enabled
        if want_sink and self.device_sink_builder is None:
            raise DFError(Code.CLIENT_DEVICE_SINK_ERROR,
                          "this daemon has no device sink")
        if want_sink and reuse is not None:
            # no download runs for content already on disk, so nothing
            # would carry a sink (tpu/data.py stages it from storage)
            raise DFError(Code.CLIENT_DEVICE_SINK_ERROR,
                          "content already complete on disk: no download "
                          "to carry the device sink")
        if reuse is not None:
            if req.output:
                await asyncio.to_thread(
                    reuse.store_to, req.output,
                    **({"range_start": rng.start, "range_length": rng.length}
                       if rng else {}))
            length = rng.length if rng else reuse.md.content_length
            yield DownloadResponse(task_id=task_id, peer_id="reused",
                                   completed_length=length,
                                   content_length=length, done=True,
                                   output=req.output)
            return

        device_factory = None
        if want_sink:
            # brings the device runtime up (first time: seconds, off-loop)
            # BEFORE any byte moves, so a host without one fails here
            device_factory = await self.device_sink_builder(req.device_sink)

        conductor = await self.get_or_create_conductor(
            req.url, meta, task_type=req.task_type,
            disable_back_source=req.disable_back_source,
            device_sink_factory=device_factory,
            shard_manifest=req.shard_manifest)
        q = conductor.subscribe()
        try:
            while True:
                timeout = req.timeout_s if req.timeout_s > 0 else None
                try:
                    event = await asyncio.wait_for(q.get(), timeout)
                except asyncio.TimeoutError:
                    raise DFError(Code.DEADLINE_EXCEEDED,
                                  f"download timed out after {req.timeout_s}s") from None
                if event["type"] == "piece":
                    yield DownloadResponse(
                        task_id=conductor.task_id, peer_id=conductor.peer_id,
                        completed_length=event["completed"],
                        content_length=event["total"])
                elif event["type"] == "shard":
                    # sharded tasks: one progress frame per shard that
                    # became ready (all bytes verified) — dfget prints
                    # the per-shard ready timestamps off these
                    yield DownloadResponse(
                        task_id=conductor.task_id, peer_id=conductor.peer_id,
                        completed_length=conductor.completed_length,
                        content_length=conductor.content_length,
                        shard=event["name"], shard_src=event["src"],
                        shards_ready=event["ready"],
                        shards_total=event["total"])
                elif event["type"] == "done":
                    if not event.get("success"):
                        raise DFError(Code(event.get("code") or Code.UNKNOWN),
                                      event.get("message", "download failed"))
                    if want_sink and conductor.device_ingest is None:
                        # joined a download that was started without one
                        raise DFError(Code.CLIENT_DEVICE_SINK_ERROR,
                                      "the download this request joined "
                                      "carries no device sink")
                    if req.output:
                        assert conductor.storage is not None
                        await asyncio.to_thread(conductor.storage.store_to,
                                                req.output)
                    yield DownloadResponse(
                        task_id=conductor.task_id, peer_id=conductor.peer_id,
                        completed_length=conductor.completed_length,
                        content_length=conductor.content_length,
                        done=True, output=req.output)
                    return
        finally:
            conductor.unsubscribe(q)

    # ------------------------------------------------------------------
    # stream task: ordered bytes (proxy / gateway / dfget stdout)
    # ------------------------------------------------------------------

    async def stream_task(self, url: str, meta: UrlMeta | None = None,
                          ) -> tuple[str, AsyncIterator[bytes]]:
        meta = meta or UrlMeta()
        task_id = self._task_id(url, meta)
        reuse = self.storage_mgr.find_completed_task(task_id)
        if reuse is not None:
            async def replay() -> AsyncIterator[bytes]:
                for p in reuse.piece_infos():
                    yield await asyncio.to_thread(reuse.read_piece, p.num)
            return task_id, replay()
        conductor = await self.get_or_create_conductor(url, meta, ordered=True)
        return task_id, conductor.read_ordered()

    # ------------------------------------------------------------------
    # cache ops (dfcache surface)
    # ------------------------------------------------------------------

    async def stat_task(self, task_id: str, *, local_only: bool = True) -> TaskStat:
        ts = self.storage_mgr.get(task_id)
        if ts is None:
            conductor = self._conductors.get(task_id)
            if conductor is None:
                raise DFError(Code.NOT_FOUND, f"task {task_id[:12]} not found")
            return TaskStat(id=task_id, state=conductor.state,
                            content_length=conductor.content_length,
                            total_piece_count=conductor.total_pieces)
        md = ts.md
        return TaskStat(id=task_id, type=md.task_type,
                        content_length=md.content_length,
                        total_piece_count=md.total_piece_count,
                        state="success" if md.success else
                              ("done" if md.done else "running"),
                        has_available_peer=md.done and md.success)

    async def import_file(self, path: str, url: str, meta: UrlMeta | None = None,
                          task_type: TaskType = TaskType.PERSISTENT) -> str:
        meta = meta or UrlMeta()
        task_id = self._task_id(url, meta)
        if self.storage_mgr.find_completed_task(task_id) is not None:
            return task_id
        conductor = PeerTaskConductor(
            task_id=task_id,
            peer_id=ids.peer_id(self.hostname, self.host_ip, seed=self.is_seed),
            url=url, url_meta=meta, storage_mgr=self.storage_mgr,
            piece_mgr=self.piece_mgr, scheduler=None, task_type=task_type)
        self._conductors[task_id] = conductor

        async def run_import():
            try:
                await self.piece_mgr.import_file(conductor, path)
                await conductor._finish_success()
            except DFError as exc:
                await conductor._finish_fail(exc.code, exc.message)
            except Exception as exc:  # noqa: BLE001
                await conductor._finish_fail(Code.UNKNOWN, str(exc))

        # retain + drain (DF002): a fire-and-forget import task is only
        # weakly referenced by the loop — GC could kill it mid-import and
        # wait_done() below would park forever on a conductor nobody is
        # feeding
        import_task = asyncio.get_running_loop().create_task(run_import())
        try:
            ok = await conductor.wait_done()
        except BaseException:
            # caller gone/cancelled: reap the import without letting its
            # CancelledError mask what we're already raising (run_import
            # catches everything else internally)
            import_task.cancel()
            try:
                await import_task
            except asyncio.CancelledError:
                pass
            raise
        try:
            # normal path: wait_done() returns at done_event.set(), but
            # _finish_* may still owe a _piece_cond notify_all — let it
            # run to completion rather than cancelling it mid-finish and
            # stranding piece waiters until their timeouts
            await import_task
        except asyncio.CancelledError:
            import_task.cancel()
            try:
                await import_task
            except asyncio.CancelledError:
                pass
            raise
        if not ok:
            raise DFError(conductor.fail_code, conductor.fail_message)
        return task_id

    async def export_file(self, url: str, output: str,
                          meta: UrlMeta | None = None, *,
                          local_only: bool = False, timeout_s: float = 0.0) -> str:
        meta = meta or UrlMeta()
        task_id = self._task_id(url, meta)
        ts = self.storage_mgr.find_completed_task(task_id)
        if ts is not None:
            await asyncio.to_thread(ts.store_to, output)
            return task_id
        if local_only:
            raise DFError(Code.NOT_FOUND, "task not cached locally")
        req = DownloadRequest(url=url, output=output, url_meta=meta,
                              timeout_s=timeout_s)
        async for _ in self.start_file_task(req):
            pass
        return task_id

    async def delete_task(self, task_id: str) -> bool:
        conductor = self._conductors.pop(task_id, None)
        if conductor is not None and not conductor.done_event.is_set():
            conductor.cancel()
        return self.storage_mgr.delete_task(task_id)

    async def shutdown(self) -> None:
        for conductor in list(self._conductors.values()):
            if not conductor.done_event.is_set():
                conductor.cancel()
