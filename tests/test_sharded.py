"""Sharded-checkpoint delivery (ROADMAP item 3): shard math + tracker
units, the scheduler's disjoint shard-affinity arm, the dispatcher's
swap hold, flight/diag/podscope surfaces, and real-daemon e2e — shards
become ready arrays incrementally (first ``shard_ready`` precedes the
task's last wire event), a requested subset pulls only its pieces, the
whole-file path through the new code stays byte-identical, and killing
the sole holder of the swap shards degrades to a journaled tree re-pull
with zero wedged tasks."""

import asyncio
import os
import sys
import time

import pytest

from dragonfly2_tpu.common import faultgate
from dragonfly2_tpu.common.sharding import (ShardTracker, parse_shard_names,
                                            pieces_for_shards,
                                            split_affinity,
                                            validate_manifest)
from dragonfly2_tpu.idl.messages import ShardInfo, ShardManifest

sys.path.insert(0, os.path.dirname(__file__))
from test_daemon_e2e import daemon_config, start_origin  # noqa: E402
from test_scheduler import leecher_config  # noqa: E402


@pytest.fixture(autouse=True)
def _disarm():
    faultgate.reset()
    yield
    faultgate.reset()


def mk(name, start, size, **kw):
    return ShardInfo(name=name, range_start=start, range_size=size, **kw)


# ----------------------------------------------------------------------
# common/sharding.py: manifest math
# ----------------------------------------------------------------------

class TestShardMath:
    def test_parse_shard_names(self):
        assert parse_shard_names("a, b ,c,a,") == ["a", "b", "c"]
        assert parse_shard_names("") == []

    def test_validate_rejects_malformed(self):
        with pytest.raises(ValueError, match="duplicate"):
            validate_manifest([mk("a", 0, 4), mk("a", 4, 4)])
        with pytest.raises(ValueError, match="overlap"):
            validate_manifest([mk("a", 0, 8), mk("b", 4, 8)])
        with pytest.raises(ValueError, match="beyond"):
            validate_manifest([mk("a", 0, 8)], content_length=4)
        with pytest.raises(ValueError, match="size"):
            validate_manifest([mk("a", 0, 0)])
        with pytest.raises(ValueError, match="empty name"):
            validate_manifest([mk("", 0, 4)])
        # gaps are legal: a manifest may name only the tensors worth
        # landing
        validate_manifest([mk("a", 0, 4), mk("b", 100, 4)],
                          content_length=104)

    def test_a_placement_is_a_chips_ordinal_or_minus_one(self):
        assert mk("a", 0, 4).device == -1    # unplaced unless it says
        validate_manifest([mk("a", 0, 4, device=-1), mk("b", 4, 4, device=0),
                           mk("c", 8, 4, device=3)])
        with pytest.raises(ValueError, match="device -2"):
            validate_manifest([mk("a", 0, 4, device=-2)])

    def test_the_placement_rides_the_wire_and_dfgets_manifest_file(
            self, tmp_path):
        import json

        from dragonfly2_tpu.idl import base
        from dragonfly2_tpu.idl.messages import (DownloadRequest,
                                                 ShardManifest)
        from dragonfly2_tpu.tools.dfget import _load_shard_manifest

        req = DownloadRequest(url="u", shard_manifest=ShardManifest(
            shards=[mk("a", 0, 4, device=2), mk("b", 4, 4)]))
        got = base.loads(base.dumps(req)).shard_manifest.shards
        assert [(s.name, s.device) for s in got] == [("a", 2), ("b", -1)]
        # a sender from before the field: unplaced
        raw = base.encode(req)
        del raw["shard_manifest"]["shards"][0]["device"]
        assert base.decode(raw).shard_manifest.shards[0].device == -1
        path = tmp_path / "m.json"
        path.write_text(json.dumps({"shards": [
            {"name": "a", "range_start": 0, "range_size": 4, "device": 3},
            {"name": "b", "range_start": 4, "range_size": 4}]}))
        assert [s.device for s in
                _load_shard_manifest(str(path)).shards] == [3, -1]

    def test_pieces_for_shards_boundary_mid_piece(self):
        # piece size 4: shard b straddles pieces 1 and 2 — both claimed
        shards = [mk("b", 6, 4)]
        assert pieces_for_shards(shards, 4, 4) == {1, 2}
        # exactly aligned claims exactly its pieces
        assert pieces_for_shards([mk("a", 4, 4)], 4, 4) == {1}
        # tail clamp: a shard past the last piece never claims phantoms
        assert pieces_for_shards([mk("t", 6, 100)], 4, 3) == {1, 2}

    def test_split_affinity_disjoint_balanced_stable(self):
        names = [f"s{i}" for i in range(16)]
        split = split_affinity(names, ["h1", "h2", "h3"])
        assert set(split) == set(names)
        assert set(split.values()) <= {"h1", "h2", "h3"}
        # deterministic: any party computes the identical split,
        # whatever order it holds the inputs in
        assert split == split_affinity(names, ["h3", "h1", "h2"])
        assert split == split_affinity(list(reversed(names)),
                                       ["h1", "h2", "h3"])
        # BALANCED: bounded-load caps every member at ceil(16/3) = 6 —
        # the small-sample rendezvous skew (all shards on one replica)
        # is structurally impossible
        from collections import Counter
        assert max(Counter(split.values()).values()) <= 6
        two = Counter(split_affinity([f"s{i}" for i in range(6)],
                                     ["da-127.0.0.1",
                                      "db-127.0.0.1"]).values())
        assert set(two.values()) == {3}
        # bounded movement: dropping one member re-homes its shards and
        # moves at most a cap's worth of the survivors'
        smaller = split_affinity(names, ["h1", "h3"])
        moved = sum(1 for n in names
                    if split[n] != "h2" and smaller[n] != split[n])
        assert moved <= 6
        assert split_affinity(names, []) == {}


class TestShardTracker:
    SHARDS = [mk("a", 0, 10), mk("b", 10, 6), mk("c", 20, 4)]  # gap 16-20

    def test_out_of_order_and_duplicate_spans(self):
        tr = ShardTracker(self.SHARDS)
        assert tr.on_span(5, 10, 1.0) == []      # tail of a first
        assert tr.on_span(5, 10, 1.5) == []      # duplicate: no change
        assert tr.on_span(0, 5, 2.0) == ["a"]    # head completes it
        assert tr.on_span(0, 10, 3.0) == []      # re-landing a ready shard
        assert tr.ready == {"a": 2.0}
        assert tr.pending() == ["b", "c"]

    def test_boundary_span_completes_two_shards(self):
        tr = ShardTracker(self.SHARDS)
        assert tr.on_span(0, 8, 1.0) == []
        # one span covering a's tail AND all of b: both complete at once
        assert tr.on_span(8, 16, 2.0) == ["a", "b"]

    def test_gap_bytes_never_complete_anything(self):
        tr = ShardTracker(self.SHARDS)
        assert tr.on_span(16, 20, 1.0) == []     # the unnamed gap
        assert tr.on_span(20, 24, 2.0) == ["c"]

    def test_requested_subset(self):
        tr = ShardTracker(self.SHARDS, ["c", "a"])
        assert tr.total == 2
        assert tr.requested_bytes() == 14
        assert tr.on_span(0, 24, 1.0) == ["a", "c"]   # b untracked
        assert tr.needed_pieces(4, 6) == {0, 1, 2, 5}
        with pytest.raises(ValueError, match="not in manifest"):
            ShardTracker(self.SHARDS, ["zz"])


# ----------------------------------------------------------------------
# scheduler/shard_affinity.py + Scheduling arm
# ----------------------------------------------------------------------

def _mk_peer(res, task, name, pod="roll-pod"):
    from dragonfly2_tpu.idl.messages import Host as HostMsg
    from dragonfly2_tpu.idl.messages import TopologyInfo
    host = res.store_host(HostMsg(
        id=f"{name}-host", ip="10.0.0.1", port=1, download_port=2,
        topology=TopologyInfo(slice_name=pod, ici_coords=(0, 0))))
    return res.get_or_create_peer(f"{name}-peer", task, host)


class TestShardAffinity:
    def _stack(self):
        from dragonfly2_tpu.scheduler.resource import Resource, Task
        from dragonfly2_tpu.scheduler.shard_affinity import ShardAffinity
        res = Resource()
        task = Task("t" + "0" * 63, "bench://x")
        return res, task, ShardAffinity()

    def test_disjoint_cover_across_group(self):
        res, task, aff = self._stack()
        names = [f"s{i}" for i in range(8)]
        peers = [_mk_peer(res, task, f"h{i}") for i in range(3)]
        # two passes: the final split reflects full membership
        for _ in range(2):
            got = {p.host.id: aff.assign(
                task_id=task.id, peer_id=p.id, host_id=p.host.id,
                topology=p.host.msg.topology, requested=names)
                for p in peers}
        owned = [n for sub in got.values() for n in sub]
        assert sorted(owned) == sorted(names)        # disjoint + covering

    def test_solo_peer_gets_everything(self):
        res, task, aff = self._stack()
        p = _mk_peer(res, task, "solo")
        got = aff.assign(task_id=task.id, peer_id=p.id, host_id=p.host.id,
                         topology=p.host.msg.topology,
                         requested=["a", "b"])
        assert got == ["a", "b"]

    def test_groups_are_pod_scoped(self):
        res, task, aff = self._stack()
        a = _mk_peer(res, task, "pa", pod="pod-a")
        b = _mk_peer(res, task, "pb", pod="pod-b")
        for p in (a, b):
            got = aff.assign(task_id=task.id, peer_id=p.id,
                             host_id=p.host.id,
                             topology=p.host.msg.topology,
                             requested=["a", "b"])
            # different pods never split with each other: both solo
            assert got == ["a", "b"]

    def test_ledger_rows_only_on_change(self):
        res, task, aff = self._stack()
        rows = []
        aff.sink = rows.append
        p = _mk_peer(res, task, "h0")
        kw = dict(task_id=task.id, peer_id=p.id, host_id=p.host.id,
                  topology=p.host.msg.topology, requested=["a", "b"])
        aff.assign(**kw)
        aff.assign(**kw)                      # identical ruling: no row
        assert len(rows) == 1
        assert rows[0]["decision_kind"] == "shard"
        assert rows[0]["assigned"] == ["a", "b"] and rows[0]["swap"] == []
        q = _mk_peer(res, task, "h1")
        aff.assign(task_id=task.id, peer_id=q.id, host_id=q.host.id,
                   topology=q.host.msg.topology, requested=["a", "b"])
        # h1's ruling emitted; h0's next ask re-emits only if it MOVED
        n = len(rows)
        got0 = aff.assign(**kw)
        assert (len(rows) == n) == (got0 == ["a", "b"])

    def test_forget_host_moves_ownership(self):
        res, task, aff = self._stack()
        names = [f"s{i}" for i in range(8)]
        a = _mk_peer(res, task, "ha")
        b = _mk_peer(res, task, "hb")
        for p in (a, b):
            aff.assign(task_id=task.id, peer_id=p.id, host_id=p.host.id,
                       topology=p.host.msg.topology, requested=names)
        aff.forget_host(b.host.id)
        got = aff.assign(task_id=task.id, peer_id=a.id, host_id=a.host.id,
                         topology=a.host.msg.topology, requested=names)
        assert got == names                   # the survivor owns it all

    def test_scheduling_arm_disabled_rules_none(self):
        from dragonfly2_tpu.scheduler.config import SchedulerConfig
        from dragonfly2_tpu.scheduler.evaluator import make_evaluator
        from dragonfly2_tpu.scheduler.resource import Resource, Task
        from dragonfly2_tpu.scheduler.scheduling import Scheduling
        from dragonfly2_tpu.scheduler.shard_affinity import ShardAffinity
        res = Resource()
        task = Task("t" + "1" * 63, "bench://x")
        child = _mk_peer(res, task, "c0")
        off = Scheduling(SchedulerConfig(), make_evaluator("default"))
        assert off.shard_assignment(child, ["a"]) is None
        on = Scheduling(SchedulerConfig(), make_evaluator("default"),
                        sharded=ShardAffinity())
        assert on.shard_assignment(child, ["a"]) == ["a"]
        assert on.shard_assignment(child, []) is None


# ----------------------------------------------------------------------
# piece_dispatcher: needed filter + swap hold
# ----------------------------------------------------------------------

def _info(num, size=4):
    from dragonfly2_tpu.idl.messages import PieceInfo
    return PieceInfo(piece_num=num, range_start=num * size, range_size=size)


class TestDispatcherShardState:
    def test_unneeded_pieces_never_dispatch(self):
        from dragonfly2_tpu.daemon.piece_dispatcher import PieceDispatcher

        async def main():
            d = PieceDispatcher()
            d.set_shard_state({1}, set())
            await d.add_parent("p1", "a:1")
            await d.announce("p1", [_info(0), _info(1), _info(2)])
            assert d.pending_count() == 1
            got = await d.get(timeout=0.2)
            assert got is not None and got.piece.piece_num == 1
            assert [p.piece_num for p in got.pieces] == [1]  # no group leak
            await d.report(got, ok=True)
            assert await d.get(timeout=0.2) is None   # nothing else needed
            assert d.starving()    # unneeded holders don't mask starvation
            await d.close()

        asyncio.run(main())

    def test_swap_piece_waits_out_hold_then_seed_serves(self):
        from dragonfly2_tpu.daemon.piece_dispatcher import PieceDispatcher

        async def main():
            d = PieceDispatcher()
            d.set_shard_state({0, 1}, {1})
            d.swap_hold_s = 0.3
            await d.add_parent("seed", "s:1", is_seed=True)
            await d.announce("seed", [_info(0), _info(1)])
            t0 = time.monotonic()
            got = await d.get(timeout=0.2)
            assert got.piece.piece_num == 0        # tree-class: immediate
            assert [p.piece_num for p in got.pieces] == [0]  # no swap drag
            await d.report(got, ok=True)
            got = await d.get(timeout=2.0)         # swap: only after hold
            assert got is not None and got.piece.piece_num == 1
            assert time.monotonic() - t0 >= 0.25
            await d.report(got, ok=True)
            await d.close()

        asyncio.run(main())

    def test_endgame_never_races_swap_piece_onto_seed(self):
        from dragonfly2_tpu.daemon.piece_dispatcher import (
            ENDGAME_RACE_AGE_S, PieceDispatcher)

        async def main():
            d = PieceDispatcher()
            d.set_shard_state({0}, {0})
            d.endgame = True
            await d.add_parent("mate", "m:1")
            await d.add_parent("seed", "s:1", is_seed=True)
            await d.announce("mate", [_info(0)])
            await d.announce("seed", [_info(0)])
            first = await d.get(timeout=0.2)
            assert first is not None and first.parent.peer_id == "mate"
            # age the in-flight fetch past the race threshold: the only
            # alt is the SEED, and a swap-class piece must not race onto
            # it (the duplicate would re-fetch what affinity deduped)
            for ps in d._pieces.values():
                ps.dispatched_at -= ENDGAME_RACE_AGE_S + 1.0
            assert await d.get(timeout=0.15) is None
            # the same shape WITHOUT the swap class races fine
            d.swap_nums = set()
            racer = await d.get(timeout=0.3)
            assert racer is not None and racer.parent.peer_id == "seed"
            await d.close()

        asyncio.run(main())

    def test_swap_piece_rides_peer_immediately(self):
        from dragonfly2_tpu.daemon.piece_dispatcher import PieceDispatcher

        async def main():
            d = PieceDispatcher()
            d.set_shard_state({0}, {0})
            d.swap_hold_s = 30.0
            await d.add_parent("seed", "s:1", is_seed=True)
            await d.add_parent("mate", "m:1")
            await d.announce("seed", [_info(0)])
            await d.announce("mate", [_info(0)])
            got = await d.get(timeout=0.3)
            # a non-seed holder serves a swap piece with NO hold — and
            # the seed-last rank keeps the seed out of it
            assert got is not None and got.parent.peer_id == "mate"
            await d.report(got, ok=True)
            await d.close()

        asyncio.run(main())


class TestWidenCommitRace:
    def _conductor(self, tmp_path):
        from dragonfly2_tpu.daemon.conductor import PeerTaskConductor
        from dragonfly2_tpu.storage.manager import (StorageConfig,
                                                    StorageManager)
        mgr = StorageManager(StorageConfig(
            data_dir=str(tmp_path / "store")))
        return PeerTaskConductor(
            task_id="t" * 64, peer_id="p1", url="http://x/y",
            url_meta=None, storage_mgr=mgr, piece_mgr=None,
            shard_manifest=[mk("a", 0, 4), mk("b", 4, 4)],
            requested_shards=["a"])

    def test_widen_refused_once_finishing(self, tmp_path):
        async def main():
            c = self._conductor(tmp_path)
            c._finishing = True
            assert c.widen_to_whole_file() is False
            assert c.requested_shards == ["a"]     # untouched
            c2 = self._conductor(tmp_path)
            c2.done_event.set()
            assert c2.widen_to_whole_file() is False
            c3 = self._conductor(tmp_path)
            assert c3.widen_to_whole_file() is True
            assert c3.requested_shards is None
            assert c3.widen_to_whole_file() is True   # idempotent

        asyncio.run(main())

    def test_finish_success_sets_commit_flag(self, tmp_path):
        async def main():
            c = self._conductor(tmp_path)
            c.set_content_info(8, 4)
            # land both needed... only shard a needed: piece 0
            await c._land_piece(0, 0, b"abcd", 1, source="")
            await c._finish_success()
            assert c._finishing is True
            assert c.state == c.SUCCESS
            # a post-success widen is refused — the joiner gets a fresh
            # conductor instead of a success missing its shards
            assert c.widen_to_whole_file() is False

        asyncio.run(main())


# ----------------------------------------------------------------------
# flight summary + dfdiag + podscope surfaces
# ----------------------------------------------------------------------

class TestShardSurfaces:
    def _flight(self):
        from dragonfly2_tpu.daemon import flight_recorder as fr
        f = fr.TaskFlight("t" * 64, "peer-1")
        f.shards_total = 3
        f.event(fr.WIRE_DONE, 0, "p1", 100, dur_ms=5.0, t_ms=10.0)
        f.event(fr.SHARD_READY, fr.SHARD_SRC_TREE, "a", 100, t_ms=11.0)
        f.event(fr.SHARD_READY, fr.SHARD_SRC_SWAP, "b", 200, t_ms=30.0)
        f.event(fr.SHARD_FALLBACK, 5, "seed-peer")
        return f

    def test_summary_shards_block(self):
        s = self._flight().summarize()
        sh = s["shards"]
        assert sh["total"] == 3 and sh["ready"] == 2
        assert sh["tree_bytes"] == 100 and sh["swap_bytes"] == 200
        assert sh["fallbacks"] == 1
        assert sh["slowest"]["name"] == "b" and sh["slowest"]["src"] == "swap"
        # shard events never pollute the piece table
        assert [r["piece"] for r in s["piece_rows"]] == [0]

    def test_compact_summary_caps_rows(self):
        from dragonfly2_tpu.daemon import flight_recorder as fr
        f = fr.TaskFlight("t" * 64, "peer-1")
        f.shards_total = 40
        for i in range(40):
            f.event(fr.SHARD_READY, fr.SHARD_SRC_TREE, f"s{i:02d}", 10,
                    t_ms=float(i))
        c = f.compact_summary(max_parents=8)
        assert len(c["shards"]["rows"]) == 8
        assert c["shards"]["ready"] == 40      # totals stay exact
        # the kept rows are the LATEST-ready (the time-to-serving tail)
        assert c["shards"]["rows"][0]["name"] == "s39"

    def test_dfdiag_verdict_names_slowest_shard(self):
        from dragonfly2_tpu.tools.dfdiag import verdict
        text = verdict(self._flight().summarize())
        assert "slowest shard b" in text
        assert "ICI-swapped" in text
        assert "fell back to the tree" in text

    def test_podscope_shards_line(self):
        from dragonfly2_tpu.common import podscope
        summary = self._flight().summarize()
        snaps = [{"addr": "d1", "flights": {
            "t" * 64: {"task_id": "t" * 64, "peer_id": "peer-1",
                       "state": "success", "started_at": 0.0,
                       "events": [], "serves": [], "summary": summary}}}]
        report = podscope.aggregate(snaps)
        t = report["tasks"]["t" * 64]
        assert t["shards"] == {"ready": 2, "total": 3, "tree_bytes": 100,
                               "swap_bytes": 200, "fallbacks": 1}
        text = podscope.render_pod(report)
        assert "shards: 2/3 ready pod-wide" in text
        assert "tree fallback" in text


# ----------------------------------------------------------------------
# real-daemon e2e
# ----------------------------------------------------------------------

PIECE = 4 << 20


def _manifest(total, n):
    size = total // n
    return ShardManifest(shards=[
        mk(f"s{i}", i * size, size if i < n - 1 else total - i * size)
        for i in range(n)])


async def _download(daemon, url, out, *, manifest=None, shards="",
                    disable_back_source=False, timeout_s=60.0):
    from dragonfly2_tpu.idl.messages import DownloadRequest, UrlMeta
    from dragonfly2_tpu.rpc.client import Channel, ServiceClient
    ch = Channel(f"unix:{daemon.unix_sock}")
    client = ServiceClient(ch, "df.daemon.Daemon")
    frames = []
    try:
        async for resp in client.unary_stream("Download", DownloadRequest(
                url=url, output=out, shard_manifest=manifest,
                url_meta=UrlMeta(shards=shards),
                disable_back_source=disable_back_source,
                timeout_s=timeout_s)):
            frames.append(resp)
    finally:
        await ch.close()
    return frames


class TestShardedE2E:
    def test_whole_file_incremental_and_byte_identical(self, tmp_path):
        """The full manifest through a real daemon (back-source): output
        byte-identical, one shard_ready frame per shard, and the FIRST
        shard_ready precedes the task's last wire event — cut-through to
        readiness, not land-then-slice."""
        from dragonfly2_tpu.common import ids
        from dragonfly2_tpu.daemon import flight_recorder as fr
        from dragonfly2_tpu.daemon.daemon import Daemon
        data = os.urandom(3 * PIECE + 12345)      # 4 pieces
        manifest = _manifest(len(data), 6)

        async def go():
            origin, base = await start_origin({"w.bin": data})
            cfg = daemon_config(tmp_path, "whole")
            # ONE origin stream, cut front-to-back: early shards verify
            # while later pieces are still on the wire — the incremental
            # shape the assertion below pins (4 parallel range groups
            # would land every piece near-simultaneously on localhost)
            cfg.download.back_source_parallelism = 1
            daemon = Daemon(cfg)
            await daemon.start()
            try:
                url = f"{base}/w.bin"
                out = tmp_path / "w.out"
                frames = await _download(daemon, url, str(out),
                                         manifest=manifest)
                assert out.read_bytes() == data
                shard_frames = [f for f in frames if f.shard]
                assert sorted(f.shard for f in shard_frames) == \
                    [f"s{i}" for i in range(6)]
                assert all(f.shards_total == 6 for f in shard_frames)
                assert shard_frames[-1].shards_ready == 6
                # no affinity ruling (no scheduler): everything is tree
                assert {f.shard_src for f in shard_frames} == {"tree"}
                task = ids.task_id(url)
                conductor = daemon.ptm.conductor(task)
                assert conductor.state == conductor.SUCCESS
                # whole file: storage IS marked done (reuse path intact)
                assert conductor.storage.md.done \
                    and conductor.storage.md.success
                events = list(daemon.flight_recorder.get(task).events)
                ready_ts = [t for t, k, *_ in events
                            if k == fr.SHARD_READY]
                wire_ts = [t for t, k, *_ in events if k == fr.WIRE_DONE]
                assert ready_ts and wire_ts
                # incremental: the first shard was ready BEFORE the last
                # piece hit the wire
                assert min(ready_ts) < max(wire_ts)
            finally:
                await daemon.stop()
                await origin.cleanup()

        asyncio.run(go())

    def test_subset_pulls_only_needed_pieces(self, tmp_path):
        """``UrlMeta.shards`` narrows the pull: only the covering pieces
        move (origin sees no byte beyond them), storage stays a warm
        partial, and a later request for ANOTHER shard fetches only the
        gap."""
        from dragonfly2_tpu.common import ids
        from dragonfly2_tpu.daemon.daemon import Daemon
        data = os.urandom(3 * PIECE)              # 3 pieces, 3 shards
        manifest = _manifest(len(data), 3)
        served: list[tuple[int, int]] = []

        async def go():
            from aiohttp import web

            from dragonfly2_tpu.common.piece import parse_http_range

            async def handle(request: web.Request):
                headers = {"Accept-Ranges": "bytes"}
                rng = request.headers.get("Range")
                if rng:
                    r = parse_http_range(rng, len(data))
                    # only BODY transfers count as served bytes — the
                    # geometry probes (HEAD / range-support checks) are
                    # not content egress
                    if request.method == "GET":
                        served.append((r.start, r.end))
                    headers["Content-Range"] = \
                        f"bytes {r.start}-{r.end - 1}/{len(data)}"
                    return web.Response(status=206,
                                        body=data[r.start:r.end],
                                        headers=headers)
                if request.method == "GET":
                    served.append((0, len(data)))
                return web.Response(body=data, headers=headers)

            app = web.Application()
            app.router.add_route("*", "/{tail:.*}", handle)
            runner = web.AppRunner(app, access_log=None)
            await runner.setup()
            site = web.TCPSite(runner, "127.0.0.1", 0)
            await site.start()
            port = next(s._server.sockets[0].getsockname()[1]
                        for s in runner.sites)
            cfg = daemon_config(tmp_path, "subset")
            daemon = Daemon(cfg)
            await daemon.start()
            try:
                url = f"http://127.0.0.1:{port}/c.bin"
                out = tmp_path / "c.out"
                frames = await _download(daemon, url, str(out),
                                         manifest=manifest, shards="s0")
                assert [f.shard for f in frames if f.shard] == ["s0"]
                done = [f for f in frames if f.done][-1]
                assert done.completed_length == PIECE
                # the origin never served a byte beyond piece 0
                assert served and max(e for _s, e in served) <= PIECE
                assert out.read_bytes()[:PIECE] == data[:PIECE]
                task = ids.task_id(url)
                conductor = daemon.ptm.conductor(task)
                assert conductor.state == conductor.SUCCESS
                assert conductor.ready == {0}
                # warm PARTIAL: never marked done — the complete-task
                # reuse path can't serve the sparse file as whole content
                assert not conductor.storage.md.done
                # second request, different shard: fetches ONLY the gap
                served.clear()
                frames = await _download(daemon, url,
                                         str(tmp_path / "c2.out"),
                                         manifest=manifest, shards="s1")
                assert [f.shard for f in frames if f.shard] == ["s1"]
                assert served
                for s, e in served:
                    assert s >= PIECE and e <= 2 * PIECE
            finally:
                await daemon.stop()
                await runner.cleanup()

        asyncio.run(go())

    def test_affinity_swap_over_p2p_and_holder_kill_falls_back(
            self, tmp_path):
        """Scheduler-armed rollout over real daemons: replica B (first,
        solo) tree-fetches everything; replica A is assigned a rendezvous
        subset and swaps the rest off B over P2P (zero origin bytes).
        Then B — the sole holder of A2's swap shards — is KILLED before
        a third replica pulls: the ladder re-pulls from the tree
        (rung/fallback journaled), completes byte-identical, zero wedged
        tasks."""
        from dragonfly2_tpu.common import ids
        from dragonfly2_tpu.daemon import flight_recorder as fr
        from dragonfly2_tpu.daemon.daemon import Daemon
        from dragonfly2_tpu.scheduler.config import SchedulerConfig
        from dragonfly2_tpu.scheduler.server import Scheduler
        data = os.urandom(3 * PIECE)              # 3 pieces
        manifest = _manifest(len(data), 3)
        names = "s0,s1,s2"

        async def go():
            origin, base = await start_origin({"r.bin": data})
            url = f"{base}/r.bin"
            sched = Scheduler(SchedulerConfig())
            await sched.start()
            b = Daemon(leecher_config(tmp_path, "rb", sched.address))
            await b.start()
            b_stopped = False
            a = None
            c = None
            try:
                # B first: solo in its group -> assigned every shard,
                # tree-fetches the lot (back-source)
                frames = await _download(b, url, str(tmp_path / "b.out"),
                                         manifest=manifest, shards=names)
                assert (tmp_path / "b.out").read_bytes() == data
                tb = ids.task_id(url)
                assert {f.shard_src for f in frames if f.shard} == {"tree"}

                # A second: rendezvous over {A, B} -> a strict subset is
                # tree-class, the rest swap-class — all served by B over
                # P2P (origin untouched: back-source disabled)
                a = Daemon(leecher_config(tmp_path, "ra", sched.address))
                await a.start()
                frames = await _download(a, url, str(tmp_path / "a.out"),
                                         manifest=manifest, shards=names,
                                         disable_back_source=True)
                assert (tmp_path / "a.out").read_bytes() == data
                ca = a.ptm.conductor(tb)
                assert ca.state == ca.SUCCESS
                assert ca.traffic_source == 0 and ca.traffic_p2p == len(data)
                srcs = {f.shard: f.shard_src for f in frames if f.shard}
                assert len(srcs) == 3
                # the scheduler actually split the group: A was assigned
                # a strict subset, so at least one shard arrived by swap
                assert ca.affinity_shards is not None
                assert len(ca.affinity_shards) < 3
                assert "swap" in srcs.values()
                rows = sched.ledger.snapshot(limit=512)["decisions"]
                shard_rows = [r for r in rows
                              if r.get("decision_kind") == "shard"]
                assert shard_rows, "affinity ruling missing from ledger"
                assert all(set(r["assigned"]) <= set(r["requested"])
                           for r in shard_rows)

                # kill B — the sole holder — then a THIRD replica pulls:
                # its swap partners are gone, the bounded holds expire,
                # and the tree (origin back-source) covers everything
                await b.stop()
                b_stopped = True
                c = Daemon(leecher_config(tmp_path, "rc", sched.address))
                await c.start()
                t0 = time.monotonic()
                frames = await _download(c, url, str(tmp_path / "c.out"),
                                         manifest=manifest, shards=names,
                                         timeout_s=90.0)
                assert (tmp_path / "c.out").read_bytes() == data
                assert time.monotonic() - t0 < 60.0, "wedged task"
                cc = c.ptm.conductor(tb)
                assert cc.state == cc.SUCCESS
                summary = c.flight_recorder.get(tb).summarize()
                # the degradation is JOURNALED: either the ladder rung
                # (back_source / reschedule) or the swap-hold fallback
                kinds = {k for _t, k, *_ in c.flight_recorder.get(tb).events}
                assert summary["rungs"] or fr.SHARD_FALLBACK in kinds
                sh = summary["shards"]
                assert sh["ready"] == sh["total"] == 3
            finally:
                if c is not None:
                    await c.stop()
                if a is not None:
                    await a.stop()
                if not b_stopped:
                    await b.stop()
                await sched.stop()
                await origin.cleanup()

        asyncio.run(go())
