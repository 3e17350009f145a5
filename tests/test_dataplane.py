"""PR-5 zero-stall data plane: buffer pool, one-pass span landing, the
dedicated storage executor, and the acceptance e2e proving no multi-MiB
hash runs on the event loop in the P2P landing path (with loop lag staying
under the health threshold under a saturated fan-out)."""

import asyncio
import os
import threading
import time

import pytest

from dragonfly2_tpu.common import digest as digestlib
from dragonfly2_tpu.common.bufpool import BufferPool, POOL
from dragonfly2_tpu.common.errors import Code, DFError
from dragonfly2_tpu.storage import native
from dragonfly2_tpu.storage.metadata import TaskMetadata
from dragonfly2_tpu.storage.store import TaskStorage


def _algo() -> str:
    return digestlib.preferred_piece_algo()


def _mk_storage(tmp_path, name="t") -> TaskStorage:
    return TaskStorage(str(tmp_path / name), TaskMetadata(
        task_id=name * 32, url="test://dataplane"))


def _spec(blob: bytes, piece: int):
    out = []
    for i, off in enumerate(range(0, len(blob), piece)):
        chunk = blob[off:off + piece]
        out.append((i, off, len(chunk),
                    digestlib.for_bytes(_algo(), chunk)))
    return out


class TestBufferPool:
    def test_hit_miss_and_reuse(self):
        pool = BufferPool(max_bytes=1 << 20)
        a = pool.acquire(4096)
        assert len(a) == 4096
        pool.release(a)
        b = pool.acquire(4096)
        assert b is a                       # recycled, not reallocated
        assert pool.acquire(4096) is not a  # bucket drained -> fresh

    def test_exported_view_is_never_recycled(self):
        """The reuse-safety backstop: a buffer released while a memoryview
        still references it must NOT be handed to the next download —
        a stale view would silently read the new download's bytes."""
        pool = BufferPool()
        buf = pool.acquire(1024)
        view = memoryview(buf)
        pool.release(buf)                   # export alive -> discarded
        assert pool.pooled_bytes() == 0
        view.release()
        pool.release(buf)                   # export gone -> pools fine
        assert pool.pooled_bytes() == 1024

    def test_byte_cap(self):
        pool = BufferPool(max_bytes=8192)
        bufs = [pool.acquire(4096) for _ in range(3)]
        for b in bufs:
            pool.release(b)
        assert pool.pooled_bytes() == 8192  # third was discarded


class TestWriteSpan:
    """Satellite: native df_span_write + graceful pure-Python degrade,
    both exercised (the python path is forced via monkeypatch so the test
    is meaningful whether or not the .so is built)."""

    def _roundtrip(self, tmp_path, name):
        blob = os.urandom(256 * 1024 + 333)
        piece = 64 * 1024
        ts = _mk_storage(tmp_path, name)
        metas, corrupt, path = ts.write_span(_spec(blob, piece), blob)
        assert not corrupt
        assert [m.num for m in metas] == list(range(5))
        for m in metas:
            assert ts.read_piece(m.num) == blob[m.start:m.start + m.size]
            assert digestlib.verify(m.digest, ts.read_piece(m.num))
        ts.close()
        return path

    def test_python_fallback_one_write_per_span(self, tmp_path, monkeypatch):
        writes = []
        real_pwrite = os.pwrite

        def counting_pwrite(fd, data, offset):
            writes.append((offset, len(bytes(data))))
            return real_pwrite(fd, data, offset)

        monkeypatch.setattr(native, "span_write",
                            lambda *a, **k: None)      # no .so -> degrade
        monkeypatch.setattr(os, "pwrite", counting_pwrite)
        path = self._roundtrip(tmp_path, "py")
        assert path == "python"
        # still ONE write for the whole span, not one per piece
        assert len(writes) == 1

    @pytest.mark.skipif(not native.available()
                        or not getattr(native.load(), "_df_has_span_io",
                                       False),
                        reason="native lib not built")
    def test_native_fused_path(self, tmp_path):
        assert self._roundtrip(tmp_path, "nat") == "native"

    @pytest.mark.parametrize("force_python", [True, False])
    def test_corrupt_piece_rejected_groupmates_land(self, tmp_path,
                                                    monkeypatch,
                                                    force_python):
        if force_python:
            monkeypatch.setattr(native, "span_write", lambda *a, **k: None)
        elif not native.available() or not getattr(
                native.load(), "_df_has_span_io", False):
            pytest.skip("native lib not built")
        blob = bytearray(os.urandom(3 * 65536))
        spec = _spec(bytes(blob), 65536)
        blob[65536 + 7] ^= 0xFF             # corrupt the MIDDLE piece
        ts = _mk_storage(tmp_path, "c")
        metas, corrupt, _ = ts.write_span(spec, bytes(blob))
        assert corrupt == [1]
        assert [m.num for m in metas] == [0, 2]
        # the corrupted region is never recorded: served-piece lookups 404
        with pytest.raises(DFError) as ei:
            ts.read_piece(1)
        assert ei.value.code == Code.CLIENT_PIECE_NOT_FOUND
        # the retry re-lands the good bytes over the poisoned region
        good = bytes(blob)
        good = good[:65536 + 7] + bytes([good[65536 + 7] ^ 0xFF]) \
            + good[65536 + 8:]           # un-flip: original content
        metas2, corrupt2, _ = ts.write_span([spec[1]],
                                            good[65536:2 * 65536],
                                            base=65536)
        assert [m.num for m in metas2] == [1] and not corrupt2
        assert ts.read_piece(1) == good[65536:2 * 65536]
        ts.close()

    def test_duplicate_mid_span_is_not_rewritten(self, tmp_path):
        """An already-recorded piece splits the span into runs and keeps
        its original bytes (a racer's unverified copy must never overwrite
        a verified region)."""
        blob = os.urandom(3 * 65536)
        spec = _spec(blob, 65536)
        ts = _mk_storage(tmp_path, "d")
        ts.write_piece(1, 65536, blob[65536:131072], spec[1][3])
        racer = bytearray(blob)
        racer[65536 + 3] ^= 0xFF            # racer's copy of piece 1 is bad
        metas, corrupt, _ = ts.write_span(spec, bytes(racer))
        assert [m.num for m in metas] == [0, 2]
        assert corrupt == []                # dup skipped, not re-verified
        assert ts.read_piece(1) == blob[65536:131072]   # original intact
        ts.close()


class TestCachedFd:
    """The cached-fd lifetime rules: GC eviction racing in-flight storage
    IO must never close the fd out from under a pread/pwrite (a reused fd
    number would land bytes in ANOTHER task's file)."""

    def test_close_during_inflight_io_is_deferred(self, tmp_path):
        ts = _mk_storage(tmp_path, "fd")
        ts.write_piece(0, 0, b"x" * 1024)
        with ts._data_fd() as fd:
            ts.close()                       # mid-lease: must defer
            assert ts._fd is not None        # not yanked
            assert os.pread(fd, 4, 0) == b"xxxx"   # fd still valid
        assert ts._fd is None                # last release ran the close
        assert ts.read_range(0, 4) == b"xxxx"      # transparent reopen
        ts.close()

    def test_new_lease_during_deferred_close_goes_private(self, tmp_path):
        """While a close is deferred the cached fd is doomed (it may point
        at an already-unlinked inode): a new lease must get a PRIVATE fd
        opened from the path, never extend the doomed one."""
        ts = _mk_storage(tmp_path, "dfd")
        ts.write_piece(0, 0, b"x" * 16)
        with ts._data_fd() as fd1:
            ts.close()                      # deferred behind fd1's lease
            with ts._data_fd() as fd2:
                assert fd2 != fd1
                assert os.pread(fd2, 4, 0) == b"xxxx"
        assert ts._fd is None               # fd1's release ran the close
        ts.close()

    def test_io_in_destroy_window_fails_safe(self, tmp_path):
        """destroy() with a lease outstanding: the data file is unlinked
        while the close is deferred — new IO must fail safe (typed error),
        not silently write into the doomed inode."""
        ts = _mk_storage(tmp_path, "dwin")
        ts.write_piece(0, 0, b"y" * 16)
        with ts._data_fd():
            ts.destroy()                    # close deferred + dir removed
            with pytest.raises(DFError):
                ts.read_range(0, 16)

    def test_destroyed_task_io_fails_safe_as_dferror(self, tmp_path):
        """After destroy() the data file is gone: IO re-opens the path and
        fails safe (typed DFError -> the upload server's 404), exactly the
        per-call-open behavior the fd cache replaced — never a write into
        a recycled descriptor."""
        ts = _mk_storage(tmp_path, "gone")
        ts.write_piece(0, 0, b"y" * 16)
        ts.destroy()
        with pytest.raises(DFError) as ei:
            ts.read_range(0, 16)
        assert ei.value.code == Code.CLIENT_STORAGE_ERROR


class TestNativeDegrade:
    def test_span_write_signals_fallback_without_lib(self, monkeypatch):
        monkeypatch.setattr(native, "load", lambda: None)
        assert native.span_write(3, 0, b"xx", [2]) is None

    def test_span_write_rejects_size_mismatch(self):
        if not native.available() or not getattr(
                native.load(), "_df_has_span_io", False):
            pytest.skip("native lib not built")
        with pytest.raises(ValueError):
            native.span_write(0, 0, b"abc", [2])


class TestReuseSafety:
    def test_recycled_buffers_never_corrupt_landed_bytes(self, tmp_path):
        """The buffer-pool acceptance test: land spans from pooled
        buffers with an HBM sink attached, recycle each buffer the moment
        its landing returns and immediately scribble over it (the next
        download reusing the allocation) — every landed byte, on disk AND
        in the sink's host buffer, must still digest clean."""
        from dragonfly2_tpu.daemon.conductor import PeerTaskConductor
        from dragonfly2_tpu.idl.messages import PieceInfo
        from dragonfly2_tpu.tpu.hbm_sink import DeviceIngest

        piece = 128 * 1024
        n_pieces = 16
        blob = os.urandom(piece * n_pieces)

        import numpy as np
        puts = []

        def slow_put(view, device):
            time.sleep(0.02)        # transfers outlive several landings
            arr = np.array(view, copy=True)
            puts.append(device)
            return arr

        class _Mgr:
            def register_task(self, md):
                return TaskStorage(str(tmp_path / "task"), md)

        sink = DeviceIngest(len(blob), devices=[object(), object()],
                            shards_per_device=2, device_put_fn=slow_put)
        conductor = PeerTaskConductor(
            task_id="r" * 64, peer_id="reuse-peer", url="test://reuse",
            url_meta=None, storage_mgr=_Mgr(), piece_mgr=None,
            device_sink_factory=lambda n: sink)
        conductor.set_content_info(len(blob))

        async def land(first: int):
            infos = []
            for num in (first, first + 1):
                off = num * piece
                infos.append(PieceInfo(
                    piece_num=num, range_start=off, range_size=piece,
                    digest=digestlib.for_bytes(_algo(),
                                               blob[off:off + piece])))
            buf = POOL.acquire(2 * piece)
            buf[:] = blob[first * piece:(first + 2) * piece]
            placed, corrupt, raced = await conductor.on_span_from_peer(
                "parent-x", infos, buf, 1)
            assert sorted(placed) == [first, first + 1]
            assert not corrupt and not raced
            POOL.release(buf)
            # simulate the next download grabbing the allocation and
            # filling it with garbage while DMAs are still in flight
            nxt = POOL.acquire(2 * piece)
            nxt[:] = b"\xee" * (2 * piece)
            POOL.release(nxt)

        async def go():
            await asyncio.gather(*(land(i) for i in range(0, n_pieces, 2)))
            await asyncio.to_thread(sink.drain, 10)

        asyncio.run(go())
        # disk bytes intact
        st = conductor.storage
        for num in range(n_pieces):
            assert st.read_piece(num) == blob[num * piece:(num + 1) * piece]
        # what the sink staged and transferred is intact (every DMA read
        # only sink-owned memory; the host buffer itself went back to the
        # sink pool with the last transfer)
        assert sink.host is None
        assert b"".join(bytes(a) for a in sink._shard_arrays) == blob
        sink.close()
        st.close()


class TestEndgameRaceSafety:
    """Landing-time verification changed the endgame-duplicate contract:
    a duplicate claimed by a STILL-LANDING racer has an unknown outcome
    and must be reported `raced` (neither done nor corrupt) — treating it
    as done would orphan the piece forever if the racer's copy fails
    verification."""

    def test_inflight_duplicate_reported_raced_then_settled(self, tmp_path):
        from dragonfly2_tpu.daemon.conductor import PeerTaskConductor
        from dragonfly2_tpu.idl.messages import PieceInfo

        piece = 64 * 1024
        blob = os.urandom(piece)
        info = PieceInfo(piece_num=0, range_start=0, range_size=piece,
                         digest=digestlib.for_bytes(_algo(), blob))

        class _Mgr:
            def register_task(self, md):
                return TaskStorage(str(tmp_path / "task"), md)

        conductor = PeerTaskConductor(
            task_id="e" * 64, peer_id="race-peer", url="test://race",
            url_meta=None, storage_mgr=_Mgr(), piece_mgr=None,
            device_sink_factory=None)
        conductor.set_content_info(piece)
        st = conductor.storage
        gate = threading.Event()
        real_write_span = st.write_span

        def slow_write_span(*a, **k):
            gate.wait(10)            # racer A parks mid-landing off-loop
            return real_write_span(*a, **k)

        st.write_span = slow_write_span

        async def go():
            a = asyncio.get_running_loop().create_task(
                conductor.on_span_from_peer("parent-A", [info], blob, 1))
            for _ in range(100):     # until A holds the landing claim
                await asyncio.sleep(0.01)
                if 0 in conductor._landing:
                    break
            assert 0 in conductor._landing
            # duplicate arrives while A is mid-landing: raced, NOT done
            placed, corrupt, raced = await conductor.on_span_from_peer(
                "parent-B", [info], blob, 1)
            assert raced == [0] and not placed and not corrupt
            gate.set()
            placed_a, corrupt_a, raced_a = await a
            assert placed_a == [0] and not corrupt_a and not raced_a
            # a duplicate AFTER the winner landed is safely "already done"
            placed2, corrupt2, raced2 = await conductor.on_span_from_peer(
                "parent-C", [info], blob, 1)
            assert not placed2 and not corrupt2 and not raced2

        asyncio.run(go())
        assert st.read_piece(0) == blob
        st.close()

    def test_retry_conductor_counts_surviving_storage_pieces(self, tmp_path):
        """A retry conductor inherits the failed conductor's TaskStorage
        (md.pieces populated) but starts with an empty ready set. Spans
        re-downloaded over already-recorded pieces must still come back
        `placed` — write_span skips the re-write, but silently dropping
        them would leave the new conductor short of total_pieces forever
        while the engine reports them complete."""
        from dragonfly2_tpu.daemon.conductor import PeerTaskConductor
        from dragonfly2_tpu.idl.messages import PieceInfo

        piece = 64 * 1024
        blob = os.urandom(2 * piece)
        infos = [PieceInfo(piece_num=i, range_start=i * piece,
                           range_size=piece,
                           digest=digestlib.for_bytes(
                               _algo(), blob[i * piece:(i + 1) * piece]))
                 for i in range(2)]

        class _Mgr:
            def register_task(self, md):
                return TaskStorage(str(tmp_path / "task"), md)

        def conductor():
            c = PeerTaskConductor(
                task_id="s" * 64, peer_id="retry-peer", url="test://retry",
                url_meta=None, storage_mgr=_Mgr(), piece_mgr=None,
                device_sink_factory=None)
            c.set_content_info(len(blob))
            return c

        async def go():
            first = conductor()
            placed, _, _ = await first.on_span_from_peer(
                "parent-A", [infos[0]], blob[:piece], 1)
            assert placed == [0]
            # "retry": fresh conductor, SAME storage dir, empty ready set
            second = conductor()
            assert not second.ready
            placed2, corrupt2, raced2 = await second.on_span_from_peer(
                "parent-B", infos, blob, 1)
            assert sorted(placed2) == [0, 1]     # 0 came from disk
            assert not corrupt2 and not raced2
            assert second.ready == {0, 1}
            assert second.completed_length == len(blob)
            second.storage.close()
            first.storage.close()

        asyncio.run(go())


class TestStagingInTheLanding:
    """The staging copy into the device sink rides the landing: on the
    storage thread, for verified pieces, with the sink's buffer held for
    as long as a landing can touch it; what the loop does afterwards is
    the sink's bookkeeping."""

    PIECE = 64 * 1024

    def _conductor(self, tmp_path, blob, pool, **sink_kw):
        import numpy as np

        from dragonfly2_tpu.daemon.conductor import PeerTaskConductor
        from dragonfly2_tpu.daemon.flight_recorder import FlightRecorder
        from dragonfly2_tpu.tpu.hbm_sink import DeviceIngest

        stores = self.__dict__.setdefault("_stores", {})

        class _Mgr:
            def register_task(self, md):        # one store a directory
                return stores.setdefault(
                    tmp_path, TaskStorage(str(tmp_path / "task"), md))

        def factory(n):
            return DeviceIngest(
                n, devices=[object()], pool=pool,
                device_put_fn=lambda v, d: np.array(v, copy=True),
                **sink_kw)

        flight = FlightRecorder().begin("g" * 64, "stage-peer")
        c = PeerTaskConductor(
            task_id="g" * 64, peer_id="stage-peer", url="test://stage",
            url_meta=None, storage_mgr=_Mgr(), piece_mgr=None,
            device_sink_factory=factory, flight=flight)
        c.set_content_info(len(blob), self.PIECE)
        return c

    def _infos(self, blob):
        from dragonfly2_tpu.idl.messages import PieceInfo

        return [PieceInfo(piece_num=i, range_start=off,
                          range_size=len(blob[off:off + self.PIECE]),
                          digest=digestlib.for_bytes(
                              _algo(), blob[off:off + self.PIECE]))
                for i, off in enumerate(range(0, len(blob), self.PIECE))]

    def _staged(self, conductor):
        return [e for e in conductor.flight.events if e[1] == "staged"]

    def test_the_copy_runs_on_the_storage_thread_the_loop_only_accounts(
            self, tmp_path, monkeypatch):
        from dragonfly2_tpu.tpu import hbm_sink

        blob = os.urandom(4 * self.PIECE)
        pool = hbm_sink.SinkBufferPool()
        conductor = self._conductor(tmp_path, blob, pool)
        sink = conductor.device_ingest
        where = []
        real_address = hbm_sink.StageLease.address
        real_copy = hbm_sink.StageLease.copy
        real_commit = hbm_sink.DeviceIngest.commit

        def address(self, *a):
            where.append(("stage", threading.current_thread().name))
            return real_address(self, *a)

        def copy(self, *a):
            where.append(("stage", threading.current_thread().name))
            return real_copy(self, *a)

        def commit(self, *a):
            where.append(("commit", threading.current_thread().name))
            return real_commit(self, *a)

        monkeypatch.setattr(hbm_sink.StageLease, "address", address)
        monkeypatch.setattr(hbm_sink.StageLease, "copy", copy)
        monkeypatch.setattr(hbm_sink.DeviceIngest, "commit", commit)

        async def go():
            placed, corrupt, raced = await conductor.on_span_from_peer(
                "parent", self._infos(blob), blob, 1)
            assert sorted(placed) == [0, 1, 2, 3] and not corrupt
            await asyncio.to_thread(sink.drain, 10)

        asyncio.run(go())
        me = threading.current_thread().name
        assert {t for k, t in where if k == "commit"} == {me}
        staged_on = {t for k, t in where if k == "stage"}
        assert staged_on and all(t.startswith("df-storage")
                                 for t in staged_on)
        assert b"".join(bytes(a) for a in sink._shard_arrays) == blob
        (ev,) = self._staged(conductor)
        assert ev[4] == len(blob) and ev[5] > 0
        done = [e for e in conductor.flight.events if e[1] == "hbm_done"]
        assert sum(e[4] for e in done) == len(blob)
        assert sink.host is None and pool.parked_bytes() == len(blob)
        conductor.storage.close()

    def test_a_sink_lost_mid_landing_keeps_its_buffer_for_the_landing(
            self, tmp_path):
        from dragonfly2_tpu.tpu.hbm_sink import SinkBufferPool

        blob = os.urandom(2 * self.PIECE)
        pool = SinkBufferPool()
        conductor = self._conductor(tmp_path, blob, pool)
        sink = conductor.device_ingest
        st = conductor.storage
        entered, gate = threading.Event(), threading.Event()
        real_write_span = st.write_span

        def slow_write_span(*a, **k):
            entered.set()
            gate.wait(10)
            return real_write_span(*a, **k)

        st.write_span = slow_write_span

        async def go():
            landing = asyncio.get_running_loop().create_task(
                conductor.on_span_from_peer("parent", self._infos(blob),
                                            blob, 1))
            await asyncio.to_thread(entered.wait, 10)
            conductor._sink_lost("lost in the test")
            assert conductor.device_ingest is None
            for w in sink._workers:
                await asyncio.to_thread(w.join, 5)
            # closed, its workers gone, and the buffer still the landing's
            assert not any(w.is_alive() for w in sink._workers)
            assert sink.host is not None and pool.parked_bytes() == 0
            gate.set()
            placed, corrupt, raced = await landing
            # the bytes finished landing on disk; the request has lost
            assert sorted(placed) == [0, 1] and not corrupt and not raced

        asyncio.run(go())
        assert bytes(st.read_piece(0) + st.read_piece(1)) == blob
        assert conductor.sink_error == "lost in the test"
        assert sink.host is None and pool.parked_bytes() == len(blob)
        st.close()

    def test_a_failing_ingest_loses_the_sink_after_the_landing(
            self, tmp_path):
        """faultgate ``hbm.ingest`` raising: the piece's landing has
        returned (its copy with it) before the sink is lost, so the
        buffer goes back only then, and the bytes are on disk."""
        from dragonfly2_tpu.common import faultgate
        from dragonfly2_tpu.tpu.hbm_sink import SinkBufferPool

        blob = os.urandom(3 * self.PIECE)
        pool = SinkBufferPool()
        conductor = self._conductor(tmp_path, blob, pool)
        sink = conductor.device_ingest
        faultgate.reset()
        faultgate.arm("hbm.ingest", "fail", code=Code.INTERNAL, n=1)
        try:
            async def go():
                placed, corrupt, _ = await conductor.on_span_from_peer(
                    "parent", self._infos(blob), blob, 1)
                assert sorted(placed) == [0, 1, 2] and not corrupt
                for w in sink._workers:
                    await asyncio.to_thread(w.join, 5)

            asyncio.run(go())
        finally:
            faultgate.reset()
        assert "device ingest write failed at piece 0" in conductor.sink_error
        assert conductor.device_ingest is None
        (ev,) = self._staged(conductor)
        assert ev[4] == len(blob)             # staged before the loss
        assert sink.host is None and pool.parked_bytes() == len(blob)
        st = conductor.storage
        assert b"".join(st.read_piece(i) for i in range(3)) == blob
        st.close()

    @pytest.mark.parametrize("entry", ["span", "piece"])
    def test_a_piece_recorded_earlier_is_staged_from_disk(self, tmp_path,
                                                          entry):
        """A retry over surviving storage: the racer's bytes of a recorded
        piece were never checked, the store's were. The sink gets the
        store's, read and copied on the storage thread."""
        from dragonfly2_tpu.tpu.hbm_sink import SinkBufferPool

        blob = os.urandom(2 * self.PIECE)
        infos = self._infos(blob)
        pool = SinkBufferPool()
        first = self._conductor(tmp_path, blob, pool)
        first.storage.write_piece(0, 0, blob[:self.PIECE], infos[0].digest)
        first.device_ingest.close()
        conductor = self._conductor(tmp_path, blob, pool)
        assert not conductor.ready
        sink = conductor.device_ingest
        wire = bytearray(blob)
        wire[11] ^= 0x10                      # piece 0 arrives altered

        async def go():
            if entry == "span":
                placed, corrupt, _ = await conductor.on_span_from_peer(
                    "parent", infos, bytes(wire), 1)
                assert sorted(placed) == [0, 1] and not corrupt
            else:
                assert await conductor._land_piece(
                    0, 0, bytes(wire[:self.PIECE]), 1, source="parent",
                    piece_digest=infos[0].digest)
                assert await conductor._land_piece(
                    1, self.PIECE, blob[self.PIECE:], 1, source="parent",
                    piece_digest=infos[1].digest)
            await asyncio.to_thread(sink.drain, 10)

        asyncio.run(go())
        assert not conductor.sink_error
        assert b"".join(bytes(a) for a in sink._shard_arrays) == blob
        by_path = {e[3]: e[4] for e in self._staged(conductor)}
        assert by_path["disk"] == self.PIECE
        assert sum(by_path.values()) == len(blob)
        assert conductor.storage is first.storage
        conductor.storage.close()


class TestUploadLimiterOrder:
    def test_buffered_branch_acquires_before_read(self, tmp_path):
        """Satellite: the buffered upload branch must acquire the rate
        limiter BEFORE buffering the range (the sendfile branch always
        did) — a rate-limited seed otherwise reads MiBs it then sits on
        for the whole token wait."""
        import aiohttp

        from dragonfly2_tpu.daemon.upload_server import UploadServer

        order = []
        payload = b"z" * 65536

        class _StubTask:
            class _Md:
                content_length = -1      # unknown length -> buffered branch
            md = _Md()

            def has_range(self, start, length):
                return start + length <= len(payload)

            def read_range(self, start, length):
                order.append("read")
                return payload[start:start + length]

        class _StubMgr:
            def get(self, task_id):
                return _StubTask()

        srv = UploadServer(_StubMgr(), host="127.0.0.1")

        class _RecordingLimiter:
            async def acquire(self, n):
                order.append("acquire")

        srv.limiter = _RecordingLimiter()

        async def go():
            await srv.start()
            try:
                async with aiohttp.ClientSession() as s:
                    url = (f"http://127.0.0.1:{srv.port}/download/"
                           f"abc/{'a' * 64}")
                    async with s.get(url, headers={"Range": "bytes=0-1023"},
                                     params={"peerId": "p"}) as resp:
                        assert resp.status == 206
                        assert await resp.read() == payload[:1024]
            finally:
                await srv.stop()

        asyncio.run(go())
        assert order == ["acquire", "read"]

    def test_evicted_task_refunds_tokens_on_404(self):
        """Acquire-before-read must not let 404s for just-evicted tasks
        drain the rate budget: the bytes were never moved, so the tokens
        go back (same contract as acquire's cancel path)."""
        import aiohttp

        from dragonfly2_tpu.daemon.upload_server import UploadServer

        order = []

        class _GoneTask:
            class _Md:
                content_length = -1
            md = _Md()

            def has_range(self, start, length):
                return True

            def read_range(self, start, length):
                raise DFError(Code.CLIENT_STORAGE_ERROR,
                              "range read failed: data file gone")

        class _StubMgr:
            def get(self, task_id):
                return _GoneTask()

        srv = UploadServer(_StubMgr(), host="127.0.0.1")

        class _RecordingLimiter:
            async def acquire(self, n):
                order.append(("acquire", n))

            def refund(self, n):
                order.append(("refund", n))

        srv.limiter = _RecordingLimiter()

        async def go():
            await srv.start()
            try:
                async with aiohttp.ClientSession() as s:
                    url = (f"http://127.0.0.1:{srv.port}/download/"
                           f"abc/{'a' * 64}")
                    async with s.get(url, headers={"Range": "bytes=0-1023"},
                                     params={"peerId": "p"}) as resp:
                        assert resp.status == 404
            finally:
                await srv.stop()

        asyncio.run(go())
        assert order == [("acquire", 1024), ("refund", 1024)]


class TestCorruptAccounting:
    def test_corrupt_counted_journaled_and_named(self, tmp_path):
        """Satellite: a span digest mismatch is no longer an invisible
        log.debug — df_p2p_piece_total{result="corrupt"} counts it, the
        flight journal records the sending parent, and dfdiag's verdict
        names it."""
        from test_faults import TestPieceWireChaos

        from dragonfly2_tpu.common import faultgate
        from dragonfly2_tpu.common.metrics import REGISTRY
        from dragonfly2_tpu.idl.messages import DownloadRequest
        from dragonfly2_tpu.tools.dfdiag import verdict

        data = os.urandom((9 << 20) + 333)
        corrupt_ctr = REGISTRY.counter("df_p2p_piece_total", "x", ("result",))

        def count() -> float:
            return corrupt_ctr.value("corrupt")

        async def go():
            seed, leecher, url, task_id = \
                await TestPieceWireChaos()._p2p_pair(tmp_path, data)
            before = count()
            script = faultgate.arm("piece.wire", "corrupt", n=1)
            try:
                async for _ in leecher.ptm.start_file_task(DownloadRequest(
                        url=url, output=str(tmp_path / "out.bin"),
                        disable_back_source=True, timeout_s=60.0)):
                    pass
                assert (tmp_path / "out.bin").read_bytes() == data
                assert script.fired == 1
                assert count() == before + 1
                flight = leecher.flight_recorder.get(task_id)
                summary = flight.summarize()
                assert sum(summary["corrupt_pieces"].values()) == 1
                (parent,) = summary["corrupt_pieces"]
                assert parent            # a real peer id, not origin
                assert "digest verification" in verdict(summary)
            finally:
                await leecher.stop()
                await seed.stop()

        asyncio.run(go())


class TestZeroStallE2E:
    def test_saturated_fanout_keeps_loop_lag_under_threshold(self, tmp_path):
        """Acceptance: under a saturated fan-out (3 leechers x 4 workers
        against one 6-slot seed) no multi-MiB digest traversal runs on the
        event loop in the P2P landing path, and the health plane's
        df_loop_lag_max_seconds high-water stays under the stall
        threshold."""
        from test_daemon_e2e import daemon_config
        from test_p2p import (ScriptedScheduler, ScriptedSession,
                              parent_addr, seed_daemon_with)

        from dragonfly2_tpu.common.health import PLANE
        from dragonfly2_tpu.daemon.daemon import Daemon
        from dragonfly2_tpu.idl.messages import (DownloadRequest, PeerPacket,
                                                 RegisterResult, SizeScope)

        data = os.urandom(16 << 20)
        loop_thread = {}
        big_on_loop = []
        real_hash = digestlib.hash_bytes

        def spying_hash(algo, buf):
            if len(buf) >= (1 << 20) \
                    and threading.get_ident() == loop_thread.get("id"):
                big_on_loop.append((algo, len(buf)))
            return real_hash(algo, buf)

        real_update = digestlib.Hasher.update

        def spying_update(self, chunk):
            if len(chunk) >= (1 << 20) \
                    and threading.get_ident() == loop_thread.get("id"):
                big_on_loop.append((self.algo, len(chunk)))
            return real_update(self, chunk)

        async def go():
            loop_thread["id"] = threading.get_ident()
            seed, origin, url, task_id, seed_peer = await seed_daemon_with(
                tmp_path, data)
            await origin.cleanup()      # the mesh is the only source
            leechers = []
            for i in range(3):
                cfg = daemon_config(tmp_path, f"leech{i}")

                def make_session(conductor, _seed=seed, _sp=seed_peer):
                    packet = PeerPacket(task_id=conductor.task_id,
                                        src_peer_id=conductor.peer_id,
                                        main_peer=parent_addr(_seed, _sp))
                    return ScriptedSession(RegisterResult(
                        task_id=conductor.task_id,
                        size_scope=SizeScope.NORMAL), [packet])

                d = Daemon(cfg)
                d._scheduler_factory = \
                    lambda _d, mk=make_session: ScriptedScheduler(mk)
                await d.start()
                leechers.append(d)
            PLANE.max_lag_s = 0.0       # fresh high-water for this run
            try:
                async def pull(d, i):
                    out = tmp_path / f"out{i}.bin"
                    async for _ in d.ptm.start_file_task(DownloadRequest(
                            url=url, output=str(out),
                            disable_back_source=True, timeout_s=120.0)):
                        pass
                    assert out.read_bytes() == data

                await asyncio.gather(*(pull(d, i)
                                       for i, d in enumerate(leechers)))
                assert PLANE.active, "health monitor must be sampling"
            finally:
                for d in leechers:
                    await d.stop()
                await seed.stop()

        import unittest.mock as mock
        with mock.patch.object(digestlib, "hash_bytes", spying_hash), \
                mock.patch.object(digestlib.Hasher, "update", spying_update):
            asyncio.run(go())
        assert not big_on_loop, (
            f"multi-MiB digest traversal ran ON the event loop: "
            f"{big_on_loop[:5]}")
        assert PLANE.max_lag_s < PLANE.cfg.stall_threshold_s, (
            f"loop lag high-water {PLANE.max_lag_s:.3f}s crossed the "
            f"stall threshold under fan-out")


if __name__ == "__main__":
    pytest.main([__file__, "-v"])
