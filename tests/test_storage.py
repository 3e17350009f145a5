"""Stage-2 tests: piece store write/read/verify, reload, GC, subtasks."""

import os

import pytest

from dragonfly2_tpu.common import digest as digestlib
from dragonfly2_tpu.common.errors import Code, DFError
from dragonfly2_tpu.common.piece import compute_piece_size, piece_count, piece_range
from dragonfly2_tpu.idl.messages import TaskType
from dragonfly2_tpu.storage.manager import StorageConfig, StorageManager
from dragonfly2_tpu.storage.metadata import TaskMetadata


def make_manager(tmp_path, **kw):
    return StorageManager(StorageConfig(data_dir=str(tmp_path / "data"), **kw))


def fill_task(mgr, task_id: str, content: bytes, task_type=TaskType.STANDARD):
    size = compute_piece_size(len(content))
    n = piece_count(len(content), size)
    ts = mgr.register_task(TaskMetadata(
        task_id=task_id, task_type=task_type, url=f"http://o/{task_id}",
        content_length=len(content), total_piece_count=n, piece_size=size))
    for i in range(n):
        off, ln = piece_range(i, size, len(content))
        ts.write_piece(i, off, content[off:off + ln])
    ts.mark_done(success=True, digest=digestlib.for_bytes("sha256", content))
    return ts


class TestTaskStorage:
    def test_write_read_roundtrip(self, tmp_path):
        mgr = make_manager(tmp_path)
        content = os.urandom(300_000)
        ts = fill_task(mgr, "a" * 64, content)
        assert ts.read_piece(0)[:16] == content[:16]
        got = b"".join(ts.read_piece(p.num) for p in ts.piece_infos())
        assert got == content
        assert ts.verify_content()

    def test_digest_mismatch_rejected(self, tmp_path):
        mgr = make_manager(tmp_path)
        ts = mgr.register_task(TaskMetadata(task_id="b" * 64))
        bad = "crc32c:" + "0" * 8
        with pytest.raises(DFError) as ei:
            ts.write_piece(0, 0, b"data", bad)
        assert ei.value.code == Code.CLIENT_DIGEST_MISMATCH
        assert ts.piece_infos() == []

    def test_duplicate_piece_idempotent(self, tmp_path):
        mgr = make_manager(tmp_path)
        ts = mgr.register_task(TaskMetadata(task_id="c" * 64))
        m1 = ts.write_piece(0, 0, b"xxxx")
        m2 = ts.write_piece(0, 0, b"yyyy")  # ignored
        assert m1 is m2
        assert ts.read_piece(0) == b"xxxx"

    def test_missing_piece(self, tmp_path):
        mgr = make_manager(tmp_path)
        ts = mgr.register_task(TaskMetadata(task_id="d" * 64))
        with pytest.raises(DFError) as ei:
            ts.read_piece(7)
        assert ei.value.code == Code.CLIENT_PIECE_NOT_FOUND

    def test_store_to_output(self, tmp_path):
        mgr = make_manager(tmp_path)
        content = os.urandom(50_000)
        ts = fill_task(mgr, "e" * 64, content)
        out = tmp_path / "out.bin"
        ts.store_to(str(out))
        assert out.read_bytes() == content
        # ranged store
        out2 = tmp_path / "out2.bin"
        ts.store_to(str(out2), range_start=100, range_length=500)
        assert out2.read_bytes() == content[100:600]


class TestReload:
    def test_completed_tasks_survive_restart(self, tmp_path):
        mgr = make_manager(tmp_path)
        content = os.urandom(100_000)
        fill_task(mgr, "f" * 64, content)
        # partial task: registered but never done
        mgr.register_task(TaskMetadata(task_id="9" * 64)).persist()

        mgr2 = make_manager(tmp_path)
        ts = mgr2.find_completed_task("f" * 64)
        assert ts is not None
        got = b"".join(ts.read_piece(p.num) for p in ts.piece_infos())
        assert got == content
        # partial was discarded as invalid
        assert mgr2.get("9" * 64) is None

    def test_find_partial_completed(self, tmp_path):
        mgr = make_manager(tmp_path)
        fill_task(mgr, "a1" + "0" * 62, os.urandom(10_000))
        assert mgr.find_partial_completed_task("a1" + "0" * 62, 0, 5000) is not None
        assert mgr.find_partial_completed_task("a1" + "0" * 62, 9000, 5000) is None
        assert mgr.find_partial_completed_task("nope", 0, 10) is None


class TestGC:
    def test_ttl_eviction_spares_persistent(self, tmp_path):
        mgr = make_manager(tmp_path, task_ttl_s=0.0)
        fill_task(mgr, "1" * 64, b"x" * 1000)
        fill_task(mgr, "2" * 64, b"y" * 1000, task_type=TaskType.PERSISTENT)
        import time
        time.sleep(0.01)
        n = mgr.try_gc()
        assert n == 1
        assert mgr.get("1" * 64) is None
        assert mgr.get("2" * 64) is not None

    def test_capacity_eviction_oldest_first(self, tmp_path):
        mgr = make_manager(tmp_path, capacity_bytes=10_000,
                           disk_gc_high_ratio=0.5, disk_gc_low_ratio=0.3)
        ts_old = fill_task(mgr, "3" * 64, b"a" * 4000)
        ts_old.md.access_time -= 100
        fill_task(mgr, "4" * 64, b"b" * 4000)
        n = mgr.try_gc()  # 8000/10000 > 0.5 high: evict to <=3000
        assert n >= 1
        assert mgr.get("3" * 64) is None  # oldest went first


class TestSubtask:
    def test_subtask_shares_parent_file(self, tmp_path):
        mgr = make_manager(tmp_path)
        parent_id = "p" * 64
        sub = mgr.register_subtask(TaskMetadata(
            task_id="s" * 64, parent_task_id=parent_id,
            range_start=1000, range_length=2000, content_length=2000))
        sub.write_piece(0, 0, b"A" * 1500)
        sub.write_piece(1, 1500, b"B" * 500)
        sub.mark_done(success=True)
        assert sub.read_piece(0) == b"A" * 1500
        # bytes physically live at parent's offset
        parent = mgr.get(parent_id)
        assert parent.read_range(1000, 4) == b"AAAA"
        assert parent.read_range(2500, 4) == b"BBBB"
        out = tmp_path / "sub.bin"
        sub.store_to(str(out))
        assert out.read_bytes() == b"A" * 1500 + b"B" * 500


class TestNative:
    def test_native_crc32c_matches_python(self):
        from dragonfly2_tpu.common.digest import _crc32c_py
        from dragonfly2_tpu.storage import native
        if not native.available():
            pytest.skip("native lib not built")
        data = os.urandom(100_000)
        assert native.hash_bytes("crc32c", data) == f"{_crc32c_py(data):08x}"

    def test_native_sha_md5_match_hashlib(self):
        import hashlib
        from dragonfly2_tpu.storage import native
        if not native.available():
            pytest.skip("native lib not built")
        data = os.urandom(64 * 1024 + 17)
        assert native.hash_bytes("sha256", data) == hashlib.sha256(data).hexdigest()
        assert native.hash_bytes("md5", data) == hashlib.md5(data).hexdigest()


class TestNativePieceIO:
    """native/dfnative.cc piece IO (VERDICT carried item: the bindings'
    'aligned file piece IO' claim must match the exports)."""

    def test_piece_write_read_roundtrip(self, tmp_path):
        from dragonfly2_tpu.storage import native
        if not native.available():
            pytest.skip("native lib not built")
        path = str(tmp_path / "f.bin")
        open(path, "wb").write(b"\0" * 256)
        data = os.urandom(100)
        crc = native.piece_write(path, 50, data)
        assert crc is not None and len(crc) == 8
        # fused crc matches the standalone hash
        from dragonfly2_tpu.common import digest as digestlib
        assert digestlib.hash_bytes("crc32c", data) == crc
        assert native.piece_read(path, 50, 100) == data
        # short read past EOF returns what exists
        assert len(native.piece_read(path, 200, 100)) == 56

    def test_piece_write_missing_file_raises(self, tmp_path):
        from dragonfly2_tpu.storage import native
        if not native.available():
            pytest.skip("native lib not built")
        with pytest.raises(OSError):
            native.piece_write(str(tmp_path / "nope.bin"), 0, b"x")

    def test_store_fused_path_detects_corruption(self, tmp_path):
        """A wrong crc32c digest is caught by the fused write pass and the
        piece is NOT recorded (the region stays absent)."""
        from dragonfly2_tpu.storage import native
        if not native.available():
            pytest.skip("native lib not built")
        from dragonfly2_tpu.common.errors import DFError
        from dragonfly2_tpu.storage.metadata import TaskMetadata
        from dragonfly2_tpu.storage.store import TaskStorage
        md = TaskMetadata(task_id="t" * 64, url="u", content_length=200,
                          total_piece_count=2, piece_size=100)
        ts = TaskStorage(str(tmp_path), md)
        with pytest.raises(DFError):
            ts.write_piece(0, 0, b"a" * 100,
                           piece_digest="crc32c:00000000")
        assert 0 not in ts.md.pieces
        assert not ts.has_range(0, 100)


# ----------------------------------------------------------------------
# landings that stage into a device sink (``stage=``: the sink's lease)
# ----------------------------------------------------------------------

PIECE = 64 * 1024
KEEP = 0xAA                                  # what the sink's buffer held


def _sink(n: int):
    """A device sink whose host buffer is ``KEEP`` all over, so a byte a
    landing did not stage is seen to be as it was."""
    import numpy as np

    from dragonfly2_tpu.tpu.hbm_sink import DeviceIngest, SinkBufferPool

    di = DeviceIngest(n, devices=[object()], pool=SinkBufferPool(),
                      device_put_fn=lambda v, d: np.array(v, copy=True))
    di.host[:] = KEEP
    return di


def _span_spec(blob: bytes, algo: str = ""):
    algo = algo or digestlib.preferred_piece_algo()
    return [(i, off, len(blob[off:off + PIECE]),
             digestlib.for_bytes(algo, blob[off:off + PIECE]))
            for i, off in enumerate(range(0, len(blob), PIECE))]


def _force(monkeypatch, path: str) -> None:
    """Which traversal ``write_span`` takes: the fused native call that
    also stages; a library built before that export (native landing, the
    copy in Python); no library at all."""
    from dragonfly2_tpu.storage import native

    if path == "native":
        if not (native.available() and getattr(
                native.load(), "_df_has_span_stage", False)):
            pytest.skip("native lib not built")
        return
    monkeypatch.setattr(native, "span_write_staged", lambda *a, **k: None)
    if path == "python":
        monkeypatch.setattr(native, "span_write", lambda *a, **k: None)
    elif not native.available():
        pytest.skip("native lib not built")


PATHS = ("native", "stale_so", "python")


class TestStagedLanding:
    def _storage(self, tmp_path, name="st"):
        from dragonfly2_tpu.storage.store import TaskStorage
        return TaskStorage(str(tmp_path / name), TaskMetadata(
            task_id=name * 32, url="test://staged"))

    @pytest.mark.parametrize("path", PATHS)
    def test_verified_pieces_reach_the_sink_a_corrupt_one_never(
            self, tmp_path, monkeypatch, path):
        _force(monkeypatch, path)
        blob = os.urandom(4 * PIECE + 321)
        spec = _span_spec(blob)
        wire = bytearray(blob)
        wire[PIECE + 9] ^= 0x40              # piece 1 arrives corrupt
        di = _sink(len(blob))
        ts = self._storage(tmp_path)
        with di.lease() as lease:
            metas, corrupt, took = ts.write_span(spec, bytes(wire),
                                                 stage=lease)
            assert lease.error is None
            assert lease.nbytes == len(blob) - PIECE and lease.seconds > 0
        assert corrupt == [1] and [m.num for m in metas] == [0, 2, 3, 4]
        assert took == ("python" if path == "python" else "native")
        host = bytes(di.host)
        assert host[:PIECE] == blob[:PIECE]
        assert host[PIECE:2 * PIECE] == bytes([KEEP]) * PIECE   # untouched
        assert host[2 * PIECE:] == blob[2 * PIECE:]
        # the retry's good copy is staged like any other piece
        with di.lease() as lease:
            metas, corrupt, _ = ts.write_span(
                [spec[1]], blob[PIECE:2 * PIECE], base=PIECE, stage=lease)
            assert lease.nbytes == PIECE
        assert [m.num for m in metas] == [1] and not corrupt
        assert bytes(di.host) == blob
        ts.close()
        di.close()

    def test_native_and_python_stage_the_same_bytes(self, tmp_path,
                                                    monkeypatch):
        from dragonfly2_tpu.storage import native
        if not (native.available() and getattr(
                native.load(), "_df_has_span_stage", False)):
            pytest.skip("native lib not built")
        blob = os.urandom(5 * PIECE + 77)
        spec = _span_spec(blob)
        spec[3] = spec[3][:3] + ("",)        # a piece that carries no digest
        wire = bytearray(blob)
        wire[4 * PIECE + 1] ^= 1             # and a corrupt one
        hosts, recorded = [], []
        for path in PATHS:
            with monkeypatch.context() as mp:
                _force(mp, path)
                di = _sink(len(blob))
                ts = self._storage(tmp_path, path[0])
                with di.lease() as lease:
                    metas, corrupt, _ = ts.write_span(spec, bytes(wire),
                                                      stage=lease)
                hosts.append(bytes(di.host))
                recorded.append(([(m.num, m.digest) for m in metas], corrupt,
                                 lease.nbytes))
                ts.close()
                di.close()
        assert hosts[0] == hosts[1] == hosts[2]
        assert recorded[0] == recorded[1] == recorded[2]
        assert recorded[0][1] == [4]

    @pytest.mark.parametrize("path", PATHS)
    def test_an_already_recorded_piece_is_not_copied_again(
            self, tmp_path, monkeypatch, path):
        """An endgame duplicate's span carries unverified bytes of a piece
        that is already recorded: skipped on disk, and skipped in the
        sink."""
        _force(monkeypatch, path)
        blob = os.urandom(3 * PIECE)
        spec = _span_spec(blob)
        di = _sink(len(blob))
        ts = self._storage(tmp_path)
        ts.write_piece(1, PIECE, blob[PIECE:2 * PIECE], spec[1][3])
        racer = bytearray(blob)
        racer[PIECE + 3] ^= 0xFF
        with di.lease() as lease:
            metas, corrupt, _ = ts.write_span(spec, bytes(racer),
                                              stage=lease)
            assert lease.nbytes == 2 * PIECE
        assert [m.num for m in metas] == [0, 2] and not corrupt
        host = bytes(di.host)
        assert host[:PIECE] == blob[:PIECE]
        assert host[PIECE:2 * PIECE] == bytes([KEEP]) * PIECE
        assert host[2 * PIECE:] == blob[2 * PIECE:]
        ts.close()
        di.close()

    def test_a_digest_the_crc_path_cannot_check_is_staged_once_verified(
            self, tmp_path):
        """sha256 piece digests take the Python traversal whatever is
        built; the copy still follows the verdict."""
        blob = os.urandom(2 * PIECE)
        spec = _span_spec(blob, "sha256")
        wire = bytearray(blob)
        wire[5] ^= 2
        di = _sink(len(blob))
        ts = self._storage(tmp_path)
        with di.lease() as lease:
            metas, corrupt, took = ts.write_span(spec, bytes(wire),
                                                 stage=lease)
        assert took == "python" and corrupt == [0]
        assert bytes(di.host) == bytes([KEEP]) * PIECE + blob[PIECE:]
        ts.close()
        di.close()

    @pytest.mark.parametrize("algo,staged", [("crc32c", 0),
                                             ("sha256", PIECE)])
    def test_a_range_beyond_the_sink_fails_the_lease_not_the_landing(
            self, tmp_path, algo, staged):
        """Held to the digest it lands under. Left to
        ``preferred_piece_algo`` the pieces carry crc32c where
        ``native/build`` exists and zlib's crc32 where it does not yet (a
        fresh checkout under several workers: a benchmark test in another
        worker builds it), and the two stage differently, which is how
        this test failed there and passed alone. A run the crc pass can
        check asks the lease for the whole run's address at once: refused,
        and nothing is staged. Any other digest stages piece by piece, so
        the piece that fits the sink is staged before the one beyond it
        fails the lease. Either way the landing is whole."""
        blob = os.urandom(2 * PIECE)
        di = _sink(PIECE)                    # a sink too short for the span
        ts = self._storage(tmp_path)
        with di.lease() as lease:
            metas, corrupt, _ = ts.write_span(_span_spec(blob, algo), blob,
                                              stage=lease)
            assert isinstance(lease.error, ValueError)
            assert lease.nbytes == staged
        assert [m.num for m in metas] == [0, 1] and not corrupt
        assert ts.read_piece(1) == blob[PIECE:]          # on disk all the same
        assert bytes(di.host) == (blob[:PIECE] if staged
                                  else bytes([KEEP]) * PIECE)
        ts.close()
        di.close()

    @pytest.mark.parametrize("kind", ["task", "subtask"])
    def test_write_piece_stages_after_the_verdict(self, tmp_path, kind):
        blob = os.urandom(2 * PIECE)
        spec = _span_spec(blob)
        if kind == "task":
            ts = self._storage(tmp_path)
        else:
            ts = make_manager(tmp_path).register_subtask(TaskMetadata(
                task_id="s" * 64, parent_task_id="p" * 64, range_start=512,
                range_length=len(blob), content_length=len(blob)))
        di = _sink(len(blob))
        bad = bytearray(blob[:PIECE])
        bad[0] ^= 1
        with di.lease() as lease:
            with pytest.raises(DFError) as ei:
                ts.write_piece(0, 0, bytes(bad), spec[0][3], stage=lease)
            assert ei.value.code == Code.CLIENT_DIGEST_MISMATCH
            assert lease.nbytes == 0
            ts.write_piece(0, 0, blob[:PIECE], spec[0][3], stage=lease)
            assert lease.nbytes == PIECE
            # recorded already: returned as it is, and copied nowhere
            ts.write_piece(0, 0, bytes(bad), "", stage=lease)
            assert lease.nbytes == PIECE and not lease.took(PIECE)
            ts.write_piece(1, PIECE, blob[PIECE:], spec[1][3], stage=lease)
        assert bytes(di.host) == blob
        di.close()
