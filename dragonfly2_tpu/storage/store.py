"""TaskStorage: the piece-addressed store for one task.

Role parity: reference ``client/daemon/storage/local_storage.go`` (file-per-
task driver) and ``local_storage_subtask.go`` (ranged sub-tasks share the
parent's file). Pieces are written at their offsets with per-piece digest
verification; reads serve other peers (upload server) and the final sink.

Piece hashing rides the native C++ crc32c path when the library is built
(see native.py); file IO is positioned pread/pwrite on a per-task CACHED
fd (opening the data file per piece was a measurable per-piece tax at
fan-out), issued from the dedicated storage executor (io_executor.py) —
never the event loop.

``write_span`` is the one-pass landing path: a whole contiguous
downloaded span costs ONE buffer traversal (pwrite + per-piece crc32c
fused in the native library, or one pwrite + off-loop hashing in the
Python fallback) and one write syscall chain instead of N of each.

A task with a device sink hands its landings a ``stage`` (the sink's
``tpu.hbm_sink.StageLease``): each piece is copied into the sink's host
buffer on this thread, in the same hop, once it has verified and is about
to be recorded, and only then. A corrupt piece's bytes never reach the
sink and an already-recorded piece is not copied again.
"""

from __future__ import annotations

import bisect
import contextlib
import logging
import os
import shutil
import threading
import time

from ..common import digest as digestlib
from ..common.errors import Code, DFError
from . import native
from .metadata import DATA_FILE, TaskMetadata, PieceMeta

log = logging.getLogger("df.storage.task")


def _pread_all(fd: int, length: int, offset: int) -> bytes:
    """pread ``length`` bytes at ``offset``; short only at EOF."""
    out = os.pread(fd, length, offset)
    if len(out) == length or not out:
        return out
    parts = [out]
    got = len(out)
    while got < length:
        b = os.pread(fd, length - got, offset + got)
        if not b:
            break
        parts.append(b)
        got += len(b)
    return b"".join(parts)


def _crc_expect(algo: str, want: str) -> int:
    """What ``df_span_write_staged`` compares a piece's crc32c with: -1
    for no digest, else the digest's value, or one that no crc equals when
    ``want`` is not the eight lower-case hex digits that ``write_span``'s
    own string comparison would accept."""
    if not algo:
        return -1
    try:
        value = int(want, 16)
    except ValueError:
        return 1 << 32
    return value if f"{value:08x}" == want else 1 << 32


def _pwrite_all(fd: int, data, offset: int) -> None:
    """pwrite the whole buffer (kernel may write short); EINTR-safe via
    os.pwrite's PEP 475 retry."""
    view = memoryview(data)
    while len(view):
        n = os.pwrite(fd, view, offset)
        view = view[n:]
        offset += n


class TaskStorage:
    """One task's on-disk state. Thread-safe for concurrent piece writes."""

    def __init__(self, task_dir: str, metadata: TaskMetadata,
                 castore=None):
        self.dir = task_dir
        self.md = metadata
        # content-addressed index (storage/castore.py): every verified
        # piece this task lands is registered by digest so other tasks
        # can place (not transfer) identical bytes; None = dedupe off
        self.castore = castore
        self._lock = threading.Lock()
        self._fd: int | None = None        # cached O_RDWR fd (lazy)
        self._fd_users = 0                 # leases out via _data_fd()
        self._fd_close_deferred = False    # close() arrived mid-lease
        # covered_prefix memo: (piece_count, merged [start, end) spans)
        self._cover_cache: tuple[int, list[list[int]]] | None = None
        self._data_path = os.path.join(task_dir, DATA_FILE)
        os.makedirs(task_dir, exist_ok=True)
        if not os.path.exists(self._data_path):
            with open(self._data_path, "wb"):
                pass

    @contextlib.contextmanager
    def _data_fd(self):
        """Refcounted lease on the task's cached data fd. Piece IO is
        pread/pwrite against this one descriptor — per-call open() was
        pure per-piece overhead and capped the storage executor at the
        dentry lock, not the disk.

        The refcount exists because close() (GC eviction, destroy) can
        race in-flight IO on the storage executor: closing the fd under a
        lease would at best EBADF the IO and at worst — once the fd
        number is reused by another task's open() — land the bytes in the
        WRONG task's file. close() during a lease is deferred to the last
        releaser; an acquire after destroy() re-opens the unlinked path
        and fails safe (FileNotFoundError), same as the per-call-open
        behavior this cache replaced. While a close is DEFERRED the
        cached fd is doomed — it may point at an already-unlinked inode
        (destroy closes then rmtrees), so new leases must not extend it:
        they open a private fd from the path, which fails safe post-
        destroy instead of silently writing bytes that vanish with the
        inode."""
        private = None
        with self._lock:
            if self._fd_close_deferred:
                private = True           # opened below, outside the lock
            else:
                if self._fd is None:
                    self._fd = os.open(self._data_path, os.O_RDWR)
                fd = self._fd
                self._fd_users += 1
        if private:
            fd = os.open(self._data_path, os.O_RDWR)
            try:
                yield fd
            finally:
                try:
                    os.close(fd)
                except OSError:
                    pass
            return
        try:
            yield fd
        finally:
            with self._lock:
                self._fd_users -= 1
                close_now = (self._fd_close_deferred
                             and self._fd_users == 0
                             and self._fd is not None)
                if close_now:
                    fd, self._fd = self._fd, None
                    self._fd_close_deferred = False
            if close_now:
                try:
                    os.close(fd)
                except OSError:
                    pass

    def close(self) -> None:
        """Drop the cached fd (destroy() and GC call this; reopening after
        close is transparent). With IO in flight the close is deferred to
        the last lease holder — never yanked out from under a pread/pwrite."""
        with self._lock:
            if self._fd is None:
                self._fd_close_deferred = False
                return
            if self._fd_users:
                self._fd_close_deferred = True
                return
            fd, self._fd = self._fd, None
        try:
            os.close(fd)
        except OSError:
            pass

    # -- writes --------------------------------------------------------

    def write_piece(self, num: int, offset: int, data: bytes | memoryview,
                    piece_digest: str = "", *, cost_ms: int = 0,
                    source: str = "", pre_verified: bool = False,
                    stage=None) -> PieceMeta:
        """Verify + persist one piece. Idempotent per piece number.

        ``stage``: the device sink's lease (module docstring); the piece
        is copied there once verified, unless it was already recorded.

        ``pre_verified`` skips the redundant re-hash when the transport
        already checked the bytes against ``piece_digest`` (the P2P
        downloader does) — hashing each piece twice shows up directly in
        end-to-end GB/s.

        Hot path: when the piece digest is crc32c (the default), the
        native library pwrite()s the piece while folding the bytes into
        the crc in the SAME pass (``native.piece_write``) — one memory
        traversal for verify+persist instead of two. A fused-path
        mismatch is detected after the bytes hit the file, which is safe:
        the piece is never recorded in ``md.pieces``, so the region stays
        "absent" (never served, re-written by the retry)."""
        with self._lock:
            existing = self.md.pieces.get(num)
            if existing is not None:
                return existing
        algo = want = ""
        if piece_digest:
            algo, want = digestlib.parse(piece_digest)
        crc_capable = not piece_digest or algo == "crc32c"
        fused_crc = None
        if crc_capable:
            try:
                # fd-based fused span write (one piece = a span of one)
                # first — cached fd, no per-call open; fall back to the
                # path-based export for a stale .so
                with self._data_fd() as fd:
                    crcs = native.span_write(fd, offset, data,
                                             [len(data)])
                fused_crc = (crcs[0] if crcs is not None
                             else native.piece_write(self._data_path,
                                                     offset, data))
            except OSError as exc:
                raise DFError(Code.CLIENT_STORAGE_ERROR,
                              f"piece {num} write failed: {exc}") from None
        if fused_crc is not None:
            if not piece_digest:
                piece_digest = f"crc32c:{fused_crc}"
            elif fused_crc != want:
                # free double-check even for pre_verified pieces (the crc
                # came out of the write pass anyway)
                raise DFError(Code.CLIENT_DIGEST_MISMATCH,
                              f"piece {num} digest mismatch")
        else:
            if piece_digest:
                if not pre_verified and not digestlib.verify(piece_digest,
                                                             data):
                    raise DFError(Code.CLIENT_DIGEST_MISMATCH,
                                  f"piece {num} digest mismatch")
            else:
                piece_digest = digestlib.for_bytes(
                    digestlib.preferred_piece_algo(), data)
            try:
                with self._data_fd() as fd:
                    _pwrite_all(fd, data, offset)
            except OSError as exc:
                raise DFError(Code.CLIENT_STORAGE_ERROR,
                              f"piece {num} write failed: {exc}") from None
        if stage is not None:
            stage.copy(offset, data)
        meta = PieceMeta(num=num, start=offset, size=len(data),
                         digest=piece_digest, cost_ms=cost_ms, source=source)
        with self._lock:
            self.md.pieces[num] = meta
            self.md.access_time = time.time()
        if self.castore is not None:
            self.castore.add_piece(self.md.task_id, num, offset,
                                   len(data), piece_digest)
        return meta

    def write_span(self, pieces: list[tuple[int, int, int, str]], data,
                   *, base: int | None = None, cost_ms: int = 0,
                   source: str = "", stage=None,
                   ) -> tuple[list[PieceMeta], list[int], str]:
        """Land a whole contiguous downloaded span in ONE pass.

        ``pieces``: ``(num, offset, size, digest)`` in ascending offset
        order; ``data`` holds their bytes contiguously, ``data[i]`` being
        content offset ``base + i`` (``base`` defaults to the first
        piece's offset). Returns ``(landed_metas, corrupt_nums, path)``
        where ``path`` names the traversal used (``"native"`` fused
        pwrite+crc32c, ``"python"`` one pwrite + off-loop hashing).

        Per-piece verdicts: a digest-mismatched piece is returned in
        ``corrupt_nums`` — its bytes hit the file but are never recorded
        in ``md.pieces``, so the region stays "absent" (never served,
        re-written by the retry) and its groupmates land normally.
        Already-recorded pieces (endgame duplicates) are skipped without
        being re-written: overwriting a verified region with a racer's
        unverified bytes would let a corrupt duplicate trash good data.

        ``stage`` (the device sink's lease, module docstring) receives
        the pieces this call records, and no others: inside the native
        call, piece by piece as each crc matches, or by ``stage.copy``
        once the slice has verified.
        """
        if base is None:
            base = pieces[0][1]
        mv = memoryview(data)
        with self._lock:
            fresh = [p for p in pieces if p[0] not in self.md.pieces]
        # contiguous runs: normally one covering the whole span; a landed
        # duplicate mid-span splits it (each run is still one write+pass)
        runs: list[list[tuple[int, int, int, str]]] = []
        for p in fresh:
            if runs and runs[-1][-1][1] + runs[-1][-1][2] == p[1]:
                runs[-1].append(p)
            else:
                runs.append([p])
        metas: list[PieceMeta] = []
        corrupt: list[int] = []
        used_native = False
        for run in runs:
            run_off = run[0][1]
            sizes = [p[2] for p in run]
            run_len = sum(sizes)
            lo = run_off - base
            run_view = mv[lo:lo + run_len]
            digests = [digestlib.parse(p[3]) if p[3] else ("", "")
                       for p in run]
            crc_capable = all(a in ("", "crc32c") for a, _ in digests)
            crcs = None
            staged_s = None       # the native call staged: its copy seconds
            try:
                with self._data_fd() as fd:
                    dest = (stage.address(run_off, run_len)
                            if stage is not None and crc_capable else 0)
                    if dest:
                        done = native.span_write_staged(
                            fd, run_off, run_view, sizes,
                            [_crc_expect(*d) for d in digests], dest)
                        if done is not None:
                            crcs, staged_s = done
                    if crcs is None and crc_capable:
                        crcs = native.span_write(fd, run_off,
                                                 run_view, sizes)
                    if crcs is None:
                        _pwrite_all(fd, run_view, run_off)
            except OSError as exc:
                raise DFError(Code.CLIENT_STORAGE_ERROR,
                              f"span write @{run_off}+{run_len} failed: "
                              f"{exc}") from None
            pos = 0
            n_metas = len(metas)
            for i, (num, off, size, dg) in enumerate(run):
                piece_view = run_view[pos:pos + size]
                pos += size
                if crcs is not None:
                    used_native = True
                    if dg and crcs[i] != digests[i][1]:
                        corrupt.append(num)
                        continue
                    if not dg:
                        dg = f"crc32c:{crcs[i]}"
                else:
                    # python fallback: bytes already written above in one
                    # pwrite; verify by hashing the slice here — we are on
                    # the storage executor, never the event loop
                    if dg:
                        if not digestlib.verify(dg, piece_view):
                            corrupt.append(num)
                            continue
                    else:
                        dg = digestlib.for_bytes(
                            digestlib.preferred_piece_algo(), piece_view)
                if stage is not None and staged_s is None:
                    stage.copy(off, piece_view)
                metas.append(PieceMeta(num=num, start=off, size=size,
                                       digest=dg, cost_ms=cost_ms,
                                       source=source))
            if staged_s is not None:
                stage.account(staged_s,
                              sum(m.size for m in metas[n_metas:]))
        with self._lock:
            for meta in metas:
                self.md.pieces.setdefault(meta.num, meta)
            self.md.access_time = time.time()
        if self.castore is not None:
            for meta in metas:
                self.castore.add_piece(self.md.task_id, meta.num,
                                       meta.start, meta.size, meta.digest)
        return metas, corrupt, ("native" if used_native else "python")

    def adopt_from(self, src: "TaskStorage") -> None:
        """Adopt ``src``'s geometry + piece table — used when this task's
        data file has just become a hardlink of ``src``'s (content-
        identical, both immutable). Lives here so the lock discipline and
        the coverage-cache invalidation stay TaskStorage's own business:
        the piece table is replaced wholesale, and the covered_prefix
        memo (keyed on piece COUNT) would otherwise serve stale spans."""
        with self._lock:
            self.md.pieces = {
                num: PieceMeta(num=p.num, start=p.start, size=p.size,
                               digest=p.digest, source="cas")
                for num, p in src.md.pieces.items()}
            self.md.content_length = src.md.content_length
            self.md.total_piece_count = src.md.total_piece_count
            self.md.piece_size = src.md.piece_size
            self._cover_cache = None

    def mark_done(self, *, success: bool, content_length: int | None = None,
                  total_piece_count: int | None = None, digest: str = "") -> None:
        with self._lock:
            if content_length is not None:
                self.md.content_length = content_length
            if total_piece_count is not None:
                self.md.total_piece_count = total_piece_count
            if digest:
                self.md.digest = digest
            self.md.done = True
            self.md.success = success
            self.md.save(self.dir)
        if success and self.castore is not None:
            # content-identity dedupe: an identical completed task already
            # on disk absorbs this one's bytes via hardlink (castore.py);
            # runs here because mark_done already rides the storage
            # executor — never the event loop
            self.castore.on_task_complete(self)

    def persist(self) -> None:
        with self._lock:
            self.md.save(self.dir)

    # -- reads ---------------------------------------------------------

    def read_piece(self, num: int) -> bytes:
        meta = self.md.pieces.get(num)
        if meta is None:
            raise DFError(Code.CLIENT_PIECE_NOT_FOUND,
                          f"piece {num} not in task {self.md.task_id[:12]}")
        # one pread on the cached fd: no per-call open, no Python file
        # object, no intermediate copies
        try:
            with self._data_fd() as fd:
                data = _pread_all(fd, meta.size, meta.start)
        except OSError as exc:
            raise DFError(Code.CLIENT_STORAGE_ERROR,
                          f"piece {num} read failed: {exc}") from None
        if len(data) != meta.size:
            raise DFError(Code.CLIENT_STORAGE_ERROR,
                          f"short read piece {num}: {len(data)}/{meta.size}")
        self.md.access_time = time.time()
        return data

    def read_range(self, start: int, length: int) -> bytes:
        try:
            with self._data_fd() as fd:
                return _pread_all(fd, length, start)
        except OSError as exc:
            # evicted/destroyed task (or real IO failure): a typed error
            # the upload server maps to 404 instead of a bare 500
            raise DFError(Code.CLIENT_STORAGE_ERROR,
                          f"range read @{start}+{length} failed: "
                          f"{exc}") from None

    def covered_prefix(self, start: int, end: int) -> int:
        """How far recorded (verified) pieces contiguously cover from
        ``start``, clipped to ``end`` — the landed half of the relay
        plane's progress watermark (daemon/relay.py). Returns ``start``
        when the byte at ``start`` is not stored.

        Called per served chunk AND per progress wake by the streaming
        relay path, on the event loop — so the merged coverage spans are
        cached and rebuilt only when a piece lands (the piece table only
        ever grows, so the count is a valid cache key), making each call
        one bisect instead of an O(P log P) sort."""
        if end <= start:
            return start
        with self._lock:
            key = len(self.md.pieces)
            cache = self._cover_cache
            if cache is None or cache[0] != key:
                merged: list[list[int]] = []
                for s, e in sorted((p.start, p.start + p.size)
                                   for p in self.md.pieces.values()):
                    if merged and s <= merged[-1][1]:
                        if e > merged[-1][1]:
                            merged[-1][1] = e
                    else:
                        merged.append([s, e])
                cache = (key, merged)
                self._cover_cache = cache
        spans = cache[1]
        i = bisect.bisect_right(spans, [start, 1 << 62]) - 1
        if i < 0 or spans[i][1] <= start:
            return start
        return min(spans[i][1], end)

    def has_range(self, start: int, length: int) -> bool:
        """True if stored pieces fully cover [start, start+length)."""
        end = start + length
        covered = start
        with self._lock:
            spans = sorted((p.start, p.start + p.size)
                           for p in self.md.pieces.values())
        for s, e in spans:
            if s > covered:
                return False
            if e > covered:
                covered = e
            if covered >= end:
                return True
        return covered >= end

    def piece_infos(self, start_num: int = 0, limit: int = 0) -> list[PieceMeta]:
        with self._lock:
            nums = sorted(n for n in self.md.pieces if n >= start_num)
        if limit > 0:
            nums = nums[:limit]
        return [self.md.pieces[n] for n in nums]

    def verify_content(self) -> bool:
        """Re-hash the whole file against the recorded content digest."""
        if not self.md.digest:
            return True
        algo, _ = digestlib.parse(self.md.digest)
        def chunks():
            with open(self._data_path, "rb") as f:
                while True:
                    b = f.read(4 << 20)
                    if not b:
                        return
                    yield b
        return f"{algo}:{digestlib.hash_stream(algo, chunks())}" == self.md.digest

    # -- sinks ---------------------------------------------------------

    def store_to(self, output_path: str, *, range_start: int = 0,
                 range_length: int = -1) -> None:
        """Land the completed content at ``output_path``.

        Hardlink when possible (same filesystem, whole file), else copy —
        the reference's ``Store`` fast path.
        """
        os.makedirs(os.path.dirname(os.path.abspath(output_path)) or ".", exist_ok=True)
        whole = range_start == 0 and (
            range_length < 0 or range_length == self.md.content_length)
        if whole:
            try:
                if os.path.exists(output_path):
                    os.unlink(output_path)
                os.link(self._data_path, output_path)
                return
            except OSError:
                shutil.copyfile(self._data_path, output_path)
                return
        length = range_length if range_length >= 0 else self.md.content_length - range_start
        with open(self._data_path, "rb") as src, open(output_path, "wb") as dst:
            src.seek(range_start)
            remaining = length
            while remaining > 0:
                b = src.read(min(4 << 20, remaining))
                if not b:
                    break
                dst.write(b)
                remaining -= len(b)

    def data_path(self) -> str:
        return self._data_path

    def disk_usage(self) -> int:
        """LOGICAL bytes: what this task's content occupies from its own
        point of view. Digest-shared (hardlinked) data counts once per
        task here; StorageManager.usage() dedupes by inode for the
        physical number GC watermarks act on."""
        try:
            return os.path.getsize(self._data_path)
        except OSError:
            return 0

    def inode(self) -> tuple[int, int] | None:
        """(st_dev, st_ino) of the data file — the physical identity
        shared pieces coalesce on. None when the file is gone."""
        try:
            st = os.stat(self._data_path)
            return st.st_dev, st.st_ino
        except OSError:
            return None

    def nlink(self) -> int:
        try:
            return os.stat(self._data_path).st_nlink
        except OSError:
            return 0

    def destroy(self) -> None:
        self.close()
        shutil.rmtree(self.dir, ignore_errors=True)


class SubTaskStorage:
    """A ranged sub-task view over a parent TaskStorage.

    Role parity: ``local_storage_subtask.go`` — piece offsets are relative to
    the sub-range; bytes live in the parent's file at ``range_start + offset``.
    Completing the sub-range does not complete the parent, but the parent's
    piece table gains nothing — the sub-task keeps its own metadata.
    """

    def __init__(self, parent: TaskStorage, metadata: TaskMetadata):
        if metadata.range_length < 0:
            raise ValueError("subtask needs range_length")
        self.parent = parent
        self.md = metadata
        self._lock = threading.Lock()

    def write_piece(self, num: int, offset: int, data: bytes | memoryview,
                    piece_digest: str = "", *, cost_ms: int = 0,
                    source: str = "", pre_verified: bool = False,
                    stage=None) -> PieceMeta:
        if offset + len(data) > self.md.range_length:
            raise DFError(Code.CLIENT_STORAGE_ERROR,
                          f"piece {num} spills past sub-range: "
                          f"{offset}+{len(data)} > {self.md.range_length}")
        if piece_digest and not pre_verified \
                and not digestlib.verify(piece_digest, data):
            raise DFError(Code.CLIENT_DIGEST_MISMATCH, f"piece {num} digest mismatch")
        if not piece_digest:
            piece_digest = digestlib.for_bytes(
                digestlib.preferred_piece_algo(), data)
        with self._lock:
            existing = self.md.pieces.get(num)
            if existing is not None:
                return existing
        abs_off = self.md.range_start + offset
        try:
            with self.parent._data_fd() as fd:
                _pwrite_all(fd, data, abs_off)
        except OSError as exc:
            raise DFError(Code.CLIENT_STORAGE_ERROR,
                          f"piece {num} write failed: {exc}") from None
        if stage is not None:
            stage.copy(offset, data)
        meta = PieceMeta(num=num, start=offset, size=len(data),
                         digest=piece_digest, cost_ms=cost_ms, source=source)
        with self._lock:
            self.md.pieces[num] = meta
            self.md.access_time = time.time()
        self.parent.md.access_time = time.time()
        return meta

    def read_piece(self, num: int) -> bytes:
        meta = self.md.pieces.get(num)
        if meta is None:
            raise DFError(Code.CLIENT_PIECE_NOT_FOUND, f"piece {num} missing")
        return self.parent.read_range(self.md.range_start + meta.start, meta.size)

    def piece_infos(self, start_num: int = 0, limit: int = 0) -> list[PieceMeta]:
        with self._lock:
            nums = sorted(n for n in self.md.pieces if n >= start_num)
        if limit > 0:
            nums = nums[:limit]
        return [self.md.pieces[n] for n in nums]

    def mark_done(self, *, success: bool) -> None:
        with self._lock:
            self.md.done = True
            self.md.success = success

    def store_to(self, output_path: str) -> None:
        self.parent.store_to(output_path, range_start=self.md.range_start,
                             range_length=self.md.range_length)
