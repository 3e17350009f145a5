"""Piece-buffer pool: recycles the 4-16 MiB download buffers.

Role parity: the reference's Go client leans on the runtime allocator +
``sync.Pool``; CPython's allocator hands multi-MiB bytearrays straight to
mmap/munmap, so a saturated fan-out paid a page-fault storm per piece:
every downloaded piece/span allocated a fresh bytearray
(piece_downloader._read_body), used it once, and dropped it. At 4 workers
x 4-16 MiB that is hundreds of MB/s of allocate-touch-free churn on the
one core the daemon owns.

Contract (the reuse-safety rules the pool's consumers live by):

* ``acquire(size)`` returns a bytearray of EXACTLY ``size`` bytes, possibly
  dirty — callers must overwrite every byte they later read (the
  downloader hands a buffer on only when the body it asked for, announced
  at exactly ``size`` bytes, has been received to its last byte).
* One holder besides the owner may keep a ``memoryview`` of a pooled
  buffer, and only for the length of one read: the connection the
  downloader receives the body on (``piece_downloader._Conn``), whose
  transport writes each read straight into the buffer. It lets go when
  the body is complete, before the buffer is handed on; and on every
  other exit (deadline, cancel, error, short or long read) it drops the
  view FIRST, aborts its transport (never reused), and only then does
  ``_read_body`` release the buffer — all on the loop's thread, in that
  order, so no late byte can be written into a buffer that is already
  another download's.
* ``release(buf)`` parks the buffer for reuse. The caller promises that no
  consumer still references its memory: the storage write has returned,
  and with it the staging copy into a device sink, which the landing makes
  on its storage thread in the same hop (``tpu.hbm_sink.StageLease``) —
  when ``on_span_from_peer`` returns, nothing reads the buffer any more.
  (The sink's own file-sized host buffers are recycled the same way, by
  ``tpu.hbm_sink.SinkBufferPool``.)
* A buffer released while a ``memoryview`` over it is still exported is
  NOT recycled: release probes with a resize (append+pop), which raises
  ``BufferError`` iff exports exist, and such buffers are discarded
  (counted ``df_bufpool_discards_total{reason="exported"}``) — a leaked
  view can therefore never observe, or write into, another download's
  bytes. For the receive path above this probe is the second line, not
  the first.

Buffers are keyed by exact size (piece geometry is uniform per task, so
exact-size buckets hit ~always); the pool is bounded by total parked bytes
and per-size depth, and is thread-safe (release may run from executor
threads).
"""

from __future__ import annotations

import threading

from .metrics import REGISTRY

_acquires = REGISTRY.counter(
    "df_bufpool_acquires_total", "piece-buffer pool acquires", ("result",))
_discards = REGISTRY.counter(
    "df_bufpool_discards_total",
    "piece buffers dropped at release instead of pooled", ("reason",))
_pooled = REGISTRY.gauge(
    "df_bufpool_bytes", "bytes currently parked in the piece-buffer pool")


class BufferPool:
    def __init__(self, *, max_bytes: int = 256 << 20,
                 max_per_size: int = 16):
        self.max_bytes = max_bytes
        self.max_per_size = max_per_size
        self._lock = threading.Lock()
        self._free: dict[int, list[bytearray]] = {}
        self._bytes = 0

    def acquire(self, size: int) -> bytearray:
        """A buffer of exactly ``size`` bytes; contents undefined."""
        if size <= 0:
            return bytearray(0)
        with self._lock:
            bucket = self._free.get(size)
            if bucket:
                buf = bucket.pop()
                self._bytes -= size
                _pooled.set(self._bytes)
                _acquires.labels("hit").inc()
                return buf
        _acquires.labels("miss").inc()
        return bytearray(size)

    def release(self, buf) -> None:
        """Park ``buf`` for reuse (see the module contract). Anything that
        is not a recyclable bytearray — wrong type, zero-size, still
        exported to a memoryview — is silently dropped."""
        if not isinstance(buf, bytearray) or len(buf) == 0:
            return
        try:
            # export probe: resizing a bytearray with live memoryview
            # exports raises BufferError — exactly the case where pooling
            # would let a stale view read the NEXT download's bytes
            buf.append(0)
            buf.pop()
        except BufferError:
            _discards.labels("exported").inc()
            return
        size = len(buf)
        with self._lock:
            bucket = self._free.setdefault(size, [])
            if (self._bytes + size > self.max_bytes
                    or len(bucket) >= self.max_per_size):
                _discards.labels("full").inc()
                return
            bucket.append(buf)
            self._bytes += size
            _pooled.set(self._bytes)

    def clear(self) -> None:
        with self._lock:
            self._free.clear()
            self._bytes = 0
            _pooled.set(0)

    def pooled_bytes(self) -> int:
        with self._lock:
            return self._bytes


# process-wide pool, shared by every downloader the way REGISTRY is shared
POOL = BufferPool()
