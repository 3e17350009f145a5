"""The HBM sink: verified pieces land in device memory, overlapped with the
download.

This is the TPU-native replacement for the GPUDirect/pinned-CUDA-memory role
in GPU-side distribution stacks (see BASELINE.json north star). Design:

- Verified pieces are copied into a host ``numpy`` buffer (the staging
  area) at their content offsets. The copy runs on the storage thread
  that landed the piece, in the same hop (``StageLease``); the sink's
  bookkeeping (``commit``) follows on the caller's thread. The buffer is
  leased from a pool of released sink buffers (``SinkBufferPool``), so
  the copy writes pages that are already there instead of faulting a
  file's worth of fresh ones in.
- The content is split into ``shard_count`` contiguous byte shards. The
  moment every byte of a shard is present, that shard's index is enqueued to
  the transfer thread of the chip it goes to: one queue and one thread per
  device the task's shards reach, each owning every ``jax.device_put`` onto
  its chip, so that the chips' DMAs run at once and a slow chip holds up
  only its own queue. ``write()`` never waits on a device transfer — on
  real TPU hardware ``device_put`` of an unpinned host buffer is
  synchronous (it blocks the caller for the whole staging copy + DMA), so
  dispatching it from the asyncio event loop or awaiting it from the
  piece-landing path stalls the daemon's own sockets. The worker threads
  absorb that blocking.
- ``result()`` drains the transfer queue, blocks until the DMAs finish, and
  assembles per-device shards into ONE logically-global jax.Array via
  ``jax.make_array_from_single_device_arrays`` when a mesh sharding is
  given, so downstream JAX code sees a normal sharded array on the mesh.

Single-host by design: each daemon feeds its own host's devices; cross-host
distribution is the P2P fabric's job, not XLA's.
"""

from __future__ import annotations

import logging
import queue
import threading
import time
from typing import Any, Callable

import numpy as np

from ..common import faultgate, tracing
from ..common.metrics import REGISTRY

log = logging.getLogger("df.storage.hbm")

# sink telemetry in the process registry (scraped at /metrics) instead of
# instance-private fields only a result() caller could read: the DMA
# overlap picture must survive the task and be visible to an operator
# mid-download
_hbm_transfer_s = REGISTRY.histogram(
    "df_hbm_transfer_seconds", "device shard DMA duration",
    buckets=(0.001, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5,
             5.0, 10.0))
_hbm_transfers = REGISTRY.counter(
    "df_hbm_transfers_total", "device shard transfers", ("result",))
_hbm_bytes = REGISTRY.counter(
    "df_hbm_staged_bytes_total", "bytes staged into the host buffer")
_hbm_queue = REGISTRY.gauge(
    "df_hbm_transfer_queue_depth", "shard transfers enqueued, not yet done")
_pool_acquires = REGISTRY.counter(
    "df_sinkpool_acquires_total", "sink host-buffer pool leases", ("result",))
_pool_parked = REGISTRY.gauge(
    "df_sinkpool_bytes", "bytes parked in the sink host-buffer pool")


class SinkBufferPool:
    """Recycles the sinks' file-sized host buffers, as ``common/bufpool``
    recycles the 4-16 MiB piece buffers and for the same reason: a fresh
    buffer's every page is a first-touch fault under the staging copy
    (about 1 GB/s into fresh pages against 6 into touched ones).

    Contract:

    * ``acquire(size)`` returns ``(buffer, hit)``: a uint8 array of AT
      LEAST ``size`` bytes, the smallest parked one that fits (a 640 MiB
      lease fits a parked 1,056 MiB buffer), else a fresh one. Contents
      are undefined: the sink zeroes its pad tail and transfers nothing
      else that ``CoverageMap`` has not seen written.
    * ``release(buffer, recycle)`` ends the lease, exactly once. The
      holder passes ``recycle`` only when nothing can read or write the
      buffer any more (``DeviceIngest._release_host``).
    * Parked bytes are bounded by what the sinks themselves held: parked
      plus leased bytes never exceed the high-water mark of leased bytes,
      so the pool keeps what the process held a moment earlier and nothing
      beyond. A lease that no parked buffer fits drops the smallest parked
      ones until its fresh buffer fits under that mark.

    Thread-safe: leases start on the loop, end on transfer and storage
    threads."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._parked: list[np.ndarray] = []    # ascending by size
        self._parked_bytes = 0
        self._leased_bytes = 0
        self._leased_peak = 0

    def _park(self, delta: int) -> None:
        self._parked_bytes += delta
        _pool_parked.inc(delta)    # a delta: tests make pools of their own

    def acquire(self, size: int) -> tuple[np.ndarray, bool]:
        with self._lock:
            fit = next((i for i, b in enumerate(self._parked)
                        if b.nbytes >= size), None)
            if fit is not None:
                buf = self._parked.pop(fit)
                self._park(-buf.nbytes)
            self._leased_bytes += size if fit is None else buf.nbytes
            self._leased_peak = max(self._leased_peak, self._leased_bytes)
            while self._parked and (self._parked_bytes + self._leased_bytes
                                    > self._leased_peak):
                self._park(-self._parked.pop(0).nbytes)
        if fit is not None:
            _pool_acquires.labels("hit").inc()
            return buf, True
        _pool_acquires.labels("miss").inc()
        return np.empty(size, dtype=np.uint8), False

    def release(self, buf: np.ndarray, recycle: bool) -> None:
        with self._lock:
            self._leased_bytes -= buf.nbytes
            if recycle and (self._parked_bytes + self._leased_bytes
                            + buf.nbytes <= self._leased_peak):
                self._parked.append(buf)
                self._parked.sort(key=lambda b: b.nbytes)
                self._park(buf.nbytes)

    def parked_bytes(self) -> int:
        with self._lock:
            return self._parked_bytes


# process-wide, as bufpool.POOL is: every sink of the daemon shares it
HOST_POOL = SinkBufferPool()


class StageLease:
    """One landing's hold on a sink's host buffer: what the storage layer
    is handed (``write_span(..., stage=)``) to copy VERIFIED pieces into
    the sink on its own thread, in the landing's hop. While a lease is
    out the sink neither frees nor recycles the buffer, whatever happens
    to the sink meanwhile (``close``, a lost sink, the last transfer).

    ``address`` serves the native landing (``df_span_write_staged``
    copies each piece once its crc has matched), ``copy`` the Python
    ones; both touch only ``host[offset:end]`` and no bookkeeping, so any
    number of landings stage at once over their disjoint ranges. A
    failure is kept in ``error`` and never raised into the landing: the
    bytes still finish landing on disk, and the caller loses the sink
    when it accounts the piece (``conductor._ingest_to_device``).
    ``seconds`` and ``nbytes`` are the copies this lease made (flight
    ``staged``). Once the sink has let its buffer go (every shard is on
    the device, or it was closed) a lease is inert and copies nothing.

    A sink whose ``write`` is not ``DeviceIngest``'s own (a subclass, a
    test's double, the benchmark's planted fault) is handed every piece
    through that ``write``, whole, on the same thread: nothing goes past
    it, the native copy included."""

    __slots__ = ("_ingest", "_host", "_via_write", "seconds", "nbytes",
                 "error")

    def __init__(self, ingest: "DeviceIngest", host: np.ndarray | None):
        self._ingest = ingest
        self._host = host
        self._via_write = type(ingest).write is not _OWN_WRITE
        self.seconds = 0.0
        self.nbytes = 0
        self.error: Exception | None = None

    def _check(self, offset: int, length: int) -> None:
        if offset < 0 or offset + length > self._ingest.content_length:
            raise ValueError(f"write beyond content: {offset + length} > "
                             f"{self._ingest.content_length}")

    def address(self, offset: int, length: int) -> int:
        """Where ``length`` bytes at content ``offset`` belong in the host
        buffer, for native code to copy to; 0 when the copy is not native
        code's to make (an inert lease, a replaced ``write``, or a range
        beyond the content, which is kept as the lease's error)."""
        if self._host is None or self.error is not None or self._via_write:
            return 0
        try:
            self._check(offset, length)
        except ValueError as exc:
            self.error = exc
            return 0
        return self._host.ctypes.data + offset

    def took(self, since: int) -> bool:
        """Whether a landing staged what it was given: ``nbytes`` moved
        past ``since``, or could not have (a failed or inert lease). False
        is a landing that skipped its piece as already recorded, whose
        verified bytes are on disk and were copied nowhere."""
        return (self.nbytes > since or self.error is not None
                or self._ingest.host is None)

    def account(self, seconds: float, nbytes: int) -> None:
        """Copies that native code made through ``address``."""
        self.seconds += seconds
        self.nbytes += nbytes
        _hbm_bytes.inc(nbytes)

    def _copy(self, offset: int, data) -> None:
        self._check(offset, len(data))
        with tracing.annotate("stage_copy"):
            self._host[offset:offset + len(data)] = np.frombuffer(
                data, dtype=np.uint8)
        _hbm_bytes.inc(len(data))

    def copy(self, offset: int, data) -> None:
        """The staging copy of one verified piece. Keeps no reference to
        ``data`` past its return (the piece-buffer pool depends on it)."""
        if self._host is None or self.error is not None:
            return
        t0 = time.perf_counter()
        try:
            if self._via_write:
                self._ingest.write(offset, data)
            else:
                self._copy(offset, data)
        except Exception as exc:  # noqa: BLE001 - the caller loses the sink
            self.error = exc
            return
        self.seconds += time.perf_counter() - t0
        self.nbytes += len(data)

    def release(self) -> None:
        if self._host is not None:
            self._host = None
            self._ingest._end_lease()

    def __enter__(self) -> "StageLease":
        return self

    def __exit__(self, *exc) -> None:
        self.release()


class CoverageMap:
    """Tracks which byte ranges are present; answers 'is [a,b) complete?'.

    Piece arrivals are arbitrary-order; ranges are merged as they land.
    """

    def __init__(self) -> None:
        self._ranges: list[tuple[int, int]] = []  # merged, sorted [start,end)
        self._lock = threading.Lock()

    def add(self, start: int, end: int) -> None:
        with self._lock:
            ranges = self._ranges
            lo, hi = start, end
            out = []
            inserted = False
            for s, e in ranges:
                if e < lo or s > hi:   # disjoint
                    if s > hi and not inserted:
                        out.append((lo, hi))
                        inserted = True
                    out.append((s, e))
                else:                   # overlap/adjacent: merge
                    lo, hi = min(lo, s), max(hi, e)
            if not inserted:
                out.append((lo, hi))
            out.sort()
            self._ranges = out

    def covers(self, start: int, end: int) -> bool:
        if start >= end:
            return True
        with self._lock:
            for s, e in self._ranges:
                if s <= start and end <= e:
                    return True
        return False

    def covered_bytes(self) -> int:
        with self._lock:
            return sum(e - s for s, e in self._ranges)


class DeviceIngest:
    """Streams a task's bytes into per-device shards as pieces arrive.

    All device transfers run on dedicated worker threads, one per chip
    the task's shards go to, so neither the asyncio event loop nor the
    piece-landing path ever blocks on DMA (the round-3 TPU failure mode:
    ``device_put`` on-loop starved the daemon's sockets mid-download).
    """

    def __init__(self, content_length: int, *, devices: Any = None,
                 sharding: Any = None, dtype: str = "uint8",
                 shards_per_device: int = 1,
                 shard_specs: list | None = None,
                 on_shard_ready: Callable[[str, float], None] | None = None,
                 device_put_fn: Callable[[Any, Any], Any] | None = None,
                 pool: SinkBufferPool | None = None):
        """``devices``: explicit device list (contiguous shards per device),
        or ``sharding``: a 1-D jax NamedSharding to assemble a global array
        on. ``shards_per_device`` > 1 pipelines the host->HBM DMA: each
        device's range is cut into that many transfer units so streaming can
        overlap even on a single chip (a 1-device host would otherwise hold
        its one transfer until the last byte arrived) and so no single
        ``device_put`` blocks the worker for the whole file. Only 1 is
        supported with ``sharding`` (global-array assembly needs one array
        per device). ``device_put_fn`` is injectable for tests (defaults to
        ``jax.device_put``).

        ``shard_specs`` switches the sink to MANIFEST mode (sharded tasks,
        common/sharding.py): instead of equal-split anonymous shards, each
        entry is ``(name, start, size[, dtype, shape, device])`` — a named
        byte range that transfers the moment its bytes are covered (ranges
        may be uneven, need not cover the content, and gaps never
        transfer). ``result()`` then returns ``{name: array}``, each array
        viewed as the spec's dtype (the sink default when "") and reshaped
        to the spec's shape when one is given. ``device`` is the ordinal in
        ``devices`` of the chip the array is to be on (``ShardInfo.device``:
        the manifest says, the sink obeys); -1 or absent is unplaced, and
        an unplaced spec goes round-robin by its index. An ordinal the sink
        has no device for is refused here. Incompatible with ``sharding``
        (global-array assembly needs the equal-split geometry).
        ``on_shard_ready`` is called ON
        THE TRANSFER THREAD as ``(name, monotonic_done_time)`` after each
        named shard's device transfer completes — callbacks must be cheap
        and thread-safe (hand off to the loop, don't compute). ``pool``:
        where the host buffer is leased from (the process's ``HOST_POOL``;
        tests pass their own)."""
        import jax

        if content_length <= 0:
            raise ValueError("content_length must be known for device ingest")
        self.content_length = content_length
        self.dtype = np.dtype(dtype)
        self._sharding = sharding
        if sharding is not None:
            if shards_per_device != 1:
                raise ValueError("shards_per_device must be 1 with sharding")
            if shard_specs is not None:
                raise ValueError("shard_specs incompatible with sharding")
            devices = list(sharding.mesh.devices.flat)
        elif devices is None:
            # this host's chips: the sink is single-host by design
            devices = jax.local_devices()
        self.devices = list(devices)
        self.shards_per_device = max(1, shards_per_device)
        self.on_shard_ready = on_shard_ready
        self._specs: list[tuple] | None = None
        if shard_specs is not None:
            if not shard_specs:
                raise ValueError("shard_specs must be non-empty")
            specs = []
            for sp in shard_specs:
                name, start, size = sp[0], int(sp[1]), int(sp[2])
                sdtype = np.dtype(sp[3]) if len(sp) > 3 and sp[3] \
                    else self.dtype
                shape = tuple(sp[4]) if len(sp) > 4 and sp[4] else None
                if size <= 0 or start < 0 or start + size > content_length:
                    raise ValueError(f"shard {name}: bad range "
                                     f"[{start}, {start + size})")
                if size % sdtype.itemsize:
                    raise ValueError(f"shard {name}: size {size} not a "
                                     f"multiple of {sdtype} itemsize")
                device = int(sp[5]) if len(sp) > 5 and sp[5] is not None \
                    else -1
                if not -1 <= device < len(self.devices):
                    raise ValueError(
                        f"shard {name}: placed on device {device}, the "
                        f"sink is open over {len(self.devices)}")
                specs.append((name, start, size, sdtype, shape, device))
            self._specs = specs
            n = len(specs)
            self._shard_device = [sp[5] if sp[5] >= 0
                                  else i % len(self.devices)
                                  for i, sp in enumerate(specs)]
            self.n_shards = n
            self.padded_length = content_length
            self.shard_bytes = 0            # uneven; see _shard_range
            # overlap scan order: (start, end, index) sorted by start
            self._spec_order = sorted(
                (sp[1], sp[1] + sp[2], i) for i, sp in enumerate(specs))
        else:
            n = len(self.devices) * self.shards_per_device
            self.n_shards = n
            # equal shards padded to dtype & shard-count alignment
            itemsize = self.dtype.itemsize
            padded = -(-content_length // (n * itemsize)) * (n * itemsize)
            self.padded_length = padded
            self.shard_bytes = padded // n
            self._shard_device = [i // self.shards_per_device
                                  for i in range(n)]
        self._coverage = CoverageMap()
        self._shard_arrays: list[Any | None] = [None] * n
        self._shard_sent = [False] * n       # transfer COMPLETED
        self._shard_queued = [False] * n     # enqueued to the worker
        # (monotonic start, end) of each completed device transfer — lets
        # callers measure how much DMA ran concurrently with the download
        # without run-to-run wall-clock subtraction (bench + tracing)
        self.transfer_spans: list[tuple[float, float]] = []
        # beside each span: (ordinal of the chip it went to, bytes)
        self.transfer_chips: list[tuple[int, int]] = []
        self._lock = threading.Lock()
        self._device_put = device_put_fn or jax.device_put
        # one queue and one worker per chip that a shard of this task goes
        # to (one, on a one-chip host)
        self._queues: dict[int, queue.SimpleQueue] = {
            d: queue.SimpleQueue() for d in sorted(set(self._shard_device))}
        self._pending = 0                    # queued-but-unfinished transfers
        self._idle = threading.Event()
        self._idle.set()
        self._error: BaseException | None = None
        self._closed = False
        # the host buffer: a lease on a pooled buffer at least as long,
        # dirty but for the pad tail (every other byte is covered before
        # its shard may be enqueued, and gaps never transfer). It goes
        # back once nothing can touch it: see _release_host
        self._pool = pool if pool is not None else HOST_POOL
        self._backing, self.pool_hit = self._pool.acquire(self.padded_length)
        self.host: np.ndarray | None = self._backing[:self.padded_length]
        self.host[content_length:] = 0
        self._leases = 0                     # StageLeases out
        self._workers_live = len(self._queues)
        self._workers_done = False           # no worker can touch the buffer
        self._host_aliased = False           # a device array reads it in place
        self._workers = [threading.Thread(
            target=self._transfer_loop, args=(q,), name=f"hbm-sink-{d}",
            daemon=True) for d, q in self._queues.items()]
        for w in self._workers:
            w.start()
        if content_length < self.padded_length:  # pad tail trivially "present"
            self._coverage.add(content_length, self.padded_length)
        log.info("device sink open: %d bytes -> %d shards on %d %s device(s) "
                 "(%s)", content_length, n, len(self.devices),
                 getattr(self.devices[0], "platform", "?"),
                 getattr(self.devices[0], "device_kind", "?"))

    # ------------------------------------------------------------------
    # producer side (piece-landing path) — never blocks on DMA
    # ------------------------------------------------------------------

    def lease(self) -> StageLease:
        """A landing's hold on the host buffer (see ``StageLease``); the
        caller releases it when the landing has returned."""
        with self._lock:
            host = self.host
            if host is not None:
                self._leases += 1
        return StageLease(self, host)

    def _end_lease(self) -> None:
        with self._lock:
            self._leases -= 1
            self._release_host()

    def _release_host(self) -> None:
        """Let the host buffer go once no reader or writer of it can
        exist: every shard's ``device_put`` has returned from
        ``block_until_ready``, or every transfer worker has exited (the
        sink was closed and the transfers queued before that are through,
        on every chip), and no landing holds a lease. It is recycled
        unless a device array reads it in place. Called under ``_lock``."""
        if (self._workers_done and not self._leases
                and self._backing is not None):
            backing, self._backing, self.host = self._backing, None, None
            self._pool.release(
                backing,
                recycle=not self._host_aliased and self._error is None)

    def commit(self, offset: int, nbytes: int) -> None:
        """The bookkeeping for one staged piece: marks ``[offset, offset +
        nbytes)`` present and enqueues the device transfer of every shard
        that completes. Call it only after the piece's staging copy has
        returned (the landing that held the ``StageLease`` has): a shard
        is transferred the moment its range is covered."""
        if faultgate.ARMED:
            # a raising script here exercises the conductor's sink-loss
            # path: the bytes finish landing on disk, the task FAILS
            faultgate.fire_sync("hbm.ingest")
        end = offset + nbytes
        if end > self.content_length:
            raise ValueError(f"write beyond content: {end} > {self.content_length}")
        self._coverage.add(offset, end)
        if self._specs is not None:
            # manifest mode: enqueue every named range this span touches
            # (a piece straddling a shard boundary can complete two)
            for s, e, idx in self._spec_order:
                if e <= offset:
                    continue
                if s >= end:
                    break
                self._maybe_enqueue(idx)
            return
        first = offset // self.shard_bytes
        last = (end - 1) // self.shard_bytes
        for shard in range(first, min(last + 1, self.n_shards)):
            self._maybe_enqueue(shard)

    def write(self, offset: int, data: bytes | memoryview) -> None:
        """Stage one verified piece and account it, on the calling thread:
        the staging copy, then ``commit``. For callers that already hold
        verified bytes off the loop (``ShardPrefetcher``'s re-ingest from
        storage); the download path stages inside the landing instead.

        Buffer lifetime rule (the piece-buffer pool depends on it): no
        reference to ``data`` outlives the staging copy, which is
        complete when this returns — as it is, for a landing, when the
        landing returns. Device transfers read ONLY the sink's host
        buffer, never the caller's."""
        with self.lease() as lease:
            if lease._host is not None:
                lease._copy(offset, data)
        self.commit(offset, len(data))

    def _shard_range(self, shard: int) -> tuple[int, int]:
        if self._specs is not None:
            s, size = self._specs[shard][1:3]
            return s, s + size
        return shard * self.shard_bytes, (shard + 1) * self.shard_bytes

    def _maybe_enqueue(self, shard: int) -> None:
        s, e = self._shard_range(shard)
        with self._lock:
            if self._shard_queued[shard] or self._closed:
                return
            if not self._coverage.covers(s, min(e, self.content_length)):
                return
            self._shard_queued[shard] = True
            self._pending += 1
            # delta, not set(): several sinks share the process gauge and
            # one instance's private _pending must not clobber the others'
            _hbm_queue.inc()
            self._idle.clear()
            # put stays under the lock (SimpleQueue.put never blocks): outside
            # it, a concurrent close() could slip its sentinel in first and
            # the worker would exit with this shard queued behind it, leaving
            # _pending stuck > 0 and drain() hung
            self._queues[self._shard_device[shard]].put(shard)

    def flush(self) -> None:
        """Enqueue any fully-covered shard whose transfer hasn't fired — in
        practice the padding-only tail shards that no write ever touches.
        Non-blocking; shards with missing content bytes are left unsent
        (result() will name them)."""
        for shard in range(self.n_shards):
            self._maybe_enqueue(shard)

    # ------------------------------------------------------------------
    # worker threads — each owns every device_put onto its chip
    # ------------------------------------------------------------------

    def _transfer_loop(self, q: queue.SimpleQueue) -> None:
        try:
            while True:
                shard = q.get()
                # None: shutdown sentinel
                if shard is None or self._transfer(shard):
                    return
        finally:
            with self._lock:
                self._workers_live -= 1
                if not self._workers_live:
                    self._workers_done = True
                    self._release_host()

    def _stop_workers(self) -> None:
        """A sentinel behind whatever each chip's queue still holds.
        Called under ``_lock``, once."""
        self._closed = True
        for q in self._queues.values():
            q.put(None)

    def _reads_host_in_place(self, arr: Any, device: Any) -> bool:
        """Whether a transferred array is a view of the host buffer and
        not a copy of it: ``jax.device_put`` on the CPU backend returns
        one for a 64-byte-aligned source. Such a buffer is never
        recycled. Observed, not configured: device memory is not host
        memory; on a host-memory backend the array's buffer pointer says;
        where nothing says, it is taken to alias."""
        backing = self._backing
        lo = backing.ctypes.data
        if isinstance(arr, np.ndarray):
            ptr = arr.ctypes.data
        elif getattr(device, "platform", "cpu") != "cpu":
            return False
        else:
            try:
                ptr = arr.unsafe_buffer_pointer()
            except Exception:  # noqa: BLE001 - unknown: do not recycle
                return True
        return lo <= ptr < lo + backing.nbytes

    def _transfer(self, shard: int) -> bool:
        """One shard onto its device; True once every shard has shipped."""
        try:
            s, e = self._shard_range(shard)
            if self._specs is not None:
                name, _s, _size, sdtype, shape, _dev = self._specs[shard]
                view = self.host[s:e].view(sdtype)
                if shape is not None:
                    view = view.reshape(shape)
            else:
                name = None
                view = self.host[s:e].view(self.dtype)
            chip = self._shard_device[shard]
            device = self.devices[chip]
            t0 = time.monotonic()
            with tracing.annotate("hbm_transfer"):
                arr = self._device_put(view, device)
                # span must end at transfer COMPLETION, not dispatch —
                # on backends where device_put returns before the DMA
                # lands, a dispatch-end span would report overlap that
                # never ran
                wait = getattr(arr, "block_until_ready", None)
                if wait is not None:
                    wait()
            t1 = time.monotonic()
            if not self._host_aliased:
                self._host_aliased = self._reads_host_in_place(arr, device)
            with self._lock:
                self._shard_arrays[shard] = arr
                self._shard_sent[shard] = True
                self.transfer_spans.append((t0, t1))
                self.transfer_chips.append((chip, e - s))
            _hbm_transfer_s.observe(t1 - t0)
            _hbm_transfers.labels("ok").inc()
            if name is not None and self.on_shard_ready is not None:
                try:
                    self.on_shard_ready(name, t1)
                except Exception:  # noqa: BLE001 - observer only
                    log.exception("on_shard_ready(%s) raised", name)
            log.debug("shard %d/%d -> %s", shard, self.n_shards, device)
        except BaseException as exc:  # noqa: BLE001 - surfaced by result()
            with self._lock:
                if self._error is None:
                    self._error = exc
            _hbm_transfers.labels("fail").inc()
            log.exception("device transfer of shard %d failed", shard)
        finally:
            with self._lock:
                self._pending -= 1
                _hbm_queue.dec()
                # self-terminate once every shard has shipped: a consumer
                # that never calls result()/close() (task finished, nobody
                # collected) must not leak these threads, nor keep the
                # file-sized host buffer out of the pool. The last transfer
                # of the task is this one, so the other chips' workers are
                # parked on empty queues: the buffer goes now, before
                # drain() is woken, so that the next task's sink finds it
                # parked
                done = all(self._shard_sent)
                if done:
                    if not self._closed:
                        self._stop_workers()
                    self._workers_done = True
                    self._release_host()
                if self._pending == 0:
                    self._idle.set()
        return done

    # ------------------------------------------------------------------
    # consumer side
    # ------------------------------------------------------------------

    def done_fraction(self) -> float:
        return self._coverage.covered_bytes() / self.padded_length

    def drain(self, timeout: float | None = None) -> None:
        """Block (the CALLING thread — run via to_thread from async code)
        until every enqueued transfer has completed. Raises the first
        transfer error, if any."""
        if not self._idle.wait(timeout):
            raise TimeoutError("device transfers still in flight")
        with self._lock:
            if self._error is not None:
                raise RuntimeError("device transfer failed") from self._error

    def close(self) -> None:
        """Stop the worker threads. Idempotent; safe mid-stream (pending
        transfers finish first — the sentinels queue behind them)."""
        with self._lock:
            if not self._closed:
                self._stop_workers()

    def result(self, timeout: float | None = None):
        """Flush + drain, then return the device-resident data.

        Blocking — call via ``asyncio.to_thread`` from the event loop. With
        a sharding: one global jax.Array of shape (padded_length //
        itemsize,) sharded over the mesh axis. With ``shard_specs``: a
        ``{name: array}`` dict in manifest order. Without either: list of
        per-device arrays.
        """
        import jax

        try:
            self.flush()
            self.drain(timeout)
            with self._lock:
                sent = list(self._shard_sent)
                arrays = list(self._shard_arrays)
            if not all(sent):
                missing = [self._specs[i][0] if self._specs is not None
                           else i for i, s in enumerate(sent) if not s]
                raise RuntimeError(f"shards incomplete: {missing}")
        finally:
            # stop the workers on EVERY exit — a raising result() must not
            # leave a thread parked on queue.get holding the host buffer
            self.close()
        for a in arrays:
            # (an injected device_put_fn may hand back plain numpy)
            wait = getattr(a, "block_until_ready", None)
            if wait is not None:
                wait()
        if self._specs is not None:
            return {sp[0]: arrays[i] for i, sp in enumerate(self._specs)}
        if self._sharding is None:
            return arrays
        global_shape = (self.padded_length // self.dtype.itemsize,)
        return jax.make_array_from_single_device_arrays(
            global_shape, self._sharding, arrays)


# what StageLease compares a sink's ``write`` with, to see it replaced
_OWN_WRITE = DeviceIngest.write
