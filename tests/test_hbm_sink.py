"""Stage-2 tests: coverage map + device ingest onto the 8-device CPU mesh."""

import time

import numpy as np
import pytest

from dragonfly2_tpu.tpu.hbm_sink import CoverageMap, DeviceIngest
from dragonfly2_tpu.tpu.mesh import make_mesh, named_sharding
from dragonfly2_tpu.tpu import topology
from dragonfly2_tpu.idl.messages import LinkType, TopologyInfo


class TestCoverageMap:
    def test_merge_and_covers(self):
        c = CoverageMap()
        c.add(0, 10)
        c.add(20, 30)
        assert c.covers(0, 10) and not c.covers(5, 25)
        c.add(10, 20)  # bridges the gap
        assert c.covers(0, 30)
        assert c.covered_bytes() == 30

    def test_out_of_order_overlaps(self):
        c = CoverageMap()
        c.add(50, 60)
        c.add(0, 5)
        c.add(3, 55)
        assert c.covers(0, 60)
        assert c.covered_bytes() == 60

    def test_duplicate_landing_counts_once(self):
        # an endgame duplicate (or a retry's re-land) must not inflate
        # coverage — merged intervals count each byte once
        c = CoverageMap()
        c.add(0, 10)
        c.add(0, 10)
        c.add(2, 8)
        assert c.covered_bytes() == 10
        assert c.covers(0, 10)

    def test_boundary_mid_piece_spans(self):
        # a piece straddling a shard boundary covers the tail of one
        # range and the head of the next — both queries see their half
        c = CoverageMap()
        c.add(6, 14)                      # piece across the 10-boundary
        assert c.covers(6, 10) and c.covers(10, 14)
        assert not c.covers(0, 10) and not c.covers(10, 20)
        c.add(0, 6)
        assert c.covers(0, 10)

    def test_adjacent_ranges_merge(self):
        c = CoverageMap()
        c.add(0, 10)
        c.add(10, 20)                     # exactly adjacent: one range
        assert c.covers(0, 20)
        assert c._ranges == [(0, 20)]

    def test_empty_and_degenerate_queries(self):
        c = CoverageMap()
        assert c.covers(5, 5)             # empty range trivially covered
        assert not c.covers(0, 1)
        assert c.covered_bytes() == 0


class TestDeviceIngestManifest:
    """Manifest mode (sharded tasks): named uneven shards, each a device
    array the moment its bytes are covered."""

    def test_named_shards_ready_incrementally(self):
        import jax
        done: list[str] = []
        di = DeviceIngest(
            24, devices=jax.devices()[:2],
            shard_specs=[("a", 0, 10), ("b", 10, 6), ("tail", 20, 4)],
            on_shard_ready=lambda n, _t: done.append(n))
        di.write(0, bytes(range(12)))     # completes a; b partial
        di.drain(timeout=10)
        assert done == ["a"]
        di.write(12, bytes(range(12, 24)))  # b + the gap + tail
        res = di.result(timeout=10)
        assert set(res) == {"a", "b", "tail"}
        assert list(res["a"]) == list(range(10))
        assert list(res["b"]) == [10, 11, 12, 13, 14, 15]
        assert list(res["tail"]) == [20, 21, 22, 23]
        assert set(done) == {"a", "b", "tail"}

    def test_gap_bytes_never_transfer(self):
        import jax
        di = DeviceIngest(24, devices=jax.devices()[:1],
                          shard_specs=[("a", 0, 8)])
        di.write(0, bytes(8))
        res = di.result(timeout=10)
        assert set(res) == {"a"}          # the 16-byte gap has no array

    def test_per_shard_dtype_and_shape(self):
        import jax.numpy as jnp
        import jax
        di = DeviceIngest(
            16, devices=jax.devices()[:1],
            shard_specs=[("w", 0, 16, "float32", [2, 2])])
        di.write(0, np.arange(4, dtype=np.float32).tobytes())
        arr = di.result(timeout=10)["w"]
        assert arr.shape == (2, 2) and arr.dtype == jnp.float32
        assert float(arr[1][1]) == 3.0

    def test_incomplete_shard_named_in_error(self):
        import jax
        di = DeviceIngest(16, devices=jax.devices()[:1],
                          shard_specs=[("a", 0, 8), ("b", 8, 8)])
        di.write(0, bytes(8))
        with pytest.raises(RuntimeError, match="b"):
            di.result(timeout=5)

    def test_bad_specs_rejected(self):
        import jax
        devs = jax.devices()[:1]
        with pytest.raises(ValueError, match="bad range"):
            DeviceIngest(16, devices=devs, shard_specs=[("a", 8, 16)])
        with pytest.raises(ValueError, match="itemsize"):
            DeviceIngest(16, devices=devs,
                         shard_specs=[("a", 0, 6, "float32", None)])
        with pytest.raises(ValueError, match="incompatible"):
            mesh = make_mesh()
            DeviceIngest(16, sharding=named_sharding(mesh),
                         shard_specs=[("a", 0, 16)])


class TestDeviceIngest:
    def test_shards_land_on_all_devices(self):
        import jax

        content = np.random.default_rng(0).integers(0, 255, 1_000_000, dtype=np.uint8)
        raw = content.tobytes()
        ingest = DeviceIngest(len(raw), devices=jax.devices())
        # feed pieces out of order
        piece = 100_000
        order = list(range(0, len(raw), piece))
        order = order[1::2] + order[0::2]
        for off in order:
            ingest.write(off, raw[off:off + piece])
        arrays = ingest.result()
        assert len(arrays) == len(jax.devices())
        flat = np.concatenate([np.asarray(a) for a in arrays])[:len(raw)]
        assert np.array_equal(flat, content)

    def test_global_sharded_array(self):
        import jax

        mesh = make_mesh({"data": len(jax.devices())})
        sharding = named_sharding(mesh, "data")
        raw = bytes(range(256)) * 1000
        ingest = DeviceIngest(len(raw), sharding=sharding)
        step = 64 * 1024
        for off in range(0, len(raw), step):
            ingest.write(off, raw[off:off + step])
        arr = ingest.result()
        assert arr.shape[0] == ingest.padded_length
        assert len(arr.sharding.device_set) == len(jax.devices())
        np.testing.assert_array_equal(
            np.asarray(arr)[:len(raw)], np.frombuffer(raw, dtype=np.uint8))

    def test_incomplete_result_raises(self):
        ingest = DeviceIngest(1000)
        ingest.write(0, b"x" * 10)
        with pytest.raises(RuntimeError):
            ingest.result()

    def test_overlap_send_before_completion(self):
        """Early shards ship while later bytes are still missing."""
        import jax

        n_dev = len(jax.devices())
        ingest = DeviceIngest(n_dev * 1000, devices=jax.devices())
        ingest.write(0, b"a" * 1000)  # completes shard 0 only
        ingest.drain(timeout=10)      # wait for the worker, not the loop
        assert ingest._shard_sent[0]
        assert not any(ingest._shard_sent[1:])

    def test_write_never_blocks_on_transfer(self):
        """The round-3 TPU regression: device_put is synchronous on real
        hardware; write() must not wait on it. A deliberately-slow fake
        device_put proves the landing path and the event loop stay live
        while transfers grind on the worker thread."""
        import asyncio
        import time

        import jax

        put_calls = []

        def slow_put(view, device):
            time.sleep(0.25)          # a real-TPU-sized stall
            put_calls.append(device)
            return jax.device_put(view, device)

        raw = bytes(1000) * 8
        ingest = DeviceIngest(len(raw), devices=[jax.devices()[0]],
                              shards_per_device=8, device_put_fn=slow_put)

        async def scenario():
            ticks = 0

            async def heartbeat():
                nonlocal ticks
                while True:
                    await asyncio.sleep(0.01)
                    ticks += 1

            hb = asyncio.get_running_loop().create_task(heartbeat())
            t0 = time.monotonic()
            for off in range(0, len(raw), 1000):
                ingest.write(off, raw[off:off + 1000])  # on-loop, like a piece landing
            write_elapsed = time.monotonic() - t0
            # 8 shards x 0.25s of fake DMA; writes must not have waited
            assert write_elapsed < 0.25, f"write blocked: {write_elapsed:.2f}s"
            arrays = await asyncio.to_thread(ingest.result, 30)
            hb.cancel()
            return ticks, arrays

        ticks, arrays = asyncio.run(scenario())
        assert len(put_calls) == 8
        assert len(arrays) == 8
        # the loop kept running during the ~2s of transfers
        assert ticks > 50, f"event loop starved: only {ticks} heartbeats"

    def test_transfer_error_surfaces_in_result(self):
        import jax

        def bad_put(view, device):
            raise RuntimeError("boom")

        ingest = DeviceIngest(100, devices=[jax.devices()[0]],
                              device_put_fn=bad_put)
        ingest.write(0, b"x" * 100)
        with pytest.raises(RuntimeError):
            ingest.result(timeout=10)
        for w in ingest._workers:   # raising result() must still stop them
            w.join(5)
        assert not any(w.is_alive() for w in ingest._workers)

    def test_training_steps_while_ingest_streams(self):
        """BASELINE config #4's overlap claim at test scale: a jitted train
        loop must keep stepping (no deadlock, bounded stall) while
        DeviceIngest grinds slow transfers on its worker thread — the
        bench measures the same scenario on the real chip
        (bench.py _train_during_ingest)."""
        import threading
        import time

        import jax

        from dragonfly2_tpu.trainer import models

        def slow_put(view, device):
            time.sleep(0.1)           # a real-TPU-sized DMA stall per shard
            return jax.device_put(view, device)

        raw = bytes(8) * 100_000     # 800 KB, 8 shards x 0.1s fake DMA
        ingest = DeviceIngest(len(raw), devices=[jax.devices()[0]],
                              shards_per_device=8, device_put_fn=slow_put)

        key = jax.random.PRNGKey(0)
        params = models.init_mlp(key)
        opt = models.make_optimizer()
        opt_state = opt.init(params)
        batch = models.synthetic_mlp_batch(key, 64)
        step = models.make_train_step(models.mlp_loss, opt)
        params, opt_state, loss = step(params, opt_state, batch)  # compile
        jax.block_until_ready(loss)

        steps = {"n": 0}
        stop = threading.Event()

        def train_loop():
            nonlocal params, opt_state
            while not stop.is_set():
                params, opt_state, l = step(params, opt_state, batch)
                jax.block_until_ready(l)
                steps["n"] += 1

        t = threading.Thread(target=train_loop, daemon=True)
        t.start()
        try:
            for off in range(0, len(raw), 100_000):
                ingest.write(off, raw[off:off + 100_000])
            arrays = ingest.result(timeout=30)   # ≥0.8s of fake DMA
        finally:
            stop.set()
            t.join(timeout=10)
        assert not t.is_alive(), "train loop deadlocked against ingest"
        assert len(arrays) == 8
        assert steps["n"] >= 3, (
            f"training starved during ingest: {steps['n']} steps")

    def test_worker_self_terminates_when_complete(self):
        """A task nobody collects must not leak the transfer thread (one
        file-sized host buffer pinned per leaked thread on a long-lived
        daemon)."""
        import jax

        ingest = DeviceIngest(1000, devices=[jax.devices()[0]])
        ingest.write(0, b"y" * 1000)   # completes the only shard
        for w in ingest._workers:
            w.join(5)
        assert not any(w.is_alive() for w in ingest._workers)
        # result() still works after self-termination
        arrays = ingest.result(timeout=5)
        assert len(arrays) == 1


class TestTopology:
    def test_link_classification(self):
        a = TopologyInfo(slice_name="s0", zone="z0", ici_coords=(0, 0, 0))
        b = TopologyInfo(slice_name="s0", zone="z0", ici_coords=(1, 2, 0))
        c = TopologyInfo(slice_name="s1", zone="z0")
        d = TopologyInfo(slice_name="s2", zone="z9")
        assert topology.link_type(a, b) == LinkType.ICI
        assert topology.link_type(a, c) == LinkType.DCN
        assert topology.link_type(a, d) == LinkType.WAN
        assert topology.link_type(a, b, same_host=True) == LinkType.LOCAL
        assert topology.link_type(None, b) == LinkType.WAN

    def test_ici_hops(self):
        a = TopologyInfo(ici_coords=(0, 0, 0))
        b = TopologyInfo(ici_coords=(1, 2, 0))
        assert topology.ici_hops(a, b) == 3
        assert topology.ici_hops(a, TopologyInfo()) == 1 << 16

    def test_detect_runs(self):
        info = topology.detect()
        assert info.zone  # falls back to "local"


class TestMesh:
    def test_make_mesh_axes(self):
        import jax

        n = len(jax.devices())
        mesh = make_mesh({"data": -1, "model": 2})
        assert mesh.shape["model"] == 2
        assert mesh.shape["data"] == n // 2
        with pytest.raises(ValueError):
            make_mesh({"data": 3}) if n % 3 else (_ for _ in ()).throw(ValueError())


# ----------------------------------------------------------------------
# the staging copy off the loop, into a recycled host buffer
# ----------------------------------------------------------------------

from dragonfly2_tpu.tpu.hbm_sink import (  # noqa: E402
    HOST_POOL, SinkBufferPool, StageLease)


def _copying_put(view, device):
    """A transfer that copies, as a chip's does: the array owns its bytes."""
    return np.array(view, copy=True)


def _dirty_pool(nbytes: int, fill: int = 0xFF) -> SinkBufferPool:
    """A pool with one released buffer of ``nbytes``, every byte ``fill``."""
    pool = SinkBufferPool()
    buf, hit = pool.acquire(nbytes)
    assert not hit
    buf[:] = fill
    pool.release(buf, recycle=True)
    assert pool.parked_bytes() == nbytes
    return pool


def _source(n: int, seed: int = 0) -> bytes:
    return np.random.default_rng(seed).integers(
        0, 256, n, dtype=np.uint8).tobytes()


MODES = {
    # name: (content length, constructor keywords, (offset, size) of each
    # array the sink hands over, in the order it hands them over)
    "manifest": (1000, dict(shard_specs=[("a", 0, 300), ("b", 300, 500),
                                          ("tail", 900, 100)]),
                 [(0, 300), (300, 500), (900, 100)]),
    "whole_buffer": (1001, dict(devices=[object(), object(), object()],
                                dtype="uint16"),
                     [(0, 334), (334, 334), (668, 334)]),
    "shards_per_device": (1000, dict(devices=[object()],
                                     shards_per_device=4),
                          [(0, 250), (250, 250), (500, 250), (750, 250)]),
}


def _arrays(result) -> list:
    return list(result.values()) if isinstance(result, dict) else result


class TestStagingSplit:
    @pytest.mark.parametrize("mode", sorted(MODES))
    def test_copy_then_commit_from_threads_gives_what_write_gave(self, mode):
        """The copy (any thread, disjoint ranges, no bookkeeping) and the
        bookkeeping (afterwards) hand over the arrays ``write`` does."""
        import threading

        n, kw, _ranges = MODES[mode]
        kw = dict({"devices": [object()]}, **kw)
        raw = _source(n)
        step = 97
        offsets = list(range(0, n, step))

        whole = DeviceIngest(n, device_put_fn=_copying_put,
                             pool=SinkBufferPool(), **kw)
        for off in offsets:
            whole.write(off, raw[off:off + step])
        want = _arrays(whole.result(timeout=10))

        split = DeviceIngest(n, device_put_fn=_copying_put,
                             pool=SinkBufferPool(), **kw)
        leases = [split.lease() for _ in range(4)]
        go = threading.Barrier(len(leases))

        def stage(k: int) -> None:
            go.wait(5)
            for off in offsets[k::len(leases)]:
                leases[k].copy(off, raw[off:off + step])

        threads = [threading.Thread(target=stage, args=(k,))
                   for k in range(len(leases))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(10)
            assert not t.is_alive()
        assert sum(ls.nbytes for ls in leases) == n
        assert all(ls.error is None and ls.seconds > 0 for ls in leases)
        # nothing was accounted by the copies: no shard has been enqueued
        assert not any(split._shard_queued)
        for ls in leases:
            ls.release()
        for off in offsets:
            split.commit(off, len(raw[off:off + step]))
        got = _arrays(split.result(timeout=10))
        assert len(got) == len(want)
        for a, b in zip(got, want):
            assert a.dtype == b.dtype and np.array_equal(a, b)

    def test_lease_keeps_errors_and_never_raises_into_the_landing(self):
        di = DeviceIngest(100, devices=[object()],
                          device_put_fn=_copying_put, pool=SinkBufferPool())
        with di.lease() as lease:
            assert lease.address(90, 20) == 0
            assert isinstance(lease.error, ValueError)
            lease.copy(0, b"x" * 10)          # a failed lease copies nothing
            assert lease.nbytes == 0
        with pytest.raises(ValueError, match="beyond content"):
            di.write(95, b"y" * 10)
        with pytest.raises(ValueError, match="beyond content"):
            di.commit(95, 10)
        di.close()

    def test_a_replaced_write_is_handed_every_piece(self):
        """A sink whose ``write`` is not DeviceIngest's own (a subclass, a
        double, the benchmark's planted fault) sees every piece through it:
        the lease offers native code no address to copy past it."""
        seen = []

        class Doubling(DeviceIngest):
            def write(self, offset, data):
                seen.append((offset, len(data)))
                super().write(offset, bytes(b ^ 0xFF for b in data))

        raw = _source(64)
        di = Doubling(64, devices=[object()], device_put_fn=_copying_put,
                      pool=SinkBufferPool())
        with di.lease() as lease:
            assert lease.address(0, 64) == 0 and lease.error is None
            lease.copy(0, raw)
            assert lease.nbytes == 64
        di.commit(0, 64)                     # idempotent after write's own
        (arr,) = di.result(timeout=10)
        assert seen == [(0, 64)]
        assert bytes(arr) == bytes(b ^ 0xFF for b in raw)


class TestSinkBufferPool:
    @pytest.mark.parametrize("mode", sorted(MODES))
    def test_a_dirty_recycled_buffer_yields_the_sources_bytes(self, mode):
        """A released buffer comes back full of another task's bytes (here
        0xFF): what reaches the device is the source and, past the content,
        zeros."""
        n, kw, ranges = MODES[mode]
        kw = dict({"devices": [object()]}, **kw)
        raw = _source(n, seed=1)
        pool = _dirty_pool(4096)
        di = DeviceIngest(n, device_put_fn=_copying_put, pool=pool, **kw)
        assert di.pool_hit and pool.parked_bytes() == 0
        assert di.host.nbytes == di.padded_length <= 4096
        for off in range(0, n, 128):
            di.write(off, raw[off:off + 128])
        got = _arrays(di.result(timeout=10))
        padded = np.frombuffer(
            raw + bytes(di.padded_length - n), dtype=np.uint8)
        assert len(got) == len(ranges)
        for arr, (off, size) in zip(got, ranges):
            assert np.array_equal(arr.view(np.uint8).reshape(-1),
                                  padded[off:off + size])
        # every shard shipped: the whole 4096-byte buffer is parked again
        for w in di._workers:
            w.join(5)
        assert di.host is None and pool.parked_bytes() == 4096

    def test_a_smaller_lease_hits_a_larger_parked_buffer_best_fit(self):
        pool = SinkBufferPool()
        bufs = [pool.acquire(n)[0] for n in (1000, 3000, 2000)]
        for b in bufs:
            pool.release(b, recycle=True)
        assert pool.parked_bytes() == 6000
        got, hit = pool.acquire(1500)
        assert hit and got.nbytes == 2000     # the smallest that fits
        got2, hit2 = pool.acquire(2500)
        assert hit2 and got2.nbytes == 3000
        fresh, hit3 = pool.acquire(2500)      # only the 1000 is left
        assert not hit3 and fresh.nbytes == 2500

    def test_parked_bytes_are_bounded_by_what_was_leased_at_once(self):
        """Parked + leased never pass the high-water mark of leased bytes:
        the pool keeps what the sinks held a moment earlier, no more."""
        pool = SinkBufferPool()
        a, _ = pool.acquire(640)
        pool.release(a, recycle=True)
        assert pool.parked_bytes() == 640
        b, hit = pool.acquire(1056)           # the 640 cannot serve it
        assert not hit and pool.parked_bytes() == 0     # and had to go
        pool.release(b, recycle=True)
        assert pool.parked_bytes() == 1056
        c, hit = pool.acquire(640)
        assert hit and c is b
        d, hit = pool.acquire(640)            # two at once: the mark rises
        assert not hit
        pool.release(c, recycle=True)
        pool.release(d, recycle=True)
        assert pool.parked_bytes() == 1056 + 640
        e, _ = pool.acquire(100)
        pool.release(e, recycle=False)        # an aliased buffer is dropped
        assert pool.parked_bytes() == 1056

    def test_not_parked_while_a_landing_holds_it(self):
        """close() mid-landing (a lost sink) must neither free nor recycle
        the buffer under the landing's pointer."""
        pool = SinkBufferPool()
        di = DeviceIngest(1000, devices=[object()],
                          device_put_fn=_copying_put, pool=pool)
        lease = di.lease()
        addr = lease.address(0, 1000)
        assert addr == di.host.ctypes.data
        di.close()
        for w in di._workers:
            w.join(5)
        assert not any(w.is_alive() for w in di._workers)
        assert di.host is not None and pool.parked_bytes() == 0
        lease.copy(0, b"z" * 1000)            # the landing finishes its copy
        assert lease.error is None and bytes(di.host) == b"z" * 1000
        lease.release()
        assert di.host is None and pool.parked_bytes() == 1000
        lease.release()                       # idempotent
        assert pool.parked_bytes() == 1000
        # a landing that begins after the buffer has gone copies nowhere
        late = di.lease()
        late.copy(0, b"q" * 10)
        assert late.nbytes == 0 and late.error is None and late.took(0)
        late.release()

    def test_not_parked_while_a_transfer_reads_it(self):
        import threading

        pool = SinkBufferPool()
        reading, release = threading.Event(), threading.Event()

        def held_put(view, device):
            reading.set()
            assert release.wait(10)
            return np.array(view, copy=True)

        di = DeviceIngest(100, devices=[object()], device_put_fn=held_put,
                          pool=pool)
        di.write(0, b"k" * 100)
        assert reading.wait(5)
        di.close()                            # the sentinel queues behind it
        assert di.host is not None and pool.parked_bytes() == 0
        release.set()
        di.drain(timeout=10)
        assert di.host is None and pool.parked_bytes() == 100

    def test_a_failed_transfer_does_not_recycle(self):
        pool = SinkBufferPool()

        def bad_put(view, device):
            raise RuntimeError("boom")

        di = DeviceIngest(100, devices=[object()], device_put_fn=bad_put,
                          pool=pool)
        di.write(0, b"x" * 100)
        with pytest.raises(RuntimeError):
            di.result(timeout=10)
        for w in di._workers:
            w.join(5)
        assert di.host is None and pool.parked_bytes() == 0

    def test_nothing_is_recycled_under_a_live_array_on_the_cpu_backend(self):
        """``jax.device_put`` on the CPU backend hands back a VIEW of a
        64-byte-aligned source. Such a buffer must never reach the pool:
        a second task through the same pool leaves the first one's arrays
        as they were."""
        import jax

        dev = jax.devices()[0]
        size, count = 4096, 64
        # starts 4097 apart walk through every residue mod 64, so whatever
        # the buffer's own alignment at least one range is 64-byte aligned
        specs = [(f"s{k}", k * (size + 1), size) for k in range(count)]
        n = count * (size + 1)
        pool = SinkBufferPool()

        def task(seed: int):
            raw = _source(n, seed)
            di = DeviceIngest(n, devices=[dev], shard_specs=specs, pool=pool)
            di.write(0, raw)
            out = di.result(timeout=30)
            for w in di._workers:
                w.join(5)
            return raw, di, out

        raw1, di1, first = task(1)
        aliased = di1._host_aliased
        if not aliased:
            pytest.skip("this CPU backend copied every range")
        assert pool.parked_bytes() == 0       # dropped, not parked
        raw2, di2, second = task(2)
        assert not di2.pool_hit
        for name, off, sz in specs:
            assert bytes(np.asarray(first[name])) == raw1[off:off + sz]
            assert bytes(np.asarray(second[name])) == raw2[off:off + sz]

    def test_the_default_pool_is_the_process_pool(self):
        import jax

        di = DeviceIngest(10, devices=[jax.devices()[0]])
        assert di._pool is HOST_POOL
        di.close()

    def test_leases_and_pool_survive_many_threads(self):
        """More threads than cores open sinks on one pool, stage, ship and
        let go: no buffer is ever in two sinks at once, every array is its
        own source, and the pool's books balance at the end."""
        import sys
        import threading

        pool = SinkBufferPool()
        in_use: set[int] = set()
        guard = threading.Lock()
        errors: list[BaseException] = []

        def put(view, device):
            return np.array(view, copy=True)

        def worker(k: int) -> None:
            try:
                for r in range(12):
                    n = 500 + 37 * ((k + r) % 5)
                    raw = _source(n, seed=k * 100 + r)
                    di = DeviceIngest(n, devices=[object()],
                                      shards_per_device=2,
                                      device_put_fn=put, pool=pool)
                    key = di._backing.ctypes.data
                    with guard:
                        assert key not in in_use
                        in_use.add(key)
                    with di.lease() as lease:
                        lease.copy(0, raw)
                    assert lease.error is None
                    with guard:       # before the last shard can ship
                        in_use.discard(key)
                    di.commit(0, n)
                    out = np.concatenate(di.result(timeout=20))
                    assert bytes(out[:n]) == raw
            except BaseException as exc:  # noqa: BLE001 - reported below
                errors.append(exc)

        old = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            threads = [threading.Thread(target=worker, args=(k,))
                       for k in range(24)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(60)
                assert not t.is_alive()
        finally:
            sys.setswitchinterval(old)
        assert not errors, errors[0]
        assert pool._leased_bytes == 0
        assert pool.parked_bytes() == sum(b.nbytes for b in pool._parked)
        assert pool.parked_bytes() <= pool._leased_peak


# ----------------------------------------------------------------------
# placement by manifest, one transfer worker a chip
# ----------------------------------------------------------------------

class TestPlacementAndPerChipWorkers:
    N = 16                                   # specs, 64 bytes each

    def _specs(self, devices_of):
        return [(f"t{i}", i * 64, 64, "uint8", None, devices_of(i))
                for i in range(self.N)]

    def _feed(self, di) -> bytes:
        raw = _source(self.N * 64, seed=7)
        for off in range(0, len(raw), 96):   # boundaries inside pieces
            di.write(off, raw[off:off + 96])
        return raw

    def test_a_placed_manifest_puts_every_array_on_the_named_device(self):
        import jax
        devices = jax.devices()
        assert len(devices) == 8             # tests/conftest.py's mesh
        # an expert's three matrices together, four experts a chip
        where = lambda i: (i // 3) % 4       # noqa: E731
        di = DeviceIngest(self.N * 64, devices=devices,
                          shard_specs=self._specs(where))
        assert sorted(di._queues) == [0, 1, 2, 3]       # a worker a chip
        raw = self._feed(di)
        res = di.result(timeout=10)
        for i in range(self.N):
            arr = res[f"t{i}"]
            assert arr.devices() == {devices[where(i)]}
            assert bytes(np.asarray(arr)) == raw[i * 64:(i + 1) * 64]
        # the chip of each span is kept beside it
        assert len(di.transfer_chips) == len(di.transfer_spans) == self.N
        per_chip = {}
        for chip, nbytes in di.transfer_chips:
            per_chip[chip] = per_chip.get(chip, 0) + nbytes
        assert per_chip == {c: 64 * sum(1 for i in range(self.N)
                                        if where(i) == c) for c in range(4)}

    @pytest.mark.parametrize("specs", ["five_fields", "minus_one", "none"])
    def test_an_unplaced_manifest_lands_exactly_where_it_went_before(
            self, specs):
        """Round-robin by spec index over every device of the sink, however
        "unplaced" is spelt: no sixth element, -1, or None."""
        import jax
        devices = jax.devices()
        full = self._specs(lambda i: {"minus_one": -1, "none": None,
                                      "five_fields": 0}[specs])
        if specs == "five_fields":
            full = [sp[:5] for sp in full]
        di = DeviceIngest(self.N * 64, devices=devices, shard_specs=full)
        self._feed(di)
        res = di.result(timeout=10)
        for i in range(self.N):
            assert res[f"t{i}"].devices() == {devices[i % len(devices)]}
        assert [c for c, _n in sorted(di.transfer_chips)] == sorted(
            i % 8 for i in range(self.N))

    def test_placed_and_unplaced_specs_mix_in_one_manifest(self):
        import jax
        devices = jax.devices()[:4]
        di = DeviceIngest(256, devices=devices, shard_specs=[
            ("a", 0, 64, "uint8", None, 3), ("b", 64, 64),
            ("c", 128, 64, "uint8", None, 3), ("d", 192, 64)])
        di.write(0, bytes(256))
        res = di.result(timeout=10)
        assert [next(iter(res[n].devices())) for n in "abcd"] == [
            devices[3], devices[1], devices[3], devices[3]]

    def test_an_ordinal_the_sink_lacks_is_refused_at_construction(self):
        import jax
        pool = SinkBufferPool()
        for bad in (4, 99, -2):
            with pytest.raises(ValueError, match=f"device {bad}"):
                DeviceIngest(128, devices=jax.devices()[:4], pool=pool,
                             shard_specs=[("a", 0, 64, "uint8", None, 1),
                                          ("b", 64, 64, "uint8", None, bad)])
        # refused before a buffer was leased or a thread started
        assert pool._leased_bytes == 0

    def test_four_chips_transfers_overlap(self):
        """Each transfer takes 50 ms: 16 of them on four chips' workers
        take about four in a row, far under the sum."""
        def slow_put(view, device):
            time.sleep(0.05)
            return np.array(view, copy=True)

        di = DeviceIngest(self.N * 64, devices=[object()] * 4,
                          device_put_fn=slow_put,
                          shard_specs=self._specs(lambda i: i % 4))
        t0 = time.monotonic()
        self._feed(di)
        di.result(timeout=10)
        wall = time.monotonic() - t0
        busy = sum(e - s for s, e in di.transfer_spans)
        assert busy >= self.N * 0.05
        assert wall < busy / 2, (wall, busy)
        # and one chip's transfers never overlap each other: one worker
        by_chip: dict = {}
        for (s, e), (chip, _n) in zip(di.transfer_spans, di.transfer_chips):
            by_chip.setdefault(chip, []).append((s, e))
        for spans in by_chip.values():
            spans.sort()
            assert all(a[1] <= b[0] for a, b in zip(spans, spans[1:]))

    def test_a_slow_chip_holds_up_only_its_own_queue(self):
        import threading

        gate = threading.Event()

        def put(view, device):
            if device == "slow":
                assert gate.wait(10)
            return np.array(view, copy=True)

        done: list[str] = []
        di = DeviceIngest(self.N * 64, devices=["slow", "b", "c", "d"],
                          device_put_fn=put,
                          shard_specs=self._specs(lambda i: i % 4),
                          on_shard_ready=lambda n, _t: done.append(n))
        self._feed(di)
        deadline = time.monotonic() + 5
        while len(done) < 12 and time.monotonic() < deadline:
            threading.Event().wait(0.01)
        # the three other chips are through; chip 0's four wait
        assert sorted(done) == sorted(f"t{i}" for i in range(self.N)
                                      if i % 4)
        gate.set()
        assert len(di.result(timeout=10)) == self.N

    def test_one_chips_error_fails_the_task_while_the_others_drain(self):
        """Chip 2's first transfer raises. The task fails with that error,
        every other transfer still runs (the other chips' queues drain,
        and chip 2's own), and the host buffer goes back, unrecycled, only
        when the last worker is done."""
        import threading

        pool = SinkBufferPool()
        gate = threading.Event()
        put_on: list = []

        def put(view, device):
            put_on.append(device)
            if device == 2 and put_on.count(2) == 1:
                raise RuntimeError("chip 2 lost (test)")
            if device == 3:
                assert gate.wait(10)         # chip 3 is slow
            return np.array(view, copy=True)

        di = DeviceIngest(self.N * 64, devices=[0, 1, 2, 3],
                          device_put_fn=put, pool=pool,
                          shard_specs=self._specs(lambda i: i % 4))
        self._feed(di)
        deadline = time.monotonic() + 5
        while len(put_on) < 13 and time.monotonic() < deadline:
            threading.Event().wait(0.01)
        # chips 0, 1 and 2 went through all four of theirs; chip 3 is
        # inside its first
        assert sorted(put_on) == [0] * 4 + [1] * 4 + [2] * 4 + [3]
        di.close()
        for w in di._workers[:3]:
            w.join(5)
        assert [w.is_alive() for w in di._workers] == [False, False, False,
                                                       True]
        # three workers gone, one still reading the buffer: it stays
        assert di.host is not None
        gate.set()
        with pytest.raises(RuntimeError, match="device transfer failed"):
            di.result(timeout=10)
        di._workers[3].join(5)
        assert len(put_on) == self.N and len(di.transfer_spans) == 15
        assert di.host is None and pool.parked_bytes() == 0
        assert pool._leased_bytes == 0       # released, and not recycled

    def test_the_buffer_is_parked_when_the_last_chips_transfer_is_done(self):
        import threading

        pool = SinkBufferPool()
        gate = threading.Event()

        def put(view, device):
            if device == 3:
                assert gate.wait(10)
            return np.array(view, copy=True)

        di = DeviceIngest(256, devices=[0, 1, 2, 3], device_put_fn=put,
                          pool=pool, shard_specs=[
                              (f"t{i}", i * 64, 64, "uint8", None, i)
                              for i in range(4)])
        di.write(0, bytes(256))
        deadline = time.monotonic() + 5
        while len(di.transfer_spans) < 3 and time.monotonic() < deadline:
            threading.Event().wait(0.01)
        assert len(di.transfer_spans) == 3
        assert di.host is not None and pool.parked_bytes() == 0
        gate.set()
        di.drain(timeout=10)
        assert di.host is None and pool.parked_bytes() == 256
        for w in di._workers:                # every worker self-terminates
            w.join(5)
        assert not any(w.is_alive() for w in di._workers)

    def test_whole_buffer_mode_gets_a_worker_a_device_too(self):
        import jax
        devices = jax.devices()[:4]
        di = DeviceIngest(4096, devices=devices, shards_per_device=2)
        assert sorted(di._queues) == [0, 1, 2, 3]
        raw = _source(4096, seed=3)
        di.write(0, raw)
        arrays = di.result(timeout=10)
        assert [next(iter(a.devices())) for a in arrays] == [
            d for d in devices for _ in range(2)]
        assert b"".join(bytes(np.asarray(a)) for a in arrays) == raw
        assert sorted(c for c, _n in di.transfer_chips) == [0, 0, 1, 1, 2, 2,
                                                            3, 3]
