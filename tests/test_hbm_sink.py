"""Stage-2 tests: coverage map + device ingest onto the 8-device CPU mesh."""

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
        ingest._worker.join(5)   # raising result() must still stop the worker
        assert not ingest._worker.is_alive()

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
        ingest._worker.join(5)
        assert not ingest._worker.is_alive()
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
