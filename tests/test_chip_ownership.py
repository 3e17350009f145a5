"""One process owns the chip, and a device sink is never lost in silence.

A chip belongs to ONE process, so a process brings JAX up only to serve a
device sink (or to train): constructing and running a Daemon without one
never imports jax, the host's position comes from the environment until a
sink adds what the devices tell, and the compile cache is placed from
outside. A request that asked for a sink and lost it — refused at open, a
failed write, a failed transfer — ends FAILED with the reason in its
terminal frame and flight summary, with the bytes still verified on disk.
"""

import asyncio
import os
import subprocess
import sys

import numpy as np
import pytest

from dragonfly2_tpu.common import faultgate
from dragonfly2_tpu.common.errors import Code, DFError
from dragonfly2_tpu.daemon.daemon import Daemon
from dragonfly2_tpu.idl.messages import (DeviceSink, DownloadRequest,
                                         TopologyInfo, UrlMeta)
from dragonfly2_tpu.tpu import runtime, topology
from dragonfly2_tpu.tpu.data import ShardPrefetcher
from test_daemon_e2e import daemon_config, start_origin

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_NO_SINK_DAEMON = """
import asyncio, os, sys
from dragonfly2_tpu.daemon.config import DaemonConfig, StorageSection
from dragonfly2_tpu.daemon.daemon import Daemon
from dragonfly2_tpu.idl.messages import DownloadRequest

work, src, out = sys.argv[1:4]

async def main():
    daemon = Daemon(DaemonConfig(workdir=work, host_ip="127.0.0.1",
                                 hostname="plain",
                                 storage=StorageSection(gc_interval_s=3600)))
    await daemon.start()
    try:
        async for _ in daemon.ptm.start_file_task(
                DownloadRequest(url="file://" + src, output=out)):
            pass
    finally:
        await daemon.stop()

asyncio.run(main())
assert open(out, "rb").read() == open(src, "rb").read()
touched = sorted(m for m in ("jax", "jaxlib") if m in sys.modules)
assert not touched, f"a sink-less daemon imported {touched}"
print("OK")
"""


class TestChipStaysFree:
    def test_daemon_without_a_sink_never_imports_jax(self, tmp_path):
        src = tmp_path / "blob.bin"
        src.write_bytes(os.urandom(300_000))
        env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
        proc = subprocess.run(
            [sys.executable, "-c", _NO_SINK_DAEMON, str(tmp_path / "d"),
             str(src), str(tmp_path / "out.bin")],
            env={**env, "PYTHONPATH": REPO}, cwd=REPO, capture_output=True,
            text=True, timeout=120)
        assert proc.returncode == 0, proc.stderr[-2000:]
        assert proc.stdout.strip().endswith("OK")

    def test_detect_reads_the_environment_only(self, monkeypatch):
        for k, v in (("TPU_SLICE_NAME", "v5e-16-slice-3"),
                     ("TPU_WORKER_ID", "2"), ("DF_ICI_COORDS", "1,0"),
                     ("DF_POD_ID", "pod-a"), ("DF_ZONE", "z-1")):
            monkeypatch.setenv(k, v)
        topology.detect.cache_clear()
        try:
            t = topology.detect()
        finally:
            monkeypatch.undo()
            topology.detect.cache_clear()
        assert t == TopologyInfo(slice_name="v5e-16-slice-3", worker_index=2,
                                 ici_coords=(1, 0), num_chips=0, zone="z-1",
                                 pod="pod-a")

    def test_with_devices_adds_what_only_the_chips_tell(self):
        class Chip:
            platform = "tpu"
            device_kind = "TPU v5 lite"
            process_index = 0

            def __init__(self, coords):
                self.coords = coords

        chips = [Chip((0, 0, 0)), Chip((1, 0, 0))]
        t = topology.with_devices(TopologyInfo(zone="z"), chips)
        assert (t.num_chips, t.ici_coords, t.worker_index) == (2, (0, 0, 0), 0)
        # the device kind is no slice identity: one-chip hosts of a fleet
        # must not all land in one ICI domain
        assert t.slice_name == "" and topology.pod_id(t) == ""
        # what the environment gave wins over what the devices tell
        env = TopologyInfo(slice_name="s", worker_index=5, ici_coords=(9,))
        t = topology.with_devices(env, chips)
        assert (t.slice_name, t.worker_index, t.ici_coords) == ("s", 5, (9,))
        # no chips (the cpu backend): nothing to add
        import jax
        assert topology.with_devices(env, jax.local_devices()) is env


class TestCompileCache:
    def test_env_dir_wins_and_no_other_is_set(self, monkeypatch, tmp_path):
        import jax

        monkeypatch.setenv(runtime.CACHE_ENV, str(tmp_path / "cc"))
        before = jax.config.jax_compilation_cache_dir
        assert runtime.place_compile_cache() == str(tmp_path / "cc")
        assert jax.config.jax_compilation_cache_dir == before
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0

    def test_fixed_in_tree_path_for_an_accelerator(self, monkeypatch):
        import jax

        monkeypatch.delenv(runtime.CACHE_ENV, raising=False)
        assert runtime.place_compile_cache() is None     # the cpu backend
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        before = jax.config.jax_compilation_cache_dir
        try:
            got = runtime.place_compile_cache()
            assert got == os.path.join(REPO, ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == got
        finally:
            jax.config.update("jax_compilation_cache_dir", before)


def _daemon_with_origin(tmp_path, files: dict, body):
    async def go():
        origin, base = await start_origin(files)
        daemon = Daemon(daemon_config(tmp_path))
        await daemon.start()
        try:
            await body(daemon, base)
        finally:
            await daemon.stop()
            await origin.cleanup()
    asyncio.run(go())


class TestSinkIsNeverLostInSilence:
    def setup_method(self):
        faultgate.reset()

    def teardown_method(self):
        faultgate.reset()

    def test_failed_write_fails_the_task_with_the_reason(self, tmp_path):
        """The hbm.ingest chaos script: the first staged piece raises. The
        download still finishes landing on disk (the swarm can be fed, a
        retry re-stages without the wire), but the request FAILS, typed,
        with the reason in the terminal frame and the flight summary."""
        data = os.urandom(600_000)

        async def body(daemon, base):
            url = f"{base}/w.bin"
            faultgate.arm("hbm.ingest", "fail", code=Code.INTERNAL, n=1)
            with pytest.raises(DFError) as ei:
                async for _ in daemon.ptm.start_file_task(DownloadRequest(
                        url=url, device_sink=DeviceSink(enabled=True))):
                    pass
            assert ei.value.code == Code.CLIENT_DEVICE_SINK_ERROR
            assert "device ingest write failed" in ei.value.message
            task_id = daemon.ptm._task_id(url, UrlMeta())
            conductor = daemon.ptm.conductor(task_id)
            assert conductor.state == conductor.FAILED
            summary = daemon.flight_recorder.get(task_id).summarize()
            assert summary["state"] == "failed"
            assert "device ingest write failed" in summary["fail_reason"]
            # the bytes all landed and stay verified on disk
            store = daemon.ptm.storage_mgr.find_completed_task(task_id)
            assert store is not None
            out = tmp_path / "disk.bin"
            store.store_to(str(out))
            assert out.read_bytes() == data
            # content already on disk: a sink request gets no download to
            # ride, and says so instead of answering plain success
            with pytest.raises(DFError) as ei:
                async for _ in daemon.ptm.start_file_task(DownloadRequest(
                        url=url, device_sink=DeviceSink(enabled=True))):
                    pass
            assert ei.value.code == Code.CLIENT_DEVICE_SINK_ERROR

        _daemon_with_origin(tmp_path, {"w.bin": data}, body)

    def test_failed_transfer_fails_the_task(self, tmp_path, monkeypatch):
        """device_put itself fails on the transfer thread: the done frame
        waits for the transfers, so the failure reaches it."""
        import jax

        def broken_put(view, device):
            raise RuntimeError("HBM exhausted (test)")

        monkeypatch.setattr(jax, "device_put", broken_put)

        async def body(daemon, base):
            with pytest.raises(DFError) as ei:
                async for _ in daemon.ptm.start_file_task(DownloadRequest(
                        url=f"{base}/w.bin",
                        device_sink=DeviceSink(enabled=True))):
                    pass
            assert ei.value.code == Code.CLIENT_DEVICE_SINK_ERROR
            assert "HBM exhausted" in ei.value.message

        _daemon_with_origin(tmp_path, {"w.bin": os.urandom(300_000)}, body)

    def test_refused_sink_fails_the_task(self, tmp_path):
        """A sink that cannot be opened (a manifest dtype that does not
        tile its range) is a failed request, not a disk-only success."""
        from dragonfly2_tpu.idl.messages import ShardInfo, ShardManifest

        async def body(daemon, base):
            with pytest.raises(DFError) as ei:
                async for _ in daemon.ptm.start_file_task(DownloadRequest(
                        url=f"{base}/w.bin",
                        device_sink=DeviceSink(enabled=True),
                        shard_manifest=ShardManifest(shards=[ShardInfo(
                            name="t", range_start=0, range_size=1001,
                            dtype="float32")]))):
                    pass
            assert ei.value.code == Code.CLIENT_DEVICE_SINK_ERROR
            assert "device sink refused" in ei.value.message

        _daemon_with_origin(tmp_path, {"w.bin": os.urandom(300_000)}, body)

    def test_a_placed_manifest_lands_on_the_named_devices(self, tmp_path):
        """Through ``start_file_task``: every array is on the chip its
        manifest names (three of a kind together, as an expert's
        matrices), the unplaced one where it went before, and the flight
        journal's ``hbm_shard`` events name the chips and the bytes."""
        import jax

        from dragonfly2_tpu.idl.messages import ShardInfo, ShardManifest

        data = os.urandom(13 * 40_000)
        place = [3, 3, 3, 0, 0, 0, 2, 2, 2, 1, 1, 1, -1]
        manifest = ShardManifest(shards=[ShardInfo(
            name=f"t{i}", range_start=i * 40_000, range_size=40_000,
            dtype="uint16", shape=[100, 200], device=d)
            for i, d in enumerate(place)])

        async def body(daemon, base):
            url = f"{base}/w.bin"
            async for _ in daemon.ptm.start_file_task(DownloadRequest(
                    url=url, device_sink=DeviceSink(enabled=True),
                    shard_manifest=manifest)):
                pass
            task_id = daemon.ptm._task_id(url, UrlMeta())
            arrays = daemon.ptm.conductor(task_id).device_ingest.result()
            devices = jax.local_devices()
            for i, d in enumerate(place):
                arr = arrays[f"t{i}"]
                want = devices[d if d >= 0 else i % len(devices)]
                assert arr.devices() == {want} and arr.shape == (100, 200)
                assert np.asarray(arr).tobytes() == \
                    data[i * 40_000:(i + 1) * 40_000]
            events = [e for e in daemon.flight_recorder.get(task_id).events
                      if e[1] == "hbm_shard"]
            assert len(events) == 13
            assert sorted(e[3] for e in events) == sorted(
                str(d if d >= 0 else 12 % len(devices)) for d in place)
            assert all(e[4] == 40_000 for e in events)

        _daemon_with_origin(tmp_path, {"w.bin": data}, body)

    def test_a_manifest_naming_a_missing_chip_fails_before_any_piece(
            self, tmp_path):
        """The host's sink is open over 8 devices here; a shard placed on a
        ninth fails the request at open, typed, with the reason in the
        terminal frame and the flight summary, and nothing was registered,
        dispatched or stored."""
        from dragonfly2_tpu.idl.messages import ShardInfo, ShardManifest

        async def body(daemon, base):
            url = f"{base}/w.bin"
            with pytest.raises(DFError) as ei:
                async for _ in daemon.ptm.start_file_task(DownloadRequest(
                        url=url, device_sink=DeviceSink(enabled=True),
                        shard_manifest=ShardManifest(shards=[
                            ShardInfo(name="a", range_start=0,
                                      range_size=1000, device=0),
                            ShardInfo(name="b", range_start=1000,
                                      range_size=1000, device=8)]))):
                    pass
            assert ei.value.code == Code.CLIENT_DEVICE_SINK_ERROR
            assert "shard b is placed on device 8" in ei.value.message
            assert "open over 8" in ei.value.message
            task_id = daemon.ptm._task_id(url, UrlMeta())
            conductor = daemon.ptm.conductor(task_id)
            assert conductor.state == conductor.FAILED
            assert conductor.storage is None and not conductor.ready
            flight = daemon.flight_recorder.get(task_id)
            summary = flight.summarize()
            assert summary["state"] == "failed"
            assert "placed on device 8" in summary["fail_reason"]
            stages = {e[1] for e in flight.events}
            assert not stages & {"registered", "scheduled", "dispatched",
                                 "wire_done", "sink_open"}
            assert not list(daemon.ptm.storage_mgr.tasks())

        _daemon_with_origin(tmp_path, {"w.bin": os.urandom(100_000)}, body)

    def test_no_device_runtime_fails_before_any_byte_moves(
            self, tmp_path, monkeypatch):
        def no_backend():
            raise RuntimeError("no accelerator (test)")

        monkeypatch.setattr(runtime, "bring_up", no_backend)

        async def body(daemon, base):
            with pytest.raises(DFError) as ei:
                async for _ in daemon.ptm.start_file_task(DownloadRequest(
                        url=f"{base}/w.bin",
                        device_sink=DeviceSink(enabled=True))):
                    pass
            assert ei.value.code == Code.CLIENT_DEVICE_SINK_ERROR
            assert "no accelerator" in ei.value.message
            assert not list(daemon.ptm.storage_mgr.tasks())

        _daemon_with_origin(tmp_path, {"w.bin": os.urandom(100_000)}, body)

    def test_lost_sink_makes_the_prefetcher_raise(self, tmp_path):
        shards = {f"shard-{i}.tar": os.urandom(200_000) for i in range(2)}

        async def body(daemon, base):
            faultgate.arm("hbm.ingest", "fail", code=Code.INTERNAL, n=1)
            pf = ShardPrefetcher(daemon, [f"{base}/{n}" for n in shards],
                                 depth=1)
            with pytest.raises(DFError) as ei:
                async for _ in pf.astream():
                    pass
            assert ei.value.code == Code.CLIENT_DEVICE_SINK_ERROR

        _daemon_with_origin(tmp_path, shards, body)

    def test_sink_opening_daemon_announces_its_devices(self, tmp_path):
        """The first sink brings the runtime up once and off the loop; the
        sink it hands back lands on this host's devices."""
        data = os.urandom(400_000)

        async def body(daemon, base):
            assert daemon._devices is None
            async for _ in daemon.ptm.start_file_task(DownloadRequest(
                    url=f"{base}/w.bin",
                    device_sink=DeviceSink(enabled=True))):
                pass
            import jax
            assert daemon._devices == jax.local_devices()
            conductor = daemon.ptm.conductor(
                daemon.ptm._task_id(f"{base}/w.bin", UrlMeta()))
            arrays = conductor.device_ingest.result()
            flat = np.concatenate([np.asarray(a) for a in arrays])
            assert flat[:len(data)].tobytes() == data

        _daemon_with_origin(tmp_path, {"w.bin": data}, body)
