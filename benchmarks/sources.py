"""Where a driver's answers come from.

``FabricSource`` is the system under test: the embedded daemon's
``ptm.start_file_task`` with a device sink (and the file's manifest), and
``ShardPrefetcher``. It is all the benchmark takes from the program, with
the flight journals and counters that the per-layer readers read. It owns
the chip holder's embedded daemon.

``ReferenceSource`` is the plain reference put in the program's place: the
origin's bytes read directly and placed on the device with ``jax.device_put``,
tensor by tensor as the manifest says, or cut into equal units as the sink's
whole-buffer mode does. It imports nothing of the program. With ``broken``
set it breaks one guarantee the configurations state (every piece verified
before it lands, arrays bit-equal to the origin's bytes): one seeded bit of
each file is flipped on the way, as an unverified wire would deliver it.
That is the control of ``correct``; ``benchmarks/control.py`` runs it.
"""

from __future__ import annotations

import asyncio
import dataclasses
import time
from typing import Any, Iterator

MiB = 1 << 20
PULL_TIMEOUT_S = 120.0


@dataclasses.dataclass
class Request:
    """One unit of the window: a file or a shard asked for."""
    name: str
    size: int
    t_issue: float
    t_ready: float | None = None
    ok: bool = False
    error: str = ""
    flight: Any = None                       # the holder's TaskFlight
    bytes_p2p: int = 0
    bytes_source: int = 0


class FabricSource:
    name = "fabric"

    def __init__(self, net: dict):
        """``net`` is what ``swarm.start_swarm`` returns. The chip holder's
        embedded daemon starts here and is this source's to stop."""
        from dragonfly2_tpu.common.config import from_dict
        from dragonfly2_tpu.daemon.config import DaemonConfig

        from . import swarm
        self.net = net
        self.origin = net["origin"]
        self.emb = swarm.EmbeddedDaemon(from_dict(
            DaemonConfig, net["daemon_cfg"]("bench-chip")))

    def delete(self, files: list[dict]) -> None:
        """What ``ShardPrefetcher(delete_after=True)`` does in production:
        the pieces of what is on the device leave the holder's disk."""
        from dragonfly2_tpu.idl.messages import UrlMeta

        ptm = self.emb.daemon.ptm

        async def go():
            for f in files:
                await ptm.delete_task(ptm._task_id(self.url(f), UrlMeta()))

        self.emb.call(go(), 120)

    def take_stall_ms(self) -> float:
        return self.emb.take_stall_ms()

    def stop(self) -> None:
        self.emb.stop()

    def url(self, f: dict) -> str:
        """The origin serves a file by its base name under any directory,
        so ``url_path`` gives one file's bytes several URLs, each a task of
        its own."""
        return f"{self.origin}/{f.get('url_path', f['name'])}"

    def preseed(self, files: list[dict]) -> None:
        """The swarm's plain peer fetches every file from the origin, all at
        once, and this returns when it holds them. It is asked as any client
        asks a daemon: ``Download`` on its socket, no output file."""
        from dragonfly2_tpu.idl.messages import DownloadRequest
        from dragonfly2_tpu.rpc.client import Channel, ServiceClient

        from .swarm import check
        check(self.net["holder_of_content"] == "peer",
              "only a swarm with a plain peer can be pre-seeded: a child fed "
              "by nothing but a seed that holds the task complete is "
              "sometimes never fed (PERF.md, Open questions)")

        async def one(client, f: dict) -> None:
            async for resp in client.unary_stream("Download", DownloadRequest(
                    url=self.url(f), timeout_s=PULL_TIMEOUT_S)):
                if resp.done:
                    return
            raise RuntimeError(f"the peer's pull of {f['name']} ended early")

        async def go():
            ch = Channel(f"unix:{self.net['peer_sock']}")
            try:
                client = ServiceClient(ch, "df.daemon.Daemon")
                await asyncio.gather(*(one(client, f) for f in files))
            finally:
                await ch.close()

        self.emb.call(go(), PULL_TIMEOUT_S + 60)

    # -- one file into named arrays (manifest mode) ----------------------

    def pull(self, f: dict, *, disable_back_source: bool) -> tuple[dict,
                                                                   Request]:
        from dragonfly2_tpu.idl.messages import (DeviceSink, DownloadRequest,
                                                 ShardInfo, ShardManifest)

        emb = self.emb

        async def go():
            req = DownloadRequest(
                url=self.url(f), disable_back_source=disable_back_source,
                timeout_s=PULL_TIMEOUT_S,
                device_sink=DeviceSink(enabled=True, dtype=f["shards"][0][
                    "dtype"]),
                shard_manifest=ShardManifest(
                    shards=[ShardInfo(**s) for s in f["shards"]]))
            task_id = None
            async for resp in emb.daemon.ptm.start_file_task(req):
                task_id = resp.task_id or task_id
            conductor = emb.daemon.ptm.conductor(task_id)
            arrays = await asyncio.to_thread(conductor.device_ingest.result,
                                             PULL_TIMEOUT_S)
            conductor.device_ingest = None   # the sink's host buffer goes
            return conductor, arrays

        r = Request(f["name"], f["size"], time.monotonic())
        try:
            conductor, arrays = emb.call(go(), PULL_TIMEOUT_S + 60)
        except Exception as exc:
            raise RuntimeError(f"{exc!r}; the holder's journal of it: "
                               f"{self._journal(f)}") from exc
        r.t_ready = time.monotonic()
        r.ok = True
        r.flight = conductor.flight
        r.bytes_p2p = conductor.traffic_p2p
        r.bytes_source = conductor.traffic_source
        return arrays, r

    def _journal(self, f: dict) -> str:
        """What the holder's flight recorder holds of a request that
        failed: events by stage, and the last few."""
        flight = self.flight_of(f)
        if flight is None:
            return "none"
        events = list(flight.events)
        stages: dict[str, int] = {}
        for _t, stage, *_ in events:
            stages[stage] = stages.get(stage, 0) + 1
        tail = [(round(t), stage, piece, parent[-12:])
                for t, stage, piece, parent, _n, _d in events[-8:]]
        return f"{stages}; last {tail}"

    # -- many files as whole buffers, prefetched --------------------------

    def stream(self, files: list[dict], *, depth: int,
               delete_after: bool) -> Iterator[list]:
        from dragonfly2_tpu.tpu.data import ShardPrefetcher

        return iter(ShardPrefetcher(
            self.emb.daemon, [self.url(f) for f in files], depth=depth,
            loop=self.emb.loop, delete_after=delete_after))

    def flight_of(self, f: dict):
        from dragonfly2_tpu.idl.messages import UrlMeta

        daemon = self.emb.daemon
        return daemon.flight_recorder.get(
            daemon.ptm._task_id(self.url(f), UrlMeta()))


class ReferenceSource:
    """The origin's bytes placed on the device directly; see the module's
    docstring. ``unit_bytes`` is the whole-buffer mode's transfer unit (the
    sink's: 32 MiB) and only shapes the arrays a shard comes as."""
    name = "reference"

    def __init__(self, bytes_of, devices: list, *, broken: bool, seed: int,
                 unit_bytes: int = 32 * MiB):
        self.bytes_of = bytes_of             # file -> the origin's bytes
        self.devices = devices
        self.broken = broken
        self.seed = seed
        self.unit_bytes = unit_bytes

    def _read(self, f: dict):
        import numpy as np

        data = np.array(self.bytes_of(f))    # a copy: the origin's stay whole
        if self.broken:
            rng = np.random.default_rng([self.seed, data.shape[0]])
            data[int(rng.integers(data.shape[0]))] ^= np.uint8(
                1 << int(rng.integers(8)))
        return data

    def pull(self, f: dict, *, disable_back_source: bool) -> tuple[dict,
                                                                   Request]:
        import jax
        import numpy as np

        r = Request(f["name"], f["size"], time.monotonic())
        data = self._read(f)
        arrays = {}
        for i, s in enumerate(f["shards"]):
            view = data[s["range_start"]:s["range_start"] + s["range_size"]]
            arrays[s["name"]] = jax.device_put(
                view.view(np.dtype(s["dtype"])).reshape(s["shape"]),
                self.devices[i % len(self.devices)])
        jax.block_until_ready(list(arrays.values()))
        r.t_ready = time.monotonic()
        r.ok = True
        r.bytes_p2p = f["size"]              # nothing came from an origin
        return arrays, r

    def delete(self, files: list[dict]) -> None:
        pass

    def preseed(self, files: list[dict]) -> None:
        pass

    def stream(self, files: list[dict], *, depth: int,
               delete_after: bool) -> Iterator[list]:
        import jax
        import numpy as np

        for f in files:
            data = self._read(f)
            n = -(-data.shape[0] // self.unit_bytes)
            padded = np.zeros(n * self.unit_bytes, np.uint8)
            padded[:data.shape[0]] = data
            arrays = [jax.device_put(
                padded[i * self.unit_bytes:(i + 1) * self.unit_bytes],
                self.devices[0]) for i in range(n)]
            jax.block_until_ready(arrays)
            yield arrays

    def flight_of(self, f: dict):
        return None
