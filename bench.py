"""Benchmark: P2P fan-out aggregate throughput vs naive direct downloads.

Shape of BASELINE config #2 shrunk to one machine, with every component in
its OWN OS process (origin, scheduler, seed daemon, N leecher daemons —
sharing one event loop would measure the GIL, not the framework): an origin
serving a synthetic weights file, one seed daemon, a real scheduler, and N
leechers that must replicate the file with back-source disabled (every byte
rides the mesh). The baseline is N processes each pulling the whole file
straight from the origin — what a fleet without the framework does.

Piece stores live in tmpfs: the TPU-native terminal sink is HBM/host RAM
(tpu/hbm_sink.py), so a ~100 MB/s VM boot disk would measure itself.

Prints ONE JSON line:
  {"metric": ..., "value": GB/s aggregate delivered, "unit": "GB/s",
   "vs_baseline": ours / naive}
"""

from __future__ import annotations

import asyncio
import json
import logging
import os
import statistics
import subprocess
import sys
import tempfile
import time

# BENCH_DEBUG_DIR enables timelines/parent dumps (cheap); BENCH_LOG_DEBUG
# additionally turns on DEBUG logging (expensive — distorts the measurement
# on CPU-bound hosts; keep off unless chasing a specific trace)
logging.basicConfig(
    level=logging.DEBUG if os.environ.get("BENCH_LOG_DEBUG") else logging.WARNING,
    stream=sys.stderr)

SIZE_MB = int(os.environ.get("BENCH_SIZE_MB", "128"))
N_LEECHERS = int(os.environ.get("BENCH_LEECHERS", "16"))
ORIGIN_MBPS = float(os.environ.get("BENCH_ORIGIN_MBPS", "64"))
# per-host upload NIC model (MB/s). On one machine loopback is ~free, which
# makes a star (seed serves everyone) look optimal and measures nothing; the
# cap restores the real constraint — each host's egress bandwidth — so the
# mesh only wins by actually fanning out through intermediate peers.
NIC_MBPS = float(os.environ.get("BENCH_NIC_MBPS", "128"))
REPO = os.path.dirname(os.path.abspath(__file__))


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def ensure_native() -> None:
    so = os.path.join(REPO, "native", "build", "libdfnative.so")
    if not os.path.exists(so):
        subprocess.run(["make", "-C", os.path.join(REPO, "native")],
                       capture_output=True, check=False)
    # environments with PYTHONDONTWRITEBYTECODE make every spawned role
    # re-compile the whole package (~170 modules, seconds per process, ×17
    # processes): compile once so the .pyc cache serves the fleet
    import compileall
    compileall.compile_dir(os.path.join(REPO, "dragonfly2_tpu"),
                           quiet=2, workers=0)


def base_tmp() -> str:
    return "/dev/shm" if os.path.isdir("/dev/shm") else tempfile.gettempdir()


# ======================================================================
# worker roles (each runs in its own process: python bench.py --role X)
# ======================================================================

async def role_origin(path: str, mbps: float) -> None:
    """Serve ``path`` with Range support, paced at ``mbps`` MB/s total.

    The cap models the real scarce resource — origin/WAN/GCS egress per
    cluster (BASELINE's "% origin egress saved"). An uncapped loopback
    origin would make any P2P layer look like pure overhead, which is not
    the deployment the reference or this framework exists for. Tracks bytes
    served at /__stats__.
    """
    from aiohttp import web

    from dragonfly2_tpu.common.piece import parse_http_range
    from dragonfly2_tpu.common.rate import TokenBucket

    size = os.path.getsize(path)
    bucket = TokenBucket(mbps * 1e6, burst=4e6) if mbps > 0 else None
    served = {"bytes": 0}

    async def handle(request: web.Request):
        if request.path == "/__stats__":
            return web.json_response(served)
        start, length = 0, size
        status, headers = 200, {"Accept-Ranges": "bytes",
                                "Content-Length": "0"}
        rng = request.headers.get("Range")
        if rng:
            r = parse_http_range(rng, size)
            start, length = r.start, r.length
            status = 206
            headers["Content-Range"] = f"bytes {r.start}-{r.end-1}/{size}"
        headers["Content-Length"] = str(length)
        if request.method == "HEAD":
            # NEVER write a body for HEAD: a manually-streamed body poisons
            # the keep-alive connection (the client pools it as clean, the
            # stale body bytes then hang the next GET that reuses it)
            return web.Response(status=status, headers=headers)
        resp = web.StreamResponse(status=status, headers=headers)
        await resp.prepare(request)
        with open(path, "rb") as f:
            f.seek(start)
            remaining = length
            while remaining > 0:
                chunk = f.read(min(1 << 20, remaining))
                if not chunk:
                    break
                if bucket is not None:
                    await bucket.acquire(len(chunk))
                await resp.write(chunk)
                served["bytes"] += len(chunk)
                remaining -= len(chunk)
        await resp.write_eof()
        return resp

    app = web.Application()
    app.router.add_route("*", "/{tail:.*}", handle)
    runner = web.AppRunner(app, access_log=None)
    await runner.setup()
    site = web.TCPSite(runner, "127.0.0.1", 0)
    await site.start()
    from dragonfly2_tpu.common.aiohttp_util import resolve_port
    print(json.dumps({"port": resolve_port(runner)}), flush=True)
    await asyncio.Event().wait()


async def role_seed(workdir: str) -> None:
    from dragonfly2_tpu.daemon.config import (DaemonConfig, StorageSection,
                                              UploadConfig)
    from dragonfly2_tpu.daemon.daemon import Daemon

    cfg = DaemonConfig(workdir=workdir, host_ip="127.0.0.1", hostname="seed",
                       is_seed=True,
                       upload=UploadConfig(
                           rate_limit_bps=int(NIC_MBPS * 1e6),
                           # live /debug/{stacks,profile} on the upload port
                           # for wave-stall investigations
                           debug_endpoints=bool(
                               os.environ.get("BENCH_DEBUG_DIR"))),
                       storage=StorageSection(gc_interval_s=3600))
    daemon = Daemon(cfg)
    await daemon.start()
    print(json.dumps({"rpc_port": daemon.rpc.port,
                      "download_port": daemon.upload_server.port}), flush=True)
    await asyncio.Event().wait()


async def role_scheduler(seed_rpc: int, seed_dl: int) -> None:
    from dragonfly2_tpu.scheduler import Scheduler, SchedulerConfig
    from dragonfly2_tpu.scheduler.config import SeedPeerAddr

    sched = Scheduler(SchedulerConfig(seed_peers=[SeedPeerAddr(
        ip="127.0.0.1", rpc_port=seed_rpc, download_port=seed_dl)]))
    await sched.start()
    print(json.dumps({"addr": sched.address}), flush=True)
    await asyncio.Event().wait()


async def role_leecher(workdir: str, name: str, sched_addr: str,
                       url: str) -> None:
    from dragonfly2_tpu.daemon.config import (DaemonConfig,
                                              SchedulerConfig as DSched,
                                              StorageSection, TracingConfig,
                                              UploadConfig)
    from dragonfly2_tpu.daemon.daemon import Daemon
    from dragonfly2_tpu.idl.messages import DownloadRequest
    from dragonfly2_tpu.rpc.client import Channel, ServiceClient

    dbg = os.environ.get("BENCH_DEBUG_DIR")
    cfg = DaemonConfig(workdir=workdir, host_ip="127.0.0.1", hostname=name,
                       scheduler=DSched(addresses=[sched_addr],
                                        schedule_timeout_s=60.0),
                       upload=UploadConfig(rate_limit_bps=int(NIC_MBPS * 1e6)),
                       storage=StorageSection(gc_interval_s=3600),
                       tracing=TracingConfig(
                           enabled=bool(dbg),
                           jsonl_path=dbg and os.path.join(
                               dbg, f"{name}.traces.jsonl") or ""))
    daemon = Daemon(cfg)
    await daemon.start()
    print("READY", flush=True)
    await asyncio.get_running_loop().run_in_executor(None, sys.stdin.readline)

    ch = Channel(f"unix:{daemon.unix_sock}")
    client = ServiceClient(ch, "df.daemon.Daemon")
    out = os.path.join(workdir, "replica.bin")
    t0 = time.monotonic()
    task_id = None
    timeline: list[tuple[float, int]] = []
    sampler = None
    if os.environ.get("BENCH_DEBUG_DIR"):
        async def sample() -> None:
            while True:
                c = daemon.ptm.conductor(task_id) if task_id else None
                n_seed = n_known = -1
                if c is not None:
                    n = len(c.ready)
                    if c.storage is not None:
                        n_seed = sum(1 for p in c.storage.md.pieces.values()
                                     if "seed" in (p.source or ""))
                    eng = c._p2p_engine
                    if eng is not None:
                        n_known = len(eng.dispatcher._pieces) + n
                else:
                    n = -1
                timeline.append((time.monotonic() - t0, n, n_seed, n_known))
                await asyncio.sleep(0.1)
        sampler = asyncio.get_running_loop().create_task(sample())
    async for resp in client.unary_stream("Download", DownloadRequest(
            url=url, output=out, disable_back_source=True, timeout_s=600.0)):
        task_id = resp.task_id or task_id
    elapsed = time.monotonic() - t0
    if sampler is not None:
        sampler.cancel()
        print(json.dumps({"timeline": [[round(t, 2), *rest]
                                       for t, *rest in timeline]}),
              file=sys.stderr, flush=True)
    size = os.path.getsize(out)
    sources: dict[str, int] = {}
    engine_state = {}
    conductor = daemon.ptm.conductor(task_id) if task_id else None
    engine = conductor._p2p_engine if conductor is not None else None
    if conductor is not None and conductor.storage is not None:
        for p in conductor.storage.md.pieces.values():
            key = (p.source or "origin")[-10:]
            sources[key] = sources.get(key, 0) + 1
        if engine is not None and os.environ.get("BENCH_DEBUG_DIR"):
            engine_state = {
                pid[-10:]: {"ejected": st.ejected,
                            "nspb": round(st.ns_per_byte, 1),
                            "try": st.attempts, "ann": st.announced}
                for pid, st in engine.dispatcher.parents.items()}
    out_msg = {"elapsed": elapsed, "bytes": size, "sources": sources,
               "name": name}
    if engine is not None:
        # structural convoy accounting: fraction of worker-seconds spent
        # parked in the dispatcher, and the slice of that waiting on a
        # busy seed (see PieceDispatcher.wait_stats)
        ws = dict(engine.dispatcher.wait_stats)
        worker_s = max(elapsed * engine.parallelism, 1e-9)
        out_msg["wait"] = {k: round(v, 3) for k, v in ws.items()}
        out_msg["idle_frac"] = round(sum(ws.values()) / worker_s, 4)
        out_msg["seed_wait_frac"] = round(ws["seed_busy_s"] / worker_s, 4)
    if engine_state:
        out_msg["parents"] = engine_state
    print(json.dumps(out_msg), flush=True)
    # stay up until the whole wave is done: a real fleet's daemons keep
    # serving after their own download completes — early exit here would
    # rip parents out from under the stragglers
    await asyncio.get_running_loop().run_in_executor(None, sys.stdin.readline)
    await ch.close()
    await daemon.stop()


async def role_direct(workdir: str, url: str) -> None:
    import aiohttp

    print("READY", flush=True)
    await asyncio.get_running_loop().run_in_executor(None, sys.stdin.readline)
    t0 = time.monotonic()
    got = 0
    out = os.path.join(workdir, "direct.bin")
    async with aiohttp.ClientSession() as session:
        async with session.get(url) as resp:
            with open(out, "wb") as f:
                async for chunk in resp.content.iter_chunked(1 << 20):
                    f.write(chunk)
                    got += len(chunk)
    elapsed = time.monotonic() - t0
    print(json.dumps({"elapsed": elapsed, "bytes": got}), flush=True)


# ======================================================================
# TPU device-ingest phase (runs in the MAIN process on the real chip)
# ======================================================================

async def tpu_ingest_bench(data_path: str, workdir: str) -> dict:
    """BASELINE config #4's device leg: origin → pieces → device_put →
    result() through the real daemon path (conductor + DeviceIngest), on
    this host's chips. Reports:

      device_ingest_gbps   — pure host-buffer → HBM transfer bandwidth
      ingest_overlap_eff   — fraction of that transfer time hidden behind
                             the download (1.0 = fully overlapped)
    """
    import numpy as np

    from aiohttp import web

    import jax

    from dragonfly2_tpu.common.piece import parse_http_range
    from dragonfly2_tpu.daemon.config import DaemonConfig, StorageSection
    from dragonfly2_tpu.daemon.daemon import Daemon
    from dragonfly2_tpu.idl.messages import DeviceSink

    size = os.path.getsize(data_path)

    async def handle(request: web.Request):
        start, length = 0, size
        status, headers = 200, {"Accept-Ranges": "bytes"}
        rng = request.headers.get("Range")
        if rng:
            r = parse_http_range(rng, size)
            start, length = r.start, r.length
            status = 206
            headers["Content-Range"] = f"bytes {r.start}-{r.end-1}/{size}"
        headers["Content-Length"] = str(length)
        if request.method == "HEAD":
            # see role_origin: a HEAD body poisons the pooled connection
            return web.Response(status=status, headers=headers)
        resp = web.StreamResponse(status=status, headers=headers)
        await resp.prepare(request)
        with open(data_path, "rb") as f:
            f.seek(start)
            remaining = length
            while remaining > 0:
                chunk = f.read(min(1 << 20, remaining))
                if not chunk:
                    break
                await resp.write(chunk)
                remaining -= len(chunk)
        await resp.write_eof()
        return resp

    app = web.Application()
    app.router.add_route("*", "/{tail:.*}", handle)
    runner = web.AppRunner(app, access_log=None)
    await runner.setup()
    site = web.TCPSite(runner, "127.0.0.1", 0)
    await site.start()
    from dragonfly2_tpu.common.aiohttp_util import resolve_port
    base = f"http://127.0.0.1:{resolve_port(runner)}"

    daemon = Daemon(DaemonConfig(
        workdir=os.path.join(workdir, "tpudaemon"), host_ip="127.0.0.1",
        hostname="tpubench", storage=StorageSection(gc_interval_s=3600)))
    await daemon.start()
    try:
        # 1) pure device transfer bandwidth: same bytes, one put per DMA unit
        buf = np.fromfile(data_path, dtype=np.uint8)
        dev = jax.local_devices()[0]
        jax.device_put(buf[:1 << 20], dev).block_until_ready()   # warm path
        t0 = time.monotonic()
        put = jax.device_put(buf, dev)
        put.block_until_ready()
        t_ingest = time.monotonic() - t0
        del put

        async def run_download(url: str, sink: DeviceSink | None):
            """Returns (total_wall, hidden_fraction). hidden is measured
            STRUCTURALLY inside the one run — the fraction of device-
            transfer time that executed before the download's last byte —
            because on this host single-download wall clocks swing ±50%
            (VM jitter), far more than the transfer time being hidden, so
            subtracting wall clocks of separate runs measures only noise."""
            t0 = time.monotonic()
            task_id, ingest, t_dl_end = await _run_sink_task(
                daemon, url, os.path.join(workdir, "tpu.out"), sink)
            hidden = 0.0
            if ingest is not None:
                # block on the last DMA off-loop (result() is blocking)
                await asyncio.to_thread(ingest.result)
                spans = list(ingest.transfer_spans)
                total = sum(e - s for s, e in spans)
                if total > 0:
                    hidden = sum(max(0.0, min(e, t_dl_end) - s)
                                 for s, e in spans) / total
            elapsed = time.monotonic() - t0
            # 6 runs over distinct URLs: drop each task's pieces + device
            # arrays before the next, or peak residency is 6x file size
            if task_id is not None:
                await daemon.ptm.delete_task(task_id)
            return elapsed, hidden

        t_dl = statistics.median(
            [(await run_download(f"{base}/plain{i}.bin", None))[0]
             for i in range(3)])
        sink_runs = [await run_download(f"{base}/sink{i}.bin",
                                        DeviceSink(enabled=True))
                     for i in range(3)]
        t_overlap = statistics.median([t for t, _ in sink_runs])
        hidden = statistics.median([h for _, h in sink_runs])
        gbps = size / 1e9 / t_ingest
        log(f"tpu ingest: pure device_put {gbps:.2f} GB/s ({t_ingest:.2f}s), "
            f"download {t_dl:.2f}s, with sink {t_overlap:.2f}s -> "
            f"{hidden:.0%} of device transfer ran during the download "
            f"[{dev.platform}]")
        train_stats = await _train_during_ingest(daemon, base, workdir, size)
        return {"device_ingest_gbps": round(gbps, 3),
                "ingest_overlap_efficiency": round(hidden, 3),
                "device_platform": dev.platform,
                "device_kind": dev.device_kind,
                **train_stats}
    finally:
        await daemon.stop()
        await runner.cleanup()


async def _run_sink_task(daemon, url: str, out_path: str, sink):
    """One download task's lifecycle through the real daemon path; returns
    (task_id, device_ingest | None, when the last piece landed). Both
    overlap measurements share this so a fix to task collection applies
    to each exactly once. A sink task's done frame only comes once every
    shard is on the device, so the download's own end is the last
    progress frame before it."""
    from dragonfly2_tpu.idl.messages import DownloadRequest

    task_id = None
    t_last_piece = time.monotonic()
    async for resp in daemon.ptm.start_file_task(DownloadRequest(
            url=url, output=out_path, device_sink=sink, timeout_s=600.0)):
        task_id = resp.task_id or task_id
        if not resp.done:
            t_last_piece = time.monotonic()
    conductor = daemon.ptm.conductor(task_id) if task_id else None
    ingest = conductor.device_ingest if conductor is not None else None
    return task_id, ingest if sink is not None else None, t_last_piece


async def _train_during_ingest(daemon, base: str, workdir: str,
                               size: int) -> dict:
    """BASELINE config #4's actual claim: prefetch into HBM *during* JAX
    training. Runs a jitted train-step loop on the same device while
    ``DeviceIngest`` streams the file through the real daemon path, and
    reports how much the training loop slowed down plus the DMA-active
    ingest bandwidth achieved concurrently. On real TPU the device_put
    contends with the train step for DMA engines + HBM bandwidth — this is
    the number the README's overlap story rests on.
    """
    import threading

    import jax

    from dragonfly2_tpu.idl.messages import DeviceSink
    from dragonfly2_tpu.trainer import models

    key = jax.random.PRNGKey(0)
    params = models.init_mlp(key)
    opt = models.make_optimizer()
    opt_state = opt.init(params)
    batch = models.synthetic_mlp_batch(key, 4096)
    train_step = models.make_train_step(models.mlp_loss, opt)
    params, opt_state, loss = train_step(params, opt_state, batch)
    jax.block_until_ready(loss)                      # compile outside timing

    state = {"params": params, "opt": opt_state}

    def steps_per_s(duration_s: float, stop: threading.Event | None = None,
                    stamps: list | None = None) -> tuple[float, int]:
        n = 0
        t0 = time.monotonic()
        while time.monotonic() - t0 < duration_s \
                and (stop is None or not stop.is_set()):
            state["params"], state["opt"], loss = train_step(
                state["params"], state["opt"], batch)
            jax.block_until_ready(loss)
            n += 1
            if stamps is not None:
                stamps.append(time.monotonic())
        dt = time.monotonic() - t0
        return n / dt if dt > 0 else 0.0, n

    base_sps, _ = steps_per_s(3.0)

    stop = threading.Event()
    stamps: list[float] = []
    train_task = asyncio.create_task(
        asyncio.to_thread(steps_per_s, 600.0, stop, stamps))
    dma_active = 0.0
    streamed = 0
    windows: list[tuple[float, float]] = []
    try:
        # stream until the train loop has a statistically usable window
        # (a single fast download can be < a handful of steps): up to 3
        # serial files, each a distinct task
        for i in range(3):
            t_w0 = time.monotonic()
            task_id, ingest, _ = await _run_sink_task(
                daemon, f"{base}/train-overlap{i}.bin",
                os.path.join(workdir, "train-overlap.out"),
                DeviceSink(enabled=True))
            if ingest is not None:
                await asyncio.to_thread(ingest.result)
                dma_active += sum(e - s for s, e in ingest.transfer_spans)
                streamed += size
                # window closes at last-DMA-done, BEFORE the bookkeeping
                # (delete_task, loop checks) — the slowdown number must
                # only average steps that ran against live ingest, not the
                # gaps (and a failed sink task contributes no window)
                windows.append((t_w0, time.monotonic()))
            if task_id is not None:
                await daemon.ptm.delete_task(task_id)
            in_window = sum(1 for t in stamps
                            if any(s <= t <= e for s, e in windows))
            if in_window >= 15 or stop.is_set() or train_task.done():
                break
    finally:
        stop.set()
    await train_task
    window_s = sum(e - s for s, e in windows)
    during_steps = sum(1 for t in stamps
                       if any(s <= t <= e for s, e in windows))
    during_sps = during_steps / window_s if window_s > 0 else 0.0
    slowdown = (1.0 - during_sps / base_sps) if base_sps > 0 else 0.0
    gbps_during = streamed / 1e9 / dma_active if dma_active > 0 else 0.0
    log(f"train during ingest: {base_sps:.1f} -> {during_sps:.1f} steps/s "
        f"({slowdown:.1%} slowdown, {during_steps} steps while streaming), "
        f"ingest DMA-active bandwidth {gbps_during:.2f} GB/s")
    return {"train_steps_per_s_baseline": round(base_sps, 2),
            "train_steps_per_s_during_ingest": round(during_sps, 2),
            "train_step_slowdown_pct": round(100 * slowdown, 1),
            "device_ingest_gbps_during_train": round(gbps_during, 3)}


# ======================================================================
# orchestration
# ======================================================================

class Proc:
    def __init__(self, args: list[str], stderr_path: str | None = None,
                 env: dict | None = None):
        stderr = (open(stderr_path, "w") if stderr_path
                  else subprocess.DEVNULL)
        self.p = subprocess.Popen(
            [sys.executable, os.path.join(REPO, "bench.py"), *args],
            stdout=subprocess.PIPE, stderr=stderr,
            stdin=subprocess.PIPE, text=True, cwd=REPO,
            env={**os.environ, **env} if env else None)

    def read_json(self, timeout: float = 120.0):
        line = self._read_line(timeout)
        return json.loads(line)

    def wait_ready(self, timeout: float = 240.0) -> None:
        # generous: 16 fresh interpreters importing on one contended vCPU
        # can legitimately take minutes to all come up
        line = self._read_line(timeout)
        assert line.strip() == "READY", f"unexpected: {line!r}"

    def _read_line(self, timeout: float) -> str:
        import select
        deadline = time.monotonic() + timeout
        while True:
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                raise TimeoutError("worker did not report in time")
            # select before readline: readline() itself blocks and would
            # defeat the deadline when a worker hangs without printing
            ready, _, _ = select.select([self.p.stdout], [], [],
                                        min(remaining, 1.0))
            if ready:
                line = self.p.stdout.readline()
                if line:
                    return line
            if self.p.poll() is not None:
                raise RuntimeError(f"worker died: rc={self.p.returncode}")

    def go(self) -> None:
        try:
            self.p.stdin.write("\n")
            self.p.stdin.flush()
        except (BrokenPipeError, OSError):
            pass   # role already exited (direct pulls don't linger)

    def kill(self) -> None:
        if self.p.poll() is None:
            self.p.kill()
            self.p.wait()


def _cpu_sample() -> tuple[float, float]:
    """(busy_jiffies, total_jiffies) across the host."""
    with open("/proc/stat") as f:
        parts = f.readline().split()[1:]
    vals = [float(x) for x in parts]
    idle = vals[3] + (vals[4] if len(vals) > 4 else 0.0)
    return sum(vals) - idle, sum(vals)


def run_wave(procs: list[Proc]) -> tuple[float, list[float], float, dict]:
    """READY-barrier, then GO all; returns (max elapsed, per-proc
    seed-sourced piece fractions, host CPU utilization during the wave,
    wait accounting {idle_fracs, seed_wait_fracs}).

    The utilization is reported so sublinearity reads honestly: on a
    host with fewer cores than daemons (the 1-vCPU bench VM) a 2x-work
    wave on a saturated CPU takes ~2x wall-clock regardless of scheduling
    quality — the NICs in the model scale with peer count, the cores
    running the daemons do not. The wait accounting separates those: a
    convoy shows up as workers idle in the dispatcher, CPU saturation as
    idle ≈ 0 with util ≈ 1.
    """
    for p in procs:
        p.wait_ready()
    cpu0 = _cpu_sample()
    for p in procs:
        p.go()
    results = [p.read_json(timeout=600.0) for p in procs]
    cpu1 = _cpu_sample()
    cpu_util = ((cpu1[0] - cpu0[0]) / max(cpu1[1] - cpu0[1], 1.0))
    seed_fracs: list[float] = []
    waits = {"idle_fracs": [], "seed_wait_fracs": []}
    for r in results:
        assert r["bytes"] == SIZE_MB << 20, f"short transfer: {r}"
        if "idle_frac" in r:
            waits["idle_fracs"].append(r["idle_frac"])
            waits["seed_wait_fracs"].append(r["seed_wait_frac"])
        if r.get("sources"):
            log(f"  piece sources: {r['sources']} ({r['elapsed']:.2f}s"
                + (f", idle {r['idle_frac']:.0%}" if "idle_frac" in r else "")
                + ")"
                + (f" parents={r['parents']}" if r.get("parents") else ""))
            total = sum(r["sources"].values())
            from_seed = sum(n for k, n in r["sources"].items() if "seed" in k)
            seed_fracs.append(from_seed / total if total else 0.0)
    for p in procs:
        p.go()   # whole wave done: daemons may now exit
    return max(r["elapsed"] for r in results), seed_fracs, cpu_util, waits


def _clean_wave_dirs(workdir: str, tag: str, n: int) -> None:
    """Drop a wave's piece stores + replicas NOW: workdirs live in
    /dev/shm (RAM), and N waves x 16 leechers x 2 file-size copies
    accumulate tens of GB of tmpfs pages — which measurably slowed every
    later wave on the 1-vCPU bench VM (the r04 escalating-wave mystery:
    13s -> 67s across identical waves, cured by this cleanup)."""
    import shutil
    dbg = os.environ.get("BENCH_DEBUG_DIR")
    for i in range(n):
        d = os.path.join(workdir, f"{tag}{i}")
        if dbg:
            # keep logs/ — the finally-block forensics copytree needs the
            # per-daemon file logs; drop only the bulky payload dirs
            for sub in ("data", "cache", "run"):
                shutil.rmtree(os.path.join(d, sub), ignore_errors=True)
            try:
                os.unlink(os.path.join(d, "replica.bin"))
            except OSError:
                pass
        else:
            shutil.rmtree(d, ignore_errors=True)


def fanout_wave(workdir: str, tag: str, n: int, sched_addr: str,
                url: str, daemons: list["Proc"], *,
                origin_bytes_fn=None, _retry: bool = True,
                env: dict | None = None
                ) -> tuple[float, list[float], float, dict, int]:
    """Returns (max elapsed, seed fractions, cpu util, wait accounting,
    origin egress).

    Egress is sampled INSIDE the wave (around the attempt that succeeded)
    so an aborted first attempt's partial origin pulls don't inflate the
    successful retry's egress-saved accounting."""
    pre = origin_bytes_fn() if origin_bytes_fn else 0
    leechers = [Proc(["--role", "leecher",
                      os.path.join(workdir, f"{tag}{i}"), f"{tag}leech{i}",
                      sched_addr, url],
                     stderr_path=os.environ.get("BENCH_DEBUG_DIR") and
                     os.path.join(os.environ["BENCH_DEBUG_DIR"],
                                  f"{tag}{i}.err"),
                     env=env)
                for i in range(n)]
    daemons.extend(leechers)   # killed on any failure path
    try:
        result = run_wave(leechers)
    except (TimeoutError, RuntimeError) as exc:
        # a straggler spawn on a contended host (16 interpreters on one
        # vCPU) must not abort the whole bench — kill this wave's procs,
        # free its tmpfs, and retry ONCE on a fresh tag + task
        for p in leechers:
            p.kill()
        _clean_wave_dirs(workdir, tag, n)
        if not _retry:
            raise
        log(f"wave {tag} spawn failed ({exc}); retrying once")
        return fanout_wave(workdir, f"{tag}r", n, sched_addr,
                           url + ".retry", daemons,
                           origin_bytes_fn=origin_bytes_fn, _retry=False,
                           env=env)
    # reap this wave's processes BEFORE the caller starts the next one:
    # 16 daemons' teardown (channel close, daemon.stop, interpreter exit)
    # costs seconds of CPU that would otherwise bleed into the next timed
    # wave on a core-bound host
    for p in leechers:
        try:
            p.p.wait(timeout=30)
        except subprocess.TimeoutExpired:
            p.kill()
    _clean_wave_dirs(workdir, tag, n)
    egress = (origin_bytes_fn() - pre) if origin_bytes_fn else 0
    return (*result, egress)


def role_tpu(data_path: str, workdir: str) -> None:
    """The TPU ingest phase, in this process (the one that holds the
    chip): prints one JSON line. A host without a TPU fails here — the
    phase measures the device and never stands in another backend."""
    from dragonfly2_tpu.tpu import runtime

    platform = runtime.bring_up()[0].platform
    if platform != "tpu":
        raise SystemExit(f"bench tpu phase needs a TPU; jax found "
                         f"{platform!r}")
    stats = asyncio.run(tpu_ingest_bench(data_path, workdir))
    print(json.dumps(stats), flush=True)


def _tpu_phase(data_path: str, workdir: str) -> dict:
    """Run the TPU phase once in its own process (this one never touches
    jax); its failure is the bench's failure."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"),
         "--role", "tpu", data_path, workdir],
        stdout=subprocess.PIPE, text=True, cwd=REPO, timeout=900.0,
        check=True)
    return json.loads(proc.stdout.strip().splitlines()[-1])


def _calibrate() -> float:
    """Fixed-work CPU probe (GB/s of sha256 over 64 MiB): the bench host's
    effective speed swings ~2-3x between runs (shared-host phases — the pure
    device_put figure shows the same oscillation), so every run records the
    host speed it saw alongside the numbers it produced."""
    import hashlib
    buf = b"\xa5" * (64 << 20)
    t0 = time.monotonic()
    hashlib.sha256(buf).hexdigest()
    return round(len(buf) / 1e9 / (time.monotonic() - t0), 3)


def _calibrate_mp(workers: int = 4) -> float:
    """Aggregate GB/s of ``workers`` parallel sha256 processes. The
    single-thread calib stays flat while co-tenant load slows saturated
    multi-process waves 2x (r5: full waves 12s -> 27s at constant
    single-thread calib) — THIS probe captures the contention those waves
    actually run under, so cross-run wave comparisons can be normalized."""
    # readiness handshake then a SHARED start epoch: without the barrier,
    # spawn skew (interpreter startup is seconds on this host) lets the
    # windows land disjoint and the "contended" sum approaches N x
    # single-thread. Each worker reports when its window actually opened
    # so stragglers can be excluded from the sum. Any failure degrades to
    # 0.0 — this probe must never cost the run its one JSON output line.
    code = ("import hashlib,sys,time\n"
            "print('ready', flush=True)\n"
            "start = float(sys.stdin.readline())\n"
            "time.sleep(max(0.0, start - time.time()))\n"
            "opened = time.time()\n"
            "buf = b'\\xa5' * (8 << 20)\n"
            "n, t0 = 0, time.monotonic()\n"
            "while time.monotonic() - t0 < 1.5:\n"
            "    hashlib.sha256(buf).hexdigest(); n += 1\n"
            "print(opened, n * (8 << 20) / (time.monotonic() - t0))")
    procs = []
    try:
        for _ in range(workers):
            procs.append(subprocess.Popen(
                [sys.executable, "-c", code], stdin=subprocess.PIPE,
                stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
                text=True))
        for p in procs:
            if p.stdout.readline().strip() != "ready":
                raise RuntimeError("calib worker failed to start")
        start_at = time.time() + 0.5
        for p in procs:
            p.stdin.write(f"{start_at}\n")
            p.stdin.flush()
        results = []
        for p in procs:
            opened, rate = p.stdout.readline().split()
            results.append((float(opened), float(rate)))
            p.wait(timeout=30)
    except Exception:  # noqa: BLE001 - diagnostic probe only
        return 0.0
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    on_time = [rate for opened, rate in results
               if opened <= start_at + 1.0]
    if len(on_time) < 2:
        return 0.0       # windows didn't overlap: no contention measured
    return round(sum(on_time) / 1e9, 3)


def main() -> None:
    ensure_native()
    workdir = tempfile.mkdtemp(prefix="dfbench-", dir=base_tmp())
    data_path = os.path.join(workdir, "weights.bin")
    with open(data_path, "wb") as f:
        remaining = SIZE_MB << 20
        while remaining > 0:
            n = min(remaining, 64 << 20)
            f.write(os.urandom(n))
            remaining -= n

    daemons: list[Proc] = []
    try:
        origin = Proc(["--role", "origin", data_path, str(ORIGIN_MBPS)])
        daemons.append(origin)
        origin_base = f"http://127.0.0.1:{origin.read_json()['port']}"
        url = f"{origin_base}/weights.bin"

        import urllib.request

        def origin_bytes() -> int:
            with urllib.request.urlopen(f"{origin_base}/__stats__") as r:
                return json.loads(r.read())["bytes"]

        log(f"bench: {SIZE_MB} MiB x {N_LEECHERS} leechers, origin "
            f"{ORIGIN_MBPS:.0f} MB/s, per-host upload NIC {NIC_MBPS:.0f} MB/s "
            f"(multi-process)")
        # direct baseline: origin-capped, so aggregate throughput is the
        # origin rate no matter how many clients pull — 4 processes measure
        # it; egress for N direct clients is N x size by definition.
        n_direct = min(N_LEECHERS, 4)
        direct = [Proc(["--role", "direct", os.path.join(workdir, f"d{i}"),
                        url]) for i in range(n_direct)]
        daemons.extend(direct)   # killed on any failure path
        for i in range(n_direct):
            os.makedirs(os.path.join(workdir, f"d{i}"), exist_ok=True)
        direct_s, _, _, _ = run_wave(direct)
        direct_rate = n_direct * (SIZE_MB << 20) / direct_s
        direct_egress = N_LEECHERS * (SIZE_MB << 20)
        log(f"baseline direct: {n_direct} pulls in {direct_s:.2f}s "
            f"-> {direct_rate / 1e9:.3f} GB/s aggregate (egress for "
            f"{N_LEECHERS} clients = {direct_egress / 1e6:.0f} MB)")

        dbg = os.environ.get("BENCH_DEBUG_DIR")
        seed = Proc(["--role", "seed", os.path.join(workdir, "seed")],
                    stderr_path=dbg and os.path.join(dbg, "seed.err"))
        daemons.append(seed)
        seed_info = seed.read_json()
        sched = Proc(["--role", "scheduler", str(seed_info["rpc_port"]),
                      str(seed_info["download_port"])],
                     stderr_path=dbg and os.path.join(dbg, "sched.err"))
        daemons.append(sched)
        sched_addr = sched.read_json()["addr"]

        # Interleaved half/full cold waves, MEDIAN of each: one wave's
        # wall-clock on this shared host swings 2-3x within minutes, so a
        # single half wave against median-of-3 full waves measures drift,
        # not sublinearity (one run read 8.9x from a lucky half wave).
        # Alternating H,F,H,F,... exposes both sizes to the same drift.
        n_half = max(N_LEECHERS // 2, 1)
        runs = []
        half_runs = []
        n_runs = int(os.environ.get("BENCH_FANOUT_RUNS", "3"))
        for r in range(n_runs):
            half_s_r, _, half_cpu_r, _, half_egress = fanout_wave(
                workdir, f"h{r}x", n_half, sched_addr,
                f"{origin_base}/wave-half-{r}.bin", daemons,
                origin_bytes_fn=origin_bytes)
            half_runs.append({"elapsed_s": half_s_r, "cpu": half_cpu_r})
            log(f"fan-out {n_half} leechers (half run {r}): {half_s_r:.2f}s "
                f"(origin egress {half_egress / 1e6:.0f} MB)")
            fanout_s, seed_fracs, full_cpu, waits, p2p_egress = fanout_wave(
                workdir, f"l{r}x", N_LEECHERS, sched_addr,
                f"{origin_base}/wave-full-{r}.bin", daemons,
                origin_bytes_fn=origin_bytes)
            runs.append({"elapsed_s": fanout_s, "egress": p2p_egress,
                         "seed_fracs": seed_fracs, "cpu": full_cpu,
                         "waits": waits})
            seed_active = "?"
            try:
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{seed_info['download_port']}"
                        f"/metrics", timeout=5) as resp:
                    for line in resp.read().decode().splitlines():
                        if line.startswith("df_upload_active_transfers"):
                            seed_active = line.split()[-1]
            except Exception:
                pass
            log(f"fan-out {N_LEECHERS} leechers (run {r}): {fanout_s:.2f}s "
                f"(origin egress {p2p_egress / 1e6:.0f} MB, seed active "
                f"slots after: {seed_active})")
        runs.sort(key=lambda r: r["elapsed_s"])
        med = runs[len(runs) // 2]
        fanout_s, p2p_egress, full_cpu = (med["elapsed_s"], med["egress"],
                                          med["cpu"])
        seed_fracs = med["seed_fracs"]
        # elapsed AND cpu from the median half run (mixing the median
        # elapsed with the last run's cpu pairs different machine moments)
        half_runs.sort(key=lambda h: h["elapsed_s"])
        half_med = half_runs[len(half_runs) // 2]
        half_s, half_cpu = half_med["elapsed_s"], half_med["cpu"]
        egress_saved = 1.0 - p2p_egress / max(direct_egress, 1)
        max_seed_frac = max(seed_fracs) if seed_fracs else 0.0
        med_waits = med.get("waits", {"idle_fracs": [], "seed_wait_fracs": []})
        idle_max = max(med_waits["idle_fracs"], default=0.0)
        idle_med = (statistics.median(med_waits["idle_fracs"])
                    if med_waits["idle_fracs"] else 0.0)
        seed_wait_max = max(med_waits["seed_wait_fracs"], default=0.0)
        log(f"framework fan-out (median of {n_runs}): {N_LEECHERS} leechers "
            f"in {fanout_s:.2f}s (origin egress {p2p_egress / 1e6:.0f} MB, "
            f"saved {egress_saved:.1%}); sublinearity "
            f"{fanout_s / half_s:.2f}x for 2x leechers; max seed-sourced "
            f"fraction {max_seed_frac:.0%}; worker idle med {idle_med:.0%} "
            f"max {idle_max:.0%} (seed-wait max {seed_wait_max:.0%})")

        # CPU-unsaturated sublinearity: same protocol, rates cut far enough
        # that the 1-vCPU host stays below ~80% busy, making the wall-clock
        # scaling falsifiable (at full rates the host saturates and 2x work
        # MUST take ~2x wall regardless of scheduling quality). A dedicated
        # seed+scheduler pair carries the capped NIC model.
        unsat_stats = {}
        if os.environ.get("BENCH_UNSAT", "1") != "0":
            cap_nic = float(os.environ.get("BENCH_UNSAT_NIC_MBPS", "4"))
            cap_env = {"BENCH_NIC_MBPS": str(cap_nic)}
            useed = Proc(["--role", "seed", os.path.join(workdir, "useed")],
                         stderr_path=dbg and os.path.join(dbg, "useed.err"),
                         env=cap_env)
            daemons.append(useed)
            useed_info = useed.read_json()
            usched = Proc(["--role", "scheduler",
                           str(useed_info["rpc_port"]),
                           str(useed_info["download_port"])],
                          stderr_path=dbg and os.path.join(dbg, "usched.err"))
            daemons.append(usched)
            usched_addr = usched.read_json()["addr"]
            uhalf_s, _, uhalf_cpu, _, _ = fanout_wave(
                workdir, "uh", n_half, usched_addr,
                f"{origin_base}/wave-unsat-half.bin", daemons, env=cap_env)
            log(f"unsaturated fan-out {n_half} leechers: {uhalf_s:.2f}s "
                f"(cpu {uhalf_cpu:.0%})")
            ufull_s, _, ufull_cpu, uwaits, _ = fanout_wave(
                workdir, "uf", N_LEECHERS, usched_addr,
                f"{origin_base}/wave-unsat-full.bin", daemons, env=cap_env)
            u_idle_max = max(uwaits["idle_fracs"], default=0.0)
            log(f"unsaturated fan-out {N_LEECHERS} leechers: {ufull_s:.2f}s "
                f"(cpu {ufull_cpu:.0%}) -> sublinearity "
                f"{ufull_s / uhalf_s:.2f}x at NIC {cap_nic:.0f} MB/s, "
                f"worker idle max {u_idle_max:.0%}")
            unsat_stats = {
                "sublinearity_2x_cpu_unsaturated": round(ufull_s / uhalf_s, 3),
                "unsat_nic_mbps": cap_nic,
                "unsat_wave_cpu_util": {"half": round(uhalf_cpu, 3),
                                        "full": round(ufull_cpu, 3)},
                "unsat_runs_s": {"half": round(uhalf_s, 2),
                                 "full": round(ufull_s, 2)},
                "unsat_idle_frac_max": round(u_idle_max, 4),
            }

        tpu_stats = _tpu_phase(data_path, workdir)
    finally:
        for p in daemons:
            p.kill()
        import shutil
        if os.environ.get("BENCH_DEBUG_DIR"):
            # keep the role daemons' file logs (dflog writes per-concern
            # files under each workdir, not stderr) for stall forensics
            dst = os.path.join(os.environ["BENCH_DEBUG_DIR"], "workdir")
            shutil.rmtree(dst, ignore_errors=True)
            try:
                shutil.copytree(workdir, dst,
                                ignore=shutil.ignore_patterns(
                                    "*.bin", "*.out", "data", "pieces"))
            except Exception:  # noqa: BLE001 - forensics only
                pass
        shutil.rmtree(workdir, ignore_errors=True)

    delivered_gb = (SIZE_MB << 20) * N_LEECHERS / 1e9
    value = delivered_gb / fanout_s
    baseline = direct_rate / 1e9
    print(json.dumps({
        "metric": "p2p_fanout_aggregate_throughput",
        "value": round(value, 3),
        "unit": "GB/s",
        "vs_baseline": round(value / baseline, 3) if baseline else 0.0,
        "egress_saved": round(egress_saved, 3),
        "max_seed_sourced_fraction": round(max_seed_frac, 3),
        "sublinearity_2x": round(fanout_s / half_s, 3),
        "host_cpus": os.cpu_count(),
        "calib_sha256_gbps": _calibrate(),
        "calib_mp_gbps": _calibrate_mp(),
        "wave_cpu_util": {"half": round(half_cpu, 3),
                          "full": round(full_cpu, 3)},
        "fanout_runs_s": [round(r["elapsed_s"], 2) for r in runs],
        "half_runs_s": [round(h["elapsed_s"], 2) for h in half_runs],
        "leecher_idle_frac": {"median": round(idle_med, 4),
                              "max": round(idle_max, 4)},
        "seed_wait_frac_max": round(seed_wait_max, 4),
        **unsat_stats,
        **tpu_stats,
    }))


def _run_role(coro) -> None:
    """asyncio.run with optional cProfile dump (BENCH_PROFILE=dir)."""
    prof_dir = os.environ.get("BENCH_PROFILE")
    if not prof_dir:
        asyncio.run(coro)
        return
    import cProfile
    prof = cProfile.Profile()
    try:
        prof.runcall(asyncio.run, coro)
    finally:
        role = sys.argv[sys.argv.index("--role") + 1]
        prof.dump_stats(os.path.join(prof_dir, f"{role}-{os.getpid()}.prof"))


if __name__ == "__main__":
    if "--role" in sys.argv:
        role = sys.argv[sys.argv.index("--role") + 1]
        args = sys.argv[sys.argv.index("--role") + 2:]
        if role == "origin":
            _run_role(role_origin(args[0], float(args[1])))
        elif role == "seed":
            _run_role(role_seed(args[0]))
        elif role == "scheduler":
            _run_role(role_scheduler(int(args[0]), int(args[1])))
        elif role == "leecher":
            _run_role(role_leecher(args[0], args[1], args[2], args[3]))
        elif role == "direct":
            _run_role(role_direct(args[0], args[1]))
        elif role == "tpu":
            role_tpu(args[0], args[1])
        else:
            raise SystemExit(f"unknown role {role}")
    else:
        main()
