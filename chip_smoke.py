"""chip_smoke.py: the quickest proof that the system still starts on the chip.

One command, no network, everything made from ``--seed``. This process is
the ONE that holds the chip; around it run, as real child processes through
the launchers, a range-capable HTTP origin, a scheduler, a seed daemon and a
plain leecher daemon, none of which may touch JAX. In order:

  backend     bring JAX up off an event loop; the platform must be the
              expected one (``main()`` fixes it to ``tpu``)
  native      build native/build/libdfnative.so from the committed source
  checkpoint  a file whose manifest is the embedding and the routed experts
              of a few Moonlight-16B-A3B MoE layers at published widths:
              dfget through the leecher lands it on disk (the CLI path),
              then this process embeds a Daemon and pulls the same URL over
              P2P into device memory; every named array must be bit-equal
              to its slice of the file. It is ONE file where the machine
              lets a process write one that large, and otherwise as many
              files of whole tensors as the limit forces, the way a
              published checkpoint is sharded
  dataset     ShardPrefetcher over 4 shard URLs from a synchronous consumer,
              every array fed to a jitted reduction checked against numpy;
              one shard assembled into a global array over all devices
  trainer     train_mlp / train_gnn for a few epochs on the chip, the blobs
              round-tripped through trainer/serving.py

It exits non-zero on ANY failure and prints, as the last line of stdout on
success only, ``{"ok": true, "device": {...}}``. Timings it prints are smoke
timings for orientation, not benchmark results.
"""

from __future__ import annotations

import argparse
import asyncio
import dataclasses
import errno
import hashlib
import json
import os
import resource
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time

REPO = os.path.dirname(os.path.abspath(__file__))
MiB = 1 << 20


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What the smoke moves. The defaults are Moonlight-16B-A3B's published
    widths (moonshotai/Moonlight-16B-A3B config.json: vocab_size,
    hidden_size, n_routed_experts, moe_intermediate_size); scale is cut by
    the number of MoE layers only (the model has 26), never by a width."""

    vocab: int = 163840
    hidden: int = 2048
    experts: int = 64
    expert_width: int = 1408
    moe_layers: int = 3
    dataset_shards: int = 4
    dataset_shard_bytes: int = 256 * MiB


class SmokeFailure(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def say(msg: str) -> None:
    print(f"chip_smoke: {msg}", flush=True)


# ======================================================================
# the origin (a child process: python chip_smoke.py --role origin DIR PORT)
# ======================================================================

async def role_origin(root: str, port: int) -> None:
    """Serve ``root`` with Range support; bytes asked for are tallied at
    /__stats__ (a HEAD asks for none)."""
    from aiohttp import web

    from dragonfly2_tpu.common.piece import parse_http_range

    served = {"bytes": 0}

    async def handle(request: web.Request):
        if request.path == "/__stats__":
            return web.json_response(served)
        path = os.path.join(root, os.path.basename(request.path))
        if not os.path.isfile(path):
            return web.Response(status=404)
        if request.method == "GET":
            size = os.path.getsize(path)
            rng = request.headers.get("Range")
            served["bytes"] += (parse_http_range(rng, size).length
                                if rng else size)
        return web.FileResponse(path)

    app = web.Application()
    app.router.add_route("*", "/{tail:.*}", handle)
    runner = web.AppRunner(app, access_log=None)
    await runner.setup()
    await web.TCPSite(runner, "127.0.0.1", port).start()
    print(f"origin up: 127.0.0.1:{port}", flush=True)
    await asyncio.Event().wait()


# ======================================================================
# children
# ======================================================================

def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def die_with_parent():
    """A preexec_fn: the child gets SIGTERM when this process dies, however
    it dies — a smoke killed at a time limit leaves no swarm behind. The
    libc handle is taken here, before the fork; after it only prctl runs."""
    import ctypes
    prctl = ctypes.CDLL(None).prctl
    return lambda: prctl(1, signal.SIGTERM)      # PR_SET_PDEATHSIG


def child_env() -> dict:
    """Nothing steers a child's JAX: a child that needed steering would be
    a child that touches JAX."""
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    env.update(PYTHONPATH=REPO, PYTHONUNBUFFERED="1")
    return env


class Children:
    """The processes the smoke starts; every one is stopped on the way out."""

    def __init__(self, logdir: str):
        self.logdir = logdir
        self.procs: dict[str, subprocess.Popen] = {}

    def spawn(self, name: str, argv: list[str], needle: str) -> None:
        """Start one child and wait for ``needle`` in its log."""
        with open(self.log_path(name), "w") as f:
            self.procs[name] = subprocess.Popen(
                [sys.executable, *argv], stdout=f, stderr=subprocess.STDOUT,
                env=child_env(), cwd=REPO, preexec_fn=die_with_parent())
        deadline = time.monotonic() + 120.0
        while needle not in self.log(name):
            self.check_alive()
            check(time.monotonic() < deadline,
                  f"{name} did not report {needle!r} in 120s")
            time.sleep(0.1)

    def log_path(self, name: str) -> str:
        return os.path.join(self.logdir, f"{name}.log")

    def log(self, name: str) -> str:
        with open(self.log_path(name), errors="replace") as f:
            return f.read()

    def check_alive(self) -> None:
        for name, p in self.procs.items():
            check(p.poll() is None, f"child {name} died (rc={p.returncode})")

    def check_off_the_chip(self) -> None:
        """No child may have JAX's runtime mapped, let alone libtpu."""
        for name, p in self.procs.items():
            with open(f"/proc/{p.pid}/maps") as f:
                maps = f.read()
            for lib in ("libtpu", "jaxlib"):
                check(lib not in maps, f"child {name} has {lib} mapped")

    def log_tails(self) -> str:
        return "\n".join(f"--- {name} log tail ---\n{self.log(name)[-1500:]}"
                         for name in self.procs)

    def stop(self) -> None:
        for p in self.procs.values():
            if p.poll() is None:
                p.send_signal(signal.SIGTERM)
        for p in self.procs.values():
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait()


class EmbeddedDaemon:
    """A Daemon inside this process, its asyncio loop on a background
    thread: the arrangement tpu/data.py documents (device arrays must land
    in the runtime of the process that uses them)."""

    def __init__(self, cfg):
        self.daemon = None
        self.loop: asyncio.AbstractEventLoop | None = None
        self._up = threading.Event()
        self._error: BaseException | None = None
        self._stall = 0.0
        self._thread = threading.Thread(target=self._main, args=(cfg,),
                                        name="smoke-daemon", daemon=True)
        self._thread.start()
        check(self._up.wait(60.0), "embedded daemon did not start in 60s")
        if self._error is not None:
            raise self._error

    def _main(self, cfg) -> None:
        from dragonfly2_tpu.daemon.daemon import Daemon

        async def ticker():
            while True:
                t = time.monotonic()
                await asyncio.sleep(0.01)
                self._stall = max(self._stall, time.monotonic() - t - 0.01)

        async def serve():
            self.loop = asyncio.get_running_loop()
            self._stop = asyncio.Event()
            try:
                self.daemon = Daemon(cfg)
                await self.daemon.start()
            except BaseException as exc:  # noqa: BLE001 - re-raised by __init__
                self._error = exc
                return
            finally:
                self._up.set()
            tick = asyncio.create_task(ticker())
            await self._stop.wait()
            tick.cancel()
            await self.daemon.stop()

        asyncio.run(serve())

    def take_stall_ms(self) -> float:
        """The daemon loop's longest stall since the last call: pieces
        land on this loop (the staging memcpy rides it by design), so it
        says what the landing path costs the daemon's own sockets."""
        stall, self._stall = self._stall, 0.0
        return round(stall * 1e3, 1)

    def call(self, coro, timeout: float):
        return asyncio.run_coroutine_threadsafe(coro, self.loop).result(
            timeout)

    def stop(self) -> None:
        if self.loop is not None and self._error is None:
            self.loop.call_soon_threadsafe(self._stop.set)
        self._thread.join(30.0)


# ======================================================================
# data
# ======================================================================

def write_random(path: str, size: int, rng) -> str:
    """``size`` seeded random bytes at ``path``; returns their sha256.
    Random bit patterns are what bf16 tensors must survive: NaNs, infs and
    denormals included."""
    h = hashlib.sha256()
    with open(path, "wb") as f:
        left = size
        while left > 0:
            n = min(left, 64 * MiB)
            # (Generator.bytes is an order of magnitude slower)
            chunk = memoryview(rng.integers(
                0, 1 << 64, -(-n // 8), dtype="uint64")).cast("B")[:n]
            f.write(chunk)
            h.update(chunk)
            left -= n
    return h.hexdigest()


def checkpoint_manifest(sizes: Sizes) -> list[dict]:
    """The embedding, then per MoE layer each routed expert's three
    matrices, back to back in bf16 — Moonlight-16B-A3B's (deepseek_v3)
    tensor names and shapes. Layer 0 of the model is dense, so MoE layers
    count from 1."""
    shards, offset = [], 0

    def add(name: str, shape: tuple[int, int]) -> None:
        nonlocal offset
        size = shape[0] * shape[1] * 2
        shards.append({"name": name, "range_start": offset,
                       "range_size": size, "dtype": "bfloat16",
                       "shape": list(shape)})
        offset += size

    add("model.embed_tokens.weight", (sizes.vocab, sizes.hidden))
    for layer in range(1, sizes.moe_layers + 1):
        for e in range(sizes.experts):
            base = f"model.layers.{layer}.mlp.experts.{e}"
            add(f"{base}.gate_proj.weight", (sizes.expert_width, sizes.hidden))
            add(f"{base}.up_proj.weight", (sizes.expert_width, sizes.hidden))
            add(f"{base}.down_proj.weight", (sizes.hidden, sizes.expert_width))
    return shards


def checkpoint_files(manifest: list[dict], cap: int) -> list[dict]:
    """The manifest cut into files no larger than ``cap``, each a run of
    whole tensors with offsets of its own — one file when the whole fits.
    A tensor larger than ``cap`` can be in no file here; the caller says
    which were left out."""
    files: list[dict] = []
    for s in manifest:
        if s["range_size"] > cap:
            continue
        if not files or files[-1]["size"] + s["range_size"] > cap:
            files.append({"size": 0, "shards": []})
        files[-1]["shards"].append({**s, "range_start": files[-1]["size"]})
        files[-1]["size"] += s["range_size"]
    for i, f in enumerate(files, 1):
        f["name"] = f"moonlight-experts-{i:05d}-of-{len(files):05d}.bf16"
    return files


def pick_workdir(need: int) -> str:
    """RAM-backed when it has the room (piece stores on a VM boot disk
    would time the disk), else the temp dir; never silently short."""
    for base in ("/dev/shm", tempfile.gettempdir()):
        if os.path.isdir(base) and shutil.disk_usage(base).free > need * 1.15:
            return tempfile.mkdtemp(prefix="chip-smoke-", dir=base)
    raise SmokeFailure(f"no directory with {need / MiB:.0f} MiB free for "
                       "the swarm's piece stores")


def lift_file_size_limit() -> str:
    """Raise RLIMIT_FSIZE as far as this process may, before any child
    inherits it; returns what is left, for the record."""
    _, hard = resource.getrlimit(resource.RLIMIT_FSIZE)
    resource.setrlimit(resource.RLIMIT_FSIZE, (hard, hard))
    return ("unlimited" if hard == resource.RLIM_INFINITY
            else f"{hard / MiB:.0f} MiB")


def largest_file(base: str, want: int) -> int:
    """The largest file, up to ``want`` bytes, that ``base`` lets this
    process write — whatever sets the bound, RLIMIT_FSIZE or the
    filesystem. Probed with one byte at the last offset of a sparse file."""
    fd, path = tempfile.mkstemp(dir=base)
    os.unlink(path)

    def fits(size: int) -> bool:
        try:
            os.pwrite(fd, b"\0", size - 1)
            return True
        except OSError as exc:
            if exc.errno != errno.EFBIG:
                raise
            return False
        finally:
            os.ftruncate(fd, 0)

    try:
        if fits(want):
            return want
        lo, hi = 0, want                     # fits(lo), not fits(hi)
        while hi - lo > 1:
            mid = (lo + hi) // 2
            lo, hi = (mid, hi) if fits(mid) else (lo, mid)
        return lo
    finally:
        os.close(fd)


def origin_bytes(origin: str) -> int:
    import urllib.request
    with urllib.request.urlopen(f"{origin}/__stats__", timeout=10) as r:
        return json.loads(r.read())["bytes"]


# ======================================================================
# the legs
# ======================================================================

def leg_backend(expect_platform: str) -> tuple[list, dict]:
    """Bring JAX up the way a daemon does — in a worker thread under a
    running event loop — and see how long the loop ever stalled."""
    from dragonfly2_tpu.tpu import runtime

    async def up():
        stall = 0.0
        done = False

        async def ticker():
            nonlocal stall
            while not done:
                t = time.monotonic()
                await asyncio.sleep(0.01)
                stall = max(stall, time.monotonic() - t - 0.01)

        tick = asyncio.create_task(ticker())
        t0 = time.monotonic()
        devices = await asyncio.to_thread(runtime.bring_up)
        init_s = time.monotonic() - t0
        done = True
        await tick
        return devices, init_s, stall

    devices, init_s, stall = asyncio.run(up())
    d0 = devices[0]
    say(f"platform: {d0.platform}  device_kind: {d0.device_kind}  "
        f"devices: {len(devices)}")
    say(f"cold backend init {init_s:.2f}s off-loop; the loop's longest "
        f"stall meanwhile {stall * 1e3:.0f}ms")
    check(d0.platform == expect_platform,
          f"expected platform {expect_platform!r}, jax found "
          f"{d0.platform!r}: this run proves nothing about the chip")
    with open("/proc/self/maps") as f:
        check(("libtpu" in f.read()) == (expect_platform == "tpu"),
              "libtpu mapping of this process does not match its platform")
    return devices, {"cold_init_s": round(init_s, 3),
                     "loop_stall_ms": round(stall * 1e3, 1)}


def leg_native() -> None:
    """The landing path under test is the native one, built here from the
    committed source: a checkout carries no .so, and without it the data
    path silently differs (crc32c vs zlib crc32, fused span write)."""
    proc = subprocess.run(["make", "-C", os.path.join(REPO, "native")],
                          capture_output=True, text=True)
    check(proc.returncode == 0, f"make -C native failed:\n{proc.stderr}")
    from dragonfly2_tpu.common import digest
    from dragonfly2_tpu.storage import native
    check(native.load() is not None, "libdfnative.so built but did not load")
    check(digest.preferred_piece_algo() == "crc32c",
          "native library loaded but pieces would not hash with crc32c")
    say("native: built native/build/libdfnative.so; piece digests crc32c, "
        "span landing fused")


def leg_checkpoint(emb: EmbeddedDaemon, kids: Children, devices: list,
                   origin: str, www: str, files: list[dict],
                   leech_sock: str, workdir: str) -> dict:
    import numpy as np

    from dragonfly2_tpu.idl.messages import (DeviceSink, DownloadRequest,
                                             ShardInfo, ShardManifest)

    size = sum(f["size"] for f in files)
    n_shards = sum(len(f["shards"]) for f in files)
    # -- the CLI path: dfget through the plain leecher lands it on disk
    t0 = time.monotonic()
    ready = 0
    for f in files:
        mpath = os.path.join(workdir, "manifest.json")
        with open(mpath, "w") as mf:
            json.dump({"shards": f["shards"]}, mf)
        out = os.path.join(workdir, "dfget.out")
        proc = subprocess.run(
            [sys.executable, "-m", "dragonfly2_tpu.tools.dfget",
             f"{origin}/{f['name']}", "-O", out, "--daemon-sock", leech_sock,
             "--shard-manifest", mpath, "--timeout", "900"],
            env=child_env(), cwd=REPO, capture_output=True, text=True,
            timeout=1000)
        check(proc.returncode == 0,
              f"dfget {f['name']} failed: {proc.stderr[-2000:]}")
        ready += proc.stdout.count(" ready [")
        h = hashlib.sha256()
        with open(out, "rb") as of:
            while chunk := of.read(16 * MiB):
                h.update(chunk)
        check(h.hexdigest() == f["sha256"],
              f"dfget output differs from the origin's {f['name']}")
        os.unlink(out)
    t_cli = time.monotonic() - t0
    check(ready == n_shards, f"dfget saw {ready} shards become ready, the "
                             f"manifest has {n_shards}")
    say(f"checkpoint/cli: dfget landed {size / MiB:.0f} MiB in "
        f"{len(files)} file(s), sha256 equal, {ready} shards ready "
        f"({t_cli:.1f}s)")

    # -- the device path: same URLs, this process, P2P only, into HBM
    async def pull(f: dict):
        req = DownloadRequest(
            url=f"{origin}/{f['name']}", disable_back_source=True,
            timeout_s=900.0,
            device_sink=DeviceSink(enabled=True, dtype="bfloat16"),
            shard_manifest=ShardManifest(
                shards=[ShardInfo(**s) for s in f["shards"]]))
        task_id = None
        async for resp in emb.daemon.ptm.start_file_task(req):
            task_id = resp.task_id or task_id
        conductor = emb.daemon.ptm.conductor(task_id)
        arrays = await asyncio.to_thread(conductor.device_ingest.result, 600)
        conductor.device_ingest = None       # the sink's host buffer goes
        return conductor, arrays

    emb.take_stall_ms()
    t0 = time.monotonic()
    pulled = []                              # every file's arrays stay held
    for f in files:
        conductor, arrays = emb.call(pull(f), 1000)
        check(conductor.traffic_p2p == f["size"]
              and conductor.traffic_source == 0,
              f"device pull of {f['name']} was not all P2P: "
              f"p2p={conductor.traffic_p2p} "
              f"origin={conductor.traffic_source} of {f['size']}")
        check(list(arrays) == [s["name"] for s in f["shards"]],
              f"{f['name']}: named arrays do not match the manifest")
        pulled.append(arrays)
    t_dev = time.monotonic() - t0
    stall_ms = emb.take_stall_ms()
    kids.check_alive()

    held = {d: 0 for d in devices}
    t0 = time.monotonic()
    for f, arrays in zip(files, pulled):
        ref = np.memmap(os.path.join(www, f["name"]), dtype=np.uint8,
                        mode="r")
        for i, s in enumerate(f["shards"]):
            arr = arrays[s["name"]]
            (dev,) = arr.devices()
            check(dev == devices[i % len(devices)],
                  f"{s['name']} on {dev}, not its round-robin device")
            check(str(arr.dtype) == s["dtype"]
                  and list(arr.shape) == s["shape"],
                  f"{s['name']}: {arr.dtype}{list(arr.shape)} != manifest")
            # raw bytes, not values: random bf16 patterns include NaNs
            got = np.asarray(arr).view(np.uint8).reshape(-1)
            want = ref[s["range_start"]:s["range_start"] + s["range_size"]]
            check(np.array_equal(got, want),
                  f"{s['name']} differs from its slice of the file")
            held[dev] += s["range_size"]
    t_cmp = time.monotonic() - t0
    in_use = [(d.memory_stats() or {}).get("bytes_in_use") for d in devices]
    say(f"checkpoint/device: {size / MiB:.0f} MiB landed as {n_shards} "
        f"named bf16 arrays on {devices[0].platform}, bit-equal to the "
        f"file slices; all {size / MiB:.0f} MiB over P2P "
        f"({t_dev:.1f}s to ready arrays, {t_cmp:.1f}s to compare; the "
        f"daemon loop's longest stall {stall_ms:.0f}ms)")
    say(f"checkpoint/device: bytes held per device "
        f"{[held[d] for d in devices]}; memory_stats bytes_in_use {in_use}")
    return {"files": len(files), "cli_s": round(t_cli, 2),
            "to_ready_arrays_s": round(t_dev, 2), "loop_stall_ms": stall_ms,
            "bytes_per_device": [held[d] for d in devices],
            "bytes_in_use": in_use}


def leg_dataset(emb: EmbeddedDaemon, devices: list, urls: list[str],
                paths: list[str]) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dragonfly2_tpu.tpu.data import ShardPrefetcher
    from dragonfly2_tpu.tpu.hbm_sink import DeviceIngest
    from dragonfly2_tpu.tpu.mesh import make_mesh, named_sharding

    @jax.jit
    def weigh(x, offset):
        """Position-weighted byte sum, mod 2**32."""
        i = jnp.arange(x.shape[0], dtype=jnp.uint32) + offset
        return jnp.sum(x.astype(jnp.uint32) * (i % 65521 + 1),
                       dtype=jnp.uint32)

    def weigh_np(x: np.ndarray, offset: int) -> int:
        """The same sum; MiB at a time, so the temporaries stay in cache."""
        total = 0
        for lo in range(0, x.shape[0], MiB):
            part = x[lo:lo + MiB]
            i = np.arange(part.shape[0], dtype=np.uint32)
            i += np.uint32(offset + lo)
            i %= 65521
            i += 1
            i *= part
            total += int(i.sum(dtype=np.uint32))
        return total % (1 << 32)

    emb.take_stall_ms()
    t0 = time.monotonic()
    t_wait = 0.0
    n_arrays = 0
    pf = iter(ShardPrefetcher(emb.daemon, urls, depth=2, loop=emb.loop))
    for path in paths:                       # the synchronous consumer
        t = time.monotonic()
        shard = next(pf, None)
        t_wait += time.monotonic() - t
        check(shard is not None, f"prefetcher ran dry before {path}")
        ref = np.fromfile(path, dtype=np.uint8)
        offset = 0
        for arr in shard:
            n = arr.shape[0]
            want = ref[offset:offset + n]
            if want.shape[0] < n:            # the sink pads the tail
                want = np.concatenate(
                    [want, np.zeros(n - want.shape[0], np.uint8)])
            got = int(weigh(arr, np.uint32(offset)))
            check(got == weigh_np(want, offset),
                  f"{os.path.basename(path)}@{offset}: jitted reduction "
                  "disagrees with numpy")
            offset += n
            n_arrays += 1
        check(offset >= ref.shape[0], f"{path}: shard arrays are short")
    check(next(pf, None) is None, "prefetcher yielded more than it was given")
    t_stream = time.monotonic() - t0
    stall_ms = emb.take_stall_ms()
    say(f"dataset: {len(paths)} x {os.path.getsize(paths[0]) / MiB:.0f} MiB "
        f"shards prefetched (depth 2) as {n_arrays} device arrays, each "
        f"consumed by a jitted reduction equal to numpy's ({t_stream:.1f}s, "
        f"of which the consumer waited {t_wait:.1f}s for shards; the daemon "
        f"loop's longest stall {stall_ms:.0f}ms)")

    # one shard as ONE global array over all devices (the sharding= path)
    ref = np.fromfile(paths[0], dtype=np.uint8)
    ingest = DeviceIngest(ref.shape[0], sharding=named_sharding(
        make_mesh(devices=devices), "data"))
    for off in range(0, ref.shape[0], 4 * MiB):
        ingest.write(off, memoryview(ref[off:off + 4 * MiB]))
    whole = ingest.result(timeout=600)
    on = {s.device for s in whole.addressable_shards}
    check(len(whole.addressable_shards) == len(devices)
          and on == set(devices),
          f"global array has {len(whole.addressable_shards)} addressable "
          f"shards on {len(on)} devices, host has {len(devices)}")
    total = int(jax.jit(lambda x: jnp.sum(x.astype(jnp.uint32),
                                          dtype=jnp.uint32))(whole))
    check(total == int(np.sum(ref, dtype=np.uint64)) % (1 << 32),
          "sum over the global array disagrees with numpy")
    say(f"dataset: one shard assembled as a global array, one addressable "
        f"shard on each of {len(devices)} device(s), summed by one jitted "
        "program")
    return {"stream_s": round(t_stream, 2), "waited_s": round(t_wait, 2),
            "arrays": n_arrays, "loop_stall_ms": stall_ms}


def leg_trainer(devices: list, records_dir: str, seed: int) -> dict:
    import math

    import jax
    import numpy as np

    from dragonfly2_tpu.trainer import (features, models, params_io,
                                        pipeline, serving, training)

    platform = devices[0].platform
    key = jax.random.PRNGKey(seed)

    time.sleep(1.5)     # the scheduler flushes record rows within a second
    rows = pipeline.load_records_jsonl(records_dir)
    usable = features.records_to_arrays(rows)
    n_usable = 0 if usable is None else usable["x"].shape[0]
    if n_usable < 8:
        say(f"trainer: the scheduler recorded only {n_usable} usable piece "
            "rows; fitting the MLP on models.synthetic_mlp_batch instead")
        b = models.synthetic_mlp_batch(key, 256)
        usable = {"x": np.asarray(b["x"]), "y": np.asarray(b["y"])}
        rows = [{"features": x.tolist(), "label": float(y)}
                for x, y in zip(usable["x"], usable["y"])]
    else:
        say(f"trainer: fitting the MLP on the {n_usable} piece rows the "
            "scheduler recorded during the two legs above")
    # the launcher writes piece rows only; probe snapshots travel to a
    # trainer SERVICE, which here would be a second process on the chip
    say("trainer: the scheduler's records dir holds no topology rows; "
        "fitting the GNN on models.synthetic_gnn_batch instead")
    g = models.synthetic_gnn_batch(key, n_nodes=32, n_edges=128)
    topo_rows = [{"src": f"h{int(s)}", "dst": f"h{int(d)}",
                  "avg_rtt_us": 10.0 ** (1.0 + 3.0 * float(f[0])),
                  "count": 1}
                 for s, d, f in zip(np.asarray(g["edge_src"]),
                                    np.asarray(g["edge_dst"]),
                                    np.asarray(g["edge_feat"]))]
    out = {}
    for name, fit in (("mlp", lambda: training.train_mlp(rows, seed=seed)),
                      ("gnn", lambda: training.train_gnn(topo_rows,
                                                         seed=seed))):
        t0 = time.monotonic()
        fitted = fit()
        fit_s = time.monotonic() - t0
        check(fitted is not None, f"{name}: nothing to fit on")
        blob, m = fitted
        check(math.isfinite(m["first_epoch_loss"])
              and math.isfinite(m["final_loss"]), f"{name}: loss not finite")
        check(m["param_platforms"] == [platform],
              f"{name}: parameters lived on {m['param_platforms']}, not "
              f"{platform}")
        spanned = math.prod(m["mesh"].values()) if m["mesh"] else 1
        check(spanned == len(devices),
              f"{name}: mesh {m['mesh']} does not span the host's "
              f"{len(devices)} device(s)")
        say(f"trainer/{name}: {m['epochs']} epochs on {platform}, mesh "
            f"{m['mesh']}, loss {m['first_epoch_loss']:.4f} -> "
            f"{m['final_loss']:.4f} ({fit_s:.1f}s, compiles included)")
        out[name] = {"fit_s": round(fit_s, 2), "loss": m["final_loss"]}
        if name == "mlp":
            # serving's numpy forward against the jitted one on the chip,
            # on unit-range inputs: bf16 matmuls vs f32, the inputs and
            # the bound of tests/test_ml_loop.py's parity test
            params, _ = params_io.deserialize_params(blob)
            on_chip = jax.jit(models.mlp_forward)
            served = serving.make_mlp_infer(blob)

            def apart(x: np.ndarray) -> float:
                got = np.asarray(served(x.tolist()))
                check(np.all(np.isfinite(got)), "mlp: served scores not "
                                                "finite")
                return float(np.max(np.abs(np.asarray(on_chip(params, x))
                                           - got)))

            unit = np.asarray(jax.random.uniform(
                key, (64, features.FEATURE_DIM)))
            check(apart(unit) <= 0.15,
                  f"mlp: serving.py's forward is {apart(unit):.3f} from the "
                  "chip's on unit-range inputs")
            # the recorded rows carry raw counts (finished_pieces in the
            # hundreds), where a bf16 and an f32 forward part ways: said,
            # not checked — which forward is right is not this script's call
            rows_x = usable["x"][np.argsort(usable["x"].max(axis=1))[-64:]]
            say(f"trainer/mlp: serving.py's f32 forward and the chip's bf16 "
                f"one agree within 0.15 on unit-range inputs; on recorded "
                f"rows (features up to {rows_x.max():.0f}) they are "
                f"{apart(rows_x):.2f} apart")
        else:
            pairs = [(r["src"], r["dst"]) for r in topo_rows[:16]]
            rtts = serving.make_gnn_impute(blob)(topo_rows, pairs)
            check(rtts and all(math.isfinite(v) and v > 0
                               for v in rtts.values()),
                  "gnn: serving.py's imputer returned no finite RTTs")
    return out


# ======================================================================
# the run
# ======================================================================

def start_swarm(kids: Children, workdir: str, www: str) -> dict:
    """The swarm around the chip holder, through the launchers: origin,
    seed daemon, scheduler (told of the seed, recording rows) and a plain
    leecher. Every daemon gets loopback addresses and a hostname of its
    own: host ids are hostname-ip, and daemons that share one collapse
    into one host at the scheduler."""
    ports = {k: free_port() for k in
             ("origin", "sched", "seed_rpc", "seed_up")}
    sched_addr = f"127.0.0.1:{ports['sched']}"
    records_dir = os.path.join(workdir, "records")
    leech_sock = os.path.join(workdir, "leech.sock")

    def config(name: str, body: dict) -> str:
        path = os.path.join(workdir, f"{name}.json")
        with open(path, "w") as f:
            json.dump(body, f)
        return path

    def daemon_cfg(name: str, **more) -> dict:
        return {"workdir": os.path.join(workdir, name),
                "host_ip": "127.0.0.1", "listen_ip": "127.0.0.1",
                "hostname": name, "announce_interval_s": 2.0,
                "storage": {"gc_interval_s": 3600},
                "scheduler": {"addresses": [sched_addr]}, **more}

    kids.spawn("origin", [os.path.abspath(__file__), "--role", "origin",
                          www, str(ports["origin"])], "origin up:")
    kids.spawn("seed", ["-m", "dragonfly2_tpu.tools.daemon", "--seed",
                        "--config", config("seed", daemon_cfg(
                            "smoke-seed", rpc_port=ports["seed_rpc"],
                            upload={"port": ports["seed_up"]},
                            scheduler={}))],
               "daemon up:")
    kids.spawn("scheduler",
               ["-m", "dragonfly2_tpu.tools.scheduler", "--config",
                config("sched", {
                    "listen_ip": "127.0.0.1", "port": ports["sched"],
                    "seed_peers": [{"ip": "127.0.0.1",
                                    "rpc_port": ports["seed_rpc"],
                                    "download_port": ports["seed_up"]}]}),
                "--records-dir", records_dir], "scheduler up:")
    kids.spawn("leecher", ["-m", "dragonfly2_tpu.tools.daemon", "--config",
                           config("leech", daemon_cfg(
                               "smoke-leech", unix_sock=leech_sock))],
               "daemon up:")
    return {"origin": f"http://127.0.0.1:{ports['origin']}",
            "records_dir": records_dir, "leech_sock": leech_sock,
            "daemon_cfg": daemon_cfg}


def run(*, expect_platform: str, sizes: Sizes | None = None,
        seed: int = 0) -> dict:
    """The whole smoke; raises on any failure. ``expect_platform`` is an
    argument so the test suite can drive the same body on the CPU (at
    ``sizes`` of a few MiB); nothing reads it from the environment."""
    import numpy as np

    t_run = time.monotonic()
    fsize_limit = lift_file_size_limit()
    devices, report = leg_backend(expect_platform)
    leg_native()

    import jax.monitoring
    cache = {"hits": 0, "misses": 0}

    def on_event(name: str, **_kw) -> None:
        if name.endswith("/cache_hits"):
            cache["hits"] += 1
        elif name.endswith("/cache_misses"):
            cache["misses"] += 1

    jax.monitoring.register_event_listener(on_event)
    from dragonfly2_tpu.tpu import runtime
    cache_dir = runtime.place_compile_cache()

    def cache_entries() -> int:
        return len(os.listdir(cache_dir)) if cache_dir and \
            os.path.isdir(cache_dir) else 0

    entries0 = cache_entries()

    if sizes is None:
        # about a quarter of the host's device memory: 3 MoE layers beside
        # the embedding on one 16 GB chip, 7 on a four-chip host
        sizes = Sizes(moe_layers=7 if len(devices) >= 4 else 3)
    manifest = checkpoint_manifest(sizes)
    ckpt_bytes = manifest[-1]["range_start"] + manifest[-1]["range_size"]
    data_bytes = sizes.dataset_shards * sizes.dataset_shard_bytes
    say(f"seed {seed}; checkpoint: embedding {sizes.vocab}x{sizes.hidden} + "
        f"{sizes.moe_layers} MoE layers x {sizes.experts} experts x 3 "
        f"matrices of {sizes.expert_width}x{sizes.hidden}, bf16 = "
        f"{len(manifest)} tensors, {ckpt_bytes / MiB:.0f} MiB "
        f"(cut: {sizes.moe_layers} of Moonlight-16B-A3B's 26 MoE layers; "
        + ("every width as published" if dataclasses.replace(
            sizes, moe_layers=Sizes.moe_layers,
            dataset_shards=Sizes.dataset_shards,
            dataset_shard_bytes=Sizes.dataset_shard_bytes) == Sizes()
           else "WIDTHS CUT TOO: test sizes, not the model's")
        + f"); dataset: {sizes.dataset_shards} x "
        f"{sizes.dataset_shard_bytes / MiB:.0f} MiB shards")

    workdir = pick_workdir(4 * ckpt_bytes + 3 * data_bytes)
    kids = Children(workdir)
    emb = None
    try:
        # a machine may bound the size of a file: the driver's first run
        # of this script on its chip machine died here with EFBIG
        cap = largest_file(workdir, ckpt_bytes)
        files = checkpoint_files(manifest, cap)
        if cap < ckpt_bytes:
            say(f"this machine lets a process write no file over "
                f"{cap / MiB:.0f} MiB (RLIMIT_FSIZE: {fsize_limit}), and a "
                f"task's content is one file in every piece store: the "
                f"checkpoint goes out as {len(files)} files of whole "
                "tensors, as a published checkpoint is sharded")
            left_out = [s["name"] for s in manifest if s["range_size"] > cap]
            if left_out:
                say(f"CUT: {len(left_out)} tensors larger than that are in "
                    f"no file: {left_out[:3]}")
            check(files, "not one tensor of the checkpoint fits in a file")
            ckpt_bytes = sum(f["size"] for f in files)
            if cap < sizes.dataset_shard_bytes:
                sizes = dataclasses.replace(sizes, dataset_shard_bytes=cap)
                data_bytes = sizes.dataset_shards * cap
                say(f"CUT: dataset shards of {cap / MiB:.0f} MiB each")

        t0 = time.monotonic()
        rng = np.random.default_rng(seed)
        www = os.path.join(workdir, "www")
        os.makedirs(www)
        for f in files:
            f["sha256"] = write_random(os.path.join(www, f["name"]),
                                       f["size"], rng)
        shard_paths = [os.path.join(www, f"shard-{i:05d}.tar")
                       for i in range(sizes.dataset_shards)]
        for p in shard_paths:
            write_random(p, sizes.dataset_shard_bytes, rng)
        say(f"data made in {time.monotonic() - t0:.1f}s under {workdir}")

        swarm = start_swarm(kids, workdir, www)
        origin = swarm["origin"]
        from dragonfly2_tpu.common.config import from_dict
        from dragonfly2_tpu.daemon.config import DaemonConfig
        emb = EmbeddedDaemon(from_dict(
            DaemonConfig, swarm["daemon_cfg"]("smoke-chip")))

        report["checkpoint"] = leg_checkpoint(
            emb, kids, devices, origin, www, files, swarm["leech_sock"],
            workdir)
        topo = emb.daemon.topology
        check(topo.num_chips == (len(devices) if expect_platform == "tpu"
                                 else 0),
              f"the chip holder announces num_chips={topo.num_chips}")
        say(f"chip holder's topology after its first sink: {topo}")
        from_origin = origin_bytes(origin)
        check(ckpt_bytes <= from_origin <= 1.02 * ckpt_bytes,
              f"origin served {from_origin} bytes for a {ckpt_bytes}-byte "
              "checkpoint pulled by two hosts: not one copy")

        report["dataset"] = leg_dataset(
            emb, devices,
            [f"{origin}/{os.path.basename(p)}" for p in shard_paths],
            shard_paths)
        from_origin = origin_bytes(origin)
        check(from_origin <= 1.02 * (ckpt_bytes + data_bytes),
              f"origin served {from_origin} bytes for "
              f"{ckpt_bytes + data_bytes} bytes of content: not one copy")
        say(f"bytes by source: origin {from_origin / MiB:.0f} MiB for "
            f"{(2 * ckpt_bytes + data_bytes) / MiB:.0f} MiB delivered to "
            "the leecher and the chip holder; the rest rode P2P")

        report["trainer"] = leg_trainer(devices, swarm["records_dir"], seed)

        kids.check_alive()
        kids.check_off_the_chip()
        say(f"{len(kids.procs)} children alive, none with jaxlib or libtpu "
            "mapped: this process alone holds the chip")
    except BaseException:
        print(kids.log_tails(), file=sys.stderr)
        print(f"chip_smoke: failed with RLIMIT_FSIZE {fsize_limit}, under "
              f"{workdir} ({shutil.disk_usage(workdir).free / MiB:.0f} MiB "
              "free)", file=sys.stderr, flush=True)
        raise
    finally:
        jax.monitoring.unregister_event_listener(on_event)
        if emb is not None:
            emb.stop()
        kids.stop()
        shutil.rmtree(workdir, ignore_errors=True)

    say(f"compile cache: {cache_dir or 'off (cpu backend)'}; entries "
        f"{entries0} -> {cache_entries()}; this run {cache['hits']} hits, "
        f"{cache['misses']} misses")
    report["compile_cache"] = {"dir": cache_dir, **cache}
    report["total_s"] = round(time.monotonic() - t_run, 1)
    say(f"smoke timings, not benchmark results: {json.dumps(report)}")
    d0 = devices[0]
    return {"ok": True, "device": {"platform": d0.platform,
                                   "kind": d0.device_kind,
                                   "count": len(devices)}}


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--role", nargs=3, metavar=("origin", "DIR", "PORT"),
                   help=argparse.SUPPRESS)
    args = p.parse_args(argv)
    if args.role:
        asyncio.run(role_origin(args.role[1], int(args.role[2])))
        return 0
    try:
        result = run(expect_platform="tpu", seed=args.seed)
    except SmokeFailure as exc:
        print(f"chip_smoke: FAILED: {exc}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
