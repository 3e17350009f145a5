"""The swarm around the chip holder, and the machine it runs on.

Copies of ``chip_smoke.py``'s helpers (PR 21: ran on the chip and on the
driver's machine), kept here so that a later PR that changes the smoke cannot
change the yardstick: ``Children``, ``EmbeddedDaemon``, ``start_swarm``,
``lift_file_size_limit``, ``largest_file``, ``origin_bytes``. The originals
are listed in PERF.md's Open questions for a later PR to fold together. Not
copies: ``role_origin`` serves the content from memory, and ``pick_workdir``
keeps to the run's own temporary directory.

This process is the ONE that holds the chip. Origin, scheduler and seed are
child processes through the launchers; none may touch JAX.
"""

from __future__ import annotations

import asyncio
import errno
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

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MiB = 1 << 20


class BenchFailure(Exception):
    """The run cannot produce a result; the command exits non-zero and
    prints no result line."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise BenchFailure(what)


def say(msg: str) -> None:
    """Progress goes to standard error: standard output's last line is the
    result and nothing else."""
    print(f"bench: {msg}", file=sys.stderr, flush=True)


# ======================================================================
# the origin (a child process: python benchmarks/swarm.py --role origin SPEC PORT)
# ======================================================================

async def role_origin(spec_path: str, port: int) -> None:
    """Serve the files of ``datagen.OriginBytes`` from the memory files
    this process inherited (the spec names each file's descriptor and
    size), with Range support; a file answers by its base name under any
    directory. Bytes asked for are tallied at /__stats__ (a HEAD asks for
    none)."""
    from aiohttp import web

    with open(spec_path) as f:
        files = json.load(f)
    served = {"bytes": 0}

    async def handle(request: web.Request):
        if request.path == "/__stats__":
            return web.json_response(served)
        fd, size = files.get(os.path.basename(request.path), (None, 0))
        if fd is None:
            return web.Response(status=404)
        if request.method == "GET":
            first, _, last = request.headers.get(
                "Range", "bytes=0-").removeprefix("bytes=").partition("-")
            if first.isdigit() and (last == "" or last.isdigit()):
                end = min(int(last), size - 1) if last else size - 1
                served["bytes"] += max(0, end - int(first) + 1)
        return web.FileResponse(f"/proc/self/fd/{fd}")

    app = web.Application()
    app.router.add_route("*", "/{tail:.*}", handle)
    runner = web.AppRunner(app, access_log=None)
    await runner.setup()
    await web.TCPSite(runner, "127.0.0.1", port).start()
    print(f"origin up: 127.0.0.1:{port}", flush=True)
    await asyncio.Event().wait()


def origin_bytes(origin: str) -> int:
    import urllib.request
    with urllib.request.urlopen(f"{origin}/__stats__", timeout=10) as r:
        return json.loads(r.read())["bytes"]


# ======================================================================
# children
# ======================================================================

def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def die_with_parent():
    """A preexec_fn: the child gets SIGTERM when this process dies, however
    it dies, so a run killed at a time limit leaves no swarm behind. The
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
    """The processes a run starts; every one is stopped on the way out."""

    def __init__(self, logdir: str):
        self.logdir = logdir
        self.procs: dict[str, subprocess.Popen] = {}

    def spawn(self, name: str, argv: list[str], needle: str,
              pass_fds: tuple[int, ...] = ()) -> None:
        """Start one child and wait for ``needle`` in its log."""
        with open(self.log_path(name), "w") as f:
            self.procs[name] = subprocess.Popen(
                [sys.executable, *argv], stdout=f, stderr=subprocess.STDOUT,
                env=child_env(), cwd=REPO, preexec_fn=die_with_parent(),
                pass_fds=pass_fds)
        deadline = time.monotonic() + 120.0
        while needle not in self.log(name):
            self.check_alive()
            check(time.monotonic() < deadline,
                  f"{name} did not report {needle!r} in 120s")
            time.sleep(0.05)

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
                                        name="bench-daemon", daemon=True)
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
        return stall * 1e3

    def call(self, coro, timeout: float):
        return asyncio.run_coroutine_threadsafe(coro, self.loop).result(
            timeout)

    def stop(self) -> None:
        if self.loop is not None and self._error is None:
            self.loop.call_soon_threadsafe(self._stop.set)
        self._thread.join(30.0)


# ======================================================================
# the machine
# ======================================================================

def lift_file_size_limit() -> str:
    """Raise RLIMIT_FSIZE as far as this process may, before any child
    inherits it; returns what is left, for the record."""
    _, hard = resource.getrlimit(resource.RLIMIT_FSIZE)
    resource.setrlimit(resource.RLIMIT_FSIZE, (hard, hard))
    return ("unlimited" if hard == resource.RLIM_INFINITY
            else f"{hard / MiB:.0f} MiB")


def largest_file(base: str, want: int) -> int:
    """The largest file, up to ``want`` bytes, that ``base`` lets this
    process write, whatever sets the bound: RLIMIT_FSIZE or the
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


def _ram_backed(path: str) -> bool:
    """Whether ``path`` lies on a tmpfs, by the longest matching mount."""
    path = os.path.realpath(path)
    best, fstype = "", ""
    try:
        with open("/proc/mounts") as f:
            for line in f:
                _dev, mnt, typ = line.split()[:3]
                if (path == mnt or path.startswith(mnt.rstrip("/") + "/")) \
                        and len(mnt) > len(best):
                    best, fstype = mnt, typ
    except OSError:
        return False
    return fstype in ("tmpfs", "ramfs")


def pick_workdir(need: int) -> str:
    """Where the origin's files and every piece store live for this run: a
    fresh directory under the run's own temporary directory
    (``tempfile.gettempdir()``, that is ``TMPDIR``), and nowhere else: the
    driver gives each side a ``TMPDIR`` of its own and clears it, a run
    killed at its time limit included. Whether that is RAM or a disk is the
    machine's to say; the ``machine:`` line prints which. Removed on the
    way out; never silently short."""
    base = tempfile.gettempdir()
    check(shutil.disk_usage(base).free > need * 1.05,
          f"{base} has {shutil.disk_usage(base).free / MiB:.0f} MiB free; "
          f"the swarm's piece stores need {need * 1.05 / MiB:.0f} MiB")
    return tempfile.mkdtemp(prefix="df-bench-", dir=base)


def cpu_seconds(kids: Children) -> dict[str, float]:
    """User + system CPU seconds so far of every child and of each thread of
    this process (by its name): where the host's time goes in a window, and
    what tells a run that waited from one that worked harder."""
    tick = os.sysconf("SC_CLK_TCK")

    def of(path: str) -> tuple[str, float]:
        with open(path) as f:
            head, _, rest = f.read().rpartition(")")
        fields = rest.split()
        return (head.partition("(")[2],
                (int(fields[11]) + int(fields[12])) / tick)

    out = {}
    for name, p in kids.procs.items():
        try:
            out[name] = of(f"/proc/{p.pid}/stat")[1]
        except (OSError, ValueError, IndexError):
            pass
    try:
        for tid in os.listdir("/proc/self/task"):
            comm, secs = of(f"/proc/self/task/{tid}/stat")
            out[f"holder:{comm}:{tid}"] = secs
    except (OSError, ValueError, IndexError):
        pass
    return out


def machine_line(workdir: str, cap: int, want: int, fsize_limit: str,
                 n_files: int) -> str:
    """What the machine allows, said before the window in every run: the
    driver's file bound was unknown for three PRs."""
    try:
        with open("/proc/meminfo") as f:
            ram = int(f.readline().split()[1]) / (1 << 20)
    except (OSError, ValueError, IndexError):
        ram = float("nan")
    bound = (f"none up to {want / MiB:.0f} MiB" if cap >= want
             else f"{cap / MiB:.0f} MiB")
    return (f"machine: file bound {bound} (RLIMIT_FSIZE {fsize_limit}); "
            f"{n_files} content files; workdir {workdir} "
            f"({'RAM-backed' if _ram_backed(workdir) else 'on disk'}, "
            f"{shutil.disk_usage(workdir).free / MiB:.0f} MiB free); "
            f"host cores {os.cpu_count()}, RAM {ram:.1f} GiB")


# ======================================================================
# the swarm
# ======================================================================

def start_swarm(kids: Children, workdir: str, origin_spec: dict,
                holder_of_content: str = "seed") -> dict:
    """The swarm around the chip holder, through the launchers: the origin,
    the scheduler, and the daemon that holds (or fetches) the content for
    the swarm: ``seed``, a seed daemon the scheduler is told of and
    triggers; or ``peer``, a plain daemon that pulls the content itself
    (``FabricSource.preseed``) with no seed in the swarm. Every daemon gets
    loopback addresses and a hostname of its own: host ids are hostname-ip,
    and daemons that share one collapse into one host at the scheduler."""
    check(holder_of_content in ("seed", "peer"),
          f"no swarm with a {holder_of_content!r} in it")
    ports = {k: free_port() for k in
             ("origin", "sched", "seed_rpc", "seed_up")}
    sched_addr = f"127.0.0.1:{ports['sched']}"
    peer_sock = os.path.join(workdir, "peer.sock")

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
                          config("origin", origin_spec),
                          str(ports["origin"])], "origin up:",
               pass_fds=tuple(fd for fd, _size in origin_spec.values()))
    seeds = []
    if holder_of_content == "seed":
        kids.spawn("seed", ["-m", "dragonfly2_tpu.tools.daemon", "--seed",
                            "--config", config("seed", daemon_cfg(
                                "bench-seed", rpc_port=ports["seed_rpc"],
                                upload={"port": ports["seed_up"]},
                                scheduler={}))],
                   "daemon up:")
        seeds = [{"ip": "127.0.0.1", "rpc_port": ports["seed_rpc"],
                  "download_port": ports["seed_up"]}]
    kids.spawn("scheduler",
               ["-m", "dragonfly2_tpu.tools.scheduler", "--config",
                config("sched", {"listen_ip": "127.0.0.1",
                                 "port": ports["sched"],
                                 "seed_peers": seeds})],
               "scheduler up:")
    if holder_of_content == "peer":
        kids.spawn("peer", ["-m", "dragonfly2_tpu.tools.daemon", "--config",
                            config("peer", daemon_cfg(
                                "bench-peer", unix_sock=peer_sock))],
                   "daemon up:")
    return {"origin": f"http://127.0.0.1:{ports['origin']}",
            "scheduler": sched_addr, "peer_sock": peer_sock,
            "holder_of_content": holder_of_content,
            "daemon_cfg": daemon_cfg}


def build_native() -> None:
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


if __name__ == "__main__":
    if len(sys.argv) == 5 and sys.argv[1:3] == ["--role", "origin"]:
        asyncio.run(role_origin(sys.argv[3], int(sys.argv[4])))
    else:
        sys.exit("usage: swarm.py --role origin SPEC PORT")
