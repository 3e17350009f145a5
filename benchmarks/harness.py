"""One run of one cell: set-up, the measured window, the comparison.

Everything that belongs to one configuration, one traffic mix, one driver or
one metric sits in a file of its own, found by the name ``BENCHMARK.json``
gives; this file knows none of them:

  configs/<config>.json        the deployment's sizes; ``content`` names
  content/<content>.py         the generator of its files and manifests
  traffic/<traffic>.json       the mix's parameters; ``driver`` names
  drivers/<driver>.py          the loop that turns a mix into calls on the
                               entry point, and the comparison of what came
  end_to_end/<metric>.py       one reader each: ``read(obs)`` -> number
  layer_metrics/<metric>.py    or None where there is nothing to read

``run_cell`` takes the platform as an argument so that the tests drive the
same body on the CPU at sizes of a few MiB; nothing reads it from the
environment. ``benchmarks/run.py`` fixes it to ``tpu``.
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import math
import os
import shutil
import statistics
import sys
import threading
import time
import types
from typing import Any

from . import datagen, swarm, trace_reduce
from .swarm import MiB, BenchFailure, check, say

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
PROBE_FILE_BYTES = 1 << 34                   # 16 GiB, as a sparse file


# ======================================================================
# BENCHMARK.json -> a cell
# ======================================================================

@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    end_to_end: list[dict]
    per_layer: list[dict]


def load_module(folder: str, name: str, root: str = ROOT):
    """A module found by a name that need not be an identifier
    (``device_idle_share.job``)."""
    path = os.path.join(root, "benchmarks", folder, f"{name}.py")
    check(os.path.isfile(path), f"no {folder}/{name}.py for {name!r}")
    spec = importlib.util.spec_from_file_location(
        f"benchmarks.{folder}.{name.replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_cell(workload: str, root: str = ROOT) -> Cell:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    check(workload in cells, f"no workload {workload!r} in BENCHMARK.json; "
                             f"it has {sorted(cells)}")
    w = cells[workload]
    cfg = next(c for c in bench["configs"] if c["name"] == w["config"])
    with open(os.path.join(root, cfg["file"])) as f:
        config = json.load(f)
    traffic_path = os.path.join(root, "benchmarks", "traffic",
                                f"{w['traffic']}.json")
    with open(traffic_path) as f:
        traffic = json.load(f)

    def mine(metrics: list[dict]) -> list[dict]:
        return [m for m in metrics
                if workload in m.get("workloads", [workload])]

    return Cell(workload, w["chips"], w["config"], config, w["traffic"],
                traffic, mine(bench["end_to_end"]), mine(bench["per_layer"]))


def chip_peaks(device_kind: str) -> dict:
    with open(os.path.join(HERE, "peaks.json")) as f:
        peaks = json.load(f)
    check(device_kind in peaks, f"device kind {device_kind!r} is not in "
                                "benchmarks/peaks.json: an unknown chip is an "
                                "error, not a default")
    return peaks[device_kind]


# ======================================================================
# what a run hands to its driver, and what the readers read
# ======================================================================

@dataclasses.dataclass
class Ctx:
    cell: Cell
    seed: int
    devices: list
    files: list[dict]
    origin: datagen.OriginBytes
    source: Any                              # sources.FabricSource / Reference
    state: dict = dataclasses.field(default_factory=dict)

    def rng(self, *salt: int):
        import numpy as np
        return np.random.default_rng([self.seed, *salt])

    @staticmethod
    def span(name: str):
        """A host span in the profiler's own trace (free while no trace is
        taken): the idle gaps are given to these."""
        import jax
        return jax.profiler.TraceAnnotation(trace_reduce.HOST_PREFIX + name)

    def bytes_of(self, f: dict):
        """The file's bytes as the origin has them (a uint8 view of the
        origin's own memory): what every comparison is made against."""
        return self.origin.bytes_of(f)


@dataclasses.dataclass
class WindowResult:
    t0: float                                # first request issued
    t1: float                                # last counted request ready
    requests: list                           # sources.Request, every attempt
    bytes_ready: int
    job_steps: list[tuple[float, float]] = dataclasses.field(
        default_factory=list)                # (start, end), host clock


@dataclasses.dataclass
class Obs:
    """What the metric readers read. Times are ``time.monotonic()``."""
    cell: Cell
    window: WindowResult
    setup_s: float
    stall_ms: float | None                   # the daemon loop's longest stall
    origin_bytes: int | None                 # served by the origin in the window
    span_lands: dict[str, float]             # df_span_land_total{path} deltas
    trace: trace_reduce.Trace | None = None
    reduced: dict | None = None              # trace_reduce.reduce(trace)
    peaks: dict | None = None               # the chip's row of peaks.json,
    # for the roofline readers later PRs add (none exists yet)
    _summaries: list | None = None

    @property
    def window_s(self) -> float:
        return self.window.t1 - self.window.t0

    def flights(self) -> list[tuple[Any, dict]]:
        """(request, flight summary) of every counted request that has a
        journal."""
        if self._summaries is None:
            self._summaries = [(r, r.flight.summarize())
                               for r in self.window.requests
                               if r.ok and r.flight is not None]
        return self._summaries

    def piece_rows(self) -> list[dict]:
        return [row for _r, s in self.flights() for row in s["piece_rows"]]

    def transfer_spans(self) -> list[tuple[float, float]]:
        """Every host->device transfer of the window's requests, on the
        monotonic clock: ``DeviceIngest.transfer_spans`` as the conductor
        journals them (``hbm_shard`` events)."""
        out = []
        for r in self.window.requests:
            if r.ok and r.flight is not None:
                for t, stage, _p, _par, _n, dur in list(r.flight.events):
                    if stage == "hbm_shard":
                        s = r.flight._m0 + t / 1e3
                        out.append((s, s + dur / 1e3))
        return out

    def steps_in_window(self) -> list[tuple[float, float]]:
        w = self.window
        return [(s, e) for s, e in w.job_steps if s >= w.t0 and e <= w.t1]


def read_counters() -> dict[str, float]:
    from dragonfly2_tpu.common.metrics import REGISTRY
    counter = REGISTRY.counter(
        "df_span_land_total", "downloaded spans landed in storage, by landing "
        "path", ("path",))
    return {k[0]: v for k, _suffix, v in counter._samples()}


def percentile(values: list[float], q: float) -> float:
    """Nearest rank on the sorted sample."""
    s = sorted(values)
    return s[max(0, math.ceil(q * len(s)) - 1)]


def median(values: list[float]) -> float | None:
    return statistics.median(values) if values else None


def union_seconds(spans: list[tuple[float, float]], lo: float,
                  hi: float) -> float:
    return sum(e - s for s, e in trace_reduce.union(
        trace_reduce.clip(spans, lo, hi)))


# ======================================================================
# the run
# ======================================================================

def _tree_bytes(path: str) -> int:
    total = 0
    for folder, _dirs, files in os.walk(path):
        for f in files:
            try:
                total += os.lstat(os.path.join(folder, f)).st_blocks * 512
            except OSError:
                pass
    return total


def _bring_up_jax(out: dict) -> None:
    """JAX comes up on a worker thread while the data is written and the
    swarm starts; the daemon's own bring-up is then a lookup."""
    try:
        from dragonfly2_tpu.tpu import runtime
        t0 = time.monotonic()
        out["devices"] = runtime.bring_up()
        out["init_s"] = time.monotonic() - t0
    except BaseException as exc:  # noqa: BLE001 - re-raised by the caller
        out["error"] = exc


def _devices(jax_thread: threading.Thread, up: dict, cell: Cell,
             expect_platform: str) -> list:
    """Wait for the backend, and hold it to what the run was told."""
    jax_thread.join()
    if "error" in up:
        raise BenchFailure(f"no device runtime: {up['error']!r}")
    devices = up["devices"]
    d0 = devices[0]
    say(f"platform: {d0.platform}  device_kind: {d0.device_kind}  "
        f"devices: {len(devices)}  (backend up in {up['init_s']:.1f}s, "
        "beside the data and the swarm)")
    check(d0.platform == expect_platform,
          f"expected platform {expect_platform!r}, jax found "
          f"{d0.platform!r}: this run says nothing about the chip")
    check(len(devices) >= cell.chips,
          f"cell {cell.name} asks for {cell.chips} chip(s), jax found "
          f"{len(devices)}")
    return devices


def run_cell(workload: str, *, seed: int, seconds: float, trace: bool,
             expect_platform: str, t_start: float | None = None,
             config_overrides: dict | None = None,
             traffic_overrides: dict | None = None,
             control: bool = False, fault: str | None = None,
             root: str = ROOT) -> dict:
    """The whole run; returns the result line's object, raises
    ``BenchFailure`` where there is none to give. ``control`` puts the
    broken reference in the program's place (``sources.ReferenceSource``);
    ``fault`` names a fault of ``benchmarks/faults.py`` to plant in the
    program before the window. Both exist to show ``correct`` come out
    false and are never set by ``run.py``."""
    t_start = time.monotonic() if t_start is None else t_start
    # before any thread starts: several threads importing numpy at once can
    # trip the import system's deadlock detection
    import numpy  # noqa: F401
    cell = load_cell(workload, root)
    cell.config.update(config_overrides or {})
    cell.traffic.update(traffic_overrides or {})
    driver = load_module("drivers", cell.traffic["driver"], root)
    content = load_module("content", cell.config["content"], root)
    fsize_limit = swarm.lift_file_size_limit()

    up: dict = {}
    jax_thread = threading.Thread(target=_bring_up_jax, args=(up,),
                                  name="bench-jax-up", daemon=True)
    jax_thread.start()
    swarm.build_native()

    want_files, _ = content.files(cell.config, 1 << 62)
    want = max(f["size"] for f in want_files)
    total = sum(f["size"] for f in want_files)
    # the swarm's store of the content + the holder's transient (the
    # origin's own bytes are in memory)
    try:
        workdir = swarm.pick_workdir(
            int(total * cell.traffic.get("workdir_copies", 2.3)))
    except BenchFailure:
        # a host with no chip says that first, whatever else it lacks
        _devices(jax_thread, up, cell, expect_platform)
        raise
    kids = swarm.Children(workdir)
    fabric = None
    try:
        # probed well past what this cell needs, so that every run says
        # what the machine allows
        probe = max(want, PROBE_FILE_BYTES)
        cap = swarm.largest_file(workdir, probe)
        files, notes = content.files(cell.config, cap)
        check(files, "not one file of the content fits under the file bound")
        say(swarm.machine_line(workdir, cap, probe, fsize_limit, len(files)))
        for note in notes:
            say(note)
        say(f"cell {cell.name}: config {cell.config_name}, traffic "
            f"{cell.traffic_name}, seed {seed}; content {len(files)} files, "
            f"{sum(f['size'] for f in files) / MiB:.0f} MiB")
        t0 = time.monotonic()
        extras = getattr(driver, "origin_extras", lambda cell, files: [])(
            cell, files)
        content_bytes = datagen.OriginBytes(files + extras, seed)
        say(f"data made in {time.monotonic() - t0:.1f}s, in memory: the "
            "origin's files are on no disk")

        from . import sources
        origin = ""
        if not control:
            net = swarm.start_swarm(
                kids, workdir, content_bytes.spec(),
                cell.traffic.get("holder_of_content", "seed"))
            origin = net["origin"]
            fabric = sources.FabricSource(net)

        devices = _devices(jax_thread, up, cell, expect_platform)
        d0 = devices[0]
        peaks = chip_peaks(d0.device_kind) if expect_platform == "tpu" \
            else None

        source = (sources.ReferenceSource(
            content_bytes.bytes_of, devices, broken=True, seed=seed)
            if control else fabric)
        ctx = Ctx(cell, seed, devices, files, content_bytes, source)
        driver.prepare(ctx)
        if fault:
            from . import faults
            faults.plant(fault, seed)

        # -- the window ------------------------------------------------
        import jax
        tracedir = os.path.join(workdir, "trace")
        lands0 = read_counters()
        origin0 = swarm.origin_bytes(origin) if origin else None
        if fabric is not None:
            fabric.take_stall_ms()
        cpu0 = swarm.cpu_seconds(kids)
        if trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(tracedir, profiler_options=opts)
        setup_s = time.monotonic() - t_start
        try:
            with ctx.span("window"):
                result = driver.window(ctx, seconds)
        finally:
            if trace:
                jax.profiler.stop_trace()
        stall_ms = fabric.take_stall_ms() if fabric is not None else None
        cpu1 = swarm.cpu_seconds(kids)
        say("host CPU seconds in the window, by process and by this "
            "process's busiest threads: " + ", ".join(
                f"{k} {cpu1[k] - cpu0.get(k, 0.0):.2f}" for k in sorted(
                    cpu1, key=lambda k: cpu0.get(k, 0.0) - cpu1[k])[:12]))
        say(f"window: {result.t1 - result.t0:.2f}s of {seconds:g}s asked, "
            f"{result.bytes_ready / MiB:.0f} MiB ready; seconds a request: "
            + " ".join(f"{r.t_ready - r.t_issue:.2f}" if r.ok else "failed"
                       for r in result.requests))
        origin1 = swarm.origin_bytes(origin) if origin else None
        lands1 = read_counters()
        kids.check_alive()
        say(f"work directory after the window: "
            f"{_tree_bytes(workdir) / MiB:.0f} MiB in files")
        peak = max(((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                    for d in devices), default=0)

        obs = Obs(cell, result, setup_s, stall_ms,
                  None if origin0 is None else origin1 - origin0,
                  {k: v - lands0.get(k, 0.0) for k, v in lands1.items()},
                  peaks=peaks)
        if trace:
            xplane = trace_reduce.find_xplane(tracedir)
            check(xplane is not None, "the profiler wrote no .xplane.pb")
            obs.trace = trace_reduce.extract(xplane)
            obs.reduced = trace_reduce.reduce(obs.trace, cell.chips)

        # -- the comparison, once the window has closed and the peak is read
        t0 = time.monotonic()
        compared = driver.compare(ctx, result, obs)
        failed = sum(1 for r in result.requests if not r.ok)
        compared["requests_failed"] = (failed, 0)
        if failed:
            say(kids.log_tails())
        say(f"comparison took {time.monotonic() - t0:.1f}s")
        ctx.state.clear()
        if not control:
            kids.check_off_the_chip()
            with open("/proc/self/maps") as f:
                check(("libtpu" in f.read()) == (expect_platform == "tpu"),
                      "libtpu mapping of this process does not match its "
                      "platform")
    except BaseException:
        print(kids.log_tails(), file=sys.stderr)
        raise
    finally:
        if fabric is not None:
            fabric.stop()
        kids.stop()
        shutil.rmtree(workdir, ignore_errors=True)

    wanted, folder = ((cell.per_layer, "layer_metrics") if trace
                      else (cell.end_to_end, "end_to_end"))
    metrics = {}
    for m in wanted:
        value = load_module(folder, m["name"], root).read(obs)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    out = {"correct": all(v <= lim for v, lim in compared.values()),
           "attempted": len(result.requests), "failed": failed,
           "metrics": metrics, "device": device}
    if trace and obs.reduced is not None:
        device["busy_s"] = obs.reduced["busy_s"]
        device["window_s"] = obs.reduced["window_s"]
        out["breakdown"] = obs.reduced["breakdown"]
    out["compared"] = {k: {"value": v, "limit": lim}
                       for k, (v, lim) in compared.items()}
    for k, (v, lim) in compared.items():
        print(f"compared {k}: {v} (limit {lim})"
              + ("" if v <= lim else "  <-- NOT CORRECT"),
              file=sys.stderr, flush=True)
    return out


@contextlib.contextmanager
def job_thread(step, state):
    """The co-located job: one thread running ``state = step(state)`` back
    to back, each step waited for, until the block ends. Yields a namespace
    with ``steps``, the (start, end) of every step, and ``state``, the
    job's newest state."""
    import jax

    job = types.SimpleNamespace(steps=[], state=state)
    stop = threading.Event()

    def loop():
        while not stop.is_set():
            t = time.monotonic()
            job.state = jax.block_until_ready(step(job.state))
            job.steps.append((t, time.monotonic()))

    th = threading.Thread(target=loop, name="bench-job", daemon=True)
    th.start()
    try:
        yield job
    finally:
        stop.set()
        th.join(60.0)
