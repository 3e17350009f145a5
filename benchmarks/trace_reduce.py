"""From a profiler trace (``.xplane.pb``) to busy, idle and the gaps.

Two steps, kept apart so that each can be checked alone:

``extract(path)`` reads the trace with nothing but JAX
(``jax.profiler.ProfileData``) into plain intervals on one clock, in
seconds: the operations that ran on each device, the module executions
(one jitted program run from its first operation to its last), the host
spans the benchmark wrote with ``TraceAnnotation`` (named ``bench:...``),
and the window (the host span ``bench:window``).

- On a TPU, a device is a plane ``/device:TPU:<n>``; its line ``XLA Ops``
  holds the operations and ``XLA Modules`` the program executions.
- On the CPU backend (the test suite's recorded trace) there is no device
  plane: operations are the events of ``/host:CPU`` that carry an
  ``hlo_op`` stat, the device is their ``device_ordinal``, and a module
  execution is the span of the operations that share ``hlo_module`` and
  ``run_id``.

``reduce(trace)`` is interval arithmetic: busy seconds are the union of a
device's operation intervals inside the window, averaged over the devices
in use; idle share is 1 - busy / window; each gap between operations is
given to the host span that covers most of it.
"""

from __future__ import annotations

import dataclasses
import glob
import os

WINDOW_SPAN = "bench:window"
HOST_PREFIX = "bench:"
Interval = tuple[str, float, float]          # name, start s, end s


@dataclasses.dataclass
class Trace:
    ops: dict[str, list[Interval]]           # device -> operations
    modules: dict[str, list[Interval]]       # device -> program executions
    host: list[Interval]                     # the benchmark's host spans
    window: tuple[float, float] | None


def find_xplane(logdir: str) -> str | None:
    found = sorted(glob.glob(os.path.join(
        logdir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def extract(path: str) -> Trace:
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    ops: dict[str, list[Interval]] = {}
    modules: dict[str, list[Interval]] = {}
    host: list[Interval] = []
    cpu_runs: dict[tuple, list[float]] = {}
    planes = list(data.planes)
    on_tpu = any(p.name.startswith("/device:TPU") for p in planes)
    for plane in planes:
        if on_tpu and plane.name.startswith("/device:TPU"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    into = ops.setdefault(plane.name, [])
                elif line.name == "XLA Modules":
                    into = modules.setdefault(plane.name, [])
                else:
                    continue
                for e in line.events:
                    s = e.start_ns * 1e-9
                    into.append((e.name, s, s + e.duration_ns * 1e-9))
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    s = e.start_ns * 1e-9
                    end = s + e.duration_ns * 1e-9
                    if e.name.startswith(HOST_PREFIX):
                        host.append((e.name, s, end))
                    elif not on_tpu and e.duration_ns > 0:
                        stats = dict(e.stats)
                        if "hlo_op" not in stats:
                            continue
                        dev = f"/device:CPU:{stats.get('device_ordinal', 0)}"
                        ops.setdefault(dev, []).append((e.name, s, end))
                        run = cpu_runs.setdefault(
                            (dev, stats.get("hlo_module", ""),
                             stats.get("run_id", 0)), [s, end])
                        run[0], run[1] = min(run[0], s), max(run[1], end)
    for (dev, module, _run), (s, end) in cpu_runs.items():
        modules.setdefault(dev, []).append((module, s, end))
    window = next(((s, e) for n, s, e in host if n == WINDOW_SPAN), None)
    return Trace(ops, modules, host, window)


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Merged, sorted, disjoint."""
    out: list[tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def reduce(trace: Trace, chips: int = 1, top: int = 10) -> dict | None:
    """``busy_s`` and ``window_s`` as the contract's ``device`` key wants
    them, the idle share, and the breakdown. None where the trace holds no
    window."""
    if trace.window is None:
        return None
    lo, hi = trace.window
    window_s = hi - lo
    busy_by_dev, gaps = [], []
    for dev in sorted(trace.ops):
        merged = union(clip([(s, e) for _n, s, e in trace.ops[dev]], lo, hi))
        busy_by_dev.append(sum(e - s for s, e in merged))
        edges = [lo] + [t for iv in merged for t in iv] + [hi]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    if not busy_by_dev:
        gaps = [(lo, hi)]
    # a chip of the cell that ran nothing has no plane: it was idle
    busy_s = sum(busy_by_dev) / max(chips, len(busy_by_dev), 1)
    by_op: dict[str, float] = {}
    for dev_ops in trace.ops.values():
        for name, s, e in dev_ops:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                by_op[name] = by_op.get(name, 0.0) + d
    by_host: dict[str, float] = {}
    spans = [(n, s, e) for n, s, e in trace.host if n != WINDOW_SPAN]
    for gs, ge in gaps:
        best, cover = "host: no benchmark span", 0.0
        for n, s, e in spans:
            c = min(e, ge) - max(s, gs)
            if c > cover:
                best, cover = n, c
        # idle seconds of one device: averaged, as busy_s is
        by_host[best] = by_host.get(best, 0.0) \
            + (ge - gs) / max(len(busy_by_dev), 1)

    def ranked(d: dict[str, float]) -> list[list]:
        return [[n, v] for n, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]

    return {"busy_s": busy_s, "window_s": window_s,
            "idle_share": 1.0 - busy_s / window_s if window_s > 0 else None,
            "devices_with_ops": len(busy_by_dev),
            "longest_gap_s": max((e - s for s, e in gaps), default=0.0),
            "breakdown": {"device_ops": ranked(by_op),
                          "idle_gaps": ranked(by_host)}}


def module_durations(trace: Trace, needle: str) -> list[float]:
    """Device seconds of every execution, inside the window, of a program
    whose name holds ``needle``."""
    if trace.window is None:
        return []
    lo, hi = trace.window
    return [e - s for runs in trace.modules.values() for n, s, e in runs
            if needle in n and s >= lo and e <= hi]
