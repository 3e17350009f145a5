"""What the tests under ``tests/benchmark`` share: the look-up of the sizes of
a few MiB at which a cell's body runs on the CPU (a data file beside each
configuration), and the fresh process it runs in (the body builds and loads
the native library, brings JAX up and runs a daemon loop on a thread of its
own, as on the chip)."""

from __future__ import annotations

import json
import os
import subprocess
import sys
import warnings

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MiB = 1 << 20


def bench(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def cells() -> list[str]:
    return [w["name"] for w in bench()["workloads"]]


def tiny(workload: str, root: str = ROOT) -> dict:
    """The test sizes of the cell's configuration and what its test runs
    have to say: ``<the configuration's file>.tiny.json``, beside the
    configuration, found by name. A later PR's configuration brings its
    own; nothing here or in the tests knows one."""
    b = bench(root)
    cell = next(w for w in b["workloads"] if w["name"] == workload)
    cfg = next(c for c in b["configs"] if c["name"] == cell["config"])
    stem, ext = os.path.splitext(cfg["file"])
    with open(os.path.join(root, f"{stem}.tiny{ext}")) as f:
        return json.load(f)


def cases(key: str) -> list[tuple[str, str, str]]:
    """(cell, fault, the compared number that has to catch it) for every
    fault each cell's tiny file lists under ``key``."""
    return [(cell, fault, caught_by) for cell in cells()
            for fault, caught_by in tiny(cell).get(key, {}).items()]


_BODY = """
import json, resource, sys
sys.path.insert(0, {root!r})
if {fsize}:
    resource.setrlimit(resource.RLIMIT_FSIZE, ({fsize}, {fsize}))
from benchmarks import harness
print(json.dumps(harness.run_cell(
    {workload!r}, seed={seed}, seconds={seconds}, trace={trace},
    expect_platform="cpu", config_overrides={config!r},
    traffic_overrides={traffic!r}, control={control}, fault={fault!r},
    root={root!r})))
"""


def run_body(workload: str, *, trace: bool = False, fsize: int = 0,
             control: bool = False, fault: str | None = None,
             seed: int = 3000000019, seconds: float = 2.5,
             root: str = ROOT, timeout: float = 240.0) -> tuple[dict, str]:
    """One run of a cell's body on the CPU in a fresh process; returns the
    result line's object and everything the run said."""
    sizes = tiny(workload, root)
    body = _BODY.format(root=root, fsize=fsize, workload=workload, seed=seed,
                        seconds=seconds, trace=trace, config=sizes["config"],
                        traffic=sizes["traffic"], control=control, fault=fault)

    def once() -> subprocess.CompletedProcess:
        return subprocess.run(
            [sys.executable, "-c", body], cwd=root,
            env={**os.environ, "PYTHONPATH": root},
            capture_output=True, text=True, timeout=timeout)

    try:
        proc = once()
    except subprocess.TimeoutExpired as exc:
        # ONE documented retry, for a rare stall in the fabric that the
        # body only drives (PERF.md, Open questions). A warning, not a
        # print: a retried pass must stay visible
        warnings.warn(f"benchmark body stalled ({exc}); retrying once")
        proc = once()
    said = proc.stdout + proc.stderr
    assert proc.returncode == 0, said[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1]), said
