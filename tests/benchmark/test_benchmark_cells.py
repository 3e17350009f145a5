"""Each cell's body on the CPU: the same set-up, window, comparison and
result line the chip run makes, at sizes of a few MiB, so that the harness
is proven to run before chip time is spent on it; the same body on a machine
that bounds the size of a file; and ``run.py``'s refusal of a host without a
TPU. A number from these runs is no device number and goes nowhere."""

import json
import os
import subprocess
import sys

import pytest

from benchmarks import testing

ROOT = testing.ROOT
BENCH = testing.bench()
CELLS = testing.cells()


def _wanted(cell: str, group: str) -> list[str]:
    return [m["name"] for m in BENCH[group]
            if cell in m.get("workloads", [cell])]


@pytest.mark.parametrize("trace", [False, True], ids=["untraced", "traced"])
@pytest.mark.parametrize("cell", CELLS)
def test_cell_body_ends_with_the_contracts_line(cell, trace):
    result, said = testing.run_body(cell, trace=trace)
    assert result["correct"] is True, said[-3000:]
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert list(result)[-1] == "compared"
    for key in ("correct", "attempted", "failed", "metrics", "device"):
        assert key in result
    assert result["device"]["platform"] == "cpu"
    assert result["device"]["count"] >= 1
    assert "memory_peak_bytes" in result["device"]
    wanted = _wanted(cell, "per_layer" if trace else "end_to_end")
    assert sorted(result["metrics"]) == sorted(wanted), said[-3000:]
    for name, m in result["metrics"].items():
        assert isinstance(m["value"], (int, float)) and m["unit"]
    if trace:
        assert result["device"]["busy_s"] > 0
        assert result["device"]["window_s"] > result["device"]["busy_s"]
        assert 1 <= len(result["breakdown"]["device_ops"]) <= 10
        assert 1 <= len(result["breakdown"]["idle_gaps"]) <= 10
    # every number compared is said beside its limit, last on stderr too
    for name, c in result["compared"].items():
        assert f"compared {name}: {c['value']} (limit {c['limit']})" in said
    assert "machine: file bound" in said and "host cores" in said


@pytest.mark.parametrize("cell", [c for c in CELLS
                                  if "file_bound" in testing.tiny(c)])
def test_cell_body_where_files_are_bounded(cell):
    """The driver's chip machine refused a 3.7 GiB file with EFBIG. Under a
    hard RLIMIT_FSIZE (inherited by every child, as there) the files are
    cut, the run says so, and it is still correct. The bound and what the
    run has to say under it are the configuration's own, in its tiny file."""
    bound = testing.tiny(cell)["file_bound"]
    result, said = testing.run_body(cell, fsize=bound["bytes"])
    assert result["correct"] is True, said[-3000:]
    for line in bound["says"]:
        assert line in said, (line, bound["why"])


def test_checkpoint_files_are_cut_at_whole_tensors_under_a_bound():
    from benchmarks import harness
    content = harness.load_module("content", "moe_checkpoint")
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "moonlight-16b-a3b-ckpt.json")) as f:
        config = json.load(f)
    files, notes = content.files(config, 1 << 62)
    assert [f["size"] / testing.MiB for f in files] == [640, 1056, 1056, 1056]
    assert sum(len(f["shards"]) for f in files) == 577 and notes == []
    names = [s["name"] for f in files for s in f["shards"]]
    cut, notes = content.files(config, 700 * testing.MiB)
    assert len(cut) == 7 and notes == []
    assert [s["name"] for f in cut for s in f["shards"]] == names
    for f in cut:
        assert f["size"] <= 700 * testing.MiB
        assert f["shards"][0]["range_start"] == 0
        last = f["shards"][-1]
        assert last["range_start"] + last["range_size"] == f["size"]
    small, notes = content.files(config, 512 * testing.MiB)
    assert sum(len(f["shards"]) for f in small) == 576
    assert notes and notes[0].startswith("CUT: 1 tensors larger")


def test_run_py_refuses_a_host_without_a_tpu():
    proc = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"], cwd=ROOT,
        capture_output=True, text=True, timeout=240)
    assert proc.returncode != 0
    assert "expected platform 'tpu', jax found 'cpu'" in proc.stderr
    assert proc.stdout.strip() == ""
