"""chip_smoke.py on the CPU: the same body, launchers, legs and comparisons
the chip run makes, at a few MiB over the 8-device CPU mesh — so the script
that proves the program on the chip is itself proven to run before chip
time is spent on it — ``main()``'s refusal of a host without a TPU, and the
same body on a machine that bounds the size of a file.
"""

import json
import os
import subprocess
import sys

import chip_smoke

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# a fresh process, as on the chip: the body builds and loads the native
# library, brings JAX up and runs a daemon loop on a thread of its own
_BODY = """
import json
import resource
import chip_smoke
if {fsize}:
    resource.setrlimit(resource.RLIMIT_FSIZE, ({fsize}, {fsize}))
tiny = chip_smoke.Sizes(vocab=4096, hidden=256, experts=8, expert_width=176,
                        moe_layers=3, dataset_shards=4,
                        dataset_shard_bytes=5 * chip_smoke.MiB + 123)
print(json.dumps(chip_smoke.run(expect_platform="cpu", sizes=tiny, seed=1)))
"""


def _run_body(fsize: int = 0) -> subprocess.CompletedProcess:
    try:
        return _run_body_once(fsize)
    except subprocess.TimeoutExpired as exc:
        # ONE documented retry, for a stall in the fabric this script only
        # drives: about once in fifty runs at these sizes a two-piece task
        # whose seed finished within ~100 ms of the child's register is
        # never announced to the child (seed flight: 0 serves; PERF.md
        # open questions). The body takes ~20 s when it does not stall. A
        # warning, not a print: a retried pass must stay visible.
        import warnings
        warnings.warn(f"chip_smoke body stalled ({exc}); retrying once")
        return _run_body_once(fsize)


def _run_body_once(fsize: int) -> subprocess.CompletedProcess:
    return subprocess.run(
        [sys.executable, "-c", _BODY.format(fsize=fsize)], cwd=REPO,
        env={**os.environ, "PYTHONPATH": REPO},
        capture_output=True, text=True, timeout=240)


def _passed(proc: subprocess.CompletedProcess) -> str:
    assert proc.returncode == 0, (proc.stdout + proc.stderr)[-4000:]
    lines = proc.stdout.strip().splitlines()
    assert json.loads(lines[-1]) == {
        "ok": True, "device": {"platform": "cpu", "kind": "cpu", "count": 8}}
    return "\n".join(lines)


def test_body_runs_every_leg_on_the_cpu_mesh():
    out = _passed(_run_body())
    for said in (
            "native: built native/build/libdfnative.so",
            "dfget landed 8 MiB in 1 file(s)",
            "73 shards ready",
            "landed as 73 named bf16 arrays on cpu, bit-equal",
            "all 8 MiB over P2P",
            "4 x 5 MiB shards prefetched (depth 2) as 32 device arrays",
            "one addressable shard on each of 8 device(s)",
            "trainer/mlp: 40 epochs on cpu, mesh {'dp': 4, 'tp': 2}",
            "trainer/gnn: 60 epochs on cpu, mesh {'dp': 4, 'tp': 2}",
            "4 children alive, none with jaxlib or libtpu mapped"):
        assert said in out, f"{said!r} not in:\n{out}"
    # every fourth-and-later tensor is 88 KiB against 4 MiB pieces: the
    # test sizes keep the real run's property that shard boundaries fall
    # inside pieces, and say that they are not the model's widths
    assert "WIDTHS CUT TOO" in out


def test_body_shards_the_checkpoint_where_files_are_bounded():
    """The driver's chip machine refused the 3.7 GiB checkpoint file with
    EFBIG. Under a hard RLIMIT_FSIZE (inherited by every child, as there)
    the same bytes go out as files of whole tensors, nothing left out, and
    what must shrink says so."""
    out = _passed(_run_body(fsize=3 * chip_smoke.MiB))
    for said in (
            "lets a process write no file over 3 MiB (RLIMIT_FSIZE: 3 MiB)",
            "the checkpoint goes out as 3 files of whole tensors",
            "CUT: dataset shards of 3 MiB each",
            "dfget landed 8 MiB in 3 file(s), sha256 equal, 73 shards ready",
            "landed as 73 named bf16 arrays on cpu, bit-equal",
            "4 x 3 MiB shards prefetched"):
        assert said in out, f"{said!r} not in:\n{out}"
    assert "tensors larger than that" not in out


def test_checkpoint_files_keep_tensors_whole_at_published_widths():
    manifest = chip_smoke.checkpoint_manifest(chip_smoke.Sizes())
    total = manifest[-1]["range_start"] + manifest[-1]["range_size"]
    (one,) = chip_smoke.checkpoint_files(manifest, total)
    assert one["size"] == total == 3808 * chip_smoke.MiB
    assert one["shards"] == manifest
    files = chip_smoke.checkpoint_files(manifest, 1 << 30)
    assert [f["size"] / chip_smoke.MiB for f in files] == [
        1019.5, 1023.0, 1023.0, 742.5]
    assert [s["name"] for f in files for s in f["shards"]] == [
        s["name"] for s in manifest]
    for f in files:
        assert f["shards"][0]["range_start"] == 0
        last = f["shards"][-1]
        assert last["range_start"] + last["range_size"] == f["size"]
    # a tensor no file can hold is left out, and the caller says so
    small = chip_smoke.checkpoint_files(manifest, 512 * chip_smoke.MiB)
    assert sum(len(f["shards"]) for f in small) == len(manifest) - 1


def test_largest_file_finds_the_bound(tmp_path):
    import resource
    soft, hard = resource.getrlimit(resource.RLIMIT_FSIZE)
    assert chip_smoke.largest_file(str(tmp_path), 1 << 34) == 1 << 34
    resource.setrlimit(resource.RLIMIT_FSIZE, (5 * chip_smoke.MiB, hard))
    try:
        got = chip_smoke.largest_file(str(tmp_path), 1 << 34)
    finally:
        resource.setrlimit(resource.RLIMIT_FSIZE, (soft, hard))
    assert got == 5 * chip_smoke.MiB


def test_main_refuses_a_host_without_a_tpu(capsys):
    assert chip_smoke.main([]) != 0
    out, err = capsys.readouterr()
    assert "expected platform 'tpu', jax found 'cpu'" in err
    assert '"ok"' not in out
