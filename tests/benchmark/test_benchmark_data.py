"""``BENCHMARK.json`` and the files it names: every workload resolves to
files that exist, every name and unit keeps to the allowed characters and
lengths, every per-layer metric moves an end-to-end metric that each of its
cells reports, and a configuration, a traffic mix, a driver and a metric
reader can each be added as new files plus new entries, with no edit to a
file that was there."""

import filecmp
import json
import os
import re
import shutil

import pytest

from benchmarks import testing

ROOT = testing.ROOT
BENCH = testing.bench()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
CELLS = testing.cells()
E2E = {m["name"]: m for m in BENCH["end_to_end"]}


def _cells_of(metric: dict) -> list[str]:
    return metric.get("workloads", CELLS)


def test_top_level_keys_and_limits():
    assert sorted(BENCH) == sorted(["command", "paths", "run_seconds",
                                    "configs", "workloads", "end_to_end",
                                    "per_layer"])
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 << 10
    assert isinstance(BENCH["run_seconds"], int)
    assert 1 <= BENCH["run_seconds"] <= 51
    # the full check has to fit with all 24 cells a benchmark may hold
    runs = 2 + 14 * 24
    assert runs * (BENCH["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    assert 1 <= len(BENCH["paths"]) <= 16
    for p in BENCH["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert os.path.isdir(os.path.join(ROOT, p))
    assert len(BENCH["command"]) <= 32
    assert all(1 <= len(w) <= 200 for w in BENCH["command"])
    assert os.path.isfile(os.path.join(ROOT, BENCH["command"][1]))
    assert any(BENCH["command"][1].startswith(p + "/")
               for p in BENCH["paths"])


def test_every_file_under_paths_has_an_allowed_name():
    for p in BENCH["paths"]:
        for folder, dirs, files in os.walk(os.path.join(ROOT, p)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for f in files:
                rel = os.path.relpath(os.path.join(folder, f), ROOT)
                assert PATH.match(rel), rel


def test_configs_resolve_and_keep_the_sources_numbers():
    names = [c["name"] for c in BENCH["configs"]]
    assert len(set(names)) == len(names) and 1 <= len(names) <= 24
    files = [c["file"] for c in BENCH["configs"]]
    assert len(set(files)) == len(files)
    used = {w["config"] for w in BENCH["workloads"]}
    for c in BENCH["configs"]:
        assert sorted(c) == ["file", "name", "reduced", "source", "why"]
        assert NAME.match(c["name"]) and c["name"] in used
        assert 1 <= len(c["source"]) <= 200 and "\n" not in c["source"]
        assert 1 <= len(c["why"]) <= 200 and "\t" not in c["why"]
        assert any(c["file"].startswith(p + "/") for p in BENCH["paths"])
        assert len(c["reduced"]) <= 16
        for key in c["reduced"]:
            assert NAME.match(key)
            assert not re.search(r"(_dim|_rank|_size|width)$", key) \
                and key != "num_experts_per_tok", f"{key} names a width"
        with open(os.path.join(ROOT, c["file"])) as f:
            config = json.load(f)
        assert os.path.isfile(os.path.join(
            ROOT, "benchmarks", "content", config["content"] + ".py"))
        assert config["guarantees"], "a deployment states its guarantees"
        # its test sizes, as data beside it
        stem, ext = os.path.splitext(c["file"])
        with open(os.path.join(ROOT, f"{stem}.tiny{ext}")) as f:
            tiny = json.load(f)
        assert isinstance(tiny["config"], dict)
        assert isinstance(tiny["traffic"], dict)
    # the catalog's entry for Moonlight-16B-A3B, where the catalog is at hand:
    # every number under the same key, but for what `reduced` lists
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.isfile(catalog):
        with open(catalog) as f:
            rows = {r["source_url"]: r for r in map(json.loads, f)}
        for c in BENCH["configs"]:
            if c["source"] in rows:
                with open(os.path.join(ROOT, c["file"])) as f:
                    config = json.load(f)
                for key, value in rows[c["source"]]["config"].items():
                    if key not in c["reduced"]:
                        assert config[key] == value, key


def test_workloads_resolve_to_files_that_exist():
    names = [w["name"] for w in BENCH["workloads"]]
    assert len(set(names)) == len(names) and 1 <= len(names) <= 24
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(set(pairs)) == len(pairs)
    configs = {c["name"] for c in BENCH["configs"]}
    four = sum(1 for w in BENCH["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(names) // 2)
    for w in BENCH["workloads"]:
        assert sorted(w) == ["chips", "config", "name", "traffic", "why"]
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
        mix = os.path.join(ROOT, "benchmarks", "traffic",
                           w["traffic"] + ".json")
        assert os.path.isfile(mix), mix
        with open(mix) as f:
            driver = json.load(f)["driver"]
        assert os.path.isfile(os.path.join(
            ROOT, "benchmarks", "drivers", driver + ".py"))
        from benchmarks import harness
        cell = harness.load_cell(w["name"])
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
        assert len(cell.end_to_end) >= 2 and len(cell.per_layer) >= 1


def test_metrics_names_units_sources_and_what_they_move():
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    names = [m["name"] for m in metrics]
    assert len(set(names)) == len(names)
    assert 1 <= len(BENCH["end_to_end"]) <= 16
    assert 1 <= len(BENCH["per_layer"]) <= 128
    assert "setup_s" in E2E and E2E["setup_s"]["bound"] <= 0.25
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
        assert os.path.isfile(os.path.join(
            ROOT, "benchmarks", "end_to_end", m["name"] + ".py"))
    layers = set()
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert 1 <= len(m["layer"]) <= 200 and "\n" not in m["layer"]
        layers.add(m["layer"])
        assert os.path.isfile(os.path.join(
            ROOT, "benchmarks", "layer_metrics", m["name"] + ".py"))
        # it moves one end-to-end metric, which each of its cells reports
        moved = E2E[m["moves"]]
        for cell in _cells_of(m):
            assert cell in CELLS and cell in _cells_of(moved), (m["name"],
                                                                cell)
        assert "roofline" not in m["name"] and "mfu" not in m["name"]
    for m in metrics:
        assert NAME.match(m["name"]), m["name"]
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        for cell in m.get("workloads", []):
            assert cell in CELLS
    # PERF.md's list of layers has each layer, letter for letter
    with open(os.path.join(ROOT, "PERF.md")) as f:
        perf = f.read()
    for layer in layers:
        assert f"| {layer} |" in perf, layer


def test_an_unknown_chip_is_an_error():
    from benchmarks import harness
    assert harness.chip_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(harness.BenchFailure):
        harness.chip_peaks("TPU v9 imaginary")


def test_a_later_pr_adds_a_cell_as_new_files_and_entries_only(tmp_path):
    """A throw-away configuration, content kind, traffic mix, driver and two
    metric readers, added to a temporary copy as new files plus new entries
    of BENCHMARK.json, make a cell that runs; no file that was there is
    edited."""
    root = str(tmp_path / "copy")
    os.makedirs(root)
    for name in ("benchmarks", "dragonfly2_tpu", "native"):
        shutil.copytree(os.path.join(ROOT, name), os.path.join(root, name),
                        ignore=shutil.ignore_patterns("__pycache__"))
    before = {os.path.relpath(os.path.join(d, f), root)
              for d, _dirs, files in os.walk(os.path.join(root, "benchmarks"))
              for f in files}
    b = os.path.join(root, "benchmarks")
    with open(os.path.join(b, "configs", "throwaway.json"), "w") as f:
        json.dump({"content": "throwaway_blobs", "blobs": 3,
                   "blob_bytes": (1 << 20) + 17,
                   "guarantees": ["bytes as the origin has them"]}, f)
    # its test sizes come as a file too, found by the same look-up
    with open(os.path.join(b, "configs", "throwaway.tiny.json"), "w") as f:
        json.dump({"config": {"blobs": 3}, "traffic": {}}, f)
    with open(os.path.join(b, "content", "throwaway_blobs.py"), "w") as f:
        f.write("def files(config, cap):\n"
                "    return ([{'name': f'blob-{i}.bin', 'size': "
                "min(cap, config['blob_bytes']), 'shards': None}\n"
                "             for i in range(config['blobs'])], [])\n")
    with open(os.path.join(b, "traffic", "throwaway-mix.json"), "w") as f:
        json.dump({"driver": "throwaway_driver", "depth": 1}, f)
    with open(os.path.join(b, "drivers", "throwaway_driver.py"), "w") as f:
        f.write(
            "import time\n"
            "from benchmarks.harness import WindowResult\n"
            "from benchmarks.sources import Request\n"
            "def prepare(ctx):\n    pass\n"
            "def window(ctx, seconds):\n"
            "    t0 = time.monotonic()\n"
            "    reqs, kept, n = [], [], 0\n"
            "    for f, shard in zip(ctx.files, ctx.source.stream(\n"
            "            ctx.files, depth=ctx.cell.traffic['depth'],\n"
            "            delete_after=True)):\n"
            "        reqs.append(Request(f['name'], f['size'], t0,\n"
            "                            time.monotonic(), True))\n"
            "        kept.append((f, shard))\n"
            "        n += f['size']\n"
            "    ctx.state['kept'] = kept\n"
            "    return WindowResult(t0, time.monotonic(), reqs, n)\n"
            "def compare(ctx, result, obs):\n"
            "    import numpy as np\n"
            "    off = 0\n"
            "    for f, shard in ctx.state.pop('kept'):\n"
            "        got = np.concatenate([np.asarray(a) for a in shard])\n"
            "        want = ctx.bytes_of(f)\n"
            "        off += int(np.count_nonzero(\n"
            "            got[:want.shape[0]] != want))\n"
            "    return {'bytes_differing': (off, 0)}\n")
    with open(os.path.join(b, "end_to_end", "throwaway_requests.py"),
              "w") as f:
        f.write("def read(obs):\n    return len(obs.window.requests)\n")
    with open(os.path.join(b, "layer_metrics", "throwaway.silent.py"),
              "w") as f:
        f.write("def read(obs):\n    return None\n")
    with open(os.path.join(b, "layer_metrics", "throwaway_land.py"),
              "w") as f:
        f.write("def read(obs):\n    return sum(obs.span_lands.values())\n")
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({"name": "throwaway", "source": "a test",
                             "file": "benchmarks/configs/throwaway.json",
                             "reduced": [], "why": "a test"})
    bench["workloads"].append({"name": "throwaway-cell",
                               "config": "throwaway",
                               "traffic": "throwaway-mix", "chips": 1,
                               "why": "a test"})
    one = ["throwaway-cell"]
    bench["end_to_end"].append({"name": "throwaway_requests", "unit": "n",
                                "better": "higher", "bound": 0.01,
                                "source": "host_clock", "workloads": one})
    for name in ("throwaway.silent", "throwaway_land"):
        bench["per_layer"].append({
            "name": name, "unit": "n", "better": "higher",
            "source": "program_counter", "layer": "landing + verify",
            "moves": "throwaway_requests", "workloads": one})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)

    result, said = testing.run_body("throwaway-cell", root=root)
    assert result["correct"] is True, said[-3000:]
    assert result["attempted"] == 3
    # an end-to-end metric with no `workloads` key is every cell's, the new
    # cell's too
    assert sorted(result["metrics"]) == ["ready_MiB_per_s", "setup_s",
                                         "throwaway_requests"]
    assert result["metrics"]["throwaway_requests"]["value"] == 3
    result, said = testing.run_body("throwaway-cell", root=root, trace=True)
    # a reader that finds nothing to read is left out of the line
    assert sorted(result["metrics"]) == ["throwaway_land"]
    assert result["metrics"]["throwaway_land"]["value"] >= 3

    for rel in before:
        assert filecmp.cmp(os.path.join(root, rel), os.path.join(ROOT, rel),
                           shallow=False), f"{rel} was edited"
