"""Content kind ``moe_checkpoint_placed`` and the plain reference of a placed
load: the full-size manifest's sizes, and one test that ties the four chips'
shares to the model at a small size: through the sink onto four devices, the
chips' arrays together are every tensor of the uncut manifest exactly once."""

import json
import os

import numpy as np

from benchmarks import harness, reference_placed, testing

ROOT = testing.ROOT
MiB = testing.MiB
CELL = "ckpt-warm-pull-4chip"


def _config(**overrides) -> dict:
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "moonlight-16b-a3b-ckpt-host4.json")) as f:
        return {**json.load(f), **overrides}


def test_the_full_size_manifest_is_the_deployments():
    content = harness.load_module("content", "moe_checkpoint_placed")
    files, notes = content.files(_config(), 1 << 62)
    assert notes == []
    assert [f["size"] / MiB for f in files] == [640] + [1056] * 10
    shards = [s for f in files for s in f["shards"]]
    assert len(shards) == 1924 and len({s["name"] for s in shards}) == 1924
    assert reference_placed.chip_bytes(files, 4) == [2800 * MiB] * 4
    for f in files:                          # whole arrays, back to back
        assert [s["range_start"] for s in f["shards"]] == list(np.cumsum(
            [0] + [s["range_size"] for s in f["shards"][:-1]]))
    assert [(s["shape"], s["device"]) for s in files[0]["shards"]] == [
        ([40960, 2048], c) for c in range(4)]
    # under a file bound the cut is at whole arrays and moves no array to
    # another chip
    cut, notes = content.files(_config(), 700 * MiB)
    assert notes == [] and all(f["size"] <= 700 * MiB for f in cut)
    assert [(s["name"], s["device"]) for f in cut for s in f["shards"]] == [
        (s["name"], s["device"]) for s in shards]
    # the traffic asks the machine for less than the 16.0 GB it grants
    with open(os.path.join(ROOT, "benchmarks", "traffic",
                           "warm-host-load.json")) as f:
        copies = json.load(f)["workdir_copies"]
    total = sum(f["size"] for f in files)
    assert total * copies * 1.05 < 16.0e9 and total < 12e9


def test_the_four_chips_shares_add_up_to_the_uncut_model():
    """At the cell's test sizes, through ``DeviceIngest`` onto four of the
    CPU mesh's devices: every tensor of ``moe_checkpoint``'s uncut manifest
    is on the chips exactly once (the embedding's four row ranges
    concatenate to the tensor), an expert's gate, up and down share a chip,
    chip c holds experts 2c and 2c+1 of every layer (16c..16c+15 at full
    size), and the four chips' bytes are equal."""
    import jax

    from dragonfly2_tpu.tpu.hbm_sink import DeviceIngest

    config = _config(**testing.tiny(CELL)["config"])
    placed = harness.load_module("content", "moe_checkpoint_placed")
    uncut = harness.load_module("content", "moe_checkpoint")
    files, _ = placed.files(config, 1 << 62)
    whole, _ = uncut.files(config, 1 << 62)
    assert [f["size"] for f in files] == [f["size"] for f in whole]
    devices = jax.devices()[:4]
    per_chip = config["n_routed_experts"] // 4
    on_chip = [0] * 4
    seen: list[str] = []
    for i, (f, w) in enumerate(zip(files, whole)):
        data = np.random.default_rng([11, i]).integers(
            0, 256, f["size"], dtype=np.uint8)
        sink = DeviceIngest(f["size"], devices=devices, dtype="bfloat16",
                            shard_specs=[(s["name"], s["range_start"],
                                          s["range_size"], s["dtype"],
                                          s["shape"], s["device"])
                                         for s in f["shards"]])
        for off in range(0, f["size"], 1 << 20):
            sink.write(off, data[off:off + (1 << 20)].tobytes())
        arrays = sink.result(timeout=30)
        reference = reference_placed.put(data, f["shards"], devices)
        for s, ref in zip(f["shards"], reference):
            got = arrays[s["name"]]
            assert got.devices() == ref.devices() == {devices[s["device"]]}
            assert np.asarray(got).tobytes() == np.asarray(ref).tobytes()
            on_chip[s["device"]] += s["range_size"]
        # against the uncut manifest of the same file's bytes
        for t in w["shards"]:
            want = data[t["range_start"]:t["range_start"] + t["range_size"]]
            if t["name"] == placed.EMBED:
                rows = sorted((s for s in f["shards"]),
                              key=lambda s: s["range_start"])
                assert [s["device"] for s in rows] == [0, 1, 2, 3]
                got = np.concatenate([np.asarray(arrays[s["name"]])
                                      for s in rows])
                assert list(got.shape) == t["shape"]
            else:
                got = np.asarray(arrays[t["name"]])
                assert list(got.shape) == t["shape"]
                expert = int(t["name"].split(".experts.")[1].split(".")[0])
                (chip,) = arrays[t["name"]].devices()
                assert chip == devices[expert // per_chip]
            assert got.tobytes() == want.tobytes()
            seen.append(t["name"])
    assert seen == [t["name"] for w in whole for t in w["shards"]]
    assert len(set(on_chip)) == 1 and sum(on_chip) == sum(
        f["size"] for f in files)
