"""The plain reference of a placed load: the origin's bytes, cut by the
manifest with ``np.frombuffer`` and put on the chip each shard names with a
plain ``jax.device_put``. Nothing of the program is imported. The driver's
comparison holds the program's arrays to these: the same words, on the same
chip."""

from __future__ import annotations


def views(data, shards: list[dict]) -> list:
    """Each shard of the manifest as an array over the file's bytes."""
    import numpy as np

    return [np.frombuffer(data, dtype=np.dtype(s["dtype"]),
                          count=s["range_size"] // np.dtype(
                              s["dtype"]).itemsize,
                          offset=s["range_start"]).reshape(s["shape"])
            for s in shards]


def chip_of(shard: dict, devices: list):
    """Where the manifest says the shard's array is to be."""
    return devices[shard["device"]]


def put(data, shards: list[dict], devices: list) -> list:
    """The file's arrays on the chips its manifest names, in manifest
    order; waited for."""
    import jax

    out = [jax.device_put(v, chip_of(s, devices))
           for v, s in zip(views(data, shards), shards)]
    jax.block_until_ready(out)
    return out


def chip_bytes(files: list[dict], n_chips: int) -> list[int]:
    """The manifests' bytes for each chip, over ``files``."""
    out = [0] * n_chips
    for f in files:
        for s in f["shards"]:
            out[s["device"]] += s["range_size"]
    return out
