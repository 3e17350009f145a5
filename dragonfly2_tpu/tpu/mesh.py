"""Device-mesh helpers: named-axis meshes over this host's devices.

The fabric uses meshes in two places: the HBM sink shards downloaded content
across a mesh axis, and the trainer pjit-shards its training step. Axis
conventions: ``data`` (batch / file-shard parallel), ``model`` (tensor
parallel within the predictor).
"""

from __future__ import annotations

import numpy as np


def make_mesh(axis_sizes: dict[str, int] | None = None, *, devices=None):
    """A ``jax.sharding.Mesh`` with named axes.

    Without ``axis_sizes``, all devices go on one ``data`` axis. Sizes must
    multiply to the device count (use -1 for one inferred axis).
    """
    import jax
    from jax.sharding import Mesh

    if devices is None:
        devices = jax.local_devices()
    n = len(devices)
    if not axis_sizes:
        axis_sizes = {"data": n}
    names = list(axis_sizes.keys())
    sizes = list(axis_sizes.values())
    if -1 in sizes:
        known = int(np.prod([s for s in sizes if s != -1]))
        if known <= 0 or n % known:
            raise ValueError(f"cannot infer axis size: {n} devices over {sizes}")
        sizes[sizes.index(-1)] = n // known
    if int(np.prod(sizes)) != n:
        raise ValueError(f"axis sizes {dict(zip(names, sizes))} != {n} devices")
    dev_array = np.asarray(devices).reshape(sizes)
    return Mesh(dev_array, axis_names=tuple(names))


def named_sharding(mesh, *axes: str | None):
    """``NamedSharding`` over ``mesh`` with a PartitionSpec of ``axes``."""
    from jax.sharding import NamedSharding, PartitionSpec

    return NamedSharding(mesh, PartitionSpec(*axes))
