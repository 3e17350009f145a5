"""Training runs: fit the MLP/GNN on uploaded scheduler records.

Role parity: reference ``trainer/training/training.go:60-97`` — the
pipeline exists there, the fitting is a TODO stub. This module completes
it: minibatch adamw over the fused ``sharded_train_step`` from
``trainer/models.py`` (dp×tp mesh when >1 device; single-device jit
otherwise), with model serialization + content-addressed versioning for the
manager registry (reference ``manager/models/model.go:36``).

Serialization is npz (numpy archive) of the flattened param pytree — no
pickle; the scheduler's serving side (``trainer/serving.py``) reloads it
with plain numpy and never needs jax on the hot path.
"""

from __future__ import annotations

import logging
import time

import numpy as np

from . import features, models
from .params_io import serialize_params, version_of  # noqa: F401 - re-export

log = logging.getLogger("df.trainer.training")

MLP_MODEL_NAME = features.MLP_MODEL_NAME
GNN_MODEL_NAME = features.GNN_MODEL_NAME


# ---------------------------------------------------------------- fitting

def _make_step(loss_fn, opt, mesh):
    if mesh is not None:
        return models.sharded_train_step(loss_fn, opt, mesh)
    import jax
    return jax.jit(models.make_train_step(loss_fn, opt))


def _mesh(use_mesh: bool):
    """Bring JAX up for a fit; a dp x tp mesh when this host has more
    than one device."""
    from ..tpu import runtime
    devices = runtime.bring_up()
    return models.make_mesh() if use_mesh and len(devices) > 1 else None


def _placement(params, mesh) -> dict:
    """Where the fit ran — returned metrics only, never serialized (the
    blob stays a function of rows and seed)."""
    import jax
    leaves = jax.tree_util.tree_leaves(params)
    return {"param_platforms": sorted({d.platform for leaf in leaves
                                       for d in leaf.devices()}),
            "mesh": dict(mesh.shape) if mesh is not None else None}


def train_mlp(rows: list[dict], *, epochs: int = 40, batch_size: int = 512,
              lr: float = 1e-3, seed: int = 0,
              use_mesh: bool = True) -> tuple[bytes, dict] | None:
    """Fit the parent-goodness MLP on download-record rows.

    Returns (model_bytes, metrics) or None when the rows hold no usable
    feature/label pairs. Batch dp-sharded + weights tp-sharded when more
    than one device is visible.
    """
    import jax

    data = features.records_to_arrays(rows)
    if data is None or data["x"].shape[0] < 8:
        return None
    n = data["x"].shape[0]
    rng = np.random.default_rng(seed)
    mesh = _mesh(use_mesh)
    key = jax.random.PRNGKey(seed)
    params = models.init_mlp(key)
    opt = models.make_optimizer(lr)
    if mesh is not None:
        params = models.shard_params(params, mesh)
    opt_state = opt.init(params)
    step = _make_step(models.mlp_loss, opt, mesh)

    bs = min(batch_size, n)
    if mesh is not None:
        # P("dp") needs the batch to tile over dp: an odd row count rounds
        # UP, the wraparound below fills the extra rows
        dp = mesh.shape["dp"]
        bs = -(-bs // dp) * dp
    # static batch shape: pad the epoch to a multiple of bs via wraparound
    steps_per_epoch = max(1, n // bs)
    first_loss = last_loss = None
    t0 = time.monotonic()
    for _ in range(epochs):
        order = rng.permutation(n)
        for s in range(steps_per_epoch):
            idx = order[(s * bs) % n:(s * bs) % n + bs]
            if idx.size < bs:
                idx = np.concatenate([idx, np.resize(order, bs - idx.size)])
            batch = {"x": data["x"][idx], "y": data["y"][idx]}
            if mesh is not None:
                batch = models.shard_batch(batch, mesh)
            params, opt_state, loss = step(params, opt_state, batch)
        loss_f = float(loss)
        if first_loss is None:
            first_loss = loss_f
        last_loss = loss_f
    metrics = {
        "model": MLP_MODEL_NAME,
        "rows": int(n),
        "epochs": epochs,
        "seed": int(seed),
        "first_epoch_loss": first_loss,
        "final_loss": last_loss,
        "feature_dim": features.FEATURE_DIM,
        "feature_names": list(features.PARENT_FEATURES),
        "schema_version": features.FEATURE_SCHEMA_VERSION,
        "devices": len(jax.local_devices()),
    }
    host_params = jax.tree_util.tree_map(np.asarray, params)
    data_bytes = serialize_params(host_params, metrics)
    metrics.update(_placement(params, mesh))
    # version + wall clock ride in the RETURNED metrics only: the
    # serialized meta must be a function of (rows, seed) alone so the
    # same fit yields the same blob bytes — the rollout path dedupes on
    # version and dfbench --pr19 gates refit-to-refit determinism on it
    metrics["version"] = version_of(data_bytes)
    metrics["train_seconds"] = time.monotonic() - t0
    log.info("mlp fit: rows=%d loss %.4f -> %.4f (%.1fs, %d devices)",
             n, first_loss, last_loss, metrics["train_seconds"],
             metrics["devices"])
    return data_bytes, metrics


def train_gnn(topo_rows: list[dict], *, epochs: int = 60, lr: float = 1e-3,
              seed: int = 0, use_mesh: bool = True
              ) -> tuple[bytes, dict] | None:
    """Fit the host-graph GNN on topology snapshot rows (bandwidth
    imputation for unprobed links)."""
    import jax

    graph = features.topology_to_graph(topo_rows)
    if graph is None or float(graph["edge_mask"].sum()) < 4:
        return None
    batch = {k: v for k, v in graph.items() if k != "host_ids"}
    mesh = _mesh(use_mesh)
    key = jax.random.PRNGKey(seed)
    params = models.init_gnn(key)
    opt = models.make_optimizer(lr)
    if mesh is not None:
        params = models.shard_params(params, mesh)
        # graph batches replicate (node/edge dims aren't batch dims)
        from jax.sharding import NamedSharding, PartitionSpec as P
        batch = {k: jax.device_put(v, NamedSharding(mesh, P()))
                 for k, v in batch.items()}
    opt_state = opt.init(params)
    step = _make_step(models.gnn_loss, opt, mesh)
    first_loss = last_loss = None
    t0 = time.monotonic()
    for _ in range(epochs):
        params, opt_state, loss = step(params, opt_state, batch)
        loss_f = float(loss)
        if first_loss is None:
            first_loss = loss_f
        last_loss = loss_f
    metrics = {
        "model": GNN_MODEL_NAME,
        "edges": int(graph["edge_mask"].sum()),
        "nodes": int(len(graph["host_ids"])),
        "node_features": list(features.NODE_FEATURES),
        "schema_version": features.FEATURE_SCHEMA_VERSION,
        "epochs": epochs,
        "seed": int(seed),
        "first_epoch_loss": first_loss,
        "final_loss": last_loss,
        "devices": len(jax.local_devices()),
    }
    host_params = jax.tree_util.tree_map(np.asarray, params)
    data_bytes = serialize_params(host_params, metrics)
    metrics.update(_placement(params, mesh))
    # same determinism contract as train_mlp: wall clock stays out of
    # the serialized meta so identical (rows, seed) → identical bytes
    metrics["version"] = version_of(data_bytes)
    metrics["train_seconds"] = time.monotonic() - t0
    log.info("gnn fit: edges=%d loss %.4f -> %.4f (%.1fs)",
             metrics["edges"], first_loss, last_loss,
             metrics["train_seconds"])
    return data_bytes, metrics
