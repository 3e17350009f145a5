"""JAX models: MLP bandwidth predictor + host-graph GNN.

Role parity: the models the reference *intended* (``trainer/training``
GNN+MLP stubs, ``manager/models/model.go`` model registry names) built
TPU-first:

* static shapes everywhere (edge lists padded + masked) so XLA tiles onto
  the MXU;
* bfloat16 matmul compute with float32 params/accumulators;
* a single fused ``train_step`` (loss + grads + adamw update) designed to be
  ``jax.jit``-ed over a ``Mesh`` — batch sharded on ``dp``, hidden features
  on ``tp`` (see ``shard_params`` / ``shard_batch``).

The MLP consumes the 7-feature parent row (``scheduler/evaluator_ml.py``
``feature_row`` — keep in sync) and predicts a goodness score; the GNN
consumes the host graph (nodes = hosts, edges = probed links with RTT) and
predicts per-link bandwidth class.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

MLP_FEATURES = 7          # scheduler/evaluator_ml.py feature_row length
GNN_NODE_FEATURES = 7     # host features: type, upload ratio, load,
                          # coords, pod id (features.NODE_FEATURES v2)
GNN_EDGE_FEATURES = 2     # log-rtt, link-class

Params = Any  # pytree of jnp arrays


# ------------------------------------------------------------------ init

def _dense_init(key, n_in: int, n_out: int) -> dict:
    w_key, _ = jax.random.split(key)
    scale = (2.0 / n_in) ** 0.5
    return {"w": jax.random.normal(w_key, (n_in, n_out), jnp.float32) * scale,
            "b": jnp.zeros((n_out,), jnp.float32)}


def init_mlp(key, *, in_dim: int = MLP_FEATURES, hidden: int = 128,
             depth: int = 2, out_dim: int = 1) -> Params:
    keys = jax.random.split(key, depth + 1)
    layers = [_dense_init(keys[0], in_dim, hidden)]
    for i in range(1, depth):
        layers.append(_dense_init(keys[i], hidden, hidden))
    layers.append(_dense_init(keys[-1], hidden, out_dim))
    return {"layers": layers}


def init_gnn(key, *, node_dim: int = GNN_NODE_FEATURES,
             edge_dim: int = GNN_EDGE_FEATURES, hidden: int = 128,
             layers: int = 2) -> Params:
    keys = jax.random.split(key, 2 * layers + 2)
    params: dict = {"encode": _dense_init(keys[0], node_dim, hidden),
                    "msg": [], "upd": []}
    for i in range(layers):
        params["msg"].append(
            _dense_init(keys[1 + 2 * i], 2 * hidden + edge_dim, hidden))
        params["upd"].append(
            _dense_init(keys[2 + 2 * i], 2 * hidden, hidden))
    # head reads NODE EMBEDDINGS only: feeding edge_feat (which contains
    # the observed log-RTT the label is computed from) lets training learn
    # the trivial copy-the-answer shortcut — the model must predict a
    # link's quality from where its endpoints sit in the graph, which is
    # the only information available for an UNPROBED pair at impute time
    params["head"] = _dense_init(keys[-1], 2 * hidden, 1)
    return params


# ------------------------------------------------------------------ forward

def _dense(p: dict, x: jnp.ndarray) -> jnp.ndarray:
    # bf16 matmul on the MXU, f32 accumulate via preferred_element_type
    y = jax.lax.dot_general(
        x.astype(jnp.bfloat16), p["w"].astype(jnp.bfloat16),
        (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    return y + p["b"]


def mlp_forward(params: Params, x: jnp.ndarray) -> jnp.ndarray:
    """x: [batch, MLP_FEATURES] -> [batch] predicted goodness."""
    h = x.astype(jnp.float32)
    for layer in params["layers"][:-1]:
        h = jax.nn.gelu(_dense(layer, h))
    out = _dense(params["layers"][-1], h)
    return out[..., 0]


def gnn_forward(params: Params, nodes: jnp.ndarray, edge_src: jnp.ndarray,
                edge_dst: jnp.ndarray, edge_feat: jnp.ndarray,
                edge_mask: jnp.ndarray) -> jnp.ndarray:
    """Host-graph message passing.

    nodes:      [N, node_dim]   edge_src/dst: [E] int32 (padded)
    edge_feat:  [E, edge_dim]   edge_mask:    [E] {0,1}
    returns     [E] predicted link bandwidth score for EVERY edge index
    (the caller masks; query edges ride with mask=0 so they never inject
    fabricated messages into aggregation yet still get head scores)

    Observed edges' features (incl. their measured log-RTT) inform the
    MESSAGES — a node's links say where it sits — but the head scores a
    pair from the two node embeddings alone (no label leak; see init_gnn).

    Static [N, E] shapes: the scheduler pads its host graph to the next
    bucket so recompilation only happens on bucket growth.
    """
    n = nodes.shape[0]
    h = jax.nn.gelu(_dense(params["encode"], nodes))
    mask = edge_mask[:, None].astype(jnp.float32)
    for msg_p, upd_p in zip(params["msg"], params["upd"]):
        src_h = h[edge_src]                       # [E, H] gather
        dst_h = h[edge_dst]
        m = jax.nn.gelu(_dense(msg_p, jnp.concatenate(
            [src_h, dst_h, edge_feat], axis=-1))) * mask
        agg = jax.ops.segment_sum(m, edge_dst, num_segments=n)
        deg = jax.ops.segment_sum(mask, edge_dst, num_segments=n)
        agg = agg / jnp.maximum(deg, 1.0)
        h = jax.nn.gelu(_dense(upd_p, jnp.concatenate([h, agg], axis=-1)))
    return _dense(params["head"], jnp.concatenate(
        [h[edge_src], h[edge_dst]], axis=-1))[..., 0]


# ------------------------------------------------------------------ training

def mlp_loss(params: Params, batch: dict) -> jnp.ndarray:
    pred = mlp_forward(params, batch["x"])
    return jnp.mean((pred - batch["y"]) ** 2)


def gnn_loss(params: Params, batch: dict) -> jnp.ndarray:
    pred = gnn_forward(params, batch["nodes"], batch["edge_src"],
                       batch["edge_dst"], batch["edge_feat"],
                       batch["edge_mask"])
    err = (pred - batch["y"]) ** 2 * batch["edge_mask"]
    return jnp.sum(err) / jnp.maximum(jnp.sum(batch["edge_mask"]), 1.0)


def make_optimizer(lr: float = 1e-3) -> optax.GradientTransformation:
    return optax.adamw(lr, weight_decay=1e-4)


def make_train_step(loss_fn, optimizer):
    """(params, opt_state, batch) -> (params, opt_state, loss); pure, jittable."""

    def step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    return step


# ------------------------------------------------------------------ sharding

def make_mesh(n_devices: int | None = None, *,
              dp: int | None = None) -> Mesh:
    """A (dp, tp) mesh over this host's devices; tp gets the residue."""
    devices = np.array(jax.local_devices())
    n = n_devices or devices.size
    devices = devices[:n]
    if dp is None:
        dp = max(1, n // 2) if n > 1 else 1
    tp = n // dp
    return Mesh(devices[:dp * tp].reshape(dp, tp), ("dp", "tp"))


def _param_spec(leaf: jnp.ndarray, tp: int) -> P:
    # weight matrices shard the output-features dim over tp (when it tiles
    # evenly — the 1-wide output head replicates); biases/scalars replicate.
    if leaf.ndim == 2 and tp > 1 and leaf.shape[1] % tp == 0 \
            and leaf.shape[1] >= tp:
        return P(None, "tp")
    return P()


def shard_params(params: Params, mesh: Mesh) -> Params:
    tp = mesh.shape.get("tp", 1)

    def put(leaf):
        return jax.device_put(leaf, NamedSharding(mesh, _param_spec(leaf, tp)))
    return jax.tree_util.tree_map(put, params)


def shard_batch(batch: dict, mesh: Mesh) -> dict:
    def put(leaf):
        spec = P("dp") if leaf.ndim >= 1 else P()
        return jax.device_put(leaf, NamedSharding(mesh, spec))
    return {k: put(v) for k, v in batch.items()}


def sharded_train_step(loss_fn, optimizer, mesh: Mesh):
    """jit the full train step over the mesh: batch dp-sharded, weight
    matrices tp-sharded; XLA inserts the psum/all-gather collectives."""
    step = make_train_step(loss_fn, optimizer)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def jitted(params, opt_state, batch):
        return step(params, opt_state, batch)

    return jitted


# ------------------------------------------------------------------ synthetic data (tests/dryrun)

def synthetic_mlp_batch(key, batch_size: int = 256) -> dict:
    x_key, n_key = jax.random.split(key)
    x = jax.random.uniform(x_key, (batch_size, MLP_FEATURES))
    w = jnp.linspace(1.0, 0.2, MLP_FEATURES)
    y = x @ w + 0.05 * jax.random.normal(n_key, (batch_size,))
    return {"x": x, "y": y}


def synthetic_gnn_batch(key, n_nodes: int = 32, n_edges: int = 128) -> dict:
    k1, k2, k3, k4 = jax.random.split(key, 4)
    nodes = jax.random.uniform(k1, (n_nodes, GNN_NODE_FEATURES))
    edge_src = jax.random.randint(k2, (n_edges,), 0, n_nodes)
    edge_dst = jax.random.randint(k3, (n_edges,), 0, n_nodes)
    edge_feat = jax.random.uniform(k4, (n_edges, GNN_EDGE_FEATURES))
    y = 1.0 / (1.0 + edge_feat[:, 0])      # bandwidth ~ inverse log-rtt
    edge_mask = jnp.ones((n_edges,), jnp.float32)
    return {"nodes": nodes, "edge_src": edge_src, "edge_dst": edge_dst,
            "edge_feat": edge_feat, "edge_mask": edge_mask, "y": y}
