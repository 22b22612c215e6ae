"""GraphSAGE (Hamilton et al.) on the AMPLE engine — Eq. 4 of the paper.

    x_i' = W1 x_i + W2 · mean_{j ∈ N(i)} σ(W3 x_j + b)

φ is a dense projection applied to *all* nodes once (every node is someone's
neighbour), the mean runs through the event-driven AGE with 1/deg
coefficients, and γ adds the W1 transformation-side residual (Table 3).

``sage_pool`` is GraphSAGE with the max-pooling aggregator (Hamilton, Ying
and Leskovec, arXiv:1706.02216, Eq. 3 and Algorithm 1, at the reference
code's layout: ``MaxPoolingAggregator``, concatenated halves, only the final
embedding L2-normalised, a dense prediction head). Layer k:

    p_u  = ReLU(h_u W_pool + b_pool)                    pooling MLP (FTE)
    a_v  = max_{u ∈ N(v)} p_u    (feature-wise; 0 if N(v) = ∅)   AGE, max
    h'_v = act_k(concat(h_v W_self, a_v W_neigh))       ReLU, none on the last
    y    = (h_K / ‖h_K‖₂) W_pred + b_pred

``cfg.gnn_layer_dims`` gives the widths: the input, each layer's
concatenated output (two halves) and the classes; ``cfg.d_ff`` is the pool
width.

Entry points are uniform and config-driven (see models/gnn/api.py).
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.message_passing import AmpleEngine
from repro.graphs.csr import Graph
from repro.models.gnn import api
from repro.models.gnn.layers import linear_init
from repro.observe import trace as otrace

__all__ = ["init", "apply", "reference", "pool_init", "pool_apply", "pool_reference"]


def init(cfg: ModelConfig, key) -> Dict:
    dims = cfg.gnn_layer_dims
    layers = []
    for i in range(len(dims) - 1):
        k1, k2, k3, key = jax.random.split(key, 4)
        layers.append(
            {
                "w1": linear_init(k1, dims[i], dims[i + 1], bias=False),
                "w2": linear_init(k2, dims[i], dims[i + 1], bias=False),
                "w3": linear_init(k3, dims[i], dims[i], bias=True),
            }
        )
    return {"layers": layers}


def apply(cfg: ModelConfig, params: Dict, engine: AmpleEngine, x: jnp.ndarray) -> jnp.ndarray:
    mode = api.agg_mode(cfg)
    n = len(params["layers"])
    rec = otrace.get_recorder()
    for i, lyr in enumerate(params["layers"]):
        with rec.span("layer", cat="model", args={"index": i}):
            msgs = engine.transform(x, lyr["w3"]["w"], lyr["w3"]["b"], jax.nn.relu)  # φ
            m = engine.aggregate(msgs, mode=mode)  # A
            x = engine.transform(x, lyr["w1"]["w"]) + engine.transform(m, lyr["w2"]["w"])
            if i < n - 1:
                x = jax.nn.relu(x)
    return x


def reference(cfg: ModelConfig, params: Dict, g: Graph, x: jnp.ndarray) -> jnp.ndarray:
    import numpy as np

    a = g.dense_adjacency()
    deg = np.maximum(a.sum(axis=1, keepdims=True), 1.0)
    a_mean = jnp.asarray(a / deg)
    n = len(params["layers"])
    for i, lyr in enumerate(params["layers"]):
        msgs = jax.nn.relu(x @ lyr["w3"]["w"] + lyr["w3"]["b"])
        m = a_mean @ msgs
        x = x @ lyr["w1"]["w"] + m @ lyr["w2"]["w"]
        if i < n - 1:
            x = jax.nn.relu(x)
    return x


api.register_arch(
    "sage",
    init=init,
    apply=apply,
    reference=reference,
    default_agg="mean",
)


# ------------------------------------------------ GraphSAGE-pool (max)
#: ``tf.nn.l2_normalize``'s floor on the squared norm, as the reference code.
L2_EPS = 1e-12


def _halves(dims, i: int) -> int:
    if dims[i + 1] % 2:
        raise ValueError(
            f"sage_pool concatenates two halves, so layer {i}'s width "
            f"{dims[i + 1]} must be even"
        )
    return dims[i + 1] // 2


def pool_init(cfg: ModelConfig, key) -> Dict:
    dims, pool = cfg.gnn_layer_dims, cfg.d_ff
    layers = []
    for i in range(len(dims) - 2):
        k1, k2, k3, key = jax.random.split(key, 4)
        half = _halves(dims, i)
        layers.append(
            {
                "pool": linear_init(k1, dims[i], pool, bias=True),
                "self": linear_init(k2, dims[i], half, bias=False),
                "neigh": linear_init(k3, pool, half, bias=False),
            }
        )
    head = linear_init(key, dims[-2], dims[-1], bias=True)
    return {"layers": layers, "head": head}


def _l2_normalize(x: jnp.ndarray) -> jnp.ndarray:
    sq = jnp.sum(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.maximum(sq, L2_EPS))


def pool_apply(
    cfg: ModelConfig, params: Dict, engine: AmpleEngine, x: jnp.ndarray
) -> jnp.ndarray:
    mode = api.agg_mode(cfg)
    n = len(params["layers"])
    rec = otrace.get_recorder()
    for i, lyr in enumerate(params["layers"]):
        with rec.span("layer", cat="model", args={"index": i}):
            p = engine.transform(x, lyr["pool"]["w"], lyr["pool"]["b"], jax.nn.relu)
            a = engine.aggregate(p, mode=mode, op="max")
            x = jnp.concatenate(
                [engine.transform(x, lyr["self"]["w"]),
                 engine.transform(a, lyr["neigh"]["w"])],
                axis=-1,
            )
            if i < n - 1:
                x = jax.nn.relu(x)
    with rec.span("layer", cat="model", args={"index": n}):
        return engine.transform(
            _l2_normalize(x), params["head"]["w"], params["head"]["b"]
        )


def pool_reference(
    cfg: ModelConfig, params: Dict, g: Graph, x: jnp.ndarray
) -> jnp.ndarray:
    import numpy as np

    adj = np.zeros((g.num_nodes, g.num_nodes), bool)  # [dst, src], structure only
    adj[np.repeat(np.arange(g.num_nodes), g.degrees), g.indices] = True
    adj = jnp.asarray(adj)
    has_nbr = adj.any(axis=1, keepdims=True)
    n = len(params["layers"])
    for i, lyr in enumerate(params["layers"]):
        p = jax.nn.relu(x @ lyr["pool"]["w"] + lyr["pool"]["b"])
        a = jnp.max(jnp.where(adj[:, :, None], p[None], -jnp.inf), axis=1)
        a = jnp.where(has_nbr, a, 0.0)
        x = jnp.concatenate([x @ lyr["self"]["w"], a @ lyr["neigh"]["w"]], axis=-1)
        if i < n - 1:
            x = jax.nn.relu(x)
    return _l2_normalize(x) @ params["head"]["w"] + params["head"]["b"]


api.register_arch(
    "sage_pool",
    init=pool_init,
    apply=pool_apply,
    reference=pool_reference,
    # coefficient 1 on every real edge: the max reads it as the lane mask
    default_agg="sum",
)
