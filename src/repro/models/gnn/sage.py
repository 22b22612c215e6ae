"""GraphSAGE (Hamilton et al.) on the AMPLE engine — Eq. 4 of the paper.

    x_i' = W1 x_i + W2 · mean_{j ∈ N(i)} σ(W3 x_j + b)

φ is a dense projection applied to *all* nodes once (every node is someone's
neighbour), the mean runs through the event-driven AGE with 1/deg
coefficients, and γ adds the W1 transformation-side residual (Table 3).

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

__all__ = ["init", "apply", "reference"]


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
