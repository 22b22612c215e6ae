"""GIN (Xu et al.) on the AMPLE engine — Eq. 3 of the paper.

    x_i' = MLP( (1 + ε) · x_i  +  Σ_{j ∈ N(i)} x_j )

Aggregation: plain sum, no normalisation; residual on the aggregation side
(Table 3) — the (1+ε)x_i term. The MLP (2 layers, ReLU) is the γ transform and
runs through the engine's mixed-precision FTE one linear at a time.

Entry points are uniform and config-driven (see models/gnn/api.py).
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.message_passing import AmpleEngine
from repro.graphs.csr import Graph
from repro.memory.prefetcher import StreamedFeatures, scale_add_streamed
from repro.models.gnn import api
from repro.models.gnn.layers import mlp_init
from repro.observe import trace as otrace

__all__ = ["init", "apply", "reference"]


def init(cfg: ModelConfig, key, *, hidden_mult: int = 1, eps: float = 0.0) -> Dict:
    """One 2-layer MLP per GNN layer: [d_in -> d_out*mult -> d_out]."""
    dims = cfg.gnn_layer_dims
    keys = jax.random.split(key, len(dims) - 1)
    return {
        "eps": jnp.asarray(eps, jnp.float32),
        "layers": [
            mlp_init(k, [dims[i], dims[i + 1] * hidden_mult, dims[i + 1]])
            for i, k in enumerate(keys)
        ],
    }


def _mlp_through_engine(engine: AmpleEngine, mlp: Dict, h: jnp.ndarray) -> jnp.ndarray:
    n = len(mlp["layers"])
    for i, lyr in enumerate(mlp["layers"]):
        h = engine.transform(
            h,
            lyr["w"],
            lyr.get("b"),
            activation=jax.nn.relu if i < n - 1 else None,
        )
    return h


def apply(cfg: ModelConfig, params: Dict, engine: AmpleEngine, x: jnp.ndarray) -> jnp.ndarray:
    mode = api.agg_mode(cfg)
    n = len(params["layers"])
    rec = otrace.get_recorder()
    for i, mlp in enumerate(params["layers"]):
        with rec.span("layer", cat="model", args={"index": i}):
            m = engine.aggregate(x, mode=mode)
            if isinstance(x, StreamedFeatures):  # out-of-core first layer
                h = scale_add_streamed(x, 1.0 + params["eps"], m)
            else:
                h = (1.0 + params["eps"]) * x + m  # aggregation-side residual
            x = _mlp_through_engine(engine, mlp, h)
            if i < n - 1:
                x = jax.nn.relu(x)
    return x


def reference(cfg: ModelConfig, params: Dict, g: Graph, x: jnp.ndarray) -> jnp.ndarray:
    a = jnp.asarray(g.dense_adjacency())
    n = len(params["layers"])
    for i, mlp in enumerate(params["layers"]):
        h = (1.0 + params["eps"]) * x + a @ x
        for k, lyr in enumerate(mlp["layers"]):
            h = h @ lyr["w"] + lyr.get("b", 0.0)
            if k < len(mlp["layers"]) - 1:
                h = jax.nn.relu(h)
        x = jax.nn.relu(h) if i < n - 1 else h
    return x


api.register_arch(
    "gin",
    init=init,
    apply=apply,
    reference=reference,
    default_agg="sum",
)
