"""GCN (Kipf & Welling) on the AMPLE engine — Eq. 2 of the paper.

    x_i' = W ( Σ_{j ∈ N(i) ∪ {i}}  e_ji / √(d̂_j d̂_i) · x_j )

Aggregation: sum with GCN normalisation coefficients (folded into the plan);
no residual; normalisation on the aggregation side (Table 3). The graph must
carry explicit self-loops so the ∪{i} term is an edge — the registry's
``needs_self_loops`` flag makes ``prepare_graph`` add them.

Entry points are uniform and config-driven (see models/gnn/api.py): layer
dims come from ``cfg.gnn_layer_dims``, the coefficient mode from
``api.agg_mode(cfg)``.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.message_passing import AmpleEngine
from repro.graphs.csr import Graph, gcn_norm_coeffs
from repro.models.gnn import api
from repro.models.gnn.layers import glorot
from repro.observe import trace as otrace

__all__ = ["init", "apply", "reference"]


def init(cfg: ModelConfig, key) -> Dict:
    """One weight per layer (Eq. 2 has no bias)."""
    dims = cfg.gnn_layer_dims
    keys = jax.random.split(key, len(dims) - 1)
    return {
        "layers": [
            {"w": glorot(k, (dims[i], dims[i + 1]))} for i, k in enumerate(keys)
        ]
    }


def apply(cfg: ModelConfig, params: Dict, engine: AmpleEngine, x: jnp.ndarray) -> jnp.ndarray:
    mode = api.agg_mode(cfg)
    n = len(params["layers"])
    rec = otrace.get_recorder()
    for i, lyr in enumerate(params["layers"]):
        with rec.span("layer", cat="model", args={"index": i}):
            m = engine.aggregate(x, mode=mode)
            x = engine.transform(
                m, lyr["w"], activation=jax.nn.relu if i < n - 1 else None
            )
    return x


def reference(cfg: ModelConfig, params: Dict, g: Graph, x: jnp.ndarray) -> jnp.ndarray:
    """Dense-adjacency float oracle (test-scale only)."""
    import numpy as np

    a = g.dense_adjacency()
    coeff = gcn_norm_coeffs(g)
    rows = np.repeat(np.arange(g.num_nodes), g.degrees)
    a_norm = np.zeros_like(a)
    a_norm[rows, g.indices] = coeff
    a_norm = jnp.asarray(a_norm)
    n = len(params["layers"])
    for i, lyr in enumerate(params["layers"]):
        x = (a_norm @ x) @ lyr["w"]
        if i < n - 1:
            x = jax.nn.relu(x)
    return x


api.register_arch(
    "gcn",
    init=init,
    apply=apply,
    reference=reference,
    default_agg="gcn",
    needs_self_loops=True,
)
