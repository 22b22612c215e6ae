"""GAT (Veličković et al.) on the AMPLE engine — runtime edge coefficients.

    e_ij   = LeakyReLU( a_src · (W x_j)  +  a_dst · (W x_i) )
    α_ij   = softmax_{j ∈ N(i) ∪ {i}} e_ij          (per destination segment)
    x_i'   = ‖_h  Σ_{j}  α_ij · W_h x_j             (concat heads; mean on the
                                                     output layer)

Unlike the Table-3 family, the aggregation coefficient is not a structural
constant: α depends on the node features, per layer, per request. The engine
therefore compiles plans in ``"runtime"`` mode (static coeff 1 as a pure lane
mask) and the attention vector is scattered through the plan's ``edge_ids``
indirection at request time — plans, size classes and shard caches all stay
structure-keyed, exactly as for GCN/GIN/SAGE.

The destination-segment softmax runs over the *same* event-driven tiles as
aggregation: a segment-max pass (numerically stable shift) and a segment-sum
denominator pass, both via the partial-response scatter mechanism
(``AmpleEngine.edge_softmax``). The dense projection W reuses the engine's
mixed-precision FTE, so Degree-Quant tags carry over unchanged; attention
scores and coefficients are always f32 (they are control values, not
bandwidth-bound embeddings).

Self-loops are explicit edges (∪{i} above), added by ``prepare_graph`` via the
registry's ``needs_self_loops`` flag — same mechanism as GCN.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.message_passing import AmpleEngine
from repro.graphs.csr import Graph
from repro.models.gnn import api
from repro.models.gnn.layers import glorot
from repro.observe import trace as otrace

__all__ = ["init", "apply", "reference", "LEAKY_SLOPE"]

LEAKY_SLOPE = 0.2  # the paper's LeakyReLU negative slope


def _heads(cfg: ModelConfig) -> int:
    """Every layer runs cfg.gnn_heads heads: hidden layers concatenate the
    head outputs, the output layer averages them (standard GAT practice)."""
    return max(int(cfg.gnn_heads), 1)


def _head_dim(cfg: ModelConfig, layer: int) -> int:
    dims = cfg.gnn_layer_dims
    d_out = dims[layer + 1]
    h = _heads(cfg)
    concat = layer < len(dims) - 2
    if concat:
        if d_out % h != 0:
            raise ValueError(
                f"layer {layer} output width {d_out} is not divisible by "
                f"gnn_heads={h} (hidden layers concatenate head outputs)"
            )
        return d_out // h
    return d_out  # output layer: every head spans the full width, then mean


def init(cfg: ModelConfig, key) -> Dict:
    """Per layer: one projection per head (packed [d_in, H·dh]) plus the
    split attention vectors a_src/a_dst [H, dh] (no bias, like GCN)."""
    dims = cfg.gnn_layer_dims
    layers = []
    for i in range(len(dims) - 1):
        kw, ks, kd, key = jax.random.split(key, 4)
        h = _heads(cfg)
        dh = _head_dim(cfg, i)
        layers.append(
            {
                "w": glorot(kw, (dims[i], h * dh)),
                "a_src": glorot(ks, (h, dh)),
                "a_dst": glorot(kd, (h, dh)),
            }
        )
    return {"layers": layers}


def apply(cfg: ModelConfig, params: Dict, engine: AmpleEngine, x: jnp.ndarray) -> jnp.ndarray:
    mode = api.agg_mode(cfg)
    src, dst = engine.edge_endpoints()
    n_layers = len(params["layers"])
    num_nodes = engine.graph.num_nodes
    rec = otrace.get_recorder()
    for i, lyr in enumerate(params["layers"]):
        with rec.span("layer", cat="model", args={"index": i}):
            h = _heads(cfg)
            dh = _head_dim(cfg, i)
            concat = i < n_layers - 1
            # φ: one mixed-precision FTE over all heads at once (x may be a
            # StreamedFeatures handle on the out-of-core first layer; the
            # projection output is dense either way).
            z = engine.transform(x, lyr["w"])  # [N, H*dh]
            zh = z.reshape(num_nodes, h, dh)
            src_sc = jnp.einsum("nhd,hd->nh", zh, lyr["a_src"])  # [N, H]
            dst_sc = jnp.einsum("nhd,hd->nh", zh, lyr["a_dst"])  # [N, H]
            # RAW scores [E, H] — one edge-endpoint gather per layer; LeakyReLU,
            # softmax and the weighted aggregate all run head-vectorized inside
            # the engine (one fused Pallas launch per layer under use_kernel).
            scores = src_sc[src] + dst_sc[dst]
            out = engine.attention_aggregate(
                scores, zh, mode=mode, leaky_slope=LEAKY_SLOPE
            )  # [N, H, dh]
            x = (
                out.reshape(num_nodes, h * dh)
                if concat
                else out.sum(axis=1) / float(h)
            )
            if i < n_layers - 1:
                x = jax.nn.elu(x)
    return x


def reference(cfg: ModelConfig, params: Dict, g: Graph, x: jnp.ndarray) -> jnp.ndarray:
    """Dense-adjacency float oracle: masked softmax attention (test-scale)."""
    mask = jnp.asarray(g.dense_adjacency() > 0)  # [N, N]; row i = in-nbrs of i
    n_layers = len(params["layers"])
    num_nodes = g.num_nodes
    for i, lyr in enumerate(params["layers"]):
        h = _heads(cfg)
        dh = _head_dim(cfg, i)
        concat = i < n_layers - 1
        zh = (x @ lyr["w"]).reshape(num_nodes, h, dh)
        src_sc = jnp.einsum("nhd,hd->nh", zh, lyr["a_src"])
        dst_sc = jnp.einsum("nhd,hd->nh", zh, lyr["a_dst"])
        outs = []
        for head in range(h):
            # e[i, j] = leaky(a_src·z_j + a_dst·z_i) over edges j -> i
            e = jax.nn.leaky_relu(
                src_sc[None, :, head] + dst_sc[:, None, head], LEAKY_SLOPE
            )
            e = jnp.where(mask, e, -jnp.inf)
            m = jnp.max(e, axis=1, keepdims=True)
            m = jnp.where(jnp.isfinite(m), m, 0.0)
            ex = jnp.where(mask, jnp.exp(e - m), 0.0)
            denom = ex.sum(axis=1, keepdims=True)
            alpha = ex / jnp.where(denom > 0, denom, 1.0)
            outs.append(alpha @ zh[:, head, :])
        x = (
            jnp.concatenate(outs, axis=-1)
            if concat
            else sum(outs) / float(h)
        )
        if i < n_layers - 1:
            x = jax.nn.elu(x)
    return x


api.register_arch(
    "gat",
    init=init,
    apply=apply,
    reference=reference,
    default_agg="runtime",
    needs_self_loops=True,
)
