"""ShapeDtypeStruct stand-ins for every (arch × shape) dry-run cell.

No device allocation happens here: everything is a ShapeDtypeStruct, weak-type
correct and shardable, mirroring what launch/train.py / serve.py would feed at
runtime. ``[audio]``/``[vlm]`` archs receive precomputed frontend embeddings
(the modality frontend is a stub per the assignment).
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, ShapeSpec

__all__ = ["train_input_specs", "prefill_input_specs", "decode_token_specs", "gnn_input_specs"]


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def train_input_specs(cfg: ModelConfig, shape: ShapeSpec) -> Dict:
    b, s = shape.global_batch, shape.seq_len
    if cfg.family == "audio":  # enc-dec: frames in, tokens out (split budget)
        return {
            "src_embeds": _sds((b, s // 2, cfg.d_model), jnp.float32),
            "tgt_tokens": _sds((b, s // 2), jnp.int32),
            "labels": _sds((b, s // 2), jnp.int32),
        }
    if cfg.family == "vlm":  # patch+text embeddings from the stub frontend
        return {
            "embeds": _sds((b, s, cfg.d_model), jnp.float32),
            "positions": _sds((3, b, s), jnp.int32),
            "labels": _sds((b, s), jnp.int32),
        }
    return {
        "tokens": _sds((b, s), jnp.int32),
        "labels": _sds((b, s), jnp.int32),
    }


def prefill_input_specs(cfg: ModelConfig, shape: ShapeSpec) -> Dict:
    spec = train_input_specs(cfg, shape)
    spec.pop("labels", None)
    return spec


def decode_token_specs(cfg: ModelConfig, shape: ShapeSpec) -> Dict:
    b = shape.global_batch
    if cfg.family == "vlm":
        return {"embeds": _sds((b, 1, cfg.d_model), jnp.float32)}
    return {"tokens": _sds((b, 1), jnp.int32)}


def gnn_input_specs(cfg: ModelConfig, *, dataset: str = "yelp",
                    edges_per_tile: int = 256) -> Tuple[Dict, Dict]:
    """(features+plan specs, static meta) for the paper's GNN at full scale.

    Tile counts are derived from the dataset's published edge statistics —
    the ExecutionPlan arrays are inputs (built host-side), so only their
    shapes matter for lowering.
    """
    from repro.graphs.datasets import PAPER_DATASETS

    ds = PAPER_DATASETS[dataset]
    n = ds.num_nodes
    e_total = int(ds.num_nodes * ds.mean_degree)
    t = max(1, int(np.ceil(e_total / edges_per_tile * 1.02)))  # 2% split slack
    t = ((t + 511) // 512) * 512  # divisible by any dp size; pad tiles are inert
    s = edges_per_tile
    specs = {
        "x": _sds((n, cfg.d_model), jnp.float32),
        "gather_idx": _sds((t, edges_per_tile), jnp.int32),
        "coeff": _sds((t, edges_per_tile), jnp.float32),
        "seg_ids": _sds((t, edges_per_tile), jnp.int32),
        "out_node": _sds((t, s), jnp.int32),
        "row_start": _sds((t,), jnp.int32),
        "node_row": _sds((n,), jnp.int32),
        "edge_ids": _sds((t, edges_per_tile), jnp.int32),
        "w1": _sds((cfg.d_model, cfg.d_ff), jnp.float32),
        "w2": _sds((cfg.d_ff, cfg.vocab_size), jnp.float32),
    }
    meta = {"num_nodes": n, "segments_per_tile": s, "num_tiles": t}
    return specs, meta
