"""The paper's workload plus the attention extension: GCN/GIN/GraphSAGE/GAT
inference over Table-4 graphs.

One registered config per Table-3 model — plus ``ample-gat``, the runtime-
coefficient arch the event-driven pipeline unlocks beyond the paper, and
``ample-sage-pool``, GraphSAGE's max-pooling aggregator at Reddit's widths — all
family="gnn" and dispatched through the unified model API (models/api.py ->
models/gnn/api.py). d_model carries the feature width, d_ff the hidden width
and vocab_size the class count (see launch/dryrun.py for the GNN input
specs); ``gnn_arch`` selects the registry entry, ``gnn_precision`` the
Degree-Quant policy, ``gnn_heads`` the GAT attention heads (hidden widths
must divide by it). The FULL configs are Yelp-scale (717k nodes, 300
features, 100 classes); the REDUCED ones smoke-test on CPU.
"""
import dataclasses
import functools

from repro.configs.base import ModelConfig, register

# GAT concatenates head outputs on hidden layers, so d_ff % heads == 0.
_HEADS = {"gat": 4}
_HEADS_REDUCED = {"gat": 2}


def _full(arch: str) -> ModelConfig:
    return ModelConfig(
        name=f"ample-{arch}", family="gnn", gnn_arch=arch,
        num_layers=2, d_model=300, num_heads=1, num_kv_heads=1,
        d_ff=256, vocab_size=100,  # yelp: 300 features, 100 classes
        dtype="float32",
        gnn_heads=_HEADS.get(arch, 1),
        # Continuous batching at production scale: admit up to 8 graphs per
        # micro-batch and pad the union to coarse size classes so the plan
        # and jit caches stay warm under varying request mixes.
        gnn_batch_window=8,
        gnn_union_node_bucket=1024,
        gnn_union_edge_bucket=8192,
    )


def _reduced(arch: str) -> ModelConfig:
    return ModelConfig(
        name=f"ample-{arch}", family="gnn", gnn_arch=arch, reduced=True,
        num_layers=2, d_model=32, num_heads=1, num_kv_heads=1,
        d_ff=16, vocab_size=7, dtype="float32",
        gnn_heads=_HEADS_REDUCED.get(arch, 1),
        gnn_edges_per_tile=64,
        gnn_batch_window=4,
        # buckets stay 0 here: smoke tests opt into padded size classes
        # explicitly (GNNServeEngine union_node_bucket/union_edge_bucket)
    )


for _arch in ("gcn", "gin", "sage", "gat"):
    register(
        f"ample-{_arch}",
        functools.partial(_full, _arch),
        functools.partial(_reduced, _arch),
    )


# GraphSAGE-pool at its Reddit widths (arXiv:1706.02216; the reference code's
# defaults): 602 features -> two layers of 128 + 128 concatenated -> 41
# classes, pool width 512 (``model_size="small"``) carried by d_ff.
register(
    "ample-sage-pool",
    lambda: dataclasses.replace(
        _full("sage_pool"), name="ample-sage-pool",
        d_model=602, gnn_hidden=(256, 256), d_ff=512, vocab_size=41,
    ),
    lambda: dataclasses.replace(
        _reduced("sage_pool"), name="ample-sage-pool",
        gnn_hidden=(16, 16), d_ff=24,
    ),
)
