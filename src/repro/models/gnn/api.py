"""GNN model registry — the family="gnn" half of the unified model API.

Every arch (gcn / gin / sage / gat / sage_pool) registers an ``ArchSpec``
with three uniform, config-driven entry points:

    init(cfg, key)                     -> params
    apply(cfg, params, engine, x)      -> node outputs (through AmpleEngine)
    reference(cfg, params, g, x)       -> dense float oracle (test-scale)

replacing the historical per-module ``init(key, dims)`` signatures. Layer
dims, aggregation mode and precision policy all come from ``ModelConfig``
(``gnn_layer_dims``, ``gnn_agg``, ``gnn_precision``), so ``models/api.py``
can dispatch LM and GNN configs through the same five-function surface —
the software analogue of AMPLE's single NID host interface across models.
"""
from __future__ import annotations

import dataclasses
import importlib
from typing import Callable, Dict, Optional, Tuple

import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.message_passing import AmpleEngine, EngineConfig, compile_sharded_plans
from repro.graphs.csr import Graph, add_self_loops
from repro.observe import trace as otrace

__all__ = [
    "ArchSpec",
    "register_arch",
    "get_arch",
    "list_archs",
    "agg_mode",
    "engine_config",
    "prepare_graph",
    "make_engine",
    "gnn_init",
    "gnn_apply",
    "gnn_reference",
    "gnn_forward",
]


@dataclasses.dataclass(frozen=True)
class ArchSpec:
    """A registered GNN architecture: uniform entry points + plan needs."""

    name: str
    init: Callable[[ModelConfig, object], Dict]
    apply: Callable[[ModelConfig, Dict, AmpleEngine, jnp.ndarray], jnp.ndarray]
    reference: Callable[[ModelConfig, Dict, Graph, jnp.ndarray], jnp.ndarray]
    default_agg: str  # aggregation coefficient mode when cfg.gnn_agg == ""
    needs_self_loops: bool = False  # GCN's ∪{i} term is an explicit edge


_ARCHS: Dict[str, ArchSpec] = {}

_ARCH_MODULES = ["gcn", "gin", "sage", "gat"]


def register_arch(
    name: str,
    *,
    init,
    apply,
    reference,
    default_agg: str,
    needs_self_loops: bool = False,
) -> ArchSpec:
    spec = ArchSpec(
        name=name,
        init=init,
        apply=apply,
        reference=reference,
        default_agg=default_agg,
        needs_self_loops=needs_self_loops,
    )
    _ARCHS[name] = spec
    return spec


def _ensure_loaded() -> None:
    for m in _ARCH_MODULES:
        importlib.import_module(f"repro.models.gnn.{m}")


def get_arch(name: str) -> ArchSpec:
    _ensure_loaded()
    if name not in _ARCHS:
        raise KeyError(f"unknown GNN arch {name!r}; have {sorted(_ARCHS)}")
    return _ARCHS[name]


def list_archs() -> Tuple[str, ...]:
    _ensure_loaded()
    return tuple(sorted(_ARCHS))


# ------------------------------------------------------------- config glue
def agg_mode(cfg: ModelConfig) -> str:
    """The aggregation coefficient mode this config's plans are built with."""
    return cfg.gnn_agg or get_arch(cfg.gnn_arch).default_agg


def engine_config(cfg: ModelConfig) -> EngineConfig:
    """Map the ModelConfig precision/tiling policy onto an EngineConfig."""
    if cfg.gnn_precision not in ("mixed", "float"):
        raise ValueError(f"unknown gnn_precision {cfg.gnn_precision!r}")
    return EngineConfig(
        edges_per_tile=cfg.gnn_edges_per_tile,
        mixed_precision=cfg.gnn_precision == "mixed",
        use_kernel=cfg.gnn_use_kernel,
    )


def prepare_graph(cfg: ModelConfig, g: Graph) -> Graph:
    """Arch-specific structural preprocessing (idempotent)."""
    if get_arch(cfg.gnn_arch).needs_self_loops:
        return add_self_loops(g)
    return g


def make_engine(
    cfg: ModelConfig,
    prepared: Graph,
    *,
    num_shards: Optional[int] = None,
    partition=None,
    partitioner: Optional[str] = None,
    mesh=None,
    halo_overlap: Optional[bool] = None,
) -> AmpleEngine:
    """Build the execution engine ``cfg`` calls for over a *prepared* graph.

    ``gnn_num_shards`` (or the explicit ``num_shards``/``partition``
    overrides) selects between the single-plan ``AmpleEngine`` and the
    partition-aware ``ShardedAmpleEngine`` — the arch apply functions are
    agnostic, so gcn/gin/sage thread through either unchanged.
    ``gnn_partitioner`` picks the splitting algorithm ("edges" contiguous /
    "mincut" halo-minimizing) and ``gnn_halo_overlap`` the overlapped halo
    exchange; the keyword arguments override the config fields.
    """
    shards = cfg.gnn_num_shards if num_shards is None else num_shards
    if partition is None and shards <= 1:
        return AmpleEngine(prepared, engine_config(cfg))
    from repro.distributed.graph_shard import ShardedAmpleEngine

    splan = compile_sharded_plans(
        prepared,
        engine_config(cfg),
        num_shards=None if partition is not None else shards,
        partition=partition,
        partitioner=(
            cfg.gnn_partitioner if partitioner is None else partitioner
        ) or "edges",
        modes=(agg_mode(cfg),),
    )
    return ShardedAmpleEngine(
        prepared,
        splan,
        mesh=mesh,
        halo_overlap=(
            cfg.gnn_halo_overlap if halo_overlap is None else halo_overlap
        ),
    )


# --------------------------------------------------- uniform entry points
def gnn_init(cfg: ModelConfig, key) -> Dict:
    return get_arch(cfg.gnn_arch).init(cfg, key)


def gnn_apply(cfg: ModelConfig, params: Dict, engine: AmpleEngine, x) -> jnp.ndarray:
    from repro.memory.prefetcher import StreamedFeatures

    if not isinstance(x, StreamedFeatures):  # streamed handles pass through
        x = jnp.asarray(x)
    return get_arch(cfg.gnn_arch).apply(cfg, params, engine, x)


def gnn_reference(cfg: ModelConfig, params: Dict, g: Graph, x) -> jnp.ndarray:
    """Dense-adjacency float oracle on the *prepared* graph (test-scale)."""
    return get_arch(cfg.gnn_arch).reference(
        cfg, params, prepare_graph(cfg, g), jnp.asarray(x)
    )


def gnn_forward(params: Dict, cfg: ModelConfig, batch: Dict) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """model_forward body for family="gnn".

    ``batch`` carries ``graph`` (a CSR Graph) and ``features`` f32[N, D];
    callers holding a compiled engine (the serving path) pass it as
    ``batch["engine"]`` to skip plan compilation. ``features`` may also be a
    ``memory.StreamedFeatures`` handle — the out-of-core path: the feature
    matrix stays host-resident and the engine streams it chunk-wise under
    the handle's budget. Returns ``(logits, aux)`` with logits
    f32[N, num_classes], matching the LM tuple contract so ``loss_fn``
    works unchanged for node classification.
    """
    from repro.memory.prefetcher import StreamedFeatures

    feats = batch["features"]
    if isinstance(feats, StreamedFeatures):
        x = feats
    else:
        with otrace.get_recorder().span(
            "upload", cat="model", args={"bytes": getattr(feats, "nbytes", 0)}
        ):
            x = jnp.asarray(feats)
    engine = batch.get("engine")
    n = engine.graph.num_nodes if engine is not None else batch["graph"].num_nodes
    want = cfg.gnn_layer_dims[0]
    if x.ndim != 2 or tuple(x.shape) != (n, want):
        raise ValueError(
            f"features must be [{n}, {want}] for {cfg.name} on this graph "
            f"(num_nodes={n}, cfg.d_model={want}), got {tuple(x.shape)}"
        )
    if engine is None:
        g = prepare_graph(cfg, batch["graph"])
        engine = make_engine(cfg, g)
    engine.begin_forward()
    y = gnn_apply(cfg, params, engine, x)
    return y, jnp.asarray(0.0, jnp.float32)
