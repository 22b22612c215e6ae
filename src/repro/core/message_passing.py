"""The AMPLE engine facade: graph in → event-driven mixed-precision layer out.

``AmpleEngine`` is the software equivalent of the accelerator's top level
(Figure 1): it owns the planner outputs (NID programming), the precision tags
(Degree-Quant), the aggregation coefficients per model (AGE configuration) and
the weight quantization cache (Weight Bank), and exposes a single
``layer(x, phi/gamma weights)`` entry point the GNN models call per layer.

Message-passing semantics follow Eq. 1:
    x_i' = γ(x_i, A_{j∈N(i)} φ(x_i, x_j, e_ij))
with φ folded into per-edge coefficients for GCN/GIN (φ = c_ij · x_j) and a
dense pre-projection for GraphSAGE (φ = σ(W3 x_j + b)).
"""
from __future__ import annotations

import dataclasses
import hashlib
from collections import OrderedDict
from typing import Callable, Dict, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import scheduler as sched
from repro.core.aggregation import (
    aggregate_edge_tiles,
    aggregate_mixed_precision,
    edge_segment_sum_tiles,
    live_rows,
    segment_max_edge_tiles,
    tile_edge_coeff,
    to_device_plan,
)
from repro.core.degree_quant import DegreeQuantConfig, inference_precision_tags
from repro.core.quantization import (
    QuantParams,
    compute_scale_zp,
    dequantize,
    quantize,
    quantize_per_channel,
)
from repro.core.transformation import (
    transform_dense,
    transform_int8,
    transform_mixed_precision,
)
from repro.graphs.csr import Graph, gcn_norm_coeffs
from repro.observe import trace as otrace
from repro.graphs.partition import (
    Partition,
    ShardSubgraph,
    make_partition,
    partition_by_edges,
    shard_subgraph,
    validate_partition,
)

# repro.memory imports repro.core (scheduler/quantization/transformation), so
# the engine pulls the streamed executors in lazily — a module-level import
# here would deadlock whichever package is imported first.


def _streamed_features_type():
    from repro.memory.prefetcher import StreamedFeatures

    return StreamedFeatures

__all__ = [
    "EngineConfig",
    "ExecutionPlan",
    "ShardPlan",
    "ShardedExecutionPlan",
    "compile_plans",
    "compile_shard_plan",
    "compile_sharded_plans",
    "assemble_union_plan",
    "shard_plan_key",
    "aggregation_coefficients",
    "engine_precision_tags",
    "AmpleEngine",
]


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    edges_per_tile: int = 256
    segments_per_tile: Optional[int] = None
    mixed_precision: bool = True
    use_kernel: bool = False  # route through Pallas kernels (interpret on CPU)
    dq: DegreeQuantConfig = dataclasses.field(default_factory=DegreeQuantConfig)


def aggregation_coefficients(g: Graph, mode: str) -> np.ndarray:
    """Per-edge coefficients folding the aggregation function into the plan.

      * "sum"     — coeff 1 (GIN)
      * "mean"    — coeff 1/deg(i) (GraphSAGE)
      * "gcn"     — coeff 1/√(d̂_i d̂_j) (GCN; self-loops must already be present)
      * "runtime" — coeff 1 as a pure lane mask: the real per-edge values
        arrive at request time (GAT attention) and are scattered through the
        plan's ``edge_ids`` indirection, multiplying the static 1s — so the
        compiled plan stays structure-keyed while coefficients change every
        request.
    """
    if mode in ("sum", "runtime"):
        return np.ones(g.num_edges, np.float32)
    if mode == "mean":
        deg = np.maximum(g.degrees, 1).astype(np.float32)
        return (1.0 / np.repeat(deg, g.degrees)).astype(np.float32)
    if mode == "gcn":
        return gcn_norm_coeffs(g)
    raise ValueError(f"unknown aggregation mode {mode!r}")


@dataclasses.dataclass(frozen=True, eq=False)
class ExecutionPlan:
    """The compiled, graph-specific half of the engine — NID programming.

    Everything the planner derives from (graph structure, EngineConfig) lives
    here: the Degree-Quant precision tags, the per-precision node groups the
    FTE partitions over, and one mixed-precision tile-plan set per aggregation
    coefficient mode. It holds no jnp state and no weight caches, so it is a
    pure host-side artifact: hashable by fingerprint, safe to share across
    engines, and the unit the serving layer caches (a plan compiled for one
    request is bitwise-valid for every later request on the same structure).
    """

    fingerprint: str
    graph_fp: str  # structure hash of the graph the plan was compiled for
    num_nodes: int
    num_edges: int
    cfg: EngineConfig
    precision_tags: np.ndarray  # str[N]
    node_groups: Mapping[str, np.ndarray]  # tag -> node ids
    mode_plans: Mapping[str, Mapping[str, sched.EdgeTilePlan]]  # mode -> tag -> plan

    def __hash__(self) -> int:
        return hash(self.fingerprint)

    def __eq__(self, other) -> bool:
        return isinstance(other, ExecutionPlan) and other.fingerprint == self.fingerprint

    @property
    def modes(self) -> Tuple[str, ...]:
        return tuple(sorted(self.mode_plans))


def engine_precision_tags(g: Graph, cfg: EngineConfig) -> np.ndarray:
    """The precision tags the planner would assign under ``cfg`` (str[N])."""
    if cfg.mixed_precision:
        return inference_precision_tags(g, cfg.dq)
    return np.full(g.num_nodes, "float", dtype=object).astype(str)


def compile_plans(
    g: Graph,
    cfg: Optional[EngineConfig] = None,
    *,
    modes: Sequence[str] = ("sum",),
    precision_tags: Optional[np.ndarray] = None,
    coeffs: Optional[Mapping[str, np.ndarray]] = None,
) -> ExecutionPlan:
    """Compile a graph into a reusable ExecutionPlan (the expensive host step).

    This is the pure planning half of what ``AmpleEngine.__init__`` + lazy
    ``plans(mode)`` used to do: Degree-Quant tagging plus one edge-tile plan
    set per requested coefficient mode. The result is immutable and keyed by
    ``fingerprint`` = hash(structure, cfg, modes) — identical fingerprints
    mean the planner would emit identical tiles.

    ``precision_tags`` overrides the Degree-Quant tagging (str[N]); the
    serving engine uses this to tag batched disjoint-union graphs per member
    graph rather than union-wide. ``coeffs`` overrides the per-edge
    aggregation coefficients per mode (f32[E] aligned with ``g.indices``);
    shard-local plans pass slices of globally computed coefficients here,
    since e.g. GCN normalisation needs the *global* degree of halo sources.
    Overridden tags/coeffs are folded into the fingerprint.
    """
    cfg = cfg if cfg is not None else EngineConfig()
    if precision_tags is None:
        tags = engine_precision_tags(g, cfg)
        tag_part = ""
    else:
        tags = np.asarray(precision_tags)
        if tags.shape != (g.num_nodes,):
            raise ValueError(
                f"precision_tags must be [{g.num_nodes}], got {tags.shape}"
            )
        tag_part = "tags:" + hashlib.blake2b(
            np.asarray(tags, dtype="U8").tobytes(), digest_size=16
        ).hexdigest()
    groups = {
        tag: np.nonzero(tags == tag)[0] for tag in np.unique(tags)
    }

    def mode_coeff(mode: str) -> np.ndarray:
        if coeffs is not None and mode in coeffs:
            c = np.asarray(coeffs[mode], np.float32)
            if c.shape != (g.num_edges,):
                raise ValueError(f"coeffs[{mode!r}] must be [{g.num_edges}], got {c.shape}")
            return c
        return aggregation_coefficients(g, mode)

    mode_plans = {
        mode: sched.build_mixed_precision_plans(
            g,
            tags,
            edges_per_tile=cfg.edges_per_tile,
            segments_per_tile=cfg.segments_per_tile,
            coeff=mode_coeff(mode),
        )
        for mode in dict.fromkeys(modes)  # dedupe, keep order
    }
    coeff_part = ""
    if coeffs is not None:
        h = hashlib.blake2b(digest_size=16)
        for mode in sorted(set(coeffs) & set(dict.fromkeys(modes))):
            h.update(mode.encode())
            h.update(np.ascontiguousarray(coeffs[mode], np.float32).tobytes())
        coeff_part = "coeffs:" + h.hexdigest()
    graph_fp = sched.graph_fingerprint(g)
    fp = sched.plan_fingerprint(
        g,
        repr(cfg),
        *sorted(dict.fromkeys(modes)),
        *((tag_part,) if tag_part else ()),
        *((coeff_part,) if coeff_part else ()),
    )
    return ExecutionPlan(
        fingerprint=fp,
        graph_fp=graph_fp,
        num_nodes=g.num_nodes,
        num_edges=g.num_edges,
        cfg=cfg,
        precision_tags=tags,
        node_groups=groups,
        mode_plans=mode_plans,
    )


def assemble_union_plan(
    member_plans: Sequence[ExecutionPlan],
    union: Graph,
    *,
    cfg: Optional[EngineConfig] = None,
    edge_bucket: int = 0,
) -> ExecutionPlan:
    """Compose per-member ExecutionPlans into one padded disjoint-union plan.

    The incremental counterpart of ``compile_plans``: each member graph was
    planned once (Degree-Quant tags + edge tiles, both exactly as if served
    solo) and the union plan is assembled by index relabelling
    (``scheduler.concat_tile_plans``) — O(E) array copies, no planner. The
    admission loop of the continuous-batching engine leans on this: a new
    batch composition over known member structures costs assembly, not
    planning.

    ``union`` is the (possibly node-padded) disjoint union of the members'
    *prepared* graphs, in member order; padding nodes beyond the members are
    isolated, carry no plan tiles, and are excluded from the transform node
    groups, so their rows stay exactly zero through every layer — batch-wide
    int8 activation scales never see them. ``edge_bucket`` rounds each
    per-(mode, tag) tile stack up to the size-class tile count so device
    shapes recur across member mixes.
    """
    if not member_plans:
        raise ValueError("assemble_union_plan of no member plans")
    cfg = cfg if cfg is not None else member_plans[0].cfg
    for p in member_plans:
        if p.cfg != cfg:
            raise ValueError("member plans were compiled under a different EngineConfig")
    modes = member_plans[0].modes
    for p in member_plans[1:]:
        if p.modes != modes:
            raise ValueError("member plans disagree on aggregation modes")
    offsets = np.cumsum([0] + [p.num_nodes for p in member_plans])
    edge_offsets = np.cumsum([0] + [p.num_edges for p in member_plans])
    n_real = int(offsets[-1])
    if n_real > union.num_nodes:
        raise ValueError(
            f"member plans cover {n_real} nodes but union has {union.num_nodes}"
        )
    n_pad = union.num_nodes - n_real

    tags = np.concatenate(
        [np.asarray(p.precision_tags, dtype="U8") for p in member_plans]
        + ([np.full(n_pad, "pad", dtype="U8")] if n_pad else [])
    )
    # Padding nodes belong to no precision group: the FTE streams skip their
    # rows (they stay 0), so batch-wide activation calibration matches the
    # unpadded union's exactly.
    groups = {
        tag: np.nonzero(tags == tag)[0]
        for tag in np.unique(tags)
        if tag != "pad"
    }

    mode_plans: Dict[str, Dict[str, sched.EdgeTilePlan]] = {}
    for mode in modes:
        per_tag: Dict[str, sched.EdgeTilePlan] = {}
        tag_names = sorted(
            {t for p in member_plans for t in p.mode_plans[mode]}
        )
        for tag in tag_names:
            pieces = [
                (p.mode_plans[mode][tag], offsets[i], edge_offsets[i])
                for i, p in enumerate(member_plans)
                if tag in p.mode_plans[mode]
            ]
            min_tiles = 0
            if edge_bucket > 0:
                ept = pieces[0][0].edges_per_tile
                real = sum(pl.total_edges for pl, _, _ in pieces)
                _, e_class = sched.size_class(0, real, 0, edge_bucket)
                min_tiles = -(-e_class // ept)
            per_tag[tag] = sched.concat_tile_plans(
                [pl for pl, _, _ in pieces],
                [off for _, off, _ in pieces],
                num_nodes=union.num_nodes,
                min_tiles=min_tiles,
                # Member edges occupy contiguous slices of the union's edge
                # array (members precede padding self-edges), so the member
                # graphs' cumulative edge counts relabel edge_ids into union
                # edge space — a request-time coefficient vector over the
                # union then scatters correctly through the assembled plan.
                edge_offsets=[eoff for _, _, eoff in pieces],
            )
        mode_plans[mode] = per_tag

    graph_fp = sched.graph_fingerprint(union)
    h = hashlib.blake2b(digest_size=16)
    h.update(graph_fp.encode())
    h.update(f"\x00assembled:{edge_bucket}".encode())
    for p in member_plans:
        h.update(b"\x00")
        h.update(p.fingerprint.encode())
    return ExecutionPlan(
        fingerprint=h.hexdigest(),
        graph_fp=graph_fp,
        num_nodes=union.num_nodes,
        num_edges=union.num_edges,
        cfg=cfg,
        precision_tags=tags,
        node_groups=groups,
        mode_plans=mode_plans,
    )


# ---------------------------------------------------------------------------
# Partition-aware planning: one ExecutionPlan per edge-balanced shard
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True, eq=False)
class ShardPlan:
    """One shard's compiled slice of a ``ShardedExecutionPlan``.

    ``plan`` is a full ExecutionPlan over the shard's *local* subgraph
    (owned rows first, halo sources appended — see
    ``graphs.partition.shard_subgraph``), so every property of the single-graph
    plan (hashability, persistence, bitwise-valid reuse) holds per shard.
    ``fingerprint`` is the global identity — hash(structure, partition
    boundaries, shard index, planner config) via
    ``scheduler.shard_plan_fingerprint`` — and is what the serving layer keys
    its per-shard LRU on.
    """

    fingerprint: str
    shard: ShardSubgraph
    plan: ExecutionPlan  # over shard.graph, in local index space

    def __hash__(self) -> int:
        return hash(self.fingerprint)

    def __eq__(self, other) -> bool:
        return isinstance(other, ShardPlan) and other.fingerprint == self.fingerprint

    @property
    def num_owned(self) -> int:
        return self.shard.num_owned

    @property
    def halo_size(self) -> int:
        return int(self.shard.halo.size)

    @property
    def num_edges(self) -> int:
        return self.shard.num_edges


@dataclasses.dataclass(frozen=True, eq=False)
class ShardedExecutionPlan:
    """A partitioned graph's execution plan: one ShardPlan per shard.

    The distributed analogue of ``ExecutionPlan``: Degree-Quant tags are
    computed once on the global graph (a node's precision must not depend on
    which shard owns it), aggregation coefficients likewise (halo sources need
    their global degree), and each shard gets its own edge-tile plan over its
    local subgraph plus a precomputed halo gather map. Pure host-side and
    hashable by fingerprint, so the serving layer caches it — and each member
    ShardPlan independently — exactly like the single-graph plan.
    """

    fingerprint: str
    graph_fp: str
    partition_fp: str
    partition: Partition
    num_nodes: int
    num_edges: int
    cfg: EngineConfig
    precision_tags: np.ndarray  # str[N] — global tags
    node_groups: Mapping[str, np.ndarray]  # tag -> global node ids
    shards: Tuple[ShardPlan, ...]

    def __hash__(self) -> int:
        return hash(self.fingerprint)

    def __eq__(self, other) -> bool:
        return (
            isinstance(other, ShardedExecutionPlan)
            and other.fingerprint == self.fingerprint
        )

    @property
    def num_shards(self) -> int:
        return len(self.shards)

    @property
    def modes(self) -> Tuple[str, ...]:
        return self.shards[0].plan.modes if self.shards else ()

    @property
    def halo_total(self) -> int:
        """Rows crossing the cut per layer — the halo-exchange volume metric."""
        return sum(s.halo_size for s in self.shards)

    @property
    def edge_balance(self) -> float:
        """max shard edges / ideal edges-per-shard (1.0 = perfectly balanced)."""
        if not self.shards or self.num_edges == 0:
            return 1.0
        ideal = self.num_edges / self.num_shards
        return max(s.num_edges for s in self.shards) / ideal


def shard_plan_key(
    g: Graph,
    part: Partition,
    k: int,
    cfg: EngineConfig,
    *,
    modes: Sequence[str],
    precision_tags: np.ndarray,
) -> str:
    """The fingerprint ``compile_shard_plan`` would stamp on shard ``k``.

    Separated out so a serving cache can probe its per-shard LRU *before*
    deciding which shards actually need the planner.
    """
    tag_part = "tags:" + hashlib.blake2b(
        np.asarray(precision_tags, dtype="U8").tobytes(), digest_size=16
    ).hexdigest()
    return sched.shard_plan_fingerprint(
        g,
        part,
        k,
        repr(cfg),
        *sorted(dict.fromkeys(modes)),
        tag_part,
    )


def compile_shard_plan(
    g: Graph,
    part: Partition,
    k: int,
    cfg: Optional[EngineConfig] = None,
    *,
    modes: Sequence[str] = ("sum",),
    precision_tags: Optional[np.ndarray] = None,
    mode_coeffs: Optional[Mapping[str, np.ndarray]] = None,
) -> ShardPlan:
    """Compile shard ``k`` of a partitioned graph independently.

    ``precision_tags``/``mode_coeffs`` are *global* (length N / E); pass them
    when compiling several shards so tagging and coefficient work runs once —
    omitted, they are derived here (correct, just repeated per shard).
    The returned ShardPlan is exactly what ``compile_sharded_plans`` would
    have produced for this shard, so a serving cache can mix shards compiled
    together and separately.
    """
    cfg = cfg if cfg is not None else EngineConfig()
    if precision_tags is None:
        precision_tags = engine_precision_tags(g, cfg)
    tags = np.asarray(precision_tags)
    if tags.shape != (g.num_nodes,):
        raise ValueError(f"precision_tags must be [{g.num_nodes}], got {tags.shape}")
    if mode_coeffs is None:
        mode_coeffs = {m: aggregation_coefficients(g, m) for m in dict.fromkeys(modes)}
    sub = shard_subgraph(g, part, k)
    local_coeffs = {
        m: sub.slice_edges(np.asarray(c)) for m, c in mode_coeffs.items()
    }
    local_tags = tags[sub.local_ids]
    plan = compile_plans(
        sub.graph,
        cfg,
        modes=modes,
        precision_tags=local_tags,
        coeffs=local_coeffs,
    )
    fp = shard_plan_key(g, part, k, cfg, modes=modes, precision_tags=tags)
    return ShardPlan(fingerprint=fp, shard=sub, plan=plan)


def compile_sharded_plans(
    g: Graph,
    cfg: Optional[EngineConfig] = None,
    *,
    num_shards: Optional[int] = None,
    partition: Optional[Partition] = None,
    partitioner: str = "edges",
    modes: Sequence[str] = ("sum",),
    precision_tags: Optional[np.ndarray] = None,
    shard_plans: Optional[Mapping[int, ShardPlan]] = None,
) -> ShardedExecutionPlan:
    """Partition-aware planning pipeline: Partition in, sharded plan out.

    Give either an explicit ``partition`` (validated against ``g``) or
    ``num_shards`` — then ``partitioner`` selects the algorithm ("edges" =
    contiguous edge-balanced cut, "mincut" = halo-minimizing multilevel
    refinement; see ``graphs.partition.make_partition``). The partitioner
    identity is folded into ``partition_fp`` so plans never collide across
    partitioners. Degree-Quant tags and per-mode coefficients are computed
    once globally, then each shard is compiled over its local subgraph.
    ``shard_plans`` supplies already-compiled shards by index (the serving
    layer's per-shard cache hits); only missing shards run the planner.
    """
    cfg = cfg if cfg is not None else EngineConfig()
    if partition is None:
        if num_shards is None:
            raise ValueError("pass either partition or num_shards")
        partition = make_partition(g, num_shards, partitioner)
    else:
        validate_partition(g, partition)
        if num_shards is not None and partition.num_shards != num_shards:
            raise ValueError(
                f"partition has {partition.num_shards} shards, asked for {num_shards}"
            )
    if precision_tags is None:
        tags = engine_precision_tags(g, cfg)
    else:
        tags = np.asarray(precision_tags)
        if tags.shape != (g.num_nodes,):
            raise ValueError(f"precision_tags must be [{g.num_nodes}], got {tags.shape}")
    shard_plans = shard_plans or {}
    mode_coeffs = None
    if any(k not in shard_plans for k in range(partition.num_shards)):
        # Global per-edge coefficient work runs once, and only when some
        # shard actually needs the planner (all-warm assembly skips it).
        mode_coeffs = {m: aggregation_coefficients(g, m) for m in dict.fromkeys(modes)}
    shards = tuple(
        shard_plans[k]
        if k in shard_plans
        else compile_shard_plan(
            g,
            partition,
            k,
            cfg,
            modes=modes,
            precision_tags=tags,
            mode_coeffs=mode_coeffs,
        )
        for k in range(partition.num_shards)
    )
    groups = {tag: np.nonzero(tags == tag)[0] for tag in np.unique(tags)}
    partition_fp = sched.partition_fingerprint(g, partition)
    h = hashlib.blake2b(digest_size=16)
    h.update(partition_fp.encode())
    for s in shards:
        h.update(b"\x00")
        h.update(s.fingerprint.encode())
    return ShardedExecutionPlan(
        fingerprint=h.hexdigest(),
        graph_fp=sched.graph_fingerprint(g),
        partition_fp=partition_fp,
        partition=partition,
        num_nodes=g.num_nodes,
        num_edges=g.num_edges,
        cfg=cfg,
        precision_tags=tags,
        node_groups=groups,
        shards=shards,
    )


class AmpleEngine:
    """Thin per-graph execution wrapper around an ``ExecutionPlan``.

    The engine owns only transient device-facing state (the weight-quant
    cache); all planning lives in the plan. Construct either way:

      * ``AmpleEngine(g, cfg)`` — compiles tags up front, tile plans lazily
        per aggregation mode (the historical behaviour), or
      * ``AmpleEngine(g, plan=plan)`` — reuses a cached ``compile_plans``
        artifact and skips the planner entirely.
    """

    def __init__(
        self,
        g: Graph,
        cfg: Optional[EngineConfig] = None,
        *,
        plan: Optional[ExecutionPlan] = None,
    ):
        if plan is not None:
            if plan.graph_fp != sched.graph_fingerprint(g):
                raise ValueError(
                    f"plan was compiled for a different graph structure "
                    f"({plan.num_nodes} nodes, {plan.num_edges} edges vs "
                    f"{g.num_nodes}, {g.num_edges}; fingerprints differ)"
                )
            if cfg is not None and cfg != plan.cfg:
                raise ValueError("cfg disagrees with plan.cfg; pass one or the other")
            cfg = plan.cfg
        else:
            cfg = cfg if cfg is not None else EngineConfig()
            plan = compile_plans(g, cfg, modes=())
        self.graph = g
        self.cfg = cfg
        self.plan = plan
        self.precision_tags = plan.precision_tags
        self.node_groups: Dict[str, np.ndarray] = dict(plan.node_groups)
        self._plans: Dict[str, Mapping[str, sched.EdgeTilePlan]] = dict(plan.mode_plans)
        self._init_runtime_state()

    _WQ_CACHE_CAP = 64  # weights per engine; LRU-evicted beyond this

    def _init_runtime_state(self) -> None:
        """Transient device-facing caches — shared with ShardedAmpleEngine."""
        # id(w) -> (w, w_q, qp). The weight itself is held alongside its
        # quantized copy: a cache keyed on id() alone is unsound once the
        # original is garbage collected (CPython recycles ids), so the strong
        # ref both pins the id and lets us verify the hit is really for w.
        # Bounded LRU: a loop feeding ever-fresh weight arrays (training)
        # must not grow engine memory without limit.
        self._wq_cache: "OrderedDict[int, tuple]" = OrderedDict()
        # Static per-plan quantization state (serving): to_device_plan uploads
        # and activation scale/zero-points are calibrated once per (plan,
        # call-site) and reused on warm requests — see begin_forward().
        self._dplan_cache: Dict[str, Dict] = {}
        # mode -> the ``age`` span's args, static per cached upload: live
        # accumulator rows and tile windows (T·S), summed over the plans.
        self._age_rows: Dict[str, Dict[str, int]] = {}
        self._empty_row_count: Optional[int] = None  # see _empty_rows
        self._act_qp: Dict[tuple, QuantParams] = {}
        self._forward_active = False
        self._agg_slot = 0
        self._fte_slot = 0
        # (plan, schedule) pairs for the out-of-core path, keyed on
        # (mode, tag, chunk_rows, reorder, packing) — per-plan-static like
        # dplans. The plan entry is the one the stream executes: the packed
        # variant when packing is on, the compiled plan otherwise.
        self._chunk_schedules: Dict[tuple, tuple] = {}
        # Device copies of per-tile plan arrays for the streamed executor,
        # keyed like _chunk_schedules: a warm streamed request re-uploads
        # zero plan bytes (the instruction stream is plan-static).
        self._stream_tiles: Dict[tuple, object] = {}
        # (src, dst) node ids per edge — structural, cached for edge_softmax.
        self._edge_endpoints: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None
        # Modes whose plans were verified to carry live edge ids (the check
        # scans the tile arrays once; results are plan-static).
        self._eids_checked: set = set()

    # ------------------------------------------------- static quant state
    def begin_forward(self) -> None:
        """Mark the start of one model forward pass over this engine.

        Activation quantization parameters (int8 scale/zero-point for the AGE
        gather stream and the FTE int8 matmul) are keyed by call-site slot
        within a forward: the first forward calibrates them from its
        activations and later forwards reuse that static state — warm plan-
        cache hits skip ``compute_scale_zp`` entirely, and repeat requests
        with identical features are bitwise-identical to the cold request.
        Callers that never invoke this (direct engine use) keep the historical
        per-call dynamic calibration.
        """
        self._forward_active = True
        self._agg_slot = 0
        self._fte_slot = 0

    def _activation_qp(
        self,
        values_fn: Optional[Callable[[], jnp.ndarray]],
        kind: str,
        *,
        make_qp: Optional[Callable[[], QuantParams]] = None,
    ) -> QuantParams:
        """Scale/zp for one quantized call site (lazy: warm slots skip the calc).

        ``make_qp`` overrides the cold calibration source — the streamed
        paths pass a host-side factory (bitwise-equal to the device
        reduction) so the SAME slot protocol serves dense and streamed
        forwards; a warm slot cached by either path feeds both.
        """
        calibrate = (
            make_qp
            if make_qp is not None
            else lambda: compute_scale_zp(values_fn(), symmetric=True)
        )
        if not self._forward_active:
            return calibrate()
        if kind == "agg":
            slot = ("agg", self._agg_slot)
            self._agg_slot += 1
        else:
            slot = ("fte", self._fte_slot)
            self._fte_slot += 1
        if slot not in self._act_qp:
            qp = calibrate()
            if isinstance(qp.scale, jax.core.Tracer):
                # Under jit/grad tracing (training) the calibration is part of
                # the traced computation — caching it would leak tracers, so
                # stay dynamic and leave the slot empty for eager serving.
                return qp
            self._act_qp[slot] = qp
        return self._act_qp[slot]

    def _device_plans(
        self,
        mode: str,
        plans: Mapping[str, sched.EdgeTilePlan],
        *,
        edge_ids: bool = False,
    ) -> Dict:
        """Cached device uploads of one mode's tile plans.

        ``edge_ids`` uploads the runtime-coefficient indirection map too —
        it is as large as ``gather_idx`` and static-coeff modes never read
        it, so it rides along only on first runtime-coefficient use (a
        cached entry without it is upgraded in place).
        """
        cached = self._dplan_cache.get(mode)
        if cached is not None and (
            not edge_ids
            or all(d.edge_ids is not None for d in cached.values())
        ):
            return cached
        dplans = {
            tag: to_device_plan(p, with_edge_ids=edge_ids)
            for tag, p in plans.items()
        }
        # Inside jit/grad tracing, array creation is staged into the trace
        # (DynamicJaxprTracer constants) — caching those would leak tracers
        # into later eager calls, so only concrete uploads are kept.
        if not any(
            isinstance(d.gather_idx, jax.core.Tracer) for d in dplans.values()
        ):
            self._dplan_cache[mode] = dplans
            self._age_rows[mode] = {
                "rows": sum(live_rows(p) for p in plans.values()),
                "window_rows": sum(
                    p.num_tiles * p.segments_per_tile for p in plans.values()
                ),
            }
        return dplans

    def _require_edge_ids(self, mode: str, plans: Mapping[str, sched.EdgeTilePlan]) -> None:
        """Refuse runtime coefficients on plans without live edge ids.

        Plans persisted before the indirection existed load with every lane
        at -1 (structurally valid, statically servable); scattering through
        them would silently zero every coefficient — fail loudly instead.
        """
        if mode in self._eids_checked:
            return
        for tag, p in plans.items():
            # Every real edge must own exactly one live lane — a partial
            # count means some member of an assembled union was loaded from
            # a pre-indirection file (its lanes sit at -1) and would be
            # silently zeroed by the scatter.
            if int((p.edge_ids >= 0).sum()) != p.total_edges:
                raise ValueError(
                    f"plan for mode {mode!r} tag {tag!r} carries edge-id "
                    "indirection for only part of its edges (a member "
                    "persisted before runtime coefficients?); recompile the "
                    "plan to use edge_coeff / edge_softmax"
                )
        self._eids_checked.add(mode)

    # ---------------------------------------------------------------- plans
    def plans(self, mode: str) -> Mapping[str, sched.EdgeTilePlan]:
        if mode not in self._plans:  # lazy extension beyond the compiled modes
            self._plans[mode] = sched.build_mixed_precision_plans(
                self.graph,
                self.precision_tags,
                edges_per_tile=self.cfg.edges_per_tile,
                segments_per_tile=self.cfg.segments_per_tile,
                coeff=aggregation_coefficients(self.graph, mode),
            )
        return self._plans[mode]

    # ------------------------------------------------- out-of-core streaming
    def _stream_plan_schedule(self, mode: str, tag: str, sf):
        """(plan, schedule) the streamed path executes (per-plan-static).

        ``sf.packing`` swaps in the chunk-packed variant of the compiled
        plan (``scheduler.pack_tiles_by_chunk``, bitwise-equal outputs) with
        plan-order execution — packing already emitted tiles in chunk order,
        so the run-reordering pass has nothing left to sort. Unpacked plans
        keep the ``sf.reorder`` run permutation.
        """
        key = (mode, tag, sf.store.chunk_rows, sf.reorder, sf.packing)
        if key not in self._chunk_schedules:
            plan = self.plans(mode)[tag]
            if sf.packing:
                plan = sched.pack_tiles_by_chunk(plan, sf.store.chunk_rows)
                schedule = sched.build_chunk_schedule(
                    plan, sf.store.chunk_rows, reorder=False
                )
            else:
                schedule = sched.build_chunk_schedule(
                    plan, sf.store.chunk_rows, reorder=sf.reorder
                )
            self._chunk_schedules[key] = (plan, schedule)
        return self._chunk_schedules[key]

    def _chunk_schedule(self, mode: str, tag: str, sf):
        """Schedule cache for the streamed path (per-plan-static artifact)."""
        return self._stream_plan_schedule(mode, tag, sf)[1]

    def _stream_tiles_for(self, mode: str, tag: str, sf):
        """Device copies of one plan's per-tile arrays (plan-static).

        Built (and charged to ``instr_bytes``) once per (mode, tag, chunking)
        — warm streamed requests re-upload zero plan bytes; only feature
        chunks move.
        """
        from repro.memory.prefetcher import make_device_tile_stream

        key = (mode, tag, sf.store.chunk_rows, sf.reorder, sf.packing)
        if key not in self._stream_tiles:
            plan, schedule = self._stream_plan_schedule(mode, tag, sf)
            ts = make_device_tile_stream(plan, schedule)
            self._stream_tiles[key] = ts
            sf.stats.instr_bytes += ts.nbytes  # the cold upload, charged once
        return self._stream_tiles[key]

    def _aggregate_streamed(self, sf, mode: str) -> jnp.ndarray:
        from repro.memory.prefetcher import aggregate_streamed

        if sf.store.num_rows != self.graph.num_nodes:
            raise ValueError(
                f"feature store has {sf.store.num_rows} rows but graph has "
                f"{self.graph.num_nodes} nodes"
            )
        pairs = {
            tag: self._stream_plan_schedule(mode, tag, sf)
            for tag in self.plans(mode)
        }
        plans = {tag: p for tag, (p, _) in pairs.items()}
        schedules = {tag: s for tag, (_, s) in pairs.items()}
        tiles = {tag: self._stream_tiles_for(mode, tag, sf) for tag in plans}
        qp = None
        if self.cfg.mixed_precision and "int8" in plans:
            qp = self._activation_qp(None, "agg", make_qp=sf.agg_qp)
        return aggregate_streamed(
            sf,
            plans,
            schedules,
            num_nodes=self.graph.num_nodes,
            mixed=self.cfg.mixed_precision,
            qp=qp,
            tiles=tiles,
        )

    def _transform_streamed(
        self,
        sf,
        w: jnp.ndarray,
        b: Optional[jnp.ndarray],
        activation: Optional[Callable[[jnp.ndarray], jnp.ndarray]],
    ) -> jnp.ndarray:
        from repro.memory.prefetcher import _host_fte_qp, transform_streamed

        if sf.store.num_rows != self.graph.num_nodes:
            raise ValueError(
                f"feature store has {sf.store.num_rows} rows but graph has "
                f"{self.graph.num_nodes} nodes"
            )
        if not self.cfg.mixed_precision:
            # A float-policy FTE over the full matrix cannot be row-blocked
            # bitwise-identically (f32 matmul blocking reassociates), so the
            # store is materialized — loud in telemetry, never silent.
            sf.stats.fallbacks += 1
            sf.stats.fallback_bytes += sf.nbytes
            return transform_dense(jnp.asarray(sf.store.dense()), w, b, activation)
        w_q, w_qp, _ = self._weight_q(w)
        a_qp = None
        ids = self.node_groups.get("int8")
        if self._forward_active and ids is not None and ids.size:
            a_qp = self._activation_qp(
                None, "fte", make_qp=lambda: _host_fte_qp(sf.store.amax_rows(ids))
            )
        return transform_streamed(
            sf, self.node_groups, w, b, activation,
            w_q=w_q, w_qp=w_qp, a_qp=a_qp,
        )

    # ----------------------------------------------------------------- AGE
    def aggregate(
        self,
        x: jnp.ndarray,
        *,
        mode: str = "sum",
        edge_coeff: Optional[jnp.ndarray] = None,
        op: str = "sum",
    ) -> jnp.ndarray:
        """Event-driven mixed-precision aggregation of node embeddings.

        ``op="max"`` takes the feature-wise max over each node's in-
        neighbours (GraphSAGE-pool) through the same tiles, uploaded once
        for ``mode``: the coefficients serve as the lane mask only. A node
        with no in-edge reads 0. Dense embeddings on the jnp path only: the
        streamed executor, the Pallas kernel (``use_kernel``) and the
        sharded engine sum, and refuse it with ``ValueError``.

        ``x`` may be a ``memory.StreamedFeatures`` handle instead of a dense
        matrix: aggregation then runs chunk-streamed through the prefetcher
        under its feature budget, bitwise-identical to the dense path.

        ``edge_coeff`` is a runtime per-edge coefficient vector (f32[E] in
        this graph's edge space), scattered into tile layout through the
        plan's ``edge_ids`` map and multiplied with the static coefficients
        — the GAT attention path. The plan itself stays structure-keyed, so
        serving caches are untouched by per-request coefficient changes.

        Multi-head: ``edge_coeff`` f32[E, H] with ``x`` f32[N, H, dh]
        aggregates all heads in one tile scan (each head's column bitwise-
        equal to its solo 1-D run on the jnp path).

        Recorded as one ``age`` span with args ``mode`` and ``op``, the
        plans' ``rows`` and ``window_rows`` once they are uploaded and, for
        a max, ``empty_rows`` (real nodes with no in-edge, which read 0);
        engines override ``_aggregate``.
        """
        rec = otrace.get_recorder()
        with rec.span("age", cat="engine", args={"mode": mode, "op": op}) as span:
            out = self._aggregate(x, mode=mode, edge_coeff=edge_coeff, op=op)
            span.set(**self._age_rows.get(mode, {}))
            if op == "max":
                span.set(empty_rows=self._empty_rows())
        return out

    def _empty_rows(self) -> int:
        """Real nodes (those of a precision group, so not size-class
        padding) with no in-edge; static per engine, counted once."""
        if self._empty_row_count is None:
            real = np.concatenate(
                [np.zeros(0, np.int64)]
                + [np.asarray(ids, np.int64) for ids in self.node_groups.values()]
            )
            self._empty_row_count = int((self.graph.degrees[real] == 0).sum())
        return self._empty_row_count

    def _aggregate(
        self,
        x: jnp.ndarray,
        *,
        mode: str = "sum",
        edge_coeff: Optional[jnp.ndarray] = None,
        op: str = "sum",
    ) -> jnp.ndarray:
        if isinstance(x, _streamed_features_type()):
            if op != "sum":
                raise ValueError(
                    f"op={op!r} needs dense embeddings: the streamed AGE "
                    "(feature budget) only sums; lift the feature budget"
                )
            if edge_coeff is not None:
                raise ValueError(
                    "runtime edge coefficients require dense embeddings; the "
                    "streamed aggregation path serves static-coefficient "
                    "plans only (attention models stream through transform())"
                )
            return self._aggregate_streamed(x, mode)
        plans = self.plans(mode)
        if edge_coeff is not None:
            edge_coeff = jnp.asarray(edge_coeff, jnp.float32)
            e = self.graph.num_edges
            if not (
                edge_coeff.shape == (e,)
                or (edge_coeff.ndim == 2 and edge_coeff.shape[0] == e)
            ):
                raise ValueError(
                    f"edge_coeff must be [{e}] or [{e}, H], got "
                    f"{tuple(edge_coeff.shape)}"
                )
            if edge_coeff.ndim == 2 and (
                x.ndim != 3 or x.shape[1] != edge_coeff.shape[1]
            ):
                raise ValueError(
                    f"multi-head edge_coeff {tuple(edge_coeff.shape)} needs "
                    f"x shaped [N, {edge_coeff.shape[1]}, dh], got "
                    f"{tuple(x.shape)}"
                )
            self._require_edge_ids(mode, plans)
        dplans = self._device_plans(mode, plans, edge_ids=edge_coeff is not None)
        if self.cfg.mixed_precision:
            qp = self._activation_qp(lambda: x, "agg") if "int8" in plans else None
            return aggregate_mixed_precision(
                x,
                plans,
                num_nodes=self.graph.num_nodes,
                use_kernel=self.cfg.use_kernel,
                qp=qp,
                device_plans=dplans,
                edge_coeff=edge_coeff,
                op=op,
            )
        p = plans["float"]
        return aggregate_edge_tiles(
            x,
            dplans["float"],
            num_nodes=self.graph.num_nodes,
            segments_per_tile=p.segments_per_tile,
            use_kernel=self.cfg.use_kernel,
            edge_coeff=edge_coeff,
            op=op,
        )

    # ------------------------------------------------ runtime coefficients
    def edge_endpoints(self) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """(src, dst) node id per edge, int32[E] each — cached structural
        arrays (dst follows from the CSR row layout)."""
        if self._edge_endpoints is None:
            g = self.graph
            dst = np.repeat(np.arange(g.num_nodes, dtype=np.int64), g.degrees)
            self._edge_endpoints = (
                jnp.asarray(g.indices, jnp.int32),
                jnp.asarray(dst, jnp.int32),
            )
        return self._edge_endpoints

    def edge_softmax(
        self, scores: jnp.ndarray, *, mode: str = "runtime"
    ) -> jnp.ndarray:
        """Destination-segment softmax of per-edge scores: f32[E(, H)].

        Runs over the same event-driven tiles as aggregation (per precision
        group, covering disjoint destination sets): a segment-max pass
        scatter-maxes tile partials into per-node maxima (the numerically
        stable shift), scores are exp-shifted in edge space, and a
        segment-sum pass accumulates the denominators through the same
        partial-response scatter-add. Nodes with no in-edges in the plan
        (size-class padding nodes) get max 0 / denominator 1, so the result
        is finite everywhere.

        ``scores`` may be f32[E, H]: every head shares one pair of tile
        scans and ONE destination-endpoint gather (``node_max[dst]`` /
        ``denom[dst]`` broadcast over the head axis), where the per-head
        loop paid both H times. Each head's column is bitwise-equal to its
        solo 1-D call.
        """
        scores = jnp.asarray(scores, jnp.float32)
        e = self.graph.num_edges
        if not (
            scores.shape == (e,)
            or (scores.ndim == 2 and scores.shape[0] == e)
        ):
            raise ValueError(
                f"scores must be [{e}] or [{e}, H], got "
                f"{tuple(scores.shape)}"
            )
        plans = self.plans(mode)
        self._require_edge_ids(mode, plans)
        dplans = self._device_plans(mode, plans, edge_ids=True)
        n = self.graph.num_nodes
        node_max = jnp.full((n,) + scores.shape[1:], -jnp.inf, jnp.float32)
        for tag, p in plans.items():
            node_max = jnp.maximum(
                node_max,
                segment_max_edge_tiles(
                    scores,
                    dplans[tag],
                    num_nodes=n,
                    segments_per_tile=p.segments_per_tile,
                ),
            )
        node_max = jnp.where(jnp.isfinite(node_max), node_max, 0.0)
        _, dst = self.edge_endpoints()
        # One structural gather per pass, shared by all heads.
        ex = jnp.exp(scores - node_max[dst])
        denom = jnp.zeros((n,) + scores.shape[1:], jnp.float32)
        for tag, p in plans.items():
            denom = denom + edge_segment_sum_tiles(
                ex,
                dplans[tag],
                num_nodes=n,
                segments_per_tile=p.segments_per_tile,
            )
        denom = jnp.where(denom > 0, denom, 1.0)
        return ex / denom[dst]

    def attention_aggregate(
        self,
        scores: jnp.ndarray,
        z: jnp.ndarray,
        *,
        mode: str = "runtime",
        leaky_slope: float = 0.2,
    ) -> jnp.ndarray:
        """One GAT layer's attention: softmax(LeakyReLU(scores)) aggregate.

        ``scores`` are the RAW per-edge logits f32[E, H] (pre-activation);
        ``z`` the head-stacked projected embeddings f32[N, H, dh]. Returns
        f32[N, H, dh].

        With ``use_kernel`` off this decomposes into the vectorized jnp
        passes (``edge_softmax`` + ``aggregate`` on the [E, H] layout — the
        always-on oracle). With ``use_kernel`` on, each precision group runs
        the fused Pallas kernel: LeakyReLU → tile-local segment-max → exp →
        segment-sum → weighted aggregate in ONE tile scan, combined across
        tiles by a flash-attention-style log-sum-exp rescale at the
        partial-response scatter. Precision groups cover disjoint
        destination nodes, so per-group softmax is exact; the fused path
        matches the oracle to float tolerance (tile-grouped summation
        re-associates), not bitwise.

        Recorded as one ``age`` span, with the plans' ``rows`` and
        ``window_rows`` once they are uploaded; engines override
        ``_attention_aggregate``.
        """
        rec = otrace.get_recorder()
        with rec.span("age", cat="engine", args={"mode": mode}) as span:
            out = self._attention_aggregate(
                scores, z, mode=mode, leaky_slope=leaky_slope)
            span.set(**self._age_rows.get(mode, {}))
        return out

    def _attention_aggregate(
        self,
        scores: jnp.ndarray,
        z: jnp.ndarray,
        *,
        mode: str = "runtime",
        leaky_slope: float = 0.2,
    ) -> jnp.ndarray:
        if isinstance(z, _streamed_features_type()):
            raise ValueError(
                "attention requires dense embeddings; streamed features "
                "cannot carry the per-edge softmax (compute z densely or "
                "lift the feature budget)"
            )
        scores = jnp.asarray(scores, jnp.float32)
        z = jnp.asarray(z, jnp.float32)
        e, n = self.graph.num_edges, self.graph.num_nodes
        if scores.ndim != 2 or scores.shape[0] != e:
            raise ValueError(
                f"scores must be [{e}, H], got {tuple(scores.shape)}"
            )
        h = scores.shape[1]
        if z.ndim != 3 or z.shape[0] != n or z.shape[1] != h:
            raise ValueError(
                f"z must be [{n}, {h}, dh], got {tuple(z.shape)}"
            )
        if not self.cfg.use_kernel:
            act = jax.nn.leaky_relu(scores, leaky_slope)
            alpha = self.edge_softmax(act, mode=mode)
            return self._aggregate(z, mode=mode, edge_coeff=alpha)

        from repro.kernels.segment_agg import attn_ops

        plans = self.plans(mode)
        self._require_edge_ids(mode, plans)
        dplans = self._device_plans(mode, plans, edge_ids=True)
        qp = None
        if self.cfg.mixed_precision and "int8" in plans:
            qp = self._activation_qp(lambda: z, "agg")
        out = jnp.zeros_like(z)
        for tag, p in plans.items():
            x = z
            if tag == "int8" and self.cfg.mixed_precision:
                x = dequantize(quantize(z, qp), qp)
            dp = dplans[tag]
            sc_t = tile_edge_coeff(dp, scores, fill=-jnp.inf)
            out = out + attn_ops.attend_tiles(
                x,
                dp.gather_idx,
                sc_t,
                dp.coeff,
                dp.seg_ids,
                dp.out_node,
                num_nodes=n,
                segments_per_tile=p.segments_per_tile,
                leaky_slope=leaky_slope,
            )
        return out

    # ----------------------------------------------------------------- FTE
    def _weight_q(self, w: jnp.ndarray):
        """Per-weight quantization cache → (w_q, w_qp, w_packed).

        ``w_packed`` is the load-time Marlin-style repack of ``w_q`` into the
        Pallas matmul's native tile order — built once per weight, only when
        the engine routes the FTE through the kernel (the jnp oracle never
        reads it), so every warm transform hands the kernel its preferred
        layout with zero per-call transpose.
        """
        key = id(w)
        entry = self._wq_cache.get(key)
        if entry is None or entry[0] is not w:
            w_q, w_qp = quantize_per_channel(w, axis=-1)
            packed = None
            if self.cfg.use_kernel:
                from repro.kernels.quant_matmul import ops as qm_ops

                packed = qm_ops.repack_weight(w_q)
            entry = (w, w_q, w_qp, packed)
            self._wq_cache[key] = entry
            while len(self._wq_cache) > self._WQ_CACHE_CAP:
                self._wq_cache.popitem(last=False)
        else:
            self._wq_cache.move_to_end(key)
        return entry[1], entry[2], entry[3]

    def transform(
        self,
        h: jnp.ndarray,
        w: jnp.ndarray,
        b: Optional[jnp.ndarray] = None,
        activation: Optional[Callable[[jnp.ndarray], jnp.ndarray]] = None,
    ) -> jnp.ndarray:
        """Mixed-precision transformation of aggregated embeddings.

        Accepts a ``memory.StreamedFeatures`` handle for ``h``: the int8
        group then streams chunk-blocked (1-byte rows, exact int32 matmul)
        and the float-protected block is gathered once — bitwise-identical
        to the dense mixed path (GraphSAGE's φ over stored features).

        Recorded as one ``fte`` span, weight and activation quantization
        included.
        """
        with otrace.get_recorder().span("fte", cat="engine"):
            if isinstance(h, _streamed_features_type()):
                return self._transform_streamed(h, w, b, activation)
            if not self.cfg.mixed_precision:
                return transform_dense(h, w, b, activation)
            w_q, w_qp, w_packed = self._weight_q(w)
            a_qp = None
            ids = self.node_groups.get("int8")
            if self._forward_active and ids is not None and ids.size:
                a_qp = self._activation_qp(
                    lambda: h[jnp.asarray(ids, jnp.int32)], "fte"
                )
            return transform_mixed_precision(
                h,
                self.node_groups,
                w,
                b,
                activation,
                w_q=w_q,
                w_qp=w_qp,
                a_qp=a_qp,
                use_kernel=self.cfg.use_kernel,
                w_packed=w_packed,
            )

    # ------------------------------------------------------------- metrics
    def occupancy_report(self) -> Dict[str, float]:
        """Lane economics vs the double-buffered baseline (same graph)."""
        plan = sched.build_edge_tile_plan(
            self.graph, edges_per_tile=self.cfg.edges_per_tile
        )
        padded = sched.build_padded_plan(self.graph, batch_size=64)
        return {
            "event_driven_lane_occupancy": plan.lane_occupancy,
            "double_buffer_pipeline_gap_ratio": padded.pipeline_gap_ratio,
            "float_node_ratio": float(
                (self.precision_tags == "float").mean() if self.graph.num_nodes else 0
            ),
        }
