"""Plan-cached GNN serving engine — the GNN analogue of the token ServeEngine.

AMPLE's host programs a graph into nodeslots once and then streams inference;
the expensive part of serving a GNN request on this stack is likewise the
host-side planner (Degree-Quant tagging + edge-tile packing), not the device
call. ``GNNServeEngine`` therefore treats the compiled ``ExecutionPlan`` as
the cacheable artifact:

  * requests are ``(graph, features)``; the engine keys an LRU cache on the
    graph's **structure fingerprint** + engine config + arch, so repeat
    traffic on the same graph skips plan compilation entirely — the serving
    analogue of nodeslot recycling;
  * independent small-graph requests are batched by ``infer_batch`` into one
    disjoint-union graph and served in a single padded device call (the
    union's plan is itself cached under the union fingerprint, so a repeated
    batch mix is also a cache hit);
  * cached plans are bitwise-faithful: a warm request returns exactly the
    output a cold engine would produce for the same graph and features.
"""
from __future__ import annotations

import dataclasses
import hashlib
import os
import time
from collections import OrderedDict
from typing import Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.degree_quant import inference_precision_tags
from repro.core.message_passing import (
    AmpleEngine,
    EngineConfig,
    ExecutionPlan,
    ShardPlan,
    ShardedExecutionPlan,
    assemble_union_plan,
    compile_plans,
    compile_shard_plan,
    compile_sharded_plans,
    engine_precision_tags,
    shard_plan_key,
)
from repro.core.scheduler import plan_fingerprint, size_class, union_bucket_fingerprint
from repro.distributed.graph_shard import ShardedAmpleEngine
from repro.graphs.csr import Graph, disjoint_union
from repro.graphs.partition import Partition, make_partition, validate_partition
from repro.models.gnn import api as gnn_api
from repro.observe import metrics as ometrics
from repro.observe import trace as otrace

__all__ = ["GNNRequest", "GNNResponse", "GNNServeEngine", "request_stamp"]


def request_stamp() -> float:
    """The serving stack's one lifecycle clock: ``time.perf_counter()``.

    Every admission/arrival stamp (``GNNRequest.admitted_at``,
    ``GNNTicket.arrival``, ``RoutedTicket.arrival``) and every duration
    (``plan_ms``/``run_ms``/``stall_ms``/``copy_ms``) must come from this
    clock. Mixing clocks (the old code stamped lifecycle points with
    ``time.monotonic()``) silently breaks queue-wait arithmetic on
    platforms where the two clocks differ, and splits the trace into two
    irreconcilable timelines. Routed (tenancy) and direct async requests
    both stamp through here, at admission — the parity the satellite tests
    pin down.
    """
    return time.perf_counter()


@dataclasses.dataclass(frozen=True)
class GNNRequest:
    """One inference request: a graph, its node features, optional arch."""

    graph: Graph
    features: np.ndarray  # f32[N, D]
    arch: str = ""  # "" -> the engine config's arch
    admitted_at: float = 0.0  # time.perf_counter() at admission; 0 = unqueued.
    # Set by queueing fronts (AsyncGNNEngine.submit, the tenancy router) so
    # the response's queue_ms attributes wait separately from compute. The
    # stamp shares the perf_counter clock with every duration measurement,
    # so admission->execution renders as one span on the trace timeline.
    trace_id: str = ""  # per-request correlation id (observe.trace); ""
    # when tracing is disabled — the engine then skips span recording.


@dataclasses.dataclass(frozen=True)
class GNNResponse:
    outputs: np.ndarray  # f32[N, num_classes]
    cache_hit: bool
    fingerprint: str  # plan-cache key the request resolved to
    plan_ms: float  # host planning time (0.0 on a cache hit)
    run_ms: float  # device execution wall time of the WHOLE batch this
    # request rode in (every member of one union call reports the same
    # number; divide by batch_size — or read run_ms_per_member — for an
    # amortized per-request figure)
    num_shards: int = 1  # shards the plan executed over (1 = unsharded path)
    batch_size: int = 1  # members in the union device call that produced this
    queue_ms: float = 0.0  # admission -> execution-start wait. 0.0 for
    # requests that never queued (direct sync calls without admitted_at);
    # on the async/tenancy paths this is the time the request spent waiting
    # for its micro-batch window, so SLO attribution can separate queueing
    # (scheduler's fault) from plan_ms + run_ms (compute's fault).
    # Out-of-core telemetry (all zero on the in-memory path). Like run_ms,
    # these describe the WHOLE device call: every member of one streamed
    # union batch reports the same bytes_streamed — read
    # bytes_streamed_per_member for an amortized per-request figure.
    streamed: bool = False  # features stayed host-resident, chunk-streamed
    bytes_streamed: int = 0  # feature bytes moved host->device by the call
    chunk_hit_rate: float = 0.0  # chunk-cache hits / accesses
    prefetch_overlap: float = 0.0  # wall-clock copy time hidden behind compute
    stall_ms: float = 0.0  # wall time the stream blocked on feature copies
    copy_ms: float = 0.0  # wall time of the feature copies themselves
    trace_id: str = ""  # correlation id of this request's trace spans ("" =
    # tracing disabled or no id assigned upstream)
    # Halo-exchange telemetry (sharded host-loop path; zero elsewhere). Like
    # run_ms these describe the whole device call this request rode in.
    halo_ms: float = 0.0  # wall time of the fenced halo row fetches
    halo_bytes: int = 0  # feature bytes crossing shard boundaries this call
    halo_overlap: float = 0.0  # fraction of halo fetch time hidden behind
    # interior-tile aggregation (1 - wait/fetch); 0.0 when overlap is off
    # or the engine is unsharded

    @property
    def run_ms_per_member(self) -> float:
        """Amortized device time per batch member (= run_ms when served solo)."""
        return self.run_ms / max(self.batch_size, 1)

    @property
    def bytes_streamed_per_member(self) -> float:
        """Amortized feature traffic per batch member (= bytes_streamed solo)."""
        return self.bytes_streamed / max(self.batch_size, 1)


class GNNServeEngine:
    """Serve ``(graph, features)`` requests with an LRU ``ExecutionPlan`` cache.

    Parameters
    ----------
    cfg: a ``family="gnn"`` ModelConfig (arch, dims, precision policy).
    params: model params; initialised from ``key`` when omitted.
    engine_cfg: EngineConfig override; derived from ``cfg`` by default.
    plan_cache_size: max distinct graph structures kept warm (LRU).
    num_shards: >1 partitions every served graph edge-balanced into this many
        shards and executes through ``ShardedAmpleEngine`` (halo exchange +
        one plan per shard); 1 is the existing single-plan path.
    partition: explicit ``Partition`` override (validated per graph); implies
        the sharded path and fixes ``num_shards`` to its shard count.
    partitioner: algorithm that splits served graphs when no explicit
        ``partition`` is given — "edges" (contiguous edge-balanced ranges)
        or "mincut" (halo-minimizing multilevel; params inline, e.g.
        "mincut(seed=1)"). Default ``cfg.gnn_partitioner``. Part of the plan
        cache key: the same graph served under two partitioners yields two
        distinct cached plans.
    mesh: optional 1-D ``("shard",)`` device mesh for SPMD shard execution;
        without one, shards run as a host loop on the local device. Must
        hold exactly ``num_shards`` devices.
    halo_overlap: overlap each shard's halo exchange with its interior-tile
        aggregation (outputs bitwise-identical; see
        ``scheduler.split_plan_by_halo``). Default ``cfg.gnn_halo_overlap``.
        Mutually exclusive with the Pallas kernel path.
    union_node_bucket / union_edge_bucket: >0 switches batched serving to
        **padded union size classes**: member graphs are planned (and cached)
        individually, the union plan is assembled by index relabelling, and
        nodes/tiles are padded up to the bucket so different member mixes
        share device shapes. 0 (default) keeps exact-shape union plans.
        Defaults come from ``cfg.gnn_union_node_bucket`` /
        ``cfg.gnn_union_edge_bucket``; ignored on the sharded path, whose
        unions are planned exactly.
    feature_budget_bytes: >0 enables **out-of-core serving**: a request whose
        feature matrix exceeds the budget keeps features host-resident in a
        chunked ``memory.FeatureStore`` and the engine streams them through
        a budget-bound device chunk cache (reuse-distance eviction, double-
        buffered prefetch) — outputs are bitwise-identical to the in-memory
        path. Requests that fit take the existing path unchanged. Default
        ``cfg.gnn_feature_budget_bytes`` (0 = off).
    feature_chunk_rows: rows per feature chunk (0 derives a size from the
        budget). Default ``cfg.gnn_feature_chunk_rows``.
    stream_packing: serve streamed requests through chunk-packed tile plans
        (``scheduler.pack_tiles_by_chunk``; bitwise-identical outputs, tiles
        draw from fewer chunks). Default ``cfg.gnn_stream_packing``.
    stream_reorder: locality-reorder tile runs on the streamed path; False
        keeps plan order (the reorder-vs-pack control arm benchmarks A/B
        without hand-built prefetchers). Default ``cfg.gnn_stream_reorder``.
    stream_prefetch_depth: tiles of lookahead granted to the async staging
        worker and slot prefetcher (0 = fully synchronous streaming).
    """

    def __init__(
        self,
        cfg: ModelConfig,
        params=None,
        *,
        engine_cfg: Optional[EngineConfig] = None,
        plan_cache_size: int = 32,
        num_shards: int = 1,
        partition: Optional[Partition] = None,
        partitioner: Optional[str] = None,
        mesh=None,
        halo_overlap: Optional[bool] = None,
        union_node_bucket: Optional[int] = None,
        union_edge_bucket: Optional[int] = None,
        feature_budget_bytes: Optional[int] = None,
        feature_chunk_rows: Optional[int] = None,
        stream_packing: Optional[bool] = None,
        stream_reorder: Optional[bool] = None,
        stream_prefetch_depth: int = 2,
        key=None,
    ):
        if cfg.family != "gnn":
            raise ValueError(f"GNNServeEngine needs a family='gnn' config, got {cfg.family!r}")
        if num_shards < 1:
            raise ValueError("num_shards must be >= 1")
        self.cfg = cfg
        self.engine_cfg = engine_cfg if engine_cfg is not None else gnn_api.engine_config(cfg)
        if params is None:
            params = gnn_api.gnn_init(cfg, key if key is not None else jax.random.PRNGKey(0))
        self.params = params
        self.plan_cache_size = plan_cache_size
        self.partition = partition
        self.num_shards = partition.num_shards if partition is not None else num_shards
        self.partitioner = (
            cfg.gnn_partitioner if partitioner is None else partitioner
        ) or "edges"
        self.mesh = mesh
        self.halo_overlap = (
            cfg.gnn_halo_overlap if halo_overlap is None else halo_overlap
        )
        if self.halo_overlap and self.engine_cfg.use_kernel:
            # Same contract as the streamed-path refusal below: the split
            # interior/boundary schedule continues a scan accumulator, which
            # the fused Pallas kernel has no hook for — refuse loudly rather
            # than silently serving unsplit.
            raise ValueError(
                "halo_overlap and use_kernel are mutually exclusive: the "
                "overlapped halo exchange continues the jnp scan accumulator "
                "(the Pallas kernel owns its own). Drop "
                "ModelConfig.gnn_use_kernel / EngineConfig.use_kernel, or "
                "set gnn_halo_overlap=False / --halo-overlap off."
            )
        if mesh is not None and mesh.devices.size != self.num_shards:
            raise ValueError(
                f"mesh has {mesh.devices.size} devices but num_shards="
                f"{self.num_shards}; pass --num-shards {mesh.devices.size} "
                f"(or a mesh with one device per shard)"
            )
        self.union_node_bucket = (
            cfg.gnn_union_node_bucket if union_node_bucket is None else union_node_bucket
        )
        self.union_edge_bucket = (
            cfg.gnn_union_edge_bucket if union_edge_bucket is None else union_edge_bucket
        )
        self.feature_budget_bytes = (
            cfg.gnn_feature_budget_bytes
            if feature_budget_bytes is None
            else feature_budget_bytes
        )
        self.feature_chunk_rows = (
            cfg.gnn_feature_chunk_rows
            if feature_chunk_rows is None
            else feature_chunk_rows
        )
        self.stream_packing = (
            cfg.gnn_stream_packing if stream_packing is None else stream_packing
        )
        self.stream_reorder = (
            cfg.gnn_stream_reorder if stream_reorder is None else stream_reorder
        )
        self.stream_prefetch_depth = max(int(stream_prefetch_depth), 0)
        if self.feature_budget_bytes > 0 and self.engine_cfg.use_kernel:
            # The streamed executors are jnp-only (chunk-blocked passes are
            # bitwise-equal to the dense jnp path; the Pallas kernels
            # re-associate) — refuse the combination outright rather than
            # silently serving every request fully in-memory.
            raise ValueError(
                "feature_budget_bytes and use_kernel are mutually exclusive: "
                "the out-of-core streamed executors serve the jnp path only "
                "(Pallas kernel rounding differs from the streamed oracle). "
                "Drop EngineConfig.use_kernel / ModelConfig.gnn_use_kernel, "
                "or set feature_budget_bytes=0 to serve in-memory."
            )
        if self.feature_budget_bytes > 0 and self.sharded:
            # Better a loud no-op than a user believing the cap is active
            # and meeting an OOM on a genuinely large graph.
            import warnings

            warnings.warn(
                "feature_budget_bytes is ignored on sharded engines: the "
                "streamed executors serve the plain single-device jnp path "
                "only; requests will run fully in-memory",
                stacklevel=2,
            )
        # fingerprint -> (prepared graph, plan, engine); OrderedDict as LRU.
        # The engine rides along so its weight-quant cache survives across
        # requests (params are fixed for this serve engine's lifetime).
        # Sharded requests store (prepared, ShardedExecutionPlan,
        # ShardedAmpleEngine) tuples under the same LRU.
        self._cache: "OrderedDict[str, Tuple[Graph, Union[ExecutionPlan, ShardedExecutionPlan], AmpleEngine]]" = OrderedDict()
        # Per-shard plan LRU, keyed on shard_plan_key (structure, partition
        # boundaries, shard index, planner config): a shard compiled for one
        # request is reusable by any later request on the same partitioned
        # structure, independently of the assembled plan above.
        self._shard_plans: "OrderedDict[str, ShardPlan]" = OrderedDict()
        # Member-plan pieces for the padded-union path, keyed on the member's
        # structure fingerprint: value = (prepared member graph, its solo
        # ExecutionPlan). A member planned for one batch mix is reusable by
        # every later mix containing it — this cache, not the assembled-plan
        # LRU, is what keeps the planner cold under varying compositions.
        self._member_plans: "OrderedDict[str, Tuple[Graph, ExecutionPlan]]" = OrderedDict()
        # Size classes already served (device shapes warm); statistics only.
        self._classes_seen: "OrderedDict[str, None]" = OrderedDict()
        # FeatureStore LRU for the out-of-core path, keyed on (feature array
        # identity, row count, chunk rows) with a strong ref held — id()
        # alone is unsound once the original is collected, same reasoning as
        # the weight-quant cache.
        self._stores: "OrderedDict[tuple, Tuple[np.ndarray, object]]" = OrderedDict()
        self._last_stream = None  # StreamStats of the most recent _run
        # Historical dict API over registry-backed cells: the metrics
        # registry (observe.metrics) holds the single copy of every counter;
        # this view keeps `engine.stats[...]` value-identical to the old
        # ad-hoc dict (ints stay ints, the *_ms accumulators stay floats).
        self.instance = ometrics.next_instance("gnn_serve")
        self.stats: ometrics.StatsView = ometrics.StatsView(
            ometrics.get_registry(),
            "gnn_serve",
            {"engine": self.instance},
            keys=(
                "requests",
                "batches",
                "cache_hits",
                "cache_misses",
                "planner_calls",
                "evictions",
                "shard_hits",
                "warm_loads",
                "member_hits",
                "member_misses",
                "class_hits",
                "class_misses",
                "streamed_requests",
                "bytes_streamed",
                "chunk_hits",
                "chunk_misses",
                "prefetched_uploads",
                "stream_fallbacks",
                "stall_ms",
                "copy_ms",
                "halo_exchanges",
                "halo_bytes",
                "halo_ms",
                "halo_wait_ms",
            ),
            float_keys=("stall_ms", "copy_ms", "halo_ms", "halo_wait_ms"),
        )
        self._last_halo: Optional[Dict[str, float]] = None

    @property
    def sharded(self) -> bool:
        return self.num_shards > 1 or self.partition is not None

    @property
    def padded_unions(self) -> bool:
        """True when batched requests plan through padded union size classes."""
        return (
            (self.union_node_bucket > 0 or self.union_edge_bucket > 0)
            and not self.sharded
        )

    # ------------------------------------------------------------ plan cache
    def _cache_key(self, g: Graph, arch: str, members: Optional[Sequence[Graph]]) -> str:
        """Structure hash + engine config + arch — everything that shapes a plan.

        Keyed on the *raw* request graph so arch-specific preprocessing
        (GCN's self-loops) is part of the cached work, not repeated per hit.
        Batched unions also key on the member boundaries, since Degree-Quant
        tags are computed per member graph (the same union structure split
        differently plans differently).
        """
        parts = [repr(self.engine_cfg), arch]
        if members is not None:
            parts.append("bounds:" + ",".join(str(m.num_nodes) for m in members))
        if self.sharded:
            if self.partition is not None:
                parts.append(
                    "starts:" + ",".join(str(int(s)) for s in self.partition.starts)
                )
                parts.append(f"kind:{self.partition.kind}")
            else:
                parts.append(f"shards:{self.num_shards}")
                parts.append(f"partitioner:{self.partitioner}")
            if self.halo_overlap:
                # plan contents are identical, but the cached engine holds
                # split-plan device state — keep the entries distinct
                parts.append("halo_overlap")
        return plan_fingerprint(g, *parts)

    def _plan_for(
        self, g: Graph, arch: str, members: Optional[Sequence[Graph]] = None
    ) -> Tuple[Graph, ExecutionPlan, AmpleEngine, bool, float]:
        key = self._cache_key(g, arch, members)
        hit = key in self._cache
        plan_ms = 0.0
        if hit:
            self._cache.move_to_end(key)
            self.stats["cache_hits"] += 1
        else:
            self.stats["cache_misses"] += 1
            self.stats["planner_calls"] += 1
            cfg = dataclasses.replace(self.cfg, gnn_arch=arch)
            t0 = time.perf_counter()
            prepared = gnn_api.prepare_graph(cfg, g)
            tags = None
            if members is not None and self.engine_cfg.mixed_precision:
                # Tag each member independently: a small graph batched with a
                # hub-heavy one must keep its own Degree-Quant-protected
                # nodes, exactly as if served solo.
                tags = self._member_tags(cfg, members)
            plan = compile_plans(
                prepared, self.engine_cfg, modes=(gnn_api.agg_mode(cfg),),
                precision_tags=tags,
            )
            plan_ms = (time.perf_counter() - t0) * 1e3
            self._cache[key] = (prepared, plan, AmpleEngine(prepared, plan=plan))
            while len(self._cache) > self.plan_cache_size:
                self._cache.popitem(last=False)
                self.stats["evictions"] += 1
        prepared, plan, engine = self._cache[key]
        return prepared, plan, engine, hit, plan_ms

    def _member_tags(self, cfg, members: Sequence[Graph]) -> np.ndarray:
        """Per-member Degree-Quant tags for a batched disjoint union."""
        return np.concatenate([
            inference_precision_tags(
                gnn_api.prepare_graph(cfg, m), self.engine_cfg.dq
            )
            for m in members
        ])

    # ------------------------------------------ padded union size classes
    def _member_plan(self, cfg, m: Graph, arch: str) -> Tuple[Graph, ExecutionPlan]:
        """One member graph's (prepared graph, solo plan), LRU-cached.

        Tags are computed on the member's own degree distribution — identical
        Degree-Quant protection to solo serving — so any assembly of cached
        members preserves the per-member tagging guarantee of ``infer_batch``.
        """
        key = plan_fingerprint(m, repr(self.engine_cfg), arch, "member")
        if key in self._member_plans:
            self._member_plans.move_to_end(key)
            self.stats["member_hits"] += 1
            return self._member_plans[key]
        self.stats["member_misses"] += 1
        self.stats["planner_calls"] += 1
        prepared = gnn_api.prepare_graph(cfg, m)
        plan = compile_plans(
            prepared,
            self.engine_cfg,
            modes=(gnn_api.agg_mode(cfg),),
            precision_tags=engine_precision_tags(prepared, self.engine_cfg),
        )
        self._member_plans[key] = (prepared, plan)
        while len(self._member_plans) > max(self.plan_cache_size * 8, 64):
            self._member_plans.popitem(last=False)
        return prepared, plan

    def _plan_for_padded(
        self, members: Sequence[Graph], arch: str
    ) -> Tuple[Graph, ExecutionPlan, AmpleEngine, bool, float]:
        """Size-class planning: cached member pieces → assembled padded union.

        The serve cache resolves in two levels. The **size class**
        (``union_bucket_fingerprint`` over the bucketed node/edge counts) is
        the shape-level key: a warm class means the device executable and
        upload shapes recur, whatever the member mix. The member mix itself
        only decides which cached plan pieces are relabelled into the
        assembled plan — an O(E) copy, never a planner call for known
        members. ``cache_hit`` is True when neither the members nor the
        assembly needed the planner; ``plan_ms`` covers whatever planning +
        assembly this call actually paid (member compilation still counts
        when the assembled plan itself was resident, e.g. right after
        ``load_plan_cache`` warmed the assembled LRU but not the pieces).
        """
        cfg = dataclasses.replace(self.cfg, gnn_arch=arch)
        t0 = time.perf_counter()
        misses_before = self.stats["member_misses"]
        pieces = [self._member_plan(cfg, m, arch) for m in members]
        members_cold = self.stats["member_misses"] > misses_before
        n_real = sum(p.num_nodes for p, _ in pieces)
        e_real = sum(p.num_edges for p, _ in pieces)
        class_fp = union_bucket_fingerprint(
            n_real,
            e_real,
            self.union_node_bucket,
            self.union_edge_bucket,
            repr(self.engine_cfg),
            arch,
        )
        if class_fp in self._classes_seen:
            self._classes_seen.move_to_end(class_fp)
            self.stats["class_hits"] += 1
        else:
            self._classes_seen[class_fp] = None
            self.stats["class_misses"] += 1
            while len(self._classes_seen) > self.plan_cache_size * 8:
                self._classes_seen.popitem(last=False)

        h = hashlib.blake2b(digest_size=16)
        h.update(class_fp.encode())
        for _, mp in pieces:
            h.update(b"\x00")
            h.update(mp.fingerprint.encode())
        key = h.hexdigest()
        if key in self._cache:
            self._cache.move_to_end(key)
            self.stats["cache_hits"] += 1
            prepared, plan, engine = self._cache[key]
            plan_ms = (
                (time.perf_counter() - t0) * 1e3 if members_cold else 0.0
            )
            return prepared, plan, engine, not members_cold, plan_ms

        self.stats["cache_misses"] += 1
        n_class, _ = size_class(
            n_real, e_real, self.union_node_bucket, self.union_edge_bucket
        )
        union = disjoint_union(
            [p for p, _ in pieces], pad_num_nodes=n_class
        )
        plan = assemble_union_plan(
            [mp for _, mp in pieces],
            union,
            cfg=self.engine_cfg,
            edge_bucket=self.union_edge_bucket,
        )
        engine = AmpleEngine(union, plan=plan)
        plan_ms = (time.perf_counter() - t0) * 1e3
        self._cache[key] = (union, plan, engine)
        while len(self._cache) > self.plan_cache_size:
            self._cache.popitem(last=False)
            self.stats["evictions"] += 1
        return union, plan, engine, False, plan_ms

    def _plan_for_sharded(
        self, g: Graph, arch: str, members: Optional[Sequence[Graph]] = None
    ) -> Tuple[Graph, ShardedExecutionPlan, ShardedAmpleEngine, bool, float]:
        """Sharded analogue of ``_plan_for``: per-shard plan-cache economics.

        The assembled (prepared graph, ShardedExecutionPlan, engine) triple is
        cached under the request key like the unsharded path; below it, every
        ShardPlan lives in a per-shard LRU keyed on (structure, partition,
        shard) fingerprints, so only shards never seen before run the planner.
        ``cache_hit`` is True iff no shard needed compiling; ``plan_ms``
        counts planner time only (0.0 on a full hit).
        """
        key = self._cache_key(g, arch, members)
        if key in self._cache:
            self._cache.move_to_end(key)
            self.stats["cache_hits"] += 1
            prepared, splan, engine = self._cache[key]
            return prepared, splan, engine, True, 0.0

        cfg = dataclasses.replace(self.cfg, gnn_arch=arch)
        prepared = gnn_api.prepare_graph(cfg, g)
        if self.partition is not None:
            validate_partition(prepared, self.partition)
            part = self.partition
        else:
            part = make_partition(prepared, self.num_shards, self.partitioner)
        modes = (gnn_api.agg_mode(cfg),)
        if members is not None and self.engine_cfg.mixed_precision:
            tags = self._member_tags(cfg, members)
        else:
            tags = None
        eff_tags = (
            tags if tags is not None else engine_precision_tags(prepared, self.engine_cfg)
        )

        plan_ms = 0.0
        warm: Dict[int, ShardPlan] = {}
        missing: List[int] = []
        for k in range(part.num_shards):
            skey = shard_plan_key(
                prepared, part, k, self.engine_cfg, modes=modes, precision_tags=eff_tags
            )
            if skey in self._shard_plans:
                self._shard_plans.move_to_end(skey)
                warm[k] = self._shard_plans[skey]
                self.stats["shard_hits"] += 1
            else:
                missing.append(k)
        if missing:
            from repro.core.message_passing import aggregation_coefficients

            self.stats["planner_calls"] += len(missing)
            t0 = time.perf_counter()
            # Global O(E) coefficient work once per request, not per shard.
            mode_coeffs = {m: aggregation_coefficients(prepared, m) for m in modes}
            for k in missing:
                sp = compile_shard_plan(
                    prepared, part, k, self.engine_cfg,
                    modes=modes, precision_tags=eff_tags, mode_coeffs=mode_coeffs,
                )
                warm[k] = sp
                self._shard_plans[sp.fingerprint] = sp
            plan_ms = (time.perf_counter() - t0) * 1e3
            while len(self._shard_plans) > self.plan_cache_size * max(self.num_shards, 1):
                self._shard_plans.popitem(last=False)
        splan = compile_sharded_plans(
            prepared, self.engine_cfg,
            partition=part, modes=modes, precision_tags=eff_tags, shard_plans=warm,
        )
        engine = ShardedAmpleEngine(
            prepared, splan, mesh=self.mesh, halo_overlap=self.halo_overlap
        )
        hit = not missing
        self.stats["cache_hits" if hit else "cache_misses"] += 1
        self._cache[key] = (prepared, splan, engine)
        while len(self._cache) > self.plan_cache_size:
            self._cache.popitem(last=False)
            self.stats["evictions"] += 1
        return prepared, splan, engine, hit, plan_ms

    # -------------------------------------------------------------- serving
    def _arch(self, requested: str) -> str:
        if requested and requested != self.cfg.gnn_arch:
            raise ValueError(
                f"this engine holds {self.cfg.gnn_arch!r} params; route "
                f"{requested!r} requests to an engine configured for that arch"
            )
        return requested or self.cfg.gnn_arch

    def _validate_request(self, graph: Graph, features) -> np.ndarray:
        """Admission-time input checks with actionable errors.

        Without these, a bad request surfaces deep in the union path as a
        cryptic concatenate/split shape failure — after other members'
        work was already spent.
        """
        if graph.num_nodes == 0:
            raise ValueError(
                "cannot serve a zero-node graph; drop empty members before "
                "submission"
            )
        f = np.asarray(features, np.float32)
        if f.ndim != 2:
            raise ValueError(
                f"features must be 2-D [num_nodes, feature_dim], got shape "
                f"{tuple(f.shape)}"
            )
        if f.shape[0] != graph.num_nodes:
            raise ValueError(
                f"features have {f.shape[0]} rows but graph {graph.name!r} has "
                f"{graph.num_nodes} nodes"
            )
        want = self.cfg.gnn_layer_dims[0]
        if f.shape[1] != want:
            raise ValueError(
                f"features have {f.shape[1]} columns but {self.cfg.name} "
                f"expects {want} (cfg.d_model)"
            )
        return f

    def _plan_for_batch(
        self, members: Sequence[Graph], arch: str
    ) -> Tuple[Graph, Union[ExecutionPlan, ShardedExecutionPlan], AmpleEngine, bool, float]:
        """Plan-assembly step for a disjoint-union batch — path dispatch.

        The reusable half the continuous-batching loop drives incrementally:
        sharded engines plan the exact union per shard, padded engines
        assemble cached member pieces into a size-class plan, and the default
        engine compiles the exact union (with per-member Degree-Quant tags).
        """
        if self.padded_unions:
            return self._plan_for_padded(members, arch)
        union = disjoint_union(list(members))
        if self.sharded:
            return self._plan_for_sharded(union, arch, members)
        return self._plan_for(union, arch, members)

    @staticmethod
    def _pad_features(features: np.ndarray, num_nodes: int) -> np.ndarray:
        """Zero rows up to the size-class node count (no-op when exact)."""
        if num_nodes <= features.shape[0]:
            return features
        return np.concatenate(
            [features,
             np.zeros((num_nodes - features.shape[0], features.shape[1]),
                      np.float32)],
            axis=0,
        )

    # ------------------------------------------------- out-of-core streaming
    def _stream_eligible(self, engine: AmpleEngine, features: np.ndarray) -> bool:
        """Stream iff a budget is set, the matrix exceeds it, and the plan
        executes on the plain single-device engine (the sharded executor
        gathers per-shard row sets and is served in-memory). Kernel-routed
        engines (``use_kernel``) are excluded: the streamed executors are
        the jnp oracle, and Pallas vs oracle can differ by an int8 rounding
        step — streaming there would break the bitwise guarantee."""
        return (
            self.feature_budget_bytes > 0
            and type(engine) is AmpleEngine
            and not self.engine_cfg.use_kernel
            and features.nbytes > self.feature_budget_bytes
        )

    def _feature_stream(
        self,
        features: np.ndarray,
        *,
        cache_store: bool = True,
        store_key=None,  # caller-held object of any array-like type
    ):
        """Wrap ``features`` in a StreamedFeatures handle (store LRU-cached).

        Repeat traffic holding the same feature array skips the store build
        (chunking + int8 quantization) exactly like repeat structures skip
        the planner. The store is tag-independent — it holds every row in
        both representations — so one store serves any plan over the matrix.

        ``store_key`` is the caller-held array the cache identity hangs on
        when ``features`` itself is derived per call — the padded-union path
        pads a fresh copy each request, so keying on the *original* matrix
        (plus the padded row count) is what lets warm padded requests hit.
        ``cache_store=False`` builds an ephemeral store instead: the batch
        path concatenates a fresh union matrix per call, so id-keyed entries
        could never hit again and would only pin dead matrices in the LRU.
        """
        from repro.memory.feature_store import FeatureStore, default_chunk_rows
        from repro.memory.prefetcher import StreamedFeatures

        rows = self.feature_chunk_rows or default_chunk_rows(
            features.shape[0], features.shape[1], self.feature_budget_bytes
        )
        def wrap(store):
            return StreamedFeatures(
                store,
                self.feature_budget_bytes,
                prefetch_depth=self.stream_prefetch_depth,
                reorder=self.stream_reorder,
                packing=self.stream_packing,
            )

        if not cache_store:
            return wrap(FeatureStore.from_array(features, chunk_rows=rows))
        key_arr = store_key if store_key is not None else features
        key = (id(key_arr), features.shape[0], rows)
        entry = self._stores.get(key)
        if entry is None or entry[0] is not key_arr:
            store = FeatureStore.from_array(features, chunk_rows=rows)
            self._stores[key] = (key_arr, store)
            while len(self._stores) > 4:
                self._stores.popitem(last=False)
        else:
            self._stores.move_to_end(key)
        return wrap(self._stores[key][1])

    def _run(
        self,
        arch: str,
        prepared: Graph,
        engine: AmpleEngine,
        features,
        *,
        cache_store: bool = True,
        store_key=None,
        trace_id: str = "",
    ) -> Tuple[np.ndarray, float]:
        """Execution step: one padded device call over an assembled plan.

        When the feature matrix exceeds ``feature_budget_bytes`` (and the
        plan runs on the single-device engine), features stay host-resident
        and the engine streams them chunk-wise — same outputs, bit for bit;
        telemetry lands in ``stats`` and on the response. ``cache_store``
        is False on the batch path (per-call union matrices never repeat);
        ``store_key`` carries the caller-held array identity when
        ``features`` is a per-call padded copy.
        """
        cfg = dataclasses.replace(self.cfg, gnn_arch=arch)
        self._last_stream = None
        self._last_halo = None
        batch_features = features
        if self._stream_eligible(engine, features):
            sf = self._feature_stream(
                features, cache_store=cache_store, store_key=store_key
            )
            sf.trace_id = trace_id  # prefetcher stamps copy/stall spans
            batch_features = sf
            self._last_stream = sf.stats
        halo_before = None
        if isinstance(engine, ShardedAmpleEngine):
            engine.trace_id = trace_id  # halo spans join this request's trace
            halo_before = dict(engine.halo_stats)
        rec = otrace.get_recorder()
        with rec.span(
            "execute", cat="serve", trace_id=trace_id,
            args={"arch": arch, "streamed": self._last_stream is not None},
        ) as span:
            t0 = time.perf_counter()
            y, _ = gnn_api.gnn_forward(
                self.params, cfg,
                {"graph": prepared, "features": batch_features, "engine": engine},
            )
            with rec.span("wait", cat="serve"):
                y = jax.block_until_ready(y)
            with rec.span("fetch", cat="serve", args={"bytes": y.nbytes}):
                y = np.asarray(y)
            t1 = time.perf_counter()
            # Same stamps as run_ms, so the execute span reconciles exactly.
            span.stamps(t0, t1)
        run_ms = (t1 - t0) * 1e3
        if self._last_stream is not None:
            s = self._last_stream
            self.stats["bytes_streamed"] += s.bytes_streamed
            self.stats["chunk_hits"] += s.chunk_hits
            self.stats["chunk_misses"] += s.chunk_misses
            self.stats["prefetched_uploads"] += s.prefetched
            self.stats["stream_fallbacks"] += s.fallbacks
            self.stats["stall_ms"] += s.stall_ms
            self.stats["copy_ms"] += s.copy_ms
        if halo_before is not None:
            # This call's halo traffic = engine accumulator delta (the engine
            # is shared across cached requests; only the delta is ours).
            delta = {
                k: engine.halo_stats.get(k, 0.0) - halo_before.get(k, 0.0)
                for k in ("halo_ms", "halo_wait_ms", "halo_bytes", "halo_exchanges")
            }
            if delta["halo_exchanges"] > 0:
                self._last_halo = delta
                self.stats["halo_exchanges"] += int(delta["halo_exchanges"])
                self.stats["halo_bytes"] += int(delta["halo_bytes"])
                self.stats["halo_ms"] += delta["halo_ms"]
                self.stats["halo_wait_ms"] += delta["halo_wait_ms"]
        return y, run_ms

    def _stream_fields(self) -> Dict[str, object]:
        """Response fields describing the most recent ``_run``'s streaming."""
        s = self._last_stream
        if s is None:
            return {}
        return {
            "streamed": True,
            "bytes_streamed": s.bytes_streamed,
            "chunk_hit_rate": s.hit_rate,
            "prefetch_overlap": s.prefetch_overlap,
            "stall_ms": s.stall_ms,
            "copy_ms": s.copy_ms,
        }

    def _halo_fields(self) -> Dict[str, object]:
        """Response fields describing the most recent ``_run``'s halo traffic.

        ``halo_overlap`` is wall-clock truth, mirroring ``prefetch_overlap``:
        the fraction of fenced halo-fetch time the aggregation did NOT block
        on (``1 - halo_wait_ms / halo_ms``).
        """
        h = self._last_halo
        if h is None:
            return {}
        halo_ms = h["halo_ms"]
        overlap = (
            min(max(1.0 - h["halo_wait_ms"] / halo_ms, 0.0), 1.0)
            if halo_ms > 0.0
            else 0.0
        )
        return {
            "halo_ms": halo_ms,
            "halo_bytes": int(h["halo_bytes"]),
            "halo_overlap": overlap,
        }

    @staticmethod
    def _queue_ms(admitted_at: float, exec_start: float) -> float:
        """Admission→execution wait; 0.0 for requests that never queued.

        Both stamps are ``time.perf_counter()`` — the one clock the whole
        serving stack uses (see ``request_stamp``) — so this subtraction,
        the trace's queue span, and every duration share a timeline.
        """
        if admitted_at <= 0.0:
            return 0.0
        return max(exec_start - admitted_at, 0.0) * 1e3

    def infer(
        self,
        graph: Graph,
        features,
        *,
        arch: str = "",
        admitted_at: float = 0.0,
        trace_id: str = "",
    ) -> GNNResponse:
        """Serve one request; plans come from the LRU cache when warm.

        With padded unions enabled the request is served as a batch of one —
        its member plan piece then pre-warms every future batch containing
        this structure. ``admitted_at`` (a ``time.perf_counter()`` stamp, see
        ``request_stamp``) marks when the request was admitted upstream; the
        response's ``queue_ms`` reports the wait between then and execution
        start.
        """
        rec = otrace.get_recorder()
        if rec.recording and not trace_id:
            trace_id = otrace.new_trace_id()
        with rec.span("request", cat="serve", trace_id=trace_id,
                      args={"nodes": graph.num_nodes}) as request:
            arch = self._arch(arch)
            # The store-cache identity is the CALLER's object: validation may
            # convert (float64/jnp inputs), and padding copies — keying on
            # either derived array would rebuild the store on every warm
            # request.
            original = features
            with rec.span("validate", cat="serve"):
                features = self._validate_request(graph, features)
            with rec.span("plan", cat="serve") as span:
                exec_start = time.perf_counter()
                span.stamps(t0=exec_start)  # the queue span ends here
                queue_ms = self._queue_ms(admitted_at, exec_start)
                if admitted_at > 0.0:
                    rec.add_span("queue", admitted_at, exec_start, cat="serve",
                                 trace_id=trace_id)
                if self.padded_unions:
                    prepared, plan, engine, hit, plan_ms = self._plan_for_padded(
                        [graph], arch)
                elif self.sharded:
                    prepared, plan, engine, hit, plan_ms = self._plan_for_sharded(
                        graph, arch)
                else:
                    prepared, plan, engine, hit, plan_ms = self._plan_for(graph, arch)
                span.set(cache_hit=hit, plan_ms=plan_ms)
            request.set(cache_hit=hit, padded_nodes=prepared.num_nodes)
            if self.padded_unions:
                with rec.span("pad", cat="serve"):
                    features = self._pad_features(features, prepared.num_nodes)
            y, run_ms = self._run(
                arch, prepared, engine, features, store_key=original,
                trace_id=trace_id,
            )
        self.stats["requests"] += 1
        if self._last_stream is not None:
            self.stats["streamed_requests"] += 1
        return GNNResponse(
            outputs=y[: graph.num_nodes],
            cache_hit=hit,
            fingerprint=plan.fingerprint,
            plan_ms=plan_ms,
            run_ms=run_ms,
            num_shards=getattr(plan, "num_shards", 1),
            queue_ms=queue_ms,
            trace_id=trace_id,
            **self._stream_fields(),
            **self._halo_fields(),
        )

    def infer_batch(self, requests: Sequence[GNNRequest]) -> List[GNNResponse]:
        """Batch independent small-graph requests into one padded device call.

        All requests must target this engine's arch (group upstream
        otherwise). The disjoint union is block-diagonal and every
        aggregation coefficient depends only on per-node degree, so in float
        precision the union forward equals the per-request forwards stacked
        exactly; outputs are split back by node counts. Under the mixed
        policy, Degree-Quant tags are computed per member graph (identical
        protection to solo serving), while int8 activation scale/zero-point
        remain batch-wide — the usual granularity trade-off of batched
        quantized serving.

        Internally this is ``_plan_for_batch`` (plan assembly) followed by
        ``_run`` (one device call) — the same two steps the continuous-
        batching ``AsyncGNNEngine`` drives per admission window, so a
        micro-batch admitted asynchronously is bitwise-identical to the same
        composition served here.
        """
        if not requests:
            return []
        rec = otrace.get_recorder()
        batch_tid = requests[0].trace_id
        if rec.recording and not batch_tid:
            batch_tid = otrace.new_trace_id()
        # Per-member queue spans carry each request's own id; the
        # window-level request/plan/execute spans carry the lead member's.
        with rec.span("request", cat="serve", trace_id=batch_tid,
                      args={"batch": len(requests)}) as request:
            arch = self._arch(requests[0].arch)
            for r in requests[1:]:
                self._arch(r.arch)  # every request must match this engine's arch
            with rec.span("validate", cat="serve"):
                feats = [self._validate_request(r.graph, r.features)
                         for r in requests]
            with rec.span("plan", cat="serve") as span:
                exec_start = time.perf_counter()
                span.stamps(t0=exec_start)  # the queue spans end here
                queue_waits = [self._queue_ms(r.admitted_at, exec_start)
                               for r in requests]
                for r in requests:
                    if r.admitted_at > 0.0:
                        rec.add_span("queue", r.admitted_at, exec_start,
                                     cat="serve", trace_id=r.trace_id or batch_tid)
                members = [r.graph for r in requests]
                prepared, plan, engine, hit, plan_ms = self._plan_for_batch(
                    members, arch)
                span.set(cache_hit=hit, plan_ms=plan_ms, batch=len(requests))
            request.set(cache_hit=hit,
                        nodes=sum(m.num_nodes for m in members),
                        padded_nodes=prepared.num_nodes)
            with rec.span("pad", cat="serve"):
                features = self._pad_features(np.concatenate(feats, axis=0),
                                              prepared.num_nodes)
            y, run_ms = self._run(
                arch, prepared, engine, features, cache_store=False,
                trace_id=batch_tid,
            )
        # Counted only on success, so a failed-and-requeued continuous-batching
        # window doesn't double-count when it retries.
        self.stats["requests"] += len(requests)
        if self._last_stream is not None:
            # Every member of the streamed union call counts, so
            # streamed_requests / requests is the true streamed fraction.
            self.stats["streamed_requests"] += len(requests)
        self.stats["batches"] += 1
        out: List[GNNResponse] = []
        start = 0
        stream_fields = {**self._stream_fields(), **self._halo_fields()}
        scatter_t0 = time.perf_counter()
        for r, q_ms in zip(requests, queue_waits):
            stop = start + r.graph.num_nodes
            out.append(
                GNNResponse(
                    outputs=y[start:stop],
                    cache_hit=hit,
                    fingerprint=plan.fingerprint,
                    plan_ms=plan_ms,
                    run_ms=run_ms,
                    num_shards=getattr(plan, "num_shards", 1),
                    batch_size=len(requests),
                    queue_ms=q_ms,
                    trace_id=r.trace_id or batch_tid,
                    **stream_fields,
                )
            )
            start = stop
        if rec.enabled:
            rec.add_span(
                "scatter", scatter_t0, time.perf_counter(), cat="serve",
                trace_id=batch_tid, args={"batch": len(requests)},
            )
        return out

    # --------------------------------------------------------- persistence
    def save_plan_cache(self, directory: str) -> List[str]:
        """Persist every cached plan (npz via ``checkpoint.plan_store``).

        One file per cache entry, named by the serve-cache key; the prepared
        graph structure rides along so ``load_plan_cache`` can rebuild the
        execution engine without re-running arch preprocessing.
        """
        from repro.checkpoint.plan_store import save_plan

        os.makedirs(directory, exist_ok=True)
        paths = []
        for key, (prepared, plan, _) in self._cache.items():
            path = os.path.join(directory, f"{key}.plan.npz")
            save_plan(path, plan, graph=prepared, extra={"serve_key": key})
            paths.append(path)
        return paths

    def load_plan_cache(self, directory: str) -> int:
        """Warm the plan cache from ``save_plan_cache`` output; returns count.

        A restarted server calls this instead of paying the planner again:
        the first request on a persisted structure reports ``cache_hit=True``
        with ``plan_ms == 0.0``, exactly like in-memory repeat traffic.
        Entries whose file lacks a serve key or graph are skipped.
        """
        from repro.checkpoint.plan_store import load_plan

        loaded = 0
        if not os.path.isdir(directory):
            return 0
        for name in sorted(os.listdir(directory)):
            if not name.endswith(".plan.npz"):
                continue
            rec = load_plan(os.path.join(directory, name))
            key = rec.extra.get("serve_key")
            if key is None or rec.graph is None:
                continue
            if isinstance(rec.plan, ShardedExecutionPlan):
                engine: AmpleEngine = ShardedAmpleEngine(
                    rec.graph, rec.plan, mesh=self.mesh,
                    halo_overlap=self.halo_overlap,
                )
                for sp in rec.plan.shards:
                    self._shard_plans[sp.fingerprint] = sp
            else:
                engine = AmpleEngine(rec.graph, plan=rec.plan)
            self._cache[key] = (rec.graph, rec.plan, engine)
            loaded += 1
        while len(self._cache) > self.plan_cache_size:
            self._cache.popitem(last=False)
            self.stats["evictions"] += 1
        self.stats["warm_loads"] += loaded
        return loaded

    # ------------------------------------------------------------- metrics
    def cache_info(self) -> Dict[str, float]:
        """Plan-cache counters plus derived streaming rates.

        ``chunk_hit_rate`` / ``prefetch_overlap`` aggregate over every
        streamed request this engine served (0.0 when nothing streamed).
        ``prefetch_overlap`` is wall-clock: the fraction of measured copy
        time the streams did NOT block on (``1 - stall_ms / copy_ms``).
        """
        accesses = self.stats["chunk_hits"] + self.stats["chunk_misses"]
        copy_ms = self.stats["copy_ms"]
        overlap = (
            min(max(1.0 - self.stats["stall_ms"] / copy_ms, 0.0), 1.0)
            if copy_ms > 0.0
            else 0.0
        )
        halo_ms = self.stats["halo_ms"]
        halo_overlap = (
            min(max(1.0 - self.stats["halo_wait_ms"] / halo_ms, 0.0), 1.0)
            if halo_ms > 0.0
            else 0.0
        )
        return {
            "size": len(self._cache),
            "capacity": self.plan_cache_size,
            **self.stats,
            "chunk_hit_rate": (
                self.stats["chunk_hits"] / accesses if accesses else 0.0
            ),
            "prefetch_overlap": overlap,
            "halo_overlap": halo_overlap,
        }

    def shard_report(self) -> Optional[Dict[str, object]]:
        """Shard economics (edge balance, halo volume) of the most recently
        planned sharded request; None when nothing sharded is cached."""
        for _, _, engine in reversed(list(self._cache.values())):
            if isinstance(engine, ShardedAmpleEngine):
                return engine.shard_report()
        return None
