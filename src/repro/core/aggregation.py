"""Aggregation Engine (AGE) — device-side execution of the schedules.

Three execution paths mirror the paper's comparison:

* ``aggregate_edge_tiles``  — event-driven path (AMPLE): ``lax.scan`` over the
  planner's dense edge tiles; each step gathers a tile of neighbour embeddings
  (HBM→VMEM stream in the Pallas version), reduces by local segment, and
  adds (``op="sum"``) or maxes (``op="max"``, feature-wise, jnp path only)
  the partial results into the tile's window of a segment-row accumulator
  (partial-response combining). Compute ∝ E.
* ``aggregate_bucket_plan`` — degree-bucketed padding (≤2× waste); sum, mean
  or max.
* ``aggregate_padded_plan`` — HyGCN-style double-buffer baseline, one padded
  dense batch at a time; its wasted lanes are the pipeline gaps AMPLE removes.

All paths produce identical results (property-tested); they differ only in
lane economics, which the benchmarks measure.

The per-edge ``coeff`` folds the aggregation function into the plan:
sum → 1, mean → 1/deg, GCN → 1/√(d̂_i d̂_j). Invalid lanes carry coeff 0, and
every mode gives a real edge a nonzero coeff, so a max reads the coeff as the
lane mask alone.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import scheduler as sched
from repro.core.quantization import QuantParams, compute_scale_zp, dequantize, quantize

__all__ = [
    "DeviceTilePlan",
    "to_device_plan",
    "segment_rows",
    "live_rows",
    "tile_edge_coeff",
    "aggregate_edge_tiles",
    "aggregate_bucket_plan",
    "aggregate_padded_plan",
    "aggregate_mixed_precision",
    "segment_max_edge_tiles",
    "edge_segment_sum_tiles",
    "dense_reference",
]


class DeviceTilePlan(NamedTuple):
    """jnp mirror of scheduler.EdgeTilePlan (tile leaves scanned over axis 0).

    ``row_start`` and ``node_row`` place the plan's live segments in
    *segment-row space* (``segment_rows``): each tile's live segments own the
    contiguous accumulator rows ``[row_start[t], row_start[t] + k_t)``, so
    the scans combine a tile's partial results with one window update
    instead of a scatter into node space, and ``node_row`` gathers the rows
    back into node order at the end.

    ``edge_ids`` is None when the plan was uploaded without the runtime-
    coefficient indirection (static-coeff modes never read it, and the array
    is as large as ``gather_idx`` — engines upload it on first use instead).
    """

    gather_idx: jnp.ndarray  # int32[T, E]
    coeff: jnp.ndarray  # f32[T, E]
    seg_ids: jnp.ndarray  # int32[T, E]
    out_node: jnp.ndarray  # int32[T, S]
    row_start: jnp.ndarray  # int32[T]: accumulator row of each tile's segment 0
    node_row: jnp.ndarray  # int32[num_nodes]: accumulator row of each node
    edge_ids: Optional[jnp.ndarray]  # int32[T, E]; -1 on padding lanes


def _tile_rows(out_node: np.ndarray, num_nodes: int):
    """(live segments per tile, whether segment 0 continues the previous
    tile's last live node), after checking that live segments are a prefix."""
    t, s = out_node.shape
    live = out_node != num_nodes
    k = live.sum(axis=1)
    if np.any((out_node < 0) | (out_node > num_nodes)) or not np.array_equal(
        live, np.arange(s) < k[:, None]
    ):
        raise ValueError(
            "tile plan breaks the segment-row invariant: each tile's live "
            "segments must be the prefix 0..k-1 of node ids below num_nodes"
        )
    last = out_node[np.arange(t), np.maximum(k - 1, 0)]
    cont = np.zeros(t, bool)
    cont[1:] = (k[1:] > 0) & (k[:-1] > 0) & (out_node[1:, 0] == last[:-1])
    return k, cont


def live_rows(plan: sched.EdgeTilePlan) -> int:
    """Accumulator rows a plan's live segments occupy: live segments less
    continuations (one per node the plan gives any edge)."""
    k, cont = _tile_rows(np.asarray(plan.out_node), plan.num_nodes)
    return int(k.sum() - cont.sum())


def segment_rows(plan: sched.EdgeTilePlan) -> Tuple[np.ndarray, np.ndarray]:
    """Host derivation of ``(row_start int32[T], node_row int32[num_nodes])``.

    Rows follow tile order: tile ``t``'s live segments take the rows after
    tile ``t - 1``'s, except that a segment 0 continuing tile ``t - 1``'s
    last live node (a node split across consecutive tiles, the planner's
    partial response) reuses that node's row. Every node the plan covers
    then owns exactly one row in ``[0, R)``; each uncovered node gets a
    spare row of its own from ``R + S`` on, beyond any tile's window, so
    the accumulator needs ``num_nodes + S`` rows.

    Every plan producer keeps the invariant this relies on
    (``build_edge_tile_plan``, ``concat_tile_plans``, ``split_plan_by_halo``,
    ``pack_tiles_by_chunk``); a plan that breaks it — a node in two
    non-adjacent tiles, or twice in one — raises ``ValueError``.
    """
    out_node = np.asarray(plan.out_node)
    num_nodes = plan.num_nodes
    s = out_node.shape[1]
    k, cont = _tile_rows(out_node, num_nodes)
    row_start = np.cumsum(k - cont) - k
    rows = int(np.sum(k - cont))
    t_idx, s_idx = np.nonzero(out_node != num_nodes)
    nodes = out_node[t_idx, s_idx]
    seg_row = row_start[t_idx] + s_idx
    node_row = np.full(num_nodes, -1, np.int64)
    node_row[nodes] = seg_row
    if not np.array_equal(node_row[nodes], seg_row):
        raise ValueError(
            "tile plan breaks the segment-row invariant: a node's segments "
            "lie in non-adjacent tiles, so it would need two accumulator rows"
        )
    free = node_row < 0
    node_row[free] = rows + s + np.arange(int(free.sum()))
    return row_start.astype(np.int32), node_row.astype(np.int32)


def to_device_plan(
    plan: sched.EdgeTilePlan, *, with_edge_ids: bool = True
) -> DeviceTilePlan:
    row_start, node_row = segment_rows(plan)
    return DeviceTilePlan(
        gather_idx=jnp.asarray(plan.gather_idx, jnp.int32),
        coeff=jnp.asarray(plan.coeff, jnp.float32),
        seg_ids=jnp.asarray(plan.seg_ids, jnp.int32),
        out_node=jnp.asarray(plan.out_node, jnp.int32),
        row_start=jnp.asarray(row_start),
        node_row=jnp.asarray(node_row),
        edge_ids=(
            jnp.asarray(plan.edge_ids, jnp.int32) if with_edge_ids else None
        ),
    )


def tile_edge_coeff(
    dplan: DeviceTilePlan, edge_coeff: jnp.ndarray, *, fill: float = 0.0
) -> jnp.ndarray:
    """Scatter a per-edge runtime matrix into tile layout: f32/…[T, E(, H)].

    ``edge_coeff`` is indexed by graph edge position (the space
    ``EdgeTilePlan.edge_ids`` maps lanes into); padding lanes (edge id -1)
    read ``fill``. This is the runtime half of the coefficient indirection:
    the tile arrays stay structure-keyed while the values change per request.

    ``edge_coeff`` may carry trailing dims — ``[E, H]`` for per-head
    attention coefficients scatters every head in one gather, yielding the
    ``[T, lanes, H]`` tile layout the vectorized softmax/aggregate passes
    consume (the 1-D case is bitwise-unchanged).
    """
    if dplan.edge_ids is None:
        raise ValueError(
            "device plan was uploaded without edge_ids; rebuild it with "
            "to_device_plan(plan, with_edge_ids=True) to use runtime "
            "coefficients"
        )
    e = edge_coeff.shape[0]
    padded = jnp.concatenate(
        [edge_coeff, jnp.full((1,) + edge_coeff.shape[1:], fill, edge_coeff.dtype)]
    )
    idx = jnp.where(dplan.edge_ids < 0, e, dplan.edge_ids)
    return padded[idx]


@partial(
    jax.jit, static_argnames=("num_nodes", "segments_per_tile", "use_kernel", "op")
)
def aggregate_edge_tiles(
    x: jnp.ndarray,
    dplan: DeviceTilePlan,
    *,
    num_nodes: int,
    segments_per_tile: int,
    use_kernel: bool = False,
    edge_coeff: Optional[jnp.ndarray] = None,
    out_init: Optional[jnp.ndarray] = None,
    op: str = "sum",
) -> jnp.ndarray:
    """Event-driven aggregation: scan tiles, segment-reduce, window-add.

    ``op="max"`` is the feature-wise neighbour max (GraphSAGE-pool): each
    step gathers its tile's rows, sets padding lanes (static coeff 0) to
    −inf, takes a segment max into ``[S, …]`` and maxes it into the window,
    so a node split across tiles combines by max. The plan's coefficients
    are read as the lane mask only. A node that receives no message reads 0.
    jnp path only, without ``edge_coeff`` or ``out_init``.

    ``use_kernel`` routes the per-tile reduction through the Pallas AGE kernel
    (kernels/segment_agg); the default path is pure jnp and serves as its
    always-on oracle.

    ``edge_coeff`` supplies a runtime per-edge coefficient vector (f32[E] in
    graph edge space); it is scattered into tile layout through the plan's
    ``edge_ids`` and **multiplied** with the static coeff. Plans compiled in
    ``"runtime"`` mode carry static coeff 1 on every real lane, so the
    runtime vector takes effect verbatim there (``1.0 * c == c`` bitwise);
    padding lanes are 0 in both factors.

    Multi-head layout: ``edge_coeff`` f32[E, H] with ``x`` f32[N, H, dh]
    aggregates every head in ONE tile scan — per-head coefficients broadcast
    over the head's feature slice, and each head's lane/segment reduction
    order is identical to its solo 1-D run (bitwise per head on this path).

    ``out_init`` (f32[num_nodes, …]) seeds the accumulator instead of
    zeros — the continuation hook of the split interior/boundary execution
    (``scheduler.split_plan_by_halo``): the boundary scan picks up exactly
    where the interior scan left off, so split == unsplit bitwise. jnp path
    only (the Pallas kernel owns its accumulator).
    """
    if op == "max":
        if use_kernel:
            raise ValueError(
                "op='max' has no Pallas AGE kernel (use_kernel): the kernel "
                "only sums; run the max on the jnp path"
            )
        if edge_coeff is not None or out_init is not None:
            raise ValueError(
                "op='max' takes neither edge_coeff nor out_init: the max "
                "reads the plan's coefficients as a lane mask and runs unsplit"
            )

        def partial_maxes(gather_idx, coeff, seg_ids):
            gathered = x[gather_idx]  # [E, D] or [E, H, dh]
            real = (coeff != 0).reshape(coeff.shape + (1,) * (gathered.ndim - 1))
            return jax.ops.segment_max(
                jnp.where(real, gathered, -jnp.inf),
                seg_ids,
                num_segments=segments_per_tile,
            )  # [S, …]

        return _window_scan(
            partial_maxes,
            (dplan.gather_idx, dplan.coeff, dplan.seg_ids),
            dplan,
            num_nodes=num_nodes,
            segments_per_tile=segments_per_tile,
            like=x,
            op="max",
            uncovered=0.0,
        )
    if op != "sum":
        raise ValueError(f"unknown aggregation op {op!r}; have 'sum', 'max'")
    coeff = dplan.coeff
    if edge_coeff is not None:
        tc = tile_edge_coeff(dplan, edge_coeff)  # [T, E] or [T, E, H]
        coeff = coeff[..., None] * tc if tc.ndim == 3 else coeff * tc
    if use_kernel:
        if out_init is not None:
            raise ValueError(
                "out_init continuation is only supported on the jnp path; "
                "run the kernel path unsplit"
            )
        if coeff.ndim == 3:
            from repro.kernels.segment_agg import attn_ops

            return attn_ops.aggregate_tiles_mh(
                x,
                dplan.gather_idx,
                coeff,
                dplan.seg_ids,
                dplan.out_node,
                num_nodes=num_nodes,
                segments_per_tile=segments_per_tile,
            )
        from repro.kernels.segment_agg import ops as seg_ops

        if x.ndim == 3:
            # head-uniform coefficients: heads are just feature columns
            n, h, dh = x.shape
            flat = seg_ops.aggregate_tiles(
                x.reshape(n, h * dh),
                dplan.gather_idx,
                coeff,
                dplan.seg_ids,
                dplan.out_node,
                num_nodes=num_nodes,
                segments_per_tile=segments_per_tile,
            )
            return flat.reshape(num_nodes, h, dh)
        return seg_ops.aggregate_tiles(
            x,
            dplan.gather_idx,
            coeff,
            dplan.seg_ids,
            dplan.out_node,
            num_nodes=num_nodes,
            segments_per_tile=segments_per_tile,
        )

    def partial_sums(gather_idx, coeff, seg_ids):
        gathered = x[gather_idx]  # [E, D] or [E, H, dh]
        cf = coeff.reshape(coeff.shape + (1,) * (gathered.ndim - coeff.ndim))
        return jax.ops.segment_sum(
            gathered * cf, seg_ids, num_segments=segments_per_tile
        )  # [S, …]

    return _window_scan(
        partial_sums,
        (dplan.gather_idx, coeff, dplan.seg_ids),
        dplan,
        num_nodes=num_nodes,
        segments_per_tile=segments_per_tile,
        like=x,
        op="sum",
        init=out_init,
    )


# op -> (combine, accumulator fill, value masked into sentinel segments)
_WINDOW_OPS = {
    "sum": (jnp.add, 0.0, -0.0),
    "max": (jnp.maximum, -jnp.inf, -jnp.inf),
}


def _window_scan(
    partial: Callable[..., jnp.ndarray],
    tiles: tuple,
    dplan: DeviceTilePlan,
    *,
    num_nodes: int,
    segments_per_tile: int,
    like: jnp.ndarray,
    op: str,
    init: Optional[jnp.ndarray] = None,
    uncovered: Optional[float] = None,
) -> jnp.ndarray:
    """Scan the tiles, combining each tile's ``[S, …]`` partial results into
    a window of a segment-row accumulator; return it gathered to node order.

    The accumulator holds ``num_nodes + S`` rows (``segment_rows``), set to
    the op's identity (0 for ``sum``, −inf for ``max``) or, for the
    rows of ``init``'s nodes, seeded from ``init`` (the continuation of a
    split interior/boundary run). ``uncovered`` instead seeds the spare rows
    of the nodes no tile covers, which lie past the last window (from
    ``max(row_start) + S`` on), so those nodes read it and no window
    touches it. Each step reads the S rows at
    ``row_start[t]``, combines the tile's partials in and writes them back:
    one contiguous in-place update where a scatter into node space took one
    row update per segment. Sentinel segments are masked to the exact
    identity first (−0.0 for ``sum``: ``x + (-0.0) == x`` bitwise for every
    x), so a non-finite value that only padding lanes read reaches no row,
    and every row sees the same sequence of operations as the node-space
    scatter did — the two are bitwise equal.
    """
    s = segments_per_tile
    trailing = like.shape[1:]
    combine, fill, blank = _WINDOW_OPS[op]
    acc = jnp.full((num_nodes + s,) + trailing, fill, like.dtype)
    if uncovered is not None:
        spare = jnp.arange(num_nodes + s) >= jnp.max(dplan.row_start, initial=0) + s
        acc = jnp.where(
            spare.reshape((-1,) + (1,) * len(trailing)),
            jnp.asarray(uncovered, like.dtype),
            acc,
        )
    if init is not None:
        acc = acc.at[dplan.node_row].set(init.astype(like.dtype))

    def body(acc, tile):
        *tile_args, out_node, row0 = tile
        part = partial(*tile_args)
        live = (out_node != num_nodes).reshape((s,) + (1,) * len(trailing))
        part = jnp.where(live, part, jnp.asarray(blank, like.dtype))
        window = jax.lax.dynamic_slice_in_dim(acc, row0, s)
        acc = jax.lax.dynamic_update_slice_in_dim(
            acc, combine(window, part), row0, 0
        )
        return acc, None

    acc, _ = jax.lax.scan(body, acc, tiles + (dplan.out_node, dplan.row_start))
    return acc[dplan.node_row]


def aggregate_bucket_plan(
    x: jnp.ndarray,
    plan: sched.BucketPlan,
    *,
    op: str = "sum",
) -> jnp.ndarray:
    """Degree-bucketed aggregation. op ∈ {sum, mean, max}.

    mean/GCN normalisation is normally folded into coeff; ``op='mean'`` here
    divides by the true lane count instead (used by GraphSAGE whose mean is
    over the *messages*, after φ). ``max`` masks padding lanes to -inf.
    """
    n = plan.num_nodes
    d = x.shape[1]
    if op == "max":
        out = jnp.full((n + 1, d), -jnp.inf, x.dtype)
    else:
        out = jnp.zeros((n + 1, d), x.dtype)
    for b in plan.buckets:
        gi = jnp.asarray(b.gather_idx)  # [M, C]
        cf = jnp.asarray(b.coeff)  # [M, C]
        ids = jnp.asarray(b.node_ids, jnp.int32)
        gathered = x[gi]  # [M, C, D]
        if op == "max":
            masked = jnp.where(cf[..., None] != 0, gathered, -jnp.inf)
            red = jnp.max(masked, axis=1)
            out = out.at[ids].max(red)
        elif op == "mean":
            cnt = jnp.maximum((cf != 0).sum(axis=1, keepdims=True), 1)
            red = (gathered * (cf != 0)[..., None]).sum(axis=1) / cnt
            out = out.at[ids].add(red)
        else:
            red = (gathered * cf[..., None]).sum(axis=1)
            out = out.at[ids].add(red)
    out = out[:n]
    if op == "max":
        out = jnp.where(jnp.isfinite(out), out, 0.0)
    return out


def aggregate_padded_plan(x: jnp.ndarray, plan: sched.PaddedPlan) -> jnp.ndarray:
    """Double-buffer baseline: one padded batch at a time (distinct shapes per
    batch — exactly the recompile/stall economics of static batching)."""
    n = plan.num_nodes
    d = x.shape[1]
    out = jnp.zeros((n, d), x.dtype)
    for b in plan.batches:
        gi = jnp.asarray(b.gather_idx)
        cf = jnp.asarray(b.coeff)
        ids = jnp.asarray(b.node_ids, jnp.int32)
        red = (x[gi] * cf[..., None]).sum(axis=1)
        out = out.at[ids].set(red)
    return out


@partial(jax.jit, static_argnames=("num_nodes", "segments_per_tile"))
def segment_max_edge_tiles(
    scores: jnp.ndarray,
    dplan: DeviceTilePlan,
    *,
    num_nodes: int,
    segments_per_tile: int,
) -> jnp.ndarray:
    """Destination-segment max of a per-edge vector, over the event-driven
    tiles: f32[N] (−inf for nodes this plan gives no edges).

    The max-shift pass of a numerically stable segment softmax (GAT): scores
    are scattered into tile layout through ``edge_ids`` (padding lanes read
    −inf), reduced per segment, and combined across split tiles by a window
    max — the partial-response mechanism with max in place of add.

    ``scores`` may be f32[E, H]: all heads reduce in the same scan
    (→ f32[N, H]), each head's column bitwise-equal to its solo 1-D pass.
    """
    sc = tile_edge_coeff(dplan, scores, fill=-jnp.inf)
    return _window_scan(
        lambda sc_t, seg_ids: jax.ops.segment_max(
            sc_t, seg_ids, num_segments=segments_per_tile
        ),
        (sc, dplan.seg_ids),
        dplan,
        num_nodes=num_nodes,
        segments_per_tile=segments_per_tile,
        like=scores,
        op="max",
    )


@partial(jax.jit, static_argnames=("num_nodes", "segments_per_tile"))
def edge_segment_sum_tiles(
    values: jnp.ndarray,
    dplan: DeviceTilePlan,
    *,
    num_nodes: int,
    segments_per_tile: int,
) -> jnp.ndarray:
    """Destination-segment sum of a per-edge vector over the tiles: f32[N].

    The denominator pass of the segment softmax: exp-shifted scores scatter
    through ``edge_ids`` (padding lanes read 0) and accumulate exactly like
    the aggregation scan, so split nodes combine by the same partial-response
    window add.

    ``values`` may be f32[E, H] (→ f32[N, H], one scan for all heads).
    """
    v = tile_edge_coeff(dplan, values, fill=0.0)
    return _window_scan(
        lambda v_t, seg_ids: jax.ops.segment_sum(
            v_t, seg_ids, num_segments=segments_per_tile
        ),
        (v, dplan.seg_ids),
        dplan,
        num_nodes=num_nodes,
        segments_per_tile=segments_per_tile,
        like=values,
        op="sum",
    )


def aggregate_mixed_precision(
    x: jnp.ndarray,
    plans: Dict[str, sched.EdgeTilePlan],
    *,
    num_nodes: int,
    use_kernel: bool = False,
    qp: Optional[QuantParams] = None,
    device_plans: Optional[Dict[str, DeviceTilePlan]] = None,
    edge_coeff: Optional[jnp.ndarray] = None,
    op: str = "sum",
) -> jnp.ndarray:
    """Mixed-precision AGE: the float plan consumes fp32 embeddings; the int8
    plan consumes int8-quantized embeddings (4× lighter gather traffic — the
    bandwidth win the paper banks on), dequantized on-chip before accumulate.

    The two streams write disjoint node sets, so the combined output is just
    the sum of the two scatter targets. That holds for ``op="max"`` too:
    each stream's max gives 0 on the nodes it does not cover, and the int8
    stream maxes the dequantized messages (rounding is monotone, so the max
    of the rounded rows is the rounded max).

    ``qp`` overrides the activation scale/zero-point (per-call min/max
    calibration otherwise) — the engine passes its per-plan static quant state
    here, and the sharded executor a globally calibrated qp so every shard
    quantizes identically. ``device_plans`` supplies already-uploaded
    ``DeviceTilePlan`` mirrors keyed like ``plans`` (host→device conversion is
    per-plan-static and cacheable). ``edge_coeff`` is the runtime per-edge
    coefficient vector (graph edge space) both precision streams scatter
    through their ``edge_ids`` maps — each plan covers a disjoint destination
    subset, so one vector feeds both. A 2-D ``edge_coeff`` (f32[E, H]) with
    ``x`` f32[N, H, dh] runs the multi-head layout through both streams.
    """
    device_plans = device_plans or {}

    def dplan(tag):
        return device_plans.get(tag) or to_device_plan(plans[tag])

    out = jnp.zeros((num_nodes,) + x.shape[1:], jnp.float32)
    if "float" in plans:
        p = plans["float"]
        out = out + aggregate_edge_tiles(
            x,
            dplan("float"),
            num_nodes=num_nodes,
            segments_per_tile=p.segments_per_tile,
            use_kernel=use_kernel,
            edge_coeff=edge_coeff,
            op=op,
        )
    if "int8" in plans:
        p = plans["int8"]
        if qp is None:
            qp = compute_scale_zp(x, symmetric=True)
        xq = quantize(x, qp)
        xdq = dequantize(xq, qp)  # on-chip dequant after int8 gather
        out = out + aggregate_edge_tiles(
            xdq,
            dplan("int8"),
            num_nodes=num_nodes,
            segments_per_tile=p.segments_per_tile,
            use_kernel=use_kernel,
            edge_coeff=edge_coeff,
            op=op,
        )
    for tag, p in plans.items():
        if tag not in ("float", "int8"):
            raise ValueError(f"unknown precision tag {tag!r}")
    return out


def dense_reference(x: jnp.ndarray, adjacency: np.ndarray) -> jnp.ndarray:
    """O(N²) oracle: A @ X with A[i,j] = coeff of edge j→i (tests only)."""
    return jnp.asarray(adjacency) @ x
