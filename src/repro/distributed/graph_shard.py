"""Sharded GNN layer execution — the cluster-level Feature Bank.

``ShardedAmpleEngine`` executes a ``ShardedExecutionPlan``: each shard owns an
edge-balanced node block (contiguous, or a min-cut assignment carried by
``Partition.order``); before aggregating, it fetches the embeddings of its
remote ("halo") neighbours — the distributed analogue of AMPLE's Feature Bank
fetching off-chip rows — then runs its own event-driven mixed-precision
aggregation over its local subgraph and writes exactly its owned output rows.
Per-node transformations (FTE) are row-parallel and stay on the regular
mixed-precision path.

Two execution backends, numerically interchangeable:

* **host loop** (default) — one shard at a time on the local device. Works on
  a single-device CPU, and is what the serving engine uses; the halo gather is
  an explicit ``x[halo_ids]`` row fetch. With ``halo_overlap`` the gather runs
  on a worker thread while the shard's *interior* tiles (no halo sources —
  ``scheduler.split_plan_by_halo``) aggregate in flight; the boundary tiles
  then continue from the interior accumulator, bitwise-identical to the
  unsplit scan. ``halo_ms``/``halo_wait_ms`` are wall-clock truth: the fetch
  is fenced and timestamped on the worker, the consumer measures its actual
  blocking wait — the same accounting contract as the out-of-core
  ``prefetch_overlap``.
* **shard_map** — SPMD over a 1-D ``("shard",)`` device mesh with one device
  per shard (CPU host-device simulation, as in ``test_distributed``). Owned
  rows live sharded; the halo exchange is a ``lax.all_gather`` of the owned
  blocks followed by a (owner, row) gather, and each device scans its own
  padded edge tiles. Runtime per-edge coefficients (GAT attention) ride along
  as a padded per-shard operand ``[K, e_max(, H)]`` scattered through the
  tiles' ``edge_ids`` — bitwise-equal to the host loop. Under
  ``halo_overlap`` the tile scan is split interior/boundary inside the SPMD
  body with the all-gather issued first, so the compiler is free to overlap
  the collective with the interior scan.

Activation quantization uses a *global* scale/zero-point (calibrated over the
full embedding matrix, exactly as the unsharded engine does), so every shard
quantizes identically and sharded output matches unsharded output to float
accumulation order.
"""
from __future__ import annotations

import dataclasses
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.aggregation import (
    aggregate_edge_tiles,
    aggregate_mixed_precision,
    to_device_plan,
)
from repro.core.message_passing import (
    AmpleEngine,
    ShardedExecutionPlan,
    compile_sharded_plans,
)
from repro.core import scheduler as sched
from repro.core.quantization import QuantParams, dequantize, quantize
from repro.graphs.csr import Graph
from repro.observe import trace as otrace

__all__ = ["ShardedAmpleEngine", "sharded_aggregate", "build_mesh_state"]


# One worker is enough: the host loop is serialized per shard, and a single
# thread lets shard k+1's halo fetch overlap shard k's boundary compute.
_HALO_POOL: Optional[ThreadPoolExecutor] = None


def _halo_pool() -> ThreadPoolExecutor:
    global _HALO_POOL
    if _HALO_POOL is None:
        _HALO_POOL = ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="halo"
        )
    return _HALO_POOL


def _note_halo(stats: Optional[Dict[str, float]], **delta: float) -> None:
    if stats is None:
        return
    for k, v in delta.items():
        stats[k] = stats.get(k, 0.0) + v


# ---------------------------------------------------------------------------
# Host-loop backend: one shard at a time on the local device
# ---------------------------------------------------------------------------


def _shard_state_entry(state: Dict, sp, mode: str, *, with_edge_ids: bool):
    """The per-shard device cache entry (local_ids, plans, dplans).

    One fill/upgrade rule for every consumer of the ``("host", fingerprint,
    mode)`` key: built on first use, and upgraded in place with the
    ``edge_ids`` indirection map when a runtime-coefficient pass needs it
    after static-coeff traffic populated the entry without one.
    """
    key = ("host", sp.fingerprint, mode)
    entry = state.get(key)
    if entry is None:
        plans = sp.plan.mode_plans.get(mode)
        if plans is None:
            raise KeyError(
                f"shard {sp.shard.index} was compiled for modes "
                f"{sp.plan.modes}, not {mode!r}; recompile the sharded "
                f"plan with this mode"
            )
        entry = (
            jnp.asarray(sp.shard.local_ids, jnp.int32),
            plans,
            {
                tag: to_device_plan(p, with_edge_ids=with_edge_ids)
                for tag, p in plans.items()
            },
        )
        state[key] = entry
    elif with_edge_ids and any(d.edge_ids is None for d in entry[2].values()):
        entry = (
            entry[0],
            entry[1],
            {tag: to_device_plan(p) for tag, p in entry[1].items()},
        )
        state[key] = entry
    return entry


def _local_edge_coeff(state: Dict, sp, edge_coeff: jnp.ndarray) -> jnp.ndarray:
    """This shard's slice of a global per-edge vector, on device.

    Contiguous partitions slice ``edge_range``; min-cut partitions gather
    through the shard's cached ``edge_idx`` map (global CSR positions in
    local edge order).
    """
    if sp.shard.edge_range is not None:
        e_lo, e_hi = sp.shard.edge_range
        return jax.lax.slice_in_dim(edge_coeff, e_lo, e_hi)
    key = ("edge_idx", sp.fingerprint)
    idx = state.get(key)
    if idx is None:
        idx = jnp.asarray(sp.shard.edge_idx, jnp.int32)
        state[key] = idx
    return edge_coeff[idx]


def _shard_split_entry(state: Dict, sp, mode: str, *, with_edge_ids: bool):
    """Interior/boundary split artifacts for the overlapped halo exchange.

    Per (shard, mode): owned/halo gather ids and the two plan halves per
    precision tag (empty halves omitted), with device mirrors. Built once,
    reused across requests like the unsplit entry.
    """
    key = ("split", sp.fingerprint, mode, bool(with_edge_ids))
    entry = state.get(key)
    if entry is None:
        _, plans, _ = _shard_state_entry(
            state, sp, mode, with_edge_ids=with_edge_ids
        )
        owned = sp.num_owned
        plans_int: Dict[str, sched.EdgeTilePlan] = {}
        plans_bnd: Dict[str, sched.EdgeTilePlan] = {}
        for tag, p in plans.items():
            p_int, p_bnd = sched.split_plan_by_halo(p, owned)
            if p_int.num_tiles:
                plans_int[tag] = p_int
            if p_bnd.num_tiles:
                plans_bnd[tag] = p_bnd
        entry = {
            "owned": jnp.asarray(sp.shard.local_ids[:owned], jnp.int32),
            "halo": jnp.asarray(sp.shard.local_ids[owned:], jnp.int32),
            "plans_int": plans_int,
            "plans_bnd": plans_bnd,
            "d_int": {
                t: to_device_plan(p, with_edge_ids=with_edge_ids)
                for t, p in plans_int.items()
            },
            "d_bnd": {
                t: to_device_plan(p, with_edge_ids=with_edge_ids)
                for t, p in plans_bnd.items()
            },
        }
        state[key] = entry
    elif with_edge_ids and any(
        d.edge_ids is None
        for d in list(entry["d_int"].values()) + list(entry["d_bnd"].values())
    ):
        entry = dict(
            entry,
            d_int={t: to_device_plan(p) for t, p in entry["plans_int"].items()},
            d_bnd={t: to_device_plan(p) for t, p in entry["plans_bnd"].items()},
        )
        state[key] = entry
    return entry


def _unshuffle(state: Dict, splan: ShardedExecutionPlan, stacked: jnp.ndarray):
    """Map shard-block-ordered rows back to global node order.

    Contiguous partitions concatenate back verbatim; permuted (min-cut)
    partitions apply the cached inverse permutation.
    """
    part = splan.partition
    if part.order is None:
        return stacked
    key = ("inv_order", splan.partition_fp)
    inv = state.get(key)
    if inv is None:
        inv = jnp.asarray(part._position, jnp.int32)
        state[key] = inv
    return stacked[inv]


def sharded_aggregate(
    x: jnp.ndarray,
    splan: ShardedExecutionPlan,
    *,
    mode: str,
    qp: Optional[QuantParams] = None,
    use_kernel: bool = False,
    device_state: Optional[Dict] = None,
    edge_coeff: Optional[jnp.ndarray] = None,
    overlap: bool = False,
    halo_stats: Optional[Dict[str, float]] = None,
    trace_id: str = "",
) -> jnp.ndarray:
    """Aggregate ``x`` shard by shard; returns the full [N, D] result.

    Per shard: gather owned + halo rows into local index space, run the
    shard's event-driven plan, keep the owned output rows. ``qp`` must be the
    globally calibrated activation scale/zp when the plan is mixed-precision
    (pass None for float-only plans). ``device_state`` caches per-shard
    uploaded artifacts across calls (the engine owns one). ``edge_coeff`` is
    a *global* runtime per-edge coefficient vector (f32[E] — or f32[E, H]
    with ``x`` f32[N, H, dh] for head-vectorized attention); each shard takes
    its local slice — ``edge_range`` when contiguous, the ``edge_idx`` gather
    otherwise — and scatters it through its local ``edge_ids`` map.

    ``overlap=True`` runs the split interior/boundary schedule: the halo row
    fetch is fenced on a worker thread while interior tiles aggregate, then
    boundary tiles continue from the interior accumulator
    (bitwise-identical to the unsplit scan — see
    ``scheduler.split_plan_by_halo``). ``halo_stats`` accumulates
    ``halo_ms`` / ``halo_wait_ms`` / ``halo_bytes`` / ``halo_exchanges``;
    ``halo_gather`` and ``halo_wait`` spans land on the trace when recording.
    The kernel path has no continuation hook, so ``use_kernel`` falls back
    to the unsplit schedule.
    """
    parts = []
    state = device_state if device_state is not None else {}
    with_eids = edge_coeff is not None
    rec = otrace.get_recorder()
    for sp in splan.shards:
        local_ids, plans, dplans = _shard_state_entry(
            state, sp, mode, with_edge_ids=with_eids
        )
        local_coeff = None
        if edge_coeff is not None:
            local_coeff = _local_edge_coeff(state, sp, edge_coeff)
        split_ok = (
            overlap
            and not use_kernel
            and sp.halo_size > 0
            and not ("int8" in plans and qp is None)
        )
        if not split_ok:
            x_local = x[local_ids]
            m = aggregate_mixed_precision(
                x_local,
                plans,
                num_nodes=sp.shard.num_local,
                use_kernel=use_kernel,
                qp=qp,
                device_plans=dplans,
                edge_coeff=local_coeff,
            )
            parts.append(m[: sp.num_owned])
            continue

        split = _shard_split_entry(state, sp, mode, with_edge_ids=with_eids)
        halo_ids = split["halo"]

        def fetch(halo_ids=halo_ids):
            t0 = time.perf_counter()
            h = x[halo_ids]
            h.block_until_ready()
            t1 = time.perf_counter()
            return h, t0, t1

        fut = _halo_pool().submit(fetch)
        x_owned = x[split["owned"]]
        zeros_h = jnp.zeros((sp.halo_size,) + x.shape[1:], x.dtype)
        x_int = jnp.concatenate([x_owned, zeros_h], axis=0)
        n_local = sp.shard.num_local
        partials: Dict[str, jnp.ndarray] = {}
        for tag in ("float", "int8"):
            p_int = split["plans_int"].get(tag)
            if tag not in plans or p_int is None:
                continue
            xin = (
                dequantize(quantize(x_int, qp), qp) if tag == "int8" else x_int
            )
            partials[tag] = aggregate_edge_tiles(
                xin,
                split["d_int"][tag],
                num_nodes=n_local,
                segments_per_tile=p_int.segments_per_tile,
                edge_coeff=local_coeff,
            )
        w0 = time.perf_counter()
        halo_buf, t0, t1 = fut.result()
        w1 = time.perf_counter()
        if rec.enabled:
            rec.add_span(
                "halo_gather", t0, t1, cat="halo", lane="halo",
                trace_id=trace_id, args={"shard": sp.shard.index},
            )
            rec.add_span(
                "halo_wait", w0, w1, cat="halo",
                trace_id=trace_id, args={"shard": sp.shard.index},
            )
        _note_halo(
            halo_stats,
            halo_ms=(t1 - t0) * 1e3,
            halo_wait_ms=(w1 - w0) * 1e3,
            halo_bytes=float(halo_buf.nbytes),
            halo_exchanges=1.0,
        )
        x_loc = jnp.concatenate([x_owned, halo_buf], axis=0)
        m = jnp.zeros((n_local,) + x.shape[1:], jnp.float32)
        for tag in ("float", "int8"):
            if tag not in plans:
                continue
            res = partials.get(tag)
            p_bnd = split["plans_bnd"].get(tag)
            if p_bnd is not None:
                xin = (
                    dequantize(quantize(x_loc, qp), qp)
                    if tag == "int8"
                    else x_loc
                )
                res = aggregate_edge_tiles(
                    xin,
                    split["d_bnd"][tag],
                    num_nodes=n_local,
                    segments_per_tile=p_bnd.segments_per_tile,
                    edge_coeff=local_coeff,
                    out_init=res,
                )
            if res is not None:
                m = m + res
        parts.append(m[: sp.num_owned])
    if not parts:
        return jnp.zeros_like(x)
    return _unshuffle(state, splan, jnp.concatenate(parts, axis=0))


# ---------------------------------------------------------------------------
# shard_map backend: one device per shard, all-gather halo exchange
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class _MeshState:
    """Shape-uniform (padded, stacked) device mirror of a ShardedExecutionPlan.

    ``groups`` holds one tile-array dict per execution phase: a single full
    group normally, or (interior, boundary) halves when the state was built
    with ``overlap=True``. Each tiles tuple is (gather, coeff, seg, out[,
    edge_ids]) — the edge-id stack rides along only when the state carries
    the runtime-coefficient operand.
    """

    p_max: int  # padded owned rows per shard
    h_max: int  # padded halo rows per shard
    e_max: int  # padded local edges per shard (runtime-coeff operand width)
    seg: int  # segments per tile
    owned: Tuple[int, ...]  # real owned count per shard
    pad_gather: np.ndarray  # int64[K * p_max] global row feeding each padded row
    halo_owner: np.ndarray  # int32[K, h_max]
    halo_idx: np.ndarray  # int32[K, h_max] row within the owner's padded block
    edge_gather: Optional[np.ndarray]  # int64[K, e_max] global edge per slot
    groups: Tuple[Dict[str, Tuple[np.ndarray, ...]], ...]
    with_edge_ids: bool
    overlap: bool

    @property
    def tags(self) -> Tuple[str, ...]:
        return tuple(sorted({t for g in self.groups for t in g}))


def build_mesh_state(
    splan: ShardedExecutionPlan,
    mode: str,
    *,
    with_edge_ids: bool = False,
    overlap: bool = False,
) -> _MeshState:
    """Pad per-shard plans to a common shape for SPMD execution.

    The padded local index space per shard is ``[0, p_max)`` owned rows
    (shard's own block) followed by ``[p_max, p_max + h_max)`` halo rows;
    tile gather indices are remapped from the compact local space accordingly.
    The scatter sentinel becomes row ``p_max + h_max`` (a scratch row sliced
    off on return). Padding tiles carry coeff 0 and sentinel outputs, so they
    aggregate nothing — lane waste, not wrong answers.

    ``with_edge_ids`` additionally stacks each tile's local edge ids (-1 on
    padding lanes) and the per-shard ``edge_gather`` map (global edge id per
    padded local edge slot, sentinel = E), so a runtime per-edge operand can
    be sliced host-side into ``[K, e_max(, H)]`` and scattered on device.
    ``overlap=True`` splits every shard plan into interior/boundary halves
    (run granularity — bitwise-safe) and emits two tile groups.
    """
    K = splan.num_shards
    part = splan.partition
    p_max = max((s.num_owned for s in splan.shards), default=1) or 1
    h_max = max((s.halo_size for s in splan.shards), default=0)
    l_pad = p_max + h_max

    pad_gather = np.zeros(K * p_max, np.int64)
    halo_owner = np.zeros((K, max(h_max, 1)), np.int32)
    halo_idx = np.zeros((K, max(h_max, 1)), np.int32)
    for k, sp in enumerate(splan.shards):
        pad_gather[k * p_max : k * p_max + sp.num_owned] = sp.shard.owned
        if sp.halo_size:
            halo_owner[k, : sp.halo_size] = part.owner_of(sp.shard.halo)
            halo_idx[k, : sp.halo_size] = part.rank_of(sp.shard.halo)

    e_max = max((s.shard.num_edges for s in splan.shards), default=1) or 1
    edge_gather = None
    if with_edge_ids:
        edge_gather = np.full((K, e_max), splan.num_edges, np.int64)
        for k, sp in enumerate(splan.shards):
            if sp.shard.edge_range is not None:
                e_lo, e_hi = sp.shard.edge_range
                edge_gather[k, : e_hi - e_lo] = np.arange(e_lo, e_hi)
            else:
                edge_gather[k, : sp.shard.num_edges] = sp.shard.edge_idx

    tags = sorted({t for s in splan.shards for t in s.plan.mode_plans[mode]})
    E = splan.cfg.edges_per_tile
    seg = None

    # per shard and tag: the plan halves to stack (one group, or two)
    n_groups = 2 if overlap else 1
    shard_tag_plans = [
        [dict() for _ in range(n_groups)] for _ in range(K)
    ]
    for k, sp in enumerate(splan.shards):
        for tag, p in sp.plan.mode_plans[mode].items():
            if seg is None:
                seg = p.segments_per_tile
            elif p.segments_per_tile != seg:
                raise ValueError("segments_per_tile must be uniform across tags")
            if overlap:
                p_int, p_bnd = sched.split_plan_by_halo(p, sp.num_owned)
                shard_tag_plans[k][0][tag] = p_int
                shard_tag_plans[k][1][tag] = p_bnd
            else:
                shard_tag_plans[k][0][tag] = p

    groups = []
    for gi_group in range(n_groups):
        tag_tiles: Dict[str, Tuple[np.ndarray, ...]] = {}
        for tag in tags:
            per_shard = [shard_tag_plans[k][gi_group].get(tag) for k in range(K)]
            t_max = max(
                (p.num_tiles for p in per_shard if p is not None), default=0
            )
            if t_max == 0:
                continue  # group contributes nothing for this tag
            gi = np.zeros((K, t_max, E), np.int32)
            cf = np.zeros((K, t_max, E), np.float32)
            si = np.full((K, t_max, E), (seg or E) - 1, np.int32)
            on = np.full((K, t_max, seg or E), l_pad, np.int32)
            ei = np.full((K, t_max, E), -1, np.int32)
            for k, (sp, p) in enumerate(zip(splan.shards, per_shard)):
                if p is None or p.num_tiles == 0:
                    continue
                owned = sp.num_owned
                # compact local space -> padded local space
                g_remap = np.where(
                    p.gather_idx < owned,
                    p.gather_idx,
                    p.gather_idx - owned + p_max,
                )
                o_remap = np.where(
                    p.out_node < owned,
                    p.out_node,
                    np.where(
                        p.out_node >= sp.shard.num_local,  # sentinel
                        l_pad,
                        p.out_node - owned + p_max,
                    ),
                )
                t = p.num_tiles
                gi[k, :t] = np.minimum(g_remap, max(l_pad - 1, 0))
                cf[k, :t] = p.coeff
                si[k, :t] = p.seg_ids
                on[k, :t] = o_remap
                if with_edge_ids:
                    ei[k, :t] = p.edge_ids
            tiles = (gi, cf, si, on) + ((ei,) if with_edge_ids else ())
            tag_tiles[tag] = tiles
        groups.append(tag_tiles)

    return _MeshState(
        p_max=p_max,
        h_max=h_max,
        e_max=e_max,
        seg=seg if seg is not None else E,
        owned=tuple(s.num_owned for s in splan.shards),
        pad_gather=pad_gather,
        halo_owner=halo_owner,
        halo_idx=halo_idx,
        edge_gather=edge_gather,
        groups=tuple(groups),
        with_edge_ids=with_edge_ids,
        overlap=overlap,
    )


def _make_shard_map_fn(
    state: _MeshState,
    mesh,
    *,
    x_ndim: int = 2,
    coeff_ndim: Optional[int] = None,
):
    """Build the jitted SPMD program for one mesh state.

    ``coeff_ndim`` is the rank of the global runtime-coefficient vector
    (1 for f32[E], 2 for f32[E, H]); None means no runtime operand.
    ``x_ndim`` distinguishes [N, D] from the multi-head [N, H, dh] layout —
    both run the same per-tile arithmetic as ``aggregate_edge_tiles``
    (coefficients broadcast over trailing dims), which is what keeps the
    mesh backend bitwise-equal to the host loop.
    """
    from jax.sharding import PartitionSpec as P

    seg, p_max, h_max, e_max = state.seg, state.p_max, state.h_max, state.e_max
    l_pad = p_max + h_max
    with_eids = state.with_edge_ids
    with_coeff = coeff_ndim is not None
    na = 5 if with_eids else 4
    tags = state.tags
    group_tags = tuple(
        tuple(t for t in tags if t in g) for g in state.groups
    )

    def body(xpad, howner, hidx, scale, zp, *rest):
        idx = 0
        ecoeff = None
        if with_coeff:
            ecoeff = rest[0][0]  # [e_max(, H)] this shard's padded slice
            idx = 1
        it = iter(rest[idx:])
        groups_t = []
        for gtags in group_tags:
            groups_t.append(
                {tag: tuple(next(it)[0] for _ in range(na)) for tag in gtags}
            )

        gathered = jax.lax.all_gather(xpad, "shard")  # [K, p_max, …]
        halo = gathered[howner[0], hidx[0]][:h_max]  # [h_max, …]
        xl_full = jnp.concatenate([xpad, halo], axis=0)  # [l_pad, …]
        qp = QuantParams(scale=scale, zero_point=zp)

        def xin_for(tag, xl):
            return dequantize(quantize(xl, qp), qp) if tag == "int8" else xl

        def run(tiles, xbuf, out):
            if with_eids:
                gi, cf, si, on, ei = tiles
            else:
                gi, cf, si, on = tiles
                ei = None
            if with_coeff:
                # identical precompute to aggregate_edge_tiles: pad slot at
                # e_max reads 0, then static coeff × runtime coeff.
                cl = jnp.concatenate(
                    [
                        ecoeff,
                        jnp.zeros((1,) + ecoeff.shape[1:], ecoeff.dtype),
                    ]
                )
                rc = cl[jnp.where(ei < 0, e_max, ei)]
                cf = cf[..., None] * rc if rc.ndim == 3 else cf * rc

            def step(out, t):
                g_, c_, s_, o_ = t
                gath = xbuf[g_]  # [E, …]
                c_r = c_.reshape(c_.shape + (1,) * (gath.ndim - c_.ndim))
                partial = jax.ops.segment_sum(
                    gath * c_r, s_, num_segments=seg
                )
                return out.at[o_].add(partial), None

            out, _ = jax.lax.scan(step, out, (gi, cf, si, on))
            return out

        tail = xpad.shape[1:]

        def zeros_acc():
            # each device scatters into its own accumulator: varying over
            # "shard", as the scan carry must be
            return jax.lax.pcast(
                jnp.zeros((l_pad + 1,) + tail, jnp.float32), ("shard",),
                to="varying",
            )

        m = zeros_acc()
        if state.overlap and len(groups_t) == 2:
            # interior first on [owned | zeros]: no data dependency on the
            # all-gather, so the collective overlaps the interior scan;
            # boundary continues from the interior accumulator (bitwise ==
            # the unsplit scan — run-granularity split).
            xl_int = jnp.concatenate(
                [xpad, jnp.zeros((h_max,) + tail, xpad.dtype)], axis=0
            )
            for tag in tags:
                acc = zeros_acc()
                if tag in groups_t[0]:
                    acc = run(groups_t[0][tag], xin_for(tag, xl_int), acc)
                if tag in groups_t[1]:
                    acc = run(groups_t[1][tag], xin_for(tag, xl_full), acc)
                m = m + acc
        else:
            for tag in tags:
                acc = zeros_acc()
                if tag in groups_t[0]:
                    acc = run(groups_t[0][tag], xin_for(tag, xl_full), acc)
                m = m + acc
        return m[:p_max]

    n_tile_arrays = sum(na * len(g) for g in group_tags)
    x_spec = P("shard", *([None] * (x_ndim - 1)))
    in_specs = [
        x_spec,  # xpad [K * p_max, …]
        P("shard", None),  # halo owner [K, h_max]
        P("shard", None),  # halo idx [K, h_max]
        P(),  # scale
        P(),  # zero point
    ]
    if with_coeff:
        in_specs.append(P("shard", *([None] * coeff_ndim)))
    in_specs.extend([P("shard", None, None)] * n_tile_arrays)
    mapped = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=x_spec,
    )
    return jax.jit(mapped)


# ---------------------------------------------------------------------------
# Engine facade
# ---------------------------------------------------------------------------


class ShardedAmpleEngine(AmpleEngine):
    """AmpleEngine over a partitioned graph: sharded AGE, row-parallel FTE.

    Drop-in for ``AmpleEngine`` wherever the model apply functions use it
    (``aggregate`` / ``transform`` / ``edge_softmax``), so gcn/gin/sage/gat
    run sharded without change. Construct from a compiled
    ``ShardedExecutionPlan``:

        splan = compile_sharded_plans(g, cfg, num_shards=4, modes=("gcn",))
        eng = ShardedAmpleEngine(g, splan)              # host loop
        eng = ShardedAmpleEngine(g, splan, mesh=mesh)   # shard_map SPMD

    ``mesh`` must be a 1-D ``("shard",)`` mesh with exactly one device per
    shard; without one, shards execute as a host loop (single-device
    simulation — identical numerics, no SPMD). ``halo_overlap=True`` enables
    the split interior/boundary schedule on both backends (bitwise-identical
    outputs); wall-clock halo accounting accumulates in ``halo_stats`` on
    the host loop (the mesh backend's exchange happens inside the SPMD
    program, so only ``halo_bytes`` is accounted there).
    """

    def __init__(
        self,
        g: Graph,
        plan: ShardedExecutionPlan,
        *,
        mesh=None,
        halo_overlap: bool = False,
    ):
        if plan.graph_fp != sched.graph_fingerprint(g):
            raise ValueError(
                f"sharded plan was compiled for a different graph structure "
                f"({plan.num_nodes} nodes, {plan.num_edges} edges vs "
                f"{g.num_nodes}, {g.num_edges}; fingerprints differ)"
            )
        if mesh is not None:
            if tuple(mesh.axis_names) != ("shard",):
                raise ValueError(f"mesh axes must be ('shard',), got {mesh.axis_names}")
            if mesh.devices.size != plan.num_shards:
                raise ValueError(
                    f"mesh has {mesh.devices.size} devices but the plan has "
                    f"{plan.num_shards} shards"
                )
            # The SPMD program's output is sliced and gathered eagerly on
            # return; that needs Auto axes (an Explicit-axis mesh, the
            # jax.make_mesh default, would ask every such op for a sharding).
            mesh = jax.sharding.Mesh(
                mesh.devices, mesh.axis_names,
                axis_types=(jax.sharding.AxisType.Auto,),
            )
        if halo_overlap and plan.cfg.use_kernel:
            raise ValueError(
                "halo_overlap needs the jnp aggregation path (the fused "
                "kernel has no continuation hook): clear gnn_use_kernel or "
                "gnn_halo_overlap"
            )
        self.graph = g
        self.cfg = plan.cfg
        self.plan = plan
        self.sharded_plan = plan
        self.mesh = mesh
        self.halo_overlap = bool(halo_overlap)
        self.precision_tags = plan.precision_tags
        self.node_groups = dict(plan.node_groups)
        self._plans = {}
        self._init_runtime_state()
        self._shard_state: Dict = {}
        self._mesh_exec: Dict[tuple, tuple] = {}
        #: wall-clock halo accounting, drained by the serving layer:
        #: halo_ms (fenced fetch), halo_wait_ms (consumer stall),
        #: halo_bytes, halo_exchanges.
        self.halo_stats: Dict[str, float] = {}
        #: set per request by the serving layer so halo spans join the trace
        self.trace_id: str = ""
        #: mesh backend: the device holding each shard's output block in the
        #: most recent SPMD call, in shard order (empty on the host loop)
        self.shard_devices: Tuple[str, ...] = ()

    def plans(self, mode: str):
        raise NotImplementedError(
            "a sharded engine holds one plan per shard, not a global plan; "
            "use sharded_plan.shards[k].plan.mode_plans[mode]"
        )

    # ----------------------------------------------------------------- AGE
    def aggregate(
        self,
        x: jnp.ndarray,
        *,
        mode: str = "sum",
        edge_coeff: Optional[jnp.ndarray] = None,
    ) -> jnp.ndarray:
        splan = self.sharded_plan
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
            for sp in splan.shards:
                self._require_edge_ids(
                    (mode, sp.shard.index), sp.plan.mode_plans.get(mode, {})
                )
        has_int8 = self.cfg.mixed_precision and any(
            "int8" in s.plan.mode_plans.get(mode, {}) for s in splan.shards
        )
        qp = self._activation_qp(lambda: x, "agg") if has_int8 else None
        if self.mesh is not None:
            return self._aggregate_shard_map(x, mode, qp, edge_coeff)
        return sharded_aggregate(
            x,
            splan,
            mode=mode,
            qp=qp,
            use_kernel=self.cfg.use_kernel,
            device_state=self._shard_state,
            edge_coeff=edge_coeff,
            overlap=self.halo_overlap,
            halo_stats=self.halo_stats,
            trace_id=self.trace_id,
        )

    # ------------------------------------------------ runtime coefficients
    def edge_softmax(
        self, scores: jnp.ndarray, *, mode: str = "runtime"
    ) -> jnp.ndarray:
        """Destination-segment softmax of per-edge scores, sharded: f32[E(, H)].

        Each destination node (and each edge) belongs to exactly one shard,
        so the segment-max and denominator passes run per shard over its
        local tiles and the owned rows map back to the global node order
        (through the partition's inverse permutation when non-contiguous);
        the exp-shift and final normalisation happen in global edge space.
        Matches the single-plan ``AmpleEngine.edge_softmax`` up to float
        accumulation order. ``scores`` f32[E, H] runs all heads in the same
        per-shard passes.
        """
        from repro.core.aggregation import (
            edge_segment_sum_tiles,
            segment_max_edge_tiles,
        )

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
        splan = self.sharded_plan
        for sp in splan.shards:
            self._require_edge_ids(
                (mode, sp.shard.index), sp.plan.mode_plans.get(mode, {})
            )

        def owned_pass(fn, vec, init):
            parts = []
            for sp in splan.shards:
                local = _local_edge_coeff(self._shard_state, sp, vec)
                plans = sp.plan.mode_plans.get(mode)
                if plans is None:
                    raise KeyError(
                        f"shard {sp.shard.index} was compiled for modes "
                        f"{sp.plan.modes}, not {mode!r}"
                    )
                acc = jnp.full(
                    (sp.shard.num_local,) + vec.shape[1:], init, jnp.float32
                )
                for tag, p in plans.items():
                    dplan = self._softmax_dplan(sp, mode, tag, p)
                    res = fn(
                        local,
                        dplan,
                        num_nodes=sp.shard.num_local,
                        segments_per_tile=p.segments_per_tile,
                    )
                    acc = (
                        jnp.maximum(acc, res)
                        if init == -jnp.inf
                        else acc + res
                    )
                parts.append(acc[: sp.num_owned])
            return _unshuffle(
                self._shard_state, splan, jnp.concatenate(parts, axis=0)
            )

        node_max = owned_pass(segment_max_edge_tiles, scores, -jnp.inf)
        node_max = jnp.where(jnp.isfinite(node_max), node_max, 0.0)
        dst = self.edge_endpoints()[1]
        ex = jnp.exp(scores - node_max[dst])
        denom = owned_pass(edge_segment_sum_tiles, ex, 0.0)
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
        """Sharded GAT attention on raw scores f32[E, H] / z f32[N, H, dh].

        Always the oracle decomposition (head-vectorized softmax, then the
        [E, H] weighted aggregate) — a shard's softmax partials are complete
        because every in-edge lives in its destination's shard, but the
        per-shard tile plans index local node space, so the single-launch
        fused kernel stays a single-plan fast path. Under ``use_kernel`` the
        weighted aggregate still runs the multi-head Pallas kernel per shard.
        On a mesh, the weighted aggregate runs the SPMD program with the
        attention matrix as the runtime operand.
        """
        scores = jnp.asarray(scores, jnp.float32)
        z = jnp.asarray(z, jnp.float32)
        e, n = self.graph.num_edges, self.graph.num_nodes
        if scores.ndim != 2 or scores.shape[0] != e:
            raise ValueError(
                f"scores must be [{e}, H], got {tuple(scores.shape)}"
            )
        if z.ndim != 3 or z.shape[0] != n or z.shape[1] != scores.shape[1]:
            raise ValueError(
                f"z must be [{n}, {scores.shape[1]}, dh], got "
                f"{tuple(z.shape)}"
            )
        act = jax.nn.leaky_relu(scores, leaky_slope)
        alpha = self.edge_softmax(act, mode=mode)
        return self.aggregate(z, mode=mode, edge_coeff=alpha)

    def _softmax_dplan(self, sp, mode: str, tag: str, plan):
        """Per-shard device plan mirror, shared with sharded_aggregate.

        The softmax passes scatter through ``edge_ids``, so an entry cached
        by static-coeff traffic (uploaded without the map) is upgraded here.
        """
        entry = _shard_state_entry(
            self._shard_state, sp, mode, with_edge_ids=True
        )
        return entry[2][tag]

    def _aggregate_shard_map(
        self,
        x: jnp.ndarray,
        mode: str,
        qp,
        edge_coeff: Optional[jnp.ndarray] = None,
    ) -> jnp.ndarray:
        """SPMD execution: one jitted program per (mode, operand layout).

        The runtime per-edge operand is sliced host-side into the padded
        per-shard stack ``[K, e_max(, H)]`` through the mesh state's
        ``edge_gather`` (padding slots read 0), then scattered through each
        tile's ``edge_ids`` on device — the same two-hop indirection the
        host loop uses, so outputs are bitwise-equal to it.
        """
        with_coeff = edge_coeff is not None
        key = (mode, with_coeff, x.ndim)
        if key not in self._mesh_exec:
            state = build_mesh_state(
                self.sharded_plan,
                mode,
                with_edge_ids=with_coeff,
                overlap=self.halo_overlap,
            )
            fn = _make_shard_map_fn(
                state,
                self.mesh,
                x_ndim=x.ndim,
                coeff_ndim=(edge_coeff.ndim if with_coeff else None),
            )
            tile_args = tuple(
                jnp.asarray(a)
                for g in state.groups
                for tag in state.tags
                if tag in g
                for a in g[tag]
            )
            self._mesh_exec[key] = (state, fn, tile_args)
        state, fn, tile_args = self._mesh_exec[key]
        if qp is None:  # float-only plans still feed the qp slots
            qp = QuantParams(
                scale=jnp.ones((), jnp.float32), zero_point=jnp.zeros((), jnp.float32)
            )
        xpad = x[jnp.asarray(state.pad_gather)]  # [K * p_max, …]
        args = [
            xpad,
            jnp.asarray(state.halo_owner),
            jnp.asarray(state.halo_idx),
            qp.scale,
            qp.zero_point,
        ]
        if with_coeff:
            padded = jnp.concatenate(
                [
                    edge_coeff,
                    jnp.zeros((1,) + edge_coeff.shape[1:], edge_coeff.dtype),
                ]
            )
            args.append(padded[jnp.asarray(state.edge_gather)])
        out = fn(*args, *tile_args)
        blocks = sorted(out.addressable_shards, key=lambda b: b.index[0].start or 0)
        self.shard_devices = tuple(str(b.device) for b in blocks)
        parts = [
            out[k * state.p_max : k * state.p_max + owned]
            for k, owned in enumerate(state.owned)
        ]
        if not parts:
            return jnp.zeros_like(x)
        halo_rows = sum(s.halo_size for s in self.sharded_plan.shards)
        _note_halo(
            self.halo_stats,
            halo_bytes=float(
                halo_rows * x.dtype.itemsize * int(np.prod(x.shape[1:]))
            ),
            halo_exchanges=1.0,
        )
        return _unshuffle(
            self._shard_state, self.sharded_plan, jnp.concatenate(parts, axis=0)
        )

    # ------------------------------------------------------------- metrics
    def shard_report(self) -> Dict[str, object]:
        """Cluster-level lane economics: work balance + halo traffic."""
        splan = self.sharded_plan
        return {
            "num_shards": splan.num_shards,
            "partitioner": splan.partition.kind,
            "edge_balance": splan.edge_balance,
            "halo_total": splan.halo_total,
            "halo_per_shard": [s.halo_size for s in splan.shards],
            "edges_per_shard": [s.num_edges for s in splan.shards],
            "owned_per_shard": [s.num_owned for s in splan.shards],
            "shard_devices": list(self.shard_devices),
        }


def make_sharded_engine(
    g: Graph,
    cfg=None,
    *,
    num_shards: Optional[int] = None,
    partition=None,
    partitioner: str = "edges",
    modes=("sum",),
    mesh=None,
    halo_overlap: bool = False,
) -> ShardedAmpleEngine:
    """Compile + wrap in one call (the non-serving convenience path)."""
    splan = compile_sharded_plans(
        g,
        cfg,
        num_shards=num_shards,
        partition=partition,
        partitioner=partitioner,
        modes=modes,
    )
    return ShardedAmpleEngine(g, splan, mesh=mesh, halo_overlap=halo_overlap)
