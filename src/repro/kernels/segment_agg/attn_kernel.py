"""Pallas TPU kernels: fused multi-head graph attention over edge tiles.

One tile scan replaces GAT's four (LeakyReLU → segment-max → exp →
segment-sum → weighted aggregate): each grid step gathers the tile's
neighbour embeddings for **all heads at once** (rows packed ``[N, H·dhp]``),
applies LeakyReLU to the pre-scattered raw scores, reduces a tile-local
softmax triple on-chip, and emits per-tile partials

    m[t, s, h]        — tile-local segment max of the activated scores
    l[t, s, h]        — Σ exp(score − m) over the segment's lanes
    a[t, s, h·dhp]    — Σ coeff·exp(score − m)·x[idx] (numerator partials)

The cross-tile combine (flash-attention-style log-sum-exp rescale at the
partial-response scatter) runs in XLA — see ``attn_ops.attend_tiles``. The
decomposition is exact: rescaling by ``exp(m − M_global)`` makes the combined
(l, a) equal to the globally max-shifted sums, so the fused path computes the
same stable softmax as the four-pass oracle (up to float re-association
across tiles, which is why parity tests use the dense-reference tolerance
rather than bitwise equality).

Gather scaffolding (scalar-prefetched indices driving double-buffered
per-row DMAs, one group of tiles per call) is ``segment_agg``'s — the AGE
mechanisms carry over; only the on-chip reduction changes. Per-lane operands
arrive head-major (``[T, H, E]``), so each head's scores or coefficients are
one lane-dense row; the heads are a static loop of [S, E] reductions and one
MXU matmul each against the head's 128-aligned column slice.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.segment_agg.segment_agg import gather_tile_rows

__all__ = ["fused_attention_tiles", "gather_weighted_tiles_mh"]


def _onehot(segs_ref, s: int, e: int):
    """bool[S, E]: lane e belongs to segment s."""
    return jax.lax.broadcasted_iota(jnp.int32, (s, e), 0) == segs_ref[0]


def _head_dot(oh, w_row, xb, hh: int, dhp: int):
    """Σ_lanes w·x for head ``hh``: [S, E] weights @ the head's [E, dhp] rows."""
    p = jnp.where(oh, w_row, 0.0)
    return jnp.dot(
        p, xb[:, hh * dhp : (hh + 1) * dhp],
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )


def _fused_kernel(
    idx_ref,
    x_hbm,
    scores_ref,
    coeff_ref,
    segs_ref,
    m_ref,
    l_ref,
    a_ref,
    xbuf,
    sems,
    *,
    h: int,
    dhp: int,
    slope: float,
):
    t = pl.program_id(0)
    e = coeff_ref.shape[-1]
    s = m_ref.shape[1]
    xb = gather_tile_rows(
        idx_ref, x_hbm, xbuf, sems,
        t=t, num_tiles=pl.num_programs(0), col0=0, width=h * dhp,
    )

    # LeakyReLU on raw scores; padding lanes arrive as −inf and stay −inf
    # (slope > 0), so they contribute exp(−inf − finite) = 0 downstream.
    sc = scores_ref[0]  # [H, E]
    sc = jnp.where(sc >= 0.0, sc, slope * sc)
    oh = _onehot(segs_ref, s, e)
    h_iota = jax.lax.broadcasted_iota(jnp.int32, (s, h), 1)
    m_all = jnp.zeros((s, h), jnp.float32)
    l_all = jnp.zeros((s, h), jnp.float32)
    for hh in range(h):
        row = sc[hh : hh + 1, :]  # [1, E]
        # Tile-local segment max, then back to lanes through the one-hot;
        # where(isfinite) keeps empty segments from leaking −inf.
        m = jnp.max(jnp.where(oh, row, -jnp.inf), axis=1, keepdims=True)
        m_fin = jnp.where(jnp.isfinite(m), m, 0.0)  # [S, 1]
        m_lane = jnp.sum(jnp.where(oh, m_fin, 0.0), axis=0, keepdims=True)
        p = jnp.exp(row - m_lane)  # [1, E]
        l = jnp.sum(jnp.where(oh, p, 0.0), axis=1, keepdims=True)
        m_all = jnp.where(h_iota == hh, m, m_all)
        l_all = jnp.where(h_iota == hh, l, l_all)
        # Numerator partials: static lane coeff multiplies post-softmax (the
        # oracle's aggregate semantics — the denominator stays Σ exp, unscaled).
        a_ref[0, :, hh * dhp : (hh + 1) * dhp] = _head_dot(
            oh, p * coeff_ref[0], xb, hh, dhp
        )
    m_ref[0] = m_all
    l_ref[0] = l_all


def _mh_kernel(
    idx_ref, x_hbm, coeff_ref, segs_ref, parts_ref, xbuf, sems, *, h: int, dhp: int
):
    t = pl.program_id(0)
    e = segs_ref.shape[-1]
    s = parts_ref.shape[1]
    xb = gather_tile_rows(
        idx_ref, x_hbm, xbuf, sems,
        t=t, num_tiles=pl.num_programs(0), col0=0, width=h * dhp,
    )
    oh = _onehot(segs_ref, s, e)
    w = coeff_ref[0]  # [H, E]
    for hh in range(h):
        parts_ref[0, :, hh * dhp : (hh + 1) * dhp] = _head_dot(
            oh, w[hh : hh + 1, :], xb, hh, dhp
        )


def _lane_spec(e: int):
    return pl.BlockSpec((1, 1, e), lambda tt, idx: (tt, 0, 0))


@functools.partial(
    jax.jit,
    static_argnames=("segments_per_tile", "leaky_slope", "interpret"),
)
def fused_attention_tiles(
    x_rows: jnp.ndarray,  # f32[N, 1, H·dhp] (head-packed, dh padded to dhp)
    gather_idx: jnp.ndarray,  # int32[G, E]
    scores_t: jnp.ndarray,  # f32[G, H, E] raw scores, −inf padding lanes
    coeff: jnp.ndarray,  # f32[G, E] static lane coeff
    seg_ids: jnp.ndarray,  # int32[G, E]
    *,
    segments_per_tile: int,
    leaky_slope: float,
    interpret: bool,
):
    """One fused pass over a group of tiles → softmax partials (m, l, a).

    Returns ``(m f32[G, S, H], l f32[G, S, H], a f32[G, S, H·dhp])``; the
    caller owns the cross-tile log-sum-exp combine and the dhp→dh unpad.
    """
    g, h, e = scores_t.shape
    s = segments_per_tile
    d = x_rows.shape[-1]
    dhp = d // h

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(g,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),  # x stays in HBM
            pl.BlockSpec((1, h, e), lambda tt, idx: (tt, 0, 0)),  # scores
            _lane_spec(e),  # coeff
            _lane_spec(e),  # seg_ids
        ],
        out_specs=(
            pl.BlockSpec((1, s, h), lambda tt, idx: (tt, 0, 0)),  # m
            pl.BlockSpec((1, s, h), lambda tt, idx: (tt, 0, 0)),  # l
            pl.BlockSpec((1, s, d), lambda tt, idx: (tt, 0, 0)),  # a
        ),
        scratch_shapes=[
            pltpu.VMEM((2, e, 1, d), jnp.float32),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    return pl.pallas_call(
        functools.partial(_fused_kernel, h=h, dhp=dhp, slope=leaky_slope),
        grid_spec=grid_spec,
        out_shape=(
            jax.ShapeDtypeStruct((g, s, h), jnp.float32),
            jax.ShapeDtypeStruct((g, s, h), jnp.float32),
            jax.ShapeDtypeStruct((g, s, d), jnp.float32),
        ),
        interpret=interpret,
        name="ample_fused_attention",
    )(gather_idx, x_rows, scores_t, coeff[:, None, :], seg_ids[:, None, :])


@functools.partial(
    jax.jit, static_argnames=("segments_per_tile", "interpret")
)
def gather_weighted_tiles_mh(
    x_rows: jnp.ndarray,  # f32[N, 1, H·dhp]
    gather_idx: jnp.ndarray,  # int32[G, E]
    coeff: jnp.ndarray,  # f32[G, H, E] per-head lane coefficients
    seg_ids: jnp.ndarray,  # int32[G, E]
    *,
    segments_per_tile: int,
    interpret: bool,
) -> jnp.ndarray:
    """Multi-head weighted segment reduce: f32[G, S, H·dhp] partials."""
    g, h, e = coeff.shape
    s = segments_per_tile
    d = x_rows.shape[-1]
    dhp = d // h

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(g,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((1, h, e), lambda tt, idx: (tt, 0, 0)),  # coeff
            _lane_spec(e),  # seg_ids
        ],
        out_specs=pl.BlockSpec((1, s, d), lambda tt, idx: (tt, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, e, 1, d), jnp.float32),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    return pl.pallas_call(
        functools.partial(_mh_kernel, h=h, dhp=dhp),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((g, s, d), jnp.float32),
        interpret=interpret,
        name="ample_gather_segment_agg_mh",
    )(gather_idx, x_rows, coeff, seg_ids[:, None, :])
