"""Public wrappers for the fused multi-head attention kernel.

``attend_tiles`` is the one-launch GAT layer: raw (pre-LeakyReLU) scores in
tile layout, head-stacked embeddings in, softmax-normalized aggregates out.
The Pallas kernel emits per-tile softmax partials (tile-local max ``m``,
exp-sum ``l``, weighted numerator ``a``); the cross-tile combine here is the
flash-attention identity at the partial-response scatter:

    M[n]     = max over tiles of m                      (scatter-max)
    L[n]     = Σ l · exp(m − M[n])                      (rescaled scatter-add)
    A[n]     = Σ a · exp(m − M[n])
    out[n]   = A[n] / L[n]

which equals the globally max-shifted softmax aggregate exactly (up to the
float re-association of summing tiles in a different grouping than the
oracle's two global passes).

``aggregate_tiles_mh`` is the multi-head analogue of ``ops.aggregate_tiles``
for already-normalized per-head coefficients. Both run the kernel one group
of tiles at a time (``segment_agg.scan_tile_groups``); the fused path takes
the node max M from the tile-layout scores up front, so each group's
partials rescale and scatter-add at once and no group's partials outlive
its step. ``interpret=None`` compiles on a TPU and interprets on the CPU.
Head packing pads dh up to a 128-lane multiple so each DMA'd row is MXU/VPU
aligned; the pad is sliced off after combine.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.backend import resolve_interpret
from repro.kernels.segment_agg.attn_kernel import (
    fused_attention_tiles,
    gather_weighted_tiles_mh,
)
from repro.kernels.segment_agg.segment_agg import pad_rows, scan_tile_groups

__all__ = ["attend_tiles", "aggregate_tiles_mh", "combine_attention", "pack_heads"]


def _dhp(dh: int) -> int:
    return max(128, ((dh + 127) // 128) * 128)


def pack_heads(z: jnp.ndarray) -> jnp.ndarray:
    """f32[N, H, dh] → f32[N, H·dhp] with dh zero-padded to a 128 multiple."""
    n, h, dh = z.shape
    dhp = _dhp(dh)
    if dhp != dh:
        z = jnp.pad(z, ((0, 0), (0, 0), (0, dhp - dh)))
    return z.reshape(n, h * dhp)


def _finite_or_zero(big_m: jnp.ndarray) -> jnp.ndarray:
    return jnp.where(jnp.isfinite(big_m), big_m, 0.0)


def _rescaled_add(acc, m, l, a, out_node, big_m):
    """Scatter-add one set of tile partials, each rescaled to the node max."""
    big_l, big_a = acc
    t, s, h = m.shape
    flat = out_node.reshape(t * s)
    # Empty-segment partials carry m = −inf → scale 0, so they vanish here.
    scale = jnp.exp(m.reshape(t * s, h) - big_m[flat])
    big_l = big_l.at[flat].add(l.reshape(t * s, h) * scale)
    big_a = big_a.at[flat].add(a.reshape(t * s, h, -1) * scale[:, :, None])
    return big_l, big_a


def _normalize(big_l, big_a, *, num_nodes: int, dh: int) -> jnp.ndarray:
    denom = jnp.where(big_l > 0, big_l, 1.0)
    return (big_a / denom[:, :, None])[:num_nodes, :, :dh]


def combine_attention(
    m: jnp.ndarray,  # f32[T, S, H]
    l: jnp.ndarray,  # f32[T, S, H]
    a: jnp.ndarray,  # f32[T, S, H, dhp]
    out_node: jnp.ndarray,  # int32[T, S]
    *,
    num_nodes: int,
    dh: int,
) -> jnp.ndarray:
    """Cross-tile log-sum-exp combine → f32[num_nodes, H, dh]."""
    t, s, h = m.shape
    big_m = jnp.full((num_nodes + 1, h), -jnp.inf).at[out_node.reshape(t * s)].max(
        m.reshape(t * s, h)
    )
    zeros = (
        jnp.zeros((num_nodes + 1, h)),
        jnp.zeros((num_nodes + 1, h, a.shape[-1])),
    )
    big_l, big_a = _rescaled_add(zeros, m, l, a, out_node, _finite_or_zero(big_m))
    return _normalize(big_l, big_a, num_nodes=num_nodes, dh=dh)


@functools.partial(
    jax.jit,
    static_argnames=("num_nodes", "segments_per_tile", "leaky_slope", "interpret"),
)
def attend_tiles(
    z: jnp.ndarray,  # f32[N, H, dh]
    gather_idx: jnp.ndarray,  # int32[T, E]
    scores_t: jnp.ndarray,  # f32[T, E, H] raw scores, −inf on padding lanes
    coeff: jnp.ndarray,  # f32[T, E] static lane coeff
    seg_ids: jnp.ndarray,  # int32[T, E]
    out_node: jnp.ndarray,  # int32[T, S]
    *,
    num_nodes: int,
    segments_per_tile: int,
    leaky_slope: float,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Fused GAT layer: softmax(LeakyReLU(scores)) aggregate, one launch.

    Returns f32[num_nodes, H, dh].
    """
    interpret = resolve_interpret(interpret)
    n, h, dh = z.shape
    x_rows = pad_rows(pack_heads(z), h * _dhp(dh))
    s = segments_per_tile
    # Head-major lane operands: one lane-dense [1, E] row per head.
    sc_h = jnp.swapaxes(scores_t, 1, 2)  # [T, H, E]
    # The node max over every lane aggregating into the node equals the max
    # of the kernel's tile-local maxima, so it can be taken up front.
    act = jnp.where(sc_h >= 0.0, sc_h, leaky_slope * sc_h)
    lane_node = jnp.take_along_axis(out_node, seg_ids, axis=1)  # [T, E]
    big_m = jnp.full((num_nodes + 1, h), -jnp.inf).at[lane_node.reshape(-1)].max(
        jnp.swapaxes(act, 1, 2).reshape(-1, h)
    )
    big_m = _finite_or_zero(big_m)

    def step(acc, gi, sc, cf, si, on):
        m, l, a = fused_attention_tiles(
            x_rows, gi, sc, cf, si,
            segments_per_tile=s, leaky_slope=leaky_slope, interpret=interpret,
        )
        g = m.shape[0]
        return _rescaled_add(acc, m, l, a.reshape(g, s, h, -1), on, big_m)

    big_l, big_a = scan_tile_groups(
        step,
        (
            jnp.zeros((num_nodes + 1, h)),
            jnp.zeros((num_nodes + 1, h, _dhp(dh))),
        ),
        (gather_idx, sc_h, coeff, seg_ids, out_node),
        (0, -jnp.inf, 0.0, s - 1, num_nodes),
    )
    return _normalize(big_l, big_a, num_nodes=num_nodes, dh=dh)


@functools.partial(
    jax.jit, static_argnames=("num_nodes", "segments_per_tile", "interpret")
)
def aggregate_tiles_mh(
    x: jnp.ndarray,  # f32[N, H, dh]
    gather_idx: jnp.ndarray,  # int32[T, E]
    coeff: jnp.ndarray,  # f32[T, E, H] per-head lane coefficients
    seg_ids: jnp.ndarray,  # int32[T, E]
    out_node: jnp.ndarray,  # int32[T, S]
    *,
    num_nodes: int,
    segments_per_tile: int,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Multi-head event-driven aggregation → f32[num_nodes, H, dh]."""
    interpret = resolve_interpret(interpret)
    n, h, dh = x.shape
    d = h * _dhp(dh)
    x_rows = pad_rows(pack_heads(x), d)
    s = segments_per_tile

    def step(out, gi, cf, si, on):
        parts = gather_weighted_tiles_mh(
            x_rows, gi, cf, si, segments_per_tile=s, interpret=interpret
        )
        return out.at[on.reshape(-1)].add(parts.reshape(-1, d))

    out = scan_tile_groups(
        step,
        jnp.zeros((num_nodes + 1, d), x.dtype),
        (gather_idx, jnp.swapaxes(coeff, 1, 2), seg_ids, out_node),
        (0, 0.0, s - 1, num_nodes),
    )
    return out[:num_nodes].reshape(num_nodes, h, -1)[:, :, :dh]
