"""jit'd public wrapper for the AGE kernel: tiles in, node aggregates out.

The Pallas kernel produces per-tile partial sums for one group of tiles; the
partial-response combine (scatter-add of split-node partials) runs in XLA
after each group, so no more than one group's partials exist at a time.
``interpret=None`` compiles the kernel on a TPU and interprets it on the CPU
(``kernels.backend.resolve_interpret``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.backend import resolve_interpret
from repro.kernels.segment_agg.segment_agg import (
    DEFAULT_BLOCK_D,
    gather_segment_tiles,
    pad_rows,
    scan_tile_groups,
)

__all__ = ["aggregate_tiles"]


def _feature_blocks(d: int, block_d: int = DEFAULT_BLOCK_D):
    """(block width, padded width) for D columns: lane-dense 128 multiples."""
    d128 = max(128, -(-d // 128) * 128)
    bd = min(block_d, d128)
    return bd, -(-d128 // bd) * bd


@functools.partial(
    jax.jit,
    static_argnames=("num_nodes", "segments_per_tile", "block_d", "interpret"),
)
def aggregate_tiles(
    x: jnp.ndarray,
    gather_idx: jnp.ndarray,
    coeff: jnp.ndarray,
    seg_ids: jnp.ndarray,
    out_node: jnp.ndarray,
    *,
    num_nodes: int,
    segments_per_tile: int,
    block_d: int = DEFAULT_BLOCK_D,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Event-driven aggregation via the Pallas AGE kernel. f32[num_nodes, D]."""
    interpret = resolve_interpret(interpret)
    d = x.shape[1]
    bd, d_pad = _feature_blocks(d, block_d)
    x_rows = pad_rows(x, d_pad)
    s = segments_per_tile

    def step(out, gi, cf, si, on):
        parts = gather_segment_tiles(
            x_rows, gi, cf, si,
            segments_per_tile=s, block_d=bd, interpret=interpret,
        )
        return out.at[on.reshape(-1)].add(parts.reshape(-1, d_pad))

    out = scan_tile_groups(
        step,
        jnp.zeros((num_nodes + 1, d_pad), x.dtype),
        (gather_idx, coeff, seg_ids, out_node),
        (0, 0.0, s - 1, num_nodes),
    )
    return out[:num_nodes, :d]
