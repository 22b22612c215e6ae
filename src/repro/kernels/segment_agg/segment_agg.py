"""Pallas TPU kernel: event-driven gather + segment-reduce (the AGE).

This kernel is the hardware heart of the reproduction — it implements, in TPU
terms, all three of AMPLE's circuit mechanisms at once:

* **Address Queue → Message Queue** (Figure 3): the tile's neighbour indices
  arrive via *scalar prefetch* (SMEM, available before the grid step runs) and
  drive per-row async DMAs from HBM into a VMEM message buffer.
* **Fetch-Tag prefetch / partial response** (§3.3): the gather for tile t+1 is
  *started* before tile t is reduced, into the alternate half of a
  double-buffered VMEM scratch — memory latency hides behind compute exactly
  as the Feature Bank hides it behind aggregation.
* **Aggregation NoC → MXU** (§3.2): the per-tile segment reduction is cast as
  a one-hot × messages matmul, P[s,e] = coeff[e]·(seg[e]==s), so the MXU does
  the permutation-invariant sum at full throughput instead of a lane-serial
  scatter.

Tile shapes are static (from the ExecutionPlan), so the kernel is a fixed
pipeline; the irregularity lives entirely in the prefetched index stream.

Layout (one call covers one group of at most ``TILE_GROUP`` tiles):
  grid = (D // BD, G)   — t varies fastest, so the double buffer alternates
                          across consecutive tiles within one feature block.
  x         : ANY (HBM) f32[N, 1, D]           (row on a leading, untiled
                                                axis: one row is one DMA)
  gather_idx: scalar-prefetch int32[G, E]      (G·E·4 bytes of SMEM)
  coeff     : VMEM f32[1, 1, E] per step
  seg_ids   : VMEM int32[1, 1, E] per step
  parts     : VMEM out f32[1, S, BD] per step
  scratch   : xbuf f32[2, E, 1, BD], sem DMA[2]

SMEM holds only one group's indices, so any tile count compiles with one
kernel shape per group size; ``scan_tile_groups`` walks the groups.
"""
from __future__ import annotations

import functools
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "gather_segment_tiles",
    "gather_tile_rows",
    "pad_rows",
    "scan_tile_groups",
    "DEFAULT_BLOCK_D",
    "TILE_GROUP",
]

DEFAULT_BLOCK_D = 512
# Tiles per kernel call. 128 tiles of 256 lanes prefetch 128 KiB of indices
# into SMEM (1 MiB on v5e); the whole [T, E] stream did not fit past T≈1000.
TILE_GROUP = 128


def pad_rows(x: jnp.ndarray, d_pad: int) -> jnp.ndarray:
    """f32[N, D] → f32[N, 1, d_pad]: the row-DMA source layout."""
    n, d = x.shape
    if d_pad != d:
        x = jnp.pad(x, ((0, 0), (0, d_pad - d)))
    return x.reshape(n, 1, d_pad)


def gather_tile_rows(idx_ref, x_hbm, xbuf, sems, *, t, num_tiles, col0, width):
    """Double-buffered row gather of tile ``t``; returns its rows f32[E, width].

    Lane ``i`` of a tile DMAs row ``idx_ref[t, i]`` (columns ``col0`` to
    ``col0 + width``) into ``xbuf[slot, i]``. The first tile of a call fetches
    synchronously; every step starts the next tile's copies before waiting
    on its own, so the gather of t+1 overlaps the reduction of t.
    """
    e = xbuf.shape[1]

    def row_copy(tile, lane, slot):
        row = idx_ref[tile, lane]
        return pltpu.make_async_copy(
            x_hbm.at[pl.ds(row, 1), :, pl.ds(col0, width)],
            xbuf.at[slot, pl.ds(lane, 1)],
            sems.at[slot],
        )

    def start_gather(tile, slot):
        def body(i, c):
            row_copy(tile, i, slot).start()
            return c

        jax.lax.fori_loop(0, e, body, 0)

    def wait_gather(tile, slot):
        def body(i, c):
            row_copy(tile, i, slot).wait()
            return c

        jax.lax.fori_loop(0, e, body, 0)

    slot = jax.lax.rem(t, 2)

    @pl.when(t == 0)
    def _():
        start_gather(0, 0)

    @pl.when(t + 1 < num_tiles)
    def _():
        start_gather(t + 1, 1 - slot)

    wait_gather(t, slot)
    return xbuf[slot].reshape(e, width)


def _kernel(idx_ref, x_hbm, coeff_ref, segs_ref, parts_ref, xbuf, sems, *, bd: int):
    j = pl.program_id(0)  # feature block
    t = pl.program_id(1)  # tile (fastest)
    e = coeff_ref.shape[-1]
    s = parts_ref.shape[1]
    xb = gather_tile_rows(
        idx_ref, x_hbm, xbuf, sems,
        t=t, num_tiles=pl.num_programs(1), col0=j * bd, width=bd,
    )

    # Segment reduce on the MXU: P[s, e] = coeff[e] * (seg_ids[e] == s).
    s_iota = jax.lax.broadcasted_iota(jnp.int32, (s, e), 0)
    p = jnp.where(s_iota == segs_ref[0], coeff_ref[0], 0.0)
    parts_ref[0] = jnp.dot(
        p, xb, precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )


@functools.partial(
    jax.jit, static_argnames=("segments_per_tile", "block_d", "interpret")
)
def gather_segment_tiles(
    x_rows: jnp.ndarray,  # f32[N, 1, D_pad] (``pad_rows``), D_pad % block_d == 0
    gather_idx: jnp.ndarray,  # int32[G, E]
    coeff: jnp.ndarray,  # f32[G, E]
    seg_ids: jnp.ndarray,  # int32[G, E]
    *,
    segments_per_tile: int,
    block_d: int,
    interpret: bool,
) -> jnp.ndarray:
    """Per-tile partial sums f32[G, S, D_pad] for one group of tiles."""
    d_pad = x_rows.shape[-1]
    g, e = gather_idx.shape
    s = segments_per_tile
    bd = block_d
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(d_pad // bd, g),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),  # x stays in HBM
            # index maps receive the scalar-prefetch ref as a trailing arg
            pl.BlockSpec((1, 1, e), lambda j, tt, idx: (tt, 0, 0)),  # coeff
            pl.BlockSpec((1, 1, e), lambda j, tt, idx: (tt, 0, 0)),  # seg_ids
        ],
        out_specs=pl.BlockSpec((1, s, bd), lambda j, tt, idx: (tt, 0, j)),
        scratch_shapes=[
            pltpu.VMEM((2, e, 1, bd), jnp.float32),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    return pl.pallas_call(
        functools.partial(_kernel, bd=bd),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((g, s, d_pad), jnp.float32),
        interpret=interpret,
        name="ample_gather_segment_agg",
    )(gather_idx, x_rows, coeff[:, None, :], seg_ids[:, None, :])


def scan_tile_groups(
    step: Callable,
    init,
    tiles: Sequence[jnp.ndarray],
    fills: Sequence,
):
    """``lax.scan`` of ``step(carry, *group_tiles)`` over groups of tiles.

    ``tiles`` share a leading tile axis T. T ≤ ``TILE_GROUP`` runs one step over
    the exact arrays; otherwise T is padded to a multiple of ``TILE_GROUP`` with
    ``fills`` (one per array: padding tiles must be inert — coeff 0 and the
    scatter sentinel as output node) and each step sees ``TILE_GROUP`` tiles.
    Returns the final carry.
    """
    t, group = tiles[0].shape[0], TILE_GROUP
    if t <= group:
        return step(init, *tiles)
    n_groups = -(-t // group)
    pad = n_groups * group - t
    grouped = []
    for a, fill in zip(tiles, fills):
        if pad:
            a = jnp.concatenate(
                [a, jnp.full((pad,) + a.shape[1:], fill, a.dtype)]
            )
        grouped.append(a.reshape((n_groups, group) + a.shape[1:]))

    def body(carry, grp):
        return step(carry, *grp), None

    out, _ = jax.lax.scan(body, init, tuple(grouped))
    return out
