"""Load-time int8 weight repacking for the Pallas quant matmul.

``quant_matmul_kernel_call`` pads the weight to its block grid and streams
``(bk, bn)`` blocks through a strided ``BlockSpec`` on **every call** — per
call, the [K, N] operand is re-padded and the DMA engine walks a 2-D stride
pattern. Marlin (GPTQ) solves the same problem on GPU by rewriting the
weight into the kernel's native tile order once at load time
(``gptq_marlin_repack.cu``); this is the TPU analogue:

    int8[K, N]  →  int8[K/bk, N/bn, bk, bn]   (tile-major, zero-padded once)

so each grid step's weight block is one contiguous ``(1, 1, bk, bn)`` slab —
no per-call transpose or padding, and the index map degenerates to a direct
tile lookup. The block sizes are derived exactly as the unpacked kernel
derives them from (K, N), so a repacked weight computes **bitwise-identical
int32** results (integer arithmetic, same block accumulation order).

The engine calls ``repack_weight`` once per weight inside its ``_weight_q``
cache; every subsequent FTE matmul on that weight skips straight to the
kernel.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.quant_matmul.quant_matmul import _ceil, _rup

__all__ = ["RepackedWeight", "repack_weight", "quant_matmul_repacked_call"]


class RepackedWeight(NamedTuple):
    """A weight laid out in the quant-matmul kernel's preferred tiling."""

    tiles: jnp.ndarray  # int8[K/bk, N/bn, bk, bn]
    k: int  # true (unpadded) K
    n: int  # true (unpadded) N
    block_k: int
    block_n: int


def repack_weight(
    w_q: jnp.ndarray,  # int8[K, N]
    *,
    block_n: int = 256,
    block_k: int = 512,
) -> RepackedWeight:
    """One-time layout transform into the kernel's (bk, bn) tile order.

    Block sizes match ``quant_matmul_kernel_call``'s derivation from (K, N),
    so the repacked kernel walks the identical block decomposition.
    """
    k, n = w_q.shape
    bk, bn = min(block_k, _rup(k)), min(block_n, _rup(n))
    kp, np_ = _ceil(k, bk) * bk, _ceil(n, bn) * bn
    if (kp, np_) != (k, n):
        w_q = jnp.pad(w_q, ((0, kp - k), (0, np_ - n)))
    tiles = w_q.reshape(kp // bk, bk, np_ // bn, bn).transpose(0, 2, 1, 3)
    return RepackedWeight(tiles=tiles, k=k, n=n, block_k=bk, block_n=bn)


def _kernel(a_ref, b_ref, out_ref, acc_ref):
    k = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(k == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        a_ref[...], b_ref[0, 0], preferred_element_type=jnp.int32
    )

    @pl.when(k == nk - 1)
    def _():
        out_ref[...] = acc_ref[...]


@functools.partial(
    jax.jit,
    static_argnames=("k", "n", "block_k", "block_n", "block_m", "interpret"),
)
def _repacked_call(
    a_q: jnp.ndarray,  # int8[M, K]
    tiles: jnp.ndarray,  # int8[K/bk, N/bn, bk, bn]
    *,
    k: int,
    n: int,
    block_k: int,
    block_n: int,
    block_m: int,
    interpret: bool,
) -> jnp.ndarray:
    m = a_q.shape[0]
    bk, bn = block_k, block_n
    kp, np_ = tiles.shape[0] * bk, tiles.shape[1] * bn
    bm = min(block_m, _rup(m))
    mp = _ceil(m, bm) * bm
    if (mp, kp) != a_q.shape:
        a_q = jnp.pad(a_q, ((0, mp - m), (0, kp - a_q.shape[1])))
    out = pl.pallas_call(
        _kernel,
        grid=(mp // bm, np_ // bn, kp // bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            # direct tile lookup — the repack already ordered the blocks
            pl.BlockSpec((1, 1, bk, bn), lambda i, j, kk: (kk, j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.int32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        interpret=interpret,
        name="ample_quant_matmul_repacked",
    )(a_q, tiles)
    return out[:m, :n]


def quant_matmul_repacked_call(
    a_q: jnp.ndarray,
    packed: RepackedWeight,
    *,
    block_m: int = 256,
    interpret: bool = True,
) -> jnp.ndarray:
    """int32[M, N] = a_q @ W from the pre-tiled layout; pads only ``a_q``."""
    if a_q.shape[1] != packed.k:
        raise ValueError(
            f"activation K={a_q.shape[1]} does not match repacked weight "
            f"K={packed.k}"
        )
    return _repacked_call(
        a_q,
        packed.tiles,
        k=packed.k,
        n=packed.n,
        block_k=packed.block_k,
        block_n=packed.block_n,
        block_m=block_m,
        interpret=interpret,
    )
