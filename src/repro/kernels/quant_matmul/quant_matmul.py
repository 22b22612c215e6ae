"""Pallas TPU kernel: int8 × int8 → int32 blocked matmul (the int8 FTE stream).

The transformation phase of unprotected (Degree-Quant int8) nodes runs here:
symmetric-quantized activations against per-channel-quantized weights, int32
accumulation, dequant outside. On real TPU the MXU executes int8 at twice the
bf16 rate, which is the throughput half of the paper's mixed-precision win
(the other half — 4× lighter gather traffic — lives in the AGE).

Blocking: grid = (M/BM, N/BN, K/BK), K fastest. A VMEM int32 accumulator is
zeroed at k==0 and flushed to the output on the last K step, so the output
block is written exactly once (standard TPU matmul pipeline; Mosaic overlaps
the HBM streams of A/B blocks with MXU work across grid steps).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["quant_matmul_kernel_call"]


def _kernel(a_ref, b_ref, out_ref, acc_ref):
    k = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(k == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # int8 operands straight into the MXU; Mosaic has no int32 matmul.
    acc_ref[...] += jnp.dot(
        a_ref[...], b_ref[...], preferred_element_type=jnp.int32
    )

    @pl.when(k == nk - 1)
    def _():
        out_ref[...] = acc_ref[...]


@functools.partial(
    jax.jit, static_argnames=("block_m", "block_n", "block_k", "interpret")
)
def quant_matmul_kernel_call(
    a_q: jnp.ndarray,  # int8[M, K]
    b_q: jnp.ndarray,  # int8[K, N]
    *,
    block_m: int = 256,
    block_n: int = 256,
    block_k: int = 512,
    interpret: bool = True,
) -> jnp.ndarray:
    """int32[M, N] = a_q @ b_q with int32 accumulation. Pads to block grid."""
    m, k = a_q.shape
    k2, n = b_q.shape
    assert k == k2, (k, k2)
    bm, bn, bk = min(block_m, _rup(m)), min(block_n, _rup(n)), min(block_k, _rup(k))
    mp, np_, kp = _ceil(m, bm) * bm, _ceil(n, bn) * bn, _ceil(k, bk) * bk
    if (mp, kp) != (m, k):
        a_q = jnp.pad(a_q, ((0, mp - m), (0, kp - k)))
    if (kp, np_) != (k, n):
        b_q = jnp.pad(b_q, ((0, kp - k), (0, np_ - n)))
    out = pl.pallas_call(
        _kernel,
        grid=(mp // bm, np_ // bn, kp // bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.int32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        interpret=interpret,
        name="ample_quant_matmul",
    )(a_q, b_q)
    return out[:m, :n]


def _ceil(a: int, b: int) -> int:
    return -(-a // b)


def _rup(x: int, mult: int = 128) -> int:
    """Round up to the MXU lane multiple (int8 tiles want 128-aligned dims)."""
    return max(mult, _ceil(x, mult) * mult)
