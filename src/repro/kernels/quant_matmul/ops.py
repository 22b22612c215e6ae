"""jit'd public wrapper for the int8 FTE kernel.

``interpret=None`` compiles the kernel on a TPU and interprets it on the CPU
(``kernels.backend.resolve_interpret``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.backend import resolve_interpret
from repro.kernels.quant_matmul.quant_matmul import quant_matmul_kernel_call
from repro.kernels.quant_matmul.repack import (
    RepackedWeight,
    quant_matmul_repacked_call,
    repack_weight,
)

__all__ = [
    "quant_matmul",
    "quant_matmul_repacked",
    "repack_weight",
    "RepackedWeight",
]


@functools.partial(jax.jit, static_argnames=("interpret",))
def quant_matmul(
    a_q: jnp.ndarray, b_q: jnp.ndarray, *, interpret: bool | None = None
) -> jnp.ndarray:
    """int32 = int8 @ int8 through the Pallas kernel."""
    return quant_matmul_kernel_call(
        a_q, b_q, interpret=resolve_interpret(interpret)
    )


def quant_matmul_repacked(
    a_q: jnp.ndarray,
    packed: RepackedWeight,
    *,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """int32 = int8 @ repacked int8 weight — bitwise == ``quant_matmul``
    on the unpacked layout (same blocks, integer accumulation), minus the
    per-call weight pad/transpose."""
    return quant_matmul_repacked_call(
        a_q, packed, interpret=resolve_interpret(interpret)
    )
