"""jit'd public wrapper for the SSD intra-chunk kernel (compiled on TPU, interpreted on CPU)."""
from __future__ import annotations

import functools

import jax

from repro.kernels.backend import resolve_interpret
from repro.kernels.ssd_scan.ssd_scan import ssd_intra_chunk_call

__all__ = ["ssd_intra_chunk"]


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssd_intra_chunk(cc, bc, xdt, acum, *, interpret: bool | None = None):
    return ssd_intra_chunk_call(
        cc, bc, xdt, acum, interpret=resolve_interpret(interpret)
    )
