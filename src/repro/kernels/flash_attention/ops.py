"""jit'd public wrapper: [B,S,H,hd] layout in, Pallas flash kernel inside."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.backend import resolve_interpret
from repro.kernels.flash_attention.flash_attention import flash_attention_call

__all__ = ["flash_attention"]


@functools.partial(jax.jit, static_argnames=("causal", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, interpret: bool | None = None):
    """q [B,S,H,hd], k/v [B,T,KV,hd] -> [B,S,H,hd]."""
    interpret = resolve_interpret(interpret)
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    out = flash_attention_call(qt, kt, vt, causal=causal, interpret=interpret)
    return out.transpose(0, 2, 1, 3)
