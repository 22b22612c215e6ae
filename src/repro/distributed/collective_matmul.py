"""Collective matmul: overlap TP collectives with MXU work (beyond-paper opt).

XLA schedules the TP all-gather/reduce-scatter around each sharded matmul
back-to-back: AG, then dot. The *collective matmul* (Wang et al., ASPLOS'23;
used by MaxText/Megatron) decomposes the collective into a ring of
``ppermute`` steps and multiplies each arriving chunk immediately — the
transfer of chunk i+1 rides under the matmul of chunk i, hiding up to
(n-1)/n of the collective term behind compute.

Expressed with ``shard_map`` so the schedule is explicit rather than left to
the XLA latency-hiding scheduler. These are the §Perf iteration levers for
collective-bound cells; numerics are validated against plain sharded matmuls
in tests on a faked multi-device backend.

``allgather_matmul``      y[M, N/n]  = (AG_rows x)[M, K] @ w[K, N/n]
                          (x arrives row-sharded — the SP residual layout)
``reduce_scatter_matmul`` y[M/n, N]  = RS_rows(Σ_k x[M, K/n] @ w[K/n, N])
                          (the down-projection / row-parallel side)
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

__all__ = ["allgather_matmul", "reduce_scatter_matmul"]


def allgather_matmul(x, w, mesh, *, axis: str = "model"):
    """Ring-pipelined ``all_gather(x, rows) @ w``.

    x: [M, K] sharded on rows over ``axis`` (local [M/n, K]);
    w: [K, N] sharded on cols over ``axis`` (local [K, N/n]);
    y: [M, N] sharded on cols (local [M, N/n]).

    At ring step s, device d holds the x block that originated at device
    (d + s) mod n; it multiplies it against its local w and writes the
    product into the matching row band of y while the block moves on.
    """
    n = mesh.shape[axis]

    def local(x_loc, w_loc):
        idx = jax.lax.axis_index(axis)
        m_loc = x_loc.shape[0]

        def step(s, carry):
            y, blk = carry
            src = jax.lax.rem(idx + s, n)  # owner of the block we hold
            band = jnp.einsum("mk,kn->mn", blk, w_loc)
            y = jax.lax.dynamic_update_slice_in_dim(y, band, src * m_loc, axis=0)
            blk = jax.lax.ppermute(
                blk, axis, [(i, (i - 1) % n) for i in range(n)]
            )
            return y, blk

        y0 = jax.lax.pcast(
            jnp.zeros((m_loc * n, w_loc.shape[-1]), x_loc.dtype), (axis,),
            to="varying",
        )
        y, _ = jax.lax.fori_loop(0, n, step, (y0, x_loc))
        return y

    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(axis, None), P(None, axis)),
        out_specs=P(None, axis),
    )
    return fn(x, w)


def reduce_scatter_matmul(x, w, mesh, *, axis: str = "model"):
    """Ring-pipelined ``reduce_scatter_rows(x @ w)`` for K-sharded operands.

    x: [M, K] sharded on K (local [M, K/n]); w: [K, N] sharded on K rows
    (local [K/n, N]); y: [M, N] sharded on rows (local [M/n, N]).

    The local partial product is computed one M-band at a time in ring order
    (receive-accumulate-forward), so each band's transfer overlaps the next
    band's matmul. After n steps device d holds Σ_j x_j[band_d] @ w_j.
    """
    n = mesh.shape[axis]

    def local(x_loc, w_loc):
        idx = jax.lax.axis_index(axis)
        m = x_loc.shape[0]
        chunk = m // n

        def step(s, acc):
            acc = jax.lax.ppermute(
                acc, axis, [(i, (i + 1) % n) for i in range(n)]
            )
            c = jax.lax.rem(idx - s - 1 + 2 * n, n)  # band index this step
            blk = jax.lax.dynamic_slice_in_dim(x_loc, c * chunk, chunk, axis=0)
            return acc + jnp.einsum("mk,kn->mn", blk, w_loc)

        acc0 = jax.lax.pcast(
            jnp.zeros((chunk, w_loc.shape[-1]), x_loc.dtype), (axis,),
            to="varying",
        )
        return jax.lax.fori_loop(0, n, step, acc0)

    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(None, axis), P(axis, None)),
        out_specs=P(axis, None),
    )
    return fn(x, w)
