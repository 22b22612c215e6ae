"""shard_map MoE: explicit event-driven EP dispatch (§Perf cell C iteration 2).

GSPMD resolves the batched scatter of the capacity dispatch by materializing
full [T, D] buffers and all-reducing them — measured 480 GB of f32 AR per
llama4 train step. This module replaces partitioner guesswork with the
explicit schedule, which is also the faithful NoC analogue: every data shard
runs its own nodeslot pool (local sort/rank/capacity — zero cross-shard
traffic), each model shard executes only its expert slice against the
*already model-replicated* token activations, and a single psum over "model"
assembles the combine — the only activation collective in the whole layer.

Communication per layer (per device):
  * expert-weight FSDP all-gather over "data"   (O(weights/TP), unavoidable)
  * one psum of [T_loc, D] over "model"          (the combine)
vs the GSPMD path's multiple full-[T, D] f32 all-reduces.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.lm.moe import moe_init  # noqa: F401 (same param layout)

__all__ = ["moe_apply_sharded", "sharded_applicable"]


def sharded_applicable(policy, num_experts: int, t: int, d_ff: int, tp_needed=None) -> bool:
    """shard_map path needs: a real mesh policy in TP mode and divisible
    tokens. Two variants: EP (experts % model axis == 0) or replicated-expert
    token-parallel (any expert count, tokens divisible by the whole mesh)."""
    mesh = getattr(policy, "mesh", None)
    if mesh is None or getattr(policy, "mode", "tp") != "tp":
        return False
    tp = mesh.shape["model"]
    dp = 1
    for a in mesh.axis_names:
        if a in ("pod", "data"):
            dp *= mesh.shape[a]
    if num_experts % tp == 0 and t % dp == 0:
        return True
    return t % (dp * tp) == 0  # replicated-expert variant (e.g. granite)


def _ag_fsdp(w: jnp.ndarray, axis_name: str, dim: int, full: int) -> jnp.ndarray:
    """Explicit FSDP gather: restore dimension ``dim`` to ``full`` size."""
    if w.shape[dim] == full:
        return w
    return jax.lax.all_gather(w, axis_name, axis=dim, tiled=True)


def moe_apply_sharded(
    params: Dict,
    x: jnp.ndarray,  # [B, S, D]
    *,
    num_experts: int,
    top_k: int,
    kind: str,
    capacity_factor: float,
    policy,
):
    mesh = policy.mesh
    dp_axes = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
    tp = mesh.shape["model"]
    b, s, d = x.shape
    t = b * s
    e = num_experts
    ep = e % tp == 0
    e_loc = e // tp if ep else e
    dp = 1
    for a in dp_axes:
        dp *= mesh.shape[a]
    # Replicated-expert variant (non-EP, e.g. granite's 40 experts): tokens
    # shard over BOTH axes and every device runs its own complete nodeslot
    # pool against the full (data-FSDP-gathered) expert set — the MoE layer
    # then needs NO activation collective at all.
    token_axes = dp_axes if ep else dp_axes + ("model",)
    t_loc = t // (dp if ep else dp * tp)
    cap = max(1, int(math.ceil(t_loc * top_k / e * capacity_factor)))
    up_name = "w_gate" if "w_gate" in params["experts"] else "w_in"
    d_ff = params["experts"][up_name].shape[-1]
    has_shared = "shared" in params
    if not ep and has_shared:
        raise NotImplementedError("replicated-expert path w/ shared expert")

    def local(xf, router, experts, shared):
        # xf: [t_loc, d] — this data shard's tokens, replicated over "model".
        m_idx = jax.lax.axis_index("model")
        logits = xf.astype(jnp.float32) @ router  # [t_loc, E] (full router)
        probs = jax.nn.softmax(logits, axis=-1)
        gate_w, gate_idx = jax.lax.top_k(probs, top_k)
        gate_w = gate_w / jnp.maximum(gate_w.sum(-1, keepdims=True), 1e-9)

        # local nodeslot schedule (identical on every model shard — cheap,
        # and keeping it redundant avoids broadcasting the schedule)
        flat_e = gate_idx.reshape(t_loc * top_k)
        order = jnp.argsort(flat_e, stable=True)
        se = flat_e[order]
        token_of = order // top_k
        counts = jnp.bincount(se, length=e)
        starts = jnp.cumsum(counts) - counts
        rank = jnp.arange(t_loc * top_k) - starts[se]
        keep = rank < cap

        # my expert slice only (EP); replicated variant owns all experts
        if ep:
            mine = keep & (se >= m_idx * e_loc) & (se < (m_idx + 1) * e_loc)
            slot = jnp.where(mine, (se - m_idx * e_loc) * cap + rank, e_loc * cap)
        else:
            mine = keep
            slot = jnp.where(mine, se * cap + rank, e_loc * cap)
        buf = jnp.zeros((e_loc * cap + 1, d), x.dtype).at[slot].set(xf[token_of])
        xin = buf[: e_loc * cap].reshape(1, e_loc, cap, d)

        # FSDP gather of this shard's expert weights over the data axis
        if ep:
            eff = {
                k_: _ag_fsdp(w, "data", 1,
                             d if k_ in ("w_gate", "w_up", "w_in") else d_ff)
                for k_, w in experts.items()
            }
        else:  # non-EP rules FSDP w_in on D(dim1) and w_out on D(dim2)
            eff = {
                k_: _ag_fsdp(w, "data", 1 if k_ in ("w_gate", "w_up", "w_in") else 2, d)
                for k_, w in experts.items()
            }
        from repro.models.lm.moe import _expert_ffn

        yflat = _expert_ffn(eff, xin, kind)[0].reshape(e_loc * cap, d)
        wsorted = gate_w.reshape(t_loc * top_k)[order]
        contrib = jnp.where(
            mine[:, None], yflat[jnp.minimum(slot, e_loc * cap - 1)], 0.0
        ) * wsorted[:, None].astype(x.dtype)
        out = jnp.zeros((t_loc, d), x.dtype).at[token_of].add(contrib)

        if not ep:  # replicated-expert variant: combine is complete locally
            return out, _aux(counts, probs)
        if shared is not None:  # TP'd shared expert folded into the same psum
            sg = {k_: _ag_fsdp(w, "data", 0 if k_ in ("w_gate", "w_up", "w_in") else 1,
                               d) for k_, w in shared.items()}
            if kind == "swiglu":
                h = jax.nn.silu(xf @ sg["w_gate"]) * (xf @ sg["w_up"])
                out = out + (h @ sg["w_down"]).astype(x.dtype) / 1  # partial over f
            else:
                h = xf @ sg["w_in"]
                h = jnp.square(jax.nn.relu(h)) if kind == "relu2" else jax.nn.gelu(h)
                out = out + (h @ sg["w_out"]).astype(x.dtype)
        out = jax.lax.psum(out, "model")
        return out, _aux(counts, probs)

    def _aux(counts, probs):
        # load-balance aux: mean over every token shard
        f_e = counts.astype(jnp.float32) / (t_loc * top_k)
        p_e = probs.mean(axis=0)
        aux = e * jnp.sum(f_e * p_e)
        for a in token_axes:
            aux = jax.lax.pmean(aux, a)
        return aux

    if ep:
        expert_specs = {k_: P("model", "data", None) for k_ in params["experts"]}
    else:  # replicated over model, FSDP over data (matches the param rules)
        expert_specs = {
            k_: (P(None, "data", None) if k_ in ("w_gate", "w_up", "w_in")
                 else P(None, None, "data"))
            for k_ in params["experts"]
        }
    shared_specs = None
    shared_arg = None
    if has_shared:
        shared_specs = {
            k_: (P("data", "model") if k_ in ("w_gate", "w_up", "w_in") else P("model", "data"))
            for k_ in params["shared"]
        }
        shared_arg = params["shared"]

    fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(token_axes, None), P(None, None), expert_specs, shared_specs),
        out_specs=(P(token_axes, None), P()),
    )
    out, aux = fn(x.reshape(t, d), params["router"], params["experts"], shared_arg)
    return out.reshape(b, s, d), aux
