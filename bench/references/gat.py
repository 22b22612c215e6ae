"""Plain GAT (Veličković et al., arXiv:1710.10903):

    z = x W,  e_ij = LeakyReLU_0.2(a_src · z_j + a_dst · z_i)   per head,
    α_ij = softmax over j ∈ N(i) ∪ {i} of e_ij,
    x_i' = ‖_h Σ_j α_ij z_j   (heads concatenated, then ELU) on hidden layers,
    x_i' = mean_h Σ_j α_ij z_j on the output layer.

Weights per layer: ``w`` [d_in, H·dh], ``a_src`` and ``a_dst`` [H, dh].
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

from bench import refcore

LEAKY_SLOPE = 0.2


def forward(g: refcore.Graph, x: np.ndarray, params: Dict, rows: np.ndarray,
            precision: refcore.Precision = refcore.FULL) -> np.ndarray:
    """Outputs of ``rows`` (sorted unique), float64 [len(rows), classes]."""
    layers: List[Dict] = params["layers"]
    pg = refcore.with_self_loops(g)
    float_rows = refcore.degree_quant_float_rows(pg, precision.float_ratio)
    sets = refcore.hop_sets(pg, rows, len(layers))
    h = np.asarray(x[sets[0]], np.float64)
    for l, lyr in enumerate(layers):
        out_rows, in_rows = sets[l + 1], sets[l]
        a_src = np.asarray(lyr["a_src"], np.float64)
        a_dst = np.asarray(lyr["a_dst"], np.float64)
        heads, dh = a_src.shape
        z = precision.matmul(h, np.asarray(lyr["w"], np.float64), float_rows[in_rows],
                             ("fte", l))
        z = z.reshape(in_rows.size, heads, dh)
        ptr, local, _, _ = refcore.sub_csr(pg, out_rows, in_rows)
        own = np.searchsorted(in_rows, out_rows)
        s_src = np.einsum("nhd,hd->nh", z, a_src)
        s_dst = np.einsum("nhd,hd->nh", z[own], a_dst)
        seg = np.repeat(np.arange(out_rows.size), np.diff(ptr))
        e = s_src[local] + s_dst[seg]
        e = np.where(e > 0, e, LEAKY_SLOPE * e)
        starts = ptr[:-1]
        e = np.exp(e - np.maximum.reduceat(e, starts, axis=0)[seg])
        alpha = e / np.add.reduceat(e, starts, axis=0)[seg]
        fr = float_rows[out_rows]
        out = np.empty((out_rows.size, heads, dh))
        if precision.exact:
            for k in range(heads):
                out[:, k] = refcore.spmm(ptr, local, alpha[:, k], z[:, k], in_rows.size)
        else:
            for low in (True, False):
                sel = ~fr if low else fr
                if not sel.any():
                    continue
                msgs = precision.messages(z, low, ("agg", l))  # one scale, all heads
                for k in range(heads):
                    out[sel, k] = refcore.spmm(ptr, local, alpha[:, k], msgs[:, k],
                                               in_rows.size, sel)
        if l < len(layers) - 1:
            h = out.reshape(out_rows.size, heads * dh)
            h = np.where(h > 0, h, np.expm1(np.minimum(h, 0.0)))
        else:
            h = out.mean(axis=1)
    return h
