"""Plain GCN (Kipf & Welling, arXiv:1609.02907; AMPLE Table 3):

    x_i' = W · Σ_{j ∈ N(i) ∪ {i}} x_j / sqrt(d_i d_j),   d = in-degree + 1,

with ReLU between layers and none after the last. Aggregation comes before
the transform, as the paper orders it; the weights are ``layers[l]["w"]``.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

from bench import refcore


def forward(g: refcore.Graph, x: np.ndarray, params: Dict, rows: np.ndarray,
            precision: refcore.Precision = refcore.FULL) -> np.ndarray:
    """Outputs of ``rows`` (sorted unique), float64 [len(rows), classes]."""
    layers: List[Dict] = params["layers"]
    pg = refcore.with_self_loops(g)
    inv_sqrt = 1.0 / np.sqrt(pg.degrees.astype(np.float64))
    float_rows = refcore.degree_quant_float_rows(pg, precision.float_ratio)
    sets = refcore.hop_sets(pg, rows, len(layers))
    h = np.asarray(x[sets[0]], np.float64)
    for l, lyr in enumerate(layers):
        out_rows, in_rows = sets[l + 1], sets[l]
        ptr, local, src, dst = refcore.sub_csr(pg, out_rows, in_rows)
        coeff = inv_sqrt[src] * inv_sqrt[dst]
        fr = float_rows[out_rows]
        if precision.exact:
            agg = refcore.spmm(ptr, local, coeff, h, in_rows.size)
        else:
            agg = np.empty((out_rows.size, h.shape[1]))
            for low in (True, False):
                sel = ~fr if low else fr
                if sel.any():
                    msgs = precision.messages(h, low, ("agg", l))
                    agg[sel] = refcore.spmm(ptr, local, coeff, msgs, in_rows.size, sel)
        h = precision.matmul(agg, np.asarray(lyr["w"], np.float64), fr, ("fte", l))
        if l < len(layers) - 1:
            h = np.maximum(h, 0.0)
    return h
