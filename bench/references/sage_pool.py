"""Plain GraphSAGE-pool (Hamilton, Ying and Leskovec, arXiv:1706.02216, Eq. 3
and Algorithm 1, as the authors' code lays it out), per layer k:

    p_u  = ReLU(h_u W_pool + b_pool)
    a_v  = max_{u ∈ N(v)} p_u     feature-wise, 0 where N(v) is empty
    h'_v = act_k(concat(h_v W_self, a_v W_neigh)),  act_k ReLU but for the last k,
    y    = (h_K / ‖h_K‖₂) W_pred + b_pred,

over every in-neighbour (no sampling, no self-loop). Weights per layer:
``pool`` {w, b}, ``self`` {w}, ``neigh`` {w}; then ``head`` {w, b}.

The neighbour max runs over destination rows in blocks, each row grouped
with the rows of its exact in-degree d, so a group of k rows is one
``[k, d, P]`` gather reduced over its middle axis; blocks run on a few
threads (numpy releases the interpreter lock in the gather and the reduce).
Rows at ``int_bits`` gather their messages' integer codes (one byte each):
a message is its code times a positive scale, so the largest code times the
scale is the largest message, as the float64 max of the messages gives it.

Every layer but the last runs on the whole graph: the two-hop set of a
checked sample of a graph of this degree is nearly all of it, and under the
calibrated (static) scales a row does not depend on which others are
computed.
"""
from __future__ import annotations

import os
import threading
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List

import numpy as np

from bench import refcore

#: ``tf.nn.l2_normalize``'s floor on the squared norm, as the authors' code.
L2_EPS = 1e-12

#: Edges gathered at once per block: [BLOCK_EDGES, P] float64 (16 MiB at 512).
BLOCK_EDGES = 1 << 12

THREADS = max(1, min(8, os.cpu_count() or 1))


def _blocks(deg: np.ndarray):
    """Blocks of positions into ``deg`` with at least one edge, each of one
    degree d and at most ``BLOCK_EDGES`` edges (or one row of more):
    (positions, d)."""
    pos = np.flatnonzero(deg > 0)
    pos = pos[np.argsort(deg[pos], kind="stable")]
    d_sorted = deg[pos]
    starts = np.flatnonzero(np.r_[True, d_sorted[1:] != d_sorted[:-1]])
    for lo, hi in zip(starts, np.r_[starts[1:], pos.size]):
        d = int(d_sorted[lo])
        k = max(1, BLOCK_EDGES // d)
        for a in range(lo, hi, k):
            yield pos[a:min(a + k, hi)], d


def neighbour_max(ptr: np.ndarray, local: np.ndarray, msgs: np.ndarray,
                  sel: np.ndarray, out: np.ndarray) -> None:
    """Write into ``out``'s rows that ``sel`` selects the feature-wise max of
    ``msgs[local[e]]`` over each row's edges e; 0 for a row with none.

    Each thread gathers into buffers of its own, allocated once: with a new
    block allocated at its own size each time, the host of a TPU v5e grew by
    gigabytes a pass outside the process's resident set, to its limit."""
    rows = np.flatnonzero(sel)
    starts = ptr[rows]
    out[rows] = 0.0
    width = msgs.shape[1]
    local_ = threading.local()

    def block(job):
        pos, d = job
        if not hasattr(local_, "edges"):
            local_.edges = np.empty(BLOCK_EDGES, np.int64)
            local_.src = np.empty(BLOCK_EDGES, local.dtype)
            local_.rows = np.empty((BLOCK_EDGES, width), msgs.dtype)
            local_.best = np.empty((BLOCK_EDGES, width), msgs.dtype)
        if d <= BLOCK_EDGES:
            k = pos.size
            edges = local_.edges[:k * d].reshape(k, d)
            np.add(starts[pos][:, None], np.arange(d), out=edges)
            src = local_.src[:k * d].reshape(k, d)
            np.take(local, edges, out=src)
            got = local_.rows[:k * d].reshape(k, d, width)
            np.take(msgs, src, axis=0, out=got)
            np.max(got, axis=1, out=local_.best[:k])
            out[rows[pos]] = local_.best[:k]
            return
        best = local_.best[0]  # one row of more edges than a block: in chunks
        best.fill(-np.inf if msgs.dtype.kind == "f" else np.iinfo(msgs.dtype).min)
        for e0 in range(0, d, BLOCK_EDGES):
            n = min(BLOCK_EDGES, d - e0)
            edges = local_.edges[:n]
            np.add(starts[pos[0]] + e0, np.arange(n), out=edges)
            np.take(local, edges, out=local_.src[:n])
            got = local_.rows[:n]
            np.take(msgs, local_.src[:n], axis=0, out=got)
            np.maximum(best, got.max(axis=0), out=best)
        out[rows[pos[0]]] = best

    with ThreadPoolExecutor(THREADS) as pool:
        for _ in pool.map(block, _blocks(ptr[rows + 1] - starts)):
            pass


def int_row_max(precision: refcore.Precision, p: np.ndarray, slot, ptr: np.ndarray,
                local: np.ndarray, sel: np.ndarray, out: np.ndarray) -> None:
    """``neighbour_max`` of ``precision.messages(p, True, slot)`` for the
    rows ``sel`` selects, over the messages' integer codes."""
    scale = precision._scale(p, slot)  # recorded or static, as messages() takes it
    qmax = 2 ** (precision.int_bits - 1) - 1
    codes = np.clip(np.rint(p / scale), -qmax - 1, qmax).astype(np.int8)
    neighbour_max(ptr, local, codes, sel, out)
    out[sel] *= scale


def _l2_normalize(h: np.ndarray) -> np.ndarray:
    return h / np.sqrt(np.maximum((h * h).sum(axis=1, keepdims=True), L2_EPS))


def forward(g: refcore.Graph, x: np.ndarray, params: Dict, rows: np.ndarray,
            precision: refcore.Precision = refcore.FULL) -> np.ndarray:
    """Outputs of ``rows`` (sorted unique), float64 [len(rows), classes]."""
    layers: List[Dict] = params["layers"]
    f64 = lambda a: np.asarray(a, np.float64)
    # Degree-Quant ranks by in-degree; a self-loop would add 1 to every
    # degree and rank the rows the same.
    float_rows = refcore.degree_quant_float_rows(g, precision.float_ratio)
    every = np.arange(g.num_nodes)
    h = f64(x)
    for l, lyr in enumerate(layers):
        last = l == len(layers) - 1
        out_rows = rows if last else every
        fr_out = float_rows[out_rows]
        pool = lyr["pool"]["w"].shape[1]
        # W_pool and W_self read h's rows at one rounding (the program
        # calibrates both on h's int rows): one product serves both
        ps = precision.matmul(
            h, np.concatenate([f64(lyr["pool"]["w"]), f64(lyr["self"]["w"])], axis=1),
            float_rows, ("pool", l))
        own = ps[:, pool:] if out_rows is every else ps[out_rows, pool:]
        p = np.maximum(ps[:, :pool] + f64(lyr["pool"]["b"]), 0.0)
        del ps
        if out_rows.size == g.num_nodes:  # sorted and unique: every row
            ptr, local = g.indptr, g.indices
        else:
            ptr, local, _, _ = refcore.sub_csr(g, out_rows, every)
        a = np.empty((out_rows.size, pool))
        for low in (True, False):
            sel = ~fr_out if low else fr_out
            if not sel.any():
                continue
            if low and not precision.exact:
                int_row_max(precision, p, ("agg", l), ptr, local, sel, a)
            else:
                neighbour_max(ptr, local, precision.messages(p, low, ("agg", l)), sel, a)
        del p
        h = np.concatenate(
            [own, precision.matmul(a, f64(lyr["neigh"]["w"]), fr_out, ("neigh", l))],
            axis=1)
        if not last:
            h = np.maximum(h, 0.0)
    z = _l2_normalize(h)
    head = params["head"]
    return (precision.matmul(z, f64(head["w"]), float_rows[rows], ("head", 0))
            + f64(head["b"]))
