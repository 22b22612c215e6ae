"""Plain host-side GNN arithmetic shared by the references in ``references/``.

Everything here is numpy (float64 unless a control asks for less) and
imports nothing of the program under test. Graphs are in-edge CSR arrays
(row = destination); the references add the self-loops GCN and GAT call for.

A reference computes the outputs of a chosen set of rows only: layer ``l``
is evaluated on the rows that layer ``l + 1`` reads, so checking a sample of
a 700k-node graph costs the sample's two-hop neighbourhood, not the graph.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

import ml_dtypes
import numpy as np
import scipy.sparse as sp

__all__ = [
    "Graph",
    "with_self_loops",
    "hop_sets",
    "sub_csr",
    "Precision",
    "FULL",
    "calibrate",
    "degree_quant_float_rows",
]


@dataclasses.dataclass(frozen=True)
class Graph:
    """In-edge CSR: ``indices[indptr[i]:indptr[i+1]]`` are the sources of i."""

    indptr: np.ndarray
    indices: np.ndarray

    @property
    def num_nodes(self) -> int:
        return int(self.indptr.size - 1)

    @property
    def degrees(self) -> np.ndarray:
        return np.diff(self.indptr)


def with_self_loops(g: Graph) -> Graph:
    """Add the edge i -> i to every row (the generators never emit one)."""
    n = g.num_nodes
    indptr = g.indptr + np.arange(n + 1, dtype=np.int64)
    indices = np.empty(int(indptr[-1]), np.int32)
    loop_pos = indptr[:-1]
    mask = np.ones(indices.size, bool)
    mask[loop_pos] = False
    indices[loop_pos] = np.arange(n, dtype=np.int32)
    indices[mask] = g.indices
    return Graph(indptr, indices)


def _row_edges(g: Graph, rows: np.ndarray):
    """(edge positions, row ptr) of the CSR rows ``rows``, in row order."""
    starts = g.indptr[rows]
    lens = g.indptr[rows + 1] - starts
    ptr = np.zeros(rows.size + 1, np.int64)
    np.cumsum(lens, out=ptr[1:])
    pos = np.repeat(starts - ptr[:-1], lens) + np.arange(ptr[-1], dtype=np.int64)
    return pos, ptr


def hop_sets(g: Graph, rows: np.ndarray, num_layers: int) -> List[np.ndarray]:
    """Sorted node sets S_0 ⊇ … ⊇ S_L = rows: layer l is computed on S_l."""
    sets = [np.unique(np.asarray(rows, np.int64))]
    for _ in range(num_layers):
        pos, _ = _row_edges(g, sets[0])
        sets.insert(0, np.union1d(sets[0], g.indices[pos].astype(np.int64)))
    return sets


def sub_csr(g: Graph, rows: np.ndarray, cols: np.ndarray):
    """(indptr, local source index, global source id, global dst id) of the
    edges into ``rows``, sources relabelled into positions of sorted ``cols``."""
    pos, ptr = _row_edges(g, rows)
    src = g.indices[pos].astype(np.int64)
    local = np.searchsorted(cols, src)
    if local.size and not np.array_equal(cols[np.minimum(local, cols.size - 1)], src):
        raise ValueError("a source lies outside the column set")
    dst = np.repeat(rows, np.diff(ptr))
    return ptr, local, src, dst


def degree_quant_float_rows(prepared: Graph, float_ratio: float) -> np.ndarray:
    """Bool[N]: the round(float_ratio * N) rows of highest in-degree (self-loop
    included), at least one, as the configuration's Degree-Quant rule states."""
    n = prepared.num_nodes
    k = min(max(int(round(float_ratio * n)), 1 if n else 0), n)
    out = np.zeros(n, bool)
    if k:
        out[np.argsort(-prepared.degrees, kind="stable")[:k]] = True
    return out


def spmm(ptr, local, data, dense: np.ndarray, num_cols: int,
         sel: Optional[np.ndarray] = None) -> np.ndarray:
    """Σ_j data_ij · dense_j per row (only the rows ``sel`` selects, when
    given), as a sparse product (no gathered copy)."""
    a = sp.csr_matrix((data, local, ptr), shape=(ptr.size - 1, num_cols))
    return np.asarray((a if sel is None else a[sel]) @ dense)


# ------------------------------------------------------------- precisions
#: Smallest scale a quantizer takes, as the configuration's int8 rule states.
SCALE_EPS = 1e-8

Slot = Tuple[str, int]
"""A quantized activation: ``("agg", l)`` the AGE's source rows of layer
``l``, ``("fte", l)`` the int8 rows entering layer ``l``'s transform."""


def _bf16(x: np.ndarray) -> np.ndarray:
    return np.asarray(x, np.float32).astype(ml_dtypes.bfloat16).astype(np.float64)


def _sym_quant(x: np.ndarray, bits: int, axis: Optional[int] = None,
               scale: Optional[float] = None) -> np.ndarray:
    """Symmetric round-to-nearest-even quantize-dequantize at ``bits`` bits;
    the scale is amax / qmax unless given."""
    qmax = 2 ** (bits - 1) - 1
    if scale is None:
        amax = np.max(np.abs(x), axis=axis, keepdims=axis is not None)
        scale = np.maximum(amax / qmax, SCALE_EPS)
    return np.clip(np.rint(x / scale), -qmax - 1, qmax) * scale


@dataclasses.dataclass(frozen=True)
class Precision:
    """How a reference rounds: ``None`` bits keeps float64 everywhere.

    With ``int_bits`` set, rows outside Degree-Quant's float rows run at that
    many bits: per-column weight scales, and per-tensor activation scales,
    either taken from ``scales`` (static, keyed by slot) or from the tensor
    at hand, and then written to ``record`` when it is given. Float rows stay
    float64, or bfloat16 with ``float_rows_bf16``.
    """

    int_bits: Optional[int] = None
    float_rows_bf16: bool = False
    float_ratio: float = 0.0
    scales: Optional[Dict[Slot, float]] = None
    record: Optional[Dict[Slot, float]] = None

    @property
    def exact(self) -> bool:
        return self.int_bits is None and not self.float_rows_bf16

    def _scale(self, x: np.ndarray, slot: Slot) -> float:
        if self.scales is not None:
            return self.scales[slot]
        qmax = 2 ** (self.int_bits - 1) - 1
        s = max(float(np.max(np.abs(x))) / qmax if x.size else 0.0, SCALE_EPS)
        if self.record is not None:
            self.record[slot] = s
        return s

    def messages(self, x: np.ndarray, low_rows: bool, slot: Slot) -> np.ndarray:
        """Source rows as the AGE gathers them for int (True) / float rows;
        an int activation scale spans all of ``x``."""
        if self.exact:
            return x
        if low_rows:
            return _sym_quant(x, self.int_bits, scale=self._scale(x, slot))
        return _bf16(x) if self.float_rows_bf16 else x

    def matmul(self, h: np.ndarray, w: np.ndarray, float_rows: np.ndarray,
               slot: Slot) -> np.ndarray:
        """h @ w with float rows (bool mask) and int rows rounded as stated;
        the int activation scale spans the int rows of ``h``."""
        if self.exact:
            return h @ w
        out = np.empty((h.shape[0], w.shape[1]))
        lo = ~float_rows
        if lo.any():
            hl = h[lo]
            hq = _sym_quant(hl, self.int_bits, scale=self._scale(hl, slot))
            wq = _sym_quant(w, self.int_bits, axis=0)
            out[lo] = hq @ wq
        if float_rows.any():
            hf, wf = h[float_rows], w
            if self.float_rows_bf16:
                hf, wf = _bf16(hf), _bf16(wf)
            out[float_rows] = hf @ wf
        return out


FULL = Precision()


def calibrate(forward, g: Graph, x: np.ndarray, params, prec: Precision) -> Precision:
    """``prec`` with static activation scales, taken as a server that
    calibrates on its first request takes them: from a whole-graph forward
    over that request's features ``x``."""
    rec: Dict[Slot, float] = {}
    forward(g, x, params, np.arange(g.num_nodes),
            dataclasses.replace(prec, scales=None, record=rec))
    return dataclasses.replace(prec, scales=rec, record=None)
