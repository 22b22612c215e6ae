"""Compulsory operations and bytes of a GraphSAGE-pool request, from its
graph's shapes, on the same terms as ``counts.py``.

E counts the graph's edges without self-loops (GraphSAGE aggregates over the
in-neighbours alone), so it is the harness's ``edges`` less ``nodes``. A max
pass over a [N, P] matrix is E·P comparisons; it reads each edge's source
index once (4 B; the max reads no coefficient), every input row once (int8
rows 1 B an element, float rows 4 B) and writes every output row once
(4 B). A layer's FTE is its pooling product (d_in → P, with bias and ReLU)
and the two halves of its output (d_in → d_out / 2 and P → d_out / 2); the
prediction head is one more product. Nothing comes from the plan's padded
tiles or from how an implementation gathers, so a share of the roofline
reads the same work whatever computes it.
"""
from __future__ import annotations

from typing import Sequence

from bench.counts import Work, _fte, _rows_bytes

__all__ = ["max_pass", "age_work", "forward_work", "request_work"]


def max_pass(n: int, e: int, n_float: int, width: int) -> Work:
    return Work(float_flops=1.0 * e * width,
                bytes=4.0 * e + _rows_bytes(n, n_float, width) + 4.0 * n * width)


def age_work(n: int, e: int, n_float: int, dims: Sequence[int], pool: int) -> Work:
    """The aggregation engine's share: one max pass of width ``pool`` a layer."""
    w = Work()
    for _ in range(len(dims) - 2):
        w = w + max_pass(n, e, n_float, pool)
    return w


def forward_work(n: int, e: int, n_float: int, dims: Sequence[int], pool: int) -> Work:
    """The whole forward: the max passes, three products a layer and the head."""
    w = age_work(n, e, n_float, dims, pool)
    for l in range(len(dims) - 2):
        half = dims[l + 1] // 2
        w = (w + _fte(n, n_float, dims[l], pool) + _fte(n, n_float, dims[l], half)
             + _fte(n, n_float, pool, half))
    return w + _fte(n, n_float, dims[-2], dims[-1])


def request_work(ctx, which: str):
    """Compulsory work of one whole-graph request of a metric reader's
    ``ctx`` (``age`` or ``forward``); None where the cell is no
    GraphSAGE-pool cell or set-up measured no graph."""
    su, cfg = ctx["setup"], ctx["cell"].cfg
    if ctx["config"].get("arch") != "sage_pool" or "nodes" not in su:
        return None
    fn = age_work if which == "age" else forward_work
    return fn(su["nodes"], su["edges"] - su["nodes"], su["float_rows"],
              cfg.gnn_layer_dims, cfg.d_ff)
