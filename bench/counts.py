"""Compulsory operations and bytes of a GNN request, from its graph's shapes.

Each count follows from N (nodes), E (edges after self-loops), the layer
widths and how many rows Degree-Quant keeps in float: int8 rows move 1 byte
an element and float rows 4. Every edge's source index and coefficient are
read once, every input matrix once, every output written once. Nothing comes
from the plan's padded tiles or from how an implementation gathers, so a
share of the roofline reads the same work whatever computes it.

``Work.seconds`` is the least time the chip could take: the larger of the
operations at their peak (float at the bf16 peak, int8 at the int8 peak;
both run on one matrix unit, so their times add) and the bytes at HBM
bandwidth.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import Dict, Sequence

__all__ = ["Work", "peaks_for", "age_work", "forward_work"]

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def peaks_for(device_kind: str) -> Dict[str, float]:
    """The peaks of ``device_kind``; an unknown device is an error."""
    table = json.loads(PEAKS_FILE.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(
            f"no peaks for device kind {device_kind!r} in {PEAKS_FILE.name}; "
            f"known: {sorted(table)}"
        )
    return table[device_kind]


@dataclasses.dataclass
class Work:
    float_flops: float = 0.0
    int8_ops: float = 0.0
    bytes: float = 0.0

    def __add__(self, other: "Work") -> "Work":
        return Work(self.float_flops + other.float_flops,
                    self.int8_ops + other.int8_ops, self.bytes + other.bytes)

    def compute_s(self, peaks: Dict[str, float]) -> float:
        return (self.float_flops / peaks["bf16_flops_per_s"]
                + self.int8_ops / peaks["int8_ops_per_s"])

    def memory_s(self, peaks: Dict[str, float]) -> float:
        return self.bytes / peaks["hbm_bytes_per_s"]

    def seconds(self, peaks: Dict[str, float]) -> float:
        return max(self.compute_s(peaks), self.memory_s(peaks))

    def bound(self, peaks: Dict[str, float]) -> str:
        return "compute" if self.compute_s(peaks) >= self.memory_s(peaks) else "memory"


def _rows_bytes(n: int, n_float: int, width: int) -> float:
    """One read of an [n, width] matrix: int8 rows 1 B, float rows 4 B."""
    return (n - n_float) * width * 1.0 + n_float * width * 4.0


def _fte(n: int, n_float: int, d_in: int, d_out: int) -> Work:
    n_int = n - n_float
    return Work(
        float_flops=2.0 * n_float * d_in * d_out,
        int8_ops=2.0 * n_int * d_in * d_out,
        bytes=_rows_bytes(n, n_float, d_in) + d_in * d_out * (1 + 4) + 4.0 * n * d_out,
    )


def _age(n: int, e: int, n_float: int, width: int, coeffs_per_edge: int = 1) -> Work:
    return Work(
        float_flops=2.0 * e * width,
        bytes=e * (4.0 + 4.0 * coeffs_per_edge)
        + _rows_bytes(n, n_float, width) + 4.0 * n * width,
    )


def _gat_softmax(n: int, e: int, heads: int) -> Work:
    """Edge scores from node scores, LeakyReLU, segment max, exp, segment sum:
    node scores read once (src and dst), per-edge arithmetic only."""
    return Work(float_flops=6.0 * e * heads, bytes=2 * 4.0 * n * heads)


def _layers(arch: str, dims: Sequence[int], heads: int):
    if arch not in ("gcn", "gat"):
        raise ValueError(f"no compulsory counts for arch {arch!r}")
    for l in range(len(dims) - 1):
        d_in, d_out = dims[l], dims[l + 1]
        hidden = l < len(dims) - 2
        if arch == "gat":
            h = max(heads, 1)
            yield l, d_in, (d_out if hidden else d_out * h), h
        else:
            yield l, d_in, d_out, 1


def age_work(arch: str, n: int, e: int, n_float: int, dims: Sequence[int],
             heads: int = 1) -> Work:
    """The aggregation engine's share: every tile pass over the edges."""
    w = Work()
    for _, d_in, d_out, h in _layers(arch, dims, heads):
        if arch == "gat":
            w = w + _gat_softmax(n, e, h) + _age(n, e, n_float, d_out, coeffs_per_edge=h)
        else:
            w = w + _age(n, e, n_float, d_in)
    return w


def forward_work(arch: str, n: int, e: int, n_float: int, dims: Sequence[int],
                 heads: int = 1) -> Work:
    """The whole forward: transforms (FTE), attention scores and the AGE."""
    w = age_work(arch, n, e, n_float, dims, heads)
    for _, d_in, d_out, h in _layers(arch, dims, heads):
        w = w + _fte(n, n_float, d_in, d_out)
        if arch == "gat":
            w = w + Work(float_flops=2.0 * 2 * n * d_out, bytes=4.0 * n * d_out)
    return w
