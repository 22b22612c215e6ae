"""Lognormal graph structures calibrated to Table 4 of the AMPLE paper.

The benchmark's own copy of the synthetic-graph generator, so that no change
to the program can move the yardstick. In-degrees are drawn from a discretised
lognormal whose mean is the published mean degree (``sigma`` sets the skew,
so hubs exist), neighbours are drawn uniformly, duplicates and self-loops are
dropped and every node keeps at least one in-neighbour. Rows of the CSR
arrays are destinations; ``indices`` holds their sources.

``structure`` caches the arrays under ``<checkout>/.dataset-cache/bench/``:
a fixed path inside the checkout, keyed by every argument and a generator
version, written atomically.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Tuple

import numpy as np

__all__ = ["lognormal_degrees", "lognormal_csr", "structure", "CACHE_DIR"]

#: Bump on any change to what ``lognormal_csr`` returns.
GEN_VERSION = 1

CACHE_DIR = Path(__file__).resolve().parents[2] / ".dataset-cache" / "bench"


def lognormal_degrees(n: int, mean_degree: float, sigma: float,
                      rng: np.random.Generator) -> np.ndarray:
    """Integer degrees >= 1 with the requested mean and a lognormal tail."""
    mu = np.log(max(mean_degree, 1e-6)) - 0.5 * sigma * sigma
    deg = rng.lognormal(mean=mu, sigma=sigma, size=n)
    deg = np.maximum(np.rint(deg), 1).astype(np.int64)
    deg = np.minimum(deg, n - 1 if n > 1 else 1)
    # Nudge a random subset by one until the edge count hits the target.
    diff = int(round(mean_degree * n)) - int(deg.sum())
    if diff:
        step = 1 if diff > 0 else -1
        idx = rng.permutation(n)
        k, pos = abs(diff), 0
        while k > 0 and pos < n:
            i = idx[pos]
            if 1 <= deg[i] + step <= n - 1:
                deg[i] += step
                k -= 1
            pos += 1
    return deg


def lognormal_csr(n: int, mean_degree: float, *, sigma: float = 1.25,
                  seed: int = 0) -> Tuple[np.ndarray, np.ndarray]:
    """(indptr int64[n+1], indices int32[E]) of a random in-edge CSR graph."""
    rng = np.random.default_rng(seed)
    deg = lognormal_degrees(n, mean_degree, sigma, rng)
    rows = np.repeat(np.arange(n, dtype=np.int64), deg)
    cols = rng.integers(0, n, size=rows.size, dtype=np.int64)
    order = np.lexsort((cols, rows))
    rows, cols = rows[order], cols[order]
    dup = np.zeros(cols.size, bool)
    if cols.size:
        dup[1:] = (cols[1:] == cols[:-1]) & (rows[1:] == rows[:-1])
    keep = ~(dup | (cols == rows))
    rows, cols = rows[keep], cols[keep]
    new_deg = np.bincount(rows, minlength=n)
    iso = np.nonzero(new_deg == 0)[0]
    if iso.size:
        extra = (iso + 1 + rng.integers(0, max(n - 1, 1), iso.size)) % n
        rows = np.concatenate([rows, iso])
        cols = np.concatenate([cols, extra])
        order = np.lexsort((cols, rows))
        rows, cols = rows[order], cols[order]
        new_deg = np.bincount(rows, minlength=n)
    indptr = np.zeros(n + 1, np.int64)
    np.cumsum(new_deg, out=indptr[1:])
    return indptr, cols.astype(np.int32)


def structure(n: int, mean_degree: float, *, sigma: float = 1.25, seed: int = 0,
              cache: bool = True) -> Tuple[np.ndarray, np.ndarray]:
    """``lognormal_csr`` through the on-disk cache."""
    if not cache:
        return lognormal_csr(n, mean_degree, sigma=sigma, seed=seed)
    key = f"lognormal-n{n}-d{mean_degree:g}-s{sigma:g}-seed{seed}-g{GEN_VERSION}"
    path = CACHE_DIR / f"{key}.npz"
    if path.exists():
        with np.load(path) as z:
            return z["indptr"], z["indices"]
    indptr, indices = lognormal_csr(n, mean_degree, sigma=sigma, seed=seed)
    CACHE_DIR.mkdir(parents=True, exist_ok=True)
    tmp = CACHE_DIR / f"{key}.{os.getpid()}.tmp.npz"
    np.savez(tmp, indptr=indptr, indices=indices)
    os.replace(tmp, path)
    return indptr, indices
