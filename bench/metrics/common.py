"""Helpers the metric readers share."""
from __future__ import annotations

from typing import List, Optional

import numpy as np


def values(ctx, key: str) -> List[float]:
    return [r[key] for r in ctx["records"] if key in r]


def mean(ctx, key: str) -> Optional[float]:
    v = values(ctx, key)
    return float(np.mean(v)) if v else None


def idle_pct(ctx) -> Optional[float]:
    t = ctx["trace"]
    if not t or t["window_s"] <= 0 or t["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


#: Jitted programs of the aggregation engine (AGE) as the trace names them:
#: the tile scan and, for runtime coefficients, its segment max and sum passes.
AGE_PROGRAMS = ("aggregate_edge_tiles", "segment_max_edge_tiles", "edge_segment_sum_tiles")


def age_seconds_per_request(ctx) -> Optional[float]:
    """Device seconds of the AGE programs per traced request."""
    t = ctx["trace"]
    if not t or not t.get("requests"):
        return None
    s = sum(sec for name, sec in t["modules"].items()
            if any(p in name for p in AGE_PROGRAMS))
    if s <= 0:
        return None
    return s / t["requests"]


def request_work(ctx, which: str):
    """Compulsory work of one whole-graph request (``age`` or ``forward``)."""
    from bench import counts

    su, cfg = ctx["setup"], ctx["cell"].cfg
    if "nodes" not in su:
        return None
    fn = counts.age_work if which == "age" else counts.forward_work
    return fn(ctx["config"]["arch"], su["nodes"], su["edges"], su["float_rows"],
              cfg.gnn_layer_dims, cfg.gnn_heads)
