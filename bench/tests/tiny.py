"""A tiny benchmark laid over the real one in a temporary directory: small
configurations, a mix and cells, for runs on the CPU."""
from __future__ import annotations

import copy
import json
from pathlib import Path
from typing import Dict

from bench.registry import BENCH_DIR, Registry

MODEL = {"d_model": 64, "d_ff": 32, "vocab_size": 16, "gnn_edges_per_tile": 64,
         "gnn_union_node_bucket": 128, "gnn_union_edge_bucket": 512,
         "gnn_batch_window": 2}

WHOLE = {"dataset": {"name": "tiny", "nodes": 400, "mean_degree": 8.0, "sigma": 1.25,
                     "seed": 0},
         "features": MODEL["d_model"], "offsets": 64, "warm_requests": 2,
         "trace_requests": 1, "check": {"hub_rows": 4, "random_rows": 40, "requests": 2}}

#: float_err: sound runs read about 1e-7 here, or up to 1.4e-4 where an int8
#: value rounds the other way than in the float64 reference and reaches half
#: of a tiny cell's few float rows (2 of 16 runs); the bf16 control reads
#: 6.0e-4 to 4.0e-3 (CPU, 8 seeds per arch).
LIMITS = {"rms_err": 0.1, "max_err": 0.2, "float_err": 3e-4, "nonfinite": 0}


def _write(path: Path, obj) -> None:
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(obj, indent=1))


def make(root: Path) -> Dict:
    """Write tiny-gcn/tiny-gat configs, the tiny-whole mix and the cells'
    limits under ``root``; return a spec that names them."""
    spec = json.loads((BENCH_DIR.parent / "BENCHMARK.json").read_text())
    cells = []
    for arch in ("gcn", "gat"):
        cfg = json.loads((BENCH_DIR / "configs" / f"ample-{arch}.json").read_text())
        cfg = copy.deepcopy(cfg)
        cfg["name"] = f"tiny-{arch}"
        cfg["model"].update(MODEL)
        if arch == "gat":
            cfg["model"]["gnn_heads"] = 2
        _write(root / "configs" / f"tiny-{arch}.json", cfg)
        name = f"tiny-{arch}-whole"
        cells.append({"name": name, "config": f"tiny-{arch}", "traffic": "tiny-whole",
                      "chips": 1, "why": "CPU test cell"})
        _write(root / "limits" / f"{name}.json", {"limits": LIMITS})
    _write(root / "traffic" / "tiny-whole.json", {"kind": "whole_closed", "params": WHOLE})
    spec["workloads"] = cells
    for group in ("end_to_end", "per_layer"):
        for m in spec[group]:
            m.pop("workloads", None)
    return spec


def registry(root: Path) -> Registry:
    return Registry([root])
