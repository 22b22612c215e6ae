"""Find a cell's parts by name: configurations, traffic mixes and their
generator kinds, metric readers and references, each in a file of its own.

    configs/<config>.json      the configuration as it is run
    traffic/<traffic>.json     a traffic mix: {"kind": ..., "params": {...}}
    traffic/<kind>.py          the generator that reads mixes of that kind
    metrics/<metric>.py        a reader: read(ctx) -> float | None
    references/<arch>.py       plain forward(graph, x, params, rows, precision)

Lookups search ``roots`` in order, so a caller can lay new files over the
benchmark's own without editing either.
"""
from __future__ import annotations

import importlib.util
import json
import sys
from pathlib import Path
from typing import Any, Dict, List, Optional, Sequence

__all__ = ["BENCH_DIR", "REPO_DIR", "Registry"]

BENCH_DIR = Path(__file__).resolve().parent
REPO_DIR = BENCH_DIR.parent


class Registry:
    def __init__(self, roots: Optional[Sequence[Path]] = None):
        self.roots: List[Path] = [Path(r) for r in (roots or [])] + [BENCH_DIR]
        self._modules: Dict[Path, Any] = {}

    def find(self, sub: str, name: str, suffix: str) -> Path:
        for root in self.roots:
            p = root / sub / f"{name}{suffix}"
            if p.is_file():
                return p
        raise FileNotFoundError(
            f"no {sub}/{name}{suffix} under {[str(r) for r in self.roots]}"
        )

    def json(self, sub: str, name: str) -> Dict[str, Any]:
        return json.loads(self.find(sub, name, ".json").read_text())

    def module(self, sub: str, name: str):
        path = self.find(sub, name, ".py")
        if path not in self._modules:
            mod_name = f"bench_{sub}_{name}".replace(".", "_").replace("-", "_")
            spec = importlib.util.spec_from_file_location(mod_name, path)
            mod = importlib.util.module_from_spec(spec)
            sys.modules[mod_name] = mod
            spec.loader.exec_module(mod)
            self._modules[path] = mod
        return self._modules[path]

    def config(self, name: str) -> Dict[str, Any]:
        return self.json("configs", name)

    def traffic(self, name: str) -> Dict[str, Any]:
        return self.json("traffic", name)

    def kind(self, kind: str):
        return self.module("traffic", kind)

    def reader(self, metric: str):
        return self.module("metrics", metric)

    def reference(self, arch: str):
        return self.module("references", arch)
