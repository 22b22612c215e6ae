"""Whole-graph requests in a closed loop: one client, one request at a time.

The graph's structure is the mix's dataset (a Table-4 lognormal graph from
the mix's own seed), so every request after the first finds its plan in the
cache. Each request carries fresh features: a view of ``nodes`` consecutive
rows, at an offset drawn from the run seed, of a host buffer drawn once in
set-up. Making a request's features costs nothing inside the window, and no
two requests in a run are likely to share them.

Parameters (``traffic/<name>.json`` ``params``):
  dataset: {nodes, mean_degree, sigma, seed}   the graph
  features: the dataset's feature width; the configuration must take it
  offsets: rows of feature buffer beyond ``nodes`` (distinct feature sets)
  warm_requests: requests served in set-up (the first plans cold)
  trace_requests: requests of the window the profiler records (trace runs)
  check: {hub_rows, random_rows, requests}: answers the reference checks:
      the highest-degree rows and random rows of requests drawn from the seed
"""
from __future__ import annotations

import time

import numpy as np

from bench import harness, refcore
from bench.traffic import table4


class Traffic:
    def __init__(self, cell: "harness.Cell"):
        self.cell = cell
        self.p = cell.mix

    def setup(self) -> None:
        from repro.serve.gnn_engine import GNNServeEngine

        self.setup_data()
        c = self.cell
        self.engine = GNNServeEngine(c.cfg, params=c.params)
        for i in range(int(self.p["warm_requests"])):
            with c.span("warmup"):
                r = self.engine.infer(self.graph, self._x(i))  # offsets 0, 1, ...
            if i == 0:
                c.setup_info["plan_cold_s"] = r.plan_ms / 1e3
                c.setup_info["cold_request_s"] = r.run_ms / 1e3

    def setup_data(self) -> None:
        """The graph, the feature buffer and the rows the reference checks."""
        from repro.graphs.csr import Graph

        c, ds = self.cell, self.p["dataset"]
        if int(self.p["features"]) != c.cfg.d_model:
            raise ValueError(f"{c.name}: the mix has {self.p['features']} features, "
                             f"the configuration takes {c.cfg.d_model}")
        n = int(ds["nodes"])
        indptr, indices = table4.structure(
            n, float(ds["mean_degree"]), sigma=float(ds["sigma"]), seed=int(ds["seed"]))
        self.n = n
        self.graph = Graph(indptr=indptr, indices=indices, num_nodes=n, name="bench")
        self.ref_graph = refcore.Graph(indptr, indices)
        self.buf = c.features(n + int(self.p["offsets"]))
        c.first_request = (self.ref_graph, self._x(0))  # set-up's first request
        c.setup_info["nodes"] = n
        c.setup_info["edges"] = int(indices.size) + n  # self-loops included
        deg = np.diff(indptr) + 1
        k = min(max(int(round(c.config["degree_quant"]["float_ratio"] * n)), 1), n)
        c.setup_info["float_rows"] = k
        chk = self.p["check"]
        hubs = np.argsort(-deg, kind="stable")[: int(chk["hub_rows"])]
        rand = c.rng.choice(n, size=int(chk["random_rows"]), replace=False)
        self.rows = np.unique(np.concatenate([hubs, rand]))

    def _x(self, offset: int) -> np.ndarray:
        return self.buf[offset: offset + self.n]

    def window(self, seconds: float) -> None:
        c = self.cell
        offsets = c.rng.integers(0, int(self.p["offsets"]), size=1 << 16)
        kept = []
        traced = int(self.p["trace_requests"]) if c.trace else 0
        t_end = time.perf_counter() + seconds
        k = 0
        while time.perf_counter() < t_end:
            x = self._x(int(offsets[k]))
            if k == 0 and traced:
                c.tracer.start()
            with c.span("infer"):
                t0 = time.perf_counter()
                try:
                    r = self.engine.infer(self.graph, x)
                    err = None
                except Exception as exc:  # counted as failed, never as correct
                    r, err = None, repr(exc)
                t1 = time.perf_counter()
            if traced and k == traced - 1:
                c.tracer.stop(requests=traced)
            rec = {"latency_ms": (t1 - t0) * 1e3, "call": k,
                   "nodes": self.n, "edges": c.setup_info["edges"],
                   "float_rows": c.setup_info["float_rows"], "batch_size": 1}
            if err is None:
                rec.update(run_ms=r.run_ms, plan_ms=r.plan_ms, queue_ms=r.queue_ms)
                kept.append((int(offsets[k]), r.outputs[self.rows].copy()))
            else:
                rec["error"] = err
            c.records.append(rec)
            k += 1
        if traced and k < traced:
            c.tracer.stop(requests=k)
        n_check = min(int(self.p["check"]["requests"]), len(kept))
        pick = c.rng.choice(len(kept), size=n_check, replace=False) if kept else []
        for i in sorted(pick):
            offset, y = kept[i]
            c.checks.append(harness.CheckItem(self.ref_graph, self._x(offset), self.rows, y))

    def answers(self) -> list:
        """What a window checks, drawn the same way, with no outputs: the
        control puts its own in the program's place."""
        offs = self.cell.rng.integers(0, int(self.p["offsets"]), int(self.p["check"]["requests"]))
        return [harness.CheckItem(self.ref_graph, self._x(int(o)), self.rows, None)
                for o in offs]

    def free(self) -> None:
        self.engine = None
