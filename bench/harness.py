"""Run one benchmark cell once: set up, measure a window, check, report.

A cell is an entry of ``BENCHMARK.json``'s ``workloads``: a configuration
(``configs/<name>.json``) under a traffic mix (``traffic/<name>.json``) whose
``kind`` names the generator module (``traffic/<kind>.py``). The harness
knows nothing of either beyond those files; the metrics it prints are read
by ``metrics/<name>.py``, and ``correct`` compares the timed path's outputs
with ``references/<arch>.py`` under the limits in ``limits/<cell>.json``.

The order of a run:

1. set-up (``setup_s``): weights and data from the seed, the engine, the
   cold plan, compilation or loading it from the cache, warm-up;
2. the window: ``seconds`` of traffic, with compilations in it counted;
   with ``trace`` on, the profiler records the part the mix names;
3. the peak of device memory, then the program's state is freed;
4. the reference over a sample of the window's answers, drawn from the seed.

The program runs as the configuration states it: float32 is float32. Where
the program leaves a float32 matrix product at JAX's default precision, the
TPU rounds its inputs to bfloat16, so a run sets JAX's default matmul
precision to ``highest`` (``MATMUL_PRECISION``) before anything is traced.
"""
from __future__ import annotations

import dataclasses
import gc
import json
import math
import sys
import time
from typing import Any, Callable, Dict, List, Optional, Sequence

import numpy as np

from bench import refcore
from bench.registry import REPO_DIR, Registry

__all__ = ["Cell", "CompileCounter", "run_cell", "load_spec", "seed_words"]

#: JAX's default precision for float32 matrix products in a run.
MATMUL_PRECISION = "highest"

#: Bits of the rows Degree-Quant does not keep in float, as configured.
INT_BITS = 8


def load_spec() -> Dict[str, Any]:
    return json.loads((REPO_DIR / "BENCHMARK.json").read_text())


def seed_words(seed: int, n: int = 4) -> List[int]:
    """``n`` 32-bit words from any whole seed, which may exceed 32 bits."""
    return [int(w) for w in np.random.SeedSequence(abs(int(seed))).generate_state(n)]


class CompileCounter:
    """Counts lowerings (programs JAX had not built in this process) and
    backend compiles (those it did not find in the persistent cache)."""

    LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    BACKEND = "/jax/core/compile/backend_compile_duration"

    _instance: Optional["CompileCounter"] = None

    def __init__(self):
        import jax

        self.lowerings = 0
        self.backend = 0
        self.backend_s = 0.0
        self.names: Dict[str, int] = {}
        jax.monitoring.register_event_duration_secs_listener(self._on)

    @classmethod
    def get(cls) -> "CompileCounter":
        """One listener per process: JAX keeps every one registered."""
        if cls._instance is None:
            cls._instance = cls()
        return cls._instance

    def _on(self, event: str, duration: float, fun_name: str = "", **_) -> None:
        if event == self.LOWER:
            self.lowerings += 1
            self.names[fun_name] = self.names.get(fun_name, 0) + 1
        elif event == self.BACKEND:
            self.backend += 1
            self.backend_s += duration

    def snapshot(self) -> Dict[str, float]:
        return {"lowerings": self.lowerings, "backend_compiles": self.backend,
                "backend_compile_s": self.backend_s, "names": dict(self.names)}


@dataclasses.dataclass
class Cell:
    """Everything a generator kind needs, and what it hands back."""

    name: str
    seed: int
    registry: Registry
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    cfg: Any  # the program's ModelConfig, built from the config file
    params: Any  # device weights in the pytree the program serves
    rng: np.random.Generator
    trace: bool
    tracer: Any = None
    # filled by the kind
    records: List[Dict[str, float]] = dataclasses.field(default_factory=list)
    setup_info: Dict[str, float] = dataclasses.field(default_factory=dict)
    checks: List[Any] = dataclasses.field(default_factory=list)
    # (reference graph, features) of the first request the program served:
    # the one its static int8 activation scales are calibrated on
    first_request: Optional[tuple] = None

    @property
    def mix(self) -> Dict[str, Any]:
        """The traffic mix's parameters."""
        return self.traffic["params"]

    def span(self, name: str):
        """A host span on the profiler's clock (free when not tracing)."""
        import jax

        return jax.profiler.TraceAnnotation(f"bench.{name}")

    def features(self, rows: int) -> np.ndarray:
        """A host float32 [rows, d_model] standard-normal buffer from the seed,
        drawn on the device in one call."""
        import jax
        import jax.numpy as jnp

        key = jax.random.PRNGKey(seed_words(self.seed + 7919, 1)[0])
        fn = jax.jit(lambda k: jax.random.normal(k, (rows, self.cfg.d_model), jnp.float32))
        return np.asarray(fn(key))


@dataclasses.dataclass
class CheckItem:
    """One answer to compare: a graph, its features, rows and what the timed
    path returned for those rows."""

    graph: refcore.Graph
    x: np.ndarray
    rows: np.ndarray
    y: np.ndarray


def model_config(config: Dict[str, Any]):
    """The program's ModelConfig, with every key of the file's ``model``."""
    import dataclasses as dc

    from repro.configs.base import get_config
    from repro.models.gnn import api as gnn_api

    cfg = get_config(config["registered"])
    fields = {f.name for f in dc.fields(cfg)}
    unknown = sorted(set(config["model"]) - fields)
    if unknown:
        raise KeyError(f"{config['name']}: ModelConfig has no field {unknown}")
    cfg = dc.replace(cfg, **config["model"])
    stated = config["degree_quant"]["float_ratio"]
    runs = gnn_api.engine_config(cfg).dq.float_ratio
    if not math.isclose(stated, runs):
        raise ValueError(
            f"{config['name']} states Degree-Quant float_ratio {stated}, the "
            f"program runs {runs}"
        )
    return cfg


#: Leaves the FTE quantizes per output column at ``INT_BITS`` bits.
QUANTIZED_LEAVES = ("w",)

#: Least distance, in quantization steps, of a quantized weight from the
#: midpoint between two steps. Float32 places w / scale within about 4e-5
#: steps of its exact value, so nearer than this the program and the
#: reference may round to different neighbours, and one such weight moves a
#: whole column of every int8 row.
TIE_STEPS = 1e-3


def off_ties(w, bits: int = INT_BITS, tie: float = TIE_STEPS):
    """``w`` with every entry that lies within ``tie`` steps of a rounding
    midpoint of its column's symmetric scale (amax / qmax) moved to ``2 * tie``
    steps from it, on its own side; the column maxima do not move."""
    import jax.numpy as jnp

    qmax = 2 ** (bits - 1) - 1
    red = tuple(range(w.ndim - 1))
    scale = jnp.max(jnp.abs(w), axis=red, keepdims=True) / qmax
    t = w / scale
    mid = jnp.floor(t) + 0.5
    near = jnp.abs(t - mid) < tie
    moved = (mid + jnp.where(t >= mid, 2 * tie, -2 * tie)) * scale
    return jnp.where(near, moved, w)


def make_params(cfg, seed: int):
    """Weights in the pytree the program's init builds, drawn Glorot-uniform
    on the device in one jitted call from the seed. The weights the FTE
    quantizes are drawn off rounding ties (``off_ties``): every seed then has
    one int8 rounding of them, at any float32 arithmetic of the scale."""
    import jax
    import jax.numpy as jnp

    from repro.models.gnn import api as gnn_api

    shapes = jax.eval_shape(lambda k: gnn_api.gnn_init(cfg, k), jax.random.PRNGKey(0))
    paths, tree = jax.tree_util.tree_flatten_with_path(shapes)

    @jax.jit
    def draw(key):
        keys = jax.random.split(key, len(paths))
        out = []
        for k, (path, s) in zip(keys, paths):
            fan_in, fan_out = s.shape[0], s.shape[-1]
            lim = math.sqrt(6.0 / (fan_in + fan_out))
            w = jax.random.uniform(k, s.shape, s.dtype, -lim, lim)
            if getattr(path[-1], "key", None) in QUANTIZED_LEAVES:
                w = off_ties(w)
            out.append(w)
        return jax.tree_util.tree_unflatten(tree, out)

    return draw(jax.random.PRNGKey(seed_words(seed, 2)[1]))


def stated_precision(config: Dict[str, Any]) -> refcore.Precision:
    """The precision the configuration states: Degree-Quant float rows in
    float, the rest at ``INT_BITS``."""
    return refcore.Precision(int_bits=INT_BITS,
                             float_ratio=config["degree_quant"]["float_ratio"])


def _float_mask(g: refcore.Graph, ratio: float, rows: np.ndarray, memo: Dict) -> np.ndarray:
    if id(g) not in memo:
        memo[id(g)] = refcore.degree_quant_float_rows(refcore.with_self_loops(g), ratio)
    return memo[id(g)][rows]


def compare(items: Sequence[CheckItem], ref_fn: Callable, params_host,
            stated: refcore.Precision) -> Dict[str, float]:
    """The numbers a run is judged by, worst answer first:

    - ``rms_err``, ``max_err``: relative RMS and max-normalised gaps to the
      float64 reference over the checked rows;
    - ``float_err``: over the checked rows Degree-Quant keeps in float, the
      median of each row's relative gap to the reference at the stated
      precision (``stated``, its int8 activation scales calibrated as the
      program calibrates them). The int8 rows' own rounding cancels, so the
      float rows decide; the median leaves out the few rows reached by an
      int8 value that float32 and float64 round to neighbouring steps, while
      a lower precision in the float rows moves every one of them;
    - ``nonfinite``: non-finite outputs.

    No answer to check, or no float row among them, fails every limit."""
    worst = {"rms_err": math.inf, "max_err": math.inf, "float_err": math.inf,
             "nonfinite": 1}
    if not items:
        return worst
    rms, mx, fe, bad, memo = 0.0, 0.0, 0.0, 0, {}
    for it in items:
        y = np.asarray(it.y, np.float64)
        bad += int((~np.isfinite(y)).sum())
        ref = ref_fn(it.graph, it.x, params_host, it.rows)
        if y.shape != ref.shape:
            return dict(worst, nonfinite=bad + 1)
        d = np.where(np.isfinite(y), y - ref, np.inf)
        rms = max(rms, float(np.sqrt((d * d).sum() / max((ref * ref).sum(), 1e-300))))
        mx = max(mx, float(np.abs(d).max() / max(np.abs(ref).max(), 1e-300)))
        fr = _float_mask(it.graph, stated.float_ratio, it.rows, memo)
        if not fr.any():
            return dict(worst, rms_err=rms, max_err=mx, nonfinite=bad)
        ref_s = ref_fn(it.graph, it.x, params_host, it.rows[fr], stated)
        ds = np.where(np.isfinite(y[fr]), y[fr] - ref_s, np.inf)
        gaps = np.linalg.norm(ds, axis=1) / np.maximum(np.linalg.norm(ref_s, axis=1), 1e-300)
        fe = max(fe, float(np.median(gaps)))
    return {"rms_err": rms, "max_err": mx, "float_err": fe, "nonfinite": bad}


def check_answers(cell: "Cell", params_host, stated: Optional[refcore.Precision] = None
                  ) -> Dict[str, float]:
    """``compare`` for the cell's answers; ``stated`` is calibrated on the
    cell's first request unless given."""
    ref = cell.registry.reference(cell.config["arch"]).forward
    if stated is None:
        stated = stated_precision(cell.config)
        if cell.checks and cell.first_request is not None:
            stated = refcore.calibrate(ref, *cell.first_request, params_host, stated)
    return compare(cell.checks, ref, params_host, stated)


def judge(numbers: Dict[str, float], limits: Dict[str, float], failed: int
          ) -> Dict[str, Dict[str, float]]:
    out = {k: {"value": numbers[k], "limit": limits[k]} for k in limits}
    out["failed"] = {"value": failed, "limit": 0}
    return out


def _device_info(devices) -> Dict[str, Any]:
    d = devices[0]
    peak = 0
    for dev in devices:
        stats = dev.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devices),
            "memory_peak_bytes": peak}


def workload_entry(spec: Dict[str, Any], workload: str) -> Dict[str, Any]:
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; have {sorted(cells)}")
    return cells[workload]


def make_cell(spec: Dict[str, Any], registry: Registry, workload: str, seed: int,
              trace: bool = False):
    """(cell, its traffic kind's module, its limits) for one run."""
    w = workload_entry(spec, workload)
    config = registry.config(w["config"])
    traffic = registry.traffic(w["traffic"])
    limits = registry.json("limits", workload)
    cfg = model_config(config)
    cell = Cell(
        name=workload, seed=seed, registry=registry, config=config,
        traffic=traffic, cfg=cfg, params=make_params(cfg, seed),
        rng=np.random.default_rng(seed_words(seed, 4)), trace=trace,
    )
    return cell, registry.kind(traffic["kind"]), limits


def host_params(params):
    import jax

    return jax.tree_util.tree_map(lambda a: np.asarray(a, np.float64), params)


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             spec: Optional[Dict[str, Any]] = None,
             registry: Optional[Registry] = None,
             t_start: Optional[float] = None,
             compile_cache: bool = True,
             matmul_precision: Optional[str] = MATMUL_PRECISION,
             log=sys.stderr) -> Dict[str, Any]:
    """One run of one cell; returns the result object (not yet printed).
    ``matmul_precision`` None leaves JAX's default (a control's run)."""
    t_start = time.perf_counter() if t_start is None else t_start
    import jax

    from bench import trace as btrace
    from repro.launch.compile_cache import configure_compile_cache

    spec = load_spec() if spec is None else spec
    registry = registry or Registry()
    w = workload_entry(spec, workload)
    devices = jax.devices()[: w["chips"]]

    if compile_cache:
        cache_dir = configure_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        print(f"compile cache: {cache_dir}", file=log)
    if matmul_precision is not None:
        jax.config.update("jax_default_matmul_precision", matmul_precision)
    counter = CompileCounter.get()

    cell, kind, limits = make_cell(spec, registry, workload, seed, trace)
    config = cell.config
    if trace:
        cell.tracer = btrace.Tracer()
    runner = kind.Traffic(cell)
    with cell.span("setup"):
        runner.setup()
    setup_s = time.perf_counter() - t_start
    before = counter.snapshot()
    runner.window(seconds)
    after = counter.snapshot()
    names = {k: v - before["names"].get(k, 0) for k, v in after.pop("names").items()}
    before.pop("names")
    in_window = {k: after[k] - before[k] for k in after}
    top = sorted(((v, k) for k, v in names.items() if v), reverse=True)[:8]
    print(f"compiles in window: lowerings={in_window['lowerings']} "
          f"backend_compiles={in_window['backend_compiles']} "
          f"backend_compile_s={in_window['backend_compile_s']:.6f} "
          f"most_lowered={[(k, v) for v, k in top]}", file=log)

    device = _device_info(devices)
    reduced = None
    if trace:
        reduced = cell.tracer.reduce()
        print(f"trace: {reduced['trace_bytes']} bytes, {reduced['requests']} requests, "
              f"stop {reduced['stop_s']:.3f} s, read {reduced['parse_s']:.3f} s", file=log)
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
    attempted = len(cell.records)
    failed = sum(1 for r in cell.records if r.get("error"))
    params_host = host_params(cell.params)
    runner.free()
    cell.params = None
    gc.collect()

    from bench import counts

    peaks = counts.peaks_for(device["kind"]) if device["platform"] == "tpu" else None
    ctx = {
        "cell": cell, "records": [r for r in cell.records if not r.get("error")],
        "setup_s": setup_s, "setup": cell.setup_info,
        "trace": reduced, "peaks": peaks, "config": config,
        "compiles": in_window,
    }
    group = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in spec[group]:
        if "workloads" in m and workload not in m["workloads"]:
            continue
        value = registry.reader(m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    t_ref = time.perf_counter()
    numbers = check_answers(cell, params_host)
    checks = judge(numbers, limits["limits"], failed)
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    print(f"reference: {len(cell.checks)} answers in "
          f"{time.perf_counter() - t_ref:.3f} s", file=log)
    result = {"correct": bool(correct), "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if reduced is not None:
        result["breakdown"] = reduced["breakdown"]
    result["checks"] = checks
    return result


def emit(result: Dict[str, Any], out=sys.stdout, err=sys.stderr) -> None:
    """Checks as the last lines of stderr, the result as the last stdout line."""
    for k, c in result["checks"].items():
        print(f"check {k}: {c['value']!r} limit {c['limit']!r}", file=err)
    err.flush()
    print(json.dumps(result), file=out)
    out.flush()
