"""Multi-pod dry-run: lower + compile every (arch × shape) cell on the
production mesh and record memory/cost/collective analysis.

MUST set the device-count flag before jax initializes — these two lines stay
first (``setdefault`` so an outer harness can test with fewer fake devices).
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")

import argparse
import json
import time
import traceback
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import SHAPES, ModelConfig, get_config, list_configs
from repro.distributed.sharding import (
    batch_shardings,
    cache_shardings,
    make_policy,
    param_shardings,
    replicated,
    state_shardings,
)
from repro.launch.hlo_analysis import analyze_collectives
from repro.launch.input_specs import (
    decode_token_specs,
    gnn_input_specs,
    prefill_input_specs,
    train_input_specs,
)
from repro.launch.mesh import make_production_mesh, mesh_tp
from repro.models.api import model_init, model_init_cache, model_prefill
from repro.train.train_step import init_train_state, make_serve_step, make_train_step

# TPU v5e hardware constants (per chip) for the roofline terms.
PEAK_FLOPS_BF16 = 197e12
HBM_BW = 819e9
ICI_BW_PER_LINK = 50e9

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..", "experiments", "dryrun")


def _mesh_name(multi_pod: bool) -> str:
    return "pod2x16x16" if multi_pod else "pod16x16"


def skip_reason(cfg: ModelConfig, shape_name: str) -> Optional[str]:
    if shape_name == "long_500k" and cfg.family not in ("ssm", "hybrid"):
        return (
            "long_500k requires sub-quadratic attention; this arch is pure "
            "full-attention (see DESIGN.md §Arch-applicability)"
        )
    return None


def _jsonable(d):
    out = {}
    for k, v in (d or {}).items():
        try:
            out[k] = float(v)
        except (TypeError, ValueError):
            out[k] = str(v)
    return out


def _mem_report(compiled):
    m = compiled.memory_analysis()
    if m is None:
        return {}
    keys = [
        "argument_size_in_bytes",
        "output_size_in_bytes",
        "temp_size_in_bytes",
        "generated_code_size_in_bytes",
        "alias_size_in_bytes",
    ]
    rep = {}
    for k in keys:
        if hasattr(m, k):
            rep[k] = int(getattr(m, k))
    if rep:
        rep["peak_bytes_per_device"] = (
            rep.get("argument_size_in_bytes", 0)
            + rep.get("output_size_in_bytes", 0)
            + rep.get("temp_size_in_bytes", 0)
            - rep.get("alias_size_in_bytes", 0)
        )
    return rep


def _analyze(lowered, compiled, cfg: ModelConfig, shape_name: str, mesh) -> Dict:
    from repro.launch.analytic import analytic_report

    cost = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    coll = analyze_collectives(hlo, ring_size=mesh_tp(mesh))
    chips = int(len(mesh.devices.flat))
    flops_hlo = float(cost.get("flops", 0.0))
    bytes_hlo = float(cost.get("bytes accessed", 0.0))
    rec: Dict = {
        "chips": chips,
        # raw cost_analysis — NOTE: XLA counts while(scan) bodies ONCE, so
        # these under-report for scanned-layer programs; the analytic numbers
        # below follow the exact einsum structure and are loop-exact
        # (cross-checked against unrolled HLO for the hillclimb cells).
        "hlo_flops_per_device": flops_hlo,
        "hlo_bytes_per_device": bytes_hlo,
        "collective_bytes_by_kind": coll.bytes_by_kind,
        "collective_counts": coll.count_by_kind,
        "collective_wire_bytes": coll.wire_bytes,
        "memory": _mem_report(compiled),
        "cost_analysis": _jsonable(cost),
        "hlo_size_chars": len(hlo),
    }
    if shape_name in SHAPES:
        rec.update(analytic_report(cfg, SHAPES[shape_name], chips))
        flops_dev = max(rec["analytic_step_flops_per_device"], flops_hlo)
        bytes_dev = max(rec["analytic_hbm_bytes_per_device"], bytes_hlo)
    else:
        flops_dev, bytes_dev = flops_hlo, bytes_hlo
    terms = {
        "compute_s": flops_dev / PEAK_FLOPS_BF16,
        "memory_s": bytes_dev / HBM_BW,
        "collective_s": coll.wire_bytes / ICI_BW_PER_LINK,
    }
    rec["roofline_terms_s"] = terms
    rec["dominant_term"] = max(terms, key=terms.get)
    bound = max(terms.values())
    rec["roofline_fraction"] = terms["compute_s"] / bound if bound else 0.0
    return rec


# ------------------------------------------------------------------ lowering
def lower_cell(
    arch: str,
    shape_name: str,
    *,
    multi_pod: bool = False,
    seq_shard: bool = False,
    capacity_factor: Optional[float] = None,
    remat: Optional[str] = None,
    parallel_mode: str = "auto",
    kv_cache_dtype: Optional[str] = None,
) -> Dict:
    """Lower+compile one cell; returns the result record (also JSON-dumped)."""
    t0 = time.time()
    cfg = get_config(arch)
    if capacity_factor is not None:
        import dataclasses

        cfg = dataclasses.replace(cfg, capacity_factor=capacity_factor)
    if remat is not None:
        import dataclasses

        cfg = dataclasses.replace(cfg, remat=remat)
    if kv_cache_dtype is not None:
        import dataclasses

        cfg = dataclasses.replace(cfg, kv_cache_dtype=kv_cache_dtype)
    elif SHAPES.get(shape_name) and SHAPES[shape_name].kind == "train":
        # paper-faithful baseline policy: block remat for every train lower
        # (saving full per-layer activations at 4k×256 does not fit any chip)
        import dataclasses

        cfg = dataclasses.replace(cfg, remat="block")
    rec: Dict = {
        "arch": arch,
        "shape": shape_name,
        "mesh": _mesh_name(multi_pod),
        "family": cfg.family,
        "params": cfg.param_count(),
        "active_params": cfg.active_param_count(),
    }
    if cfg.family == "gnn":
        return _lower_gnn(cfg, rec, multi_pod=multi_pod)
    shape = SHAPES[shape_name]
    reason = skip_reason(cfg, shape_name)
    if reason:
        rec["skipped"] = reason
        return rec
    mesh = make_production_mesh(multi_pod=multi_pod)
    tp = mesh_tp(mesh)
    if parallel_mode == "auto":
        # TP only pays for itself above ~20B params (measured: below that,
        # activation all-reduces dwarf compute); decode always keeps TP for
        # KV-cache sequence sharding.
        parallel_mode = (
            "fsdp"
            if cfg.param_count() < 20e9
            and not cfg.is_moe  # MoE group dispatch needs data-aligned tokens
            and shape.kind in ("train", "prefill")
            else "tp"
        )
    rec["parallel_mode"] = parallel_mode
    policy = make_policy(mesh, seq_shard=seq_shard, mode=parallel_mode)
    key = jax.random.PRNGKey(0)

    params_shape = jax.eval_shape(lambda: model_init(cfg, key, tp=tp))
    param_sh = param_shardings(cfg, params_shape, mesh, mode=parallel_mode)

    if shape.kind == "train":
        state_shape = jax.eval_shape(lambda p: init_train_state(cfg, p), params_shape)
        state_sh = state_shardings(cfg, state_shape, mesh, mode=parallel_mode)
        batch = train_input_specs(cfg, shape)
        batch_sh = batch_shardings(cfg, batch, mesh, mode=parallel_mode)
        step = make_train_step(cfg, policy=policy)
        out_shape = jax.eval_shape(step, state_shape, batch)
        out_sh = (state_sh, jax.tree.map(lambda _: replicated(mesh), out_shape[1]))
        jitted = jax.jit(
            step, in_shardings=(state_sh, batch_sh), out_shardings=out_sh,
            donate_argnums=0,
        )
        lowered = jitted.lower(state_shape, batch)
    elif shape.kind == "prefill":
        batch = prefill_input_specs(cfg, shape)
        batch_sh = batch_shardings(cfg, batch, mesh, mode=parallel_mode)

        def prefill_step(params, b):
            logits, cache, n = model_prefill(params, cfg, b, shape.seq_len, policy=policy)
            return logits, cache, n

        out_shape = jax.eval_shape(prefill_step, params_shape, batch)
        cache_sh = cache_shardings(cfg, out_shape[1], mesh, batch=shape.global_batch)
        logits_sh = jax.tree.map(lambda _: replicated(mesh), out_shape[0])
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.launch.mesh import data_axes

        if parallel_mode == "fsdp":
            ba = policy._batch_axes(shape.global_batch)
            seq_ax = "model" if (ba is None or "model" not in (ba or ())) else None
            logits_sh = NamedSharding(mesh, P(ba, seq_ax, None))
        else:
            logits_sh = NamedSharding(mesh, P(data_axes(mesh), None, "model"))
        jitted = jax.jit(
            prefill_step,
            in_shardings=(param_sh, batch_sh),
            out_shardings=(logits_sh, cache_sh, replicated(mesh)),
        )
        lowered = jitted.lower(params_shape, batch)
    else:  # decode
        tok = decode_token_specs(cfg, shape)
        tok_sh = batch_shardings(cfg, tok, mesh)
        cache_batch = dict(tok)
        if cfg.family == "audio":
            cache_batch = {
                "src_embeds": jax.ShapeDtypeStruct(
                    (shape.global_batch, shape.seq_len, cfg.d_model), jnp.float32
                )
            }
        cache_shape = jax.eval_shape(
            lambda p, b: model_init_cache(cfg, p, b, max_len=shape.seq_len, tp=tp),
            params_shape,
            cache_batch,
        )
        cache_sh = cache_shardings(cfg, cache_shape, mesh, batch=shape.global_batch)
        step = make_serve_step(cfg, policy=policy)
        out_shape = jax.eval_shape(
            step, params_shape, tok, cache_shape, jnp.zeros((), jnp.int32)
        )
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.launch.mesh import data_axes

        dp = data_axes(mesh)
        bdiv = shape.global_batch % (
            int(jnp.prod(jnp.asarray([mesh.shape[a] for a in dp])))
        ) == 0
        tok_out_sh = NamedSharding(mesh, P(dp if bdiv else None))
        logits_out_sh = NamedSharding(mesh, P(dp if bdiv else None, "model"))
        jitted = jax.jit(
            step,
            in_shardings=(param_sh, tok_sh, cache_sh, replicated(mesh)),
            out_shardings=(tok_out_sh, logits_out_sh, cache_sh),
            donate_argnums=2,
        )
        lowered = jitted.lower(params_shape, tok, cache_shape, jnp.zeros((), jnp.int32))

    compiled = lowered.compile()
    rec.update(_analyze(lowered, compiled, cfg, shape_name, mesh))
    rec["compile_s"] = time.time() - t0
    return rec


def _lower_gnn(cfg: ModelConfig, rec: Dict, *, multi_pod: bool) -> Dict:
    """The paper's own workload at Yelp scale on the production mesh."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core.aggregation import DeviceTilePlan, aggregate_edge_tiles
    from repro.launch.mesh import data_axes

    mesh = make_production_mesh(multi_pod=multi_pod)
    dp = data_axes(mesh)
    specs, meta = gnn_input_specs(cfg)
    n, s = meta["num_nodes"], meta["segments_per_tile"]

    def gnn_step(x, gather_idx, coeff, seg_ids, out_node, edge_ids, w1, w2):
        dplan = DeviceTilePlan(gather_idx, coeff, seg_ids, out_node, edge_ids)
        m = aggregate_edge_tiles(x, dplan, num_nodes=n, segments_per_tile=s)
        h = jax.nn.relu(m @ w1)
        m2 = aggregate_edge_tiles(h, dplan, num_nodes=n, segments_per_tile=s)
        return m2 @ w2

    sh = {
        "x": NamedSharding(mesh, P(None, None)),
        "gather_idx": NamedSharding(mesh, P(dp, None)),
        "coeff": NamedSharding(mesh, P(dp, None)),
        "seg_ids": NamedSharding(mesh, P(dp, None)),
        "out_node": NamedSharding(mesh, P(dp, None)),
        "edge_ids": NamedSharding(mesh, P(dp, None)),
        "w1": NamedSharding(mesh, P(None, "model")),
        "w2": NamedSharding(mesh, P("model", None)),
    }
    ks = ["x", "gather_idx", "coeff", "seg_ids", "out_node", "edge_ids", "w1", "w2"]
    args = [specs[k] for k in ks]
    in_sh = tuple(sh[k] for k in ks)
    t0 = time.time()
    jitted = jax.jit(gnn_step, in_shardings=in_sh,
                     out_shardings=NamedSharding(mesh, P(None, None)))
    lowered = jitted.lower(*args)
    compiled = lowered.compile()
    rec.update(_analyze(lowered, compiled, cfg, "gnn_yelp", mesh))
    rec["shape"] = "gnn_yelp"
    rec["compile_s"] = time.time() - t0
    return rec


# ---------------------------------------------------------------------- CLI
def run_and_save(arch: str, shape: str, multi_pod: bool, out_dir: str,
                 skip_existing: bool = False, **kw) -> Dict:
    os.makedirs(out_dir, exist_ok=True)
    fn = os.path.join(out_dir, f"{arch}__{shape}__{_mesh_name(multi_pod)}.json")
    if skip_existing and os.path.exists(fn):
        with open(fn) as f:
            rec = json.load(f)
        if not rec.get("error"):
            print(f"[CACHED] {arch} × {shape} × {_mesh_name(multi_pod)}", flush=True)
            return rec
    try:
        rec = lower_cell(arch, shape, multi_pod=multi_pod, **kw)
    except Exception as e:  # a failed cell is a bug — record it loudly
        rec = {
            "arch": arch, "shape": shape, "mesh": _mesh_name(multi_pod),
            "error": f"{type(e).__name__}: {e}",
            "traceback": traceback.format_exc()[-4000:],
        }
    with open(fn, "w") as f:
        json.dump(rec, f, indent=1)
    status = "SKIP" if rec.get("skipped") else ("FAIL" if rec.get("error") else "OK")
    dom = rec.get("dominant_term", "-")
    print(f"[{status}] {arch} × {shape} × {_mesh_name(multi_pod)}  dominant={dom}  "
          f"t={rec.get('compile_s', 0):.0f}s", flush=True)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default=os.path.normpath(OUT_DIR))
    ap.add_argument("--capacity-factor", type=float, default=None)
    ap.add_argument("--remat", default=None)
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--parallel-mode", default="auto")
    ap.add_argument("--kv-cache-dtype", default=None)
    args = ap.parse_args()
    archs = [a for a in list_configs()] if args.arch == "all" else args.arch.split(",")
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    for arch in archs:
        fam = get_config(arch).family
        shapes = (
            ["gnn_yelp"] if fam == "gnn"
            else (list(SHAPES) if args.shape == "all" else args.shape.split(","))
        )
        for shape in shapes:
            for mp in meshes:
                run_and_save(
                    arch, shape, mp, args.out, skip_existing=args.skip_existing,
                    capacity_factor=args.capacity_factor, remat=args.remat,
                    parallel_mode=args.parallel_mode,
                    kv_cache_dtype=args.kv_cache_dtype,
                )


if __name__ == "__main__":
    main()
