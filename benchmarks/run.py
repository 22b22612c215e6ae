"""Benchmark harness — one function per paper table/figure + LM benches.

Prints ``name,us_per_call,derived`` CSV rows (derived column is
metric-specific, annotated per row). CPU wall-clock rows measure THIS
machine's jnp engine; accelerator rows come from the discrete-event simulator
(core/simulator.py) at the paper's 200 MHz operating point; paper-published
CPU/GPU baselines are carried as reference constants where a real Xeon/A6000
is unavailable.

    PYTHONPATH=src python -m benchmarks.run [--quick]
"""
from __future__ import annotations

import argparse
import os
import time
from typing import Callable, List

import numpy as np

# Large-graph rows regenerate yelp/reddit-scale lognormal graphs; cache the
# structures on disk so repeat bench runs skip the dominant setup cost.
# Anchored to the repo root (same default as tests/conftest.py) so runs from
# any cwd share one cache.
os.environ.setdefault(
    "REPRO_DATASET_CACHE",
    os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ".dataset-cache",
    ),
)

ROWS: List[str] = []


def emit(name: str, us_per_call: float, derived: str) -> None:
    row = f"{name},{us_per_call:.1f},{derived}"
    ROWS.append(row)
    print(row, flush=True)


def _time(fn: Callable, *, reps: int = 3, warmup: int = 1) -> float:
    for _ in range(warmup):
        fn()
    t0 = time.perf_counter()
    for _ in range(reps):
        fn()
    return (time.perf_counter() - t0) / reps * 1e6


# ------------------------------------------------------- Table 4: DQ ratios
def table4_dq_ratios(quick: bool) -> None:
    """Degree-Quant protection ratios on the (synthetic) paper datasets."""
    from repro.core.degree_quant import DegreeQuantConfig, inference_precision_tags
    from repro.graphs.datasets import PAPER_DATASETS, make_dataset

    for name, spec in PAPER_DATASETS.items():
        n = min(spec.num_nodes, 50_000 if quick else 250_000)
        g = make_dataset(name, max_nodes=n, with_features=False)
        t0 = time.perf_counter()
        tags = inference_precision_tags(
            g, DegreeQuantConfig(float_ratio=spec.dq_float_ratio)
        )
        us = (time.perf_counter() - t0) * 1e6
        got = float((tags == "float").mean())
        emit(
            f"table4_dq_ratio_{name}", us,
            f"float_ratio={got:.4f};paper={spec.dq_float_ratio:.3f}",
        )


# ------------------------------------- Table 5: latency/throughput (GCN)
PAPER_CPU_MS = {"cora": 244.4, "citeseer": 244.3, "pubmed": 362.4,
                "flickr": 475.4, "reddit": 953.3, "yelp": 760.8}
PAPER_GPU_MS = {"cora": 7.2, "citeseer": 10.1, "pubmed": 4.8,
                "flickr": 14.5, "reddit": 171.0, "yelp": 110.9}
PAPER_AMPLE_MS = {"cora": 0.246, "citeseer": 0.294, "pubmed": 1.617,
                  "flickr": 7.227, "reddit": 24.6, "yelp": 57.5}


def table5_latency(quick: bool) -> None:
    from repro.core.simulator import simulate_dataset

    cap = 30_000 if quick else 120_000
    gains = []
    for name in PAPER_CPU_MS:
        t0 = time.perf_counter()
        rec = simulate_dataset(name, max_nodes=cap)
        us = (time.perf_counter() - t0) * 1e6
        gain_cpu = PAPER_CPU_MS[name] / rec["latency_ms"]
        gains.append(gain_cpu)
        emit(
            f"table5_ample_{name}", us,
            f"sim_ms={rec['latency_ms']:.3f};paper_ms={PAPER_AMPLE_MS[name]:.3f};"
            f"gain_vs_paper_cpu={gain_cpu:.0f}x;nodes_per_ms={rec['nodes_per_ms']:.0f}",
        )
    emit("table5_mean_cpu_gain", 0.0, f"mean_gain={np.mean(gains):.0f}x;paper=361x")


# ----------------------------- Figure 4: speedup across models × datasets
def figure4_speedup(quick: bool) -> None:
    """Event-driven vs double-buffered accelerator, per model family.

    GIN doubles the FTE work (2-layer MLP); GraphSAGE adds the φ projection
    before aggregation — Table 3 structure (handled in simulate_dataset via
    hidden dims).
    """
    from repro.core.simulator import SimConfig, simulate_dataset

    cap = 20_000 if quick else 90_000
    datasets = ["cora", "pubmed", "flickr"] if quick else list(PAPER_CPU_MS)
    for model in ["gcn", "gin", "sage"]:
        sp = []
        for name in datasets:
            ev = simulate_dataset(name, model=model, max_nodes=cap)
            db = simulate_dataset(
                name, model=model, max_nodes=cap, cfg=SimConfig(event_driven=False)
            )
            sp.append(db["latency_ms"] / ev["latency_ms"])
        emit(
            f"figure4_event_driven_speedup_{model}", 0.0,
            f"geomean_vs_double_buffer={float(np.exp(np.mean(np.log(sp)))):.2f}x;"
            f"datasets={len(sp)}",
        )


# ----------------------- engine wall-clock: scheduling paths on this CPU
def bench_engine_paths(quick: bool) -> None:
    import jax.numpy as jnp

    from repro.core import build_edge_tile_plan, build_padded_plan
    from repro.core.aggregation import (
        aggregate_edge_tiles,
        aggregate_padded_plan,
        to_device_plan,
    )
    from repro.graphs.datasets import make_dataset

    n = 3_000 if quick else 19_717
    g = make_dataset("pubmed", max_nodes=n, max_feature_dim=128)
    x = jnp.asarray(g.features)
    plan = build_edge_tile_plan(g, edges_per_tile=256)
    dplan = to_device_plan(plan)
    kw = dict(num_nodes=g.num_nodes, segments_per_tile=plan.segments_per_tile)

    us_ev = _time(lambda: aggregate_edge_tiles(x, dplan, **kw).block_until_ready())
    emit("engine_event_driven_agg", us_ev,
         f"occupancy={plan.lane_occupancy:.3f};edges={g.num_edges}")

    padded = build_padded_plan(g, batch_size=64)
    us_pad = _time(
        lambda: aggregate_padded_plan(x, padded).block_until_ready(), reps=1
    )
    emit("engine_double_buffer_agg", us_pad,
         f"gap_ratio={padded.pipeline_gap_ratio:.3f};speedup_ev={us_pad/us_ev:.2f}x")


def bench_mixed_precision(quick: bool) -> None:
    import jax.numpy as jnp

    from repro.core import AmpleEngine, EngineConfig
    from repro.graphs.datasets import make_dataset

    n = 2_000 if quick else 10_000
    g = make_dataset("cora", max_nodes=n, max_feature_dim=256)
    x = jnp.asarray(g.features)
    eng_fp = AmpleEngine(g, EngineConfig(mixed_precision=False))
    eng_mp = AmpleEngine(g, EngineConfig(mixed_precision=True))
    us_fp = _time(lambda: eng_fp.aggregate(x).block_until_ready())
    us_mp = _time(lambda: eng_mp.aggregate(x).block_until_ready())
    rep = eng_mp.occupancy_report()
    emit("engine_fp32_agg", us_fp, "precision=float32")
    emit("engine_mixed_agg", us_mp,
         f"float_ratio={rep['float_node_ratio']:.3f};gather_bytes_ratio=0.28")


# ------------------------------ gnn-serve: plan cache economics (serving)
def bench_gnn_serve(quick: bool) -> None:
    """Cold-plan vs cache-hit latency through GNNServeEngine, plus batched
    small-graph serving — the serving analogue of nodeslot recycling."""
    import jax

    from repro.configs.base import get_config
    from repro.graphs.datasets import make_dataset
    from repro.serve.gnn_engine import GNNRequest, GNNServeEngine

    cfg = get_config("ample-gcn", reduced=True)
    n = 1_000 if quick else 5_000
    g = make_dataset("cora", max_nodes=n, max_feature_dim=cfg.d_model, seed=0)
    eng = GNNServeEngine(cfg, key=jax.random.PRNGKey(0))

    cold = eng.infer(g, g.features)  # pays planner + jit
    warm = eng.infer(g, g.features)  # plan-cache hit, compiled device call
    warm_us = _time(lambda: eng.infer(g, g.features), reps=3)
    emit(
        "gnn_serve_cold_plan", cold.plan_ms * 1e3,
        f"nodes={g.num_nodes};edges={g.num_edges};cache_hit={cold.cache_hit}",
    )
    emit(
        "gnn_serve_cache_hit", warm_us,
        f"plan_ms={warm.plan_ms:.3f};speedup_vs_cold_plan="
        f"{(cold.plan_ms * 1e3 + warm_us) / max(warm_us, 1e-9):.2f}x;"
        f"hits={eng.stats['cache_hits']};planner_calls={eng.stats['planner_calls']}",
    )

    small = [
        make_dataset("cora", max_nodes=n // 8, max_feature_dim=cfg.d_model, seed=s)
        for s in range(1, 5)
    ]
    reqs = [GNNRequest(graph=s, features=s.features) for s in small]
    eng.infer_batch(reqs)  # compile + plan the union once
    us_batch = _time(lambda: eng.infer_batch(reqs), reps=3)
    us_seq = _time(lambda: [eng.infer(s, s.features) for s in small], reps=3)
    emit(
        "gnn_serve_batched_union", us_batch,
        f"graphs={len(reqs)};nodes={sum(s.num_nodes for s in small)};"
        f"speedup_vs_sequential={us_seq / max(us_batch, 1e-9):.2f}x",
    )

    # GAT: runtime edge coefficients through the same plan-cached engine —
    # attention changes every request, the structure-keyed plan cache does not.
    gat_cfg = get_config("ample-gat", reduced=True)
    gat = GNNServeEngine(gat_cfg, key=jax.random.PRNGKey(0))
    gat_g = make_dataset("cora", max_nodes=n, max_feature_dim=gat_cfg.d_model, seed=0)
    gat_cold = gat.infer(gat_g, gat_g.features)
    gat_warm = gat.infer(gat_g, gat_g.features)
    gat_us = _time(lambda: gat.infer(gat_g, gat_g.features), reps=3)
    emit(
        "gnn_serve_gat_cold_plan", gat_cold.plan_ms * 1e3,
        f"nodes={gat_g.num_nodes};edges={gat_g.num_edges};"
        f"heads={gat_cfg.gnn_heads};cache_hit={gat_cold.cache_hit}",
    )
    emit(
        "gnn_serve_gat_cache_hit", gat_us,
        f"plan_ms={gat_warm.plan_ms:.3f};cache_hit={gat_warm.cache_hit};"
        f"planner_calls={gat.stats['planner_calls']};"
        f"vs_gcn_warm={gat_us / max(warm_us, 1e-9):.2f}x",
    )


# -------------- runtime-coeff overhead: the scatter cost in isolation
def bench_runtime_coeff(quick: bool) -> None:
    """Static-coeff GCN vs runtime-coeff GCN on the same graph and engine:
    the same values flow, but the runtime path scatters them through the
    ``edge_ids`` indirection per call — the isolated cost of decoupling
    coefficients from compiled plans (outputs are bitwise-identical)."""
    import jax.numpy as jnp

    from repro.core.message_passing import (
        AmpleEngine,
        EngineConfig,
        aggregation_coefficients,
    )
    from repro.graphs.csr import add_self_loops
    from repro.graphs.datasets import make_dataset

    n = 2_000 if quick else 10_000
    g = add_self_loops(make_dataset("pubmed", max_nodes=n, max_feature_dim=128, seed=0))
    x = jnp.asarray(g.features)
    eng = AmpleEngine(g, EngineConfig(mixed_precision=True))
    coeff = jnp.asarray(aggregation_coefficients(g, "gcn"))

    eng.aggregate(x, mode="gcn").block_until_ready()  # jit + plan warm
    eng.aggregate(x, mode="runtime", edge_coeff=coeff).block_until_ready()
    # reps high for a ~ms-scale microbench: the overhead being isolated is a
    # few % of the call, well under run-to-run load noise at 3 reps.
    us_static = _time(
        lambda: eng.aggregate(x, mode="gcn").block_until_ready(), reps=10
    )
    us_rt = _time(
        lambda: eng.aggregate(
            x, mode="runtime", edge_coeff=coeff
        ).block_until_ready(),
        reps=10,
    )
    emit(
        "gnn_runtime_coeff_overhead", us_rt - us_static,
        f"static_us={us_static:.1f};runtime_us={us_rt:.1f};"
        f"overhead={us_rt / max(us_static, 1e-9):.2f}x;edges={g.num_edges}",
    )


def bench_attention(quick: bool) -> None:
    """Per-GAT-layer attention cost at fixed total width (H·dh = 64):
    the retired looped-head baseline (H× softmax/aggregate passes) vs the
    [E, H] head-vectorized jnp path vs the fused-kernel decomposition
    (per-tile (m, l, a) + log-sum-exp combine; jnp oracle timed — the
    Pallas launch itself targets TPU, interpret mode is not a timing).
    Acceptance: vectorized ≥ 2x the looped baseline at H=4. Also times the
    int8 FTE matmul on the load-time repacked weight layout vs unpacked."""
    import jax
    import jax.numpy as jnp

    from repro.core.message_passing import AmpleEngine, EngineConfig
    from repro.graphs.csr import add_self_loops
    from repro.graphs.datasets import make_dataset
    from repro.kernels.segment_agg.ref import attend_tiles_ref

    n = 2_000 if quick else 10_000
    g = add_self_loops(
        make_dataset("pubmed", max_nodes=n, max_feature_dim=64, seed=0)
    )
    eng = AmpleEngine(g, EngineConfig(mixed_precision=False))
    rng = np.random.default_rng(0)
    slope = 0.2

    def looped(scores, z):
        # the pre-PR per-head loop: H separate softmax + aggregate passes
        outs = []
        for h in range(scores.shape[1]):
            sc = jax.nn.leaky_relu(scores[:, h], slope)
            alpha = eng.edge_softmax(sc)
            outs.append(
                eng.aggregate(z[:, h], mode="runtime", edge_coeff=alpha)
            )
        return jnp.stack(outs, axis=1)

    for heads in (2, 4, 8):
        dh = 64 // heads
        z = jnp.asarray(
            rng.standard_normal((g.num_nodes, heads, dh)).astype(np.float32)
        )
        scores = jnp.asarray(
            rng.standard_normal((g.num_edges, heads)).astype(np.float32)
        )
        looped(scores, z).block_until_ready()
        eng.attention_aggregate(scores, z, leaky_slope=slope).block_until_ready()
        us_loop = _time(
            lambda: looped(scores, z).block_until_ready(), reps=5
        )
        us_vec = _time(
            lambda: eng.attention_aggregate(
                scores, z, leaky_slope=slope
            ).block_until_ready(),
            reps=5,
        )
        emit(
            f"gat_attention_h{heads}", us_vec,
            f"looped_us={us_loop:.1f};vectorized_us={us_vec:.1f};"
            f"speedup_vs_looped={us_loop / us_vec:.2f}x;"
            f"edges={g.num_edges};dh={dh}",
        )
        if heads == 4:
            from repro.core.aggregation import tile_edge_coeff

            plans = eng.plans("runtime")
            p = plans["float"]
            dp = eng._device_plans("runtime", plans, edge_ids=True)["float"]
            sc_t = tile_edge_coeff(dp, scores, fill=-jnp.inf)
            fused = jax.jit(
                lambda z, sc_t: attend_tiles_ref(
                    z, dp.gather_idx, sc_t, dp.coeff, dp.seg_ids,
                    dp.out_node, num_nodes=g.num_nodes,
                    segments_per_tile=p.segments_per_tile,
                    leaky_slope=slope,
                )
            )
            fused(z, sc_t).block_until_ready()
            us_fused = _time(
                lambda: fused(z, sc_t).block_until_ready(), reps=5
            )
            emit(
                "gat_attention_fused_oracle_h4", us_fused,
                f"looped_us={us_loop:.1f};"
                f"speedup_vs_looped={us_loop / us_fused:.2f}x;"
                f"tiles={p.num_tiles};one_launch_per_layer=true",
            )

    # int8 FTE: per-call pad/stride vs the load-time repacked tiling
    # (bitwise-identical int32; interpret mode on CPU, layout cost only)
    from repro.kernels.quant_matmul import ops as qm_ops

    m, k, nn = (256, 128, 128) if quick else (1024, 256, 256)
    a_q = jnp.asarray(rng.integers(-127, 128, (m, k)), jnp.int8)
    w_q = jnp.asarray(rng.integers(-127, 128, (k, nn)), jnp.int8)
    packed = qm_ops.repack_weight(w_q)
    qm_ops.quant_matmul(a_q, w_q).block_until_ready()
    qm_ops.quant_matmul_repacked(a_q, packed).block_until_ready()
    us_unpacked = _time(
        lambda: qm_ops.quant_matmul(a_q, w_q).block_until_ready(), reps=3
    )
    us_packed = _time(
        lambda: qm_ops.quant_matmul_repacked(a_q, packed).block_until_ready(),
        reps=3,
    )
    emit(
        "fte_int8_repacked_matmul", us_packed,
        f"unpacked_us={us_unpacked:.1f};"
        f"speedup_vs_unpacked={us_unpacked / us_packed:.2f}x;"
        f"m={m};k={k};n={nn};bitwise=true",
    )


# -------------------- gnn-serve continuous: event-driven offered load
def bench_continuous_serve(quick: bool) -> None:
    """Offered-load serving: per-request ``infer`` vs one-shot ``infer_batch``
    vs event-driven continuous batching (AsyncGNNEngine), plus the padded
    size-class plan-cache economics under a varying member mix."""
    import jax

    from repro.configs.base import get_config
    from repro.graphs.datasets import make_dataset
    from repro.serve.async_gnn import AsyncGNNEngine
    from repro.serve.gnn_engine import GNNRequest, GNNServeEngine

    cfg = get_config("ample-gcn", reduced=True)
    base = 120 if quick else 400
    pool = [
        make_dataset("cora", max_nodes=base + 17 * s, max_feature_dim=cfg.d_model, seed=s)
        for s in range(6)
    ]
    eng = GNNServeEngine(
        cfg,
        key=jax.random.PRNGKey(0),
        union_node_bucket=256 if quick else 1024,
        union_edge_bucket=2048 if quick else 8192,
    )
    async_eng = AsyncGNNEngine(eng, window=4)

    # Offered load: 8 outstanding requests drawn from the pool.
    outstanding = [pool[i % len(pool)] for i in range(8)]
    reqs = [GNNRequest(graph=g, features=g.features) for g in outstanding]
    for g in pool:  # warm member plans + jit for every path
        eng.infer(g, g.features)
    async_eng.serve(reqs)
    eng.infer_batch(reqs)

    us_infer = _time(lambda: [eng.infer(g, g.features) for g in outstanding], reps=3)
    us_batch = _time(lambda: eng.infer_batch(reqs), reps=3)
    us_cont = _time(lambda: async_eng.serve(reqs), reps=3)
    n = len(reqs)
    emit(
        "gnn_serve_offered_infer", us_infer / n,
        f"requests={n};throughput_rps={n / (us_infer * 1e-6):.1f};mode=per-request",
    )
    emit(
        "gnn_serve_offered_infer_batch", us_batch / n,
        f"requests={n};throughput_rps={n / (us_batch * 1e-6):.1f};"
        f"speedup_vs_infer={us_infer / max(us_batch, 1e-9):.2f}x;mode=one-union",
    )
    emit(
        "gnn_serve_offered_continuous", us_cont / n,
        f"requests={n};throughput_rps={n / (us_cont * 1e-6):.1f};"
        f"speedup_vs_infer={us_infer / max(us_cont, 1e-9):.2f}x;"
        f"window={async_eng.window};mode=continuous",
    )

    # Varying-mix workload on a fresh engine: padded size classes keep the
    # member-plan cache hot even though no two batches share a composition.
    mix_eng = GNNServeEngine(
        cfg,
        eng.params,
        union_node_bucket=256 if quick else 1024,
        union_edge_bucket=2048 if quick else 8192,
    )
    mix_async = AsyncGNNEngine(mix_eng, window=3)
    rng = np.random.default_rng(0)
    for _ in range(10):
        picks = rng.choice(len(pool), size=rng.integers(2, 4), replace=False)
        for i in picks:
            mix_async.submit(pool[i], pool[i].features)
        mix_async.step()
    mix_async.drain()
    info = mix_async.cache_info()
    lookups = info["member_hits"] + info["member_misses"]
    hit_rate = info["member_hits"] / max(lookups, 1)
    emit(
        "gnn_serve_padded_class_hit_rate", 0.0,
        f"hit_rate={hit_rate:.3f};member_hits={info['member_hits']};"
        f"member_misses={info['member_misses']};"
        f"class_hits={info['class_hits']};class_misses={info['class_misses']};"
        f"planner_calls={info['planner_calls']};batches={info['batches']}",
    )


# --------------------- gnn-serve sharded: partition-aware plan economics
def bench_sharded_serve(quick: bool) -> None:
    """Shard count vs latency, halo-exchange volume and per-shard edge
    balance through the partition-aware GNNServeEngine (host-loop backend —
    the SPMD shard_map backend needs a multi-device mesh)."""
    import jax

    from repro.configs.base import get_config
    from repro.graphs.datasets import make_dataset
    from repro.serve.gnn_engine import GNNServeEngine

    cfg = get_config("ample-gcn", reduced=True)
    n = 2_000 if quick else 10_000
    g = make_dataset("flickr", max_nodes=n, max_feature_dim=cfg.d_model, seed=0)
    base = GNNServeEngine(cfg, key=jax.random.PRNGKey(0))
    base.infer(g, g.features)  # jit warm
    us_1 = _time(lambda: base.infer(g, g.features), reps=3)

    for shards in (2, 4, 8):
        eng = GNNServeEngine(cfg, base.params, num_shards=shards)
        cold = eng.infer(g, g.features)  # pays per-shard planning + jit
        eng.infer(g, g.features)
        us_k = _time(lambda: eng.infer(g, g.features), reps=3)
        rep = eng.shard_report()
        emit(
            f"gnn_serve_sharded_{shards}", us_k,
            f"plan_ms={cold.plan_ms:.1f};vs_unsharded={us_1 / max(us_k, 1e-9):.2f}x;"
            f"edge_balance={rep['edge_balance']:.3f};"
            f"halo_rows_per_layer={rep['halo_total']};"
            f"halo_frac={rep['halo_total'] / max(g.num_nodes, 1):.3f}",
        )

    # ---- partitioner comparison: contiguous edges vs multilevel min-cut.
    # Shuffled planted communities are the adversarial case for contiguous
    # ranges (cluster membership is uncorrelated with node order), and the
    # structure the min-cut partitioner recovers — the halo-volume and
    # overlapped-exchange rows the BENCH_sharded.json baseline gates on.
    import numpy as np

    from repro.graphs.datasets import make_clustered_graph

    n_c = 1_200 if quick else 6_000
    gc = make_clustered_graph(n_c, 8, seed=1, shuffle=True, inter_degree=0.5)
    feats = np.asarray(
        np.random.default_rng(0).standard_normal((n_c, cfg.d_model)), np.float32
    )
    for shards in (2, 4, 8):
        halo_by_kind = {}
        for kind in ("edges", "mincut"):
            eng = GNNServeEngine(
                cfg, base.params, num_shards=shards, partitioner=kind,
                halo_overlap=True,
            )
            eng.infer(gc, feats)  # plan + jit
            us_k = _time(lambda: eng.infer(gc, feats), reps=3)
            r = eng.infer(gc, feats)
            rep = eng.shard_report()
            halo_by_kind[kind] = rep["halo_total"]
            extra = ""
            if kind == "mincut":
                red = 1.0 - rep["halo_total"] / max(halo_by_kind["edges"], 1)
                extra = f";halo_reduction_vs_edges={red:.3f}"
            emit(
                f"gnn_sharded_part_{kind}_{shards}", us_k,
                f"partitioner={kind};edge_balance={rep['edge_balance']:.3f};"
                f"halo_volume={rep['halo_total']};"
                f"halo_frac={rep['halo_total'] / max(gc.num_nodes, 1):.3f};"
                f"halo_bytes={r.halo_bytes};halo_ms={r.halo_ms:.2f};"
                f"halo_overlap={r.halo_overlap:.3f}" + extra,
            )


# ----------------- out-of-core serving: budget vs latency/bytes/hit rate
def _outofcore_row(eng, r, us, in_mem_us):
    s = eng._last_stream
    return (
        f"budget_mb={eng.feature_budget_bytes / (1 << 20):.1f};"
        f"bytes_streamed={r.bytes_streamed};"
        f"chunk_hit_rate={r.chunk_hit_rate:.3f};"
        f"prefetch_overlap={r.prefetch_overlap:.3f};"
        f"stall_ms={r.stall_ms:.1f};copy_ms={r.copy_ms:.1f};"
        f"sparse_rows={s.sparse_rows};evictions={s.evictions};"
        f"vs_inmem={us / max(in_mem_us, 1e-9):.2f}x;streamed={r.streamed}"
    )


def _outofcore_gate(rows) -> None:
    """--quick regression gate: measured overlap must clear 0.3 and the
    chunk hit rate must not regress >5 % (absolute) against the committed
    same-scale baseline (the ``quick_rows`` section of BENCH_prefetch.json).
    ``REPRO_BENCH_NO_GATE=1`` skips — e.g. when refreshing the baseline."""
    import json

    if os.environ.get("REPRO_BENCH_NO_GATE"):
        print("outofcore gate: skipped (REPRO_BENCH_NO_GATE)", flush=True)
        return
    failures = []
    for rec in rows:
        ov = float(rec.get("prefetch_overlap", 0.0))
        if ov < 0.3:
            failures.append(f"{rec['name']}: prefetch_overlap {ov:.3f} < 0.3")
    baseline_path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "BENCH_prefetch.json",
    )
    if os.path.exists(baseline_path):
        with open(baseline_path) as f:
            payload = json.load(f)
        base = {
            r["name"]: r
            for r in payload.get(
                "quick_rows", payload["rows"] if payload.get("quick") else []
            )
        }
        for rec in rows:
            ref = base.get(rec["name"])
            if ref is None or "chunk_hit_rate" not in ref:
                continue
            got, want = float(rec["chunk_hit_rate"]), float(ref["chunk_hit_rate"])
            if got < want - 0.05:
                failures.append(
                    f"{rec['name']}: chunk_hit_rate {got:.3f} regressed >5% "
                    f"vs baseline {want:.3f}"
                )
    else:
        print("outofcore gate: no committed baseline, overlap check only",
              flush=True)
    if failures:
        raise SystemExit(
            "outofcore --quick gate FAILED:\n  " + "\n  ".join(failures)
        )
    print(f"outofcore gate: PASS ({len(rows)} rows)", flush=True)


def bench_outofcore(quick: bool) -> None:
    """Full-scale reddit + yelp inference under feature budgets smaller than
    the feature matrix: the out-of-core path keeps features host-resident and
    streams chunks through the plan-driven prefetcher (async staging worker +
    Belady slot cache + sparse residue). Sweeps budget vs latency, bytes
    streamed, chunk-cache hit rate and the wall-clock stall/copy split, plus
    reorder/pack control arms at the 1/4 point (the artifact rows CI uploads
    as BENCH_prefetch.json). Under --quick the sweep doubles as a regression
    gate against the committed baseline."""
    import dataclasses as dc

    import jax

    from repro.configs.base import get_config
    from repro.graphs.datasets import PAPER_DATASETS, make_dataset
    from repro.serve.gnn_engine import GNNServeEngine

    # --quick: mid-size subsets, CI-friendly; full: the paper's full scales.
    cap = 8_000 if quick else None
    fdim = 128 if quick else None
    tile = 1_024 if quick else 4_096
    gate_rows = []
    for name in ("reddit", "yelp"):
        spec = PAPER_DATASETS[name]
        g = make_dataset(name, max_nodes=cap, max_feature_dim=fdim, seed=0)
        feat_bytes = g.features.nbytes
        base = get_config("ample-gcn", reduced=quick)
        cfg = dc.replace(
            base,
            d_model=g.feature_dim,
            vocab_size=spec.num_classes,
            gnn_edges_per_tile=tile,
        )
        # One engine for the whole sweep: the plan compiles once, and only
        # ``feature_budget_bytes`` (plus the locality knobs for the control
        # arms) moves between points.
        chunk_rows = 1_024 if quick else 8_192
        eng = GNNServeEngine(
            cfg,
            feature_budget_bytes=0,
            feature_chunk_rows=chunk_rows,
            key=jax.random.PRNGKey(0),
        )
        cold = eng.infer(g, g.features)  # planner + dense-path jit, untimed
        # Floor each budget at one f32 chunk (the minimum the cache can hold)
        # rather than a fixed size, so sweep points stay distinct at --quick
        # scales instead of collapsing onto one clamped value.
        floor = chunk_rows * g.feature_dim * 4
        # Untimed streamed warmup: compiles the tile-step/gather/upload jits
        # (budget-independent shapes) so the first sweep point isn't inflated
        # by one-time compilation.
        eng.feature_budget_bytes = max(feat_bytes // 8, floor)
        eng.infer(g, g.features)
        in_mem_us = None
        for frac in (0, 8, 4, 2):  # 0 = in-memory reference, then budget sweep
            eng.feature_budget_bytes = (
                0 if frac == 0 else max(feat_bytes // frac, floor)
            )
            t0 = time.perf_counter()
            r = eng.infer(g, g.features)
            us = (time.perf_counter() - t0) * 1e6
            if frac == 0:
                in_mem_us = us
                emit(
                    f"outofcore_{name}_inmem", us,
                    f"nodes={g.num_nodes};edges={g.num_edges};"
                    f"feat_mb={feat_bytes >> 20};plan_ms={cold.plan_ms:.0f};"
                    f"streamed={r.streamed}",
                )
                continue
            row_name = f"outofcore_{name}_budget_1_{frac}"
            emit(row_name, us, _outofcore_row(eng, r, us, in_mem_us))
            gate_rows.append({
                "name": row_name,
                "prefetch_overlap": f"{r.prefetch_overlap:.3f}",
                "chunk_hit_rate": f"{r.chunk_hit_rate:.3f}",
            })
        # Locality control arms at the 1/4 point: reorder-only is the sweep
        # default above; A/B the plan-order control and the chunk-packed
        # mode through the engine knobs (no hand-built prefetchers).
        eng.feature_budget_bytes = max(feat_bytes // 4, floor)
        for arm, reorder, packing in (
            ("noreorder", False, False),
            ("packed", False, True),
        ):
            eng.stream_reorder, eng.stream_packing = reorder, packing
            eng.infer(g, g.features)  # untimed: packed-plan build + jit warm
            t0 = time.perf_counter()
            r = eng.infer(g, g.features)
            us = (time.perf_counter() - t0) * 1e6
            emit(
                f"outofcore_{name}_arm_{arm}_1_4", us,
                _outofcore_row(eng, r, us, in_mem_us),
            )
        eng.stream_reorder, eng.stream_packing = True, False
    if quick:
        _outofcore_gate(gate_rows)


# ------------- prefetcher calibration: simulated depth vs measured budget
def bench_prefetch_calibration(quick: bool) -> None:
    """Calibrate the discrete-event prefetcher model against the measured
    chunk cache: sweep the simulator's prefetch depth (deeper → fewer stall
    cycles) next to the measured budget sweep (bigger cache → higher chunk
    hit rate); both trends must be monotone (asserted by tests)."""
    from repro.core.scheduler import build_chunk_schedule, build_edge_tile_plan
    from repro.core.simulator import SimConfig, simulate
    from repro.graphs.datasets import make_dataset
    from repro.memory.feature_store import FeatureStore
    from repro.memory.prefetcher import ChunkPrefetcher, StreamStats

    n = 5_000 if quick else 20_000
    g = make_dataset("flickr", max_nodes=n, max_feature_dim=64, seed=0)

    for depth in (0, 1, 2, 4):
        res = simulate(
            g, feature_dim=256, cfg=SimConfig(prefetch_depth=depth)
        )
        emit(
            f"prefetch_sim_depth_{depth}", 0.0,
            f"fetch_stall_frac={res.fetch_stall_frac:.4f};"
            f"latency_ms={res.latency_ms:.3f}",
        )

    store = FeatureStore.from_array(g.features, chunk_rows=512)
    plan = build_edge_tile_plan(g, edges_per_tile=1_024)
    schedule = build_chunk_schedule(plan, store.chunk_rows)
    # Sweep explicit slot counts (budget = slots × chunk bytes): fractional
    # budgets can round to the same slot count at --quick scales, which
    # would record duplicate rows under distinct names.
    for slots in (1, 2, 4, 8):
        budget = slots * store.chunk_bytes_f32
        stats = StreamStats()
        pf = ChunkPrefetcher(
            store, schedule, stream="f32", budget_bytes=budget, stats=stats
        )
        t0 = time.perf_counter()
        pf.aggregate(plan).block_until_ready()
        us = (time.perf_counter() - t0) * 1e6
        emit(
            f"prefetch_measured_slots_{slots}", us,
            f"budget_mb={budget / (1 << 20):.2f};"
            f"chunk_hit_rate={stats.hit_rate:.4f};"
            f"bytes_streamed={stats.bytes_streamed};"
            f"evictions={stats.evictions};waves={stats.waves}",
        )


# --------------------------------------------- MoE event-driven dispatch
# --------------- multi-tenant serving front: priorities, SLOs, fair shares
def bench_tenancy(quick: bool) -> None:
    """Offered-load sweep through the multi-tenant router (serve/tenancy):
    tenant mixes x SLO targets. The rows CI uploads as BENCH_tenancy.json:
    (1) best-effort alone = the capacity baseline; (2) a high-priority gold
    trickle against a saturating best-effort backlog, sweeping gold's SLO
    target — gold's p50/p99 and SLO hit rate, best effort's throughput as a
    fraction of its DWRR fair share (capacity x its unconsumed node
    fraction; the acceptance bar is >= 0.9); (3) a 1:2:4-weighted
    three-tenant backlog — measured node shares vs the weight vector; (4)
    token-bucket admission control under 4x over-rate offered load."""
    import jax

    from repro.configs.base import get_config
    from repro.graphs.datasets import make_dataset
    from repro.serve.async_gnn import AsyncGNNEngine
    from repro.serve.tenancy import RateLimitExceeded, TenantRouter
    from repro.serve.gnn_engine import GNNServeEngine

    cfg = get_config("ample-gcn", reduced=True)
    base = 120 if quick else 400
    pool = [
        make_dataset("cora", max_nodes=base + 13 * s,
                     max_feature_dim=cfg.d_model, seed=s)
        for s in range(5)
    ]
    eng = GNNServeEngine(
        cfg,
        key=jax.random.PRNGKey(0),
        union_node_bucket=256 if quick else 1024,
        union_edge_bucket=2048 if quick else 8192,
    )
    for g in pool:  # warm member plans + jit
        eng.infer(g, g.features)

    window = 4
    n_be = 24 if quick else 80
    n_gold = 6 if quick else 16
    be_reqs = [pool[i % len(pool)] for i in range(n_be)]
    be_nodes = sum(g.num_nodes for g in be_reqs)

    def fresh_router(**tenants):
        r = TenantRouter(AsyncGNNEngine(eng, window=window))
        for name, kw in tenants.items():
            r.add_tenant(name, **kw)
        return r

    # (1) best-effort alone: the capacity baseline (warm run measured).
    fresh_router(be={}).serve([("be", g, g.features) for g in be_reqs])
    r_alone = fresh_router(be={})
    t0 = time.perf_counter()
    r_alone.serve([("be", g, g.features) for g in be_reqs])
    alone_s = time.perf_counter() - t0
    alone_node_tput = be_nodes / alone_s
    emit(
        "tenancy_be_alone", alone_s * 1e6 / n_be,
        f"requests={n_be};throughput_rps={n_be / alone_s:.1f};"
        f"node_throughput={alone_node_tput:.0f};windows={r_alone.stats['windows']};"
        f"mode=baseline",
    )

    # (2) gold trickle vs saturating best-effort backlog, sweeping SLO.
    stride = max(1, (n_be // window) // n_gold)  # gold cadence in windows

    def run_mixed(slo_ms):
        router = fresh_router(
            gold={"priority": 1, "slo_ms": slo_ms},
            be={},
        )
        for g in be_reqs:
            router.submit("be", g, g.features)
        gi = 0
        t0 = time.perf_counter()
        while router.pending or gi < n_gold:
            if gi < n_gold and (
                router.stats["windows"] >= gi * stride or not router.pending
            ):
                g = pool[gi % len(pool)]
                router.submit("gold", g, g.features)
                gi += 1
                continue
            router.step(flush=True)
        return router, time.perf_counter() - t0

    run_mixed(100.0)  # warm this scenario's window compositions (jit + plans)
    for slo_ms in ((100.0,) if quick else (50.0, 100.0, 200.0)):
        router, mixed_s = run_mixed(slo_ms)
        snap = router.snapshot()["tenants"]
        gold, be = snap["gold"], snap["be"]
        gold_frac = gold["completed_nodes"] / (
            gold["completed_nodes"] + be["completed_nodes"]
        )
        be_node_tput = be["completed_nodes"] / mixed_s
        # DWRR fair share: gold is a trickle (never backlogged), so work
        # conservation hands best effort everything gold didn't consume.
        fair_share = alone_node_tput * (1.0 - gold_frac)
        lat = gold["latency_ms"]
        emit(
            f"tenancy_mixed_slo{int(slo_ms)}", mixed_s * 1e6 / (n_be + n_gold),
            f"gold_p50_ms={lat['p50']:.2f};gold_p99_ms={lat['p99']:.2f};"
            f"slo_ms={slo_ms:.0f};slo_hit_rate={gold['slo_hit_rate']:.3f};"
            f"gold_queue_p99_ms={gold['queue_wait_ms']['p99']:.2f};"
            f"be_node_throughput={be_node_tput:.0f};"
            f"be_fair_share_frac={be_node_tput / fair_share:.3f};"
            f"gold_node_frac={gold_frac:.3f};windows={router.stats['windows']};"
            f"mode=priority-slo",
        )

    # (3) weighted contention: three saturating tenants at weights 1:2:4.
    weights = {"w1": 1.0, "w2": 2.0, "w4": 4.0}
    per_tenant = 12 if quick else 32

    def run_weighted():
        router = fresh_router(**{t: {"weight": w} for t, w in weights.items()})
        for t in weights:
            for i in range(per_tenant):
                g = pool[i % len(pool)]
                router.submit(t, g, g.features)
        t0 = time.perf_counter()
        router.drain()
        return router, time.perf_counter() - t0

    run_weighted()  # warm
    router, contended_s = run_weighted()
    snap = router.snapshot()["tenants"]
    # Share over the contended phase: every tenant backlogged from the
    # start, so first-half windows are the weight-driven regime (the tail
    # drains lighter tenants' leftovers work-conservingly).
    first_half = list(router.window_log)[: len(router.window_log) // 2]
    served = {t: 0 for t in weights}
    for w in first_half:
        for tenant, _seq in w:
            served[tenant] += 1
    total_served = max(sum(served.values()), 1)
    wsum = sum(weights.values())
    shares = ";".join(
        f"{t}_share={served[t] / total_served:.3f}"
        f"(want={weights[t] / wsum:.3f})"
        for t in weights
    )
    max_err = max(
        abs(served[t] / total_served - weights[t] / wsum) for t in weights
    )
    emit(
        "tenancy_weighted_shares", contended_s * 1e6 / (3 * per_tenant),
        f"{shares};max_share_error={max_err:.3f};"
        f"windows={router.stats['windows']};mode=dwrr-weights",
    )

    # (4) admission control: 4x over-rate offered load hits the bucket.
    router = fresh_router(limited={"rate_rps": 200.0, "burst": float(n_be // 4)})
    admitted = rejected = 0
    for g in be_reqs:  # burst-dominated: bucket drains mid-stream
        try:
            router.submit("limited", g, g.features)
            admitted += 1
        except RateLimitExceeded:
            rejected += 1
    router.drain()
    emit(
        "tenancy_rate_limit", 0.0,
        f"offered={n_be};admitted={admitted};rejected={rejected};"
        f"rejected_telemetry={router.snapshot()['tenants']['limited']['rejected']};"
        f"mode=token-bucket",
    )


def bench_moe_dispatch(quick: bool) -> None:
    import jax
    import jax.numpy as jnp

    from repro.models.lm.moe import moe_apply, moe_init, _expert_ffn

    d, f, e, k = 128, 256, 16, 2
    t = 2_048 if quick else 8_192
    params = moe_init(jax.random.PRNGKey(0), d, f, e, "swiglu", dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, t, d))

    sorted_fn = jax.jit(
        lambda p, x: moe_apply(p, x, num_experts=e, top_k=k, kind="swiglu")[0]
    )
    us = _time(lambda: sorted_fn(params, x).block_until_ready())

    def dense(p, x):  # every expert processes every token (no dispatch)
        xf = jnp.broadcast_to(x.reshape(1, 1, t, d), (1, e, t, d))
        probs = jax.nn.softmax(x.reshape(t, d) @ p["router"], -1)
        y = _expert_ffn(p["experts"], xf, "swiglu")[0]
        return jnp.einsum("etd,te->td", y, probs)

    dense_fn = jax.jit(dense)
    us_dense = _time(lambda: dense_fn(params, x).block_until_ready(), reps=1)
    emit("moe_event_driven_dispatch", us,
         f"speedup_vs_dense_all_experts={us_dense/us:.2f}x;capacity_factor=1.25")


# --------------------------------------------------- kernel sanity timings
def bench_kernels(quick: bool) -> None:
    """Pallas kernels run in interpret mode on CPU — correctness surrogates;
    real perf is the TPU target. The oracle (jnp) path time is reported."""
    import jax.numpy as jnp

    from repro.core import build_edge_tile_plan
    from repro.graphs.datasets import make_lognormal_graph
    from repro.kernels.segment_agg.ref import aggregate_tiles_ref

    g = make_lognormal_graph(1_000, 5.0, seed=0)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((1_000, 128)).astype(np.float32))
    plan = build_edge_tile_plan(g, edges_per_tile=128)
    args = (
        jnp.asarray(plan.gather_idx), jnp.asarray(plan.coeff),
        jnp.asarray(plan.seg_ids), jnp.asarray(plan.out_node),
    )
    kw = dict(num_nodes=1_000, segments_per_tile=plan.segments_per_tile)
    us = _time(lambda: aggregate_tiles_ref(x, *args, **kw).block_until_ready())
    emit("kernel_segment_agg_oracle", us,
         f"tiles={plan.num_tiles};occupancy={plan.lane_occupancy:.3f}")

    # fused segment-softmax (attention) kernel oracle: one tile scan does
    # LeakyReLU → segment-max → exp → segment-sum → weighted aggregate
    from repro.core.aggregation import tile_edge_coeff, to_device_plan
    from repro.kernels.segment_agg.ref import attend_tiles_ref

    h, dh = 4, 32
    z = jnp.asarray(rng.standard_normal((1_000, h, dh)).astype(np.float32))
    scores = jnp.asarray(
        rng.standard_normal((g.num_edges, h)).astype(np.float32)
    )
    dp = to_device_plan(plan, with_edge_ids=True)
    sc_t = tile_edge_coeff(dp, scores, fill=-jnp.inf)
    us = _time(
        lambda: attend_tiles_ref(
            z, dp.gather_idx, sc_t, dp.coeff, dp.seg_ids, dp.out_node,
            num_nodes=1_000, segments_per_tile=plan.segments_per_tile,
            leaky_slope=0.2,
        ).block_until_ready()
    )
    emit("kernel_segment_softmax_oracle", us,
         f"tiles={plan.num_tiles};heads={h};dh={dh};fused=true")


BENCHES = [
    table4_dq_ratios,
    table5_latency,
    figure4_speedup,
    bench_engine_paths,
    bench_mixed_precision,
    bench_gnn_serve,
    bench_runtime_coeff,
    bench_attention,
    bench_continuous_serve,
    bench_sharded_serve,
    bench_outofcore,
    bench_prefetch_calibration,
    bench_tenancy,
    bench_moe_dispatch,
    bench_kernels,
]


def write_artifact(path: str, quick: bool) -> None:
    """Persist the emitted rows as a JSON artifact (CI uploads this — the
    bench trajectory across PRs lives in these files, not the logs)."""
    import json
    import platform

    records = []
    for row in ROWS:
        name, us, derived = row.split(",", 2)
        rec = {"name": name, "us_per_call": float(us)}
        for part in derived.split(";"):
            if "=" in part:
                k, v = part.split("=", 1)
                rec[k] = v
        records.append(rec)
    payload = {
        "quick": quick,
        "python": platform.python_version(),
        "machine": platform.machine(),
        "rows": records,
    }
    with open(path, "w") as f:
        json.dump(payload, f, indent=2)
    print(f"wrote {len(records)} rows to {path}", flush=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma-separated substrings of bench names to run")
    ap.add_argument("--skip", default=None,
                    help="comma-separated substrings of bench names to skip")
    ap.add_argument("--out", default=None,
                    help="write rows as a JSON artifact (e.g. BENCH_serve.json)")
    ap.add_argument("--trace-out", default=None,
                    help="record request-lifecycle spans during the benches "
                         "and write Chrome-trace-event JSON here (open in "
                         "Perfetto); empty = tracing off")
    args = ap.parse_args()
    from repro.launch.compile_cache import configure_compile_cache

    configure_compile_cache()
    wanted = [s for s in (args.only or "").split(",") if s]
    unwanted = [s for s in (args.skip or "").split(",") if s]
    if args.trace_out:
        from repro.observe import trace as otrace

        otrace.enable()
    print("name,us_per_call,derived")
    for bench in BENCHES:
        if wanted and not any(s in bench.__name__ for s in wanted):
            continue
        if any(s in bench.__name__ for s in unwanted):
            continue
        bench(args.quick)
    if args.trace_out:
        rec = otrace.get_recorder()
        rec.export(args.trace_out)
        print(
            f"trace: {len(rec.spans())} spans -> {args.trace_out} "
            f"(dropped={rec.dropped})"
        )
    if args.out:
        write_artifact(args.out, args.quick)


if __name__ == "__main__":
    main()
