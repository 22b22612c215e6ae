"""Out-of-core serving: feature store, chunk schedule, prefetcher, parity.

The load-bearing guarantee is **bitwise identity**: a request served under a
feature budget (chunk-streamed aggregation + FTE) must produce exactly the
bytes the in-memory path produces, across budgets small enough to force
chunk-cache eviction and multi-wave tiles.
"""
import numpy as np
import pytest

from _hypothesis_compat import given, st

jax = pytest.importorskip("jax")
import jax.numpy as jnp

from repro.configs.base import get_config
from repro.core.message_passing import AmpleEngine, EngineConfig
from repro.core.quantization import compute_scale_zp
from repro.core.scheduler import (
    build_chunk_schedule,
    build_edge_tile_plan,
    pack_tiles_by_chunk,
    tile_runs,
)
from repro.graphs.csr import Graph, from_edge_list
from repro.graphs.datasets import make_dataset, make_lognormal_graph
from repro.memory.feature_store import FeatureStore, default_chunk_rows
from repro.memory.prefetcher import ChunkPrefetcher, StreamStats, StreamedFeatures
from repro.serve.gnn_engine import GNNServeEngine


def _graph(n=600, deg=5.0, seed=0, dim=32):
    g = make_lognormal_graph(n, deg, seed=seed)
    rng = np.random.default_rng(seed + 1)
    return g.with_features(rng.standard_normal((n, dim)).astype(np.float32))


def _banded_graph(n=512, k=3, dim=16):
    """Neighbours within ±k — real source locality for cache/reorder tests."""
    src, dst = [], []
    for i in range(n):
        for o in range(1, k + 1):
            src.append((i + o) % n)
            dst.append(i)
    g = from_edge_list(np.asarray(src), np.asarray(dst), n)
    rng = np.random.default_rng(0)
    return g.with_features(rng.standard_normal((n, dim)).astype(np.float32))


# ------------------------------------------------------------ feature store
def test_store_agg_scale_matches_dense_calibration():
    g = _graph()
    store = FeatureStore.from_array(g.features, chunk_rows=64)
    qp = compute_scale_zp(jnp.asarray(g.features), symmetric=True)
    assert float(qp.scale) == float(store.agg_scale)  # bitwise, not approx


def test_store_int8_chunks_match_device_quantize():
    from repro.core.quantization import quantize

    g = _graph(n=300)
    store = FeatureStore.from_array(g.features, chunk_rows=128)
    qp = compute_scale_zp(jnp.asarray(g.features), symmetric=True)
    xq = np.asarray(quantize(jnp.asarray(g.features), qp))
    for c in range(store.num_chunks):
        lo, hi = store.chunk_range(c)
        np.testing.assert_array_equal(store.chunk_i8(c)[: hi - lo], xq[lo:hi])


def test_store_roundtrip_and_gather():
    g = _graph(n=200, dim=8)
    store = FeatureStore.from_array(g.features, chunk_rows=64)
    np.testing.assert_array_equal(store.dense(), g.features)
    ids = np.asarray([0, 63, 64, 150, 199])
    np.testing.assert_array_equal(store.gather_rows_f32(ids), g.features[ids])
    assert float(store.amax_rows(ids)) == float(np.max(np.abs(g.features[ids])))


def test_store_memmap_backed(tmp_path):
    g = _graph(n=200, dim=8)
    mem = FeatureStore.from_array(
        g.features, chunk_rows=64, memmap_dir=str(tmp_path)
    )
    ram = FeatureStore.from_array(g.features, chunk_rows=64)
    assert (tmp_path / "features.f32.bin").exists()
    assert (tmp_path / "features.i8.bin").exists()
    for c in range(ram.num_chunks):
        np.testing.assert_array_equal(np.asarray(mem.chunk_f32(c)), ram.chunk_f32(c))
        np.testing.assert_array_equal(np.asarray(mem.chunk_i8(c)), ram.chunk_i8(c))


def test_default_chunk_rows_scales_with_budget():
    small = default_chunk_rows(100_000, 256, 1 << 20)
    big = default_chunk_rows(100_000, 256, 1 << 28)
    assert 256 <= small <= big <= 65536


# ----------------------------------------------------------- chunk schedule
def test_chunk_schedule_covers_all_lanes():
    g = _graph(n=800, deg=8.0)
    plan = build_edge_tile_plan(g, edges_per_tile=64)
    sched = build_chunk_schedule(plan, 128)
    for t in range(plan.num_tiles):
        touched = np.unique(plan.gather_idx[t].astype(np.int64) // 128)
        assert set(touched) <= set(sched.tile_chunks[t].tolist())
    # order is a permutation of all tiles
    assert sorted(sched.order.tolist()) == list(range(plan.num_tiles))


def test_reorder_permutes_whole_runs_only():
    """Split nodes must keep their tiles consecutive and in order — the
    bitwise-identity precondition for the streamed scatter-add."""
    g = _graph(n=400, deg=20.0, seed=3)  # hubs overflow tiles -> splits
    plan = build_edge_tile_plan(g, edges_per_tile=32)
    runs = tile_runs(plan)
    assert runs[0] == 0 and runs[-1] == plan.num_tiles
    sched = build_chunk_schedule(plan, 64, reorder=True)
    pos = np.empty(plan.num_tiles, np.int64)
    pos[sched.order] = np.arange(plan.num_tiles)
    for r in range(runs.size - 1):
        span = pos[runs[r] : runs[r + 1]]
        # contiguous and increasing: the run moved as one block
        assert np.array_equal(span, np.arange(span[0], span[0] + span.size))


def test_reorder_raises_chunk_reuse_on_structured_graph():
    """Interleaved-degree banded graph: plan order hops between far-apart
    node ranges, the locality reorder clusters them back together."""
    n, dim = 1024, 8
    src, dst = [], []
    for i in range(n):
        k = 2 if i % 2 == 0 else 3  # alternate degrees -> degree sort shuffles
        for o in range(1, k + 1):
            src.append((i + o) % n)
            dst.append(i)
    g = from_edge_list(np.asarray(src), np.asarray(dst), n)
    rng = np.random.default_rng(0)
    g = g.with_features(rng.standard_normal((n, dim)).astype(np.float32))
    store = FeatureStore.from_array(g.features, chunk_rows=64)
    plan = build_edge_tile_plan(g, edges_per_tile=32)

    def uploads(reorder):
        schedule = build_chunk_schedule(plan, 64, reorder=reorder)
        stats = StreamStats()
        pf = ChunkPrefetcher(
            store, schedule, stream="f32",
            budget_bytes=3 * store.chunk_bytes_f32, prefetch_depth=0,
            stats=stats,
        )
        pf.aggregate(plan)
        return stats.uploads

    assert uploads(True) < uploads(False)


# -------------------------------------------------------- prefetcher cache
def test_belady_cache_all_resident_is_cold_misses_only():
    g = _banded_graph()
    store = FeatureStore.from_array(g.features, chunk_rows=64)
    plan = build_edge_tile_plan(g, edges_per_tile=64)
    schedule = build_chunk_schedule(plan, 64)
    stats = StreamStats()
    pf = ChunkPrefetcher(
        store, schedule, stream="f32", budget_bytes=store.nbytes * 2,
        prefetch_depth=0, stats=stats,
    )
    out = pf.aggregate(plan)
    assert stats.uploads == store.num_chunks  # each chunk moved exactly once
    assert stats.evictions == 0
    assert out.shape == (g.num_nodes, g.feature_dim)


def test_prefetch_overlap_on_local_graph():
    g = _banded_graph(n=1024, k=2)
    store = FeatureStore.from_array(g.features, chunk_rows=64)
    plan = build_edge_tile_plan(g, edges_per_tile=32)
    schedule = build_chunk_schedule(plan, 64)
    stats = StreamStats()
    pf = ChunkPrefetcher(
        store, schedule, stream="f32",
        budget_bytes=4 * store.chunk_bytes_f32, prefetch_depth=2, stats=stats,
    )
    pf.aggregate(plan)
    assert stats.prefetched > 0
    assert 0.0 < stats.prefetch_overlap <= 1.0


def test_streamed_aggregate_matches_inmemory_single_stream():
    g = _banded_graph(n=300, k=4)
    from repro.core.aggregation import aggregate_edge_tiles, to_device_plan

    plan = build_edge_tile_plan(g, edges_per_tile=32)
    ref = aggregate_edge_tiles(
        jnp.asarray(g.features), to_device_plan(plan),
        num_nodes=g.num_nodes, segments_per_tile=plan.segments_per_tile,
    )
    store = FeatureStore.from_array(g.features, chunk_rows=32)
    schedule = build_chunk_schedule(plan, 32)
    for budget in (store.chunk_bytes_f32, 3 * store.chunk_bytes_f32):
        pf = ChunkPrefetcher(
            store, schedule, stream="f32", budget_bytes=budget,
            stats=StreamStats(),
        )
        out = pf.aggregate(plan)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


# ------------------------------------------- engine-level aggregate parity
@pytest.mark.parametrize("mode", ["gcn", "sum", "mean"])  # gcn / gin / sage
def test_engine_aggregate_streamed_bitwise(mode):
    g = _graph(n=500, deg=6.0, seed=2)
    eng = AmpleEngine(g, EngineConfig(edges_per_tile=64, mixed_precision=True))
    x = jnp.asarray(g.features)
    ref = np.asarray(eng.aggregate(x, mode=mode))
    store = FeatureStore.from_array(g.features, chunk_rows=64)
    for frac in (10, 3):  # both force eviction (cache < working set)
        sf = StreamedFeatures(store, store.nbytes // frac)
        out = np.asarray(eng.aggregate(sf, mode=mode))
        np.testing.assert_array_equal(out, ref)
        assert sf.stats.bytes_streamed > 0
        assert sf.stats.evictions > 0


def test_engine_aggregate_streamed_float_policy_bitwise():
    g = _graph(n=400, deg=5.0, seed=4)
    eng = AmpleEngine(g, EngineConfig(edges_per_tile=64, mixed_precision=False))
    ref = np.asarray(eng.aggregate(jnp.asarray(g.features), mode="sum"))
    store = FeatureStore.from_array(g.features, chunk_rows=64)
    sf = StreamedFeatures(store, store.nbytes // 4)
    np.testing.assert_array_equal(np.asarray(eng.aggregate(sf, mode="sum")), ref)


def test_engine_transform_streamed_bitwise():
    g = _graph(n=400, deg=5.0, seed=5, dim=24)
    eng = AmpleEngine(g, EngineConfig(edges_per_tile=64, mixed_precision=True))
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.standard_normal((24, 16)).astype(np.float32))
    b = jnp.asarray(rng.standard_normal((16,)).astype(np.float32))
    ref = np.asarray(eng.transform(jnp.asarray(g.features), w, b, jax.nn.relu))
    store = FeatureStore.from_array(g.features, chunk_rows=64)
    sf = StreamedFeatures(store, store.nbytes // 4)
    out = np.asarray(eng.transform(sf, w, b, jax.nn.relu))
    np.testing.assert_array_equal(out, ref)
    assert sf.stats.bytes_streamed > 0


# -------------------------------------------------- serve-level end-to-end
@pytest.mark.parametrize("arch", ["gcn", "gin", "sage", "gat"])
def test_served_outofcore_bitwise_identical(arch):
    """The acceptance guarantee: streamed serving == in-memory serving, bit
    for bit, for every arch with mixed precision on, across two budgets
    small enough to force chunk-cache eviction."""
    cfg = get_config(f"ample-{arch}", reduced=True)
    g = make_dataset("cora", max_nodes=700, max_feature_dim=cfg.d_model, seed=0)
    ref_eng = GNNServeEngine(cfg, key=jax.random.PRNGKey(0))
    ref = ref_eng.infer(g, g.features)
    assert not ref.streamed
    for frac in (10, 3):
        eng = GNNServeEngine(
            cfg, ref_eng.params,
            feature_budget_bytes=g.features.nbytes // frac,
            feature_chunk_rows=64,
        )
        r = eng.infer(g, g.features)
        assert r.streamed
        np.testing.assert_array_equal(r.outputs, ref.outputs)
        assert r.bytes_streamed > 0
        info = eng.cache_info()
        assert info["streamed_requests"] == 1
        if arch not in ("sage", "gat"):
            # gcn/gin aggregate the store through the chunk cache; the tiny
            # budget must have forced eviction (misses beyond one cold pass).
            # sage's φ (and gat's attention projection) stream chunk-blocked
            # through the FTE instead — no cache, so only bytes_streamed is
            # meaningful there.
            assert info["chunk_misses"] > (700 // 64 + 1)
        # warm repeat stays bitwise too (static per-plan calibration)
        r2 = eng.infer(g, g.features)
        np.testing.assert_array_equal(r2.outputs, ref.outputs)
        assert r2.cache_hit


def test_warm_engine_different_features_bitwise():
    """Static per-plan calibration: a warm engine serves NEW features with
    the FIRST request's activation scale (existing in-memory semantics). The
    streamed int8 stream must quantize under that cached slot scale — not
    the new store's own — or warm different-feature requests silently skew
    by scale_old/scale_new."""
    cfg = get_config("ample-gcn", reduced=True)
    g = make_dataset("cora", max_nodes=500, max_feature_dim=cfg.d_model, seed=0)
    rng = np.random.default_rng(9)
    x2 = (3.0 * rng.standard_normal(g.features.shape)).astype(np.float32)
    assert np.max(np.abs(x2)) != np.max(np.abs(g.features))  # distinct scales
    ref_eng = GNNServeEngine(cfg, key=jax.random.PRNGKey(0))
    ref_eng.infer(g, g.features)  # calibrates the slots on request 1
    ref2 = ref_eng.infer(g, x2)
    eng = GNNServeEngine(
        cfg, ref_eng.params,
        feature_budget_bytes=g.features.nbytes // 4, feature_chunk_rows=64,
    )
    eng.infer(g, g.features)
    r2 = eng.infer(g, x2)
    assert r2.streamed
    np.testing.assert_array_equal(r2.outputs, ref2.outputs)


def test_padded_union_path_reuses_store_across_warm_requests():
    """Size-class padding copies the matrix per request; the store cache
    must key on the caller's original array so warm padded requests skip
    the (chunking + int8 quantization) store build."""
    from repro.memory import feature_store as fs

    cfg = get_config("ample-gcn", reduced=True)
    g = make_dataset("cora", max_nodes=500, max_feature_dim=cfg.d_model, seed=0)
    eng = GNNServeEngine(
        cfg,
        union_node_bucket=512,
        union_edge_bucket=2048,
        feature_budget_bytes=g.features.nbytes // 4,
        feature_chunk_rows=64,
        key=jax.random.PRNGKey(0),
    )
    assert eng.padded_unions
    builds = 0
    orig = fs.FeatureStore.from_array.__func__

    def counting(cls, x, **kw):
        nonlocal builds
        builds += 1
        return orig(cls, x, **kw)

    try:
        fs.FeatureStore.from_array = classmethod(counting)
        ref = eng.infer(g, g.features)
        warm = eng.infer(g, g.features)
    finally:
        fs.FeatureStore.from_array = classmethod(orig)
    assert ref.streamed and warm.streamed
    np.testing.assert_array_equal(warm.outputs, ref.outputs)
    assert builds == 1  # one build, warm request hit the store LRU
    assert len(eng._stores) == 1


def test_served_within_budget_takes_inmemory_path():
    cfg = get_config("ample-gcn", reduced=True)
    g = make_dataset("cora", max_nodes=300, max_feature_dim=cfg.d_model, seed=0)
    eng = GNNServeEngine(
        cfg, feature_budget_bytes=g.features.nbytes * 10, key=jax.random.PRNGKey(0)
    )
    r = eng.infer(g, g.features)
    assert not r.streamed
    assert eng.cache_info()["streamed_requests"] == 0


def test_streaming_telemetry_in_stats():
    cfg = get_config("ample-gcn", reduced=True)
    g = make_dataset("cora", max_nodes=600, max_feature_dim=cfg.d_model, seed=0)
    eng = GNNServeEngine(
        cfg, feature_budget_bytes=g.features.nbytes // 4,
        feature_chunk_rows=64, key=jax.random.PRNGKey(0),
    )
    r = eng.infer(g, g.features)
    info = eng.cache_info()
    assert info["bytes_streamed"] == r.bytes_streamed > 0
    assert 0.0 <= info["chunk_hit_rate"] <= 1.0
    assert 0.0 <= info["prefetch_overlap"] <= 1.0
    assert info["chunk_hits"] + info["chunk_misses"] > 0


def test_streamed_batch_responses_carry_telemetry():
    from repro.serve.gnn_engine import GNNRequest

    cfg = get_config("ample-gcn", reduced=True)
    members = [
        make_dataset("cora", max_nodes=250, max_feature_dim=cfg.d_model, seed=s)
        for s in (0, 1)
    ]
    ref_eng = GNNServeEngine(cfg, key=jax.random.PRNGKey(0))
    reqs = [GNNRequest(graph=m, features=m.features) for m in members]
    ref = ref_eng.infer_batch(reqs)
    total = sum(m.features.nbytes for m in members)
    eng = GNNServeEngine(
        cfg, ref_eng.params, feature_budget_bytes=total // 4,
        feature_chunk_rows=64,
    )
    out = eng.infer_batch(reqs)
    for a, b in zip(out, ref):
        assert a.streamed
        np.testing.assert_array_equal(a.outputs, b.outputs)
        # whole-batch telemetry on every member, amortized via the property
        assert a.bytes_streamed_per_member == a.bytes_streamed / len(reqs)
    # per-call union matrices never repeat: the store LRU must stay empty
    # (an id-keyed entry would only pin the dead concatenated matrix)
    assert len(eng._stores) == 0


# --------------------------------------- simulator/measured trend matching
def test_sim_prefetch_trend_matches_measured_hit_rate_trend():
    """Deeper simulated prefetch must not add stall cycles; a bigger
    measured chunk cache must not lower the hit rate — the two monotone
    trends the calibration sweep (bench_prefetch_calibration) reports."""
    from repro.core.simulator import SimConfig, simulate

    g = make_lognormal_graph(2_000, 10.0, seed=1)
    stalls = [
        simulate(g, feature_dim=128, cfg=SimConfig(prefetch_depth=d)).fetch_stall_frac
        for d in (0, 1, 2, 4)
    ]
    assert all(a >= b - 1e-12 for a, b in zip(stalls, stalls[1:]))
    assert stalls[-1] < stalls[0]  # lookahead hides some latency

    feats = np.random.default_rng(0).standard_normal((2_000, 32)).astype(np.float32)
    store = FeatureStore.from_array(feats, chunk_rows=128)
    plan = build_edge_tile_plan(g, edges_per_tile=128)
    schedule = build_chunk_schedule(plan, 128)
    rates = []
    for frac in (8, 4, 2, 1):
        stats = StreamStats()
        ChunkPrefetcher(
            store, schedule, stream="f32",
            budget_bytes=store.nbytes // frac, stats=stats,
        ).aggregate(plan)
        rates.append(stats.hit_rate)
    assert all(a <= b + 1e-12 for a, b in zip(rates, rates[1:]))
    assert rates[-1] > rates[0]


def test_sim_prefetch_depth_zero_is_historical_timing():
    from repro.core.simulator import SimConfig, simulate

    g = make_lognormal_graph(1_500, 8.0, seed=2)
    a = simulate(g, feature_dim=128, cfg=SimConfig())
    b = simulate(g, feature_dim=128, cfg=SimConfig(prefetch_depth=0))
    assert a.cycles == b.cycles


# ------------------------------- warm streamed requests: plan bytes stay home
def test_warm_streamed_aggregate_reuploads_zero_plan_bytes():
    """The instruction stream (per-tile coeff/seg/scatter arrays + lane
    offsets) is plan-static: the cold streamed call uploads it once into the
    engine's device cache; warm calls move feature chunks only."""
    g = _graph(n=500, deg=5.0, seed=2, dim=16)
    eng = AmpleEngine(g, EngineConfig(edges_per_tile=64, mixed_precision=True))
    store = FeatureStore.from_array(g.features, chunk_rows=64)
    cold = StreamedFeatures(store, store.nbytes // 4)
    y1 = np.asarray(eng.aggregate(cold, mode="sum"))
    assert cold.stats.instr_bytes > 0
    warm = StreamedFeatures(store, store.nbytes // 4)
    y2 = np.asarray(eng.aggregate(warm, mode="sum"))
    assert warm.stats.instr_bytes == 0  # zero plan bytes re-uploaded
    assert warm.stats.bytes_streamed > 0  # features still stream
    np.testing.assert_array_equal(y1, y2)


def test_warm_streamed_serve_reuploads_zero_plan_bytes():
    """Serve-level regression: a warm streamed request's telemetry shows
    zero instruction-stream bytes (ROADMAP PR-4 follow-on)."""
    cfg = get_config("ample-gcn", reduced=True)
    g = make_dataset("cora", max_nodes=600, max_feature_dim=cfg.d_model, seed=0)
    eng = GNNServeEngine(
        cfg, feature_budget_bytes=g.features.nbytes // 4,
        feature_chunk_rows=64, key=jax.random.PRNGKey(0),
    )
    r1 = eng.infer(g, g.features)
    assert r1.streamed
    assert eng._last_stream.instr_bytes > 0
    r2 = eng.infer(g, g.features)
    assert r2.streamed and r2.cache_hit
    assert eng._last_stream.instr_bytes == 0
    np.testing.assert_array_equal(r1.outputs, r2.outputs)


def test_direct_prefetcher_still_accounts_instr_bytes():
    """Without an engine-owned device tile cache (direct ChunkPrefetcher
    use), per-call plan uploads keep being charged — the accounting only
    moves when the cache actually exists."""
    g = _graph(n=300, deg=4.0, seed=1, dim=8)
    store = FeatureStore.from_array(g.features, chunk_rows=64)
    plan = build_edge_tile_plan(g, edges_per_tile=64)
    schedule = build_chunk_schedule(plan, store.chunk_rows)
    stats = StreamStats()
    pf = ChunkPrefetcher(
        store, schedule, stream="f32",
        budget_bytes=store.chunk_bytes_f32 * 2, stats=stats,
    )
    pf.aggregate(plan).block_until_ready()
    assert stats.instr_bytes > 0


# -------------------------------------- locality packing: pack_tiles_by_chunk
def _row_edge_sequences(plan):
    """Per destination row: real edge ids in accumulation order.

    Accumulation order is the streamed scatter-add's: tiles in plan order,
    lanes in lane order within a tile; a lane's contribution lands on the
    out_node of its segment. Padding lanes (edge_id -1) and sentinel
    segments (out_node == num_nodes) carry no edge.
    """
    rows = np.take_along_axis(plan.out_node, plan.seg_ids, axis=1)
    real = (plan.edge_ids >= 0) & (rows < plan.num_nodes)
    seqs = {}
    for r, e in zip(rows[real].tolist(), plan.edge_ids[real].tolist()):
        seqs.setdefault(r, []).append(e)
    return seqs


def _assert_repack_invariants(g, plan, packed, chunk_rows):
    from repro.core.aggregation import aggregate_edge_tiles, to_device_plan

    # Same edges, same per-row accumulation order — the order-preserving
    # permutation-with-repacking property. (Sequence equality subsumes the
    # per-row edge-multiset equality.)
    assert _row_edge_sequences(packed) == _row_edge_sequences(plan)
    assert packed.total_edges == plan.total_edges
    assert packed.num_nodes == plan.num_nodes
    # And the float semantics agree bitwise, not just structurally.
    x = jnp.asarray(g.features)
    ref = aggregate_edge_tiles(
        x, to_device_plan(plan),
        num_nodes=g.num_nodes, segments_per_tile=plan.segments_per_tile,
    )
    out = aggregate_edge_tiles(
        x, to_device_plan(packed),
        num_nodes=g.num_nodes, segments_per_tile=packed.segments_per_tile,
    )
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


@pytest.mark.parametrize("seed,deg,ept,chunk_rows", [
    (0, 5.0, 64, 64),
    (1, 12.0, 32, 128),   # hubs overflow tiles -> verbatim multi-tile runs
    (2, 3.0, 16, 32),     # tiny tiles -> many single-segment spans
    (3, 8.0, 128, 64),
])
def test_packed_plan_is_order_preserving_repack(seed, deg, ept, chunk_rows):
    g = _graph(n=400, deg=deg, seed=seed, dim=16)
    plan = build_edge_tile_plan(g, edges_per_tile=ept)
    packed = pack_tiles_by_chunk(plan, chunk_rows)
    _assert_repack_invariants(g, plan, packed, chunk_rows)


@given(
    n=st.integers(8, 60),
    ept=st.sampled_from([8, 16, 32]),
    chunk_rows=st.sampled_from([8, 16, 32]),
    seed=st.integers(0, 1_000),
)
def test_packed_plan_property(n, ept, chunk_rows, seed):
    """Randomized repacking property: arbitrary small edge lists (dupes and
    self-loops included), tile widths and chunk sizes."""
    rng = np.random.default_rng(seed)
    m = int(rng.integers(n, 6 * n))
    src = rng.integers(0, n, m).astype(np.int64)
    dst = rng.integers(0, n, m).astype(np.int64)
    g = from_edge_list(src, dst, n).with_features(
        rng.standard_normal((n, 8)).astype(np.float32)
    )
    plan = build_edge_tile_plan(g, edges_per_tile=ept)
    packed = pack_tiles_by_chunk(plan, chunk_rows)
    _assert_repack_invariants(g, plan, packed, chunk_rows)


def test_packed_streamed_aggregate_bitwise_direct():
    """Packed plan + unreordered schedule through the prefetcher: bitwise
    equal to the in-memory reference at an eviction-forcing budget."""
    from repro.core.aggregation import aggregate_edge_tiles, to_device_plan

    g = _banded_graph(n=512, k=3, dim=16)
    plan = build_edge_tile_plan(g, edges_per_tile=32)
    ref = aggregate_edge_tiles(
        jnp.asarray(g.features), to_device_plan(plan),
        num_nodes=g.num_nodes, segments_per_tile=plan.segments_per_tile,
    )
    store = FeatureStore.from_array(g.features, chunk_rows=64)
    packed = pack_tiles_by_chunk(plan, 64)
    schedule = build_chunk_schedule(packed, 64, reorder=False)
    stats = StreamStats()
    pf = ChunkPrefetcher(
        store, schedule, stream="f32",
        budget_bytes=3 * store.chunk_bytes_f32, stats=stats,
    )
    out = pf.aggregate(packed)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    assert stats.bytes_streamed > 0


# ------------------------------------- async staging: measured, not inferred
def test_async_staging_measures_wall_clock():
    g = _banded_graph(n=1024, k=2)
    store = FeatureStore.from_array(g.features, chunk_rows=64)
    plan = build_edge_tile_plan(g, edges_per_tile=32)
    schedule = build_chunk_schedule(plan, 64)
    stats = StreamStats()
    pf = ChunkPrefetcher(
        store, schedule, stream="f32",
        budget_bytes=4 * store.chunk_bytes_f32, prefetch_depth=2, stats=stats,
    )
    out = pf.aggregate(plan)
    out.block_until_ready()
    assert stats.prefetched > 0
    assert stats.copy_ms > 0.0  # copies were actually timed
    assert stats.stall_ms >= 0.0
    assert 0.0 <= stats.prefetch_overlap <= 1.0


def test_sync_path_reports_zero_overlap():
    """prefetch_depth=0 (or async_stage off) is the untimed historical path:
    overlap must read 0, never a flattering inferred number."""
    g = _banded_graph(n=256, k=2)
    store = FeatureStore.from_array(g.features, chunk_rows=64)
    plan = build_edge_tile_plan(g, edges_per_tile=32)
    schedule = build_chunk_schedule(plan, 64)
    for kw in ({"prefetch_depth": 0}, {"prefetch_depth": 2, "async_stage": False}):
        stats = StreamStats()
        ChunkPrefetcher(
            store, schedule, stream="f32",
            budget_bytes=4 * store.chunk_bytes_f32, stats=stats, **kw,
        ).aggregate(plan)
        assert stats.copy_ms == 0.0
        assert stats.prefetch_overlap == 0.0


def test_async_and_sync_staging_bitwise_identical():
    """Staging changes WHEN copies happen, never WHAT the device computes."""
    g = _graph(n=500, deg=6.0, seed=7, dim=16)
    store = FeatureStore.from_array(g.features, chunk_rows=64)
    plan = build_edge_tile_plan(g, edges_per_tile=64)
    schedule = build_chunk_schedule(plan, 64)

    def run(**kw):
        pf = ChunkPrefetcher(
            store, schedule, stream="f32",
            budget_bytes=3 * store.chunk_bytes_f32, stats=StreamStats(), **kw,
        )
        return np.asarray(pf.aggregate(plan))

    ref = run(prefetch_depth=0)
    np.testing.assert_array_equal(run(prefetch_depth=2, async_stage=True), ref)
    np.testing.assert_array_equal(run(prefetch_depth=2, async_stage=False), ref)
    np.testing.assert_array_equal(run(prefetch_depth=4, async_stage=True), ref)


def test_sparse_residue_bitwise_and_counted():
    """Uniform-neighbour graph at a 2-slot budget: most chunk visits lose
    the Belady comparison and must be served as sparse row residue — still
    bitwise, with the rows counted."""
    from repro.core.aggregation import aggregate_edge_tiles, to_device_plan

    g = _graph(n=800, deg=8.0, seed=11, dim=16)
    store = FeatureStore.from_array(g.features, chunk_rows=64)
    plan = build_edge_tile_plan(g, edges_per_tile=64)
    schedule = build_chunk_schedule(plan, 64)
    ref = aggregate_edge_tiles(
        jnp.asarray(g.features), to_device_plan(plan),
        num_nodes=g.num_nodes, segments_per_tile=plan.segments_per_tile,
    )
    stats = StreamStats()
    pf = ChunkPrefetcher(
        store, schedule, stream="f32",
        budget_bytes=2 * store.chunk_bytes_f32, stats=stats,
    )
    out = pf.aggregate(plan)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    assert stats.sparse_rows > 0
    # sparse rows are charged to bytes_streamed but cost far less than
    # serving every miss as a full chunk upload would
    assert stats.bytes_streamed < stats.chunk_misses * store.chunk_bytes_f32


# --------------------------------------- serve-level knobs and new telemetry
@pytest.mark.parametrize("arch", ["gcn", "gin", "sage", "gat"])
def test_served_packed_stream_bitwise_identical(arch):
    """Engine-level acceptance for the packing mode: streamed == in-memory,
    bit for bit, with gnn_stream_packing on AND off, every arch, at an
    eviction-forcing budget."""
    cfg = get_config(f"ample-{arch}", reduced=True)
    g = make_dataset("cora", max_nodes=600, max_feature_dim=cfg.d_model, seed=0)
    ref_eng = GNNServeEngine(cfg, key=jax.random.PRNGKey(0))
    ref = ref_eng.infer(g, g.features)
    for packing in (False, True):
        eng = GNNServeEngine(
            cfg, ref_eng.params,
            feature_budget_bytes=g.features.nbytes // 4,
            feature_chunk_rows=64, stream_packing=packing,
        )
        r = eng.infer(g, g.features)
        assert r.streamed
        np.testing.assert_array_equal(r.outputs, ref.outputs)


def test_stream_knobs_threaded_from_config():
    """gnn_stream_packing / gnn_stream_reorder flow config -> engine, with
    constructor kwargs overriding — the reorder/pack A/B needs no hand-built
    prefetchers."""
    import dataclasses

    base = get_config("ample-gcn", reduced=True)
    cfg = dataclasses.replace(
        base, gnn_stream_packing=True, gnn_stream_reorder=False
    )
    eng = GNNServeEngine(cfg, key=jax.random.PRNGKey(0))
    assert eng.stream_packing is True and eng.stream_reorder is False
    eng2 = GNNServeEngine(
        cfg, stream_packing=False, stream_reorder=True,
        key=jax.random.PRNGKey(0),
    )
    assert eng2.stream_packing is False and eng2.stream_reorder is True
    # defaults match the historical behaviour
    eng3 = GNNServeEngine(base, key=jax.random.PRNGKey(0))
    assert eng3.stream_packing is False and eng3.stream_reorder is True


def test_reorder_control_arm_served_bitwise():
    """reorder=False (the control arm) must serve identical bytes too."""
    cfg = get_config("ample-gcn", reduced=True)
    g = make_dataset("cora", max_nodes=500, max_feature_dim=cfg.d_model, seed=0)
    ref_eng = GNNServeEngine(cfg, key=jax.random.PRNGKey(0))
    ref = ref_eng.infer(g, g.features)
    eng = GNNServeEngine(
        cfg, ref_eng.params,
        feature_budget_bytes=g.features.nbytes // 4,
        feature_chunk_rows=64, stream_reorder=False,
    )
    r = eng.infer(g, g.features)
    assert r.streamed
    np.testing.assert_array_equal(r.outputs, ref.outputs)


def test_response_and_cache_info_carry_stall_copy_ms():
    cfg = get_config("ample-gcn", reduced=True)
    g = make_dataset("cora", max_nodes=600, max_feature_dim=cfg.d_model, seed=0)
    eng = GNNServeEngine(
        cfg, feature_budget_bytes=g.features.nbytes // 4,
        feature_chunk_rows=64, key=jax.random.PRNGKey(0),
    )
    r = eng.infer(g, g.features)
    assert r.streamed
    assert r.copy_ms > 0.0  # async staging is the serve default (depth 2)
    assert r.stall_ms >= 0.0
    info = eng.cache_info()
    assert info["copy_ms"] == pytest.approx(eng.stats["copy_ms"])
    assert info["stall_ms"] == pytest.approx(eng.stats["stall_ms"])
    assert 0.0 <= info["prefetch_overlap"] <= 1.0


def test_stage_worker_build_error_reaches_the_consumer():
    """A staged build that raises is re-raised by the consumer's take():
    a failed gather or device put must not turn into a silent inline
    rebuild."""
    from repro.memory.prefetcher import _StageWorker

    def build(key):
        if key == ("chunk", 1):
            raise RuntimeError("device put failed")
        return key

    worker = _StageWorker(build)
    try:
        assert worker.request(("chunk", 0)) and worker.request(("chunk", 1))
        assert worker.take(("chunk", 0))[0] == ("chunk", 0)
        with pytest.raises(RuntimeError, match="device put failed"):
            worker.take(("chunk", 1))
        assert worker.take(("chunk", 2)) is None  # never staged
    finally:
        worker.stop()
