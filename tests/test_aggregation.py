"""All aggregation paths agree with the dense oracle (property-tested)."""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, st

from repro.core import (
    build_bucket_plan,
    build_edge_tile_plan,
    build_mixed_precision_plans,
    build_padded_plan,
)
from repro.core.aggregation import (
    aggregate_bucket_plan,
    aggregate_edge_tiles,
    aggregate_mixed_precision,
    aggregate_padded_plan,
    dense_reference,
    edge_segment_sum_tiles,
    live_rows,
    segment_max_edge_tiles,
    segment_rows,
    tile_edge_coeff,
    to_device_plan,
)
from repro.core.degree_quant import DegreeQuantConfig, inference_precision_tags
from repro.core.quantization import compute_scale_zp, dequantize, quantize
from repro.core.scheduler import concat_tile_plans, pack_tiles_by_chunk, split_plan_by_halo
from repro.graphs import disjoint_union
from repro.graphs.csr import Graph, gcn_norm_coeffs
from repro.graphs.datasets import make_lognormal_graph


def _setup(n, md, d, seed, coeff=None):
    g = make_lognormal_graph(n, md, seed=seed)
    rng = np.random.default_rng(seed + 1)
    x = jnp.asarray(rng.standard_normal((n, d)).astype(np.float32))
    a = g.dense_adjacency()
    if coeff is not None:
        rows = np.repeat(np.arange(n), g.degrees)
        a = np.zeros_like(a)
        a[rows, g.indices] = coeff
    return g, x, a


@given(
    n=st.integers(2, 60),
    md=st.floats(1.0, 8.0),
    d=st.sampled_from([1, 7, 32]),
    ept=st.sampled_from([16, 64]),
    seed=st.integers(0, 500),
)
def test_edge_tiles_match_dense(n, md, d, ept, seed):
    g, x, a = _setup(n, md, d, seed)
    plan = build_edge_tile_plan(g, edges_per_tile=ept)
    out = aggregate_edge_tiles(
        x,
        to_device_plan(plan),
        num_nodes=n,
        segments_per_tile=plan.segments_per_tile,
    )
    np.testing.assert_allclose(out, dense_reference(x, a), atol=1e-4, rtol=1e-4)


@given(n=st.integers(2, 50), seed=st.integers(0, 300))
def test_gcn_coeff_tiles_match_dense(n, seed):
    g = make_lognormal_graph(n, 4.0, seed=seed)
    coeff = gcn_norm_coeffs(g)
    g2, x, a = _setup(n, 4.0, 9, seed, coeff=coeff)
    plan = build_edge_tile_plan(g, edges_per_tile=32, coeff=coeff)
    out = aggregate_edge_tiles(
        x, to_device_plan(plan), num_nodes=n, segments_per_tile=plan.segments_per_tile
    )
    np.testing.assert_allclose(out, dense_reference(x, a), atol=1e-4, rtol=1e-4)


@given(n=st.integers(2, 50), op=st.sampled_from(["sum", "mean", "max"]), seed=st.integers(0, 300))
def test_bucket_plan_ops(n, op, seed):
    g, x, a = _setup(n, 4.0, 8, seed)
    plan = build_bucket_plan(g)
    out = aggregate_bucket_plan(x, plan, op=op)
    xn = np.asarray(x)
    want = np.zeros((n, 8), np.float32)
    for i in range(n):
        nb = g.neighbors(i)
        if nb.size == 0:
            continue
        if op == "sum":
            want[i] = xn[nb].sum(0)
        elif op == "mean":
            want[i] = xn[nb].mean(0)
        else:
            want[i] = xn[nb].max(0)
    np.testing.assert_allclose(out, want, atol=1e-4, rtol=1e-4)


@given(n=st.integers(2, 50), bs=st.sampled_from([4, 16, 64]), seed=st.integers(0, 300))
def test_padded_plan_matches_dense(n, bs, seed):
    g, x, a = _setup(n, 4.0, 8, seed)
    plan = build_padded_plan(g, batch_size=bs)
    out = aggregate_padded_plan(x, plan)
    np.testing.assert_allclose(out, dense_reference(x, a), atol=1e-4, rtol=1e-4)


def test_mixed_precision_close_to_float():
    g, x, a = _setup(200, 5.0, 16, 42)
    tags = inference_precision_tags(g, DegreeQuantConfig(float_ratio=0.03))
    plans = build_mixed_precision_plans(g, tags)
    out = aggregate_mixed_precision(x, plans, num_nodes=200)
    ref = np.asarray(dense_reference(x, a))
    rel = np.abs(np.asarray(out) - ref).max() / (np.abs(ref).max() + 1e-9)
    assert rel < 0.05  # int8 path bounded error
    # protected (hub) rows are exact float
    fl = plans["float"].node_ids
    np.testing.assert_allclose(np.asarray(out)[fl], ref[fl], atol=1e-4, rtol=1e-4)


# ------------------------------------------------ segment-row window scans
#
# The three tile scans combine each tile's partials into a contiguous window
# of a segment-row accumulator. The reference below is the node-space
# scatter scan they replaced: every output must match it bitwise.


def _scatter_scan(partial, tiles, out_node, *, num_nodes, op, like, out_init=None):
    fill = 0.0 if op == "sum" else -jnp.inf
    out = jnp.full((num_nodes + 1,) + like.shape[1:], fill, like.dtype)
    if out_init is not None:
        out = out.at[:num_nodes].set(out_init)

    def body(out, tile):
        *args, on = tile
        p = partial(*args)
        return (out.at[on].add(p) if op == "sum" else out.at[on].max(p)), None

    out, _ = jax.lax.scan(body, out, tuple(tiles) + (out_node,))
    return out[:num_nodes]


@partial(jax.jit, static_argnames=("num_nodes", "s"))
def _ref_aggregate(x, dplan, *, num_nodes, s, edge_coeff=None, out_init=None):
    coeff = dplan.coeff
    if edge_coeff is not None:
        tc = tile_edge_coeff(dplan, edge_coeff)
        coeff = coeff[..., None] * tc if tc.ndim == 3 else coeff * tc

    def partial_sums(gi, cf, si):
        g = x[gi]
        cf = cf.reshape(cf.shape + (1,) * (g.ndim - cf.ndim))
        return jax.ops.segment_sum(g * cf, si, num_segments=s)

    tiles = (dplan.gather_idx, coeff, dplan.seg_ids)
    return _scatter_scan(partial_sums, tiles, dplan.out_node, num_nodes=num_nodes,
                         op="sum", like=x, out_init=out_init)


@partial(jax.jit, static_argnames=("num_nodes", "s", "op"))
def _ref_pass(values, dplan, *, num_nodes, s, op):
    seg = jax.ops.segment_max if op == "max" else jax.ops.segment_sum
    v = tile_edge_coeff(dplan, values, fill=-jnp.inf if op == "max" else 0.0)
    return _scatter_scan(lambda v_t, si: seg(v_t, si, num_segments=s),
                         (v, dplan.seg_ids), dplan.out_node,
                         num_nodes=num_nodes, op=op, like=values)


def _csr(degrees, rng):
    n = len(degrees)
    indptr = np.zeros(n + 1, np.int64)
    np.cumsum(degrees, out=indptr[1:])
    indices = rng.integers(0, n, size=int(indptr[-1])).astype(np.int32)
    return Graph(indptr=indptr, indices=indices, num_nodes=len(degrees))


def _window_plans(kind):
    """(graph, [plan, ...], num_owned) of one plan producer; the graph's edge
    space indexes every plan's edge ids."""
    rng = np.random.default_rng(7)
    if kind == "lognormal_hubs":
        g = make_lognormal_graph(300, 9.0, sigma=1.6, seed=3)
        assert g.degrees.max() > 4 * 16  # hubs split across many tiles
        return g, [build_edge_tile_plan(g, edges_per_tile=16, coeff=gcn_norm_coeffs(g))]
    if kind == "degree1_tail":
        g = _csr([40, 23] + [1] * 70, rng)
        plan = build_edge_tile_plan(g, edges_per_tile=16, segments_per_tile=8)
        assert np.any((plan.out_node != g.num_nodes).all(axis=1))
        return g, [plan]
    if kind == "empty":
        g = _csr([0] * 12, rng)
        plan = build_edge_tile_plan(g, edges_per_tile=16)
        assert plan.num_tiles == 1 and (plan.out_node == g.num_nodes).all()
        return g, [plan]
    if kind == "union":
        a = make_lognormal_graph(60, 6.0, seed=1)
        b = make_lognormal_graph(40, 5.0, seed=2)
        u = disjoint_union([a, b], pad_num_nodes=128)
        members = [build_edge_tile_plan(m, edges_per_tile=16) for m in (a, b)]
        return u, [concat_tile_plans(members, [0, 60], num_nodes=128, min_tiles=64,
                                     edge_offsets=[0, a.num_edges])]
    g = make_lognormal_graph(300, 7.0, seed=5)
    plan = build_edge_tile_plan(g, edges_per_tile=16, coeff=gcn_norm_coeffs(g))
    if kind == "halo_split":
        return g, list(split_plan_by_halo(plan, 200))
    assert kind == "packed"
    packed = pack_tiles_by_chunk(plan, 32)
    assert not np.array_equal(packed.out_node, plan.out_node)
    return g, [packed]


def _assert_same(got, want):
    got, want = np.asarray(got), np.asarray(want)
    assert got.shape == want.shape and got.dtype == want.dtype
    assert np.array_equal(got.view(np.uint32), want.view(np.uint32))


@pytest.mark.parametrize("coeffs", ["static", "edge", "heads"])
@pytest.mark.parametrize(
    "kind", ["lognormal_hubs", "degree1_tail", "empty", "union", "halo_split", "packed"]
)
def test_window_scans_match_node_space_scatter_bitwise(kind, coeffs):
    g, plans = _window_plans(kind)
    n, e = g.num_nodes, g.num_edges
    rng = np.random.default_rng(11)
    heads = 3
    if coeffs == "heads":
        x = jnp.asarray(rng.standard_normal((n, heads, 4)).astype(np.float32))
        vals = jnp.asarray(rng.standard_normal((e, heads)).astype(np.float32))
    else:
        x = jnp.asarray(rng.standard_normal((n, 5)).astype(np.float32))
        vals = jnp.asarray(rng.standard_normal(e).astype(np.float32))
    ec = None if coeffs == "static" else vals
    out = want = None  # interior, then boundary continuing from it
    for p in plans:
        dp, s = to_device_plan(p), p.segments_per_tile
        out = aggregate_edge_tiles(x, dp, num_nodes=n, segments_per_tile=s,
                                   edge_coeff=ec, out_init=out)
        want = _ref_aggregate(x, dp, num_nodes=n, s=s, edge_coeff=ec, out_init=want)
        _assert_same(out, want)
        for fn, op in ((segment_max_edge_tiles, "max"), (edge_segment_sum_tiles, "sum")):
            _assert_same(fn(vals, dp, num_nodes=n, segments_per_tile=s),
                         _ref_pass(vals, dp, num_nodes=n, s=s, op=op))
    if kind == "halo_split":  # split == unsplit, bitwise
        plan = build_edge_tile_plan(g, edges_per_tile=16, coeff=gcn_norm_coeffs(g))
        whole = aggregate_edge_tiles(x, to_device_plan(plan), num_nodes=n,
                                     segments_per_tile=16, edge_coeff=ec)
        _assert_same(out, whole)


def test_window_scans_ignore_non_finite_rows_only_padding_reads():
    """Padding lanes gather row 0; a NaN or inf there reaches no output row
    (the sentinel segments are masked before the window combine)."""
    rng = np.random.default_rng(2)
    deg = rng.integers(0, 9, 200)
    deg[1] += (7 - deg.sum()) % 16  # the last tile keeps padding lanes
    g = _csr(deg, rng)
    g = Graph(indptr=g.indptr, indices=np.maximum(g.indices, 1), num_nodes=g.num_nodes)
    plan = build_edge_tile_plan(g, edges_per_tile=16)
    padding = plan.edge_ids < 0
    assert padding.any() and (plan.gather_idx[padding] == 0).all()
    assert (plan.gather_idx[~padding] != 0).all()
    dp = to_device_plan(plan)
    x = rng.standard_normal((g.num_nodes, 4)).astype(np.float32)
    for bad in (np.nan, np.inf, -np.inf):
        x[0] = bad
        out = aggregate_edge_tiles(jnp.asarray(x), dp, num_nodes=g.num_nodes,
                                   segments_per_tile=16)
        assert np.isfinite(np.asarray(out)).all()
        _assert_same(out, _ref_aggregate(jnp.asarray(x), dp, num_nodes=g.num_nodes, s=16))


def test_segment_rows_derivation():
    g = make_lognormal_graph(300, 9.0, sigma=1.6, seed=3)
    sub = np.arange(0, 300, 3)  # a precision subset: the rest are uncovered
    plan = build_edge_tile_plan(g, edges_per_tile=16, node_ids=sub)
    n, s = g.num_nodes, plan.segments_per_tile
    row_start, node_row = segment_rows(plan)
    live = plan.out_node != n
    cont = sum(
        1 for t in range(1, plan.num_tiles)
        if live[t, 0] and live[t - 1].any()
        and plan.out_node[t, 0] == plan.out_node[t - 1][live[t - 1]][-1]
    )
    assert cont > 0  # hubs continue across tiles
    rows = live_rows(plan)
    assert rows == int(live.sum()) - cont
    covered = np.unique(plan.out_node[live])
    assert rows == covered.size
    # every covered node has exactly one row, and each live segment maps to it
    assert sorted(node_row[covered]) == list(range(rows))
    t_idx, s_idx = np.nonzero(live)
    assert np.array_equal(node_row[plan.out_node[t_idx, s_idx]], row_start[t_idx] + s_idx)
    # uncovered nodes own distinct spare rows beyond every window
    spare = np.setdiff1d(np.arange(n), covered)
    assert np.unique(node_row[spare]).size == spare.size
    assert node_row[spare].min() >= row_start.max() + s and node_row.max() < n + s
    # ... and read the identity
    x = jnp.ones((n, 2), jnp.float32)
    dp = to_device_plan(plan)
    out = np.asarray(aggregate_edge_tiles(x, dp, num_nodes=n, segments_per_tile=s))
    assert (out[spare] == 0).all()
    mx = np.asarray(segment_max_edge_tiles(jnp.ones(g.num_edges), dp, num_nodes=n,
                                           segments_per_tile=s))
    assert (mx[spare] == -np.inf).all() and (mx[covered] == 1).all()


def test_segment_rows_rejects_broken_invariant():
    g = make_lognormal_graph(100, 5.0, seed=4)
    plan = build_edge_tile_plan(g, edges_per_tile=16)
    assert plan.num_tiles > 3
    out_node = plan.out_node.copy()
    # a node in two non-adjacent tiles
    out_node[3, 0] = plan.out_node[0, 1]
    with pytest.raises(ValueError, match="non-adjacent"):
        segment_rows(dataclasses.replace(plan, out_node=out_node))
    # live segments that are not a prefix
    out_node = plan.out_node.copy()
    t = int(np.argmax((out_node != g.num_nodes).sum(axis=1) > 1))
    out_node[t, 0] = g.num_nodes
    with pytest.raises(ValueError, match="prefix"):
        to_device_plan(dataclasses.replace(plan, out_node=out_node))


# ------------------------------------------------ feature-wise max (op="max")
#
# The reference is a node-space segment max over the same gathered rows:
# every real lane's row goes to its destination node, padding lanes (coeff
# 0) are left out, and nodes no lane reaches read 0.


def _node_space_max(x, plan):
    x = np.asarray(x)
    n = plan.num_nodes
    real = plan.coeff != 0
    dst = plan.out_node[np.arange(plan.num_tiles)[:, None], plan.seg_ids][real]
    out = jax.ops.segment_max(jnp.asarray(x[plan.gather_idx[real]]),
                              jnp.asarray(dst), num_segments=n)
    covered = np.zeros(n, bool)
    covered[dst] = True
    return jnp.where(covered.reshape((n,) + (1,) * (x.ndim - 1)), out, 0.0)


@pytest.mark.parametrize(
    "kind", ["lognormal_hubs", "degree1_tail", "empty", "union", "halo_split", "packed"]
)
def test_window_max_matches_node_space_segment_max_bitwise(kind):
    g, plans = _window_plans(kind)
    n = g.num_nodes
    rng = np.random.default_rng(12)
    x = jnp.asarray(rng.standard_normal((n, 5)).astype(np.float32) - 2.0)  # mostly < 0
    for p in plans:  # halo_split: interior and boundary, each on its own nodes
        out = aggregate_edge_tiles(x, to_device_plan(p), num_nodes=n,
                                   segments_per_tile=p.segments_per_tile, op="max")
        _assert_same(out, _node_space_max(x, p))
        uncovered = np.setdiff1d(np.arange(n), p.out_node[p.out_node != n])
        assert (np.asarray(out)[uncovered] == 0).all()
        if kind in ("empty", "union"):
            assert uncovered.size > 0  # nodes with no in-edge read 0


def test_window_max_splits_hubs_and_takes_heads():
    g, (plan,) = _window_plans("lognormal_hubs")
    live = plan.out_node[plan.out_node != g.num_nodes]
    nodes, segments = np.unique(live, return_counts=True)
    split = nodes[segments > 1]
    assert split.size > 0  # hubs whose lanes run across several tiles
    x = jnp.asarray(np.random.default_rng(3).standard_normal((g.num_nodes, 2, 3)),
                    jnp.float32)
    out = aggregate_edge_tiles(x, to_device_plan(plan), num_nodes=g.num_nodes,
                               segments_per_tile=plan.segments_per_tile, op="max")
    _assert_same(out, _node_space_max(x, plan))
    nbr_max = np.stack([np.asarray(x)[g.neighbors(v)].max(axis=0) for v in split])
    _assert_same(np.asarray(out)[split], nbr_max)


def test_window_max_ignores_non_finite_rows_only_padding_reads():
    """Padding lanes gather row 0, some of them inside a live segment (S < E);
    a NaN or inf there reaches no output row."""
    rng = np.random.default_rng(2)
    g = _csr([40, 23] + [1] * 70, rng)
    g = Graph(indptr=g.indptr, indices=np.maximum(g.indices, 1), num_nodes=g.num_nodes)
    plan = build_edge_tile_plan(g, edges_per_tile=16, segments_per_tile=8)
    padding = plan.coeff == 0
    assert (plan.gather_idx[padding] == 0).all() and (plan.gather_idx[~padding] != 0).all()
    in_live = padding & (plan.out_node[np.arange(plan.num_tiles)[:, None], plan.seg_ids]
                         != g.num_nodes)
    assert in_live.any()
    dp = to_device_plan(plan)
    x = rng.standard_normal((g.num_nodes, 4)).astype(np.float32)
    for bad in (np.nan, np.inf, -np.inf):
        x[0] = bad
        out = aggregate_edge_tiles(jnp.asarray(x), dp, num_nodes=g.num_nodes,
                                   segments_per_tile=8, op="max")
        assert np.isfinite(np.asarray(out)).all()
        _assert_same(out, _node_space_max(x, plan))


def test_mixed_precision_max_rounds_only_int8_messages():
    g = make_lognormal_graph(300, 6.0, sigma=1.4, seed=9)
    tags = inference_precision_tags(g, DegreeQuantConfig(float_ratio=0.05))
    plans = build_mixed_precision_plans(g, tags, edges_per_tile=32)
    x = jnp.asarray(np.random.default_rng(4).standard_normal((300, 6)), jnp.float32)
    qp = compute_scale_zp(x, symmetric=True)
    out = np.asarray(aggregate_mixed_precision(x, plans, num_nodes=300, qp=qp, op="max"))
    xdq = np.asarray(dequantize(quantize(x, qp), qp))
    want = np.zeros_like(out)
    for v in range(300):
        src = g.neighbors(v)
        if src.size:
            want[v] = (np.asarray(x) if tags[v] == "float" else xdq)[src].max(axis=0)
    assert (tags == "int8").any() and (tags == "float").any()
    _assert_same(out, want)


def test_max_refuses_the_kernel_coefficients_and_unknown_ops():
    g, (plan,) = _window_plans("degree1_tail")
    dp, n = to_device_plan(plan), g.num_nodes
    x = jnp.ones((n, 4), jnp.float32)
    with pytest.raises(ValueError, match="Pallas AGE kernel"):
        aggregate_edge_tiles(x, dp, num_nodes=n, segments_per_tile=8, op="max",
                             use_kernel=True)
    with pytest.raises(ValueError, match="edge_coeff"):
        aggregate_edge_tiles(x, dp, num_nodes=n, segments_per_tile=8, op="max",
                             edge_coeff=jnp.ones(g.num_edges))
    with pytest.raises(ValueError, match="unknown aggregation op"):
        aggregate_edge_tiles(x, dp, num_nodes=n, segments_per_tile=8, op="min")
