"""GraphSAGE-pool (``sage_pool``, feature-wise max aggregation) through the
normal served path against its dense reference, and the paths that cannot
take a max refusing it."""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.core import AmpleEngine
from repro.graphs.csr import Graph
from repro.graphs.datasets import make_lognormal_graph
from repro.models.gnn import api as gnn_api
from repro.serve.gnn_engine import GNNServeEngine


def _cfg(precision="mixed", **kw):
    return dataclasses.replace(
        get_config("ample-sage-pool", reduced=True), gnn_precision=precision,
        gnn_edges_per_tile=32, **kw)


def _graph(n=160, isolated=(5, 77, 140)):
    """A lognormal graph whose ``isolated`` nodes have no in-edge."""
    g = make_lognormal_graph(n, 5.0, sigma=1.3, seed=21)
    deg = g.degrees.copy()
    keep = np.ones(g.num_edges, bool)
    for v in isolated:
        keep[g.indptr[v]:g.indptr[v + 1]] = False
        deg[v] = 0
    indptr = np.zeros(n + 1, np.int64)
    np.cumsum(deg, out=indptr[1:])
    return Graph(indptr=indptr, indices=g.indices[keep], num_nodes=n)


def _features(cfg, n, seed=0):
    return np.random.default_rng(seed).standard_normal((n, cfg.d_model)).astype(np.float32)


def _served_and_reference(cfg, g, x, seed=0):
    eng = GNNServeEngine(cfg, key=jax.random.PRNGKey(seed))
    y = eng.infer(g, x).outputs
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(gnn_api.gnn_reference(cfg, eng.params, g, x))
    return y, ref


def test_config_carries_reddit_widths():
    cfg = get_config("ample-sage-pool")
    assert cfg.gnn_arch == "sage_pool" and cfg.gnn_layer_dims == (602, 256, 256, 41)
    assert cfg.d_ff == 512 and gnn_api.agg_mode(cfg) == "sum"
    shapes = jax.eval_shape(lambda k: gnn_api.gnn_init(cfg, k), jax.random.PRNGKey(0))
    got = [(lyr["pool"]["w"].shape, lyr["self"]["w"].shape, lyr["neigh"]["w"].shape)
           for lyr in shapes["layers"]]
    assert got == [((602, 512), (602, 128), (512, 128)),
                   ((256, 512), (256, 128), (512, 128))]
    assert shapes["head"]["w"].shape == (256, 41) and shapes["head"]["b"].shape == (41,)


@pytest.mark.parametrize("seed", [0, 1])
def test_served_float_policy_matches_dense_reference(seed):
    """Float policy: the served forward and the dense oracle do the same
    float32 arithmetic (the max is exact), so only float32 product order
    separates them."""
    cfg, g = _cfg("float"), _graph()
    x = _features(cfg, g.num_nodes, seed)
    y, ref = _served_and_reference(cfg, g, x, seed)
    np.testing.assert_allclose(y, ref, rtol=1e-5, atol=1e-5 * np.abs(ref).max())


def test_served_mixed_policy_within_int8_error():
    """Mixed policy: 97% of rows run int8 (per-tensor activations, per-column
    weights: about 0.4% of the range a step) through seven products and two
    max passes, so the outputs sit within a few percent of the float oracle;
    the largest-degree rows stay float, but their messages come from int8
    pooled rows, so they are held to the same bound."""
    cfg, g = _cfg(), _graph()
    x = _features(cfg, g.num_nodes)
    y, ref = _served_and_reference(cfg, g, x)
    assert np.isfinite(y).all()
    rel = np.abs(y - ref).max() / np.abs(ref).max()
    rms = np.sqrt(((y - ref) ** 2).sum() / (ref ** 2).sum())
    assert rel < 0.08 and rms < 0.04, (rel, rms)


def test_nodes_with_no_in_edge_aggregate_to_zero():
    cfg = _cfg("float")
    g = _graph()
    eng = AmpleEngine(gnn_api.prepare_graph(cfg, g), gnn_api.engine_config(cfg))
    p = jnp.asarray(np.random.default_rng(1).standard_normal((g.num_nodes, 4)) - 3.0,
                    jnp.float32)
    a = np.asarray(eng.aggregate(p, mode="sum", op="max"))
    assert (a[[5, 77, 140]] == 0).all()
    v = int(np.argmax(g.degrees))
    np.testing.assert_array_equal(a[v], np.asarray(p)[g.neighbors(v)].max(axis=0))
    # the max runs on the plan the sum uploaded: no second upload
    dplans = eng._dplan_cache["sum"]
    eng.aggregate(p, mode="sum")
    assert eng._dplan_cache["sum"] is dplans and list(eng._dplan_cache) == ["sum"]


def test_max_refuses_the_kernel_path():
    cfg = _cfg(gnn_use_kernel=True)
    g = _graph()
    eng = AmpleEngine(g, gnn_api.engine_config(cfg))
    with pytest.raises(ValueError, match="use_kernel"):
        eng.aggregate(jnp.ones((g.num_nodes, 4)), mode="sum", op="max")
    with pytest.raises(ValueError, match="use_kernel"):
        GNNServeEngine(cfg, key=jax.random.PRNGKey(0)).infer(
            g, _features(cfg, g.num_nodes))


def test_max_refuses_streamed_features():
    from repro.memory.feature_store import FeatureStore
    from repro.memory.prefetcher import StreamedFeatures

    g = _graph()
    eng = AmpleEngine(g, gnn_api.engine_config(_cfg()))
    store = FeatureStore.from_array(
        np.ones((g.num_nodes, 8), np.float32), chunk_rows=32)
    with pytest.raises(ValueError, match="streamed"):
        eng.aggregate(StreamedFeatures(store, store.nbytes // 4), mode="sum", op="max")


def test_max_refuses_sharded_engines():
    cfg = _cfg()
    g = _graph()
    eng = gnn_api.make_engine(cfg, g, num_shards=2)
    assert type(eng).__name__ == "ShardedAmpleEngine"
    with pytest.raises(ValueError, match="sharded"):
        eng.aggregate(jnp.ones((g.num_nodes, 4)), mode="sum", op="max")
    summed = eng.aggregate(jnp.ones((g.num_nodes, 4)), mode="sum")  # still served
    assert np.isfinite(np.asarray(summed)).all()
