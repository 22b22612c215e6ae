"""The request front's size-class padding: a single in-memory request gets
its zero rows on the device; the streamed path and ``infer_batch`` keep the
host copy; exact and sharded engines never pad. Every path's outputs equal
the forward over the host-padded matrix, bit for bit."""
from __future__ import annotations

import jax
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.graphs.datasets import make_lognormal_graph
from repro.models.gnn import api as gnn_api
from repro.serve.gnn_engine import GNNRequest, GNNServeEngine

ARCHS = ["gcn", "gat", "sage-pool"]
BUCKET = 128


def _cfg(arch):
    return get_config(f"ample-{arch}", reduced=True)


def _request(cfg, n=300, seed=0):
    """A graph whose node count is not a multiple of ``BUCKET``."""
    assert n % BUCKET
    g = make_lognormal_graph(n, 4.0, seed=seed)
    x = np.random.default_rng(seed + 1).standard_normal((n, cfg.d_model))
    return g, x.astype(np.float32)


def _padded_engine(cfg, **kw):
    eng = GNNServeEngine(cfg, key=jax.random.PRNGKey(0), union_node_bucket=BUCKET,
                         union_edge_bucket=1024, **kw)
    assert eng.padded_unions
    return eng


def _host_padded_forward(eng, g, x):
    """The served plan's forward over the zero rows added on the host."""
    prepared, _, engine, hit, _ = eng._plan_for_padded([g], eng.cfg.gnn_arch)
    assert hit and prepared.num_nodes > g.num_nodes
    host = eng._pad_features(x, prepared.num_nodes)
    y, _ = gnn_api.gnn_forward(
        eng.params, eng.cfg, {"graph": prepared, "features": host, "engine": engine})
    return np.asarray(y)[: g.num_nodes]


@pytest.mark.parametrize("arch", ARCHS)
def test_padded_infer_equals_forward_on_host_padded_matrix(arch):
    cfg = _cfg(arch)
    g, x = _request(cfg)
    eng = _padded_engine(cfg)
    eng.infer(g, x)  # cold: plans and calibrates the activation scales
    x2 = np.random.default_rng(7).standard_normal(x.shape).astype(np.float32)
    r = eng.infer(g, x2)
    np.testing.assert_array_equal(r.outputs, _host_padded_forward(eng, g, x2))


@pytest.mark.parametrize("arch", ARCHS)
def test_warm_requests_pad_on_the_device(arch):
    cfg = _cfg(arch)
    g, x = _request(cfg, seed=3)
    eng = _padded_engine(cfg)
    for _ in range(3):
        r = eng.infer(g, x)
    assert r.cache_hit and not r.streamed
    assert eng.stats["pad_host_bytes"] == 0
    assert eng.stats["device_pads"] == 3


def test_streamed_padded_path_keeps_the_host_copy():
    """Streaming is decided on the padded size: a matrix at the budget
    streams once padded, and its store is built from host-padded rows."""
    cfg = _cfg("gcn")
    g, x = _request(cfg, seed=5)
    base = _padded_engine(cfg)
    eng = GNNServeEngine(cfg, base.params, union_node_bucket=BUCKET,
                         union_edge_bucket=1024, feature_budget_bytes=x.nbytes,
                         feature_chunk_rows=64)
    padded_bytes = (-(-g.num_nodes // BUCKET) * BUCKET) * x.shape[1] * 4
    for _ in range(2):
        ref = base.infer(g, x)
        r = eng.infer(g, x)
        assert r.streamed
        np.testing.assert_array_equal(r.outputs, ref.outputs)
    assert eng.stats["pad_host_bytes"] == 2 * padded_bytes
    assert eng.stats["device_pads"] == 0
    assert base.stats["pad_host_bytes"] == 0 and base.stats["device_pads"] == 2


@pytest.mark.parametrize("arch", ARCHS)
def test_infer_batch_keeps_the_host_copy(arch):
    """A batch of one plans what ``infer`` plans: its host pad and the
    device pad give the same outputs."""
    cfg = _cfg(arch)
    g, x = _request(cfg, seed=9)
    eng = _padded_engine(cfg)
    solo = eng.infer(g, x)
    (batched,) = eng.infer_batch([GNNRequest(g, x)])
    np.testing.assert_array_equal(batched.outputs, solo.outputs)
    padded = -(-g.num_nodes // BUCKET) * BUCKET
    assert eng.stats["pad_host_bytes"] == padded * x.shape[1] * 4
    assert eng.stats["device_pads"] == 1


@pytest.mark.parametrize("shards", [1, 2], ids=["exact", "sharded"])
def test_unbucketed_and_sharded_engines_never_pad(shards):
    cfg = _cfg("gcn")
    g, x = _request(cfg, seed=11)
    eng = GNNServeEngine(cfg, key=jax.random.PRNGKey(0), num_shards=shards)
    assert not eng.padded_unions
    r = eng.infer(g, x)
    assert r.outputs.shape[0] == g.num_nodes and r.num_shards == shards
    assert eng.stats["pad_host_bytes"] == 0 and eng.stats["device_pads"] == 0
