"""The GraphSAGE-pool cell's parts: its plain reference against the
program's dense oracle, the reference's blocked neighbour max, the
configuration file, its compulsory counts and a run of a tiny cell."""
import copy
import dataclasses
import json

import numpy as np
import pytest

from bench import counts, counts_pool, harness, refcore
from bench.references import sage_pool
from bench.registry import BENCH_DIR
from bench.traffic import table4
from bench.tests import tiny


def _tiny_cfg():
    from repro.configs.base import get_config

    return dataclasses.replace(get_config("ample-sage-pool"), d_model=24, d_ff=12,
                               gnn_hidden=(10, 8), vocab_size=5)


def test_reference_matches_program_oracle():
    import jax

    from repro.graphs.csr import Graph
    from repro.models.gnn import api as gnn_api

    cfg = _tiny_cfg()
    params = gnn_api.gnn_init(cfg, jax.random.PRNGKey(0))
    params = jax.tree_util.tree_map(  # nonzero biases
        lambda a: a + 0.05 if a.ndim == 1 else a, params)
    indptr, indices = table4.lognormal_csr(150, 4.0, seed=3)
    x = np.random.default_rng(0).standard_normal((150, 24)).astype(np.float32)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(gnn_api.gnn_reference(
            cfg, params, Graph(indptr=indptr, indices=indices, num_nodes=150), x))
    host = jax.tree_util.tree_map(lambda a: np.asarray(a, np.float64), params)
    g = refcore.Graph(indptr, indices)
    got = sage_pool.forward(g, x, host, np.arange(150))
    assert np.abs(got - want).max() <= 1e-5 * np.abs(want).max()
    rows = np.array([0, 7, 99, 149])
    np.testing.assert_allclose(sage_pool.forward(g, x, host, rows), got[rows],
                               rtol=0, atol=1e-12)


def test_blocked_degree_grouped_max_equals_per_row_max(monkeypatch):
    rng = np.random.default_rng(5)
    deg = np.r_[0, 1, 1, 3, 3, 3, 40, 0, 7, 7, 2]
    ptr = np.r_[0, np.cumsum(deg)]
    local = rng.integers(0, 30, ptr[-1])
    msgs = rng.standard_normal((30, 6)) - 1.0  # mostly negative
    sel = np.ones(deg.size, bool)
    sel[4] = False
    monkeypatch.setattr(sage_pool, "BLOCK_EDGES", 8)  # several blocks a degree
    got = np.full((deg.size, 6), np.nan)
    sage_pool.neighbour_max(ptr, local, msgs, sel, got)
    want = np.stack([msgs[local[ptr[v]:ptr[v + 1]]].max(axis=0) if deg[v] else
                     np.zeros(6) for v in range(deg.size)])
    assert np.array_equal(got[sel], want[sel])
    assert (got[[0, 7]] == 0).all()  # no in-edge
    assert np.isnan(got[4]).all()  # not selected: left alone


def test_config_file_loads_with_the_programs_degree_quant():
    config = json.loads((BENCH_DIR / "configs" / "ample-sage-pool.json").read_text())
    cfg = harness.model_config(config)
    assert cfg.gnn_arch == config["arch"] == "sage_pool"
    assert cfg.gnn_layer_dims == (602, 256, 256, 41) and cfg.d_ff == 512
    wrong = copy.deepcopy(config)
    wrong["degree_quant"]["float_ratio"] = 0.027
    with pytest.raises(ValueError, match="float_ratio"):
        harness.model_config(wrong)


def test_pool_counts_by_hand():
    # N=10 nodes (2 float), E=30 edges, widths 3 -> 4 -> 2 -> 5, pool 6
    dims, pool = (3, 4, 2, 5), 6
    one = counts.Work(30 * 6, 0, 30 * 4 + (8 * 6 + 2 * 6 * 4) + 10 * 6 * 4)
    assert counts_pool.age_work(10, 30, 2, dims, pool) == one + one
    fte = lambda a, b: counts.Work(2 * 2 * a * b, 2 * 8 * a * b,
                                   (8 * a + 2 * a * 4) + a * b * 5 + 10 * b * 4)
    layer1 = fte(3, 6) + fte(3, 2) + fte(6, 2)
    layer2 = fte(4, 6) + fte(4, 1) + fte(6, 1)
    assert counts_pool.forward_work(10, 30, 2, dims, pool) == (
        one + one + layer1 + layer2 + fte(2, 5))


def test_tiny_cell_runs_correct_on_the_served_path(tmp_path):
    """A tiny sage_pool cell laid over the benchmark runs through the
    harness: served answers agree with the plain reference."""
    spec = tiny.make(tmp_path)
    cfg = json.loads((BENCH_DIR / "configs" / "ample-sage-pool.json").read_text())
    cfg["name"] = "tiny-sage-pool"
    cfg["model"].update(tiny.MODEL, gnn_hidden=[16, 16])
    tiny._write(tmp_path / "configs" / "tiny-sage-pool.json", cfg)
    tiny._write(tmp_path / "limits" / "tiny-sage-pool-whole.json", {"limits": tiny.LIMITS})
    spec["workloads"] = [{"name": "tiny-sage-pool-whole", "config": "tiny-sage-pool",
                          "traffic": "tiny-whole", "chips": 1, "why": "CPU test cell"}]
    res = harness.run_cell("tiny-sage-pool-whole", 2**33 + 5, 0.5, False, spec=spec,
                           registry=tiny.registry(tmp_path), compile_cache=False)
    assert res["correct"], res["checks"]
    assert res["checks"]["float_err"]["value"] < 1e-4
    assert {"setup_s", "graph_ms"} <= set(res["metrics"])


@pytest.mark.parametrize("bits,static", [(8, False), (8, True), (4, False)])
def test_int_row_max_equals_the_max_of_rounded_messages(bits, static):
    """The int rows' max over integer codes times the scale is the float64
    max of the messages ``Precision.messages`` rounds, bit for bit."""
    rng = np.random.default_rng(bits)
    ptr = np.r_[0, np.cumsum(rng.integers(0, 9, 40))]
    local = rng.integers(0, 50, ptr[-1])
    p = np.maximum(rng.standard_normal((50, 7)), 0.0)
    sel = rng.random(40) < 0.8
    prec = refcore.Precision(int_bits=bits, scales={("agg", 0): 0.021} if static else None)
    got, want = np.full((40, 7), np.nan), np.full((40, 7), np.nan)
    sage_pool.int_row_max(prec, p, ("agg", 0), ptr, local, sel, got)
    sage_pool.neighbour_max(ptr, local, prec.messages(p, True, ("agg", 0)), sel, want)
    assert np.array_equal(got[sel], want[sel]) and np.isnan(got[~sel]).all()
