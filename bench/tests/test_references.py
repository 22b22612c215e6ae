"""The benchmark's plain references against the program's dense oracles on
small graphs, and a row subset against the full forward."""
import dataclasses

import numpy as np
import pytest

from bench import refcore
from bench.references import gat, gcn
from bench.traffic import table4


@pytest.mark.parametrize("arch,mod", [("gcn", gcn), ("gat", gat)])
def test_reference_matches_program_oracle(arch, mod):
    import jax

    from repro.configs.base import get_config
    from repro.graphs.csr import Graph
    from repro.models.gnn import api as gnn_api

    cfg = dataclasses.replace(get_config(f"ample-{arch}"), d_model=24, d_ff=16,
                              vocab_size=5, gnn_heads=2 if arch == "gat" else 1)
    params = gnn_api.gnn_init(cfg, jax.random.PRNGKey(0))
    indptr, indices = table4.lognormal_csr(150, 4.0, seed=3)
    x = np.random.default_rng(0).standard_normal((150, 24)).astype(np.float32)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(gnn_api.gnn_reference(
            cfg, params, Graph(indptr=indptr, indices=indices, num_nodes=150), x))
    host = jax.tree_util.tree_map(lambda a: np.asarray(a, np.float64), params)
    g = refcore.Graph(indptr, indices)
    got = mod.forward(g, x, host, np.arange(150))
    assert np.abs(got - want).max() <= 1e-5 * np.abs(want).max()
    rows = np.array([0, 7, 99, 149])
    np.testing.assert_allclose(mod.forward(g, x, host, rows), got[rows], rtol=0, atol=1e-12)


def test_self_loops_and_hop_sets():
    g = refcore.Graph(np.array([0, 1, 3, 4]), np.array([1, 0, 2, 0], np.int32))
    pg = refcore.with_self_loops(g)
    assert pg.degrees.tolist() == [2, 3, 2]
    assert sorted(pg.indices[pg.indptr[1]:pg.indptr[2]].tolist()) == [0, 1, 2]
    sets = refcore.hop_sets(pg, np.array([0]), 2)
    assert sets[-1].tolist() == [0]
    assert sets[1].tolist() == [0, 1]
    assert sets[0].tolist() == [0, 1, 2]


def test_degree_quant_rows_follow_degree():
    pg = refcore.with_self_loops(refcore.Graph(*table4.lognormal_csr(400, 6.0, seed=1)))
    fr = refcore.degree_quant_float_rows(pg, 0.03)
    assert fr.sum() == 12
    assert pg.degrees[fr].min() >= pg.degrees[~fr].max()
