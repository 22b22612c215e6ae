"""Per-kernel shape/dtype sweeps: Pallas (interpret) vs pure-jnp oracles."""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core import build_edge_tile_plan
from repro.graphs.datasets import make_lognormal_graph


# ---------------------------------------------------------------- segment_agg
class TestSegmentAgg:
    @pytest.mark.parametrize("d", [4, 20, 130, 260])
    @pytest.mark.parametrize("ept", [16, 64])
    def test_shape_sweep(self, d, ept):
        from repro.kernels.segment_agg import ops
        from repro.kernels.segment_agg.ref import aggregate_tiles_ref

        g = make_lognormal_graph(80, 4.0, seed=d * 7 + ept)
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.standard_normal((80, d)).astype(np.float32))
        plan = build_edge_tile_plan(g, edges_per_tile=ept)
        args = (
            jnp.asarray(plan.gather_idx),
            jnp.asarray(plan.coeff),
            jnp.asarray(plan.seg_ids),
            jnp.asarray(plan.out_node),
        )
        kw = dict(num_nodes=80, segments_per_tile=plan.segments_per_tile)
        out = ops.aggregate_tiles(x, *args, block_d=128, **kw)
        ref = aggregate_tiles_ref(x, *args, **kw)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)

    @given(
        n=st.integers(4, 60),
        md=st.floats(1.0, 6.0),
        seed=st.integers(0, 200),
    )
    @settings(max_examples=10)
    def test_property_random_graphs(self, n, md, seed):
        from repro.kernels.segment_agg import ops
        from repro.kernels.segment_agg.ref import aggregate_tiles_ref

        g = make_lognormal_graph(n, md, seed=seed)
        rng = np.random.default_rng(seed)
        x = jnp.asarray(rng.standard_normal((n, 16)).astype(np.float32))
        coeff = rng.uniform(0.5, 1.5, g.num_edges).astype(np.float32)
        plan = build_edge_tile_plan(g, edges_per_tile=32, coeff=coeff)
        args = (
            jnp.asarray(plan.gather_idx),
            jnp.asarray(plan.coeff),
            jnp.asarray(plan.seg_ids),
            jnp.asarray(plan.out_node),
        )
        kw = dict(num_nodes=n, segments_per_tile=plan.segments_per_tile)
        out = ops.aggregate_tiles(x, *args, block_d=128, **kw)
        ref = aggregate_tiles_ref(x, *args, **kw)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)


# --------------------------------------------------------------- quant_matmul
class TestQuantMatmul:
    @pytest.mark.parametrize(
        "m,k,n",
        [(8, 8, 8), (100, 64, 48), (256, 512, 256), (33, 130, 7), (1, 300, 5)],
    )
    def test_shape_sweep_exact(self, m, k, n):
        from repro.kernels.quant_matmul import ops
        from repro.kernels.quant_matmul.ref import quant_matmul_ref

        rng = np.random.default_rng(m * 31 + k * 7 + n)
        a = jnp.asarray(rng.integers(-128, 128, (m, k)), jnp.int8)
        b = jnp.asarray(rng.integers(-128, 128, (k, n)), jnp.int8)
        out = ops.quant_matmul(a, b)
        ref = quant_matmul_ref(a, b)
        assert out.dtype == jnp.int32
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

    def test_extreme_values_no_overflow(self):
        """Worst case |a|,|b| = 128: K*128*128 must fit int32 for K ≤ 131072."""
        from repro.kernels.quant_matmul import ops
        from repro.kernels.quant_matmul.ref import quant_matmul_ref

        k = 1024
        a = jnp.full((4, k), -128, jnp.int8)
        b = jnp.full((k, 4), -128, jnp.int8)
        out = ops.quant_matmul(a, b)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(quant_matmul_ref(a, b)))
        assert int(np.asarray(out)[0, 0]) == k * 128 * 128


# ------------------------------------------------------------- tile groups
def _many_tile_plan(seed: int):
    """~200 tiles of 8 lanes: more than one kernel call's group of tiles."""
    from repro.kernels.segment_agg.segment_agg import TILE_GROUP

    g = make_lognormal_graph(220, 8.0, seed=seed)
    plan = build_edge_tile_plan(g, edges_per_tile=8)
    assert plan.num_tiles > TILE_GROUP
    return g, plan


def test_segment_agg_spans_tile_groups():
    from repro.kernels.segment_agg import ops
    from repro.kernels.segment_agg.ref import aggregate_tiles_ref

    g, plan = _many_tile_plan(1)
    x = jnp.asarray(
        np.random.default_rng(0).standard_normal((g.num_nodes, 24)).astype(np.float32)
    )
    args = tuple(
        jnp.asarray(a) for a in (plan.gather_idx, plan.coeff, plan.seg_ids, plan.out_node)
    )
    kw = dict(num_nodes=g.num_nodes, segments_per_tile=plan.segments_per_tile)
    out = ops.aggregate_tiles(x, *args, **kw)
    ref = aggregate_tiles_ref(x, *args, **kw)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)


def test_fused_attention_spans_tile_groups():
    from repro.kernels.segment_agg import attn_ops
    from repro.kernels.segment_agg.ref import attend_tiles_ref

    g, plan = _many_tile_plan(2)
    rng = np.random.default_rng(1)
    h, dh = 2, 5
    z = jnp.asarray(rng.standard_normal((g.num_nodes, h, dh)).astype(np.float32))
    scores = rng.standard_normal((g.num_edges + 1, h)).astype(np.float32)
    scores[-1] = -np.inf  # padding lanes read -inf
    eids = np.where(plan.edge_ids < 0, g.num_edges, plan.edge_ids)
    args = (
        jnp.asarray(plan.gather_idx),
        jnp.asarray(scores[eids]),
        jnp.asarray(plan.coeff),
        jnp.asarray(plan.seg_ids),
        jnp.asarray(plan.out_node),
    )
    kw = dict(
        num_nodes=g.num_nodes,
        segments_per_tile=plan.segments_per_tile,
        leaky_slope=0.2,
    )
    out = attn_ops.attend_tiles(z, *args, **kw)
    ref = attend_tiles_ref(z, *args, **kw)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5, rtol=1e-5)


# ------------------------------------------------------ interpret resolution
@pytest.mark.parametrize(
    "backend,expect", [("cpu", True), ("tpu", False), ("gpu", None), ("METAL", None)]
)
def test_interpret_resolves_from_backend(monkeypatch, backend, expect):
    """interpret=None: interpret mode on the CPU only, compiled on a TPU, and
    any other backend refuses instead of interpreting in silence."""
    import jax

    from repro.kernels.backend import resolve_interpret

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if expect is None:
        with pytest.raises(RuntimeError, match="use_kernel=False"):
            resolve_interpret(None)
    else:
        assert resolve_interpret(None) is expect
    assert resolve_interpret(True) is True and resolve_interpret(False) is False


def test_kernel_wrapper_refuses_non_cpu_backend(monkeypatch):
    import jax

    from repro.kernels.quant_matmul import ops

    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    a = jnp.ones((3, 7), jnp.int8)  # shapes no other test traces
    b = jnp.ones((7, 5), jnp.int8)
    with pytest.raises(RuntimeError, match="target the TPU"):
        ops.quant_matmul(a, b)
