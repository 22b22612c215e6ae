"""Ahead-of-time compiles for a described TPU v5e: the served path's kernels
at real widths go through the chip's compiler (Mosaic) without a chip.

Interpret mode checks what a kernel computes; only the TPU compiler checks
that its blocks, DMAs and scratch are legal on the chip. Each case lowers a
public wrapper with ``interpret=False`` against shapes placed on one device
of a described ``v5e:2x2`` topology, compiles it, and asserts that the
kernel is in the program (``tpu_custom_call``). Nothing runs: the results
say nothing about outputs or speed.

The topology is described inside a module fixture (never at import), so
every worker of a parallel run collects the same tests and only the worker
that runs this file loads the TPU compiler.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest

pytestmark = pytest.mark.filterwarnings("ignore")

V5E_HBM_BYTES = 16 * 1024**3


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of these compiles.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, *shapes, sharding, **static):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return fn.lower(*args, **static).compile()


def _kernel_in(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("d", [300, 256])
def test_segment_agg_compiles_past_the_smem_bound(one_chip, d):
    """The AGE kernel at E=256 over 4096 tiles: the whole index stream
    (4 MiB) would not fit SMEM; per-group prefetch must."""
    from repro.kernels.segment_agg import ops

    n, t, e = 100_000, 4096, 256
    c = _compile(
        ops.aggregate_tiles,
        ((n, d), jnp.float32),
        ((t, e), jnp.int32),
        ((t, e), jnp.float32),
        ((t, e), jnp.int32),
        ((t, e), jnp.int32),
        sharding=one_chip,
        num_nodes=n,
        segments_per_tile=e,
        interpret=False,
    )
    assert _kernel_in(c)


def test_fused_attention_compiles(one_chip):
    """ample-gat's hidden layer: H=4 heads of dh=64 over 1024 tiles."""
    from repro.kernels.segment_agg import attn_ops

    n, t, e, h, dh = 20_000, 1024, 256, 4, 64
    c = _compile(
        attn_ops.attend_tiles,
        ((n, h, dh), jnp.float32),
        ((t, e), jnp.int32),
        ((t, e, h), jnp.float32),
        ((t, e), jnp.float32),
        ((t, e), jnp.int32),
        ((t, e), jnp.int32),
        sharding=one_chip,
        num_nodes=n,
        segments_per_tile=e,
        leaky_slope=0.2,
        interpret=False,
    )
    assert _kernel_in(c)


@pytest.mark.parametrize("repacked", [False, True], ids=["plain", "repacked"])
def test_quant_matmul_compiles_at_yelp_scale(one_chip, repacked):
    """The int8 FTE of ample-gcn's first layer on yelp: 716,847 × 300 × 256."""
    from repro.kernels.quant_matmul import ops
    from repro.kernels.quant_matmul.repack import _repacked_call, repack_weight

    m, k, n = 716_847, 300, 256
    if not repacked:
        c = _compile(
            ops.quant_matmul,
            ((m, k), jnp.int8),
            ((k, n), jnp.int8),
            sharding=one_chip,
            interpret=False,
        )
    else:
        spec = repack_weight(jnp.zeros((k, n), jnp.int8))  # host-side layout
        c = _compile(
            _repacked_call,
            ((m, k), jnp.int8),
            (spec.tiles.shape, jnp.int8),
            sharding=one_chip,
            k=spec.k,
            n=spec.n,
            block_k=spec.block_k,
            block_n=spec.block_n,
            block_m=256,
            interpret=False,
        )
    assert _kernel_in(c)


def test_jnp_age_compiles_at_reddit_scale(one_chip):
    """The default (jnp, lax.scan) AGE at reddit scale fits one chip."""
    from repro.core.aggregation import DeviceTilePlan, aggregate_edge_tiles

    n, d, t, e = 232_965, 300, 92_000, 256
    tile = lambda dt: jax.ShapeDtypeStruct((t, e), dt, sharding=one_chip)
    plan = DeviceTilePlan(
        gather_idx=tile(jnp.int32),
        coeff=tile(jnp.float32),
        seg_ids=tile(jnp.int32),
        out_node=tile(jnp.int32),
        row_start=jax.ShapeDtypeStruct((t,), jnp.int32, sharding=one_chip),
        node_row=jax.ShapeDtypeStruct((n,), jnp.int32, sharding=one_chip),
        edge_ids=None,
    )
    x = jax.ShapeDtypeStruct((n, d), jnp.float32, sharding=one_chip)
    c = aggregate_edge_tiles.lower(
        x, plan, num_nodes=n, segments_per_tile=e
    ).compile()
    mem = c.memory_analysis()
    used = mem.argument_size_in_bytes + mem.output_size_in_bytes + mem.temp_size_in_bytes
    assert 0 < used < V5E_HBM_BYTES


def test_jnp_age_max_compiles_at_reddit_scale(one_chip):
    """ample-sage-pool's feature-wise max at reddit scale: 512-wide pooled
    rows over about 95,000 tiles fit one chip."""
    from repro.core.aggregation import DeviceTilePlan, aggregate_edge_tiles

    n, d, t, e = 232_965, 512, 95_000, 256
    tile = lambda dt: jax.ShapeDtypeStruct((t, e), dt, sharding=one_chip)
    plan = DeviceTilePlan(
        gather_idx=tile(jnp.int32),
        coeff=tile(jnp.float32),
        seg_ids=tile(jnp.int32),
        out_node=tile(jnp.int32),
        row_start=jax.ShapeDtypeStruct((t,), jnp.int32, sharding=one_chip),
        node_row=jax.ShapeDtypeStruct((n,), jnp.int32, sharding=one_chip),
        edge_ids=None,
    )
    x = jax.ShapeDtypeStruct((n, d), jnp.float32, sharding=one_chip)
    c = aggregate_edge_tiles.lower(
        x, plan, num_nodes=n, segments_per_tile=e, op="max"
    ).compile()
    mem = c.memory_analysis()
    used = mem.argument_size_in_bytes + mem.output_size_in_bytes + mem.temp_size_in_bytes
    assert 0 < used < V5E_HBM_BYTES
    print(f"max scan: argument {mem.argument_size_in_bytes} output "
          f"{mem.output_size_in_bytes} temp {mem.temp_size_in_bytes} bytes")
