"""Smoke run of the AMPLE GNN serving path on TPU chips.

    python chip_smoke.py            # one chip
    python chip_smoke.py --chips 4  # four chips: the sharded phase only

One process drives ``repro.serve.gnn_engine.GNNServeEngine`` (the engine
``python -m repro.launch.serve`` builds) at the full width of the ``ample-*``
configs, 300 -> 256 -> 100, with random weights from seed 0, on synthetic
graphs at the full scale of the paper's Table 4, and checks every output
against a reference:

* served path (default jnp AGE): ``ample-gcn`` on yelp (716,847 nodes,
  ~14M edges). Request 0 plans cold, request 1 must hit the plan cache.
  Outputs are checked against a plain float32 GCN in ``jax.numpy``
  (segment sums over the prepared graph's edges with 1/sqrt(d_i d_j)).
* union batching: three cora-sized graphs in one ``infer_batch`` call,
  padded to the config's size classes, for gcn, gin, sage and gat, each
  checked against the dense ``gnn_reference``.
* kernel path: the yelp request again with ``gnn_use_kernel=True``
  (``segment_agg`` + ``quant_matmul``), and ``ample-gat`` with the fused
  attention kernel on a pubmed-scale graph, each checked against the jnp
  path of the same config; each kernel must lower to Mosaic
  (``tpu_custom_call``).
* ``--chips 4``: ``ample-gcn`` on reddit (232,965 nodes, 23.2M edges,
  features capped at 300) sharded four ways over a ``("shard",)`` mesh, with
  both partitioners, against the unsharded engine on device 0.

Mixed precision (Degree-Quant int8) is not expected to be allclose to a
float reference; the checks are argmax agreement and the max-normalized
error ``max|y - ref| / max|ref|``, with the limits below. Printed times
are labelled set-up (data, planning, compilation) or request wall time;
none is a benchmark. With no TPU the script exits non-zero before any phase.
The last line of standard output is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import numpy as np

# Mixed-precision GCN on yelp vs the float32 reference.
SERVED_MIN_ARGMAX = 0.90
SERVED_MAX_ERR = 0.08
# Each union member vs the dense float reference (per-arch int8 error).
UNION_MIN_ARGMAX = 0.85
UNION_MAX_ERR = 0.10
# Kernel path vs jnp path, and sharded vs unsharded: the same int8 codes up
# to a flip on a rounding boundary (order of accumulation differs).
SAME_MIN_ARGMAX = 0.99
SAME_MAX_ERR = 0.02


class SmokeFailure(AssertionError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)
    print(f"  ok: {what}")


def compare(name: str, y, ref, *, min_argmax: float, max_err: float) -> None:
    y, ref = np.asarray(y, np.float64), np.asarray(ref, np.float64)
    check(y.shape == ref.shape, f"{name}: shape {y.shape} == {ref.shape}")
    check(bool(np.isfinite(y).all()), f"{name}: outputs finite")
    agree = float((y.argmax(-1) == ref.argmax(-1)).mean())
    err = float(np.abs(y - ref).max() / (np.abs(ref).max() + 1e-12))
    print(f"  {name}: argmax agreement {agree:.6f}, max-normalized error {err:.6f}")
    check(agree >= min_argmax, f"{name}: argmax agreement >= {min_argmax}")
    check(err <= max_err, f"{name}: max-normalized error <= {max_err}")


class Clock:
    """Wall-clock phase timer; every line says what kind of time it is."""

    def __init__(self, label: str, kind: str):
        self.label, self.kind = label, kind

    def __enter__(self):
        self.t0 = time.perf_counter()

    def __exit__(self, *exc):
        if exc[0] is None:
            print(f"  [{self.kind}] {self.label}: {time.perf_counter() - self.t0:.3f} s")


def device_check(chips: int):
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"no TPU: jax found {platform!r} devices", file=sys.stderr)
        sys.exit(2)
    if len(devices) < chips:
        print(f"need {chips} chips, jax sees {len(devices)}", file=sys.stderr)
        sys.exit(2)
    import jaxlib
    from importlib import metadata

    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "not installed as a package"
    print(
        f"device_kind={devices[0].device_kind} count={len(devices)} "
        f"jax={jax.__version__} jaxlib={jaxlib.__version__} libtpu={libtpu}"
    )
    return devices


# ------------------------------------------------------------- references
def gcn_reference(params, g, x, *, edge_chunk: int = 1 << 20):
    """Plain float32 GCN: x' = W · Σ_j x_j / sqrt(d_i d_j), no plans or tiles.

    ``g`` is the prepared graph (self-loops present); d is its in-degree.
    Edges are summed in chunks so the gathered messages stay small.
    """
    import jax
    import jax.numpy as jnp

    n = g.num_nodes
    deg = np.maximum(np.diff(g.indptr), 1).astype(np.float64)
    dst = np.repeat(np.arange(n, dtype=np.int64), np.diff(g.indptr))
    src = g.indices.astype(np.int64)
    coeff = (1.0 / np.sqrt(deg[dst] * deg[src])).astype(np.float32)
    e = src.size
    chunks = max(-(-e // edge_chunk), 1)
    pad = chunks * edge_chunk - e
    src = np.concatenate([src, np.zeros(pad, np.int64)]).astype(np.int32)
    dst = np.concatenate([dst, np.full(pad, n, np.int64)]).astype(np.int32)
    coeff = np.concatenate([coeff, np.zeros(pad, np.float32)])
    shape = (chunks, edge_chunk)
    edges = tuple(jnp.asarray(a.reshape(shape)) for a in (src, dst, coeff))

    @jax.jit
    def aggregate(h, edges):
        def body(acc, chunk):
            s, d, c = chunk
            return acc + jax.ops.segment_sum(h[s] * c[:, None], d, num_segments=n + 1), None

        acc, _ = jax.lax.scan(body, jnp.zeros((n + 1, h.shape[1]), h.dtype), edges)
        return acc[:n]

    h = jnp.asarray(x, jnp.float32)
    layers = params["layers"]
    for i, lyr in enumerate(layers):
        h = jnp.dot(
            aggregate(h, edges), lyr["w"], precision=jax.lax.Precision.HIGHEST
        )
        if i < len(layers) - 1:
            h = jax.nn.relu(h)
    return np.asarray(h)


# ---------------------------------------------------------------- phases
def phase_served(cfg, g):
    """ample-gcn through GNNServeEngine.infer, twice; returns the outputs."""
    import jax

    from repro.models.gnn import api as gnn_api
    from repro.serve.gnn_engine import GNNServeEngine

    print(f"== served path (jnp AGE): {cfg.name} on {g.name} "
          f"({g.num_nodes} nodes, {g.num_edges} edges, {g.features.shape[1]} features)")
    eng = GNNServeEngine(cfg, key=jax.random.PRNGKey(0))
    with Clock("request 0 (cold plan + compile + run)", "set-up"):
        r0 = eng.infer(g, g.features)
    print(f"  request 0: plan_ms={r0.plan_ms:.3f} run_ms={r0.run_ms:.3f} "
          f"cache_hit={r0.cache_hit}")
    with Clock("request 1 (warm)", "request"):
        r1 = eng.infer(g, g.features)
    print(f"  request 1: plan_ms={r1.plan_ms:.3f} run_ms={r1.run_ms:.3f} "
          f"cache_hit={r1.cache_hit}")
    check(not r0.cache_hit, "request 0 planned cold")
    check(r1.cache_hit and r1.plan_ms == 0.0, "request 1 hit the plan cache (plan_ms == 0)")
    check(np.array_equal(r0.outputs, r1.outputs), "warm output == cold output")
    with Clock("float32 reference", "set-up"):
        ref = gcn_reference(eng.params, gnn_api.prepare_graph(cfg, g), g.features)
    compare("served vs float32 GCN", r1.outputs, ref,
            min_argmax=SERVED_MIN_ARGMAX, max_err=SERVED_MAX_ERR)
    return r1.outputs


def phase_unions(cfgs, members):
    """infer_batch over padded size classes for each arch vs gnn_reference."""
    import jax

    from repro.models.gnn import api as gnn_api
    from repro.serve.gnn_engine import GNNRequest, GNNServeEngine

    for cfg in cfgs:
        print(f"== union batching: {cfg.name}, {len(members)} graphs of "
              f"{[m.num_nodes for m in members]} nodes, size classes "
              f"{cfg.gnn_union_node_bucket}/{cfg.gnn_union_edge_bucket}")
        eng = GNNServeEngine(cfg, key=jax.random.PRNGKey(0))
        check(eng.padded_unions, f"{cfg.name}: padded union size classes on")
        reqs = [GNNRequest(graph=m, features=m.features) for m in members]
        with Clock("infer_batch (plan + compile + run)", "set-up"):
            outs = eng.infer_batch(reqs)
        check(len({r.fingerprint for r in outs}) == 1, "one device call for the batch")
        for i, (m, r) in enumerate(zip(members, outs)):
            ref = gnn_api.gnn_reference(cfg, eng.params, m, m.features)
            compare(f"{cfg.name} member {i}", r.outputs, ref,
                    min_argmax=UNION_MIN_ARGMAX, max_err=UNION_MAX_ERR)


def lowered_kernels(cfg) -> dict:
    """Lowered text of each kernel's jitted wrapper at the config's widths,
    with ``interpret`` left to the backend (None)."""
    import jax
    import jax.numpy as jnp

    from repro.core.aggregation import DeviceTilePlan, aggregate_edge_tiles
    from repro.kernels.quant_matmul import ops as qm_ops
    from repro.kernels.segment_agg import attn_ops

    n, t, e = 1024, 4, cfg.gnn_edges_per_tile
    d_in, d_hid = cfg.d_model, cfg.d_ff
    i32 = lambda *s: jnp.zeros(s, jnp.int32)
    f32 = lambda *s: jnp.zeros(s, jnp.float32)
    plan = DeviceTilePlan(i32(t, e), f32(t, e), i32(t, e), i32(t, e), None)
    a_q = jnp.zeros((n, d_in), jnp.int8)
    w_q = jnp.zeros((d_in, d_hid), jnp.int8)
    packed = qm_ops.repack_weight(w_q)
    heads = 4
    return {
        "segment_agg": aggregate_edge_tiles.lower(
            f32(n, d_in), plan, num_nodes=n, segments_per_tile=e, use_kernel=True
        ).as_text(),
        "quant_matmul": qm_ops.quant_matmul.lower(a_q, w_q).as_text(),
        "quant_matmul_repacked": jax.jit(
            lambda a: qm_ops.quant_matmul_repacked(a, packed)
        ).lower(a_q).as_text(),
        "fused_attention": attn_ops.attend_tiles.lower(
            f32(n, heads, d_hid // heads), i32(t, e), f32(t, e, heads),
            f32(t, e), i32(t, e), i32(t, e),
            num_nodes=n, segments_per_tile=e, leaky_slope=0.2,
        ).as_text(),
    }


def phase_kernel_age(cfg, g, y_jnp):
    """The served yelp request with the AGE + int8 FTE kernels."""
    import jax

    from repro.serve.gnn_engine import GNNServeEngine

    kcfg = dataclasses.replace(cfg, gnn_use_kernel=True)
    print(f"== kernel path: {kcfg.name} gnn_use_kernel=True on {g.name}")
    for name, text in lowered_kernels(kcfg).items():
        check("tpu_custom_call" in text, f"{name} lowers to a Mosaic tpu_custom_call")
    eng = GNNServeEngine(kcfg, key=jax.random.PRNGKey(0))
    with Clock("request 0 (cold plan + compile + run)", "set-up"):
        eng.infer(g, g.features)
    with Clock("request 1 (warm)", "request"):
        r = eng.infer(g, g.features)
    print(f"  request 1: run_ms={r.run_ms:.3f}")
    compare("kernel vs jnp path", r.outputs, y_jnp,
            min_argmax=SAME_MIN_ARGMAX, max_err=SAME_MAX_ERR)


def phase_kernel_gat(cfg, g):
    """ample-gat with the fused attention kernel vs its jnp path."""
    import jax

    from repro.serve.gnn_engine import GNNServeEngine

    print(f"== fused attention kernel: {cfg.name} ({cfg.gnn_heads} heads) on "
          f"{g.name} ({g.num_nodes} nodes, {g.num_edges} edges)")
    outs = {}
    for use_kernel in (False, True):
        c = dataclasses.replace(cfg, gnn_use_kernel=use_kernel)
        eng = GNNServeEngine(c, key=jax.random.PRNGKey(0))
        with Clock(f"use_kernel={use_kernel} (plan + compile + run)", "set-up"):
            eng.infer(g, g.features)
        with Clock(f"use_kernel={use_kernel} warm request", "request"):
            outs[use_kernel] = eng.infer(g, g.features).outputs
    compare("fused attention vs jnp path", outs[True], outs[False],
            min_argmax=SAME_MIN_ARGMAX, max_err=SAME_MAX_ERR)


def phase_sharded(cfg, g, devices, chips: int):
    """Sharded serving over a ("shard",) mesh vs the unsharded engine."""
    import jax

    from repro.launch.serve import shard_mesh
    from repro.serve.gnn_engine import GNNServeEngine

    print(f"== sharded serving: {cfg.name} on {g.name} ({g.num_nodes} nodes, "
          f"{g.num_edges} edges) over {chips} chips")
    with jax.default_device(devices[0]):
        base = GNNServeEngine(cfg, key=jax.random.PRNGKey(0))
        with Clock("unsharded on device 0 (plan + compile + run)", "set-up"):
            y0 = base.infer(g, g.features).outputs
    mesh = shard_mesh(chips)
    check(mesh is not None, f"launcher builds a mesh for {chips} shards on {chips} chips")
    for partitioner in ("edges", "mincut"):
        eng = GNNServeEngine(
            cfg, key=jax.random.PRNGKey(0), num_shards=chips,
            partitioner=partitioner, mesh=mesh,
        )
        with Clock(f"{partitioner}: request 0 (partition + plan + compile + run)", "set-up"):
            eng.infer(g, g.features)
        with Clock(f"{partitioner}: request 1 (warm)", "request"):
            r = eng.infer(g, g.features)
        rep = eng.shard_report()
        print(f"  {partitioner}: halo_bytes={r.halo_bytes} "
              f"halo_per_shard={rep['halo_per_shard']} "
              f"edges_per_shard={rep['edges_per_shard']} "
              f"devices={rep['shard_devices']}")
        check(r.num_shards == chips, f"{partitioner}: served over {chips} shards")
        check(r.halo_bytes > 0, f"{partitioner}: halo exchange moved bytes")
        check(
            rep["shard_devices"] == [str(d) for d in mesh.devices.flat]
            and len(set(rep["shard_devices"])) == chips,
            f"{partitioner}: shard k's output block lives on chip k",
        )
        compare(f"{partitioner} sharded vs unsharded", r.outputs, y0,
                min_argmax=SAME_MIN_ARGMAX, max_err=SAME_MAX_ERR)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 runs only the sharded phase on a four-chip mesh")
    args = ap.parse_args()

    devices = device_check(args.chips)
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))
    from repro.configs.base import get_config
    from repro.graphs import make_dataset
    from repro.launch.compile_cache import configure_compile_cache

    print(f"compile cache: {configure_compile_cache()}")
    t0 = time.perf_counter()
    cfg = get_config("ample-gcn")
    if args.chips == 4:
        with Clock("reddit dataset", "set-up"):
            g = make_dataset("reddit", seed=0, max_feature_dim=cfg.d_model)
        phase_sharded(cfg, g, devices, args.chips)
    else:
        with Clock("yelp dataset", "set-up"):
            yelp = make_dataset("yelp", seed=0)
        y_jnp = phase_served(cfg, yelp)
        members = [
            make_dataset("cora", seed=s, max_feature_dim=cfg.d_model) for s in (1, 2, 3)
        ]
        phase_unions([get_config(f"ample-{a}") for a in ("gcn", "gin", "sage", "gat")],
                     members)
        phase_kernel_age(cfg, yelp, y_jnp)
        pubmed = make_dataset("pubmed", seed=0, max_feature_dim=cfg.d_model)
        phase_kernel_gat(get_config("ample-gat"), pubmed)
    print(f"all phases passed in {time.perf_counter() - t0:.1f} s wall (set-up included)")
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
        },
    }))


if __name__ == "__main__":
    main()
