"""Observability: span recorder, metrics registry, lifecycle reconciliation.

Three layers of guarantees:

1. **Primitives** — the ring buffer bounds memory, the Chrome-trace export
   is well-formed (Perfetto-loadable), the disabled recorder is a shared
   no-op singleton (the zero-overhead default).
2. **Consolidation** — the engines' ``stats`` dicts, ``cache_info()`` and
   the Prometheus dump all read the *same* registry cells, so they can
   never disagree; value semantics (ints stay ints) are unchanged.
3. **Reconciliation** — spans are recorded from the same ``perf_counter``
   stamps the ``*_ms`` accounting uses, so trace-derived totals match the
   reported fields: exactly for queue/run, within tolerance for the
   prefetcher's reconstructed stall/copy intervals. Per-lane span sets
   must be laminar (disjoint or nested) — overlapping spans on one lane
   mean a bookkeeping bug, not concurrency.
"""
import json
import time

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from repro.configs.base import get_config
from repro.graphs import make_dataset
from repro.observe import metrics as ometrics
from repro.observe import trace as otrace
from repro.observe.trace import NULL_SPAN, TraceRecorder
from repro.serve.async_gnn import AsyncGNNEngine
from repro.serve.gnn_engine import GNNRequest, GNNServeEngine, request_stamp
from repro.serve.telemetry import TenantTelemetry
from repro.serve.tenancy import TenantRouter


@pytest.fixture()
def recorder():
    """A fresh enabled recorder installed for the test, disabled after."""
    rec = otrace.enable(capacity=1 << 14)
    yield rec
    otrace.disable()


def _cfg(arch="gcn"):
    return get_config(f"ample-{arch}", reduced=True)


def _graph(n=300, seed=0, dim=None):
    return make_dataset(
        "cora", max_nodes=n, max_feature_dim=dim or _cfg().d_model, seed=seed
    )


# ------------------------------------------------------------- primitives
def test_ring_bounds_memory_and_counts_drops():
    rec = TraceRecorder(capacity=4)
    for i in range(10):
        rec.add_span(f"s{i}", 0.0, 1.0)
    spans = rec.spans()
    assert len(spans) == 4
    assert [s.name for s in spans] == ["s6", "s7", "s8", "s9"]  # oldest evicted
    assert rec.dropped == 6
    rec.clear()
    assert rec.spans() == [] and rec.dropped == 0


def test_disabled_recorder_is_noop_singleton():
    rec = TraceRecorder(capacity=16, enabled=False)
    # Zero-allocation claim: every disabled span() is the same object.
    assert rec.span("a") is NULL_SPAN
    assert rec.span("b", cat="x", trace_id="t") is NULL_SPAN
    with rec.span("c") as sp:
        sp.set(k=1)  # no-op, no error
    rec.add_span("d", 0.0, 1.0)
    rec.add_instant("e")
    assert rec.spans() == []


def test_module_recorder_default_disabled_and_toggles():
    assert not otrace.is_enabled()  # the process default is off
    rec = otrace.enable(capacity=64)
    try:
        assert otrace.is_enabled() and otrace.get_recorder() is rec
        with otrace.get_recorder().span("x", cat="t"):
            pass
        assert [s.name for s in rec.spans()] == ["x"]
    finally:
        otrace.disable()
    assert not otrace.is_enabled()
    # the old recorder still holds its spans; the fresh one is empty
    assert len(rec.spans()) == 1 and otrace.get_recorder().spans() == []


def test_nested_spans_and_total_ms():
    rec = TraceRecorder()
    tid = "req-x"
    with rec.span("outer", trace_id=tid):
        time.sleep(0.002)
        with rec.span("inner", trace_id=tid):
            time.sleep(0.001)
    inner, outer = rec.spans()[0], rec.spans()[1]  # inner commits first
    assert (inner.name, outer.name) == ("inner", "outer")
    assert outer.t0 <= inner.t0 and inner.t1 <= outer.t1  # properly nested
    assert rec.total_ms("outer") >= rec.total_ms("inner") > 0.0
    assert rec.total_ms("outer", trace_id="other") == 0.0


def test_chrome_trace_export_shape(tmp_path):
    rec = TraceRecorder()
    rec.add_span("work", 1.0, 1.5, cat="c", lane="laneA", trace_id="req-1",
                 args={"k": 2})
    rec.add_span("work2", 1.5, 1.7, lane="laneB")
    rec.add_instant("mark", t=1.2, lane="laneA")
    doc = rec.chrome_trace()
    events = doc["traceEvents"]
    meta = [e for e in events if e["ph"] == "M"]
    complete = [e for e in events if e["ph"] == "X"]
    instants = [e for e in events if e["ph"] == "i"]
    # one thread_name record per lane, stable tid mapping
    assert {m["args"]["name"] for m in meta} == {"laneA", "laneB"}
    tid = {m["args"]["name"]: m["tid"] for m in meta}
    w = next(e for e in complete if e["name"] == "work")
    assert w["tid"] == tid["laneA"]
    assert w["dur"] == pytest.approx(0.5e6)  # microseconds
    assert w["args"] == {"k": 2, "trace_id": "req-1"}
    assert instants[0]["s"] == "t"
    assert doc["otherData"]["dropped_spans"] == 0
    # export round-trips through json (the Perfetto load path)
    path = rec.export(str(tmp_path / "trace.json"))
    with open(path) as f:
        assert json.load(f)["traceEvents"] == events


def test_new_trace_ids_are_unique():
    ids = {otrace.new_trace_id() for _ in range(100)}
    assert len(ids) == 100 and all(i.startswith("req-") for i in ids)


# -------------------------------------------------------- metrics registry
def test_registry_counters_and_labels():
    reg = ometrics.MetricsRegistry()
    fam = reg.counter("reqs_total", help="h", labels=("engine",))
    fam.labels(engine="a").inc()
    fam.labels(engine="a").inc(2)
    fam.labels(engine="b").inc()
    assert fam.labels(engine="a").value == 3.0
    assert fam.labels(engine="b").value == 1.0
    with pytest.raises(ValueError):
        fam.labels(wrong="a")
    with pytest.raises(ValueError):
        reg.gauge("reqs_total")  # kind conflict on an existing name
    text = reg.prometheus_text()
    assert '# TYPE reqs_total counter' in text
    assert 'reqs_total{engine="a"} 3' in text
    assert 'reqs_total{engine="b"} 1' in text


def test_registry_histogram_summary_exposition():
    reg = ometrics.MetricsRegistry()
    h = reg.histogram("lat_ms", help="h").labels()
    for v in (10.0, 20.0, 30.0, 40.0):
        h.record(v)
    text = reg.prometheus_text()
    assert "# TYPE lat_ms summary" in text
    assert 'lat_ms{quantile="0.5"}' in text
    assert "lat_ms_sum 100" in text
    assert "lat_ms_count 4" in text
    snap = reg.snapshot()["lat_ms"]
    assert snap["kind"] == "histogram"
    assert snap["samples"][0]["value"]["count"] == 4


def test_register_histogram_adopts_shared_object():
    reg = ometrics.MetricsRegistry()
    from repro.serve.telemetry import StreamingHistogram

    hist = StreamingHistogram()
    reg.register_histogram("ext_ms", hist, tenant="t0")
    hist.record(5.0)  # recorded through the ORIGINAL object
    fam = reg.get("ext_ms")
    (labels, child), = fam.samples()
    assert child is hist and labels == {"tenant": "t0"}
    assert 'ext_ms_count{tenant="t0"} 1' in reg.prometheus_text()


def test_stats_view_value_semantics():
    reg = ometrics.MetricsRegistry()
    sv = ometrics.StatsView(
        reg, "eng", {"engine": "e0"}, keys=("hits", "stall_ms"),
        float_keys=("stall_ms",),
    )
    sv["hits"] += 1
    sv["stall_ms"] += 1.25
    assert sv["hits"] == 1 and isinstance(sv["hits"], int)
    assert sv["stall_ms"] == 1.25 and isinstance(sv["stall_ms"], float)
    assert dict(sv) == {"hits": 1, "stall_ms": 1.25}
    # the view IS the registry cell — no second copy to drift
    assert reg.get("eng_hits").labels(engine="e0").value == 1.0
    sv["hits"] = 7
    assert reg.get("eng_hits").labels(engine="e0").value == 7.0


def test_next_instance_unique():
    a, b = ometrics.next_instance("x"), ometrics.next_instance("x")
    assert a != b and a.startswith("x-") and b.startswith("x-")


# ------------------------------------ consolidation: stats == registry cells
def test_engine_stats_cache_info_and_prometheus_agree():
    g = _graph(n=200)
    eng = GNNServeEngine(_cfg(), key=jax.random.PRNGKey(0))
    eng.infer(g, g.features)
    eng.infer(g, g.features)
    # one storage: the stats dict view, cache_info and the registry cell
    reg = ometrics.get_registry()
    cell = reg.get("gnn_serve_requests").labels(engine=eng.instance)
    assert eng.stats["requests"] == 2 == int(cell.value)
    info = eng.cache_info()
    for k, v in eng.stats.items():
        assert info[k] == v, k
    assert isinstance(eng.stats["cache_hits"], int)
    assert isinstance(eng.stats["stall_ms"], float)
    text = reg.prometheus_text()
    assert f'gnn_serve_requests{{engine="{eng.instance}"}} 2' in text


def test_concurrent_engines_do_not_alias_counters():
    g = _graph(n=150)
    e1 = GNNServeEngine(_cfg(), key=jax.random.PRNGKey(0))
    e2 = GNNServeEngine(_cfg(), key=jax.random.PRNGKey(0))
    e1.infer(g, g.features)
    assert e1.stats["requests"] == 1
    assert e2.stats["requests"] == 0  # per-instance labels keep them apart
    assert e1.instance != e2.instance


def test_async_cache_info_is_thin_view_over_stats():
    pool = [_graph(n=60, seed=s) for s in (1, 2, 3)]
    async_eng = AsyncGNNEngine(_cfg(), window=2, key=jax.random.PRNGKey(1))
    tickets = [async_eng.submit(g, g.features) for g in pool]
    async_eng.drain()
    info = async_eng.cache_info()
    for k, v in async_eng.stats.items():
        assert info[k] == v, k
    assert info["completed"] == len(tickets)
    assert all(t.done for t in tickets)


def test_tenant_telemetry_histograms_land_in_registry():
    tel = TenantTelemetry()
    tel.record_submitted("gold")
    tel.record_completion("gold", latency_ms=12.0, queue_ms=3.0, nodes=10)
    fam = ometrics.get_registry().get("tenant_latency_ms")
    children = {
        tuple(sorted(labels.items())): child for labels, child in fam.samples()
    }
    key = (("telemetry", tel.instance), ("tenant", "gold"))
    assert children[key] is tel._tenants["gold"].latency  # adopted, not copied
    assert children[key].count == 1
    text = ometrics.get_registry().prometheus_text()
    assert f'tenant_latency_ms_count{{telemetry="{tel.instance}",tenant="gold"}} 1' in text


# ---------------------------------------- lifecycle spans + reconciliation
def _laminar(spans, eps=1.5e-3):
    """Assert the intervals form a laminar family: any two are (eps-)disjoint
    or one (eps-)contains the other."""
    ivs = sorted(
        [(s.t0, s.t1, s.name) for s in spans if s.t1 > s.t0],
        key=lambda iv: (iv[0], -iv[1]),
    )
    for i, (a0, a1, an) in enumerate(ivs):
        for b0, b1, bn in ivs[i + 1:]:
            if b0 >= a1 - eps:
                continue  # disjoint (b starts after a ends)
            assert b1 <= a1 + eps, (
                f"lane overlap: {an} [{a0:.6f},{a1:.6f}) vs "
                f"{bn} [{b0:.6f},{b1:.6f})"
            )


def test_direct_request_spans_reconcile_with_response(recorder):
    g = _graph(n=400)
    eng = GNNServeEngine(_cfg(), key=jax.random.PRNGKey(0))
    eng.infer(g, g.features)  # warm the plan cache outside the window
    admitted = request_stamp() - 0.05
    r = eng.infer(g, g.features, admitted_at=admitted)
    assert r.trace_id
    mine = [s for s in recorder.spans() if s.trace_id == r.trace_id]
    names = {s.name for s in mine}
    assert {"queue", "plan", "execute"} <= names
    by = {s.name: s for s in mine}
    # same stamps as the accounting -> exact, not approximate
    assert by["execute"].dur_ms == pytest.approx(r.run_ms, rel=1e-9)
    assert by["queue"].dur_ms == pytest.approx(r.queue_ms, rel=1e-9)
    assert r.queue_ms >= 50.0  # the backdated admission is visible
    assert by["plan"].args["cache_hit"]
    assert by["plan"].t1 <= by["execute"].t0  # plan precedes execute
    # the queue span ends where planning starts
    assert by["queue"].t1 == pytest.approx(by["plan"].t0, abs=1e-9)


def test_streamed_request_trace_tree_and_totals(recorder):
    g = _graph(n=600)
    eng = GNNServeEngine(
        _cfg(), feature_budget_bytes=g.features.nbytes // 4,
        feature_chunk_rows=64, key=jax.random.PRNGKey(0),
    )
    r = eng.infer(g, g.features)
    assert r.streamed and r.copy_ms > 0.0
    mine = [s for s in recorder.spans() if s.trace_id == r.trace_id]
    names = {s.name for s in mine}
    assert "execute" in names
    assert any(n.startswith("stream:") for n in names)
    copies = [s for s in mine if s.name.startswith("copy:")]
    assert copies, "streamed request recorded no copy spans"
    # per-lane span sets must be laminar — overlap within a lane is a bug
    lanes = {}
    for s in mine:
        lanes.setdefault(s.lane, []).append(s)
    for lane, spans in lanes.items():
        _laminar(spans)
    # copy spans live on the staging lanes, not the consumer lane
    assert {s.lane for s in copies} <= {"copy", "copy-inline"}
    # trace-derived totals reconcile with the response accounting (10%
    # acceptance tolerance + a small absolute floor for sub-ms noise)
    copy_total = sum(s.dur_ms for s in copies)
    assert copy_total == pytest.approx(r.copy_ms, rel=0.10, abs=1.0)
    stall_total = sum(s.dur_ms for s in mine if s.name == "stall")
    assert stall_total == pytest.approx(r.stall_ms, rel=0.10, abs=1.0)
    # stream spans nest inside the execute window
    ex = next(s for s in mine if s.name == "execute")
    for s in mine:
        if s.name.startswith("stream:") or s.name.startswith("layer:"):
            assert s.t0 >= ex.t0 - 1e-4 and s.t1 <= ex.t1 + 1e-4, s.name


def test_batch_spans_per_member_queue_and_scatter(recorder):
    pool = [_graph(n=80, seed=s) for s in (1, 2, 3)]
    eng = GNNServeEngine(_cfg(), key=jax.random.PRNGKey(0))
    at = request_stamp() - 0.02
    reqs = [
        GNNRequest(graph=g, features=g.features, admitted_at=at,
                   trace_id=f"req-batch-{i}")
        for i, g in enumerate(pool)
    ]
    out = eng.infer_batch(reqs)
    assert [r.trace_id for r in out] == [r.trace_id for r in reqs]
    spans = recorder.spans()
    queues = [s for s in spans if s.name == "queue"]
    assert {s.trace_id for s in queues} == {r.trace_id for r in reqs}
    for r, q in zip(out, sorted(queues, key=lambda s: s.trace_id)):
        assert q.dur_ms == pytest.approx(r.queue_ms, rel=1e-9)
    assert any(s.name == "scatter" for s in spans)
    plan = next(s for s in spans if s.name == "plan")
    assert plan.args["batch"] == len(reqs)


def test_async_and_routed_paths_stamp_same_clock(recorder):
    """Satellite: queue_ms means the same thing on every path — a wait on
    the ``request_stamp`` (perf_counter) timeline, ending at execution."""
    g = _graph(n=100)
    # direct engine path: backdated admitted_at
    eng = GNNServeEngine(_cfg(), key=jax.random.PRNGKey(0))
    r_direct = eng.infer(g, g.features, admitted_at=request_stamp() - 0.2)
    assert r_direct.queue_ms >= 195.0
    # async path: backdated arrival flows through the ticket
    async_eng = AsyncGNNEngine(_cfg(), window=1, key=jax.random.PRNGKey(0))
    t = async_eng.submit(g, g.features, arrival=request_stamp() - 0.2)
    r_async = t.result()
    assert r_async.queue_ms >= 195.0
    assert t.trace_id and r_async.trace_id == t.trace_id
    # routed path: arrival is stamped at the door on the same clock, so
    # queue_ms is bounded by the submit->result wall time on that clock
    router = TenantRouter(
        AsyncGNNEngine(_cfg(), window=1, key=jax.random.PRNGKey(0))
    )
    router.add_tenant("t0")
    t0 = request_stamp()
    ticket = router.submit("t0", g, g.features)
    router.step()
    resp = ticket.result()
    wall_ms = (request_stamp() - t0) * 1e3
    assert 0.0 <= resp.queue_ms <= wall_ms
    assert ticket.trace_id and resp.trace_id == ticket.trace_id
    # every path records queue + execute spans under the request's id
    for tid in (r_direct.trace_id, r_async.trace_id, resp.trace_id):
        names = {s.name for s in recorder.spans() if s.trace_id == tid}
        assert "execute" in names, tid
    assert any(
        s.name == "dwrr_fill" for s in recorder.spans()
    ), "router fill left no span"


def test_trace_export_of_live_serving_loads_as_chrome_json(recorder, tmp_path):
    g = _graph(n=500)
    eng = GNNServeEngine(
        _cfg(), feature_budget_bytes=g.features.nbytes // 4,
        feature_chunk_rows=64, key=jax.random.PRNGKey(0),
    )
    r = eng.infer(g, g.features)
    assert r.streamed
    path = recorder.export(str(tmp_path / "trace.json"))
    with open(path) as f:
        doc = json.load(f)
    phases = {e["ph"] for e in doc["traceEvents"]}
    assert "X" in phases and "M" in phases
    lanes = {
        e["args"]["name"] for e in doc["traceEvents"] if e["ph"] == "M"
    }
    assert any(l.startswith("copy") for l in lanes), lanes


# ------------------------------------------------------------ overhead guard
def test_disabled_tracing_overhead_under_two_percent():
    """The disabled recorder must cost <2% of a warm serve request.

    Hybrid guard (robust on noisy CI): measure the per-call cost of the
    disabled-path idioms (``rec.enabled`` guard; ``span()`` returning the
    singleton), multiply by a *generous* per-request call count, and compare
    against the measured warm per-request time.
    """
    assert not otrace.is_enabled()
    rec = otrace.get_recorder()
    n = 50_000
    t0 = time.perf_counter()
    for _ in range(n):
        if rec.enabled:  # the guard every instrumentation point pays
            pass
        rec.span("x")  # the context-manager form pays this instead
    per_call_s = (time.perf_counter() - t0) / n

    g = _graph(n=200)
    eng = GNNServeEngine(_cfg(), key=jax.random.PRNGKey(0))
    eng.infer(g, g.features)  # warm the plan cache
    reps = 5
    t0 = time.perf_counter()
    for _ in range(reps):
        eng.infer(g, g.features)
    per_request_s = (time.perf_counter() - t0) / reps

    # 200 trace points per request is far above the real count (~a dozen
    # plus a few per streamed chunk; this warm path streams nothing).
    overhead = 200 * per_call_s
    assert overhead < 0.02 * per_request_s, (
        f"disabled tracing overhead {overhead * 1e6:.1f}us vs "
        f"request {per_request_s * 1e3:.2f}ms"
    )


# ------------------------------------------------- bench regression checker
def _load_checker():
    """benchmarks/ is a namespace package rooted at the repo root; load the
    checker by path so the test works regardless of invocation cwd."""
    import importlib.util
    import os

    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "benchmarks", "check_regression.py",
    )
    spec = importlib.util.spec_from_file_location("check_regression", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_check_regression_findings(tmp_path):
    cr = _load_checker()

    base = {
        "quick": True,
        "rows": [
            {"name": "a", "us_per_call": 100.0, "chunk_hit_rate": "0.8",
             "prefetch_overlap": "0.9"},
            {"name": "b", "us_per_call": 50.0},
        ],
    }
    fresh = {
        "quick": True,
        "rows": [
            {"name": "a", "us_per_call": 120.0, "chunk_hit_rate": "0.6",
             "prefetch_overlap": "0.2"},
            {"name": "c", "us_per_call": 10.0},
        ],
    }
    bp, fp = tmp_path / "base.json", tmp_path / "fresh.json"
    bp.write_text(json.dumps(base))
    fp.write_text(json.dumps(fresh))
    frows, fq = cr.load_rows(str(fp))
    brows, bq = cr.load_rows(str(bp))
    assert fq and bq
    hard = cr.check_hard_gates(frows, brows)
    assert {f.severity for f in hard} == {"FAIL"}
    msgs = " | ".join(f.message for f in hard)
    assert "prefetch_overlap" in msgs and "chunk_hit_rate" in msgs
    soft = cr.check_soft_drift(frows, brows, same_scale=True)
    assert any("no baseline row" in f.message for f in soft)  # new bench c
    assert any("missing from fresh" in f.message for f in soft)  # lost b
    # slowdown 1.2x is inside the 1.5x tolerance -> no wall-clock warn
    assert not any("us_per_call" in f.message for f in soft)
    # exit code: 1 with fails, 0 when the gate is disabled
    rc = cr.main(["--fresh", str(fp), "--baseline", str(bp)])
    assert rc == 1


def test_check_regression_gate_disable(tmp_path, monkeypatch):
    cr = _load_checker()

    fresh = {"quick": True,
             "rows": [{"name": "a", "prefetch_overlap": "0.1"}]}
    fp = tmp_path / "fresh.json"
    fp.write_text(json.dumps(fresh))
    assert cr.main(["--fresh", str(fp)]) == 1
    monkeypatch.setenv("REPRO_BENCH_NO_GATE", "1")
    assert cr.main(["--fresh", str(fp)]) == 0


def test_halo_overlap_spans_reconcile_with_response(recorder):
    """Overlapped sharded request: halo_gather/halo_wait spans on the halo
    lane, recorded from the same stamps as the halo_ms/halo_wait_ms
    accounting, carrying the request's trace_id."""
    g = _graph(n=400)
    eng = GNNServeEngine(
        _cfg(), key=jax.random.PRNGKey(0), num_shards=2,
        partitioner="mincut", halo_overlap=True,
    )
    eng.infer(g, g.features)  # warm plans + jit outside the window
    r = eng.infer(g, g.features)
    assert r.halo_bytes > 0
    mine = [s for s in recorder.spans() if s.trace_id == r.trace_id]
    gathers = [s for s in mine if s.name == "halo_gather"]
    waits = [s for s in mine if s.name == "halo_wait"]
    assert gathers and waits
    assert all(s.cat == "halo" for s in gathers)
    # span-derived totals match the reported fields (same stamps -> exact)
    assert sum(s.dur_ms for s in gathers) == pytest.approx(r.halo_ms, rel=1e-6)
    wait_total = sum(s.dur_ms for s in waits)
    stats_wait = eng.stats["halo_wait_ms"]
    assert wait_total >= 0.0 and stats_wait >= 0.0
    assert 0.0 <= r.halo_overlap <= 1.0
    # the gather runs on its own lane, apart from the consumer's spans
    assert {s.lane for s in gathers} == {"halo"}


# ------------------------------------------------ spans on the profiler
def _profiled_spans(logdir):
    """The ``ample.*`` host events of the one xplane under ``logdir``:
    [(name, start_ns, end_ns, stats, line)]."""
    import glob

    from jax.profiler import ProfileData

    (path,) = glob.glob(f"{logdir}/plugins/profile/*/*.xplane.pb")
    out = []
    for plane in ProfileData.from_file(path).planes:
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith(otrace.PROFILER_PREFIX):
                    out.append((e.name[len(otrace.PROFILER_PREFIX):],
                                int(e.start_ns), int(e.end_ns),
                                {k: str(v) for k, v in e.stats},
                                f"{plane.name}/{i}"))
    return out


def test_warm_request_spans_nest_on_the_profilers_clock(tmp_path):
    """With the ring off, a profiler session alone sees the request's span
    tree as ``ample.*`` host events, nested in time, under one trace_id."""
    g = _graph(n=300)
    eng = GNNServeEngine(_cfg(), key=jax.random.PRNGKey(0),
                         union_node_bucket=128, union_edge_bucket=512)
    eng.infer(g, g.features)  # warm: plan cache, activation scales, compiles
    assert not otrace.is_enabled()
    with jax.profiler.trace(str(tmp_path)):
        r = eng.infer(g, g.features)
    assert r.trace_id and r.cache_hit
    spans = _profiled_spans(tmp_path)
    assert {s[3].get("trace_id") for s in spans} == {r.trace_id}
    assert len({s[4] for s in spans}) == 1  # one thread
    by = {}
    for s in spans:
        by.setdefault(s[0], []).append(s)
    tree = {
        "request": {"validate", "plan", "pad", "execute"},
        "execute": {"upload", "layer", "wait", "fetch"},
        "layer": {"fte", "age"},
    }

    def inside(child, parent):
        return parent[1] <= child[1] and child[2] <= parent[2]

    for parent, children in tree.items():
        for child in children:
            assert by[child], child
            for c in by[child]:
                assert any(inside(c, p) for p in by[parent]), (child, parent)
    assert len(by["request"]) == 1 and len(by["layer"]) == 2
    assert by["request"][0][3]["padded_nodes"] == "384"
    assert by["request"][0][3]["cache_hit"] in ("1", "True")
    # the AGE spans carry the segment-row window's occupancy
    for age in by["age"]:
        assert 0 < int(age[3]["rows"]) <= int(age[3]["window_rows"])
    # siblings of one parent do not overlap, and run in the served order
    order = [by[n][0] for n in ("validate", "plan", "pad", "execute")]
    assert all(a[2] <= b[1] for a, b in zip(order, order[1:]))


def test_span_is_null_unless_ring_or_profiler_collects(tmp_path):
    rec = TraceRecorder(capacity=16, enabled=False)
    assert not rec.recording and rec.span("a") is NULL_SPAN
    with jax.profiler.trace(str(tmp_path)):
        assert rec.recording
        live = rec.span("b")
        assert live is not NULL_SPAN
        with live:
            pass
    assert rec.spans() == []  # the ring stays off; the profiler saw it
    assert [s[0] for s in _profiled_spans(tmp_path)] == ["b"]
    assert not rec.recording and rec.span("c") is NULL_SPAN


def test_ring_spans_record_parent_and_inherit_trace_id():
    rec = TraceRecorder()
    with rec.span("outer", trace_id="req-p") as outer:
        with rec.span("inner") as inner:
            pass
        with rec.span("other", trace_id="req-q"):
            pass
    with rec.span("next"):
        pass
    by = {s.name: s for s in rec.spans()}
    assert by["inner"].parent == by["outer"].sid == outer.sid
    assert by["inner"].sid == inner.sid != by["outer"].sid
    assert by["inner"].trace_id == "req-p"  # inherited from the open span
    assert by["other"].trace_id == "req-q"  # its own id wins
    assert by["outer"].parent == 0 and by["next"].parent == 0
    assert by["next"].trace_id == ""


def test_pinned_stamps_replace_entry_and_exit():
    rec = TraceRecorder()
    with rec.span("x") as sp:
        sp.stamps(t0=1.0)
        sp.stamps(t1=2.5)
    (s,) = rec.spans()
    assert (s.t0, s.t1) == (1.0, 2.5)
    assert NULL_SPAN.stamps(1.0, 2.0) is NULL_SPAN


def test_warm_sage_pool_age_spans_record_the_max(recorder):
    """GraphSAGE-pool's AGE spans say which reduction ran and how many real
    nodes had no in-edge (size-class padding rows are not counted), nested
    under their layer as every arch's are."""
    from repro.graphs.csr import Graph

    cfg = get_config("ample-sage-pool", reduced=True)
    base = _graph(n=300, dim=cfg.d_model)
    cut = np.isin(np.repeat(np.arange(300), base.degrees), [3, 50, 299])
    deg = np.bincount(np.repeat(np.arange(300), base.degrees)[~cut], minlength=300)
    g = Graph(indptr=np.concatenate([[0], np.cumsum(deg)]), indices=base.indices[~cut],
              num_nodes=300, features=base.features)
    empty = int((g.degrees == 0).sum())
    assert empty >= 3
    eng = GNNServeEngine(cfg, key=jax.random.PRNGKey(0),
                         union_node_bucket=128, union_edge_bucket=512)
    eng.infer(g, g.features)  # warm
    recorder.clear()
    r = eng.infer(g, g.features)
    assert r.cache_hit
    spans = recorder.spans()
    by_sid = {s.sid: s for s in spans}
    ages = [s for s in spans if s.name == "age"]
    assert len(ages) == 2
    for age in ages:
        assert age.args["op"] == "max" and age.args["mode"] == "sum"
        assert age.args["empty_rows"] == empty
        assert 0 < age.args["rows"] <= age.args["window_rows"]
        assert by_sid[age.parent].name == "layer"
    layers = sorted(s.args["index"] for s in spans if s.name == "layer")
    assert layers == [0, 1, 2]  # two GNN layers, then the head
