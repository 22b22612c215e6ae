"""The program-span reduction: self times, launch-to-device linking and the
launch count, on hand-made events and on a chip trace of one request."""
import gzip
import json
from pathlib import Path

import pytest

from bench import spans, trace

DATA = Path(__file__).parent / "data"
RECORDED = DATA / "pubmed-gat-spans.planes.json.gz"
L = spans.LAUNCH
AGE = ("aggregate_edge_tiles", "segment_max_edge_tiles", "edge_segment_sum_tiles")


def _events(offset=0):
    """One request: plan, then execute > layer > {fte, age}; a jitted call
    that runs another program inside its dispatch; one launch after it."""
    o = offset
    host = [("ample.request", o + 0, o + 1000), ("ample.plan", o + 10, o + 100),
            ("ample.execute", o + 200, o + 900), ("ample.layer", o + 250, o + 800),
            ("ample.fte", o + 260, o + 400), ("ample.age", o + 400, o + 700),
            ("PjitFunction(dot)", o + 270, o + 300), (L, o + 280, o + 282),
            ("PjitFunction(_broadcast_arrays)", o + 410, o + 450),
            (L, o + 415, o + 416), (L, o + 430, o + 431),
            ("PjitFunction(aggregate_edge_tiles)", o + 500, o + 520), (L, o + 505, o + 506),
            ("PjitFunction(add)", o + 1100, o + 1110), (L, o + 1102, o + 1103)]
    device = [("jit_dot(11)", o + 290, o + 330), ("jit_convert_element_type(2)", o + 420, o + 425),
              ("jit__broadcast_arrays(3)", o + 432, o + 440),
              ("jit_aggregate_edge_tiles(4)", o + 510, o + 690), ("jit_add(5)", o + 1105, o + 1108)]
    return host, device


def test_self_times_linking_and_launches():
    host, device = _events()
    out = spans.reduce_events({"host": {"python#0": host}, "device": {"/device:TPU:0": device}})
    assert out["requests"] == 1
    ms = 1e-6  # one ns in ms
    assert out["host_ms"]["request"] == pytest.approx(1000 * ms)
    assert out["self_ms"]["request"] == pytest.approx((1000 - 90 - 700) * ms)
    assert out["self_ms"]["execute"] == pytest.approx((700 - 550) * ms)
    assert out["self_ms"]["layer"] == pytest.approx((550 - 140 - 300) * ms)
    assert out["self_ms"]["fte"] == out["host_ms"]["fte"] == pytest.approx(140 * ms)
    # each device program goes to the innermost span open at its launch
    assert out["device_ms"]["fte"] == pytest.approx(40 * ms)
    assert out["device_ms"]["age"] == pytest.approx((5 + 8 + 180) * ms)
    assert out["device_ms"]["none"] == pytest.approx(3 * ms)
    assert out["program_ms"]["age/convert_element_type"] == pytest.approx(5 * ms)
    assert out["launches"] == 4  # the fifth launch is outside the request
    assert (out["paired"], out["renamed"]) == (5, 1)
    assert out["unpaired_launches"] == out["unpaired_device"] == 0


def test_means_over_requests_and_unpaired_sides():
    a, da = _events()
    b, db = _events(offset=2000)
    out = spans.reduce_events({"host": {"t": a + b}, "device": {"/device:TPU:0": da + db[:-1]}})
    assert out["requests"] == 2
    assert out["host_ms"]["request"] == pytest.approx(1000e-6)
    assert out["launches"] == 4
    assert (out["paired"], out["unpaired_launches"], out["unpaired_device"]) == (9, 1, 0)


def test_no_program_spans_reads_nothing():
    host = [("bench.infer", 0, 100), ("PjitFunction(dot)", 10, 20), (L, 12, 13)]
    out = spans.reduce_events({"host": {"t": host},
                               "device": {"/device:TPU:0": [("jit_dot(1)", 15, 30)]}})
    assert out["requests"] == 0 and out["launches"] is None
    assert out["host_ms"] == out["device_ms"] == {}


def _recorded():
    rec = json.loads(gzip.decompress(RECORDED.read_bytes()))
    planes = {p: {l: [tuple(e) for e in evs] for l, evs in lines.items()}
              for p, lines in rec["planes"].items()}
    events = {k: {l: [tuple(e) for e in evs] for l, evs in v.items()}
              for k, v in rec["events"].items()}
    return rec, planes, events


def test_recorded_request_keeps_reduce_planes_and_names_idle():
    """A chip trace with program spans: every key ``reduce_planes`` gave
    before the span reduction existed reads the same, and almost no idle
    time is left to the benchmark's bare ``infer``."""
    rec, planes, _ = _recorded()
    out = trace.reduce_planes(planes)
    want = rec["expect"]
    assert set(out) == set(want)
    for key in ("window_s", "busy_s", "devices"):
        assert out[key] == pytest.approx(want[key], rel=1e-12)
    assert out["modules"] == pytest.approx(want["modules"], rel=1e-12)
    for part in ("device_ops", "idle_gaps"):
        assert [k for k, _ in out["breakdown"][part]] == [k for k, _ in want["breakdown"][part]]
        assert [v for _, v in out["breakdown"][part]] == pytest.approx(
            [v for _, v in want["breakdown"][part]], rel=1e-12)
    idle = dict(out["breakdown"]["idle_gaps"])
    assert idle.get("infer", 0.0) < 0.05 * (out["window_s"] - out["busy_s"])
    assert max(idle, key=idle.get) == "infer>ample.pad"


def test_recorded_request_spans():
    """The chip trace's request: each span's time, and every device program
    linked to the span that launched it."""
    rec, _, events = _recorded()
    out = spans.reduce_events(events)
    want = rec["expect_spans"]
    for key in ("requests", "launches", "paired", "renamed", "unpaired_launches",
                "unpaired_device"):
        assert out[key] == want[key], key
    for table in ("host_ms", "self_ms", "device_ms", "program_ms"):
        assert out[table] == pytest.approx(want[table], rel=1e-12), table
    assert out["launches"] == 225 and out["unpaired_device"] == 0
    # the AGE programs all ran from inside age spans, as many ms as the
    # device trace's module time of the request
    age = {k: v for k, v in out["program_ms"].items() if k.split("/")[1] in AGE}
    assert all(k.startswith("age/") for k in age) and age
    modules = rec["expect"]["modules"]
    assert sum(age.values()) == pytest.approx(
        1e3 * sum(modules[f"jit_{p}"] for p in AGE), rel=1e-9)
    # the linked device time is the request's whole program time
    assert sum(out["device_ms"].values()) == pytest.approx(
        1e3 * sum(modules.values()), rel=1e-9)
    h = out["host_ms"]
    assert h["request"] == pytest.approx(h["validate"] + h["plan"] + h["pad"] + h["execute"]
                                         + out["self_ms"]["request"])
