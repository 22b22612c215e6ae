"""The trace reduction: busy union, idle share, program time, gap labels."""
import gzip
import json
from pathlib import Path

import pytest

from bench import trace

DATA = Path(__file__).parent / "data"


def _planes(thread="python#0"):
    dev = {
        trace.OPS_LINE: [("%while = (s32[]{:T(128)}, f32[8]{0})", 100, 250),
                         ("%fusion.1 = f32[8]{0:T(8)} fusion(f32[8] %x)", 120, 200),
                         ("scatter", 400, 500), ("late", 950, 1200)],
        trace.MODULES_LINE: [("jit_aggregate_edge_tiles(12)", 100, 260),
                             ("jit_dot(3)", 400, 500)],
    }
    host = {thread: [("bench.infer", 50, 1000), ("bench.step", 600, 900),
                         ("PjitFunction(x)", 60, 90), ("PjitFunction(y)", 700, 800)]}
    return {"/device:TPU:0": dev, "/host:CPU": host, "/device:TPU:0 SparseCore": {}}


@pytest.mark.parametrize("thread", ["python#0", "python3#7"])
def test_busy_idle_and_modules(thread):
    out = trace.reduce_planes(_planes(thread))
    # window = benchmark spans 50..1000; busy = [100,250] + [400,500] + [950,1000]
    assert out["window_s"] == pytest.approx(950e-9)
    assert out["busy_s"] == pytest.approx(300e-9)
    assert out["modules"]["jit_aggregate_edge_tiles"] == pytest.approx(160e-9)
    assert out["modules"]["jit_dot"] == pytest.approx(100e-9)
    ops = dict(out["breakdown"]["device_ops"])
    # self time: the loop less the fusion it holds; names cut to the shape
    assert ops["%while (s32[], f32[8])"] == pytest.approx(70e-9)
    assert ops["%fusion.1 f32[8] fusion(f32[8] %x)"] == pytest.approx(80e-9)
    assert ops["late"] == pytest.approx(50e-9)  # clipped to the window
    idle = dict(out["breakdown"]["idle_gaps"])
    # gaps 50-100 (middle 75, in PjitFunction(x)), 250-400 (in infer only)
    # and 500-950 (middle 725: in bench.step and PjitFunction(y))
    assert idle["infer>PjitFunction(x)"] == pytest.approx(50e-9)
    assert idle["infer"] == pytest.approx(150e-9)
    assert idle["step>PjitFunction(y)"] == pytest.approx(450e-9)


def test_no_device_plane_is_an_error():
    planes = _planes()
    del planes["/device:TPU:0"]
    with pytest.raises(ValueError):
        trace.reduce_planes(planes)


def test_merge_intervals():
    assert trace.merge_intervals([(5, 6), (1, 3), (2, 4), (4, 5)]) == [(1, 6)]


RECORDED = sorted(DATA.glob("*.planes.json.gz"))


@pytest.mark.parametrize("path", RECORDED, ids=[p.name for p in RECORDED])
def test_recorded_trace(path):
    rec = json.loads(gzip.decompress(path.read_bytes()))
    planes = {p: {l: [tuple(e) for e in evs] for l, evs in lines.items()}
              for p, lines in rec["planes"].items()}
    out = trace.reduce_planes(planes)
    want = rec["expect"]
    assert out["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    assert out["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert 0 < out["busy_s"] <= out["window_s"]
    for name, sec in want["modules"].items():
        assert out["modules"][name] == pytest.approx(sec, rel=1e-9)
