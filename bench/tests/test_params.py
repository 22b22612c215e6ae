"""Weights are drawn off int8 rounding ties: no quantized weight lies near the
midpoint between two steps of its column's scale, and nothing else moves."""
import numpy as np
import pytest

from bench import harness
from bench.tests import tiny


def _steps_from_midpoint(w):
    w = np.asarray(w, np.float64)
    t = w / (np.abs(w).max(axis=0) / 127)
    return np.abs(t - np.floor(t) - 0.5)


def test_off_ties_moves_only_entries_near_a_midpoint():
    w = np.array([[1.27, -0.3], [0.505, 0.9], [-0.2, -1.27], [0.50500001, 0.4]], np.float32)
    out = np.asarray(harness.off_ties(w))
    # 0.505 sits on the midpoint 50.5 of scale 0.01; 0.50500001 just above it
    assert not np.array_equal(out[1, 0], w[1, 0])
    assert not np.array_equal(out[3, 0], w[3, 0])
    keep = np.ones(w.shape, bool)
    keep[1, 0] = keep[3, 0] = False
    np.testing.assert_array_equal(out[keep], w[keep])
    np.testing.assert_array_equal(np.abs(out).max(axis=0), np.abs(w).max(axis=0))
    assert _steps_from_midpoint(out).min() >= harness.TIE_STEPS


@pytest.mark.parametrize("cell", ["tiny-gcn-whole", "tiny-gat-whole"])
def test_drawn_weights_lie_off_ties(tmp_path, cell):
    spec = tiny.make(tmp_path)
    reg = tiny.registry(tmp_path)
    w = harness.workload_entry(spec, cell)
    cfg = harness.model_config(reg.config(w["config"]))
    params = harness.make_params(cfg, 2**33 + 5)
    for lyr in params["layers"]:
        assert _steps_from_midpoint(lyr["w"]).min() >= harness.TIE_STEPS
        for k, v in lyr.items():
            assert np.isfinite(np.asarray(v)).all(), k
