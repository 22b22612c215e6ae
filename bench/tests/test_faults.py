"""A run whose timed path is broken underneath reports correct false: an
answer altered where it is produced, and the program's float32 products
left at bfloat16 input precision. The unbroken run of the same cell passes."""
import numpy as np
import pytest

from bench import harness
from bench.tests import tiny


def _run(tmp_path, cell):
    spec = tiny.make(tmp_path)
    return harness.run_cell(cell, 2**32 + 9, 0.5, False, spec=spec,
                            registry=tiny.registry(tmp_path), compile_cache=False)


@pytest.mark.parametrize("cell", ["tiny-gcn-whole", "tiny-gat-whole"])
def test_sound_run_is_correct(tmp_path, cell):
    res = _run(tmp_path, cell)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0


@pytest.mark.parametrize("cell", ["tiny-gcn-whole", "tiny-gat-whole"])
def test_altered_answer_is_caught(tmp_path, monkeypatch, cell):
    from repro.serve import gnn_engine

    real = gnn_engine.GNNServeEngine._run

    def altered(self, *a, **k):
        y, ms = real(self, *a, **k)
        return np.roll(y, 1, axis=0), ms  # each row gets its neighbour's answer

    monkeypatch.setattr(gnn_engine.GNNServeEngine, "_run", altered)
    assert not _run(tmp_path, cell)["correct"]


@pytest.mark.parametrize("cell", ["tiny-gcn-whole", "tiny-gat-whole"])
def test_float_rows_in_bfloat16_are_caught(tmp_path, monkeypatch, cell):
    """The float FTE stream with its inputs rounded to bfloat16, as the TPU's
    default precision rounds them."""
    import jax.numpy as jnp

    from repro.core import transformation

    real = transformation.transform_dense

    def bf16(h, w, *a, **k):
        def r(v):
            return v.astype(jnp.bfloat16).astype(jnp.float32)

        return real(r(h), r(w), *a, **k)

    monkeypatch.setattr(transformation, "transform_dense", bf16)
    checks = _run(tmp_path, cell)["checks"]
    assert checks["float_err"]["value"] > checks["float_err"]["limit"]
    assert checks["rms_err"]["value"] <= checks["rms_err"]["limit"]
