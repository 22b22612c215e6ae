"""The controls (the reference one precision lower in the program's place)
must fail the comparison that sound runs pass: int4 and bfloat16 through
``rms_err``, bfloat16 float rows alone through ``float_err``."""
import pytest

from bench import control
from bench.tests import tiny


@pytest.mark.parametrize("cell", ["tiny-gcn-whole", "tiny-gat-whole"])
@pytest.mark.parametrize("name", control.CONTROLS)
def test_control_is_caught(tmp_path, cell, name):
    spec = tiny.make(tmp_path)
    for seed in (1, 2**31 + 5):
        out = control.control_numbers(cell, seed, spec=spec, registry=tiny.registry(tmp_path))
        assert out[name]["caught"], out
    if name == "bf16":
        checks = out[name]["checks"]
        assert checks["float_err"]["value"] > checks["float_err"]["limit"]
        assert checks["rms_err"]["value"] <= checks["rms_err"]["limit"]
