"""A configuration, a traffic mix and a metric are found by name: new ones
laid down in a temporary directory run without an edit to any file."""
import json

from bench import harness
from bench.tests import tiny


def test_new_config_mix_and_metric_by_name(tmp_path):
    spec = tiny.make(tmp_path)
    (tmp_path / "metrics").mkdir()
    (tmp_path / "metrics" / "requests_seen.py").write_text(
        "def read(ctx):\n    return len(ctx['records'])\n")
    spec["end_to_end"].append({"name": "requests_seen", "unit": "requests",
                               "better": "higher", "bound": 0.1, "source": "host_clock"})
    spec["workloads"] = [w for w in spec["workloads"] if w["name"] == "tiny-gcn-whole"]
    reg = tiny.registry(tmp_path)
    assert reg.config("tiny-gcn")["model"]["d_model"] == tiny.MODEL["d_model"]
    assert reg.traffic("tiny-whole")["kind"] == "whole_closed"
    res = harness.run_cell("tiny-gcn-whole", 3, 0.5, False, spec=spec, registry=reg,
                           compile_cache=False)
    assert res["correct"], res["checks"]
    m = res["metrics"]
    assert m["requests_seen"]["value"] == res["attempted"] >= 1
    assert {"setup_s", "graph_ms"} <= set(m)
    assert list(res)[-1] == "checks"
    json.dumps(res)


def test_same_seed_same_inputs(tmp_path):
    spec = tiny.make(tmp_path)
    reg = tiny.registry(tmp_path)
    a, _, _ = harness.make_cell(spec, reg, "tiny-gcn-whole", 2**33 + 1)
    b, _, _ = harness.make_cell(spec, reg, "tiny-gcn-whole", 2**33 + 1)
    c, _, _ = harness.make_cell(spec, reg, "tiny-gcn-whole", 2**33 + 2)
    wa = a.params["layers"][0]["w"]
    assert (wa == b.params["layers"][0]["w"]).all()
    assert not (wa == c.params["layers"][0]["w"]).all()
    assert (a.features(8) == b.features(8)).all()
