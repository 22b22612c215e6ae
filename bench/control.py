"""The controls of a cell's comparison, which it must catch.

    python3 bench/control.py --workload <name> --seeds 1 2 3 [--program --seconds 30]

The configuration states float32 for Degree-Quant's protected rows and int8
for the rest. Two controls put the reference, computed one precision lower,
in the program's place for the answers a window of the cell checks, at the
cell's own sizes, and hand them to the same comparison as a run:

- ``int4``: int8 rows stepped down to int4 and float rows to bfloat16;
- ``bf16``: float rows stepped down to bfloat16 alone, the int8 rows as
  stated (the step the int8 rows' own error would hide from ``rms_err``).

With ``--program`` it instead runs the cell itself, window and all, with the
program's float32 products left at JAX's default precision (one bfloat16
pass on a TPU): the program's own lower-precision path. Each seed prints the
numbers compared beside their limits and whether the comparison caught it
(it must). The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
from typing import Any, Dict, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

CONTROLS = ("int4", "bf16")


def _caught(checks: Dict[str, Dict[str, float]]) -> bool:
    return not all(c["value"] <= c["limit"] for c in checks.values())


def control_numbers(workload: str, seed: int, *, spec: Optional[Dict[str, Any]] = None,
                    registry=None) -> Dict[str, Any]:
    """Each control's checks on one seed's answers, and whether it was caught."""
    from bench import harness, refcore
    from bench.registry import Registry

    spec = harness.load_spec() if spec is None else spec
    registry = registry or Registry()
    cell, kind, limits = harness.make_cell(spec, registry, workload, seed)
    runner = kind.Traffic(cell)
    runner.setup_data()
    params = harness.host_params(cell.params)
    ref = registry.reference(cell.config["arch"]).forward
    stated = refcore.calibrate(ref, *cell.first_request, params,
                               harness.stated_precision(cell.config))
    lower = {
        "int4": dataclasses.replace(stated, int_bits=4, float_rows_bf16=True, scales=None),
        "bf16": dataclasses.replace(stated, float_rows_bf16=True),
    }
    items = runner.answers()
    out = {"workload": workload, "seed": seed}
    for name in CONTROLS:
        for it in items:
            it.y = ref(it.graph, it.x, params, it.rows, lower[name])
        cell.checks = items
        checks = harness.judge(harness.check_answers(cell, params, stated),
                               limits["limits"], 0)
        out[name] = {"caught": _caught(checks), "checks": checks}
    out["caught"] = all(out[n]["caught"] for n in CONTROLS)
    return out


def program_numbers(workload: str, seed: int, seconds: float, **kw) -> Dict[str, Any]:
    """A run of the cell with JAX's default matmul precision."""
    from bench import harness

    res = harness.run_cell(workload, seed, seconds, False, matmul_precision=None, **kw)
    return {"workload": workload, "seed": seed, "control": "program_default_precision",
            "caught": _caught(res["checks"]), "checks": res["checks"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--program", action="store_true",
                    help="run the cell at JAX's default matmul precision")
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    ok = True
    for s in args.seeds:
        if args.program:
            out = program_numbers(args.workload, s, args.seconds)
        else:
            out = control_numbers(args.workload, s)
        ok &= out["caught"]
        print(json.dumps(out), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
