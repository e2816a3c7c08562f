"""The control of each cell's comparison comes out not correct: the
plain reference put in the program's place, in the precision below the
one the configuration states (float32 where it states float64, int32
where int64). At the tiny sizes of the cells' drivers; the cells' own
sizes are run with ``control.py``."""

import json
import os

import pytest

import control
from fault_runner import driver_of

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SPEC_CELLS = [w["name"] for w in json.load(
    open(os.path.join(ROOT, "BENCHMARK.json")))["workloads"]]


@pytest.mark.parametrize("seed", [3, 2**31 + 5, 9_876_543_210])
@pytest.mark.parametrize("cell", SPEC_CELLS)
def test_control_fails(cell, seed):
    out = control.control(cell, seed, overrides=driver_of(cell).TINY)
    assert out["correct"] is False
    if "max_rel_err" in out["checks"]:
        err = out["checks"]["max_rel_err"]
        assert err["value"] > 10 * err["limit"]
    else:
        # an exact comparison: its limit is 0
        assert out["checks"]["wrong_keys"]["value"] > 0
