"""The control of each cell's comparison comes out not correct: the
plain reference put in the program's place, in float32 where the
configuration states float64. At the tiny sizes of a test run; the
cells' own sizes are run with ``control.py``."""

import pytest

import control
from fault_runner import TINY

SPEC_CELLS = ["tpch-sf1.agg", "tpch-sf1.join"]


@pytest.mark.parametrize("seed", [3, 2**31 + 5, 9_876_543_210])
@pytest.mark.parametrize("cell", SPEC_CELLS)
def test_control_fails(cell, seed):
    out = control.control(cell, seed, overrides=TINY)
    assert out["correct"] is False
    err = out["checks"]["max_rel_err"]
    assert err["value"] > 10 * err["limit"]
