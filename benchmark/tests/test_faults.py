"""A run with the engine's timed path broken underneath comes out not
correct, for each fault the cell can have; the same run unbroken comes
out correct. Each run is a CPU rehearsal at a tiny size (see
``fault_runner.py``), in a process of its own."""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))

CASES = [
    ("tpch-sf1.agg", "none", True),
    ("tpch-sf1.agg", "altered", False),
    ("tpch-sf1.agg", "half", False),
    ("tpch-sf1.join", "altered", False),
    ("tpch-sf1.join", "half", False),
]


def rehearse(workload, fault, seed, trace=0):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "fault_runner.py"), workload,
         fault, str(seed), str(trace)],
        capture_output=True, text=True, env=env, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("workload,fault,correct", CASES)
def test_fault_is_caught(workload, fault, correct):
    out = rehearse(workload, fault, 2**31 + 99)
    assert out["correct"] is correct, out["checks"]
    if not correct:
        assert out["failed"] > 0
        assert any(c["value"] > c["limit"] for c in out["checks"].values())
