"""A run with the engine's timed path broken underneath comes out not
correct, for each fault the cell can have; the same run unbroken comes
out correct. Each run is a CPU rehearsal at the tiny size of the cell's
driver (see ``fault_runner.py``), in a process of its own with as many
CPU devices as the cell asks for chips. The cases are every cell of
``BENCHMARK.json`` times its driver's ``FAULTS``."""

import json
import os
import re
import subprocess
import sys

import pytest

from fault_runner import driver_of

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
SPEC = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))

CASES = [(w["name"], fault, fault == "none")
         for w in SPEC["workloads"]
         for fault in ["none", *driver_of(w["name"]).FAULTS]]


def rehearse(workload, fault, seed, trace=0, root=ROOT, env_extra=None):
    """The result line of ``fault_runner.py`` of the checkout at
    ``root``, run on as many CPU devices as the cell has chips."""
    spec = json.load(open(os.path.join(root, "BENCHMARK.json")))
    chips = {w["name"]: w["chips"] for w in spec["workloads"]}[workload]
    flags = re.sub(r"--xla_force_host_platform_device_count=\S+", "",
                   os.environ.get("XLA_FLAGS", ""))
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env["XLA_FLAGS"] = (
        f"{flags} --xla_force_host_platform_device_count={chips}".strip())
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "tests",
                                      "fault_runner.py"),
         workload, fault, str(seed), str(trace)],
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
