"""``seeds.py`` drives each cell's timed path and check once a seed in
one process: on the CPU at the driver's tiny size every seed comes out
correct, and without a chip the command refuses."""

import json
import os
import subprocess
import sys

import pytest

from test_faults import ROOT, SPEC

SEEDS = ["5", str(2**31 + 11), "12345678901"]


def _seeds(workload, chips, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={chips}")
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "seeds.py"),
         "--workload", workload, "--seed", *SEEDS, *extra],
        capture_output=True, text=True, env=env, timeout=600)


@pytest.mark.parametrize("cell", SPEC["workloads"], ids=lambda w: w["name"])
def test_every_seed_is_checked(cell):
    proc = _seeds(cell["name"], cell["chips"], "--rehearse")
    assert proc.returncode == 0, proc.stderr[-4000:]
    lines = [json.loads(s) for s in proc.stdout.strip().splitlines()]
    assert [line["seed"] for line in lines] == [int(s) for s in SEEDS]
    assert all(line["correct"] and line["attempted"] for line in lines)


def test_refuses_without_a_chip():
    proc = _seeds(SPEC["workloads"][0]["name"], 1)
    assert proc.returncode == 3 and not proc.stdout.strip()
