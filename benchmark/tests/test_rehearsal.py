"""A CPU rehearsal of every cell in ``BENCHMARK.json`` at a tiny size:
the result line has the contract's shape and names the device, and on
the CPU no device metric is reported under its name. The command itself
refuses to run without a TPU, and without the engine beside it."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from test_faults import rehearse

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
SPEC = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELLS = [w["name"] for w in SPEC["workloads"]]


def _names(section, cell):
    return sorted(m["name"] for m in SPEC[section]
                  if cell in m.get("workloads", [cell]))


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_line(cell, trace):
    chips = {w["name"]: w["chips"] for w in SPEC["workloads"]}[cell]
    out = rehearse(cell, "none", 12_345_678_901, trace)
    assert list(out)[:3] == ["correct", "attempted", "failed"]
    assert list(out)[-1] == "checks"
    assert out["correct"] is True and out["attempted"] > 0
    assert out["failed"] == 0
    dev = out["device"]
    assert dev["platform"] == "cpu" and dev["count"] == chips
    assert {"kind", "memory_peak_bytes"} <= set(dev)
    # the CPU reports no device metric, only what it did not measure
    assert out["metrics"] == {}
    assert "busy_s" not in dev and "breakdown" not in out
    section = "per_layer" if trace else "end_to_end"
    assert out["not_measured"] == _names(section, cell)
    for c in out["checks"].values():
        assert set(c) == {"value", "limit"} and c["value"] <= c["limit"]


def _cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable] + SPEC["command"][1:] + [
            "--workload", CELLS[0], "--seed", "1", "--seconds", "1",
            "--trace", "0"],
        cwd=cwd, capture_output=True, text=True, env=env, timeout=300)


def test_command_refuses_without_a_tpu():
    proc = _cli(ROOT)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_command_refuses_without_the_engine(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for p in SPEC["paths"]:
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p)
    proc = _cli(tmp_path, {"PYTHONPATH": ""})
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
