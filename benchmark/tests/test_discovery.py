"""A new configuration, traffic mix and per-layer metric are found by
their names in ``BENCHMARK.json``, with no edit to the harness: each is
one new file in a copy of the benchmark, and the copy runs the new cell
(a CPU rehearsal) and reads the new metric."""

import json
import os
import shutil
import subprocess
import sys
import textwrap

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)


def test_new_files_are_found_by_name(tmp_path):
    spec = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    b = tmp_path / "benchmark"
    (b / "configs" / "tpch-tiny.json").write_text(json.dumps(
        {"scale_factor": 0.005, "data_seed": 3, "rtol": 1e-9}))
    (b / "traffic" / "tpch-q6-stream.json").write_text(json.dumps(
        {"driver": "tpch_stream", "queries": ["q6"], "warm_passes": 1}))
    (b / "metrics" / "passes.tpch.py").write_text(textwrap.dedent('''
        def read(r):
            return r.units
        '''))
    spec["configs"].append({"name": "tpch-tiny", "source": "test",
                            "file": "benchmark/configs/tpch-tiny.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "tpch-tiny.q6", "config": "tpch-tiny",
                              "traffic": "tpch-q6-stream", "chips": 1,
                              "why": "test"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "tpch-sf1.agg" in m.get("workloads", []):
            m["workloads"].append("tpch-tiny.q6")
    spec["per_layer"].append({"name": "passes.tpch", "unit": "count",
                              "better": "higher",
                              "source": "program_counter", "layer": "test",
                              "moves": "stream_s",
                              "workloads": ["tpch-tiny.q6"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    script = textwrap.dedent(f'''
        import json, sys
        sys.path.insert(0, {str(b)!r})
        import run
        out = run.run("tpch-tiny.q6", 7, 0.5, True, require_tpu=False)
        assert out["correct"] and out["attempted"] > 0, out
        assert "passes.tpch" in out["not_measured"], out
        r = run.Reading({{}}, None, 3, 0, 1, None)
        assert run.read_per_layer(["passes.tpch"], r) == {{"passes.tpch": 3.0}}
        print("found")
        ''')
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    proc = subprocess.run([sys.executable, "-c", script], cwd=tmp_path,
                          capture_output=True, text=True, env=env,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert proc.stdout.strip().endswith("found")


TOY_DRIVER = '''
"""A toy driver: every chip sums its block of a vector, one psum a
pass; the rehearsal's size is its own configuration key."""
import time

import numpy as np

TINY = {"cells_per_chip": 64}
FAULTS = {}


def setup(config, traffic, seed, devices):
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(devices), ("w",))
    n = config["cells_per_chip"] * len(devices)
    host = np.random.default_rng(seed).integers(0, 100, n)
    x = jax.device_put(host, NamedSharding(mesh, P("w")))
    step = jax.jit(jax.shard_map(lambda b: jax.lax.psum(b.sum(), "w"),
                                 mesh=mesh, in_specs=P("w"), out_specs=P()))
    state = {"x": x, "step": step, "want": int(host.sum()), "n": n,
             "chips": len(devices)}
    step(x).block_until_ready()
    return state


def window(state, seconds):
    got, t0 = [], time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        got.append(int(state["step"](state["x"])))
    return {"units": len(got), "elapsed": time.perf_counter() - t0,
            "unit_s": [], "got": got}


def end_to_end(state, win):
    return {"stream_s": win["elapsed"] / win["units"]}


def check(state, win):
    wrong = sum(g != state["want"] for g in win["got"])
    return len(win["got"]), wrong, {
        "wrong_sums": (wrong, 0), "cells": (state["n"], 64 * state["chips"]),
        "chips": (state["chips"], 4)}
'''


def test_four_chip_cell_with_its_own_size_key(tmp_path):
    """A cell on four chips whose configuration sizes it by a key of its
    own, its driver and traffic new files: the rehearsal finds them by
    name, runs on four CPU devices and takes the driver's tiny size."""
    from test_faults import rehearse

    spec = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    b = tmp_path / "benchmark"
    (b / "configs" / "toy-x4.json").write_text(json.dumps(
        {"cells_per_chip": 1 << 20}))
    (b / "traffic" / "toy-sum.json").write_text(json.dumps(
        {"driver": "toy_sum"}))
    (b / "drivers" / "toy_sum.py").write_text(TOY_DRIVER)
    spec["configs"].append({"name": "toy-x4", "source": "test",
                            "file": "benchmark/configs/toy-x4.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "toy.x4", "config": "toy-x4",
                              "traffic": "toy-sum", "chips": 4,
                              "why": "test"})
    spec["end_to_end"][0]["workloads"].append("toy.x4")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    out = rehearse("toy.x4", "none", 2**31 + 3, root=str(tmp_path),
                   env_extra={"PYTHONPATH": ROOT})
    assert out["correct"] and out["attempted"] > 0, out
    assert out["device"]["count"] == 4
    assert out["checks"]["cells"]["value"] == 64 * 4
