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
