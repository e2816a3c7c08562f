"""Drive one run of a cell on the CPU at a tiny size, optionally with
the engine's timed path broken underneath, and print the result line.

    python fault_runner.py <workload> <fault> <seed> [<trace>]

Faults (``none`` runs the engine as it is):

* ``altered``: an answer altered where it is produced: a TPC-H result
  frame's first float column is off by one part in a million.
* ``half``: half of the rows left out: every table loses its second
  half.

The run skips the harness's look for a chip (``require_tpu=False``);
everything else is the benchmark's own path.
"""

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [BENCH, os.path.dirname(BENCH)]

#: the tiny sizes of a CPU rehearsal, by the configuration's scale key
TINY = {"scale_factor": 0.01}


def tiny(config):
    return {k: v for k, v in TINY.items() if k in config}


def _altered():
    from cylon_tpu import frame

    to_pandas = frame.DataFrame.to_pandas

    def wrong_pandas(self, *a, **kw):
        df = to_pandas(self, *a, **kw)
        f = [c for c in df.columns if df[c].dtype.kind == "f"]
        if f:
            df[f[0]] = df[f[0]] * (1 + 1e-6)
        return df

    frame.DataFrame.to_pandas = wrong_pandas


def _half():
    from cylon_tpu import tpch

    ingest = tpch.ingest

    def half_ingest(data):
        return ingest({t: ({c: v[:max(len(v) // 2, 1)]
                            for c, v in cols.items()}
                           if isinstance(cols, dict) else cols)
                       for t, cols in data.items()})

    tpch.ingest = half_ingest


FAULTS = {"none": lambda: None, "altered": _altered, "half": _half}


def main(workload, fault, seed, trace=0):
    import run

    cs = run.cell_spec(run.load_json(run.ROOT, "BENCHMARK.json"), workload)
    import cylon_tpu  # noqa: F401

    FAULTS[fault]()
    out = run.run(workload, seed, 1.0, bool(trace), require_tpu=False,
                  overrides=tiny(cs["config"]))
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main(sys.argv[1], sys.argv[2], int(sys.argv[3]),
         int(sys.argv[4]) if len(sys.argv) > 4 else 0)
