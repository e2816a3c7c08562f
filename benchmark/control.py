#!/usr/bin/env python3
"""The control of a cell's comparison: the plain reference put in the
program's place, in the precision below the one the configuration
states, held to the same checks. It has to come out not correct.

    python3 benchmark/control.py --workload <cell> --seed <n> [<n> ...]

Prints, per seed, one JSON line: the checks (each number with its
limit) and ``correct`` as a run would report it. Runs on the host
alone: the control needs no chip. Exits 1 if any seed's control came
out correct.
"""

import argparse
import json
import sys

import run


def control(workload, seed, overrides=None):
    """``{"seed", "correct", "checks"}`` of one seed's control."""
    cs = run.cell_spec(run.load_json(run.ROOT, "BENCHMARK.json"), workload)
    config = dict(cs["config"], **(overrides or {}))
    driver = run.load_module("drivers", cs["traffic"]["driver"])
    attempted, failed, checks = driver.control(config, cs["traffic"], seed)
    correct = failed == 0 and attempted > 0 and all(
        v <= lim for v, lim in checks.values())
    return {"seed": seed, "correct": correct,
            "checks": {k: {"value": v, "limit": lim}
                       for k, (v, lim) in checks.items()}}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, run.HERE)
    sys.path.insert(0, run.ROOT)
    passed = 0
    for seed in args.seed:
        out = control(args.workload, seed)
        passed += out["correct"]
        print(json.dumps(out), flush=True)
    return 1 if passed else 0


if __name__ == "__main__":
    sys.exit(main())
