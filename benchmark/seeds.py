#!/usr/bin/env python3
"""Check a cell's timed path on many seeds in one process.

    python3 benchmark/seeds.py --workload <cell> --seed <n> [<n> ...]

For each seed, as a run makes them: the driver's set-up from that seed,
one pass of its window (the timed path at the cell's own size) and its
check against the plain reference. One JSON line a seed: ``correct``,
the passes checked and each number compared with its limit. The
process's start-up and compiles are paid once, and after the first
seed the driver's warm passes (``warm_passes``) are skipped, since its
programs are warm: a dozen seeds cost far less chip time than a dozen
runs. No metric is measured here.

Exits 3 without the chips the cell asks for, and 1 if any seed came out
not correct. ``--rehearse`` runs on the CPU at the driver's tiny size.
"""

import argparse
import json
import os
import sys
import time

import run


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, nargs="+", required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    sys.path[:0] = [run.HERE, run.ROOT]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(run.ROOT,
                                                           ".jax_cache")
    cs = run.cell_spec(run.load_json(run.ROOT, "BENCHMARK.json"),
                       args.workload)
    try:
        devices = run.chips(cs["cell"]["chips"], not args.rehearse)
    except run.NoChip as e:
        run.log(f"seeds: {e}")
        return 3

    import jax

    import cylon_tpu  # noqa: F401  (x64 and the cache directory)

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)
    driver = run.load_module("drivers", cs["traffic"]["driver"])
    config = dict(cs["config"], **(driver.TINY if args.rehearse else {}))
    traffic = dict(cs["traffic"])
    wrong = 0
    for seed in args.seed:
        t0 = time.perf_counter()
        state = driver.setup(config, traffic, seed, devices)
        traffic["warm_passes"] = 0
        win = driver.window(state, 1e-3)
        attempted, failed, checks = driver.check(state, win)
        del state
        correct = failed == 0 and attempted > 0 and all(
            v <= lim for v, lim in checks.values())
        wrong += not correct
        print(json.dumps({
            "seed": seed, "correct": correct, "attempted": attempted,
            "failed": failed, "seconds": time.perf_counter() - t0,
            "checks": {k: {"value": v, "limit": lim}
                       for k, (v, lim) in checks.items()}}), flush=True)
    return 1 if wrong else 0


if __name__ == "__main__":
    sys.exit(main())
