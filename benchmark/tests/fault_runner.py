"""Drive one run of a cell on the CPU at a tiny size, optionally with
the engine's timed path broken underneath, and print the result line.

    python fault_runner.py <workload> <fault> <seed> [<trace>]

The tiny size is the cell's driver's ``TINY`` (configuration keys it
overrides) and the faults are its ``FAULTS`` (name -> a function that
breaks the engine); ``none`` runs the engine as it is. The process
needs as many devices as the cell asks for chips: on the CPU,
``--xla_force_host_platform_device_count`` in ``XLA_FLAGS`` gives them
(``test_faults.rehearse`` sets it).

The run skips the harness's look for a chip (``require_tpu=False``);
everything else is the benchmark's own path.
"""

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [BENCH, os.path.dirname(BENCH)]


def driver_of(workload):
    """The driver module of a cell, by the names in ``BENCHMARK.json``."""
    import run

    cs = run.cell_spec(run.load_json(run.ROOT, "BENCHMARK.json"), workload)
    return run.load_module("drivers", cs["traffic"]["driver"])


def main(workload, fault, seed, trace=0):
    import run

    driver = driver_of(workload)
    import cylon_tpu  # noqa: F401

    if fault != "none":
        driver.FAULTS[fault]()
    out = run.run(workload, seed, 1.0, bool(trace), require_tpu=False,
                  overrides=driver.TINY)
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main(sys.argv[1], sys.argv[2], int(sys.argv[3]),
         int(sys.argv[4]) if len(sys.argv) > 4 else 0)
