#!/usr/bin/env python3
"""Run one cell of the benchmark once, on the chips of this machine.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json`` at the root
of the checkout. Its configuration is ``benchmark/configs/<config>.json``,
its traffic ``benchmark/traffic/<traffic>.json``; the traffic names the
driver (``benchmark/drivers/<driver>.py``) that builds the data from the
seed, warms the cell's programs, runs the window and checks what the
window produced against a plain reference. Each per-layer metric is
read by ``benchmark/metrics/<metric>.py``. Nothing here names a cell,
a configuration or a metric: a new one is a new file.

A run: (1) checks that JAX sees a TPU of a kind in ``peaks.json`` and as
many chips as the cell asks for, and exits 3 otherwise; (2) builds the
data and warms up, which ``setup_s`` counts from the start of the
process; (3) measures for ``--seconds`` seconds, under the profiler
when ``--trace 1``; (4) reads the peak device memory, frees the device
state and compares the window's results with the reference; (5) prints
every number compared with its limit on standard error, and as the
last line of standard output one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics,
or with ``--trace 1`` its per-layer ones), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``.

The compilation cache is ``<checkout>/.jax_cache``, a fixed path: only
the first run of a cell in a checkout compiles.
"""

import argparse
import importlib.util
import json
import os
import sys
import tempfile
import time


def _process_start():
    """Seconds since the epoch at which this process started: its start
    in clock ticks since boot (``/proc/self/stat``) against the uptime
    now, so the interpreter's own start-up counts as set-up too."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return time.time() - (uptime - ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return time.time()


START = _process_start()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class NoChip(RuntimeError):
    """JAX finds no TPU of a known kind, or too few of them."""


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(kind, name):
    """``benchmark/<kind>/<name>.py`` as a module."""
    path = os.path.join(HERE, kind, f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name}".replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_spec(spec, workload):
    """What one cell needs from ``BENCHMARK.json``: the cell, its
    configuration and traffic files, and the metrics it reports."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(known: {sorted(cells)})")
    cell = cells[workload]

    def mine(metrics):
        return [m for m in metrics
                if workload in m.get("workloads", [workload])]

    return {
        "cell": cell,
        "config": load_json(HERE, "configs", f"{cell['config']}.json"),
        "traffic": load_json(HERE, "traffic", f"{cell['traffic']}.json"),
        "end_to_end": mine(spec["end_to_end"]),
        "per_layer": mine(spec["per_layer"]),
    }


def peaks_for(kind):
    """The published peaks of a device kind; an unknown kind is an
    error, never a default."""
    peaks = load_json(HERE, "peaks.json")
    if kind not in peaks:
        raise NoChip(f"no published peaks for device kind {kind!r} "
                     f"(known: {sorted(peaks)})")
    return peaks[kind]


def chips(n, require_tpu=True):
    """The first ``n`` devices; without a TPU (or with fewer than ``n``
    chips) :class:`NoChip`, unless ``require_tpu`` is off for a CPU
    rehearsal."""
    import jax

    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX sees {devices[0].platform}")
    if len(devices) < n:
        raise NoChip(f"the cell asks for {n} chips, JAX sees "
                     f"{len(devices)}")
    if require_tpu:
        peaks_for(devices[0].device_kind)
    return devices[:n]


class CompileWatch:
    """Counts XLA compiles and persistent-cache loads as JAX reports
    them, so a compile inside the window shows. JAX times a program
    loaded from the cache as a compile too; :meth:`counts` leaves those
    out of the compiles."""

    def __init__(self):
        import jax.monitoring as mon

        self.compiles = 0
        self.cache_loads = 0

        def on_duration(event, duration, **kw):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compiles += 1

        def on_event(event, **kw):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_loads += 1

        mon.register_event_duration_secs_listener(on_duration)
        mon.register_event_listener(on_event)

    def counts(self):
        return self.compiles - self.cache_loads, self.cache_loads


class Reading:
    """What a per-layer metric reader is given: the program's counters
    and spans over the traced window (``delta``, a telemetry delta), the
    reduced device trace (``trace``, a ``TraceSummary``, its operator
    scopes in ``trace.scopes``), the units of work the window completed
    (``units``: whole passes), the least HBM bytes one unit must move
    (``least_bytes``) and the least bytes one chip must send to other
    chips per unit (``least_ici_bytes``), each None where the driver
    has no such function, the programs loaded from the persistent
    compilation cache in the window (``cache_loads``), the chips used
    and the device's published ``peaks``."""

    def __init__(self, delta, trace, units, least_bytes, n_chips, peaks,
                 least_ici_bytes=None, cache_loads=None):
        self.delta = delta
        self.trace = trace
        self.units = units
        self.least_bytes = least_bytes
        self.least_ici_bytes = least_ici_bytes
        self.cache_loads = cache_loads
        self.chips = n_chips
        self.peaks = peaks

    def counter(self, name):
        """The change of a program counter, summed over its labels."""
        return sum(d["value"] for d in self.delta.values()
                   if d["name"] == name and d["type"] == "counter")

    def span(self, name):
        """``(count, seconds)`` of a program span over the window."""
        n, s = 0, 0.0
        for d in self.delta.values():
            if (d["name"] == "tracing.span_seconds"
                    and d["labels"].get("name") == name):
                n += d["count"]
                s += d["sum"]
        return n, s


def read_per_layer(names, reading):
    """``{metric: value}`` of each reader that found something."""
    out = {}
    for name in names:
        value = load_module("metrics", name).read(reading)
        if value is not None:
            out[name] = float(value)
    return out


def measure(driver, state, seconds, trace, profile=True):
    """The window, under the profiler when ``trace`` (and ``profile``:
    a CPU rehearsal has no device trace to read): the driver's window
    result, the telemetry delta and the trace summary."""
    import jax

    from cylon_tpu import telemetry

    import trace_reduce
    import trace_scopes

    if not (trace and profile):
        before = telemetry.snapshot()
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
            win = driver.window(state, seconds)
        return win, telemetry.delta(before), None
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with tempfile.TemporaryDirectory(prefix="bench_trace_") as logdir:
        before = telemetry.snapshot()
        jax.profiler.start_trace(logdir, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
                win = driver.window(state, seconds)
        finally:
            jax.profiler.stop_trace()
        delta = telemetry.delta(before)
        spans = {d["labels"].get("name") for d in delta.values()
                 if d["name"] == "tracing.span_seconds"}
        t0 = time.perf_counter()
        summary = trace_reduce.reduce_file(trace_reduce.find_trace(logdir),
                                           span_names=spans)
        log(f"trace reduced in {time.perf_counter() - t0:.3f} s; device "
            f"time by operator scope:\n"
            + trace_scopes.scope_table(summary.scopes, passes=win["units"]))
    return win, delta, summary


def run(workload, seed, seconds, trace, require_tpu=True, overrides=None):
    """One run of a cell; returns the result's dict. ``overrides``
    replaces configuration keys (the CPU rehearsal's tiny sizes)."""
    sys.path.insert(0, HERE)
    sys.path.insert(0, ROOT)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT,
                                                           ".jax_cache")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    cs = cell_spec(load_json(ROOT, "BENCHMARK.json"), workload)
    cell, config = cs["cell"], dict(cs["config"], **(overrides or {}))
    devices = chips(cell["chips"], require_tpu)
    kind = devices[0].device_kind
    peaks = peaks_for(kind) if require_tpu else None

    import jax

    import cylon_tpu  # noqa: F401  (x64 and the cache directory)

    # every program of the cell is cached, and none is ever evicted:
    # the directory is this checkout's own
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)
    watch = CompileWatch()
    driver = load_module("drivers", cs["traffic"]["driver"])
    state = driver.setup(config, cs["traffic"], seed, devices)
    setup_s = time.time() - START
    in_setup = watch.counts()
    log(f"setup_s={setup_s:.3f}; in set-up {in_setup[0]} XLA compiles, "
        f"{in_setup[1]} programs loaded from the compilation cache")

    win, delta, summary = measure(driver, state, seconds, trace,
                                  profile=require_tpu)
    compiled, loaded = (a - b for a, b in zip(watch.counts(), in_setup))
    log(f"window: {win['units']} units in {win['elapsed']:.3f} s (each "
        f"{', '.join(f'{t:.3f}' for t in win['unit_s'])} s); in the "
        f"window {compiled} XLA compiles, {loaded} programs loaded from "
        f"the compilation cache")
    stats = [d.memory_stats() or {} for d in devices]
    peak = max(s.get("peak_bytes_in_use", 0) for s in stats)
    least = {name: getattr(driver, name)(state)
             if trace and hasattr(driver, name) else None
             for name in ("least_bytes", "least_ici_bytes")}

    attempted, failed, checks = driver.check(state, win)
    correct = failed == 0 and attempted > 0 and all(
        v <= lim for v, lim in checks.values())

    if trace:
        names = [m["name"] for m in cs["per_layer"]]
        reading = Reading(delta, summary, win["units"],
                          least["least_bytes"], len(devices), peaks,
                          least["least_ici_bytes"], loaded)
        metrics = read_per_layer(names, reading)
        units = {m["name"]: m["unit"] for m in cs["per_layer"]}
    else:
        values = dict(driver.end_to_end(state, win), setup_s=setup_s,
                      peak_hbm_gb=peak / 1e9)
        metrics = {m["name"]: values[m["name"]] for m in cs["end_to_end"]}
        units = {m["name"]: m["unit"] for m in cs["end_to_end"]}
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": peak}
    out = {"correct": correct, "attempted": attempted, "failed": failed}
    if require_tpu:
        out["metrics"] = {k: {"value": v, "unit": units[k]}
                          for k, v in metrics.items()}
    else:
        # a CPU rehearsal: no device metric is reported under its name
        out["metrics"] = {}
        out["not_measured"] = sorted(units)
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
    out["device"] = device
    if summary is not None:
        out["breakdown"] = summary.breakdown()
    out["checks"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in checks.items()}
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        out = run(args.workload, args.seed, args.seconds, bool(args.trace))
    except NoChip as e:
        log(f"benchmark: {e}")
        return 3
    for name, c in out["checks"].items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
