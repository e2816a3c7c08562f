"""Driver of a TPC-H query stream: one client runs the traffic's
queries back to back, closed loop, each through the engine's
whole-query compiled entry (``tpch.compiled(q)(frames)``) with its
result fetched to the host as pandas, as an analyst's report would.

The dataset is the configuration's (``data_seed``), the same for every
run, as dbgen's is for a scale factor: the engine's time on Q3 and Q5
depends on the data's values (on a TPU v5e two runs over one dataset
agree to 0.3%, runs over datasets of different seeds differ by up to
3%), so a seed that drew new data would change the work. The run's seed
orders the queries within each pass.

Configuration keys: ``scale_factor``, ``data_seed``, ``rtol`` (the
float tolerance the configuration guarantees). Traffic keys: ``queries``
(each a module in ``benchmark/queries/``), ``warm_passes``.
"""

import sys
import time
import traceback

import numpy as np

#: the configuration of a CPU rehearsal
TINY = {"scale_factor": 0.01}


def _query(name):
    import importlib

    return importlib.import_module(f"queries.{name}")


def _host(out):
    """A query's result as the client sees it."""
    if hasattr(out, "to_pandas"):
        return out.to_pandas().reset_index(drop=True)
    return float(np.asarray(out))


def setup(config, traffic, seed, devices):
    """Generate all eight tables at full width, move them to the chip
    and run every query ``warm_passes`` times (the first call compiles
    the query, the second its result-slicing program)."""
    from cylon_tpu import tpch

    import tpch_gen

    queries = [str(q) for q in np.random.default_rng(seed).permutation(
        traffic["queries"])]
    data = tpch_gen.generate(config["scale_factor"], config["data_seed"])
    state = {"queries": queries, "data": data, "rtol": config["rtol"],
             "frames": tpch.ingest(data),
             "runs": {q: tpch.compiled(q) for q in queries}}
    for _ in range(traffic["warm_passes"]):
        one_pass(state)
    return state


def one_pass(state):
    """Each query once, in order: ``{query: result or exception}`` and
    ``{query: seconds}``."""
    import jax

    out, secs = {}, {}
    for q in state["queries"]:
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation(f"bench.{q}"):
            try:
                out[q] = _host(state["runs"][q](state["frames"]))
            except Exception as e:  # counted as a failed answer
                traceback.print_exc()
                out[q] = e
        secs[q] = time.perf_counter() - t
    return out, secs


def window(state, seconds):
    """Whole passes until ``seconds`` have passed. Each pass's seconds
    per query, and the host CPU seconds this process spent in it, go to
    standard error, so that a slow pass shows where its time went."""
    results = {q: [] for q in state["queries"]}
    unit_s = []
    t0 = t = time.perf_counter()
    while t - t0 < seconds:
        cpu = time.process_time()
        out, secs = one_pass(state)
        for q, r in out.items():
            results[q].append(r)
        unit_s.append(time.perf_counter() - t)
        t += unit_s[-1]
        print(f"pass {len(unit_s)}: " + ", ".join(
            f"{q} {s:.4f} s" for q, s in secs.items())
            + f"; host CPU {time.process_time() - cpu:.4f} s",
            file=sys.stderr, flush=True)
    return {"units": len(unit_s), "elapsed": t - t0, "unit_s": unit_s,
            "results": results}


def end_to_end(state, win):
    return {"stream_s": win["elapsed"] / win["units"]}


def _code_bytes(col):
    """The least bytes per row of a column: its width when numeric, a
    dictionary code of the fewest whole bytes when strings."""
    if col.dtype.kind in "iuf":
        return col.dtype.itemsize
    distinct = len(np.unique(col.astype(str)))
    return 1 if distinct <= 1 << 8 else 2 if distinct <= 1 << 16 else 4


def least_bytes(state):
    """Bytes one pass must read at least: every column each query
    reads, once, at its least width."""
    data = state["data"]
    total = 0
    for q in state["queries"]:
        for table, names in _query(q).READS.items():
            for c in names:
                total += len(data[table][c]) * _code_bytes(data[table][c])
    return total


def compare(results, wants, rtol):
    """``(attempted, failed, max_rel_err)`` of the stream's answers
    against the reference's."""
    from compare import compare_frames

    attempted = failed = 0
    worst = 0.0
    for q, outs in results.items():
        for got in outs:
            attempted += 1
            if isinstance(got, Exception):
                failed += 1
                continue
            diff, err = compare_frames(got, wants[q], rtol)
            if np.isfinite(err):
                worst = max(worst, err)
            if diff is not None:
                failed += 1
    return attempted, failed, worst


def references(data, queries, float_dtype=np.float64):
    """Each query's answer from the plain pandas reference, over the
    data with its float columns in ``float_dtype``."""
    import tpch_gen

    cast = {t: {c: (v.astype(float_dtype) if v.dtype.kind == "f" else v)
                for c, v in cols.items()} for t, cols in data.items()}
    pdfs = tpch_gen.to_pandas(cast)
    return {q: _query(q).reference(pdfs) for q in queries}


def check(state, win):
    """Free the device tables, then hold every answer of the window to
    the reference: exact on keys, counts and strings, floats to the
    configuration's ``rtol``."""
    state.pop("frames", None)
    state.pop("runs", None)
    wants = references(state["data"], state["queries"])
    attempted, failed, worst = compare(win["results"], wants,
                                       state["rtol"])
    return attempted, failed, {"max_rel_err": (worst, state["rtol"]),
                               "wrong_answers": (failed, 0)}


def control(config, traffic, seed):
    """The check of :func:`check` with the reference itself in the
    program's place, computed with every float column in float32, the
    precision below the configuration's float64: it has to fail. (The
    dataset is the configuration's; ``seed`` orders nothing compared.)"""
    import tpch_gen

    queries = list(traffic["queries"])
    data = tpch_gen.generate(config["scale_factor"], config["data_seed"])
    wants = references(data, queries)
    lower = references(data, queries, np.float32)
    attempted, failed, worst = compare({q: [lower[q]] for q in queries},
                                       wants, config["rtol"])
    return attempted, failed, {"max_rel_err": (worst, config["rtol"]),
                               "wrong_answers": (failed, 0)}


# ---------------------------------------------------------------- faults
# Each breaks the engine's timed path; a rehearsal with it has to come
# out not correct.

def _altered():
    """An answer altered where it is produced: a result frame's first
    float column is off by one part in a million."""
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
    """Half of the rows left out: every table loses its second half."""
    from cylon_tpu import tpch

    ingest = tpch.ingest

    def half_ingest(data):
        return ingest({t: ({c: v[:max(len(v) // 2, 1)]
                            for c, v in cols.items()}
                           if isinstance(cols, dict) else cols)
                       for t, cols in data.items()})

    tpch.ingest = half_ingest


FAULTS = {"altered": _altered, "half": _half}
