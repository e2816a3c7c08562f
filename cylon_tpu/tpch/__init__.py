"""TPC-H workload: dbgen-style generator + the full 22-query suite.

BASELINE.json config 5 ("TPC-H SF100 Q3/Q5 multi-way join + groupby
pipeline") names TPC-H as a headline benchmark of the rebuild; the
reference itself ships only the synthetic join benchmarks
(``cpp/src/examples/bench/``), so this subsystem is the benchmark-parity
layer: a deterministic dbgen-style generator and the queries
expressed over the :class:`cylon_tpu.frame.DataFrame` surface, runnable
locally or distributed over the mesh (``env=``).
"""

from cylon_tpu.tpch.dbgen import date_int, generate, generate_pandas
from cylon_tpu.tpch.queries import (q1, q2, q3, q4, q5, q6, q7, q8, q9,
                                    q10, q11, q12, q13, q14, q15, q16,
                                    q17, q18, q19, q20, q21, q22)

_COMPILED: dict = {}


def ingest(data) -> dict:
    """Raw dbgen mapping -> DataFrames under the TPC-H string-storage
    policy (comment columns as device bytes). The ONE place the policy
    is applied — queries, the compiled wrapper and the benches all
    route through it."""
    from cylon_tpu.frame import DataFrame
    from cylon_tpu.tpch.queries import TPCH_STRING_STORAGE

    return {k: v if isinstance(v, DataFrame)
            else DataFrame(v, string_storage=TPCH_STRING_STORAGE)
            for k, v in data.items()}


def compiled(q):
    """Whole-query-compiled variant of a TPC-H query: the entire
    multi-operator pipeline traces into ONE XLA program
    (:mod:`cylon_tpu.plan`) — one dispatch + one result fetch instead of
    an eager per-operator chain of host syncs. This is the compiled reimagining of the reference's
    L7 streaming engine (``ops/dis_join_op.cpp:21-72``).

    ``tpch.compiled("q3")(data, env=env)`` — same signature as the eager
    query; scalar-returning queries (q6/q14/q17) yield a 0-d device
    array instead of a float.
    """
    import functools

    from cylon_tpu import plan
    from cylon_tpu.tpch import queries as _q

    fn = getattr(_q, q) if isinstance(q, str) else q
    # the process-wide shared plan cache (thread-safe get-or-create):
    # every caller — bench legs, concurrent serve tenants — shares ONE
    # CompiledQuery per query fn, so repeated shapes are cache hits
    # across clients. _COMPILED stays as a mirror view for the bench's
    # regrow-scale reporting.
    cq = _COMPILED[fn] = plan.shared_compiled(fn)

    @functools.wraps(fn)
    def run(data, **kw):
        # device coercion is a host-side step — it must happen before
        # tracing (Table.from_pydict can't consume tracers)
        return cq(ingest(data), **kw)

    return run


__all__ = ["generate", "generate_pandas", "date_int", "compiled",
           "ingest"] + [f"q{i}" for i in range(1, 23)]
