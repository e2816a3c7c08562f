"""Table-level scalar aggregates.

Reference analog: ``cpp/src/cylon/compute/aggregates.cpp:26-147`` —
``compute::Sum/Count/Min/Max/MinMax`` as local Arrow compute followed by
``DoAllReduce`` (mpi::AllReduce). Here the local part is a masked XLA
reduction; the distributed part (``cylon_tpu.parallel``) wraps it in
``psum``/``pmin``/``pmax`` over the mesh axis.
"""

import jax.numpy as jnp

from cylon_tpu import dtypes
from cylon_tpu.errors import InvalidArgument
from cylon_tpu.ops import kernels
from cylon_tpu.ops.selection import _null_flags
from cylon_tpu.table import Table
from cylon_tpu.utils.tracing import scope

AGGS = ("sum", "count", "min", "max", "mean", "var", "std", "nunique",
        "median", "quantile")


def _masked_quantile(data, ok, q):
    """Pandas-style linear-interpolation quantile over the valid rows:
    sort with high sentinels, index at q*(n-1)."""
    import jax

    from cylon_tpu import dtypes as _dt

    if isinstance(q, (int, float)) and not 0.0 <= q <= 1.0:
        raise InvalidArgument(f"quantile {q} not in [0, 1]")

    f = jnp.float64 if data.dtype.itemsize >= 4 else jnp.float32
    sent = jnp.asarray(_dt.sentinel_high(data.dtype), data.dtype)
    s = jnp.sort(jnp.where(ok, data, sent)).astype(f)
    n = ok.sum(dtype=jnp.int32)
    pos = jnp.asarray(q, f) * jnp.maximum(n - 1, 0).astype(f)
    lo = jnp.floor(pos).astype(jnp.int32)
    hi = jnp.ceil(pos).astype(jnp.int32)
    cap_last = max(data.shape[0] - 1, 0)
    vlo = s[jnp.clip(lo, 0, cap_last)]
    vhi = s[jnp.clip(hi, 0, cap_last)]
    out = vlo + (vhi - vlo) * (pos - lo.astype(f))
    return jnp.where(n > 0, out, jnp.asarray(jnp.nan, f))


@scope("aggregate")
def table_aggregate(table: Table, col: str, op: str, quantile: float = 0.5):
    """Scalar aggregate of one column, skipping nulls/NaN. Returns a
    0-d jax array (device scalar; jit-safe). Op set mirrors
    ``AggregationOpId`` (compute/aggregate_kernels.hpp:40-52: SUM..MAX,
    COUNT, MEAN, VAR, NUNIQUE, QUANTILE, STDDEV); ``quantile`` mirrors
    ``QuantileKernelOptions`` (:81-84)."""
    if op not in AGGS:
        raise InvalidArgument(f"unknown aggregate {op!r}")
    c = table.column(col)
    cap = table.capacity
    vmask = kernels.valid_mask(cap, table.nrows)
    nulls = _null_flags(c)
    ok = vmask if nulls is None else vmask & (nulls == 0)
    # overflow poison folds into the scalar on-device (NaN for float
    # results, iinfo.min for integer ones — -1 would be indistinguishable
    # from a legitimate sum/min/max over negative values): a truncated
    # upstream op must never yield a silently-wrong aggregate. Under
    # whole-query tracing the flag is ALSO registered with the enclosing
    # CompiledQuery (plan.note_overflow) so scalar-returning compiled
    # queries trigger the regrow ladder instead of returning poison.
    from cylon_tpu import plan

    nr = table.nrows
    bad = ((nr > cap) if getattr(nr, "ndim", 0) == 0
           else jnp.zeros((), bool))
    plan.note_overflow(bad)

    def _guard(val):
        val = jnp.asarray(val)
        if jnp.issubdtype(val.dtype, jnp.floating):
            return jnp.where(bad, jnp.full((), jnp.nan, val.dtype), val)
        # bool (and unsigned, where iinfo.min == 0) sentinels are
        # ambiguous — there the registered flag (note_overflow above) is
        # the reliable signal; the sentinel is best-effort poison
        sent = (False if val.dtype == jnp.bool_
                else jnp.iinfo(val.dtype).min)
        return jnp.where(bad, jnp.asarray(sent, val.dtype), val)

    data = c.data
    if op == "count":
        return _guard(ok.sum(dtype=jnp.int64))
    if op == "nunique":
        gid, num_groups, _ = kernels.dense_group_ids(
            [data], ok, [None])
        return _guard(num_groups.astype(jnp.int64))
    if op in ("median", "quantile"):
        q = 0.5 if op == "median" else quantile
        return _guard(_masked_quantile(data, ok, q))
    if op == "sum":
        acc = kernels._acc_dtype(data.dtype)
        return _guard(
            jnp.where(ok, data, jnp.zeros((), data.dtype)).astype(acc).sum())
    if op == "min":
        sent = dtypes.sentinel_high(data.dtype)
        return _guard(jnp.where(ok, data, jnp.asarray(sent, data.dtype)).min())
    if op == "max":
        sent = dtypes.sentinel_low(data.dtype)
        return _guard(jnp.where(ok, data, jnp.asarray(sent, data.dtype)).max())
    f = jnp.float64 if data.dtype.itemsize >= 4 else jnp.float32
    vals = jnp.where(ok, data.astype(f), 0.0)
    n = ok.sum(dtype=f)
    s = vals.sum()
    if op == "mean":
        return _guard(s / jnp.maximum(n, 1.0))
    sq = (vals * vals).sum()
    var = (sq - s * s / jnp.maximum(n, 1.0)) / jnp.maximum(n - 1.0, 1.0)
    var = jnp.maximum(var, 0.0)
    return _guard(jnp.sqrt(var) if op == "std" else var)
