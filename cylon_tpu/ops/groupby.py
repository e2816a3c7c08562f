"""Group-by aggregation via dense group ids + segment reductions.

Reference analog: ``cpp/src/cylon/groupby/hash_groupby.cpp`` —
``make_groups`` builds a composite-row-hash map to dense ids (line 90)
then templated ``aggregate<op>`` walks rows updating per-group state
(lines 143, 221-226); op set in ``compute/aggregate_kernels.hpp:40-52``
(SUM..STDDEV, NUNIQUE, QUANTILE). The pipeline (pre-sorted) variant is
``pipeline_groupby.cpp``.

TPU-first: group ids come from one lexsort (collision-free, no hash
map). The aggregates are planned as CHANNELS, each distinct per-group
reduction once (:func:`_channel_plan`). On TPU up to
:data:`SEGSCAN_MAX_ROWS` rows every channel then fuses into ONE
segmented scan + ONE compaction sort (``kernels.segmented_totals``) —
XLA's segment-op lowering is the slowest primitive on the platform
(~97 ms/channel at 1M rows x 600k f64 segments on v5e vs ~2-4 ms
fused here); CPU meshes, and the TPU above that size, run one segment
op per channel. The "pipeline groupby" specialisation is unnecessary —
sorted input just makes the same lexsort cheap.

Group order in the output is key-sorted (== pandas ``sort=True``).
"""

import functools
import math
from typing import Sequence

import jax
import jax.numpy as jnp

from cylon_tpu.column import Column
from cylon_tpu import dtypes
from cylon_tpu.errors import InvalidArgument
from cylon_tpu.ops import kernels
from cylon_tpu.ops.selection import (_null_flags, columns_to_payloads,
                                     payloads_to_columns, take_columns)
from cylon_tpu.platform import platform_jit
from cylon_tpu.table import Table
from cylon_tpu.utils.tracing import scope

#: ops supported (parity: aggregate_kernels.hpp:40-52 + pandas extras).
#: "sumsq" is internal — the mergeable partial for distributed var/std.
AGG_OPS = ("sum", "count", "size", "min", "max", "mean", "var", "std",
           "nunique", "first", "last", "median", "quantile", "sumsq")

#: static-shape -> settled capacity scale of the eager regrow ladder
_EAGER_SCALE_MEMO: dict = {}


#: row-count ceiling for the segmented-scan aggregation path on TPU.
#: The crossover runs BOTH ways: at 1M rows / 600k groups the scan
#: beats the x64-emulated segment lowering ~16x (r3 measurement,
#: ~97 ms -> ~6 ms), but ``lax.associative_scan`` collapses at larger
#: shapes — on v5e at 6M rows even ONE segmented f64 channel runs for
#: MINUTES, and its compile grows faster than the rows: for a
#: described v5e the scan of TPC-H Q1's nine channels alone compiles in
#: 191 s at 1M rows, 683 s at 2M, and not within 700 s at 6M (the
#: compaction sort at 6M: 50 s). The segment ops are not cheap there
#: either: a v5e trace of SF1 Q1 (6M rows, 4 groups) reads ~510 ms per
#: 64-bit segment-sum channel (an x64-emulated (f32, f32) scatter) and
#: ~50 ms per 32-bit one, which is why the channel plan merges
#: duplicates and counts in int32.
#: ``CYLON_TPU_SEGSCAN_MAX`` overrides.
SEGSCAN_MAX_ROWS = 2_000_000


def _use_segscan(cap: int) -> bool:
    """Route per-group reductions through the segmented-scan +
    compaction-sort path (:func:`kernels.segmented_totals`)?

    TPU only, and only up to :data:`SEGSCAN_MAX_ROWS` (see its
    docstring: both XLA lowerings invert — segment ops lose at ~1M
    rows, the scan collapses at ~6M). XLA:CPU keeps segment ops at
    every size (~4 ms segment_sum vs ~200 ms for the 20-pass scan at
    1M rows). ``CYLON_TPU_SEGSCAN=1|0`` overrides (tests pin parity of
    the scan path on the CPU mesh with small shapes)."""
    import os

    from cylon_tpu.platform import current_platform

    mode = os.environ.get("CYLON_TPU_SEGSCAN", "auto")
    if mode in ("1", "on", "true"):
        return True
    if mode in ("0", "off", "false"):
        return False
    limit = int(os.environ.get("CYLON_TPU_SEGSCAN_MAX", SEGSCAN_MAX_ROWS))
    return current_platform() == "tpu" and cap <= limit


def groupby_aggregate(table: Table, by: Sequence[str],
                      aggs: Sequence[tuple[str, str]] | Sequence[tuple[str, str, str]],
                      out_capacity: int | None = None,
                      quantile: float = 0.5) -> Table:
    """Aggregate ``table`` grouped by key columns ``by``.

    ``aggs``: (src_column, op[, out_name]) tuples; op from AGG_OPS.
    Result: one row per distinct key tuple, keys first then aggregates,
    key-sorted. Null keys form their own group (they equal each other).
    Nulls/NaNs in value columns are skipped (pandas skipna semantics).
    """
    import os

    cap = int(table.capacity)
    by_t = tuple(by)
    aggs_t = tuple(tuple(a) for a in aggs)
    seg = _use_segscan(cap)

    def dispatch(oc):
        return _groupby_compiled(table, by=by_t, aggs=aggs_t,
                                 out_cap=oc, quantile=float(quantile),
                                 segscan=seg)

    if out_capacity is not None:
        return dispatch(int(out_capacity))
    # default bound: every per-group reduction's cost scales with the
    # static output bound (measured on v5e: 600k-segment f64
    # segment-sum ~160 ms vs ~6 ms at 8k), and most groupbys produce
    # far fewer groups than rows — so bound OPTIMISTICALLY and regrow.
    from cylon_tpu import plan

    def bound(scale):
        return min(cap, max(8192, cap // 16) * scale)

    if isinstance(table.nrows, jax.core.Tracer):
        # under a trace (whole-query compilation or a dist-op body) the
        # enclosing regrow ladder catches the overflow poison
        return dispatch(bound(plan.current_scale()))
    if not plan.adaptive_enabled():
        return dispatch(cap)  # classic fire-and-check, no host sync
    # eager: host-side ladder, one row-count sync per call (the frame
    # path pays that sync in shrink_to_fit anyway). The settled scale
    # memoizes per static shape so steady-state reruns dispatch ONCE —
    # without the memo every high-cardinality groupby would replay its
    # failed dispatches on every call.
    from cylon_tpu.errors import OutOfCapacity

    key = (cap, by_t, aggs_t, seg)
    scale = max(plan.current_scale(), _EAGER_SCALE_MEMO.get(key, 1))
    while True:
        t = dispatch(bound(scale))
        try:
            t.num_rows  # host sync; raises on overflow
            _EAGER_SCALE_MEMO[key] = scale
            return t
        except OutOfCapacity:
            # failure path only (no sync on success): an UPSTREAM
            # truncation's poison rides carry_overflow and would raise
            # at every rung — groups can never exceed rows, so detect
            # it on the input and return the poisoned result at once
            # instead of replaying the ladder's compiles
            if int(table.nrows) > cap or bound(scale) >= cap:
                return t
            scale *= 2


@functools.partial(platform_jit, static_argnames=("by", "aggs", "out_cap",
                                                  "quantile", "segscan"))
@scope("groupby")
def _groupby_compiled(table: Table, *, by, aggs, out_cap,
                      quantile, segscan=False) -> Table:
    cap = table.capacity
    keys = [table.column(n).data for n in by]
    kvals = [table.column(n).validity for n in by]

    # aggregate on the GROUP-SORTED layout, with the value columns
    # carried through the ONE sort as payload operands (random gathers
    # are ~10x the sort's own cost at 10M rows on TPU — see
    # kernels.group_sort). Monotone segment ids then let every
    # reduction run with indices_are_sorted=True. The stable sort
    # preserves original order within each group (pandas first/last).
    src_names = []
    for spec in aggs:
        src = spec[0]
        if src not in src_names:
            src_names.append(src)
    iota = jnp.arange(cap, dtype=jnp.int32)
    src_cols = {s: table.column(s) for s in src_names}
    # original row index leads the payloads (keytab + first/last);
    # multi-dim columns fall back to a post-sort gather via that index.
    # WIDE value sets instead ride one packed row gather through the
    # sorted index — each sort payload re-moves its bytes through every
    # merge stage (see selection.PAYLOAD_SORT_MAX_WORDS)
    # (on the TPU the group sort carries at most one 64-bit column:
    # TPU_GROUP_SORT_MAX_WORDS, at the end of this module)
    wide = _gather_values(src_cols, cap)
    if wide:
        payloads, pack = [iota], None
    else:
        payloads, pack = columns_to_payloads(src_cols, cap, lead=[iota],
                                             index_slot=0)

    with scope("groupby", "sort"):
        gid_s, num_groups, sorted_pl = kernels.group_sort(
            keys, table.nrows, kvals, payloads)
    orig_idx = sorted_pl[0]
    with scope("groupby", "permute"):
        if wide:
            stab = take_columns(table, orig_idx, table.nrows,
                                names=src_names)
        else:
            sorted_cols = payloads_to_columns(src_cols, sorted_pl, pack)
            stab = Table(sorted_cols, table.nrows)

    specs = []
    for spec in aggs:
        src, op, name = spec if len(spec) == 3 else (*spec, None)
        specs.append((src, op, name or f"{src}_{op}"))
        if op not in AGG_OPS:
            raise InvalidArgument(f"unknown aggregation {op!r}")

    with scope("groupby", "reduce"):
        if segscan:
            out = _aggregate_scan(stab, table, by, specs, gid_s, num_groups,
                                  out_cap, quantile, orig_idx)
        else:
            out = _aggregate_segments(stab, table, by, specs, gid_s,
                                      num_groups, out_cap, quantile,
                                      orig_idx, iota)
        return kernels.carry_overflow(Table(out, num_groups), table)


def _aggregate_segments(stab: Table, table: Table, by, specs, gid_s,
                        num_groups, out_cap: int, quantile, orig_idx,
                        iota) -> dict:
    """The segment-op path (CPU meshes, and the TPU above
    :data:`SEGSCAN_MAX_ROWS`): one XLA segment reduction per distinct
    CHANNEL of :func:`_channel_plan` (not per aggregate) over the
    group-sorted layout (``stab``/``gid_s``); ``iota`` is the original
    table's row index."""
    cap = table.capacity
    big = jnp.int32(cap)
    first_pos = jax.ops.segment_min(jnp.where(gid_s < big, iota, big),
                                    gid_s, num_segments=out_cap,
                                    indices_are_sorted=True)
    first_pos = jnp.clip(first_pos, 0, max(cap - 1, 0))

    out = {}
    # key values: one tiny gather of the group-leader rows from the
    # ORIGINAL table (out_cap rows, not cap)
    first_orig = orig_idx[first_pos]
    keytab = take_columns(table, first_orig, num_groups, names=list(by))
    for n in by:
        out[n] = keytab.column(n)

    channels, plans = _channel_plan(stab, specs, gid_s, num_groups,
                                    out_cap, quantile, _nunique, _quantile)
    outputs = [_reduce_segments(kind, val, gid_s, out_cap)
               for kind, val in channels]
    for name, post in plans:
        out[name] = post(outputs)
    return out


def _reduce_segments(kind, val, gid_s, out_cap: int) -> tuple:
    """Reduce one channel (see :func:`kernels.segmented_totals` for the
    kinds) with ONE XLA segment op over GROUP-SORTED ``gid_s``
    (monotone, padding rows last with id == capacity), hence the sorted
    flag. Missing values are masked out of the VALUES (zero /
    sentinel), never the indices: sentinel ids would break
    monotonicity. first/last are a positional segment min/max over row
    indices (the stable group sort kept original row order within each
    group, so that is pandas first/last), then an [out_cap]-row gather."""
    seg = dict(indices_are_sorted=True, num_segments=out_cap)
    if kind == "sum":
        return (jax.ops.segment_sum(val, gid_s, **seg),)
    if kind == "min":
        return (jax.ops.segment_min(val, gid_s, **seg),)
    if kind == "max":
        return (jax.ops.segment_max(val, gid_s, **seg),)
    data, has = val
    cap = gid_s.shape[0]
    iota = jnp.arange(cap, dtype=jnp.int32)
    if kind == "first":
        idx = jax.ops.segment_min(jnp.where(has, iota, cap), gid_s, **seg)
    else:
        idx = jax.ops.segment_max(jnp.where(has, iota, -1), gid_s, **seg)
    found = (idx >= 0) & (idx < cap)
    return data[jnp.clip(idx, 0, max(cap - 1, 0))], found


def _aggregate_scan(stab: Table, orig_table: Table, by, specs, gid_s,
                    num_groups, out_cap: int, q: float, orig_idx) -> dict:
    """TPU fast path: ALL channels of :func:`_channel_plan` fuse into ONE
    segmented scan + ONE compaction sort (``kernels.segmented_totals``)
    — replacing one XLA segment op per channel (each ~97 ms at 1M
    rows / 600k f64 segments on v5e) with an ~11 ms fused pass.
    nunique/median/quantile keep their own (gid, value) sort but their
    per-group reductions ride the same scan+compact machinery.
    ``stab``/``gid_s`` are the group-sorted layout."""
    channels, plans = _channel_plan(stab, specs, gid_s, num_groups,
                                    out_cap, q, _nunique_scan,
                                    _quantile_scan)
    outputs, extra = kernels.segmented_totals(gid_s, out_cap, channels,
                                              extras=[orig_idx])
    out = {}
    leader = extra[0]   # original row index of each group's last row
    keytab = take_columns(orig_table, leader, num_groups, names=list(by))
    for n in by:
        out[n] = keytab.column(n)
    for name, post in plans:
        out[name] = post(outputs)
    return out


def _channel_plan(stab: Table, specs, gid_s, num_groups, out_cap: int,
                  q: float, nunique, quantile) -> tuple[list, list]:
    """Pass 1 of both reduce paths: register each distinct per-group
    reduction ONCE as a channel, and each aggregate as a ``post``
    function over the channels' outputs.

    A channel is ``(kind, value)`` as :func:`kernels.segmented_totals`
    takes it. Channels are keyed so that equal work merges: a value sum
    is ``("sum", src, accumulator dtype)`` for sum, mean, var and std
    alike (an f64 column asked for its sum and its mean is summed once;
    an integer column's i64 sum and f64 mean stay two), sums of squares
    likewise; every count is int32 (a count is at most the capacity,
    below 2^31, so exact) and cast after the reduction, and columns
    without null flags share the one valid-row count with ``size``.
    ``nunique(stab, src, gid_s, gvalid, out_cap)`` and ``quantile(...,
    q)`` build those aggregates from their own (gid, value) sorts.

    Returns ``(channels, plans)``: ``plans`` is ``[(name, post)]`` with
    ``post(outputs) -> Column``, ``outputs[i]`` the tuple channel ``i``
    reduces to, [out_cap] rows aligned to dense group id."""
    cap = stab.capacity
    vmask = kernels.valid_mask(cap, stab.nrows)
    gvalid = jnp.arange(out_cap, dtype=jnp.int32) < num_groups

    channels: list = []
    index_of: dict = {}

    def chan(key, kind, val):
        if key not in index_of:
            index_of[key] = len(channels)
            channels.append((kind, val))
        return index_of[key]

    ok_cache: dict = {}   # src -> (count key, valid non-null rows)

    def ok_of(src):
        if src not in ok_cache:
            nulls = _null_flags(stab.column(src))
            ok_cache[src] = ((None, vmask) if nulls is None
                             else (src, vmask & (nulls == 0)))
        return ok_cache[src]

    def masked(src, fill, dtype=None):
        c = stab.column(src)
        ok_b = ok_of(src)[1].reshape((cap,) + (1,) * (c.data.ndim - 1))
        data = c.data if dtype is None else c.data.astype(dtype)
        return jnp.where(ok_b, data, jnp.asarray(fill, data.dtype))

    def count_chan(src):
        key, ok = ok_of(src)
        return chan(("count", key), "sum", ok.astype(jnp.int32))

    def sum_chan(src, acc):
        acc = jnp.dtype(acc)
        return chan(("sum", src, acc), "sum", masked(src, 0, acc))

    def sumsq_chan(src, f):
        v = masked(src, 0, f)
        return chan(("sumsq", src, jnp.dtype(f)), "sum", v * v)

    def float_of(c):
        return jnp.float64 if c.data.dtype.itemsize >= 4 else jnp.float32

    plans = []
    for src, op, name in specs:
        c = stab.column(src)
        if op in ("size", "count"):
            i = (count_chan(src) if op == "count" else
                 chan(("count", None), "sum", vmask.astype(jnp.int32)))
            plans.append((name, lambda o, i=i: Column(
                o[i][0].astype(jnp.int64), None, dtypes.int64)))
        elif op == "sum":
            acc = kernels._acc_dtype(c.data.dtype)
            i = sum_chan(src, acc)
            plans.append((name, lambda o, i=i, acc=acc: Column(
                o[i][0], None, dtypes.from_numpy_dtype(acc))))
        elif op == "sumsq":
            f = float_of(c)
            i = sumsq_chan(src, f)
            plans.append((name, lambda o, i=i, f=f: Column(
                o[i][0], None, dtypes.from_numpy_dtype(f))))
        elif op in ("min", "max"):
            # dictionary codes are order-preserving, so min/max of codes
            # is correct for string columns too
            sent = (dtypes.sentinel_high(c.data.dtype) if op == "min"
                    else dtypes.sentinel_low(c.data.dtype))
            i = chan((op, src), op, masked(src, sent))
            ic = count_chan(src)
            plans.append((name, lambda o, i=i, ic=ic, c=c: Column(
                o[i][0], gvalid & (o[ic][0] > 0), c.dtype, c.dictionary)))
        elif op in ("mean", "var", "std"):
            f = float_of(c)
            isum = sum_chan(src, f)
            ic = count_chan(src)
            isq = None if op == "mean" else sumsq_chan(src, f)

            def post(o, isum=isum, ic=ic, isq=isq, op=op, f=f):
                s = o[isum][0]
                n = o[ic][0].astype(f)
                # counts are per group; broadcast over trailing dims
                n_b = n.reshape(n.shape + (1,) * (s.ndim - 1))
                if op == "mean":
                    return Column(s / jnp.maximum(n_b, 1.0),
                                  gvalid & (n > 0),
                                  dtypes.from_numpy_dtype(f))
                sq = o[isq][0]
                # ddof=1 (pandas default)
                var = ((sq - s * s / jnp.maximum(n_b, 1.0))
                       / jnp.maximum(n_b - 1.0, 1.0))
                var = jnp.maximum(var, 0.0)
                data = jnp.sqrt(var) if op == "std" else var
                return Column(data, gvalid & (n > 1),
                              dtypes.from_numpy_dtype(f))

            plans.append((name, post))
        elif op in ("first", "last"):
            i = chan((op, src), op, (c.data, ok_of(src)[1]))
            plans.append((name, lambda o, i=i, c=c: Column(
                o[i][0], gvalid & o[i][1], c.dtype, c.dictionary)))
        elif op == "nunique":
            plans.append((name, lambda _o, a=(stab, src, gid_s, gvalid,
                                              out_cap): nunique(*a)))
        elif op in ("median", "quantile"):
            qq = 0.5 if op == "median" else q
            plans.append((name, lambda _o, a=(stab, src, gid_s, gvalid,
                                              out_cap, qq): quantile(*a)))
        else:  # pragma: no cover — specs pre-validated
            raise InvalidArgument(f"unhandled aggregation {op!r}")
    return channels, plans


def _nunique_scan(stab, src, gid_s, gvalid, out_cap: int) -> Column:
    """nunique on the scan path: sort rows by (gid, null-last, value),
    count per-group value-run starts via scan+compact."""
    c = stab.column(src)
    cap = stab.capacity
    nulls = _null_flags(c)
    vmask = kernels.valid_mask(cap, stab.nrows)
    ok = vmask if nulls is None else (vmask & (nulls == 0))
    # nulls keep their group id (every group stays present, so the
    # compaction's dense slot == gid alignment holds even for all-null
    # groups) but sort to the end of the group's run
    nf = (~ok).astype(jnp.uint8)
    g_s, nf_s, v_s = jax.lax.sort(
        (gid_s, nf, kernels.order_key(c.data)), num_keys=3,
        is_stable=False)
    iota = jnp.arange(cap, dtype=jnp.int32)
    newg = g_s != jnp.roll(g_s, 1)
    newv = v_s != jnp.roll(v_s, 1)
    boundary = (jnp.where(iota == 0, True, newg | newv)
                & (nf_s == 0) & (g_s < cap))
    outputs, _ = kernels.segmented_totals(
        g_s, out_cap, [("sum", boundary.astype(jnp.int32))])
    return Column(outputs[0][0].astype(jnp.int64), None, dtypes.int64)


def _quantile_scan(stab, src, gid_s, gvalid, out_cap: int,
                   q: float) -> Column:
    """Per-group quantile on the scan path: one (gid, null-last, value)
    sort; group sizes and non-null counts via scan+compact; two
    [out_cap]-row gathers pick the interpolation endpoints."""
    c = stab.column(src)
    cap = stab.capacity
    f = jnp.float64 if c.data.dtype.itemsize >= 4 else jnp.float32
    nulls = _null_flags(c)
    vmask = kernels.valid_mask(cap, stab.nrows)
    ok = vmask if nulls is None else (vmask & (nulls == 0))
    nf = (~ok).astype(jnp.uint8)
    g_s, nf_s, _, v_raw = jax.lax.sort(
        (gid_s, nf, kernels.order_key(c.data), c.data), num_keys=3,
        is_stable=False)
    outputs, _ = kernels.segmented_totals(
        g_s, out_cap,
        [("sum", ((nf_s == 0) & (g_s < cap)).astype(jnp.int32)),
         ("sum", (g_s < cap).astype(jnp.int32))])
    n = outputs[0][0]
    total = outputs[1][0]
    start = kernels.exclusive_cumsum(total)
    v_s = v_raw.astype(f)
    pos = q * jnp.maximum(n - 1, 0).astype(f)
    lo = jnp.floor(pos).astype(jnp.int32)
    hi = jnp.ceil(pos).astype(jnp.int32)
    w = pos - lo.astype(f)
    take_lo = jnp.clip(start + lo, 0, max(cap - 1, 0))
    take_hi = jnp.clip(start + hi, 0, max(cap - 1, 0))
    data = v_s[take_lo] * (1 - w) + v_s[take_hi] * w
    return Column(data, gvalid & (n > 0), dtypes.from_numpy_dtype(f))


def _nunique(stab: Table, src: str, gid_s, gvalid, out_cap: int) -> Column:
    """Distinct non-null values per group: sort rows by (gid, value) and
    count run boundaries per group (parity: NUNIQUE kernel,
    ``aggregate_kernels.hpp``). The (gid, value-order-key) pairs ARE the
    sort operands — no permutation, no gather; order-key equality ==
    value equality (canonical NaN / -0.0)."""
    c = stab.column(src)
    cap = c.data.shape[0]
    g_s, v_s = jax.lax.sort((_gid_of_values(stab, src, gid_s, out_cap),
                             kernels.order_key(c.data)),
                            num_keys=2, is_stable=False)
    iota = jnp.arange(cap, dtype=jnp.int32)
    new_grp = g_s != jnp.roll(g_s, 1)
    new_val = v_s != jnp.roll(v_s, 1)
    boundary = (jnp.where(iota == 0, True, new_grp | new_val)
                & (g_s < out_cap))
    data = jax.ops.segment_sum(boundary.astype(jnp.int32),
                               jnp.where(g_s < out_cap, g_s, out_cap),
                               num_segments=out_cap,
                               indices_are_sorted=True)
    return Column(data.astype(jnp.int64), None, dtypes.int64)


def _quantile(stab: Table, src: str, gid_s, gvalid, out_cap: int,
              q: float) -> Column:
    """Per-group linear-interpolated quantile over non-null values
    (parity: QUANTILE kernel). Sort by (gid, value), then index each
    group's run at q*(n-1)."""
    c = stab.column(src)
    cap = c.data.shape[0]
    f = jnp.float64 if c.data.dtype.itemsize >= 4 else jnp.float32
    # values ride the (gid, value-key) sort as payload — no perm/gather
    g_s, _, v_raw = jax.lax.sort(
        (_gid_of_values(stab, src, gid_s, out_cap),
         kernels.order_key(c.data), c.data), num_keys=2, is_stable=False)
    v_s = v_raw.astype(f)
    n = jax.ops.segment_sum(jnp.ones(cap, jnp.int32),
                            jnp.where(g_s < out_cap, g_s, out_cap),
                            num_segments=out_cap,
                            indices_are_sorted=True)
    start = kernels.exclusive_cumsum(n)
    pos = q * jnp.maximum(n - 1, 0).astype(f)
    lo = jnp.floor(pos).astype(jnp.int32)
    hi = jnp.ceil(pos).astype(jnp.int32)
    w = (pos - lo.astype(f))
    take_lo = jnp.clip(start + lo, 0, max(cap - 1, 0))
    take_hi = jnp.clip(start + hi, 0, max(cap - 1, 0))
    data = v_s[take_lo] * (1 - w) + v_s[take_hi] * w
    return Column(data, gvalid & (n > 0), dtypes.from_numpy_dtype(f))


def _gid_of_values(stab: Table, src: str, gid_s, out_cap: int):
    """Group ids with every missing value's row sent to the sentinel
    ``out_cap`` (the re-sorts of nunique/quantile need no monotone ids)."""
    c = stab.column(src)
    ok = kernels.valid_mask(stab.capacity, stab.nrows)
    nulls = _null_flags(c)
    if nulls is not None:
        ok = ok & (nulls == 0)
    return jnp.where(ok, gid_s, out_cap)


#: value words the TPU group sort may carry: one 64-bit column. Each
#: payload word adds compile time to the sort (described v5e, PR 21: a
#: (u8, u64, i32) sort of 2M rows compiles in 58 s, 124 s with two more
#: f64 columns); the 10M-rows/chip distributed groupby of
#: ``chip_smoke.py --chips 4``, whose two group sorts carried two f64
#: columns, compiled in 349 s, and in 102 s with them gathered.
TPU_GROUP_SORT_MAX_WORDS = 2


def _gather_values(src_cols, cap: int) -> bool:
    """Gather the value columns through the sorted row index instead of
    carrying them through the group sort?"""
    from cylon_tpu.ops.selection import payload_words, use_gather_path
    from cylon_tpu.platform import current_platform

    words = payload_words(src_cols)
    return use_gather_path(words, cap) or (
        words > TPU_GROUP_SORT_MAX_WORDS and current_platform() == "tpu")
