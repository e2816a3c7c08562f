"""Whole-query compilation: one XLA program per query.

The reference's answer to multi-operator queries is the L7 streaming
op-graph — ``DisJoinOP`` builds partition→shuffle→split→join chains and
overlaps their progress on table chunks (``ops/dis_join_op.cpp:21-72``,
schedulers ``ops/execution/execution.hpp:43-110``). That machinery
exists because eager C++ operators would otherwise serialise on the
network.

The TPU-first reimagining: **the query is a traced function**. Every
operator in this framework is jit-safe (static capacities, no
data-dependent host control flow), so an entire query —
filter→join→join→groupby→sort→head — compiles into ONE XLA executable
in which the compiler overlaps compute and ICI collectives at the
instruction level (what the reference's RoundRobin/Priority schedulers
approximate by hand). Host involvement drops to one dispatch plus one
result fetch, where an eager chain pays 5-10 per-operator host
syncs.

Two pieces:

* :func:`capacity_scale` / :func:`current_scale` — an ambient multiplier
  applied to every *defaulted* capacity bound chosen while tracing.
  Powers of two keep the shape space (and hence compile count) bounded.
* :func:`compile_query` — wrap a query function (Tables/DataFrames in,
  Table/DataFrame out) into a compiled, capacity-adaptive callable:
  run at scale 1; if any result shard overflowed its buffer
  (``OutOfCapacity``), double the scale and re-dispatch. The XLA
  compilation cache (persistent, see ``cylon_tpu/__init__``) makes the
  retry cheap; steady-state reruns hit the right scale's executable
  directly via :class:`CompiledQuery`'s scale memo.
"""

import collections
import contextlib
import contextvars
import functools
import threading

import jax

from cylon_tpu import telemetry
from cylon_tpu.errors import OutOfCapacity
from cylon_tpu.telemetry import trace as _trace
from cylon_tpu.utils import tracing as _tracing

__all__ = ["capacity_scale", "current_scale", "compile_query",
           "CompiledQuery", "MAX_SCALE", "note_overflow",
           "tight_enabled", "current_row_hint", "row_hint",
           "shared_compiled", "plan_cache_stats",
           "query_fingerprint"]

#: regrow ceiling: 2^10 = 1024x the default budget. Buffers grow only as
#: far as the retry that fits (geometric, ~10 re-dispatches worst case);
#: past this the workload is a near-cross-join and the caller should set
#: an explicit capacity or rethink the keys. Device memory, not this
#: constant, is the practical bound — the reference behaves the same way
#: (its dynamically allocated receives simply OOM on a true cross join).
MAX_SCALE = 1024

_SCALE: contextvars.ContextVar = contextvars.ContextVar(
    "cylon_capacity_scale", default=1)

#: trace-time overflow-flag registry. Tables carry overflow as a
#: poisoned ``nrows > capacity`` the host check reads off the result —
#: but a compiled query that returns only a *scalar* (q6/q14/q17 shape)
#: has no table in its result pytree, so an internal join/groupby
#: truncation would otherwise come back as plausible-looking on-device
#: poison (NaN / iinfo.min). Ops therefore also register their 0-d bool
#: overflow indicators here while tracing; :class:`CompiledQuery`
#: returns the OR of them alongside the result and checks it on host.
_FLAGS: contextvars.ContextVar = contextvars.ContextVar(
    "cylon_overflow_flags", default=None)


def note_overflow(flag) -> None:
    """Register a 0-d bool overflow indicator with the enclosing
    :class:`CompiledQuery` trace (no-op outside one). Ops whose result
    cannot carry table-poison (scalar aggregates) MUST call this; ops
    that do poison ``nrows`` may also call it — the flag check subsumes
    the result-table scan when intermediate poison could be masked by a
    downstream op."""
    lst = _FLAGS.get()
    if lst is not None:
        import jax.numpy as jnp

        lst.append(jnp.asarray(flag).reshape(()))


@contextlib.contextmanager
def _collect_flags(into: list):
    tok = _FLAGS.set(into)
    try:
        yield
    finally:
        _FLAGS.reset(tok)


@contextlib.contextmanager
def capacity_scale(scale: int):
    """Ambient multiplier for defaulted capacity bounds (trace-time)."""
    tok = _SCALE.set(int(scale))
    try:
        yield
    finally:
        _SCALE.reset(tok)


def current_scale() -> int:
    return _SCALE.get()


def adaptive_enabled() -> bool:
    """The ONE parse of ``CYLON_TPU_ADAPTIVE`` (default on) — every
    regrow ladder (``dist_ops._adaptive``, ``groupby``, the nunique
    ladder) consults this, so the accepted spellings live here."""
    import os

    return os.environ.get("CYLON_TPU_ADAPTIVE", "1") not in (
        "0", "off", "false")


def tight_enabled() -> bool:
    """The ONE parse of ``CYLON_TPU_TIGHT`` (default on): count-driven
    tight-capacity sizing of defaulted exchange bounds
    (``dist_ops._tight_rows_local``). ``CYLON_TPU_TIGHT=0`` restores
    the unconditional ``DEFAULT_SKEW``×capacity headroom everywhere."""
    import os

    return os.environ.get("CYLON_TPU_TIGHT", "1") not in (
        "0", "off", "false")


#: ambient trace-time hint: a power-of-2 bucket of the compiled
#: query's concrete TOTAL input rows. Inside the trace every row count
#: is a tracer, so exchanges cannot size from true counts the way
#: eager dispatches do — instead :class:`CompiledQuery` records this
#: bucket (static, so the program retraces only when the bucket
#: changes) and ``dist_ops._tight_rows_local`` derives a
#: skew-buffered per-shard bound from it. Inexact for intermediates
#: (a join can outgrow its inputs) — overflow falls back to this
#: class's whole-program regrow ladder, exactly like any other
#: defaulted bound.
_ROW_HINT: contextvars.ContextVar = contextvars.ContextVar(
    "cylon_row_hint", default=None)


def current_row_hint() -> "int | None":
    return _ROW_HINT.get()


@contextlib.contextmanager
def row_hint(rows: "int | None"):
    """Ambient input-row bucket for defaulted exchange bounds chosen
    while tracing (see :data:`_ROW_HINT`)."""
    tok = _ROW_HINT.set(rows)
    try:
        yield
    finally:
        _ROW_HINT.reset(tok)


def _result_tables(out):
    """Tables reachable in a query result (pytree of Tables/DataFrames)."""
    from cylon_tpu.table import Table

    found = []

    def visit(x):
        if isinstance(x, Table):
            found.append(x)
            return
        # DataFrame and containers
        t = getattr(x, "table", None)
        if isinstance(t, Table):
            found.append(t)
            return
        if isinstance(x, (list, tuple)):
            for v in x:
                visit(v)
        elif isinstance(x, dict):
            for v in x.values():
                visit(v)

    visit(out)
    return found


#: arrays this small ride the overflow check's batched fetch (below a
#: page of f64s — scalars and tiny vectors, never column buffers)
_PREFETCH_ELEMS = 512


def _result_scalars(out):
    """Small bare jax arrays in a query result (scalar aggregates, tiny
    vectors) — NOT table columns. Prefetched with the overflow check so
    the caller's own ``float(np.asarray(x))`` hits the host cache
    instead of paying a second host sync (q6/q14/q17-shaped
    queries return only scalars)."""
    found = []

    def visit(x):
        if isinstance(x, jax.Array):
            if x.size <= _PREFETCH_ELEMS and \
                    getattr(x, "is_fully_addressable", True):
                found.append(x)
            return
        if hasattr(x, "table") or hasattr(x, "columns"):
            return  # tables fetch via nrows; columns via to_pandas
        if isinstance(x, (list, tuple)):
            for v in x:
                visit(v)
        elif isinstance(x, dict):
            for v in x.values():
                visit(v)

    visit(out)
    return found


#: result tables whose buffers total at most this many bytes ride the
#: overflow check's batched transfer too — a later ``to_pandas`` then
#: reads host caches instead of paying its own host sync
_PREFETCH_TABLE_BYTES = 4 << 20


def _input_row_bucket(dyn_pos, dyn_kw) -> "int | None":
    """Power-of-2 bucket of the largest concrete input table's TRUE
    total rows — the per-call static row hint tight exchange sizing
    reads under the trace (see :data:`_ROW_HINT`). The count memo
    plumbing (batched fill, poison rules) is
    ``dist_ops.batched_true_rows`` — ONE home for the convention.
    Returns None — default sizing — when there are no input tables,
    any input is poisoned, or a count is not host-reachable."""
    from cylon_tpu.parallel.dist_ops import batched_true_rows
    from cylon_tpu.utils import pow2_bucket

    tables = _result_tables((list(dyn_pos), dyn_kw))
    if not tables:
        return None
    rows = batched_true_rows(tables)
    if rows is None:
        return None
    return pow2_bucket(max(rows))


def _check_overflow(out, bad=None) -> None:
    """Host-side: raise OutOfCapacity if any result shard overflowed
    (poisoned nrows > local capacity — see ``parallel.shuffle.poison``)
    or the registered poison flag ``bad`` fired.

    ONE batched device->host transfer covers the flag, every result
    table's row counts, small result scalars, and the column buffers of
    small (bucket-sliced) result tables (async copies issued together,
    then gathered — the ``Table.to_pandas`` pattern): each separate
    ``np.asarray`` is a host sync of its own, and this check + result
    fetch used to pay three of them per compiled-query call."""
    import numpy as np

    from cylon_tpu.parallel import dtable

    tables = _result_tables(out)
    leaves = [t.nrows for t in tables
              if getattr(t.nrows, "is_fully_addressable", True)]
    leaves.extend(_result_scalars(out))
    for t in tables:
        if dtable.is_distributed(t):
            continue
        nbytes = sum(c.data.size * c.data.dtype.itemsize
                     + (c.validity.size if c.validity is not None else 0)
                     for c in t.columns.values())
        if nbytes <= _PREFETCH_TABLE_BYTES:
            for c in t.columns.values():
                leaves.append(c.data)
                if c.validity is not None:
                    leaves.append(c.validity)
    if bad is not None:
        leaves.append(bad)
    telemetry.counter("plan.prefetch_bytes").inc(sum(
        int(getattr(x, "size", 0)) * x.dtype.itemsize
        for x in leaves if hasattr(x, "dtype")))
    from cylon_tpu import watchdog

    # batch; host values now cached per array. The one synchronous
    # device->host wait of a compiled-query call — a wedged chip hangs
    # exactly here, so it is a bounded watchdog section (never
    # retryable: re-fetching from a wedged device re-hangs)
    watchdog.bounded(lambda: jax.device_get(leaves), "overflow_fetch",
                     detail=f"{len(leaves)} leaves")
    if bad is not None and bool(np.asarray(bad)):
        raise OutOfCapacity(
            "an op inside the compiled query overflowed its "
            "capacity bound")
    for t in tables:
        if dtable.is_distributed(t):
            dtable.dist_num_rows(t)
        else:
            n = int(np.asarray(t.nrows))
            if n > t.capacity:
                raise OutOfCapacity(
                    f"result rows {n} exceed capacity {t.capacity}")


def _map_result_tables(out, fn):
    """Rebuild a query-result pytree with ``fn`` applied to every Table
    (DataFrames re-wrapped). Visits tables in the same order as
    :func:`_result_tables`."""
    from cylon_tpu.table import Table

    def walk(x):
        if isinstance(x, Table):
            return fn(x)
        t = getattr(x, "table", None)
        if isinstance(t, Table) and hasattr(type(x), "_wrap"):
            return type(x)._wrap(fn(t), getattr(x, "_index", None))
        if isinstance(x, list):
            return [walk(v) for v in x]
        if isinstance(x, tuple):
            return tuple(walk(v) for v in x)
        if isinstance(x, dict):
            return {k: walk(v) for k, v in x.items()}
        return x

    return walk(out)


def _shrink_results(out):
    """Trim result buffers to power-of-2 buckets of their true row
    counts. Compiled queries keep intermediate static capacities all
    the way to the output (no per-op host shrink), so a 4-row groupby
    result can sit in a 600k-row buffer — materialising that
    transfers the whole buffer for a screenful of rows. The row counts were just fetched by the overflow
    check, so this costs no extra sync; distributed tables keep their
    shard layout (the mesh contract)."""
    from cylon_tpu.parallel import dtable

    import os

    if os.environ.get("CYLON_TPU_NO_SHRINK"):
        return out

    def shrink(t):
        if dtable.is_distributed(t):
            return t
        # the row count is host-cached from the overflow check, so
        # shrink_to_fit's num_rows read costs no extra device sync
        return t.shrink_to_fit(only_above=0)

    return _map_result_tables(out, shrink)


def _apply_buckets(out, buckets):
    """Device-side: slice each local result table to its memoized
    power-of-2 bucket capacity (nrows kept — a result that outgrew its
    bucket reads nrows > capacity on the host, which retries with the
    observed size). Distributed tables keep their shard layout."""
    from cylon_tpu.parallel import dtable

    it = iter(buckets)

    def cut(t):
        b = next(it)
        if b is None or dtable.is_distributed(t) or b >= t.capacity:
            return t
        # with_capacity clamps nrows to the new capacity — restore the
        # TRUE count so an outgrown bucket reads nrows > capacity on
        # the host instead of silently truncating the result
        return t.with_capacity(b).with_nrows(t.nrows)

    return _map_result_tables(out, cut)


class CompiledQuery:
    """A query function compiled to one XLA program per capacity scale.

    Call it like the original function. Table/DataFrame/array arguments
    (positional or keyword, possibly nested in dicts/lists) are traced;
    every other argument must be hashable and becomes part of the
    compile key.
    """

    def __init__(self, fn, *, check=True):
        self._fn = fn
        self._check = check
        #: ONE lock for the three memo structures below. A CompiledQuery
        #: is shared across serving threads (``shared_compiled``) — the
        #: memos must obey a lock discipline: every read-modify-write
        #: (LRU reorder, widen-only merge, first-sight counting) holds
        #: ``_mu``; the expensive part (the jitted dispatch itself)
        #: never does. jax.jit's own executable cache is thread-safe,
        #: so concurrent first calls at worst trace twice — the memo
        #: bookkeeping here must never corrupt, double-count, or lose
        #: a widen under that race.
        self._mu = threading.Lock()
        self._scale_memo: dict = {}  # static key -> known-good scale
        #: (static key, scale, row hint, dyn-arg shape signature)
        #: 4-tuples already dispatched, LRU-ordered — first sight of a
        #: tuple is (at most) one fresh XLA program build, counted as
        #: ``plan.compile_count`` (the persistent on-disk cache may make
        #: some of these cheap; the counter tracks program-shape churn,
        #: which is what the capacity ladder is sized to bound). The
        #: shape signature matters: the same static key re-traces when a
        #: dynamic argument's buffer shapes change (pow2 capacities of
        #: bigger inputs), and those recompiles are exactly the churn.
        #: Re-sight of a tuple is a ``plan.cache_hits``; first sight a
        #: ``plan.cache_misses``; the store is bounded
        #: (``CYLON_TPU_PLAN_CACHE_ENTRIES``) with oldest-first
        #: eviction counted as ``plan.cache_evictions`` — eviction
        #: forgets only the seen-shape bookkeeping (a later identical
        #: call re-counts a miss; jax's executable cache still holds
        #: the program).
        self._compiled: collections.OrderedDict = collections.OrderedDict()
        #: static key -> per-result-table pow2 capacity buckets. After
        #: the first call observes the result sizes, later calls
        #: compile a variant that emits bucket-sized output buffers —
        #: so the overflow check's ONE batched transfer also carries
        #: the (small) result columns and a following to_pandas reads
        #: host caches: one host sync per call instead of three
        self._size_memo: dict = {}

        def traced(scale, hint, static_pos, static_kw, dyn_pos,
                   **dyn_kw):
            import jax.numpy as jnp

            n = len(static_pos) + len(dyn_pos)
            slots = dict(static_pos)
            dyn_idx = (i for i in range(n) if i not in slots)
            slots.update(zip(dyn_idx, dyn_pos))
            flags: list = []
            with capacity_scale(scale), row_hint(hint), \
                    _collect_flags(flags):
                out = fn(*(slots[i] for i in range(n)),
                         **dict(static_kw), **dyn_kw)
            with _tracing.scope("plan"):
                bad = functools.reduce(jax.numpy.logical_or, flags,
                                       jnp.zeros((), bool))
            return out, bad

        # the bucket slice is a SEPARATE tiny program composed after
        # the main one (an extra async dispatch, ~free): folding it
        # into `traced` would recompile the whole query — minutes of
        # XLA time for a big TPC-H program — the first time its result
        # sizes are known
        def slicer(buckets, out):
            with _tracing.scope("plan"):
                return _apply_buckets(out, buckets)

        # each program is named after the query (jit(q1), jit(q1_slice)),
        # so a device trace says which query an operation belongs to
        name = getattr(fn, "__name__", "query")
        if not name.isidentifier():
            name = "query"  # a lambda
        traced.__name__ = traced.__qualname__ = name
        slicer.__name__ = slicer.__qualname__ = f"{name}_slice"
        self._jitted = jax.jit(traced, static_argnums=(0, 1, 2, 3))
        self._slicer = jax.jit(slicer, static_argnums=0)

    def invalidate(self) -> None:
        """Drop the scale/shape/size memos (one lock hold). The views
        layer calls this through ``query_fn.invalidate()`` when a
        source table's generation advances: the memos key on buffer
        shapes, and an append that grows a table past its pow2
        capacity bucket would otherwise replay a stale size memo.
        jax's executable cache is untouched — identical shapes recompile
        for free; only the bookkeeping resets."""
        with self._mu:
            self._scale_memo.clear()
            self._compiled.clear()
            self._size_memo.clear()

    def lower(self, *args, **kwargs):
        """The query's program as a call with these arguments would
        dispatch it next (its memoized scale, its row hint), lowered
        and not run: ``.as_text()`` shows its HLO, ``.compile()``
        compiles it."""
        dyn_pos, static_pos, static_kw, dyn_kw = _split_args(args, kwargs)
        with self._mu:
            scale = self._scale_memo.get((static_pos, static_kw), 1)
        return self._jitted.lower(scale, self._row_hint(dyn_pos, dyn_kw),
                                  static_pos, static_kw, tuple(dyn_pos),
                                  **dyn_kw)

    def _row_hint(self, dyn_pos, dyn_kw):
        """The count-driven row bucket, which rides the compile key:
        pow2 bucketing means it changes (and retraces) only when the
        input's true row count crosses a power of two, exactly like the
        capacity-scale ladder bounds its shape space. check=False
        queries skip the probe entirely: they promise no host sync, and
        with no overflow check there is no regrow ladder to repair a
        hint-shrunk bound that real data outgrows."""
        if self._check and tight_enabled() and adaptive_enabled():
            return _input_row_bucket(dyn_pos, dyn_kw)
        return None

    def __call__(self, *args, **kwargs):
        import numpy as np

        from cylon_tpu.parallel import dtable
        from cylon_tpu.telemetry import memory as _memory
        from cylon_tpu.utils import pow2_bucket
        from cylon_tpu.utils.tracing import span as _span

        dyn_pos, static_pos, static_kw, dyn_kw = _split_args(args, kwargs)
        key = (static_pos, static_kw)
        with self._mu:
            scale = self._scale_memo.get(key, 1)
            buckets = self._size_memo.get(key) if self._check else None
        hint = self._row_hint(dyn_pos, dyn_kw)
        shape_sig = tuple(
            (getattr(x, "shape", None), str(getattr(x, "dtype", "")))
            for x in jax.tree_util.tree_leaves((tuple(dyn_pos),
                                                dyn_kw)))
        while True:
            entry = (key, scale, hint, shape_sig)
            with self._mu:
                hit = entry in self._compiled
                if hit:
                    self._compiled.move_to_end(entry)
                else:
                    self._compiled[entry] = True
                    evicted = 0
                    while len(self._compiled) > _cache_entries():
                        self._compiled.popitem(last=False)
                        evicted += 1
            telemetry.counter("plan.cache_hits" if hit
                              else "plan.cache_misses").inc()
            if not hit:
                if evicted:
                    telemetry.counter("plan.cache_evictions").inc(evicted)
                telemetry.counter("plan.compile_count").inc()
                _trace.instant("plan.compile", cat="plan", scale=scale,
                               row_hint=hint,
                               fn=getattr(self._fn, "__name__", "?"))
            # the compile-vs-execute split the ANALYZE profile reads:
            # on a cache miss this span is dominated by trace+compile
            # (dispatch is async), on a hit it is pure host dispatch;
            # the plan.fetch span below is the wait on real execution.
            # An allocation failure here gets the resident-consumer
            # forensics dump (telemetry.memory) before it propagates.
            with _span("plan.dispatch", cat="stage", cache_hit=hit), \
                    _memory.forensics("plan.dispatch"):
                # seeded-fault hook (the "plan" injection point): the
                # OOM→spill fallback layer injects deterministic
                # allocation failures exactly where a real
                # RESOURCE_EXHAUSTED would surface
                from cylon_tpu import resilience

                resilience.inject(
                    "plan", getattr(self._fn, "__name__", "?"))
                raw, bad = self._jitted(scale, hint, static_pos,
                                        static_kw, tuple(dyn_pos),
                                        **dyn_kw)
            if not self._check:
                return raw
            out = self._slicer(buckets, raw) if buckets is not None \
                else raw
            try:
                # registered flags (covers scalar-only results and
                # intermediate poison masked by downstream ops) + the
                # result-table nrows scan + small result buffers, all
                # fetched in ONE transfer
                with _span("plan.fetch", cat="stage"), \
                        _memory.forensics("plan.fetch"):
                    _check_overflow(out, bad)
            except OutOfCapacity as err:
                if buckets is not None and not bool(np.asarray(bad)):
                    # maybe only the memoized result buckets were
                    # outgrown — but an UNFLAGGED genuine overflow
                    # (nrows-poison from a local op, a distributed
                    # shard bound) reads exactly the same here. The
                    # UNBUCKETED ground truth is already in hand (the
                    # slicer is post-hoc): check it directly, no
                    # re-dispatch
                    buckets = None
                    try:
                        _check_overflow(raw, bad)
                        out = raw
                    except OutOfCapacity as err2:
                        err = err2
                        out = None
                else:
                    out = None
                if out is None:
                    # genuine op overflow: regrow the capacity budget
                    telemetry.counter("plan.overflow_events",
                                      site="compiled").inc()
                    _trace.instant("capacity.overflow", cat="capacity",
                                   site="compiled", scale=scale)
                    if scale >= MAX_SCALE:
                        raise err
                    scale *= 2
                    telemetry.counter("plan.capacity_rescales",
                                      site="compiled").inc()
                    _trace.instant("capacity.regrow", cat="capacity",
                                   site="compiled", scale=scale)
                    continue
            observed = tuple(
                None if dtable.is_distributed(t)
                else pow2_bucket(int(np.asarray(t.nrows)))
                for t in _result_tables(out))
            with self._mu:
                # scale memo is widen-only too: a concurrent call that
                # regrew further must not be clobbered back down by a
                # call that succeeded at a smaller scale
                if scale > self._scale_memo.get(key, 0):
                    self._scale_memo[key] = scale
                old = self._size_memo.get(key)
                if old is not None:
                    # widen-only: shrinking the memo would make every
                    # later larger-result call pay a wasted bucketed
                    # dispatch + overflow round trip before widening
                    # back (and, under concurrency, lose a racing
                    # call's wider observation)
                    observed = tuple(
                        None if n is None
                        else (n if o is None else max(o, n))
                        for o, n in zip(old, observed))
                if observed != old and any(b is not None
                                           for b in observed):
                    # all-None/empty buckets (scalar-only or
                    # distributed results) would recompile an identical
                    # program for a no-op _apply_buckets — leave the
                    # memo unset
                    self._size_memo[key] = observed
            return _shrink_results(out)


def _cache_entries() -> int:
    """Bound on the per-query seen-shape LRU (``CYLON_TPU_PLAN_CACHE_ENTRIES``,
    default 4096 — far above any sane shape churn; the knob exists so a
    pathological workload can't grow the bookkeeping without bound)."""
    import os

    try:
        return max(int(os.environ.get("CYLON_TPU_PLAN_CACHE_ENTRIES",
                                      "4096")), 1)
    except ValueError:
        return 4096


#: process-wide compiled-query cache: (fn, check) -> CompiledQuery.
#: THE cross-request plan cache of the serving layer — N clients
#: submitting the same query function share ONE CompiledQuery, so the
#: pow2 input-row bucket + shape signature becomes the cross-request
#: cache key and the N-1 later clients' calls are ``plan.cache_hits``
#: (one trace paid for the fleet).
_SHARED_MU = threading.Lock()
_SHARED: "dict[tuple, CompiledQuery]" = {}


def shared_compiled(fn, *, check: bool = True) -> CompiledQuery:
    """Get-or-create the process-wide :class:`CompiledQuery` for ``fn``
    (keyed on the function object + ``check``). Unlike
    :func:`compile_query` — which builds a fresh program cache per call
    site — every caller of ``shared_compiled(q3)`` shares one scale/
    size/shape memo, which is what makes a multi-tenant serving layer
    pay one trace per query shape instead of one per client."""
    key = (fn, bool(check))
    cq = _SHARED.get(key)
    if cq is None:
        with _SHARED_MU:
            cq = _SHARED.get(key)
            if cq is None:
                cq = functools.wraps(fn)(CompiledQuery(fn, check=check))
                _SHARED[key] = cq
    return cq


def plan_cache_stats() -> dict:
    """Hit/miss/eviction totals of the compiled-plan cache plus the
    derived hit rate — the block the serve bench record embeds."""
    hits = telemetry.total("plan.cache_hits")
    misses = telemetry.total("plan.cache_misses")
    looked = hits + misses
    return {
        "hits": hits,
        "misses": misses,
        "evictions": telemetry.total("plan.cache_evictions"),
        "hit_rate": (hits / looked) if looked else 0.0,
        "shared_queries": len(_SHARED),
    }


def query_fingerprint(name: str, args=(), kwargs=None) -> "str | None":
    """Stable fingerprint of a REGISTERED query invocation — the first
    half of the serve layer's result-cache key ``(fingerprint,
    table-version vector)``.

    Keyed on the query NAME (the durable, cross-process identity the
    journal already records) plus the canonical-JSON form of its
    arguments, hashed with sha256 — so two processes (an engine and a
    fleet router, or two engines behind one router) derive the SAME
    fingerprint for the same logical request without sharing any
    in-memory state. Returns None when the arguments are not
    JSON-canonicalizable (closures, arrays, ...): such an invocation
    has no stable identity and must never be coalesced or cached."""
    import hashlib
    import json

    try:
        blob = json.dumps(
            {"name": str(name), "args": list(args),
             "kwargs": dict(kwargs or {})},
            sort_keys=True, separators=(",", ":"), allow_nan=False)
    except (TypeError, ValueError):
        return None
    return hashlib.sha256(blob.encode("utf-8")).hexdigest()


def _is_dynamic(x) -> bool:
    import numpy as np

    from cylon_tpu.table import Table

    if isinstance(x, Table) or hasattr(x, "table"):
        return True
    if isinstance(x, (list, tuple)):
        return any(_is_dynamic(v) for v in x)
    if isinstance(x, dict):
        return any(_is_dynamic(v) for v in x.values())
    return isinstance(x, (jax.Array, np.ndarray))


def _split_args(args, kwargs):
    """Partition the call's arguments into traced (Tables/DataFrames/
    arrays, nested ok) and static (everything else, made hashable).
    Positional statics are carried as (index, value) pairs so the traced
    wrapper can reassemble the original argument order."""
    dyn_pos, static_pos = [], []
    for i, v in enumerate(args):
        if _is_dynamic(v):
            dyn_pos.append(v)
        else:
            static_pos.append((i, _hashable(v)))
    static_kw, dyn_kw = [], {}
    for k, v in kwargs.items():
        if _is_dynamic(v):
            dyn_kw[k] = v
        else:
            static_kw.append((k, _hashable(v)))
    return dyn_pos, tuple(static_pos), tuple(sorted(static_kw)), dyn_kw


def _hashable(v):
    if isinstance(v, list):
        return tuple(_hashable(x) for x in v)
    if isinstance(v, dict):
        return tuple(sorted((k, _hashable(x)) for k, x in v.items()))
    if isinstance(v, set):
        return frozenset(_hashable(x) for x in v)
    return v


def regrow_eager(run, *, bounded: bool):
    """Host-side regrow ladder for ONE eager local dispatch.

    ``run()`` must build and execute the op reading the ambient
    :func:`capacity_scale` for its defaulted bounds and return a local
    Table. ``bounded=True`` (caller passed an explicit capacity) keeps
    the raise-on-overflow contract. Under an outer trace the row count
    is a tracer — the check is skipped and the enclosing
    :class:`CompiledQuery` ladder regrows the whole program instead
    (seeding from ``current_scale()`` keeps the two ladders composable).
    The distributed analog with per-shard count checks is
    ``parallel.dist_ops._adaptive``.
    """
    scale = current_scale()
    while True:
        with capacity_scale(scale):
            t = run()
        if bounded or isinstance(t.nrows, jax.core.Tracer):
            return t
        try:
            t.num_rows  # host sync; raises on overflow
            return t
        except OutOfCapacity:
            telemetry.counter("plan.overflow_events",
                              site="eager").inc()
            _trace.instant("capacity.overflow", cat="capacity",
                           site="eager", scale=scale)
            if scale >= MAX_SCALE:
                raise
            scale *= 2
            telemetry.counter("plan.capacity_rescales",
                              site="eager").inc()
            _trace.instant("capacity.regrow", cat="capacity",
                           site="eager", scale=scale)


def compile_query(fn=None, *, check: bool = True):
    """Decorator/wrapper: compile a whole query into one XLA program
    with automatic capacity regrow (see module docstring).

    ``check=False`` skips the host-side overflow check (and its one
    device sync) — for callers that inspect ``num_rows`` themselves.
    """
    if fn is None:
        return functools.partial(compile_query, check=check)
    return functools.wraps(fn)(CompiledQuery(fn, check=check))
