"""Vectorised relational join (inner / left / right / full outer).

Reference analog: ``cpp/src/cylon/join/`` — dispatcher ``join::JoinTables``
(``join/join.cpp:92-98``), hash join build/probe
(``join/hash_join.cpp:22-31``), sort join with in-place fast path
(``join/sort_join.cpp:215``), result assembly
(``join/join_utils.hpp:34``).

TPU-first algorithm (replaces both hash and sort join): *dense-rank
equi-join*. Concatenate the key columns of both sides, lexsort once, and
assign every distinct key tuple a dense group id (collision-free — no
hash table, no probe loop). Then for each left row the matching right
rows are a contiguous run in the right side's gid-sorted order, and the
variable-size result is materialised by a prefix-sum run-length
expansion into a caller-bounded buffer. Every step is a sort, cumsum,
segment-sum or gather — all static-shape XLA ops that tile onto the TPU.

Cost: O((|L|+|R|) log(|L|+|R|)) like the reference's sort join, but with
no per-row control flow, so the whole join stays inside one jit.

``algorithm="hash"`` routes to the true O(n) bucketed build/probe
(:mod:`cylon_tpu.ops.hash_join` — power-of-2 bucket table, exact key
words as collision tiebreakers, sort fallback when a bucket chain
exceeds the budget). Routing is observable: every call counts
``join.algorithm{kind="requested->chosen"}`` and eager overflow
fallbacks count ``join.overflow_fallbacks`` (see ``docs/joins.md``).
"""

import functools
import os
from typing import Sequence

import jax
import jax.numpy as jnp

from cylon_tpu.column import Column
from cylon_tpu.config import JoinConfig, JoinType
from cylon_tpu.errors import InvalidArgument
from cylon_tpu.ops import kernels
from cylon_tpu.ops.dictenc import unify_dictionaries
from cylon_tpu.ops.selection import take_columns
from cylon_tpu.platform import platform_jit
from cylon_tpu.table import Table
from cylon_tpu.utils.logging import get_logger
from cylon_tpu.utils.tracing import scope

#: one-shot flags for routing downgrades that used to be silent (or,
#: historically, errors): warn the first time, count every time.
_warned: set = set()


def _env_algorithm() -> "str | None":
    """``CYLON_TPU_JOIN_ALGORITHM``: process-wide override of the
    per-call ``algorithm`` hint ("sort" | "hash"; unset/other = respect
    the caller)."""
    v = os.environ.get("CYLON_TPU_JOIN_ALGORITHM", "").lower()
    return v if v in ("sort", "hash") else None


def _warn_once(key: str, msg: str) -> None:
    if key not in _warned:
        _warned.add(key)
        get_logger().warning(msg)


def _route_algorithm(requested: str, how: str,
                     tracing: bool) -> str:
    """Resolve the user-facing ``algorithm`` hint to the kernel
    ``_join_compiled`` dispatches on, and count the decision.

    Returns one of:

    * ``"sort"`` — key-rank sort join (also every fallback target);
    * ``"hash_sort"`` — the legacy murmur-bucket-first sort join
      (``group_sort(hash_first=True)``), the pre-bucketed rendition of
      HASH kept selectable via ``CYLON_TPU_JOIN_HASH_IMPL=sort``;
    * ``"hash_bucketed"`` — bucketed build/probe, no overflow guard
      (the EAGER caller pre-checked chains host-side);
    * ``"hash_guarded"`` — bucketed build/probe with the in-graph
      ``lax.cond`` sort fallback (traced callers cannot sync).

    ``algorithm="hash"`` is a HINT, never a crash: unsupported ``how``
    downgrades to the sort path with a one-shot warning.
    """
    from cylon_tpu import telemetry
    from cylon_tpu.ops import hash_join

    chosen = requested
    if requested == "hash":
        if not hash_join.supported(how):
            # fullouter emits the sorted key union — bucket emission
            # cannot reproduce it; the old code errored/silently
            # downgraded depending on `ordered`, now it is always the
            # documented sort fallback with a one-shot warning
            _warn_once(f"hash-{how}",
                       f'join(algorithm="hash", how="{how}"): bucketed '
                       "hash join does not support this variant; "
                       "taking the sort path (the hint is honored "
                       "where supported, never an error)")
            chosen = "sort"
        elif hash_join.hash_impl() == "sort":
            chosen = "hash_sort"
        else:
            chosen = "hash_guarded" if tracing else "hash_bucketed"
    if chosen != "hash_bucketed":
        # the eager bucketed path counts AFTER its host-side overflow
        # pre-check so a fallback is recorded as exactly one decision
        telemetry.counter("join.algorithm",
                          kind=f"{requested}->{chosen}").inc()
    return chosen


def join(left: Table, right: Table, config: JoinConfig | None = None, *,
         on: Sequence[str] | str | None = None,
         left_on: Sequence[str] | str | None = None,
         right_on: Sequence[str] | str | None = None,
         how: str = "inner",
         suffixes: tuple[str, str] = ("_x", "_y"),
         out_capacity: int | None = None,
         algorithm: str = "sort", ordered: bool = True) -> Table:
    """Equi-join two tables (parity: ``join::JoinTables`` +
    ``Table::Join``; semantics follow pandas ``merge`` — the reference's
    own python-test oracle).

    ``out_capacity`` bounds the static result size (default
    ``left.capacity + right.capacity`` — enough for any 1:N join; raise it
    for N:M key duplication). Overflow is detected host-side via
    ``Table.num_rows``.

    ``ordered=False`` skips restoring pandas' left-frame output order
    (one stable sort of the index pairs) — the row SET is identical.
    The distributed operators use it per shard: the reference's own
    sort-join emits key order, and cross-shard order is
    implementation-defined anyway.

    ``algorithm`` (parity: ``JoinAlgorithm`` {SORT, HASH},
    ``join_config.hpp:25-31``): "sort" groups rows by lexicographic key
    rank; "hash" is the true O(n) bucketed build/probe
    (:mod:`cylon_tpu.ops.hash_join` — the reference's flat_hash_map
    build/probe, ``hash_join.cpp:22-31``), falling back to the sort
    path for unsupported variants (fullouter) and over-budget bucket
    chains. Both are exact; output row sets are identical (and for
    ``ordered=True`` the outputs are byte-identical).
    ``CYLON_TPU_JOIN_ALGORITHM`` overrides the hint process-wide;
    ``CYLON_TPU_JOIN_HASH_IMPL=sort`` pins "hash" to the legacy
    murmur-bucket-first sort ordering. See ``docs/joins.md``.
    """
    if config is not None:
        left_on = list(config.left_on)
        right_on = list(config.right_on)
        how = config.join_type.value
        suffixes = (config.left_suffix, config.right_suffix)
        algorithm = config.algorithm.value
    else:
        if on is not None:
            left_on = right_on = [on] if isinstance(on, str) else list(on)
        else:
            left_on = [left_on] if isinstance(left_on, str) else list(left_on or ())
            right_on = [right_on] if isinstance(right_on, str) else list(right_on or ())
    if not left_on or len(left_on) != len(right_on):
        raise InvalidArgument(f"bad join keys {left_on} / {right_on}")
    how = {"outer": "fullouter", "full_outer": "fullouter"}.get(how, how)
    if how == "right":
        # right join = left join with sides swapped, columns re-ordered
        swapped = join(right, left, left_on=right_on, right_on=left_on,
                       how="left", suffixes=(suffixes[1], suffixes[0]),
                       out_capacity=out_capacity, algorithm=algorithm,
                       ordered=ordered)
        return _reorder_right_join(swapped, left, right, left_on, right_on,
                                   suffixes)
    if how not in ("inner", "left", "fullouter"):
        raise InvalidArgument(f"unknown join type {how!r}")
    algorithm = _env_algorithm() or algorithm
    if algorithm not in ("sort", "hash"):
        raise InvalidArgument(f"unknown join algorithm {algorithm!r}")

    cl, cr = left.capacity, right.capacity
    if out_capacity is not None:
        out_cap = out_capacity
    else:
        # default: enough for any 1:N join; the ambient capacity scale
        # (cylon_tpu.plan) grows it when a caller's regrow loop retries
        from cylon_tpu import plan

        out_cap = (cl + cr) * plan.current_scale()

    # host-side: dictionary unification (string keys) happens before the
    # traced core — device code only sees codes
    left, right, lkeys, rkeys, lvals, rvals = _aligned_keys(
        left, right, left_on, right_on)

    # algorithm routing (observable: join.algorithm counter, see
    # _route_algorithm). Under a trace (shard_map / whole-query plans)
    # the overflow decision must live in-graph; eager callers pre-check
    # the build side's chains host-side and route statically instead —
    # no dual-branch program, and the fallback is counted exactly.
    tracing = any(isinstance(x, jax.core.Tracer)
                  for x in (*lkeys, *rkeys, left.nrows, right.nrows))
    kernel = _route_algorithm(algorithm, how, tracing)
    if kernel == "hash_bucketed":
        from cylon_tpu import telemetry
        from cylon_tpu.ops import hash_join

        if how == "inner" and cl <= cr:
            bkeys, bvals, brows = lkeys, lvals, left.nrows
        else:
            bkeys, bvals, brows = rkeys, rvals, right.nrows
        if hash_join.chain_overflow(bkeys, bvals, brows):
            telemetry.counter("join.overflow_fallbacks").inc()
            kernel = "sort"
        telemetry.counter(
            "join.algorithm",
            kind=("hash->sort_overflow" if kernel == "sort"
                  else "hash->hash_bucketed")).inc()

    # one compiled program for match + expansion + assembly: the eager
    # op-by-op path pays a per-primitive dispatch times hundreds of
    # primitives; jit pays one
    return _join_compiled(left, right, left_on=tuple(left_on),
                          right_on=tuple(right_on), how=how,
                          suffixes=tuple(suffixes), out_cap=int(out_cap),
                          algorithm=kernel, ordered=ordered)


@functools.partial(platform_jit, static_argnames=("left_on", "right_on",
                                                  "how", "suffixes",
                                                  "out_cap", "algorithm",
                                                  "ordered"))
@scope("join")
def _join_compiled(left: Table, right: Table, *, left_on, right_on, how,
                   suffixes, out_cap, algorithm="sort",
                   ordered=True) -> Table:
    lkeys = [left.column(n).data for n in left_on]
    rkeys = [right.column(n).data for n in right_on]
    lvals = [left.column(n).validity for n in left_on]
    rvals = [right.column(n).validity for n in right_on]
    if algorithm in ("hash_bucketed", "hash_guarded"):
        from cylon_tpu.ops import hash_join

        sort_fb = None
        if algorithm == "hash_guarded":
            def sort_fb():
                return _join_indices(lkeys, lvals, left.nrows, rkeys,
                                     rvals, right.nrows, how, out_cap,
                                     hash_first=False, ordered=ordered)
        with scope("join", "match"):
            left_idx, right_idx, total = hash_join.bucketed_join_indices(
                lkeys, lvals, left.nrows, rkeys, rvals, right.nrows, how,
                out_cap, ordered, sort_fallback=sort_fb)
    else:
        left_idx, right_idx, total = _join_indices(
            lkeys, lvals, left.nrows, rkeys, rvals, right.nrows, how,
            out_cap, hash_first=algorithm == "hash_sort",
            ordered=ordered)
    with scope("join", "gather"):
        res = _assemble(left, right, list(left_on), list(right_on),
                        suffixes, left_idx, right_idx, total, how)
        return kernels.carry_overflow(res, left, right)


def _aligned_keys(left, right, left_on, right_on):
    """Key columns with matching physical dtypes and shared dictionaries.
    Returns updated tables with the re-encoded key columns substituted
    back, so output assembly (gather + coalesce) sees the same codes the
    match ran on."""
    lkeys, rkeys, lvals, rvals = [], [], [], []
    for ln, rn in zip(left_on, right_on):
        lc, rc = left.column(ln), right.column(rn)
        if lc.dtype.is_bytes or rc.dtype.is_bytes:
            from cylon_tpu.ops.bytescol import align_storages

            if not (lc.dtype.is_bytes or lc.dtype.is_dictionary) or \
                    not (rc.dtype.is_bytes or rc.dtype.is_dictionary):
                raise InvalidArgument(
                    f"join key {ln}/{rn}: string vs non-string")
            lc, rc = align_storages([lc, rc])
            left = left.add_column(ln, lc)
            right = right.add_column(rn, rc)
            lkeys.append(lc.data)
            rkeys.append(rc.data)
            lvals.append(lc.validity)
            rvals.append(rc.validity)
            continue
        if lc.dtype.is_dictionary != rc.dtype.is_dictionary:
            raise InvalidArgument(
                f"join key {ln}/{rn}: string vs non-string")
        if lc.dtype.is_dictionary:
            lc, rc = unify_dictionaries([lc, rc])
            left = left.add_column(ln, lc)
            right = right.add_column(rn, rc)
        elif lc.data.dtype != rc.data.dtype:
            raise InvalidArgument(
                f"join key {ln}/{rn}: dtype mismatch "
                f"{lc.data.dtype} vs {rc.data.dtype} (cast first)")
        lkeys.append(lc.data)
        rkeys.append(rc.data)
        lvals.append(lc.validity)
        rvals.append(rc.validity)
    return left, right, lkeys, rkeys, lvals, rvals


def _join_indices(lkeys, lvals, lrows, rkeys, rvals, rrows, how, out_cap,
                  hash_first: bool = False, ordered: bool = True):
    """Core: (left_idx, right_idx, total) gather plans of length out_cap.

    -1 in either index array marks a null (non-matched) side for that
    output row.

    Everything runs in the COMBINED GROUP-SORTED layout from one
    ``group_sort`` over both sides' keys (the row iota as a sub-order
    key: left indices < cl precede right ones, so each group's left
    rows sort first, and its uniqueness makes the order total — the
    sort runs unstable). Per-group values
    — right-run count, right-run start — broadcast to every row by
    segmented scans (``forward_fill``/``reverse_fill``: cumsum + cummax
    encodings), NOT by random gathers: on TPU a same-size gather costs
    ~10x an elementwise scan, and the previous row-order formulation
    paid an inverse scatter, a second sort, and two [rows] gathers for
    what three scans now compute in place. The irreducible gathers that
    remain are the run expansion itself (``packed[parent]``, the right
    partner lookup) plus the final ``take_columns``. Output order is
    restored to pandas' by one stable sort of the [out_cap] index pairs
    (inner/left: left-frame order; fullouter: the sorted key union with
    null keys last).
    """
    with scope("join", "keys"):
        cl = lkeys[0].shape[0]
        cr = rkeys[0].shape[0]
        ncomb = cl + cr

        ckeys = [jnp.concatenate([l, r]) for l, r in zip(lkeys, rkeys)]
        cvals = []
        for lv, rv, lk, rk in zip(lvals, rvals, lkeys, rkeys):
            if lv is None and rv is None:
                cvals.append(None)
            else:
                lv_ = jnp.ones(cl, bool) if lv is None else lv
                rv_ = jnp.ones(cr, bool) if rv is None else rv
                cvals.append(jnp.concatenate([lv_, rv_]))
        cvalid = jnp.concatenate([kernels.valid_mask(cl, lrows),
                                  kernels.valid_mask(cr, rrows)])

        iota_c = jnp.arange(ncomb, dtype=jnp.int32)
    # the row iota is BOTH the sub-order key (left indices < cl precede
    # right ones, so each group's left rows sort first) and the
    # original-row payload — one operand instead of a side flag plus a
    # payload; its uniqueness makes the order total, so the sort can
    # skip stability bookkeeping
    want_gid = ordered and how == "fullouter"
    with scope("join", "sort"):
        gid_s, _, (orig_u,) = kernels.group_sort(
            ckeys, cvalid, cvals, hash_first=hash_first,
            suborder=[iota_c.astype(jnp.uint32)], stable=False)
    with scope("join", "match"):
        orig_s = orig_u.astype(jnp.int32)

        valid_s = gid_s < ncomb
        is_r = valid_s & (orig_s >= cl)
        is_l = valid_s & (orig_s < cl)
        boundary = valid_s & ((gid_s != jnp.roll(gid_s, 1)) | (iota_c == 0))
        is_end = valid_s & (jnp.roll(boundary, -1) | ~jnp.roll(valid_s, -1)
                            | (iota_c == ncomb - 1))

        cum_r = kernels.fast_cumsum(is_r.astype(jnp.int32))
        cum_l = kernels.fast_cumsum(is_l.astype(jnp.int32))
        s_g = kernels.forward_fill(boundary, iota_c)
        rb = kernels.forward_fill(boundary, cum_r - is_r)
        lb = kernels.forward_fill(boundary, cum_l - is_l)
        rcnt = kernels.reverse_fill(is_end, cum_r) - rb    # rights in my group
        lcnt = kernels.reverse_fill(is_end, cum_l) - lb
        right_start = s_g + lcnt   # sorted position of the group's first right

        match_counts = jnp.where(is_l, rcnt, 0)
        if how == "inner":
            ecounts = match_counts
        else:  # left / fullouter: unmatched left rows still emit one row
            ecounts = jnp.where(is_l, jnp.maximum(match_counts, 1), 0)

        # run-length expansion (row i emits ecounts[i] output slots, the
        # static-shape stand-in for the reference's dynamic index vectors,
        # join/join_utils.hpp:34): scatter each run's sorted position at its
        # start offset, running-max fills the run; the per-parent values
        # (run offset, match count, right-run start, original row) ride ONE
        # packed row-gather
        offs = kernels.exclusive_cumsum(ecounts)
        total = (offs[-1] + ecounts[-1] if ncomb else jnp.int32(0)
                 ).astype(jnp.int32)
        start = jnp.where(ecounts > 0, offs, out_cap).astype(jnp.int32)
        mark = jnp.full(out_cap, -1, jnp.int32).at[start].max(iota_c,
                                                              mode="drop")
        parent = jnp.clip(kernels.fast_cummax(mark), 0, max(ncomb - 1, 0))
        # the order-key gid column rides the packed gather only when the
        # fullouter restore needs it (gathers are priced ~10x elementwise)
        pcols = [offs.astype(jnp.int32), match_counts, right_start, orig_s]
        if want_gid:
            pcols.append(gid_s)
        packed = jnp.stack(pcols, axis=1)           # [ncomb, 4 or 5]
        g = packed[parent]                          # one packed row-gather
        j = jnp.arange(out_cap, dtype=jnp.int32)
        within = j - g[:, 0]
        matched = g[:, 1] > 0
        r_pos = jnp.clip(g[:, 2] + within, 0, max(ncomb - 1, 0))
        right_idx = jnp.where(matched, orig_s[r_pos] - cl, -1)
        left_idx = g[:, 3]
        slot_gid = g[:, 4] if want_gid else None

        if how == "fullouter":
            extra_mask = is_r & (lcnt == 0)
            perm_s, n_extra = kernels.compact_mask(extra_mask, valid_s)
            shifted = jnp.clip(j - total, 0, max(ncomb - 1, 0))
            ecols = [orig_s] + ([gid_s] if want_gid else [])
            epair = jnp.stack(ecols, axis=1)[perm_s[shifted]]
            in_main = j < total
            left_idx = jnp.where(in_main, left_idx, -1)
            right_idx = jnp.where(in_main, right_idx, epair[:, 0] - cl)
            if want_gid:
                slot_gid = jnp.where(in_main, slot_gid, epair[:, 1])
            total = total + n_extra

    if ordered:
        with scope("join", "order"):
            # restore pandas order with one stable sort of the index pairs.
            # inner/left: left-frame order (slots of one left row keep
            # their right-frame order by stability). fullouter: pandas
            # sorts the key union lexicographically with nulls last per
            # level — exactly GROUP order (group_sort ranks null keys with
            # the max word per level), so the group id is the sort key
            # (right-only extras interleave by key; within a key the
            # left-frame emission order is preserved by stability). Valid
            # slots are contiguous at the front either way, so
            # ordered=False simply skips this.
            valid_slot = j < total
            if how == "fullouter":
                okey = jnp.where(valid_slot, slot_gid.astype(jnp.uint32),
                                 jnp.uint32(0xFFFFFFFF))
            else:
                # every valid inner/left slot has a left-row parent
                okey = jnp.where(valid_slot, left_idx.astype(jnp.uint32),
                                 jnp.uint32(0xFFFFFFFF))
            _, left_idx, right_idx = jax.lax.sort(
                (okey, left_idx, right_idx), num_keys=1, is_stable=True)

    return left_idx, right_idx, total


def _assemble(left, right, left_on, right_on, suffixes,
              left_idx, right_idx, total, how):
    """Gather output columns. Shared key names coalesce (left value,
    falling back to right for right-only rows); other name collisions get
    suffixes — pandas merge naming."""
    shared_keys = [ln for ln, rn in zip(left_on, right_on) if ln == rn]
    lnull = left_idx < 0
    rnull = right_idx < 0

    lgather = take_columns(left, left_idx, total,
                           null_mask=lnull if how == "fullouter" else None)
    rgather = take_columns(right, right_idx, total,
                           null_mask=rnull if how != "inner" else None)

    out = {}
    overlap = (set(left.column_names) & set(right.column_names))
    for name in left.column_names:
        c = lgather.column(name)
        if name in shared_keys:
            rc_name = name  # same name on right
            rc = rgather.column(rc_name)
            out[name] = _coalesce(c, rc) if how == "fullouter" else c
        elif name in overlap:
            out[name + suffixes[0]] = c
        else:
            out[name] = c
    for name in right.column_names:
        if name in shared_keys:
            continue
        rc = rgather.column(name)
        if name in overlap:
            out[name + suffixes[1]] = rc
        else:
            out[name] = rc
    return Table(out, total)


def _coalesce(a: Column, b: Column) -> Column:
    """a where valid else b (key coalescing for full outer joins)."""
    av = jnp.ones(a.capacity, bool) if a.validity is None else a.validity
    bv = jnp.ones(b.capacity, bool) if b.validity is None else b.validity
    data = jnp.where(av[:, None] if a.data.ndim == 2 else av,
                     a.data, b.data)
    validity = av | bv
    # content equality, matching unify_dictionaries' pass-through for
    # equal-content dictionaries (independently ingested same-value sets)
    if a.dtype.is_dictionary and a.dictionary != b.dictionary:
        raise InvalidArgument("coalesce across different dictionaries")
    return Column(data, validity, a.dtype, a.dictionary)


def _reorder_right_join(swapped: Table, left, right, left_on, right_on,
                        suffixes):
    """Restore left-then-right column order after the swapped left join."""
    shared_keys = {ln for ln, rn in zip(left_on, right_on) if ln == rn}
    overlap = set(left.column_names) & set(right.column_names)
    order = []
    for name in left.column_names:
        if name in shared_keys:
            order.append(name)
        elif name in overlap:
            order.append(name + suffixes[0])
        else:
            order.append(name)
    for name in right.column_names:
        if name in shared_keys:
            continue
        order.append(name + suffixes[1] if name in overlap else name)
    return swapped.select(order)
