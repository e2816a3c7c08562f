"""True O(n) bucketed hash join: build/probe instead of sort.

Reference analog: ``join/hash_join.cpp:22-31`` — build the smaller side
into a flat_hash_map, probe the larger side row by row. The TPU
rendition: a power-of-2 open bucket table of fixed-width chains
(``CYLON_TPU_JOIN_BUCKET_WIDTH`` entries per bucket, entry-major
``[width, nb]`` layout so the lane dimension stays pow-2-aligned),
built from the 32-bit murmur row hash the shuffle already computes
(:mod:`cylon_tpu.ops.hash`), with the canonical u32 key-word streams
(``hash._row_words`` — nulls zeroed + validity word, so null == null
exactly like ``kernels.group_sort``) as exact collision tiebreakers.

Two bit-identical implementations per phase, selected by
:func:`pallas_kernels.bucket_join_ok`:

* the Pallas kernels (``bucket_build`` / ``bucket_probe``): the table
  VMEM-resident, one sequential pass per side;
* the jnp twins below: ``width`` scatter-min rounds (build) and
  ``width`` gather+compare rounds (probe) through XLA.

Chains longer than ``width`` cannot be stored: the build reports an
overflow count and :func:`bucketed_join_indices` falls back to the
UNCHANGED sort join (the caller passes it in) — eagerly when the
caller could pre-check host-side, via ``lax.cond`` when traced. Either
way the output is byte-identical to the sort join's (both restore
pandas order for ``ordered=True``; for ``ordered=False`` the row SET
is identical, order implementation-defined like any distributed shard).

Supported: ``how`` in {"inner", "left"} ("right" is swapped into
"left" by ``ops.join.join`` before routing; "fullouter" keeps the sort
path — the key-union output order is a sort by construction).
"""

import functools
import os

import jax
import jax.numpy as jnp

from cylon_tpu.ops import kernels
from cylon_tpu.ops import pallas_kernels as pk
from cylon_tpu.ops.hash import _row_words, hash_columns

#: default entries per bucket — the chain budget a bucket's key
#: multiplicities must exceed to force the sort fallback. Sized from
#: the compound-Poisson chain tail of UNIFORM keys with natural
#: duplication (bucket load = sum of key multiplicities): at load <= 1
#: the max chain over nb buckets grows ~log(nb)/loglog(nb) — measured
#: max 15 @ 1M rows, 16 @ 10M, 17 @ 100M — so 16 keeps uniform data on
#: the fast path through the 10M scale and any fixed width hands the
#: extreme-scale tail to the sort fallback BY DESIGN (recorded via
#: ``join.overflow_fallbacks``, see docs/joins.md).
DEFAULT_BUCKET_WIDTH = 16

SUPPORTED_HOW = ("inner", "left")


def bucket_width() -> int:
    """Entries per bucket (``CYLON_TPU_JOIN_BUCKET_WIDTH``)."""
    try:
        w = int(os.environ.get("CYLON_TPU_JOIN_BUCKET_WIDTH",
                               DEFAULT_BUCKET_WIDTH))
    except ValueError:
        return DEFAULT_BUCKET_WIDTH
    return max(1, min(w, 30))  # mask bits must fit an int32


def table_slots(build_cap: int) -> int:
    """Bucket count: pow-2 ``>= build capacity`` (expected chain length
    ~1 under uniform hashing, so ``width`` absorbs duplicates and
    collisions up to the fallback threshold)."""
    from cylon_tpu.utils import pow2_bucket

    return pow2_bucket(max(build_cap, 1), minimum=16)


def supported(how: str) -> bool:
    return how in SUPPORTED_HOW


# ------------------------------------------------------------ jnp twins

def _build_jnp(bids: jax.Array, nb: int, width: int):
    """Bit-identical twin of ``pallas_kernels.bucket_build``: entry e
    of bucket b holds the (e+1)-th smallest row id hashing to b —
    ``width`` scatter-min rounds (each round the smallest unplaced row
    per bucket wins its entry) reproduce the kernel's ascending
    first-free-entry insertion exactly."""
    cap = bids.shape[0]
    iota = jnp.arange(cap, dtype=jnp.int32)
    table = jnp.full((width, nb), -1, jnp.int32)
    unplaced = bids >= 0
    safe = jnp.where(unplaced, bids, 0)
    for e in range(width):
        idx = jnp.where(unplaced, bids, nb)
        cand = jnp.full(nb, cap, jnp.int32).at[idx].min(iota, mode="drop")
        won = unplaced & (cand[safe] == iota)
        table = table.at[e, jnp.where(won, bids, nb)].set(iota,
                                                          mode="drop")
        unplaced = unplaced & ~won
    return table, unplaced.sum(dtype=jnp.int32)


def _probe_jnp(pbids: jax.Array, pwords, table: jax.Array, bwords):
    """Bit-identical twin of ``pallas_kernels.bucket_probe``."""
    cap = pbids.shape[0]
    width = table.shape[0]
    bcap = bwords[0].shape[0] if bwords else 0
    if bcap == 0:
        return jnp.zeros(cap, jnp.int32)
    valid = pbids >= 0
    bsafe = jnp.where(valid, pbids, 0)
    mask = jnp.zeros(cap, jnp.int32)
    for e in range(width):
        rr = table[e][bsafe]
        ok = valid & (rr >= 0)
        rsafe = jnp.clip(rr, 0, bcap - 1)
        eq = ok
        for pw, bw in zip(pwords, bwords):
            eq = eq & (pw == bw[rsafe])
        mask = mask | jnp.where(eq, jnp.int32(1 << e), jnp.int32(0))
    return mask


def _build(bids, nb: int, width: int):
    if pk.bucket_join_ok(bids, nb, width, 0):
        return pk.bucket_build(bids, nb, width)
    return _build_jnp(bids, nb, width)


def _probe(pbids, pwords, table, bwords):
    nb = table.shape[1]
    width = table.shape[0]
    bcap = bwords[0].shape[0] if bwords else 0
    if bcap and pk.bucket_join_ok(pbids, nb, width, len(bwords)):
        return pk.bucket_probe(pbids, pwords, table, bwords)
    return _probe_jnp(pbids, pwords, table, bwords)


# ----------------------------------------------------------- staging
# The phase helpers below are the A/B harness + test surface: they run
# one phase each so ``bench.py --join-ab`` can attribute build vs probe
# wall (``join.build`` / ``join.probe`` spans) with separate dispatches.

def build_phase(keys, validities, nrows, width: "int | None" = None):
    """Hash + bucket-insert one side. Returns ``(table, overflow_count,
    bids, words)`` — ``words`` is the canonical u32 word stream the
    probe compares against."""
    cap = keys[0].shape[0]
    width = bucket_width() if width is None else width
    nb = table_slots(cap)
    words = _row_words(keys, validities)
    h = hash_columns(keys, validities)
    valid = kernels.valid_mask(cap, nrows)
    bids = jnp.where(valid, (h & jnp.uint32(nb - 1)).astype(jnp.int32),
                     jnp.int32(-1))
    table, overflow = _build(bids, nb, width)
    return table, overflow, bids, words


def probe_phase(keys, validities, nrows, table, bwords):
    """Hash + bucket-lookup the other side against ``table``. Returns
    ``(mask, pbids)`` — per-row match bitmasks over the chain entries."""
    cap = keys[0].shape[0]
    nb = table.shape[1]
    words = _row_words(keys, validities)
    h = hash_columns(keys, validities)
    valid = kernels.valid_mask(cap, nrows)
    pbids = jnp.where(valid, (h & jnp.uint32(nb - 1)).astype(jnp.int32),
                      jnp.int32(-1))
    return _probe(pbids, words, table, bwords), pbids


# ----------------------------------------------------------- emission

def _emit(mask, pbids, pvalid, table, how, probe_is_left, out_cap,
          ordered):
    """Matched index pairs from the probe bitmasks: run-length offsets
    by prefix sum, then one drop-scatter per chain entry. Valid output
    slots are contiguous in [0, total) (the ``ordered=False``
    contract); ``ordered=True`` restores pandas order with one sort of
    the (left, right) pairs — ascending right id within a left row IS
    the right-frame order stability gives the sort join."""
    pcap = pbids.shape[0]
    width = table.shape[0]
    iota_p = jnp.arange(pcap, dtype=jnp.int32)
    bsafe = jnp.where(pbids >= 0, pbids, 0)
    flags = [((mask >> e) & 1).astype(jnp.int32) for e in range(width)]
    mcnt = functools.reduce(jnp.add, flags) if flags \
        else jnp.zeros(pcap, jnp.int32)
    if how == "inner":
        ecounts = mcnt
    else:  # left (probe side IS the left side): unmatched rows emit one
        ecounts = jnp.where(pvalid, jnp.maximum(mcnt, 1), 0)
    offs = kernels.exclusive_cumsum(ecounts)
    total = ((offs[-1] + ecounts[-1]) if pcap else jnp.int32(0)
             ).astype(jnp.int32)
    li = jnp.full(out_cap, -1, jnp.int32)
    ri = jnp.full(out_cap, -1, jnp.int32)
    rank = jnp.zeros(pcap, jnp.int32)
    for e in range(width):
        f = flags[e] > 0
        rr = table[e][bsafe]
        pos = jnp.where(f, offs + rank, out_cap)
        if probe_is_left:
            li = li.at[pos].set(iota_p, mode="drop")
            ri = ri.at[pos].set(rr, mode="drop")
        else:
            li = li.at[pos].set(rr, mode="drop")
            ri = ri.at[pos].set(iota_p, mode="drop")
        rank = rank + flags[e]
    if how == "left":
        pos0 = jnp.where(pvalid & (mcnt == 0), offs, out_cap)
        li = li.at[pos0].set(iota_p, mode="drop")
    if ordered:
        j = jnp.arange(out_cap, dtype=jnp.int32)
        sentinel = jnp.uint32(0xFFFFFFFF)
        okl = jnp.where(j < total, li.astype(jnp.uint32), sentinel)
        okr = jnp.where(j < total, ri.astype(jnp.uint32), sentinel)
        # (left, right) pairs are unique -> total order -> the sort can
        # skip stability bookkeeping (same argument as group_sort's
        # iota suborder)
        _, _, li, ri = jax.lax.sort((okl, okr, li, ri), num_keys=2,
                                    is_stable=False)
    return li, ri, total


# -------------------------------------------------------- orchestrator

def bucketed_join_indices(lkeys, lvals, lrows, rkeys, rvals, rrows,
                          how: str, out_cap: int, ordered: bool,
                          sort_fallback=None,
                          width: "int | None" = None):
    """Core: (left_idx, right_idx, total) gather plans of length
    ``out_cap`` — the bucketed rendition of ``join._join_indices``
    (same contract: -1 marks the null side of an output row, valid
    slots contiguous at the front).

    Build side: the smaller capacity for "inner"; always the right for
    "left" (unmatched-left emission is then a per-probe-row test, no
    second pass). ``sort_fallback`` (a nullary callable returning the
    same triple) arms the in-graph overflow guard: when any bucket
    chain exceeds ``width`` the whole join takes the sort path via
    ``lax.cond``. Pass ``None`` only when overflow was already ruled
    out host-side (:func:`chain_overflow`).
    """
    cl = lkeys[0].shape[0]
    cr = rkeys[0].shape[0]
    width = bucket_width() if width is None else width
    build_left = how == "inner" and cl <= cr
    if build_left:
        bkeys, bvals, brows = lkeys, lvals, lrows
        pkeys, pvals, prows, pcap = rkeys, rvals, rrows, cr
    else:
        bkeys, bvals, brows = rkeys, rvals, rrows
        pkeys, pvals, prows, pcap = lkeys, lvals, lrows, cl

    table, overflow, _, bwords = build_phase(bkeys, bvals, brows,
                                             width=width)
    pvalid = kernels.valid_mask(pcap, prows)

    def hash_branch(_):
        mask, pbids = probe_phase(pkeys, pvals, prows, table, bwords)
        return _emit(mask, pbids, pvalid, table, how,
                     probe_is_left=not build_left, out_cap=out_cap,
                     ordered=ordered)

    if sort_fallback is None:
        return hash_branch(None)
    return jax.lax.cond(overflow > 0, lambda _: sort_fallback(),
                        hash_branch, None)


@functools.partial(jax.jit, static_argnames=("nb", "width"))
def _chain_overflow_jit(keys, validities, nrows, nb: int, width: int):
    cap = keys[0].shape[0]
    h = hash_columns(list(keys), list(validities))
    valid = kernels.valid_mask(cap, nrows)
    bids = jnp.where(valid, (h & jnp.uint32(nb - 1)).astype(jnp.int32),
                     nb)
    counts = jnp.zeros(nb, jnp.int32).at[bids].add(1, mode="drop")
    return (counts > width).any() if cap else jnp.bool_(False)


def chain_overflow(keys, validities, nrows,
                   width: "int | None" = None) -> bool:
    """Host-side pre-check (EAGER callers only — one scalar sync): does
    any bucket chain of the would-be build side exceed the chain
    budget? Lets the eager path route statically (no dual-branch
    program) and count the fallback exactly."""
    width = bucket_width() if width is None else width
    nb = table_slots(keys[0].shape[0])
    return bool(_chain_overflow_jit(tuple(keys), tuple(validities),
                                    nrows, nb, width))


# ------------------------------------------------------------- routing

#: which implementation ``algorithm="hash"`` routes to. The A/B race
#: (``bench.py --join-ab``, recorded in ``BENCH_r06.json`` and
#: ``docs/joins.md``) decided the shipped default: the sort join won
#: every distribution at 1M/10M/100M on the CPU host (the width
#: scatter-round build alone costs more than the whole sort join, and
#: the TPU prices scatters worse — ``kernels.sort_perm``), so "hash"
#: ships routed to the sort path. ``CYLON_TPU_JOIN_HASH_IMPL=bucketed``
#: re-arms this module per process — the recorded rematch recipe for
#: real TPU hardware, where the VMEM-resident Pallas kernels dodge the
#: scatters that sank the XLA twin.
DEFAULT_HASH_IMPL = "sort"


def hash_impl() -> str:
    """"bucketed" (this module) or "sort" (the legacy murmur-bucket
    ``group_sort(hash_first=True)`` ordering of the sort join)."""
    v = os.environ.get("CYLON_TPU_JOIN_HASH_IMPL", "").lower()
    return v if v in ("bucketed", "sort") else DEFAULT_HASH_IMPL


def describe_routing() -> dict:
    """Static routing facts for ``telemetry.profile.explain`` — what
    ``algorithm="hash"`` would do right now, no data needed."""
    return {
        "hash_impl": hash_impl(),
        "algorithm_env": os.environ.get("CYLON_TPU_JOIN_ALGORITHM",
                                        "") or None,
        "bucket_width": bucket_width(),
        "supported_how": list(SUPPORTED_HOW),
        "overflow_fallback": "sort",
    }
