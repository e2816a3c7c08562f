"""Pallas TPU kernels for the hot relational loops.

Reference hot loops (SURVEY §3.2–3.4): per-row murmur3 partition hashing
(``arrow/arrow_partition_kernels.cpp:140-297``) and per-group aggregate
accumulation (``groupby/hash_groupby.cpp:143,221-226``). On TPU both are
memory-bound single-pass loops — exactly what Pallas is for:

* :func:`row_hash` fuses the W-word murmur mixing chain (+ optional
  ``% num_partitions``) into ONE pass over HBM, block-resident in VMEM.
* the scan kernels (:func:`scan32`, :func:`pair_max_scan`) replace
  XLA's multi-pass reduce-window lowerings for the prefix sums /
  running maxima inside join expansion and shuffles.

(An MXU one-hot segment-sum kernel lived here through r3; it was
retired once ``kernels.segmented_totals`` — the segmented-scan +
compaction-sort path — took over ALL TPU group reductions: its gate
(f32, 1-D, <=8192 groups) had become unreachable on every default
path, and measured v5e numbers showed segmented_totals ahead at both
small and large group counts. See
``ops/groupby.py:_reduce_segments``.)

All kernels run in ``interpret`` mode off-TPU, so the exact code path
unit-tested on the CPU mesh (``tests/conftest.py``) is what compiles on
real chips. Dispatch policy: :func:`enabled` — auto-on for the TPU
backend, forceable via ``CYLON_PALLAS=1|0``.
"""

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# re-exported: dist ops wrap tracing in on_platform(mesh platform)
from cylon_tpu.platform import current_platform, on_platform

# ---------------------------------------------------------------- dispatch

_SUBLANES = 8          # Mosaic tile: second-to-last dim multiple of 8
_HASH_LANES = 1024     # lanes per hash row; tile = 8x1024 elements


def _mode() -> str:
    return os.environ.get("CYLON_PALLAS", "auto").lower()


def enabled() -> bool:
    """Should ops route through the Pallas kernels?"""
    m = _mode()
    if m in ("0", "off", "false"):
        return False
    if m in ("1", "on", "true"):
        return True
    return current_platform() == "tpu"


def _interpret() -> bool:
    """Interpret off-TPU so CPU tests (``CYLON_PALLAS=1``) execute the
    same kernels. On the TPU a kernel always compiles: interpret mode
    is decided by the platform alone, never by an option."""
    return current_platform() != "tpu"


def _vma_varying(x) -> bool:
    return bool(getattr(getattr(x, "aval", None), "vma", None))


def usable_for(x) -> bool:
    """Can the Pallas path run for this operand *here*? On TPU inside
    ``shard_map`` Mosaic compiles fine (vma is forwarded to out_shape),
    but the interpret-mode evaluator cannot mix vma-varying refs with
    kernel constants (jax-ml/jax hlo_interpreter limitation) — there the
    caller's jnp fallback (bit-identical) takes over."""
    return enabled() and not (_interpret() and _vma_varying(x))


def _pad_to(x: jax.Array, n: int, fill) -> jax.Array:
    if x.shape[0] == n:
        return x
    return jnp.concatenate(
        [x, jnp.full((n - x.shape[0],) + x.shape[1:], fill, x.dtype)])


def _out_struct(shape, dtype, like) -> jax.ShapeDtypeStruct:
    """Output aval matching ``like``'s mesh-axis variance — required for
    pallas_call under ``shard_map(check_vma=True)`` (every distributed
    op body here)."""
    vma = getattr(getattr(like, "aval", None), "vma", None)
    if vma:
        return jax.ShapeDtypeStruct(shape, dtype, vma=vma)
    return jax.ShapeDtypeStruct(shape, dtype)


# ---------------------------------------------------------------- row hash

def _hash_kernel(nparts: int, nwords_tail: int, seed: int,
                 *refs):
    """One VMEM-resident block: the same murmur chain as
    ``hash.hash_columns``'s jnp fallback — literally the same functions,
    so the two paths cannot drift apart."""
    from cylon_tpu.ops.hash import _fmix32, _mix_word

    *word_refs, out_ref = refs
    h = jnp.full(out_ref.shape, np.uint32(seed))
    for wr in word_refs:
        h = _mix_word(h, wr[...])
    h = _fmix32(h ^ np.uint32(4 * nwords_tail))
    if nparts:
        out_ref[...] = (h % np.uint32(nparts)).astype(jnp.int32)
    else:
        out_ref[...] = h


@functools.partial(jax.jit, static_argnames=("nparts", "nwords_tail",
                                             "seed", "interpret"))
def _row_hash_impl(words, nparts: int, nwords_tail: int,
                   seed: int, interpret: bool) -> jax.Array:
    cap = words[0].shape[0]
    r, b = _SUBLANES, _HASH_LANES
    tile = r * b
    capp = -(-cap // tile) * tile
    words2 = [_pad_to(w, capp, 0).reshape(capp // b, b) for w in words]
    # x64 is package-global, but Mosaic rejects the i64 constants it
    # puts into BlockSpec index maps — trace the kernel in 32-bit
    with jax.enable_x64(False):
        out = pl.pallas_call(
            functools.partial(_hash_kernel, nparts, nwords_tail, seed),
            grid=(capp // tile,),
            in_specs=[pl.BlockSpec((r, b), lambda i: (i, 0))] * len(words2),
            out_specs=pl.BlockSpec((r, b), lambda i: (i, 0)),
            out_shape=_out_struct((capp // b, b),
                                  jnp.int32 if nparts else jnp.uint32,
                                  words2[0]),
            interpret=interpret,
        )(*words2)
    return out.reshape(capp)[:cap]


def row_hash(words, nparts: int = 0, *, seed: int = 0x9747B28C) -> jax.Array:
    """Murmur-mix ``words`` (list of uint32 ``[cap]`` arrays, one per
    32-bit word of the row key) into a ``[cap]`` row hash; with
    ``nparts`` also fuses ``% nparts`` → int32 partition ids.

    Bit-identical to :func:`cylon_tpu.ops.hash.hash_columns`'s mixing
    chain (same per-word block step + fmix32 finaliser).
    """
    return _row_hash_impl(tuple(words), nparts, len(words), seed,
                          _interpret())


# ------------------------------------------------------------------ scan
#: lanes per scan tile; tile = 8 x _SCAN_LANES elements, VMEM-resident
_SCAN_LANES = 2048

def _scan_ident(kind: str, dtype):
    """Identity element: 0 for add; the dtype's minimum for max."""
    if kind == "add":
        return np.zeros((), dtype)[()]
    if jnp.issubdtype(dtype, jnp.floating):
        return np.array(-np.inf, dtype)[()]
    return np.iinfo(dtype).min


def _scan_kernel(kind: str, L: int, ident, x_ref, out_ref, carry_ref):
    """Per-ROW inclusive scan of one [8, L] tile + a running [8, 1]
    carry: Hillis-Steele along lanes only (Mosaic has no sublane
    shifts); each sublane scans an independent 1/8th of the array, and
    the tiny cross-row combine happens outside the kernel in XLA. ONE
    pass over HBM vs the ~log n passes of XLA's reduce-window lowering
    (measured 3.7 ms -> sub-ms for a 2M i32 cumsum)."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        carry_ref[...] = jnp.full_like(carry_ref, ident)

    def op(a, b):
        return a + b if kind == "add" else jnp.maximum(a, b)

    x = x_ref[...]
    idf = jnp.asarray(ident, x.dtype)
    sh = 1
    while sh < L:
        shifted = jnp.concatenate(
            [jnp.full((x.shape[0], sh), idf, x.dtype), x[:, :-sh]], axis=1)
        x = op(x, shifted)
        sh *= 2
    x = op(x, carry_ref[...])
    out_ref[...] = x
    carry_ref[...] = x[:, L - 1:L]


@functools.partial(jax.jit, static_argnames=("kind", "interpret"))
def _scan32_impl(x: jax.Array, kind: str, interpret: bool) -> jax.Array:
    n = x.shape[0]
    r, L = _SUBLANES, _SCAN_LANES
    ident = _scan_ident(kind, x.dtype)
    per_row = -(-n // r)
    m = max(-(-per_row // L), 1) * L         # lanes per row, L-padded
    npad = r * m
    # GLOBAL row-major split: sublane j scans rows [j*m, (j+1)*m)
    xp = _pad_to(x, npad, ident).reshape(r, m)
    with jax.enable_x64(False):
        out = pl.pallas_call(
            functools.partial(_scan_kernel, kind, L, ident),
            grid=(m // L,),
            in_specs=[pl.BlockSpec((r, L), lambda i: (0, i))],
            out_specs=pl.BlockSpec((r, L), lambda i: (0, i)),
            out_shape=_out_struct((r, m), x.dtype, xp),
            scratch_shapes=[pltpu.VMEM((r, 1), x.dtype)],
            interpret=interpret,
        )(xp)
    # cross-row combine: 8 row totals, exclusive-scanned in XLA.
    # Elementwise-only (roll + where with scalar literals): explicit
    # unvarying constants (concat/scan carries) fail shard_map's vma
    # type check when the data is device-varying.
    tot = out[:, -1]
    rows = jnp.arange(tot.shape[0])
    if kind == "add":
        excl = jnp.cumsum(tot) - tot
        out = out + excl[:, None]
    else:
        excl = jnp.where(rows >= 1, jnp.roll(jax.lax.cummax(tot), 1),
                         ident)
        out = jnp.maximum(out, excl[:, None])
    return out.reshape(npad)[:n]


def scan32(x: jax.Array, kind: str) -> jax.Array:
    """Inclusive 1-D scan ("add" or "max") for 32-bit dtypes — the
    replacement for ``jnp.cumsum``/``lax.cummax`` on the TPU hot paths
    (join run-length expansion, fill broadcasts, group boundaries).
    Callers gate on :func:`scan32_ok`."""
    return _scan32_impl(x, kind, _interpret())


#: minimum elements before the Pallas scan beats XLA's cumsum/cummax: a
#: kernel launch on tiny arrays (e.g. the [W] count vectors inside
#: shuffle rounds) pads to a full 8x2048 tile and loses to the plain
#: lowering (ADVICE r3)
SCAN_MIN_SIZE = 4096


def scan32_ok(x) -> bool:
    return (x.ndim == 1 and x.shape[0] >= SCAN_MIN_SIZE
            and x.dtype.itemsize == 4
            and x.dtype != jnp.bool_ and usable_for(x))


def _pair_max_kernel(L: int, hi_ref, lo_ref, oh_ref, ol_ref,
                     ch_ref, cl_ref):
    """Running LEXICOGRAPHIC max over (hi, lo) u32 pairs — bit-for-bit
    the u64 ``cummax`` of ``(hi << 32) | lo`` without any 64-bit ops
    (the x64 emulation's pair reduce-window measured 3.7 ms per fill at
    2M rows; this runs one pass, ~0.1 ms). Same per-sublane layout and
    carry scheme as :func:`_scan_kernel`."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        ch_ref[...] = jnp.zeros_like(ch_ref)
        cl_ref[...] = jnp.zeros_like(cl_ref)

    def combine(h, l, hs, ls):
        take = (hs > h) | ((hs == h) & (ls > l))
        return jnp.where(take, hs, h), jnp.where(take, ls, l)

    h = hi_ref[...]
    l = lo_ref[...]
    z = jnp.uint32(0)
    sh = 1
    while sh < L:
        hs = jnp.concatenate(
            [jnp.full((h.shape[0], sh), z, h.dtype), h[:, :-sh]], axis=1)
        ls = jnp.concatenate(
            [jnp.full((l.shape[0], sh), z, l.dtype), l[:, :-sh]], axis=1)
        h, l = combine(h, l, hs, ls)
        sh *= 2
    h, l = combine(h, l, ch_ref[...], cl_ref[...])
    oh_ref[...] = h
    ol_ref[...] = l
    ch_ref[...] = h[:, L - 1:L]
    cl_ref[...] = l[:, L - 1:L]


@functools.partial(jax.jit, static_argnames=("interpret",))
def _pair_max_impl(hi: jax.Array, lo: jax.Array, interpret: bool):
    n = hi.shape[0]
    r, L = _SUBLANES, _SCAN_LANES
    per_row = -(-n // r)
    m = max(-(-per_row // L), 1) * L
    npad = r * m
    hp = _pad_to(hi, npad, 0).reshape(r, m)
    lp = _pad_to(lo, npad, 0).reshape(r, m)
    with jax.enable_x64(False):
        oh, ol = pl.pallas_call(
            functools.partial(_pair_max_kernel, L),
            grid=(m // L,),
            in_specs=[pl.BlockSpec((r, L), lambda i: (0, i))] * 2,
            out_specs=[pl.BlockSpec((r, L), lambda i: (0, i))] * 2,
            out_shape=[_out_struct((r, m), jnp.uint32, hp)] * 2,
            scratch_shapes=[pltpu.VMEM((r, 1), jnp.uint32)] * 2,
            interpret=interpret,
        )(hp, lp)
    # cross-row combine: EXCLUSIVE running lex-max of the 8 row totals.
    # Elementwise-only formulation (unrolled Hillis-Steele over rolls):
    # under shard_map everything here is device-varying, and control
    # structures with explicit unvarying carries (lax.scan) fail the
    # vma type check — scalar literals in jnp.where broadcast fine.
    th, tl = oh[:, -1], ol[:, -1]
    rows = jnp.arange(th.shape[0])

    def lexmax(h, l, hs, ls):
        take = (hs > h) | ((hs == h) & (ls > l))
        return jnp.where(take, hs, h), jnp.where(take, ls, l)

    eh = jnp.where(rows >= 1, jnp.roll(th, 1), 0)
    el = jnp.where(rows >= 1, jnp.roll(tl, 1), 0)
    sh = 1
    while sh < th.shape[0]:
        hs = jnp.where(rows >= sh + 1, jnp.roll(eh, sh), 0)
        ls = jnp.where(rows >= sh + 1, jnp.roll(el, sh), 0)
        eh, el = lexmax(eh, el, hs, ls)
        sh *= 2
    oh, ol = lexmax(oh, ol, eh[:, None], el[:, None])
    return oh.reshape(npad)[:n], ol.reshape(npad)[:n]


def pair_max_scan(hi: jax.Array, lo: jax.Array):
    """Inclusive running lexicographic max over u32 (hi, lo) pairs —
    the fill-broadcast primitive (``kernels.forward_fill``). Positions
    before any nonzero pair read (0, 0), matching the u64 encoding's
    semantics. Callers gate on :func:`scan32_ok` for both operands."""
    return _pair_max_impl(hi, lo, _interpret())


# ------------------------------------------------- bucketed hash join
# The build/probe pair for the O(n) bucketed hash join
# (``ops/hash_join.py``): the reference's flat_hash_map build/probe
# (``join/hash_join.cpp:22-31``) rendered as a power-of-2 bucket table
# of fixed-width chains, VMEM-resident for the whole build and probe.
# Insertion and lookup are data-dependent per row, which Mosaic cannot
# vectorise — both kernels run a sequential per-element loop over each
# tile with the table pinned in VMEM, trading vector throughput for a
# single pass over HBM (the jnp twins in ``ops/hash_join.py`` pay
# ~width scatter/gather passes instead; both are bit-identical).
#
# Mosaic loads and stores VMEM only as vectors, and scalars only from
# SMEM: the per-row bucket ids, probe words and match masks travel in
# SMEM tiles, and each row touches its bucket through the 128-lane
# [width, 128] table slice holding it, selected by a lane mask.

_JOIN_LANES = 128      # lanes per build/probe tile (8 x 128 elements)


def _32bit_trace(interpret: bool):
    """x64-off trace scope for Mosaic compiles only: interpret mode
    must trace under the ambient setting (see the call sites)."""
    import contextlib

    return contextlib.nullcontext() if interpret \
        else jax.enable_x64(False)


def _bucket_slice(b, width: int):
    """(lane-aligned slice, [width, 128] lane mask) of bucket ``b``.
    ``lax.div``/``lax.rem``, not ``//``/``%``: the Python operators
    lower through ``sign``, which Mosaic re-traces at lowering time
    under the package-wide x64 and then refuses as 64-bit."""
    lanes = np.int32(_JOIN_LANES)
    off = pl.multiple_of(jax.lax.div(b, lanes) * lanes, _JOIN_LANES)
    lane = jax.lax.broadcasted_iota(jnp.int32, (width, _JOIN_LANES), 1)
    return pl.ds(off, _JOIN_LANES), lane == jax.lax.rem(b, lanes)


def _bucket_build_kernel(width: int, rows: int, lanes: int,
                         bid_ref, table_ref):
    """Sequential first-free-entry insertion, ascending row order.

    ``bid_ref``: [rows, lanes] int32 SMEM bucket ids (-1 = skip:
    padding or invalid row). ``table_ref``: [width, nb] int32 bucket
    table — entry e of bucket b ends up holding the (e+1)-th inserted
    row id (ascending), -1 when empty; the ENTRY-major layout keeps the
    lane dimension at nb (pow-2, lane-aligned), not the tiny chain
    width. A row whose chain is full is dropped (the caller counts
    them: valid rows minus stored entries).
    """
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        table_ref[...] = jnp.full_like(table_ref, -1)

    base = i.astype(jnp.int32) * np.int32(rows * lanes)
    entry = jax.lax.broadcasted_iota(jnp.int32, (width, lanes), 0)
    nl = np.int32(lanes)

    def row_body(k, carry):
        k = k.astype(jnp.int32)      # interpret mode: ambient x64 index
        b = bid_ref[jax.lax.div(k, nl), jax.lax.rem(k, nl)]

        @pl.when(b >= 0)
        def _insert():
            cols, hit = _bucket_slice(b, width)
            cur = table_ref[:, cols]
            # first free entry of the bucket's column (width = none)
            first = jnp.min(jnp.where(hit & (cur < 0), entry,
                                      np.int32(width)),
                            axis=0, keepdims=True)
            table_ref[:, cols] = jnp.where(hit & (entry == first),
                                           base + k, cur)

        return carry

    jax.lax.fori_loop(0, rows * lanes, row_body, np.int32(0))


@functools.partial(jax.jit, static_argnames=("nb", "width", "interpret"))
def _bucket_build_impl(bids, nb: int, width: int, interpret: bool):
    cap = bids.shape[0]
    r, b = _SUBLANES, _JOIN_LANES
    tile = r * b
    capp = max(-(-cap // tile) * tile, tile)
    bids2 = _pad_to(bids, capp, -1).reshape(capp // b, b)
    nbp = max(nb, b)              # whole 128-lane slices per bucket
    # Mosaic rejects the i64 constants x64 puts into BlockSpec index
    # maps — trace 32-bit for the real-TPU compile. The interpret-mode
    # evaluator is the opposite: its state discharge re-evaluates
    # stores under the AMBIENT x64 setting, so an x64-off trace there
    # manufactures i32/i64 mixes inside the loop bodies.
    with _32bit_trace(interpret):
        table = pl.pallas_call(
            functools.partial(_bucket_build_kernel, width, r, b),
            grid=(capp // tile,),
            in_specs=[pl.BlockSpec((r, b), lambda i: (i, 0),
                                   memory_space=pltpu.SMEM)],
            out_specs=pl.BlockSpec((width, nbp), lambda i: (0, 0)),
            out_shape=_out_struct((width, nbp), jnp.int32, bids2),
            interpret=interpret,
        )(bids2)[:, :nb]
    ovf = (jnp.sum(bids >= 0, dtype=jnp.int32)
           - jnp.sum(table >= 0, dtype=jnp.int32))
    return table, ovf


def bucket_build(bids: jax.Array, nb: int, width: int):
    """Build the [width, nb] bucket table from [cap] int32 bucket ids
    (-1 = skip). Returns ``(table, overflow_count)``; bit-identical to
    ``hash_join._build_jnp`` (first-free-entry, ascending row id)."""
    return _bucket_build_impl(bids, nb, width, _interpret())


def _bucket_probe_kernel(width: int, nwords: int, lanes: int, *refs):
    """Per-element bucket lookup + exact key compare.

    refs: pbid [rows, lanes] SMEM i32 (-1 = invalid probe row), then
    ``nwords`` SMEM probe word tiles [rows, lanes] i32, the full
    [width, nb] table, ``nwords`` [width, nb] tables of the build key
    words each entry holds, and the [rows, lanes] SMEM i32 output mask
    (bit e set <=> table[e, bucket] holds a row whose canonical key
    words all equal the probe row's).
    """
    pbid_ref = refs[0]
    pword_refs = refs[1:1 + nwords]
    table_ref = refs[1 + nwords]
    tword_refs = refs[2 + nwords:2 + 2 * nwords]
    mask_ref = refs[-1]
    rows = pbid_ref.shape[0]
    bit = jnp.left_shift(
        np.int32(1), jax.lax.broadcasted_iota(jnp.int32, (width, lanes), 0))

    nl = np.int32(lanes)

    def body(k, carry):
        k = k.astype(jnp.int32)      # interpret mode: ambient x64 index
        r = jax.lax.div(k, nl)
        c = jax.lax.rem(k, nl)
        b = pbid_ref[r, c]
        cols, hit = _bucket_slice(jnp.maximum(b, 0), width)
        eq = hit & (table_ref[:, cols] >= 0)
        for w in range(nwords):
            eq = eq & (tword_refs[w][:, cols] == pword_refs[w][r, c])
        # entries' bits are disjoint and only the bucket's lane is hit:
        # sum over entries, then a max (the scalar reduction Mosaic
        # lowers without widening, unlike a sum) picks that lane
        m = jnp.max(jnp.sum(jnp.where(eq, bit, np.int32(0)), axis=0,
                            keepdims=True, dtype=jnp.int32))
        mask_ref[r, c] = jnp.where(b >= 0, m, np.int32(0))
        return carry

    jax.lax.fori_loop(0, rows * lanes, body, np.int32(0))


def _as_i32(x):
    return jax.lax.bitcast_convert_type(x, jnp.int32) \
        if x.dtype == jnp.uint32 else x.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("width", "interpret"))
def _bucket_probe_impl(pbids, pwords, table, bwords, width: int,
                       interpret: bool):
    cap = pbids.shape[0]
    nwords = len(pwords)
    nb = table.shape[1]
    r, b = _SUBLANES, _JOIN_LANES
    tile = r * b
    capp = max(-(-cap // tile) * tile, tile)
    pbids2 = _pad_to(pbids, capp, -1).reshape(capp // b, b)
    pwords2 = [_pad_to(_as_i32(w), capp, 0).reshape(capp // b, b)
               for w in pwords]
    # the build words each table entry holds, gathered once in XLA:
    # the kernel then compares whole [width, 128] slices, never a
    # data-dependent scalar gather
    nbp = max(nb, b)              # whole 128-lane slices per bucket
    table = jnp.pad(table, ((0, 0), (0, nbp - nb)), constant_values=-1)
    rsafe = jnp.clip(table, 0, bwords[0].shape[0] - 1)
    twords = [_as_i32(w)[rsafe] for w in bwords]
    smem = pl.BlockSpec((r, b), lambda i: (i, 0), memory_space=pltpu.SMEM)
    whole = pl.BlockSpec((width, nbp), lambda i: (0, 0))
    with _32bit_trace(interpret):
        out = pl.pallas_call(
            functools.partial(_bucket_probe_kernel, width, nwords, b),
            grid=(capp // tile,),
            in_specs=[smem] * (1 + nwords) + [whole] * (1 + nwords),
            out_specs=smem,
            out_shape=_out_struct((capp // b, b), jnp.int32, pbids2),
            interpret=interpret,
        )(pbids2, *pwords2, table, *twords)
    return out.reshape(capp)[:cap]


def bucket_probe(pbids: jax.Array, pwords, table: jax.Array, bwords):
    """Probe the bucket table: [cap] int32 match bitmasks (bit e set
    <=> ``table[e, pbids]`` matched exactly). ``pwords``/``bwords`` are
    the canonical u32 word streams (``hash._row_words``) of the probe /
    build rows. Bit-identical to ``hash_join._probe_jnp``."""
    return _bucket_probe_impl(pbids, tuple(pwords), table, tuple(bwords),
                              table.shape[0], _interpret())


#: VMEM budget for the resident bucket table + its per-entry key-word
#: tables — above this the Pallas path loses its "table stays on-chip"
#: premise and the jnp twins (HBM scatters/gathers) take over.
JOIN_VMEM_BUDGET = 4 << 20


def bucket_join_ok(x, nb: int, width: int, nwords: int) -> bool:
    """Can the Pallas bucket kernels run for this operand here? Gated
    like every kernel on :func:`usable_for`, plus the table and the
    probe's ``nwords`` key-word tables must fit the VMEM budget."""
    import os as _os

    try:
        budget = int(_os.environ.get("CYLON_TPU_JOIN_VMEM_BUDGET",
                                     JOIN_VMEM_BUDGET))
    except ValueError:
        budget = JOIN_VMEM_BUDGET
    resident = (1 + nwords) * nb * width * 4
    return usable_for(x) and resident <= budget
