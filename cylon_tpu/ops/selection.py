"""Row selection / movement ops: take, filter, sort, concat, head, sample.

Reference analogs: ``Table::Project/Select`` and friends
(``cpp/src/cylon/table.cpp``), the split/copy kernels
(``arrow/arrow_kernels.cpp``, ``util/copy_arrray.cpp``) and
``util::SortTable[MultiColumns]`` (``util/arrow_utils.hpp:63-118``).
Everything is a gather/scatter over padded arrays; row counts stay traced.
"""

import functools
from typing import Sequence

import jax
import jax.numpy as jnp

from cylon_tpu.column import Column
from cylon_tpu.errors import InvalidArgument
from cylon_tpu.ops import kernels
from cylon_tpu.platform import platform_jit
from cylon_tpu.table import Table
from cylon_tpu.utils.tracing import scope


def _packable(data: jax.Array) -> bool:
    """float64 cannot ride the u32 packing (XLA's TPU x64-emulation
    pass implements cross-width bitcasts for 64-bit ints but not
    doubles) and neither can general multi-dim columns — but a
    device-bytes string column ([cap, w] u32, bytescol) already IS
    words and rides the packed gather as-is."""
    if data.ndim == 2 and data.dtype == jnp.uint32:
        return True
    return data.ndim == 1 and data.dtype != jnp.float64


def _to_words(data: jax.Array) -> jax.Array:
    """[cap] packable column -> [cap, w] u32 words (bit-preserving)."""
    dt = data.dtype
    if data.ndim == 2:  # bytes column: already u32 words
        return data
    if dt == jnp.bool_:
        return data.astype(jnp.uint32)[:, None]
    if dt.itemsize == 8:
        return jax.lax.bitcast_convert_type(data, jnp.uint32)
    if dt.itemsize == 4:
        return jax.lax.bitcast_convert_type(data, jnp.uint32)[:, None]
    # 8/16-bit: zero-extend each element into its own word
    unsigned = jnp.dtype(f"uint{dt.itemsize * 8}")
    return jax.lax.bitcast_convert_type(data, unsigned).astype(
        jnp.uint32)[:, None]


def _from_words(words: jax.Array, dt) -> jax.Array:
    dt = jnp.dtype(dt)
    if dt == jnp.bool_:
        return words[:, 0] != 0
    if dt.itemsize == 8:
        return jax.lax.bitcast_convert_type(words, dt)
    if dt.itemsize == 4:
        return jax.lax.bitcast_convert_type(words[:, 0], dt)
    unsigned = jnp.dtype(f"uint{dt.itemsize * 8}")
    return jax.lax.bitcast_convert_type(
        words[:, 0].astype(unsigned), dt)


def take_columns(table: Table, idx: jax.Array, nrows_out,
                 null_mask: jax.Array | None = None,
                 names: Sequence[str] | None = None) -> Table:
    """Gather rows by index into a new table of capacity ``len(idx)``.

    All fixed-width columns (and validity flags) are bit-packed into ONE
    [cap, words] u32 matrix and row-gathered in a single pass: on TPU a
    random row gather costs the same per index for 1 lane or 128, so one
    wide gather replaces ncols narrow ones (the dominant cost of join
    materialisation, ``join/join_utils.hpp:34`` build_final_table).

    ``null_mask`` marks output slots whose row should be all-null (used for
    non-matching sides of outer joins; reference builds these with -1
    indices in ``join/join_utils.cpp``).
    """
    safe = jnp.clip(idx, 0, max(table.capacity - 1, 0))
    use = list(names if names is not None else table.column_names)

    layout = []  # (name, column, word_slice | None, validity_word | None)
    word_arrays = []
    w = 0
    for name in use:
        c = table.column(name)
        sl = None
        if _packable(c.data):
            cw = _to_words(c.data)
            word_arrays.append(cw)
            sl = slice(w, w + cw.shape[1])
            w += cw.shape[1]
        vslot = None
        if c.validity is not None:
            word_arrays.append(c.validity.astype(jnp.uint32)[:, None])
            vslot = w
            w += 1
        layout.append((name, c, sl, vslot))

    out_words = None
    if word_arrays:
        packed = (jnp.concatenate(word_arrays, axis=1)
                  if len(word_arrays) > 1 else word_arrays[0])
        out_words = packed[safe]

    cols = {}
    for name, c, sl, vslot in layout:
        if sl is None:  # unpackable (f64): dedicated gather
            data = c.data[safe]
        elif c.data.ndim == 2:  # bytes column: the words are the data
            data = out_words[:, sl]
        else:
            data = _from_words(out_words[:, sl], c.data.dtype)
        validity = None if vslot is None else out_words[:, vslot] != 0
        if null_mask is not None:
            base = jnp.ones_like(null_mask) if validity is None else validity
            validity = base & ~null_mask
            # canonicalise injected-null payloads (the clipped gather
            # leaves arbitrary bytes otherwise)
            nm = null_mask.reshape(null_mask.shape + (1,) * (data.ndim - 1))
            data = jnp.where(nm, jnp.zeros((), data.dtype), data)
        cols[name] = Column(data, validity, c.dtype, c.dictionary)
    return Table(cols, nrows_out)


def columns_to_payloads(columns, capacity: int,
                        lead: Sequence[jax.Array] = (),
                        index_slot: int | None = None):
    """Flatten ``{name: Column}`` into ``lax.sort`` payload operands.

    Returns ``(payloads, spec)``: 1-D data and validity arrays become
    payload slots; multi-dim columns (rare) are marked for a post-sort
    gather through an original-index payload. ``lead`` payloads occupy
    the first slots; a caller whose lead already carries the original
    row index passes its slot as ``index_slot`` so no duplicate iota
    rides the sort. The inverse is :func:`payloads_to_columns`."""
    payloads = list(lead)
    spec = {}
    need_iota = False
    for name, c in columns.items():
        if c.data.ndim == 1:
            spec[name] = len(payloads)
            payloads.append(c.data)
        elif c.data.ndim == 2 and c.data.dtype == jnp.uint32:
            # bytes column: each word rides as its own payload slot (a
            # post-sort gather would cost ~10x the sort on TPU)
            nw = c.data.shape[1]
            spec[name] = ("w", len(payloads), nw)
            payloads.extend(c.data[:, i] for i in range(nw))
        else:
            spec[name] = None
            need_iota = True
        if c.validity is not None:
            spec[name + "\0v"] = len(payloads)
            payloads.append(c.validity)
    iota_slot = index_slot
    if need_iota and iota_slot is None:
        iota_slot = len(payloads)
        payloads.append(jnp.arange(capacity, dtype=jnp.int32))
    return payloads, (spec, iota_slot)


def payloads_to_columns(columns, sorted_payloads, pack) -> dict:
    """Rebuild ``{name: Column}`` from sorted payload slots (see
    :func:`columns_to_payloads`)."""
    spec, iota_slot = pack
    cols = {}
    for name, c in columns.items():
        slot = spec[name]
        if isinstance(slot, tuple):  # bytes column word slots
            _, start, nw = slot
            data = jnp.stack(sorted_payloads[start:start + nw], axis=1)
        elif slot is not None:
            data = sorted_payloads[slot]
        else:
            data = c.data[sorted_payloads[iota_slot]]
        vslot = spec.get(name + "\0v")
        validity = sorted_payloads[vslot] if vslot is not None else None
        cols[name] = Column(data, validity, c.dtype, c.dictionary)
    return cols


#: payload u32-words above which :func:`permute_by_sort` stops carrying
#: columns through the comparator network and instead sorts a
#: permutation + does ONE packed row gather. Every extra sort operand
#: re-moves its bytes through every merge stage of the O(log^2 n)
#: sorting network, while the gather pays per row once: measured on
#: v5e at 6M rows, 12 extra u32 operands turn a ~20 s (cold) 2-operand
#: sort into 140 s, vs ~2 s for the packed gather — the "payloads ride
#: the sort" rule that wins for narrow tables INVERTS for wide ones
#: (e.g. any table carrying a device-bytes string column).
PAYLOAD_SORT_MAX_WORDS = 6

#: ...but only at scale: below this row count the comparator network is
#: still cheap and random gathers are the expensive primitive (~10x a
#: narrow sort at 1M rows — the r3 measurement), so wide payloads keep
#: riding the sort. The blowup above is superlinear in rows; 2M is the
#: same knee the segmented-scan gate uses (groupby.SEGSCAN_MAX_ROWS).
PAYLOAD_GATHER_MIN_ROWS = 2_000_000


def use_gather_path(total_words: int, rows: int) -> bool:
    """Shared wide-table crossover for permute/groupby/unique/
    segmented_totals: sort a permutation + packed-gather instead of
    carrying payloads, once the width passes its knee — and, off the
    TPU, the row count too. On the TPU the sort's COMPILE time grows
    faster than linearly in the 32-bit words it carries, at any row
    count (v5e, measured by chip_smoke.py in PR 21: TPC-H Q1's 24-word
    sort of a 375k-row groupby result took 684 s of compile), so there
    a wide table never rides the sort."""
    from cylon_tpu.platform import current_platform

    return (total_words > PAYLOAD_SORT_MAX_WORDS
            and (rows >= PAYLOAD_GATHER_MIN_ROWS
                 or current_platform() == "tpu"))


def _column_words(c: Column) -> int:
    """u32 words this column adds per row as sort payload."""
    d = c.data
    if d.ndim == 2:
        w = d.shape[1]
    else:
        w = 2 if d.dtype.itemsize == 8 else 1
    if c.validity is not None:
        w += 1
    return w


def payload_words(columns) -> int:
    return sum(_column_words(c) for c in columns.values())


def permute_by_sort(table: Table, operands, nrows_out) -> Table:
    """Reorder a table by a stable sort on ``operands`` (pre-built
    unsigned order keys). Narrow tables carry every column through
    ``lax.sort`` as payload (random gathers cost ~10x a narrow sort);
    wide tables (> ``PAYLOAD_SORT_MAX_WORDS`` payload words at
    >= ``PAYLOAD_GATHER_MIN_ROWS`` rows) sort only a row-index payload
    and take ONE bit-packed row gather instead — see the constants'
    docstrings for the measured crossover."""
    if use_gather_path(payload_words(table.columns), table.capacity):
        iota = jnp.arange(table.capacity, dtype=jnp.int32)
        out = jax.lax.sort(tuple(operands) + (iota,),
                           num_keys=len(operands), is_stable=True)
        return take_columns(table, out[-1], nrows_out)
    payloads, pack = columns_to_payloads(table.columns, table.capacity)
    out = jax.lax.sort(tuple(operands) + tuple(payloads),
                       num_keys=len(operands), is_stable=True)
    cols = payloads_to_columns(table.columns, list(out[len(operands):]),
                               pack)
    return Table(cols, nrows_out)


@jax.jit
@scope("filter")
def filter_table(table: Table, mask: jax.Array) -> Table:
    """Keep rows where mask is True, preserving order (parity: the
    filter path of ``python/pycylon/data/compute.pyx:212``). Jitted:
    one compiled program; the compaction is a stable u8-key sort with
    the columns as payload (see permute_by_sort)."""
    with scope("filter", "mask"):
        cap = table.capacity
        iota = jnp.arange(cap, dtype=jnp.int32)
        keep = mask & (iota < table.nrows)
        count = keep.sum(dtype=jnp.int32)
    with scope("filter", "compact"):
        return kernels.carry_overflow(
            permute_by_sort(table, ((~keep).astype(jnp.uint8),), count),
            table)


def sort_table(table: Table, by: Sequence[str], ascending=True,
               na_position: str = "last") -> Table:
    """Lexicographic multi-column sort (parity: ``Table::Sort`` /
    ``util::SortTableMultiColumns``; pandas ``sort_values`` semantics:
    NaN/null keys go last regardless of direction)."""
    if isinstance(ascending, bool):
        ascending = [ascending] * len(by)
    return _sort_compiled(table, by=tuple(by), ascending=tuple(ascending),
                          na_position=na_position)


def sort_key_operands(c: Column, asc: bool,
                      na_position: str = "last") -> list:
    """The unsigned operand list that sorts one column with pandas
    semantics (null/NaN flag word ranking nulls last regardless of
    direction, order-key transform, bytes columns as their words).
    Shared by the local sort below and the distributed sample-sort's
    salted splitter tuples (``dist_ops._sort_body``) — partition order
    MUST equal local sort order or rows land on the wrong shard."""
    okeys = []
    nulls = _null_flags(c)
    key = kernels.order_key(c.data, asc)
    if nulls is not None:
        # flag ascending (0 < 1) puts nulls last; zero the data key
        # under nulls — null slots carry arbitrary payload bytes, and
        # pandas keeps null rows in original order (stable sort)
        flag = nulls if na_position == "last" else (1 - nulls)
        okeys.append(flag)
        nz = nulls == 0
        if key.ndim == 2:  # bytes column: zero every word
            nz = nz[:, None]
        key = jnp.where(nz, key, jnp.zeros((), key.dtype))
    okeys.append(key)  # 2-D bytes keys expand in pack_order_keys
    return okeys


@functools.partial(platform_jit, static_argnames=("by", "ascending",
                                                  "na_position"))
@scope("sort")
def _sort_compiled(table: Table, *, by, ascending, na_position) -> Table:
    with scope("sort", "keys"):
        okeys = []
        for name, asc in zip(by, ascending):
            okeys.extend(sort_key_operands(table.column(name), asc,
                                           na_position))
        padding = (~kernels.valid_mask(table.capacity, table.nrows)
                   ).astype(jnp.uint8)
        operands = kernels.pack_order_keys([padding] + okeys)
    with scope("sort", "permute"):
        return permute_by_sort(table, operands, table.nrows)


def _null_flags(c: Column) -> jax.Array | None:
    """[capacity] uint8, 1 where the row's value is missing (validity or
    float NaN). NaN-as-null is a scalar-column concept: multi-dim
    (embedding-like) columns are only null by validity — a NaN element
    inside a vector does not void the row."""
    flags = None
    if c.validity is not None:
        flags = (~c.validity).astype(jnp.uint8)
    if jnp.issubdtype(c.data.dtype, jnp.floating) and c.data.ndim == 1:
        nan = jnp.isnan(c.data).astype(jnp.uint8)
        flags = nan if flags is None else flags | nan
    return flags


def concat_tables(tables: Sequence[Table], capacity: int | None = None) -> Table:
    """Row-wise concatenation (parity: ``Table::Merge`` / pycylon
    ``concat``, ``table.pyx:2368``). Schemas must match by name & dtype;
    dictionary columns are re-encoded onto a shared dictionary first
    (host-side metadata op)."""
    from cylon_tpu.ops.dictenc import unify_table_dictionaries

    if not tables:
        raise InvalidArgument("concat of no tables")
    for t in tables:
        # an overflowed input (nrows > capacity, from an undersized
        # out_capacity) would silently scatter only part of its rows;
        # fail loudly when the count is concrete
        if not isinstance(t.nrows, jax.core.Tracer):
            t.num_rows
    names = tables[0].column_names
    for t in tables[1:]:
        if t.column_names != names:
            raise InvalidArgument(
                f"schema mismatch: {t.column_names} vs {names}")
    tables = unify_table_dictionaries(tables)
    from cylon_tpu.ops.bytescol import align_table_strings

    tables = align_table_strings(tables)
    cap_out = capacity if capacity is not None else sum(t.capacity for t in tables)

    nrows_list = [t.nrows for t in tables]
    total = jnp.int32(0)
    offsets = []
    for n in nrows_list:
        offsets.append(total)
        total = total + n

    cols = {}
    for name in names:
        c0 = tables[0].column(name)
        any_validity = any(t.column(name).validity is not None for t in tables)
        data = jnp.zeros((cap_out,) + c0.data.shape[1:], dtype=c0.data.dtype)
        validity = jnp.zeros(cap_out, bool) if any_validity else None
        for t, off in zip(tables, offsets):
            c = t.column(name)
            if c.data.dtype != c0.data.dtype:
                raise InvalidArgument(
                    f"dtype mismatch in column {name}: "
                    f"{c.data.dtype} vs {c0.data.dtype}")
            pos = jnp.arange(t.capacity, dtype=jnp.int32)
            dest = jnp.where(pos < t.nrows, off + pos, cap_out)
            data = data.at[dest].set(c.data, mode="drop")
            if validity is not None:
                v = (jnp.ones(t.capacity, bool) if c.validity is None
                     else c.validity)
                validity = validity.at[dest].set(v, mode="drop")
        cols[name] = Column(data, validity, c0.dtype, c0.dictionary)
    return kernels.carry_overflow(Table(cols, total), *tables)


@scope("take")
def head(table: Table, n: int) -> Table:
    """First n valid rows (valid rows are always the leading rows)."""
    return table.with_nrows(jnp.minimum(table.nrows, n))


def sample(table: Table, n: int) -> Table:
    """Deterministic systematic sample of up to ``n`` rows — the sampling
    primitive behind distributed range partitioning (parity:
    ``util::SampleArray``, ``util/arrow_utils.hpp``; the reference also
    samples rather than using all rows, ``arrow_partition_kernels.cpp:377``)."""
    nr = table.nrows
    take_n = jnp.minimum(nr, n)
    # stride so samples spread over [0, nrows)
    pos = jnp.arange(n, dtype=jnp.float32)
    idx = jnp.where(take_n > 0,
                    (pos * nr.astype(jnp.float32)
                     / jnp.maximum(take_n, 1).astype(jnp.float32)),
                    0).astype(jnp.int32)
    idx = jnp.clip(idx, 0, jnp.maximum(nr - 1, 0))
    return take_columns(table, idx, take_n)


@scope("take")
def take(table: Table, idx: jax.Array) -> Table:
    """Public gather-by-indices (parity: arrow Take used throughout
    reference join/sort paths)."""
    return take_columns(table, idx, idx.shape[0])
