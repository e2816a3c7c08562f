"""Pandas-like DataFrame facade.

Parity target: ``python/pycylon/frame.py`` (2082 LoC) — ``DataFrame``
(:183) with ``merge`` (:1516), ``join`` (:1387), ``groupby`` (:1813 →
``GroupByDataFrame`` :120), ``sort_values`` (:1272), ``drop_duplicates``
(:1743), ``concat`` (:1956), math/compare dunders, ``isin/fillna/
isnull/rename/set_index``; and the env-dispatch convention — **ops take
``env=None`` for local execution or ``env=CylonEnv`` for distributed**
(``frame.py:1728-1743``). PyCylon scripts port by changing the import.

The DataFrame wraps a :class:`cylon_tpu.table.Table` that is either
local (scalar nrows) or mesh-distributed (vector nrows); distributed
results stay distributed until materialised (``to_pandas``).
"""

from __future__ import annotations

from typing import Mapping, Sequence

import jax.numpy as jnp
import numpy as np

from cylon_tpu import dtypes
from cylon_tpu.column import Column
from cylon_tpu.config import CSVReadOptions, JoinConfig
from cylon_tpu.context import CylonEnv
from cylon_tpu.errors import InvalidArgument, KeyError_, NotImplemented_
from cylon_tpu.ops import aggregates as _aggregates
from cylon_tpu.ops import groupby as _groupby_mod
from cylon_tpu.ops import selection as _selection
from cylon_tpu.ops import setops as _setops
from cylon_tpu.ops.join import join as _join
from cylon_tpu.parallel import (
    dist_aggregate,
    dist_groupby,
    dist_join,
    dist_num_rows,
    dist_sort,
    dist_to_pandas,
    dist_unique,
    gather_table,
    is_distributed,
    scatter_table,
)
from cylon_tpu.table import Table

import os as _os

_NO_SHRINK = bool(_os.environ.get("CYLON_TPU_NO_SHRINK"))


def _shrink(t: Table) -> Table:
    """Capacity shrink-to-fit after selective local ops (see
    ``Table.shrink_to_fit``). Distributed tables keep their layout —
    per-shard counts differ and the shard shape is the mesh contract."""
    if _NO_SHRINK or is_distributed(t):
        return t
    return t.shrink_to_fit()


class DataFrame:
    """Columnar dataframe on device (parity: pycylon ``DataFrame``)."""

    def __init__(self, data=None, env: CylonEnv | None = None,
                 capacity: int | None = None, string_storage="dict"):
        index = None
        if isinstance(data, DataFrame):
            self._table = data._table
            index = data._index
        elif isinstance(data, Table):
            self._table = data
        elif data is None:
            self._table = Table({}, 0)
        elif isinstance(data, Mapping):
            self._table = Table.from_pydict(data, capacity, string_storage)
        else:
            import pandas as pd

            if isinstance(data, pd.DataFrame):
                self._table = Table.from_pandas(data, capacity,
                                                string_storage)
            elif isinstance(data, np.ndarray):
                names = [f"c{i}" for i in range(data.shape[1])]
                self._table = Table.from_numpy(names, list(data.T), capacity)
            else:
                try:
                    import pyarrow as pa

                    if isinstance(data, pa.Table):
                        self._table = Table.from_arrow(data, capacity,
                                                       string_storage)
                    else:
                        raise TypeError
                except TypeError:
                    raise InvalidArgument(
                        f"cannot build DataFrame from {type(data)}")
        if env is not None:
            self._table = scatter_table(env, self._table)
        self._index = index

    # -- construction helpers -------------------------------------------
    @staticmethod
    def _wrap(table: Table, index=None) -> "DataFrame":
        df = object.__new__(DataFrame)
        df._table = table
        df._index = index
        return df

    # -- schema / introspection -----------------------------------------
    @property
    def table(self) -> Table:
        return self._table

    @property
    def columns(self) -> list[str]:
        return self._table.column_names

    @property
    def shape(self):
        return (len(self), self._table.num_columns)

    @property
    def dtypes(self) -> dict:
        return {n: c.dtype for n, c in self._table.columns.items()}

    @property
    def is_distributed(self) -> bool:
        return is_distributed(self._table)

    def __len__(self):
        if self.is_distributed:
            return dist_num_rows(self._table)
        return self._table.num_rows

    # -- indexing (parity: indexing/ + table.hpp:183 SetArrowIndex) ------
    def _materialized(self) -> "DataFrame":
        """Local (gathered) view; the index — always built on the local
        layout, see set_index — rides along."""
        if self.is_distributed:
            return DataFrame._wrap(gather_table(None, self._table),
                                   self._index)
        return self

    @property
    def index(self):
        from cylon_tpu.indexing import RangeIndex

        if self._index is None:
            return RangeIndex(len(self))
        return self._index

    def set_index(self, key: str, indexing_type=None, drop: bool = True,
                  ) -> "DataFrame":
        """Build a value index on ``key`` (parity: pycylon
        ``DataFrame.set_index`` / ``Table::SetArrowIndex``, table.hpp:183;
        ``indexing_type`` mirrors ``IndexingType``, default HASH)."""
        from cylon_tpu.indexing import IndexingType, build_index

        if indexing_type is None:
            indexing_type = IndexingType.HASH
        df = self._materialized()
        t = df.table
        idx = build_index(t.column(key), t.nrows, indexing_type, name=key)
        if drop:
            t = t.drop([key])
        return DataFrame._wrap(t, index=idx)

    def reset_index(self, drop: bool = False) -> "DataFrame":
        """Drop the value index, materialising it back as a leading column
        unless ``drop`` (pandas semantics: a default RangeIndex becomes an
        ``index`` column of positions; a name collision raises)."""
        df = self._materialized()
        t = df.table
        idx = df._index
        if not drop:
            vc = idx.values_column() if idx is not None else None
            if vc is None:
                name = "index"
                pos = jnp.arange(t.capacity, dtype=jnp.int64)
                vc = Column(pos, None, dtypes.int64)
            else:
                name = idx.name or "index"
            if name in t:
                raise InvalidArgument(
                    f"cannot insert {name}, already exists")
            cols = {name: vc}
            cols.update(t.columns)
            t = Table(cols, t.nrows)
        return DataFrame._wrap(t)

    @property
    def loc(self):
        from cylon_tpu.indexing import LocIndexer

        return LocIndexer(self)

    @property
    def iloc(self):
        from cylon_tpu.indexing import ILocIndexer

        return ILocIndexer(self)

    def __repr__(self):
        try:
            return f"DataFrame({self.to_pandas().__repr__()})"
        except Exception:
            return f"DataFrame({self._table!r})"

    # -- selection -------------------------------------------------------
    def __getitem__(self, key):
        # pure column selection keeps rows, so the value index rides along
        if isinstance(key, str):
            return DataFrame._wrap(self._table.select([key]), self._index)
        if isinstance(key, (list, tuple)):
            return DataFrame._wrap(self._table.select(list(key)), self._index)
        from cylon_tpu.series import Series

        if isinstance(key, (DataFrame, Series, Column,
                            jnp.ndarray, np.ndarray)):
            if self.is_distributed:
                # a mask is always built on the PADDED shard layout;
                # gathering first would compact rows out from under it
                # and silently select the wrong ones — the layout-safe
                # path is the shard-local filter
                raise InvalidArgument(
                    "boolean-mask selection on a distributed frame: use "
                    ".filter(mask, env=env) (shard-local, no gather)")
            return self.filter(key)
        # no repr(key): a Series/DataFrame repr host-syncs, which under
        # whole-query tracing raises ConcretizationTypeError and masks
        # this KeyError
        raise KeyError_(
            f"bad key of type {type(key).__name__}; expected a column "
            f"name, list of names, or boolean mask "
            f"(columns: {list(self._table.column_names)!r})")

    def __setitem__(self, name, value):
        if self.is_distributed:
            # positional assignment is defined on the compacted local
            # layout; re-scatter (with env=) afterwards if needed
            self._table = gather_table(None, self._table)
        if isinstance(value, DataFrame):
            col = value._single_column()
        elif isinstance(value, Column):
            col = value
        elif np.isscalar(value):
            cap = self._table.capacity
            arr = jnp.full(cap, value)
            col = Column(arr, None, dtypes.from_numpy_dtype(np.asarray(value).dtype))
        else:
            col = Column.from_numpy(np.asarray(value), self._table.capacity)
        self._table = self._table.add_column(name, col)

    def _single_column(self) -> Column:
        if self._table.num_columns != 1:
            raise InvalidArgument("expected a single-column frame")
        return next(iter(self._table.columns.values()))

    # -- core relational ops (env dispatch, frame.py:1728) ---------------
    def merge(self, right: "DataFrame", how: str = "inner", on=None,
              left_on=None, right_on=None, suffixes=("_x", "_y"),
              env: CylonEnv | None = None,
              out_capacity: int | None = None,
              algorithm: str = "sort") -> "DataFrame":
        """Parity: ``DataFrame.merge`` (frame.py:1516). ``algorithm``
        mirrors pycylon's sort/hash choice ("hash" = the bucketed O(n)
        build/probe with sort fallback, see ``ops.join.join`` and
        ``docs/joins.md``; ``CYLON_TPU_JOIN_ALGORITHM`` overrides)."""
        if env is not None:
            t = dist_join(env, self._table, right._table, on=on,
                          left_on=left_on, right_on=right_on, how=how,
                          suffixes=suffixes, out_capacity=out_capacity,
                          algorithm=algorithm)
            return DataFrame._wrap(t)
        # local eager path regrows a defaulted capacity like the
        # distributed ops do (an N:M key blowup past the 1:N default
        # re-dispatches at 2x; the row-count check is the same sync
        # _shrink pays anyway). An explicit out_capacity keeps the
        # raise-on-overflow contract; under whole-query tracing the
        # enclosing CompiledQuery ladder takes over.
        from cylon_tpu import plan

        t = plan.regrow_eager(
            lambda: _join(self._gathered(), right._gathered(), on=on,
                          left_on=left_on, right_on=right_on, how=how,
                          suffixes=suffixes, out_capacity=out_capacity,
                          algorithm=algorithm),
            bounded=out_capacity is not None)
        return DataFrame._wrap(_shrink(t))

    def join(self, right: "DataFrame", on=None, how: str = "left",
             lsuffix: str = "_l", rsuffix: str = "_r",
             env: CylonEnv | None = None, **kw) -> "DataFrame":
        """Parity: ``DataFrame.join`` (frame.py:1387)."""
        return self.merge(right, how=how, on=on,
                          suffixes=(lsuffix, rsuffix), env=env, **kw)

    def groupby(self, by, env: CylonEnv | None = None) -> "GroupByDataFrame":
        """Parity: ``DataFrame.groupby`` (frame.py:1813)."""
        by = [by] if isinstance(by, str) else list(by)
        return GroupByDataFrame(self, by, env)

    def sort_values(self, by, ascending=True, env: CylonEnv | None = None,
                    **kw) -> "DataFrame":
        """Parity: ``DataFrame.sort_values`` (frame.py:1272); distributed
        = sample-sort (``DistributedSort``)."""
        by = [by] if isinstance(by, str) else list(by)
        if env is not None:
            return DataFrame._wrap(dist_sort(env, self._table, by,
                                             ascending=ascending, **kw))
        return DataFrame._wrap(
            _selection.sort_table(self._gathered(), by, ascending=ascending))

    def drop_duplicates(self, subset=None, keep: str = "first",
                        env: CylonEnv | None = None,
                        out_capacity: int | None = None) -> "DataFrame":
        """Parity: ``DataFrame.drop_duplicates`` (frame.py:1743) /
        ``DistributedUnique`` (table.cpp:977)."""
        subset = [subset] if isinstance(subset, str) else subset
        if env is not None:
            return DataFrame._wrap(
                dist_unique(env, self._table, subset,
                            out_capacity=out_capacity, keep=keep))
        return DataFrame._wrap(
            _shrink(_setops.unique(self._gathered(), subset, keep=keep)))

    def head(self, n: int = 5) -> "DataFrame":
        if self.is_distributed:
            from cylon_tpu.parallel import dist_head

            # no gather, no data movement: only the [W] count vector
            # changes (rows keep shard order, = the gathered order)
            return DataFrame._wrap(dist_head(self._table, n))
        return DataFrame._wrap(_selection.head(self._table, n))

    def filter(self, mask=None, env: CylonEnv | None = None,
               items: Sequence[str] | None = None) -> "DataFrame":
        """Row filter / column selection.

        With ``items=`` (or a list of column names) this is pandas
        ``DataFrame.filter``: select columns by label. With a bool
        array / Series / single-column DataFrame it is a row filter
        that preserves the table's layout: on a mesh-distributed frame
        each shard compacts its own rows — no gather (parity:
        rank-local filters, ``compute.pyx:212``). Null mask entries
        filter as False (SQL/pandas semantics)."""
        from cylon_tpu.series import Series

        if items is not None:
            return self[list(items)]
        if isinstance(mask, (list, tuple)) and all(
                isinstance(x, str) for x in mask):
            return self[list(mask)]  # pandas filter(items) shorthand
        if isinstance(mask, DataFrame):
            mask = mask._single_column()
        if isinstance(mask, Series):
            mask = mask.column
        if isinstance(mask, Column):
            m = mask.data.astype(bool)
            if mask.validity is not None:
                m = m & mask.validity
            mask = m
        mask = jnp.asarray(mask)
        if self.is_distributed:
            from cylon_tpu.parallel import dist_filter

            if env is None:
                raise InvalidArgument(
                    "filter on a distributed frame needs env= (the mesh)")
            return DataFrame._wrap(dist_filter(env, self._table, mask))
        t = _selection.filter_table(self._table, mask)
        return DataFrame._wrap(_shrink(t))

    def sample_rows(self, n: int) -> "DataFrame":
        return DataFrame._wrap(_selection.sample(self._gathered(), n))

    def add_prefix(self, prefix: str) -> "DataFrame":
        return DataFrame._wrap(self._table.add_prefix(prefix), self._index)

    def add_suffix(self, suffix: str) -> "DataFrame":
        return DataFrame._wrap(self._table.add_suffix(suffix), self._index)

    def to_csv(self, path, **kw) -> None:
        """Parity: pycylon ``DataFrame.to_csv`` / ``WriteCSV``
        (table.cpp:243)."""
        from cylon_tpu.io import write_csv

        write_csv(self, path, **kw)

    def rename(self, columns: Mapping[str, str]) -> "DataFrame":
        return DataFrame._wrap(self._table.rename(columns), self._index)

    def drop(self, columns: Sequence[str]) -> "DataFrame":
        columns = [columns] if isinstance(columns, str) else list(columns)
        return DataFrame._wrap(self._table.drop(columns), self._index)

    def astype(self, mapping: Mapping[str, dtypes.DType]) -> "DataFrame":
        t = self._table
        for name, dt in mapping.items():
            t = t.add_column(name, t.column(name).astype(dt))
        return DataFrame._wrap(t, self._index)

    # -- elementwise / predicates ----------------------------------------
    def _binop(self, other, fn) -> "DataFrame":
        t = self._table
        cols = {}
        for name, c in t.columns.items():
            if isinstance(other, DataFrame):
                o = other._table.column(name).data
            else:
                o = other
            data = fn(c.data, o)
            cols[name] = Column(data, c.validity,
                                dtypes.from_numpy_dtype(data.dtype))
        return DataFrame._wrap(Table(cols, t.nrows))

    def _unop(self, fn) -> "DataFrame":
        return self._binop(0, lambda a, _: fn(a))

    def __add__(self, o): return self._binop(o, jnp.add)
    def __radd__(self, o): return self._binop(o, lambda a, b: jnp.add(b, a))
    def __sub__(self, o): return self._binop(o, jnp.subtract)
    def __rsub__(self, o): return self._binop(o, lambda a, b: jnp.subtract(b, a))
    def __mul__(self, o): return self._binop(o, jnp.multiply)
    def __rmul__(self, o): return self._binop(o, lambda a, b: jnp.multiply(b, a))
    def __truediv__(self, o): return self._binop(o, jnp.true_divide)
    def __rtruediv__(self, o): return self._binop(o, lambda a, b: jnp.true_divide(b, a))
    def __floordiv__(self, o): return self._binop(o, jnp.floor_divide)
    def __mod__(self, o): return self._binop(o, jnp.mod)
    def __pow__(self, o): return self._binop(o, jnp.power)
    def __neg__(self): return self._unop(jnp.negative)
    def __abs__(self): return self._unop(jnp.abs)
    # bitwise on ints, logical on bools — numpy/pandas semantics
    def __invert__(self): return self._unop(jnp.invert)
    def __and__(self, o): return self._binop(o, jnp.bitwise_and)
    def __or__(self, o): return self._binop(o, jnp.bitwise_or)
    def __xor__(self, o): return self._binop(o, jnp.bitwise_xor)
    def __eq__(self, o): return self._binop(o, jnp.equal)          # noqa: E501
    def __ne__(self, o): return self._binop(o, jnp.not_equal)
    def __lt__(self, o): return self._binop(o, jnp.less)
    def __le__(self, o): return self._binop(o, jnp.less_equal)
    def __gt__(self, o): return self._binop(o, jnp.greater)
    def __ge__(self, o): return self._binop(o, jnp.greater_equal)

    def __hash__(self):  # __eq__ is elementwise; identity hashing
        return id(self)

    def abs(self) -> "DataFrame":
        return self._unop(jnp.abs)

    def applymap(self, fn) -> "DataFrame":
        """Elementwise map over every column (parity: frame.py applymap /
        ``compute.pyx`` infer_map). Traceable fns fuse into XLA; others
        fall back to a host loop per column."""
        from cylon_tpu.ops.dictenc import reencode_values

        t = self._materialized().table
        cols = {}
        nrows = t.nrows
        # bytes columns need host values; fetch them all in ONE batched
        # transfer (per-column fetches pay a host sync each)
        host_cols = (t._host_columns()
                     if any(c.dtype.is_bytes for c in t.columns.values())
                     else {})
        for name, c in t.columns.items():
            if c.dtype.is_bytes:
                host = np.array([fn(v) for v in host_cols[name]], object)
                st = ("bytes" if all(isinstance(v, str) or v is None
                                     for v in host) else "dict")
                cols[name] = Column.from_numpy(host, t.capacity,
                                               string_storage=st)
                continue
            if c.dtype.is_dictionary:
                cols[name] = reencode_values(
                    c, [fn(v) for v in c.dictionary.values])
                continue
            try:
                data = jnp.asarray(jnp.vectorize(fn)(c.data))
                cols[name] = Column(data, c.validity,
                                    dtypes.from_numpy_dtype(np.dtype(data.dtype)))
            except Exception:
                host = np.array([fn(v) for v in c.to_numpy(int(nrows))])
                cols[name] = Column.from_numpy(host, t.capacity)
        return DataFrame._wrap(Table(cols, nrows), self._index)

    map = applymap  # pandas 2.x name

    def series(self, name: str):
        """Single column as a :class:`cylon_tpu.series.Series`.

        Layout-preserving: on a distributed frame the Series wraps the
        sharded column directly (elementwise ops — arithmetic, isin,
        str predicates — never move data, so they stay shard-local);
        reductions on such a Series raise, use ``df.sum(env=...)`` /
        ``dist_aggregate`` instead."""
        from cylon_tpu.series import Series

        t = self._table
        return Series._wrap(t.column(name), t.nrows, name)

    def isnull(self) -> "DataFrame":
        """Parity: frame.py isnull."""
        t = self._table
        cols = {}
        for name, c in t.columns.items():
            flags = _selection._null_flags(c)
            data = (jnp.zeros(t.capacity, bool) if flags is None
                    else flags.astype(bool))
            cols[name] = Column(data, None, dtypes.bool_)
        return DataFrame._wrap(Table(cols, t.nrows))

    def notnull(self) -> "DataFrame":
        inv = self.isnull()
        return inv._binop(True, jnp.not_equal)

    isna = isnull
    notna = notnull

    def fillna(self, value) -> "DataFrame":
        """Parity: frame.py fillna."""
        from cylon_tpu.ops.dictenc import encode_fill_value

        t = self._table
        cols = {}
        for name, c in t.columns.items():
            if c.dtype.is_bytes:
                from cylon_tpu.ops import bytescol

                cols[name] = bytescol.fill_value(c, value)
                continue
            if c.dtype.is_dictionary:
                if c.validity is None:
                    cols[name] = c
                    continue
                c2, code = encode_fill_value(c, value)
                data = jnp.where(c2.validity, c2.data, jnp.int32(code))
                cols[name] = Column(data, None, c2.dtype, c2.dictionary)
                continue
            data, validity = c.data, c.validity
            if jnp.issubdtype(data.dtype, jnp.floating):
                data = jnp.where(jnp.isnan(data), value, data)
            if validity is not None:
                data = jnp.where(validity, data, jnp.asarray(value, data.dtype))
                validity = None
            cols[name] = Column(data, validity, c.dtype, c.dictionary)
        return DataFrame._wrap(Table(cols, t.nrows))

    def dropna(self, axis: int = 0, how: str = "any", subset=None,
               ) -> "DataFrame":
        """Drop rows (axis=0) or columns (axis=1) with missing values
        (parity: ``compute.pyx`` drop_na :728)."""
        from cylon_tpu.ops import kernels

        df = self._materialized()
        t = df.table
        names = ([subset] if isinstance(subset, str) else list(subset)
                 ) if subset is not None else t.column_names
        flags = []
        for name in names:
            f = _selection._null_flags(t.column(name))
            flags.append(jnp.zeros(t.capacity, bool) if f is None
                         else f.astype(bool))
        if not flags:
            return df
        stack = jnp.stack(flags)
        if axis == 1:
            rm = t.row_mask()
            bad = [bool((f & rm).any()) if how == "any"
                   else bool((f | ~rm).all()) for f in stack]
            keep = [n for n, b in zip(names, bad) if not b]
            keep += [n for n in t.column_names if n not in names]
            ordered = [n for n in t.column_names if n in set(keep)]
            return DataFrame._wrap(t.select(ordered), df._index)
        null_row = stack.all(axis=0) if how == "all" else stack.any(axis=0)
        perm, count = kernels.compact_mask(~null_row, t.nrows)
        out = _selection.take_columns(t, perm, count)
        idx = df.index.take(perm, count) if df._index is not None else None
        return DataFrame._wrap(out, idx)

    def where(self, cond: "DataFrame", other=np.nan) -> "DataFrame":
        """Keep values where ``cond`` holds, else ``other`` (parity:
        frame.py where/mask). ``cond`` is a boolean frame (same shape) or
        single boolean column applied to every column."""
        import math

        nan_fill = other is None or (isinstance(other, float)
                                     and math.isnan(other))
        t = self._materialized().table
        cols = {}
        for name, c in t.columns.items():
            if isinstance(cond, DataFrame):
                cc = (cond._table.column(name) if name in cond._table
                      else cond._single_column())
                m = cc.data.astype(bool)
            else:
                m = jnp.asarray(cond, bool)
            base = (jnp.ones(t.capacity, bool) if c.validity is None
                    else c.validity)
            if c.dtype.is_bytes:
                if nan_fill:
                    cols[name] = Column(c.data, base & m, c.dtype)
                else:
                    from cylon_tpu.ops import bytescol

                    validity = None if c.validity is None else (base | ~m)
                    cols[name] = bytescol.replace_where(c, m, other,
                                                        validity)
            elif c.dtype.is_dictionary:
                if nan_fill:
                    cols[name] = Column(c.data, base & m, c.dtype,
                                        c.dictionary)
                else:
                    from cylon_tpu.ops.dictenc import encode_fill_value

                    c2, code = encode_fill_value(c, other)
                    data = jnp.where(m, c2.data, jnp.int32(code))
                    # cond False takes `other` even over a prior null
                    validity = None if c.validity is None else (base | ~m)
                    cols[name] = Column(data, validity, c2.dtype,
                                        c2.dictionary)
            elif not jnp.issubdtype(jnp.asarray(c.data).dtype,
                                    jnp.floating):
                if nan_fill:
                    # non-float columns take NaN through the validity
                    # mask (null), matching Arrow semantics
                    cols[name] = Column(c.data, base & m, c.dtype)
                else:
                    data = jnp.where(m, c.data,
                                     jnp.asarray(other, c.data.dtype))
                    validity = None if c.validity is None else (base | ~m)
                    cols[name] = Column(data, validity, c.dtype)
            else:
                data = jnp.where(m, c.data,
                                 jnp.nan if nan_fill
                                 else jnp.asarray(other, c.data.dtype))
                validity = (c.validity if nan_fill or c.validity is None
                            else (base | ~m))
                cols[name] = Column(data, validity, c.dtype)
        return DataFrame._wrap(Table(cols, t.nrows), self._index)

    def mask(self, cond: "DataFrame", other=np.nan) -> "DataFrame":
        inv = (~cond) if isinstance(cond, DataFrame) else ~jnp.asarray(cond, bool)
        return self.where(inv, other)

    def equals(self, other: "DataFrame") -> bool:
        """Exact frame equality (schema + values; NaN == NaN).

        Runs device-side (``ops.setops.equal_tables(ordered=True)`` —
        one fused compare + one scalar fetch) instead of materialising
        both frames; frames carrying a value index keep the pandas
        path, since the index participates in pandas equality."""
        if not isinstance(other, DataFrame):
            return False
        if self._index is not None or other._index is not None:
            a, b = self.to_pandas(), other.to_pandas()
            return bool(a.equals(b))
        import numpy as np

        from cylon_tpu.ops.setops import (align_for_equal,
                                          dist_ordered_equal_compiled,
                                          equal_tables)
        from cylon_tpu.parallel import dtable

        ta, tb = self._table, other._table
        if ta.column_names != tb.column_names:
            return False
        for n in ta.column_names:
            da, db = ta.column(n).dtype, tb.column(n).dtype
            stringish = ((da.is_bytes or da.is_dictionary)
                         and (db.is_bytes or db.is_dictionary))
            if da != db and not stringish:
                # framework dtype mismatch (e.g. a nullable-int column
                # vs its to_pandas round trip, re-ingested as strings):
                # pandas decides value equality, not the device layout
                # (ADVICE r3)
                return bool(self.to_pandas().equals(other.to_pandas()))
        if (dtable.is_distributed(ta) and dtable.is_distributed(tb)
                and ta.capacity == tb.capacity
                and dtable.num_shards(ta) == dtable.num_shards(tb)):
            # same shard layout: compare SHARD-LOCAL — elementwise on
            # the sharded arrays, one scalar reduce, no gather. ONE
            # count fetch per table (each is a host sync)
            # serves the overflow check and the layout decision, and
            # string-storage alignment waits until the compare is
            # actually going to run on these layouts.
            ca, cb = dtable.host_counts(ta), dtable.host_counts(tb)
            cap_l = dtable.local_capacity(ta)
            if (ca > cap_l).any() or (cb > cap_l).any():
                dtable.dist_num_rows(ta)  # raises with the poisoned
                dtable.dist_num_rows(tb)  # shard's counts
            if ca.sum() != cb.sum():
                return False
            if (ca == cb).all():
                aligned = align_for_equal(ta, tb)
                if aligned is None:
                    return False
                return bool(np.asarray(
                    dist_ordered_equal_compiled(*aligned)))
            # equal totals but different shard boundaries: positional
            # equality needs the concatenated view — gather fallback
        ta = dtable.gather_table(None, ta)
        tb = dtable.gather_table(None, tb)
        return equal_tables(ta, tb, ordered=True)

    def isin(self, values: Sequence) -> "DataFrame":
        """Parity: frame.py isin (membership per element). Delegates to
        :meth:`Series.isin` per column — one implementation of the
        null-probe / type-mismatch semantics for both surfaces."""
        t = self._table
        vals = list(values)
        cols = {name: self.series(name).isin(vals).column
                for name in t.column_names}
        return DataFrame._wrap(Table(cols, t.nrows))

    # -- reductions ------------------------------------------------------
    def _reduce(self, op: str, env: CylonEnv | None = None,
                quantile: float = 0.5):
        out = {}
        local = None if env is not None else self._gathered()
        for name, c in self._table.columns.items():
            if not (c.dtype.is_numeric or op in ("count", "nunique")):
                continue
            if env is not None:
                out[name] = dist_aggregate(env, self._table, name, op,
                                           quantile=quantile)
            else:
                out[name] = _aggregates.table_aggregate(local, name, op,
                                                        quantile=quantile)
        return {k: np.asarray(v)[()] for k, v in out.items()}

    def sum(self, env=None): return self._reduce("sum", env)
    def count(self, env=None): return self._reduce("count", env)
    def min(self, env=None): return self._reduce("min", env)
    def max(self, env=None): return self._reduce("max", env)
    def mean(self, env=None): return self._reduce("mean", env)
    def var(self, env=None): return self._reduce("var", env)
    def std(self, env=None): return self._reduce("std", env)
    def nunique(self, env=None): return self._reduce("nunique", env)
    def median(self, env=None): return self._reduce("median", env)

    def quantile(self, q: float = 0.5, env=None):
        return self._reduce("quantile", env, quantile=q)

    # -- materialisation -------------------------------------------------
    def _gathered(self) -> Table:
        if self.is_distributed:
            return gather_table(None, self._table)
        return self._table

    def to_pandas(self):
        if self.is_distributed:
            return dist_to_pandas(None, self._table)
        return self._table.to_pandas()

    def to_dict(self):
        return self._gathered().to_pydict()

    def to_numpy(self):
        return self._gathered().to_numpy()

    def to_arrow(self):
        return self._gathered().to_arrow()

    def to_table(self) -> Table:
        return self._table


class GroupByDataFrame:
    """Parity: pycylon ``GroupByDataFrame`` (frame.py:120-180)."""

    def __init__(self, df: DataFrame, by: Sequence[str],
                 env: CylonEnv | None = None):
        self._df = df
        self._by = list(by)
        self._env = env

    def agg(self, spec=None, out_capacity: int | None = None,
            **named) -> DataFrame:
        """spec: {col: op | [ops]} (pandas style), [(col, op[, name])],
        or pandas named aggregation — ``agg(out=("col", "op"), ...)``."""
        aggs = []
        if spec is None and not named:
            raise InvalidArgument(
                "agg() needs a spec ({col: op}, [(col, op[, name])]) or "
                "named aggregations (out=(col, op))")
        if named:
            if spec is not None:
                raise InvalidArgument(
                    "pass either a spec or named aggregations, not both")
            for name, co in named.items():
                if not isinstance(co, (tuple, list)) or len(co) != 2:
                    raise InvalidArgument(
                        f"named aggregation {name}=... must be a "
                        f"(column, op) pair, got {type(co).__name__}")
                col, op = co
                aggs.append((col, op, name))
        elif isinstance(spec, Mapping):
            for col, ops in spec.items():
                ops = [ops] if isinstance(ops, str) else list(ops)
                for op in ops:
                    aggs.append((col, op, f"{col}_{op}"))
        else:
            aggs = [tuple(a) for a in spec]
        if self._env is not None:
            t = dist_groupby(self._env, self._df.table, self._by, aggs,
                             out_capacity=out_capacity)
        else:
            t = _groupby_mod.groupby_aggregate(self._df._gathered(),
                                               self._by, aggs,
                                               out_capacity=out_capacity)
            t = _shrink(t)
        return DataFrame._wrap(t)

    def _all_value_cols(self, op):
        cols = [c for c in self._df.columns if c not in self._by]
        return self.agg([(c, op, c) for c in cols])

    def sum(self): return self._all_value_cols("sum")
    def count(self): return self._all_value_cols("count")
    def min(self): return self._all_value_cols("min")
    def max(self): return self._all_value_cols("max")
    def mean(self): return self._all_value_cols("mean")
    def std(self): return self._all_value_cols("std")
    def var(self): return self._all_value_cols("var")
    def nunique(self): return self._all_value_cols("nunique")
    def median(self): return self._all_value_cols("median")


def merge(left: DataFrame, right: DataFrame, **kw) -> DataFrame:
    """Module-level merge (pandas style)."""
    return left.merge(right, **kw)


# DataFrame rides jit boundaries as a pytree (whole-query compilation,
# cylon_tpu.plan): the wrapped Table is the traced child; the index is
# treedef metadata (value indexes are host-built and rarely cross a
# compiled query).
import jax as _jax  # noqa: E402

_jax.tree_util.register_pytree_node(
    DataFrame,
    lambda df: ((df._table,), df._index),
    lambda idx, children: DataFrame._wrap(children[0], idx),
)


def concat(frames: Sequence[DataFrame], env: CylonEnv | None = None,
           out_capacity: int | None = None) -> DataFrame:
    """Parity: pycylon ``concat`` (frame.py:1956) / ``distributed_concat``
    (``table.pyx:2398``). With an ``env``, every shard concatenates its
    local blocks in place — no gather, no shuffle (rank-local order,
    like the reference's distributed_concat); locally, frame-major
    pandas order."""
    if env is not None and out_capacity is None:
        from cylon_tpu.parallel import dist_concat

        return DataFrame._wrap(dist_concat(env, [f._table for f in frames]))
    # an explicit out_capacity needs one global buffer of that size —
    # concatenate locally at that capacity, then lay out on the mesh
    tables = [f._gathered() for f in frames]
    t = _selection.concat_tables(tables, capacity=out_capacity)
    if env is not None:
        t = scatter_table(env, t)
    return DataFrame._wrap(t)


def read_csv(path, options: CSVReadOptions | None = None,
             env: CylonEnv | None = None, **kw) -> DataFrame:
    """CSV ingest (parity: ``FromCSV``; full IO lives in cylon_tpu.io)."""
    from cylon_tpu.io import read_csv as _read_csv

    return _read_csv(path, options, env=env, **kw)
