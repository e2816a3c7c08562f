"""Series: a single named device column with elementwise compute.

Parity target: ``python/pycylon/series.py`` (Series over a single
Cylon column) plus the single-column slice of the compute engine
(``python/pycylon/data/compute.pyx``: comparison/math ops :455-700,
``is_in`` :702, ``drop_na`` :728). All elementwise math lowers to one
fused XLA program on the padded device array; validity (null) masks
propagate through operations the way Arrow's validity bitmaps do.
"""

from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from cylon_tpu import dtypes
from cylon_tpu.column import Column
from cylon_tpu.errors import InvalidArgument, TypeError_
from cylon_tpu.utils.tracing import scope


class Series:
    """One named column + valid-row count (parity: pycylon ``Series``)."""

    def __init__(self, data=None, name: str = "", capacity: int | None = None,
                 nrows=None):
        if isinstance(data, Series):
            self._col, self._nrows, self.name = data._col, data._nrows, name or data.name
            return
        if isinstance(data, Column):
            # a bare Column carries no row count; pass nrows when the
            # column's capacity exceeds its logical length (padding)
            self._col = data
            self._nrows = jnp.asarray(
                data.capacity if nrows is None else nrows, jnp.int32)
        else:
            arr = np.asarray(data)
            self._col = Column.from_numpy(arr, capacity)
            self._nrows = jnp.asarray(len(arr), jnp.int32)
        self.name = name

    @staticmethod
    def _wrap(col: Column, nrows, name: str = "") -> "Series":
        s = object.__new__(Series)
        s._col, s._nrows, s.name = col, nrows, name
        return s

    # -- accessors -------------------------------------------------------
    @property
    def column(self) -> Column:
        return self._col

    @property
    def dtype(self) -> dtypes.DType:
        return self._col.dtype

    @property
    def nrows(self):
        return self._nrows

    def _require_local(self, what: str):
        # a [W]-vector nrows means the column is mesh-distributed
        # (frame.DataFrame.series keeps the layout); only elementwise
        # ops are defined there
        if getattr(self._nrows, "ndim", 0) == 1:
            raise InvalidArgument(
                f"{what} on a distributed Series; use the DataFrame "
                "reductions with env= (dist_aggregate) or materialise "
                "the frame first")

    def __len__(self):
        self._require_local("len()")
        return int(self._nrows)

    @property
    def shape(self):
        return (len(self),)

    @property
    def values(self) -> np.ndarray:
        return self.to_numpy()

    def to_numpy(self) -> np.ndarray:
        return self._col.to_numpy(len(self))

    def to_pandas(self):
        import pandas as pd

        return pd.Series(self.to_numpy(), name=self.name or None)

    def __repr__(self):
        return f"Series(name={self.name!r}, {self.to_numpy()!r})"

    # -- elementwise engine ---------------------------------------------
    def _valid(self) -> jax.Array | None:
        return self._col.validity

    @scope("project")
    def _binop(self, other, fn: Callable, out_kind=None) -> "Series":
        c = self._col
        if c.dtype.is_dictionary or c.dtype.is_bytes:
            raise TypeError_("math on string series requires codes/decode")
        if isinstance(other, Series):
            o, ov = other._col.data, other._col.validity
        elif isinstance(other, Column):
            o, ov = other.data, other.validity
        else:
            o, ov = other, None
        data = fn(c.data, o)
        validity = c.validity
        if ov is not None:
            validity = ov if validity is None else (validity & ov)
        dt = (dtypes.from_numpy_dtype(np.dtype(data.dtype))
              if out_kind is None else out_kind)
        return Series._wrap(Column(data, validity, dt), self._nrows, self.name)

    def _rbinop(self, other, fn):
        return self._binop(other, lambda a, b: fn(b, a))

    def __add__(self, o): return self._binop(o, jnp.add)
    def __radd__(self, o): return self._rbinop(o, jnp.add)
    def __sub__(self, o): return self._binop(o, jnp.subtract)
    def __rsub__(self, o): return self._rbinop(o, jnp.subtract)
    def __mul__(self, o): return self._binop(o, jnp.multiply)
    def __rmul__(self, o): return self._rbinop(o, jnp.multiply)
    def __truediv__(self, o): return self._binop(o, jnp.true_divide)
    def __rtruediv__(self, o): return self._rbinop(o, jnp.true_divide)
    def __floordiv__(self, o): return self._binop(o, jnp.floor_divide)
    def __rfloordiv__(self, o): return self._rbinop(o, jnp.floor_divide)
    def __mod__(self, o): return self._binop(o, jnp.mod)
    def __pow__(self, o): return self._binop(o, jnp.power)
    def __neg__(self): return self._binop(0, lambda a, _: jnp.negative(a))
    def __abs__(self): return self._binop(0, lambda a, _: jnp.abs(a))

    @scope("project")
    def _cmp_op(self, o, name: str, fn: Callable) -> "Series":
        """Comparison dispatch: device-bytes string columns compare by
        big-endian word order on device (bytewise string order — the
        binary-comparator role of ``arrow_comparator.cpp``); everything
        else goes through the elementwise engine."""
        c = self._col
        if c.dtype.is_bytes and isinstance(o, str):
            from cylon_tpu.ops import bytescol

            lt, eq = bytescol.cmp_scalar(c, o)
            m = {"eq": eq, "ne": ~eq, "lt": lt, "le": lt | eq,
                 "gt": ~(lt | eq), "ge": ~lt}[name]
            if c.validity is not None:
                # pandas null semantics: null != x is True, every other
                # comparison with null is False
                m = (m | ~c.validity) if name == "ne" else (m & c.validity)
            return Series._wrap(Column(m, None, dtypes.bool_),
                                self._nrows, self.name)
        if c.dtype.is_bytes and isinstance(o, (Series, Column)) \
                and name in ("eq", "ne"):
            from cylon_tpu.ops import bytescol

            oc = o._col if isinstance(o, Series) else o
            if oc.dtype.is_bytes or oc.dtype.is_dictionary:
                ca, cb = bytescol.align_storages([c, oc])
                m = (ca.data == cb.data).all(axis=1)
                bothv = None
                for v in (ca.validity, cb.validity):
                    if v is not None:
                        bothv = v if bothv is None else (bothv & v)
                if bothv is not None:
                    m = m & bothv
                if name == "ne":
                    m = ~m  # null != anything -> True (pandas parity,
                    #         same rule as the scalar path above)
                return Series._wrap(Column(m, None, dtypes.bool_),
                                    self._nrows, self.name)
        return self._binop(o, fn, dtypes.bool_)

    def __eq__(self, o): return self._cmp_op(o, "eq", jnp.equal)
    def __ne__(self, o): return self._cmp_op(o, "ne", jnp.not_equal)
    def __lt__(self, o): return self._cmp_op(o, "lt", jnp.less)
    def __le__(self, o): return self._cmp_op(o, "le", jnp.less_equal)
    def __gt__(self, o): return self._cmp_op(o, "gt", jnp.greater)
    def __ge__(self, o): return self._cmp_op(o, "ge", jnp.greater_equal)

    def __and__(self, o): return self._binop(o, jnp.logical_and, dtypes.bool_)
    def __or__(self, o): return self._binop(o, jnp.logical_or, dtypes.bool_)
    def __xor__(self, o): return self._binop(o, jnp.logical_xor, dtypes.bool_)

    def __invert__(self):
        return self._binop(0, lambda a, _: jnp.logical_not(a), dtypes.bool_)

    def __hash__(self):  # __eq__ is elementwise; keep identity hashing
        return id(self)

    # -- null handling ---------------------------------------------------
    def null_flags(self) -> jax.Array:
        """[capacity] bool, True where missing (validity or float NaN)."""
        from cylon_tpu.ops.selection import _null_flags

        f = _null_flags(self._col)
        return (jnp.zeros(self._col.capacity, bool) if f is None
                else f.astype(bool))

    def isnull(self) -> "Series":
        return Series._wrap(Column(self.null_flags(), None, dtypes.bool_),
                            self._nrows, self.name)

    isna = isnull

    def notnull(self) -> "Series":
        return Series._wrap(Column(~self.null_flags(), None, dtypes.bool_),
                            self._nrows, self.name)

    notna = notnull

    def fillna(self, value) -> "Series":
        c = self._col
        if c.dtype.is_bytes:
            from cylon_tpu.ops import bytescol

            return Series._wrap(bytescol.fill_value(c, value), self._nrows,
                                self.name)
        if c.dtype.is_dictionary:
            from cylon_tpu.ops.dictenc import encode_fill_value

            if c.validity is None:
                return self
            c2, code = encode_fill_value(c, value)
            data = jnp.where(c2.validity, c2.data, jnp.int32(code))
            return Series._wrap(Column(data, None, c2.dtype, c2.dictionary),
                                self._nrows, self.name)
        data = c.data
        if jnp.issubdtype(data.dtype, jnp.floating):
            data = jnp.where(jnp.isnan(data), value, data)
        if c.validity is not None:
            data = jnp.where(c.validity, data, jnp.asarray(value, data.dtype))
        return Series._wrap(Column(data, None, c.dtype, c.dictionary),
                            self._nrows, self.name)

    def dropna(self) -> "Series":
        from cylon_tpu.ops import kernels

        self._require_local("dropna()")
        mask = ~self.null_flags()
        perm, count = kernels.compact_mask(mask, self._nrows)
        c = self._col
        safe = jnp.clip(perm, 0, max(c.capacity - 1, 0))
        col = Column(c.data[safe],
                     None if c.validity is None else c.validity[safe],
                     c.dtype, c.dictionary)
        return Series._wrap(col, count, self.name)

    # -- membership / map ------------------------------------------------
    @scope("project")
    def isin(self, values) -> "Series":
        """Parity: ``compute.pyx`` is_in (:702). A null-ish probe value
        (None / NaN) matches null rows, like pandas isin([None]); a
        type-incompatible probe value never matches but does not poison
        the rest of the list (pandas: isin([1, 'a']) still matches 1)."""
        from cylon_tpu.ops.bytescol import is_nullish

        c = self._col
        vset = list(values)
        if c.dtype.is_bytes:
            from cylon_tpu.ops import bytescol

            mask = bytescol.isin(c, vset)
            return Series._wrap(Column(mask, None, dtypes.bool_),
                                self._nrows, self.name)
        has_null = any(is_nullish(v) for v in vset)
        vals = [v for v in vset if not is_nullish(v)]
        if c.dtype.is_dictionary:
            dvals = [] if c.dictionary is None else c.dictionary.values
            lut = {v: i for i, v in enumerate(dvals)}
            probe = [lut[v] for v in vals if v in lut]
            pdt = np.int32
        elif c.dtype.kind in (dtypes.Kind.TIMESTAMP, dtypes.Kind.DURATION,
                              dtypes.Kind.DATE32, dtypes.Kind.DATE64):
            # temporal columns store unit-scaled ints; coerce probes
            # through numpy temporal space at the column's unit (a raw
            # int compare against a datetime64 probe would never match)
            unit = c.dtype.unit or (
                "D" if c.dtype.kind == dtypes.Kind.DATE32 else "ms")
            cast = (np.timedelta64 if c.dtype.kind == dtypes.Kind.DURATION
                    else np.datetime64)
            pdt = np.dtype(c.data.dtype)
            probe = []
            for v in vals:
                if isinstance(v, (int, float, bool)):
                    continue  # pandas: a bare number never matches a date
                try:
                    probe.append(np.asarray(
                        cast(v, unit).astype(np.int64), pdt)[()])
                except (TypeError, ValueError):
                    continue
        else:
            pdt = np.dtype(c.data.dtype)
            probe = []
            for v in vals:
                try:
                    cv = np.asarray(v, pdt)[()]
                except (TypeError, ValueError, OverflowError):
                    continue
                if cv == v:  # 1.5 must not match int 1 via truncation
                    probe.append(cv)
        if probe:
            p = jnp.asarray(np.asarray(probe, pdt))
            mask = (c.data[:, None] == p[None, :]).any(axis=1)
        else:
            mask = jnp.zeros(c.capacity, bool)
        if c.validity is not None:
            mask = mask & c.validity
            if has_null:
                mask = mask | ~c.validity
        elif has_null and jnp.issubdtype(c.data.dtype, jnp.floating):
            # floats without a validity buffer carry nulls as NaN
            mask = mask | jnp.isnan(c.data)
        return Series._wrap(Column(mask, None, dtypes.bool_), self._nrows,
                            self.name)

    def _dict_pred(self, pred: Callable) -> "Series":
        """Boolean mask from a host predicate over the dictionary values
        of a string column. The predicate runs once per DISTINCT value
        (host-side, tiny); the row mask is an ``isin`` over matching
        codes — the device never sees bytes. This is how LIKE-style
        predicates (``p_type LIKE 'PROMO%'``) map onto dictionary
        encoding."""
        c = self._col
        if not c.dtype.is_dictionary:
            raise TypeError_("string predicate on non-string column")
        vals = [] if c.dictionary is None else list(c.dictionary.values)
        return self.isin([v for v in vals if pred(v)])

    def _bytes_pred(self, mask) -> "Series":
        return Series._wrap(Column(mask, None, dtypes.bool_), self._nrows,
                            self.name)

    @property
    def str(self) -> "_StrAccessor":
        """pandas-style string accessor (``s.str.startswith(...)``),
        covering both device layouts: device-bytes columns run windowed
        byte kernels on device, dictionary columns evaluate once per
        distinct value on host."""
        return _StrAccessor(self)

    def str_startswith(self, prefix: str) -> "Series":
        """Rows whose value starts with ``prefix`` (pandas
        ``Series.str.startswith``; always literal). Device-bytes
        columns run the windowed byte compare on device
        (:func:`bytescol.startswith`) — no host dictionary scan."""
        if self._col.dtype.is_bytes:
            from cylon_tpu.ops import bytescol

            return self._bytes_pred(bytescol.startswith(self._col, prefix))
        return self._dict_pred(lambda v: v is not None
                               and str(v).startswith(prefix))

    def str_endswith(self, suffix: str) -> "Series":
        """Rows whose value ends with ``suffix`` (pandas
        ``Series.str.endswith``; always literal)."""
        if self._col.dtype.is_bytes:
            from cylon_tpu.ops import bytescol

            return self._bytes_pred(bytescol.endswith(self._col, suffix))
        return self._dict_pred(lambda v: v is not None
                               and str(v).endswith(suffix))

    def str_contains(self, pat: str, regex: bool = True) -> "Series":
        """Rows whose value contains ``pat`` — a regex by default, same
        as pandas ``Series.str.contains``; pass ``regex=False`` for
        literal substring matching. Device-bytes columns: literal
        patterns (and regexes with no metacharacters) run the shifted
        window compare on device; a true regex decodes to host (the one
        string op with no device form)."""
        import re

        if self._col.dtype.is_bytes:
            from cylon_tpu.ops import bytescol

            if not regex or not re.search(r"[.^$*+?{}\[\]\\|()]", pat):
                return self._bytes_pred(bytescol.contains(self._col, pat))
            rx = re.compile(pat)
            self._require_local("str_contains(regex) on device bytes")
            host = self.to_numpy()
            hits = np.array([v is not None and rx.search(str(v)) is not None
                             for v in host], bool)
            mask = jnp.zeros(self._col.capacity, bool
                             ).at[:len(hits)].set(jnp.asarray(hits))
            return self._bytes_pred(mask)
        if regex:
            rx = re.compile(pat)
            return self._dict_pred(lambda v: v is not None
                                   and rx.search(str(v)) is not None)
        return self._dict_pred(lambda v: v is not None and pat in str(v))

    def map(self, fn: Callable) -> "Series":
        """Elementwise map (parity: ``compute.pyx`` infer_map :805). A
        jnp-traceable ``fn`` compiles into the XLA graph; anything else
        falls back to a host round-trip like the reference's inferred
        python loop."""
        c = self._col
        if c.dtype.is_bytes:
            # arbitrary python fn over variable-length values: host
            # round trip (decode, map, re-ingest as bytes)
            self._require_local("map() on device bytes")
            host = np.array([fn(v) for v in self.to_numpy()], object)
            col = Column.from_numpy(host, c.capacity,
                                    string_storage="bytes") \
                if all(isinstance(v, str) or v is None for v in host) \
                else Column.from_numpy(host, c.capacity)
            return Series._wrap(col, self._nrows, self.name)
        if c.dtype.is_dictionary:
            from cylon_tpu.ops.dictenc import reencode_values

            vals = [fn(v) for v in c.dictionary.values]
            return Series._wrap(reencode_values(c, vals), self._nrows,
                                self.name)
        try:
            data = jax.vmap(fn)(c.data)
            dt = dtypes.from_numpy_dtype(np.dtype(data.dtype))
            return Series._wrap(Column(data, c.validity, dt), self._nrows,
                                self.name)
        except Exception:
            host = np.array([fn(v) for v in self.to_numpy()])
            out = Series(host, self.name)
            return out

    applymap = map

    # -- reductions ------------------------------------------------------
    def _reduce(self, op: str):
        import jax

        from cylon_tpu.ops import aggregates
        from cylon_tpu.table import Table

        self._require_local(f"{op}()")
        t = Table({self.name or "x": self._col}, self._nrows)
        res = aggregates.table_aggregate(t, self.name or "x", op)
        if isinstance(res, jax.core.Tracer):
            return res  # under whole-query trace: stay on device
        return np.asarray(res)[()]

    def sum(self): return self._reduce("sum")
    def count(self): return self._reduce("count")
    def min(self): return self._reduce("min")
    def max(self): return self._reduce("max")
    def mean(self): return self._reduce("mean")
    def var(self): return self._reduce("var")
    def std(self): return self._reduce("std")
    def nunique(self): return self._reduce("nunique")

    def unique(self) -> np.ndarray:
        """Distinct values, host-side (parity: ``table.pyx`` unique on a
        single column)."""
        vals = self.to_numpy()
        seen, out = set(), []
        for v in vals:
            k = v if v == v else None  # NaN folds
            if k not in seen:
                seen.add(k)
                out.append(v)
        return np.asarray(out, dtype=vals.dtype)


class _StrAccessor:
    """``Series.str`` — the pandas string-method namespace (parity:
    pandas ``Series.str``; the reference exposes string compute through
    pycylon's compute surface). Methods dispatch on the column's device
    layout; see the ``str_*`` methods on :class:`Series`."""

    def __init__(self, s: Series):
        self._s = s

    def startswith(self, prefix: str) -> Series:
        return self._s.str_startswith(prefix)

    def endswith(self, suffix: str) -> Series:
        return self._s.str_endswith(suffix)

    def contains(self, pat: str, regex: bool = True) -> Series:
        return self._s.str_contains(pat, regex=regex)

    def len(self) -> Series:
        """Value length in CHARACTERS for both layouts (pandas
        semantics): host map over distinct values for dictionary
        columns, a device UTF-8 start-byte count
        (:func:`bytescol.char_lengths`) for device-bytes columns — the
        two storages agree on non-ASCII data."""
        s = self._s
        c = s.column
        if c.dtype.is_bytes:
            from cylon_tpu.ops import bytescol

            data = bytescol.char_lengths(c.data)
            return Series._wrap(Column(data, c.validity, dtypes.int32),
                                s._nrows, s.name)
        if c.dtype.is_dictionary:
            import numpy as np

            vals = ([] if c.dictionary is None
                    else [len(str(v)) for v in c.dictionary.values])
            lut = jnp.asarray(np.asarray(vals or [0], np.int32))
            data = lut[jnp.clip(c.data, 0, max(len(vals) - 1, 0))]
            return Series._wrap(Column(data, c.validity, dtypes.int32),
                                s._nrows, s.name)
        raise TypeError_("str.len() on non-string column")

    def _ascii_case(self, upper: bool) -> Series:
        s = self._s
        c = s.column
        if c.dtype.is_bytes:
            # ASCII case transform fully on device: flip bit 5 of a-z /
            # A-Z bytes inside each big-endian word; non-ASCII (>=0x80)
            # bytes are multi-byte UTF-8 payload and pass through
            lo, hi = (0x61, 0x7A) if upper else (0x41, 0x5A)
            data = c.data
            out = jnp.zeros_like(data)
            for shift in (24, 16, 8, 0):
                b = (data >> jnp.uint32(shift)) & jnp.uint32(0xFF)
                flip = (b >= lo) & (b <= hi)
                b = jnp.where(flip, b ^ jnp.uint32(0x20), b)
                out = out | (b << jnp.uint32(shift))
            return Series._wrap(Column(out, c.validity, c.dtype),
                                s._nrows, s.name)
        if c.dtype.is_dictionary:
            from cylon_tpu.ops.dictenc import reencode_values

            fn = str.upper if upper else str.lower
            vals = [None if v is None else fn(str(v))
                    for v in (c.dictionary.values
                              if c.dictionary is not None else [])]
            return Series._wrap(reencode_values(c, vals), s._nrows,
                                s.name)
        raise TypeError_("str case transform on non-string column")

    def upper(self) -> Series:
        """ASCII upper-case (device-side for bytes columns; non-ASCII
        characters pass through unchanged)."""
        return self._ascii_case(True)

    def lower(self) -> Series:
        return self._ascii_case(False)
