"""Device column: a fixed-width JAX array + optional validity + host dictionary.

Parity target: ``cpp/src/cylon/column.hpp:31`` (Column wraps an
``arrow::ChunkedArray``). TPU-first redesign: a column is a *single*
contiguous HBM buffer (chunking is an artifact of Arrow's incremental
builders; XLA wants one static-shape array), nulls are a separate bool
validity array (like Arrow's validity bitmap, but byte-per-row — TPU has
no cheap bit addressing and XLA packs bools), and variable-width values
live host-side in a dictionary with int32 codes on device.
"""

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from cylon_tpu import dtypes
from cylon_tpu.errors import TypeError_


class Dictionary:
    """Host-side dictionary for STRING/BINARY columns (numpy object array,
    sorted ascending so device code order == lexicographic value order).

    Hash/eq are by CONTENT (lazily cached): dictionaries ride in pytree
    aux-data, so they key every jit cache that takes a Table argument.
    Ops like dictionary unification build fresh Dictionary objects per
    call — identity hashing would force a recompile of an identical
    program on every call; content hashing makes the cache hit. The
    device program never reads the values, so equal-content dictionaries
    are genuinely interchangeable as compile keys.
    """

    __slots__ = ("values", "_key", "_hash", "_vhash")

    def __init__(self, values: np.ndarray):
        arr = np.asarray(values, dtype=object)
        if arr is values:
            # asarray aliases object ndarrays; freezing in place would
            # make the CALLER's array read-only as a side effect
            arr = arr.copy()
        self.values = arr
        # content hashing requires immutable content: mutation after the
        # first hash would silently corrupt jit-cache keys and
        # unify_dictionaries' equal-content pass-through
        self.values.flags.writeable = False
        self._key = None
        self._hash = None
        self._vhash = None

    def value_hashes(self):
        """[len] uint32 device array of stable per-VALUE hashes (crc32
        of the string form), cached: code-independent partition hashing
        maps codes through this table so independently ingested
        relations co-locate equal keys (``dist_ops._partition_keys``).
        Cached per dictionary — the streaming graph shuffles many
        chunks sharing one dictionary."""
        if self._vhash is None:
            import zlib

            import jax.numpy as jnp

            hv = np.array([zlib.crc32(str(v).encode())
                           for v in self.values], np.uint32)
            self._vhash = jnp.asarray(hv)
        return self._vhash

    def _content_key(self) -> tuple:
        if self._key is None:
            self._key = tuple(self.values.tolist())
        return self._key

    def __hash__(self):
        if self._hash is None:
            self._hash = hash(self._content_key())
        return self._hash

    def __eq__(self, other):
        if self is other:
            return True
        return (isinstance(other, Dictionary)
                and self._content_key() == other._content_key())

    def __len__(self):
        return len(self.values)

    def __repr__(self):
        return f"Dictionary(n={len(self.values)})"


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class Column:
    """One named column's device payload.

    data:      [capacity, ...] device array (physical dtype of ``dtype``)
    validity:  [capacity] bool, True = non-null. None means all-valid.
    dtype:     logical dtype (aux)
    dictionary: host dictionary for variable-width types (aux)
    """

    data: jax.Array
    validity: Optional[jax.Array] = None
    dtype: dtypes.DType = dtypes.int64
    dictionary: Optional[Dictionary] = None

    # -- pytree protocol -------------------------------------------------
    def tree_flatten(self):
        return (self.data, self.validity), (self.dtype, self.dictionary)

    @classmethod
    def tree_unflatten(cls, aux, children):
        data, validity = children
        dtype, dictionary = aux
        return cls(data, validity, dtype, dictionary)

    # -- constructors ----------------------------------------------------
    @staticmethod
    def from_numpy(arr: np.ndarray, capacity: int | None = None,
                   string_storage: str = "dict") -> "Column":
        """Host array -> Column. Strings/objects get one of two device
        layouts per ``string_storage``: ``"dict"`` (int32 codes + host
        dictionary — low-cardinality default), ``"bytes"`` (device-native
        packed byte words, :mod:`cylon_tpu.ops.bytescol` — no host
        dictionary, scales to unique-per-row columns), or ``"auto"``
        (sampled-cardinality choice). Extracts a validity mask from
        NaN/None. Pads to ``capacity`` if given."""
        arr = np.asarray(arr)
        validity = None

        if arr.dtype.kind in ("U", "S", "O"):
            import pandas as pd

            from cylon_tpu.ops import bytescol

            if string_storage == "auto":
                string_storage = bytescol.choose_storage(arr)
            if string_storage == "bytes":
                return bytescol.from_numpy(arr, capacity)
            # pd.isna handles None / float nan / pd.NA / NaT uniformly
            # (vectorised; a python per-element loop is seconds at 1M rows)
            isnull = np.asarray(pd.isna(arr))
            if isnull.ndim == 0:
                isnull = np.broadcast_to(isnull, arr.shape).copy()
            filled = np.where(isnull, "", arr.astype(object))
            # hash-based factorize beats sort-based np.unique ~4x on
            # low-cardinality string columns; sort=True keeps the
            # dictionary ordered so code comparisons = value comparisons
            codes, uniq = pd.factorize(filled, sort=True)
            dtype = dtypes.string
            data = codes.astype(np.int32)
            if isnull.any():
                validity = ~isnull
            return Column._pad(data, validity, dtype,
                               Dictionary(np.asarray(uniq, dtype=object)),
                               capacity)

        if arr.dtype.kind in ("M", "m"):
            dtype = dtypes.from_numpy_dtype(arr.dtype)
            isnat = np.isnat(arr)
            data = arr.view(np.int64)
            if isnat.any():
                validity = ~isnat
                data = np.where(isnat, 0, data)
            return Column._pad(data, validity, dtype, None, capacity)

        dtype = dtypes.from_numpy_dtype(arr.dtype)
        if arr.dtype.kind == "f":
            # float NaN stays NaN (pandas semantics); no validity extraction
            pass
        return Column._pad(arr, validity, dtype, None, capacity)

    @staticmethod
    def _pad(data, validity, dtype, dictionary, capacity):
        n = len(data)
        cap = n if capacity is None else capacity
        if cap < n:
            raise TypeError_(f"capacity {cap} < data length {n}")
        if cap > n:
            pad = cap - n
            data = np.concatenate(
                [data, np.zeros((pad,) + data.shape[1:], dtype=data.dtype)])
            if validity is not None:
                validity = np.concatenate([validity, np.zeros(pad, dtype=bool)])
        return Column(jnp.asarray(data, dtype=dtype.physical),
                      None if validity is None else jnp.asarray(validity),
                      dtype, dictionary)

    # -- accessors -------------------------------------------------------
    @property
    def capacity(self) -> int:
        return self.data.shape[0]

    def to_numpy(self, nrows: int | None = None) -> np.ndarray:
        """Device -> host, decoding dictionaries and applying validity."""
        n = self.capacity if nrows is None else nrows
        data = np.asarray(self.data[:n])
        validity = (None if self.validity is None
                    else np.asarray(self.validity[:n]))
        return self.decode_host(data, validity)

    def decode_host(self, data: np.ndarray,
                    validity: np.ndarray | None) -> np.ndarray:
        """Decode already-fetched host arrays (dictionary lookup, datetime
        views, null substitution). Shared by :meth:`to_numpy` and the
        batched single-transfer path ``Table.to_pandas`` uses — each
        device fetch pays a host sync's fixed cost, so tables fetch
        every column in ONE transfer and decode here."""
        n = len(data)
        if self.dtype.is_bytes:
            from cylon_tpu.ops import bytescol

            out = bytescol.decode_host(data, validity)
            return out
        if self.dtype.is_dictionary:
            if self.dictionary is None:
                raise TypeError_("dictionary column without dictionary")
            ncodes = len(self.dictionary)
            safe = np.clip(data, 0, max(ncodes - 1, 0))
            out = self.dictionary.values[safe] if ncodes else np.full(n, None, object)
            out = np.asarray(out, dtype=object)
        elif self.dtype.kind in (dtypes.Kind.TIMESTAMP, dtypes.Kind.DURATION,
                                 dtypes.Kind.DATE64):
            unit = self.dtype.unit or "ns"
            ch = "M" if self.dtype.kind != dtypes.Kind.DURATION else "m"
            out = data.view(f"{ch}8[{unit}]")
        else:
            out = data
        if validity is not None:
            mask = ~validity
            if mask.any():
                if out.dtype.kind == "f":
                    out = out.copy()
                    out[mask] = np.nan
                elif out.dtype.kind in "Mm":
                    # temporal nulls decode to native NaT, keeping the
                    # datetime64/timedelta64 dtype (an object column of
                    # None would lose sortability and dtype on every
                    # to_pandas round trip — e.g. the out-of-core spill)
                    out = out.copy()
                    out[mask] = (np.datetime64("NaT")
                                 if out.dtype.kind == "M"
                                 else np.timedelta64("NaT"))
                else:
                    out = out.astype(object)
                    out[mask] = None
        return out

    def astype(self, dtype: dtypes.DType) -> "Column":
        """Cast (parity: ``table.pyx:2446`` astype)."""
        if self.dtype.is_bytes or dtype.is_bytes:
            from cylon_tpu.ops import bytescol

            if self.dtype.is_dictionary and dtype.is_bytes:
                return bytescol.dict_to_bytes(
                    self, None if dtype.bytes_width is None
                    else dtype.bytes_width)
            if self.dtype.is_bytes and dtype.is_bytes:
                nw = dtype.bytes_width // 4
                cur = self.data.shape[1]
                if nw > cur:
                    pad = jnp.zeros((self.capacity, nw - cur), jnp.uint32)
                    data = jnp.concatenate([self.data, pad], axis=1)
                elif nw < cur:
                    # narrowing TRUNCATES content to the declared width
                    # (documented; raising would break schema
                    # normalisation before concat/join)
                    data = self.data[:, :nw]
                else:
                    return self
                return Column(data, self.validity,
                              dtypes.string_bytes(nw * 4), None)
            if self.dtype.is_bytes and dtype.is_dictionary:
                return bytescol.bytes_to_dict(self, self.capacity)
            raise TypeError_(
                "cast between string bytes and non-string requires "
                "host round-trip")
        if self.dtype.is_dictionary != dtype.is_dictionary:
            raise TypeError_(
                "cast between string and non-string requires host round-trip")
        return Column(self.data.astype(dtype.physical), self.validity, dtype,
                      self.dictionary if dtype.is_dictionary else None)

    def __repr__(self):
        return (f"Column({self.dtype!r}, cap={self.capacity}"
                f"{', nullable' if self.validity is not None else ''})")
