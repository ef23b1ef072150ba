"""Columnar device tables: the TPU-native answer to the reference's DataContainer.

The reference wraps a lazy dask DataFrame plus a frontend/backend column-name
mapping (/root/reference/dask_sql/datacontainer.py:14-191) because renaming
dask columns costs task-graph nodes.  Here a table is an ordered list of
``Column`` objects, each wrapping one ``jax.Array`` on device; renames and
projections are free dict surgery on the host, so no front/back mapping layer
is needed — ``Table.rename``/``limit_to`` give the same API shape with O(1)
cost.

Null handling: every column may carry a boolean validity ``mask`` (True =
valid).  TPUs have no NaN-for-int story and XLA wants uniform static buffers,
so masks are explicit companion arrays, unlike the reference's pandas nullable
dtypes (mappings.py:67-83).

Strings are dictionary-encoded at ingestion: ``data`` holds int32 codes into a
host-side numpy ``dictionary`` of unique values.  String kernels operate on
the (small) dictionary on host and on codes on device — the TPU never touches
variable-length bytes.  Code -1 is reserved for null strings' code slot (the
mask is still authoritative).
"""
from __future__ import annotations

import datetime
import itertools
from dataclasses import dataclass, replace
from typing import Any, Iterable, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from .types import (
    SqlType,
    BOOLEAN,
    DOUBLE,
    VARCHAR,
    NULLTYPE,
    physical_dtype,
    physical_to_python_value,
    python_value_to_physical,
    sql_type_from_numpy,
)


# ---------------------------------------------------------------------------
# Scalar
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class Scalar:
    """A typed SQL scalar in physical representation. ``value is None`` = NULL."""

    value: Any
    stype: SqlType

    @property
    def is_null(self) -> bool:
        return self.value is None

    def to_python(self):
        return physical_to_python_value(self.value, self.stype)


NULL = Scalar(None, NULLTYPE)


# ---------------------------------------------------------------------------
# Column
# ---------------------------------------------------------------------------

class Column:
    """One device column: jax data + optional validity mask + logical type."""

    __slots__ = ("data", "mask", "stype", "dictionary", "host_cache")

    def __init__(
        self,
        data: jax.Array,
        stype: SqlType,
        mask: Optional[jax.Array] = None,
        dictionary: Optional[np.ndarray] = None,
        host_cache: Optional[tuple] = None,
    ):
        self.data = data
        self.stype = stype
        self.mask = mask
        self.dictionary = dictionary
        # (np_data, np_mask_or_None): set when a host copy already exists
        # (e.g. the compiled executor's single-fetch materialization) so
        # to_numpy/to_pandas skip the device round trip
        self.host_cache = host_cache
        if stype.is_string and dictionary is None:
            raise ValueError("string columns require a dictionary")

    # -- constructors ------------------------------------------------------
    @staticmethod
    def from_numpy(values: np.ndarray, stype: Optional[SqlType] = None,
                   mask: Optional[np.ndarray] = None) -> "Column":
        data, m, st, dictionary = host_encode_numpy(values, stype, mask)
        return Column(jnp.asarray(data), st, _as_mask(m), dictionary)

    @staticmethod
    def _encode_strings(values: np.ndarray, mask: Optional[np.ndarray]) -> "Column":
        data, m, st, dictionary = _host_encode_strings(values, mask)
        return Column(jnp.asarray(data), st, _as_mask(m), dictionary)

    @staticmethod
    def from_scalar(scalar: Scalar, length: int) -> "Column":
        stype = scalar.stype
        if scalar.is_null:
            if stype.name == "NULL":
                stype = DOUBLE
            data = jnp.zeros(length, dtype=physical_dtype(stype))
            if stype.is_string:
                return Column(data.astype(jnp.int32), stype,
                              jnp.zeros(length, dtype=bool), np.array([""], dtype=object))
            return Column(data, stype, jnp.zeros(length, dtype=bool))
        if stype.is_string:
            return Column(jnp.zeros(length, dtype=jnp.int32), stype, None,
                          np.array([scalar.value], dtype=object))
        return Column(jnp.full(length, scalar.value, dtype=physical_dtype(stype)), stype, None)

    # -- basics ------------------------------------------------------------
    def __len__(self) -> int:
        return int(self.data.shape[0])

    @property
    def has_nulls(self) -> bool:
        return self.mask is not None

    def valid_mask(self) -> jax.Array:
        """Always-materialized validity mask."""
        if self.mask is None:
            return jnp.ones(self.data.shape[0], dtype=bool)
        return self.mask

    def null_count(self) -> int:
        if self.mask is None:
            return 0
        return int((~self.mask).sum())

    def _drop_allvalid_mask(self) -> "Column":
        """Materialization-boundary normalization: all-True mask -> None.

        Computation paths carry masks unconditionally (sync-free, traceable);
        only here, where the host is about to look at the data anyway, is the
        one-off ``mask.all()`` sync acceptable.
        """
        if self.mask is not None and bool(np.asarray(self.mask).all()):
            return Column(self.data, self.stype, None, self.dictionary)
        return self

    def with_mask(self, mask: Optional[jax.Array]) -> "Column":
        # no all-valid -> None normalization here: that would be a blocking
        # host sync per call (and a trace breaker under jit); materialization
        # (to_numpy) drops all-valid masks instead
        return Column(self.data, self.stype, mask, self.dictionary)

    def cast_data(self, data: jax.Array, stype: Optional[SqlType] = None) -> "Column":
        return Column(data, stype or self.stype, self.mask, self.dictionary)

    def take(self, indices: jax.Array) -> "Column":
        """Gather rows by position (device gather)."""
        data = jnp.take(self.data, indices, axis=0)
        mask = None if self.mask is None else jnp.take(self.mask, indices, axis=0)
        return Column(data, self.stype, mask, self.dictionary)

    def slice(self, start: int, stop: int) -> "Column":
        data = self.data[start:stop]
        mask = None if self.mask is None else self.mask[start:stop]
        return Column(data, self.stype, mask, self.dictionary)

    # -- dictionary helpers ------------------------------------------------
    def decode(self) -> np.ndarray:
        """Host numpy array of python objects (strings/None) for a string column."""
        assert self.stype.is_string
        codes = np.asarray(self.data)
        out = self.dictionary[np.clip(codes, 0, len(self.dictionary) - 1)]
        if self.mask is not None:
            out = out.copy()
            out[~np.asarray(self.mask)] = None
        return out

    def dict_ranks(self) -> "Column":
        """Map codes to sort-order ranks so ORDER BY / comparisons work on device.

        The dictionary produced at encode time is sorted (np.unique), but
        derived columns can have unsorted dictionaries — compute rank array on
        host (dictionary is small) and gather on device.
        """
        assert self.stype.is_string
        order = dict_sort_order(self.dictionary)
        ranks = np.empty(len(order), dtype=np.int32)
        ranks[order] = np.arange(len(order), dtype=np.int32)
        data = jnp.take(jnp.asarray(ranks), jnp.clip(self.data, 0, len(ranks) - 1))
        return Column(data, SqlType("INTEGER"), self.mask)

    # -- host conversion ---------------------------------------------------
    def to_numpy(self) -> np.ndarray:
        """Host representation with rich types; nulls become None/NaN/NaT."""
        if self.host_cache is not None:
            hd, hm = self.host_cache
            self = Column(hd, self.stype,
                          None if hm is None else hm, self.dictionary)
        self = self._drop_allvalid_mask()
        n = self.stype.name
        if self.stype.is_string:
            return self.decode()
        data = np.asarray(self.data)
        if n == "DATE":
            out = data.astype("datetime64[D]")
            if self.mask is not None:
                out[~np.asarray(self.mask)] = np.datetime64("NaT")
            return out
        if n in ("TIMESTAMP", "TIMESTAMP_WITH_LOCAL_TIME_ZONE"):
            out = data.astype("datetime64[us]")
            if self.mask is not None:
                out[~np.asarray(self.mask)] = np.datetime64("NaT")
            return out
        if n == "INTERVAL_DAY_TIME":
            out = data.astype("timedelta64[ms]")
            if self.mask is not None:
                out[~np.asarray(self.mask)] = np.timedelta64("NaT")
            return out
        if n == "TIME":
            from .types import physical_to_python_value
            vals = [physical_to_python_value(int(v), self.stype) for v in data.tolist()]
            out = np.array(vals, dtype=object)
            if self.mask is not None:
                out[~np.asarray(self.mask)] = None
            return out
        if self.mask is not None:
            if data.dtype.kind == "f":
                out = data.copy()
                out[~np.asarray(self.mask)] = np.nan
                return out
            # ints/bools with nulls -> object array with None
            out = data.astype(object)
            out[~np.asarray(self.mask)] = None
            return out
        return data

    def to_pylist(self) -> list:
        np_vals = self.to_numpy()
        out = []
        for v in np_vals.tolist():
            out.append(v)
        return out

    def __repr__(self):
        return f"Column({self.stype}, len={len(self)}, nulls={self.null_count()})"


def dict_sort_order(dictionary: np.ndarray) -> np.ndarray:
    """Dictionary indices in string sort order: order[rank] = dict index.

    The single source of truth for string collation — group ordering,
    MIN/MAX, and static-domain key decoding must all agree on it.
    """
    return np.argsort(dictionary.astype(str), kind="stable")


def _as_mask(mask) -> Optional[jax.Array]:
    if mask is None:
        return None
    mask = np.asarray(mask, dtype=bool)
    if mask.all():
        return None
    return jnp.asarray(mask)


# ---------------------------------------------------------------------------
# Table
# ---------------------------------------------------------------------------

class Table:
    """An ordered, named collection of equal-length Columns."""

    __slots__ = ("names", "columns", "uid")

    _uid_counter = itertools.count()

    def __init__(self, names: Sequence[str], columns: Sequence[Column]):
        assert len(names) == len(columns)
        self.names = list(names)
        self.columns = list(columns)
        # monotonic identity: unlike id(), never reused after GC — the
        # compiled-query cache keys on it (physical/compiled.py)
        self.uid = next(Table._uid_counter)

    # -- constructors ------------------------------------------------------
    @staticmethod
    def from_pandas(df) -> "Table":
        return Table.host_from_pandas(df).to_device()

    @staticmethod
    def host_from_pandas(df) -> "Table":
        """The frame encoded as the device will hold it, still on the host:
        every column's ``data`` and ``mask`` are numpy arrays.  What
        ``Context.create_table`` takes its statistics from before
        ``to_device`` uploads (the values never come back from the chip)."""
        series = [df[name] for name in df.columns]
        cols = [Column(data, stype, mask, dictionary)
                for data, mask, stype, dictionary
                in map_columns(host_encode_series, series, len(df))]
        return Table([str(name) for name in df.columns], cols)

    def to_device(self) -> "Table":
        """A host-encoded table's columns uploaded, one ``jnp.asarray`` a
        column; an all-valid mask is dropped, as at every ingestion."""
        return Table(self.names, [
            Column(jnp.asarray(c.data), c.stype, _as_mask(c.mask),
                   c.dictionary) for c in self.columns])

    @staticmethod
    def from_pydict(data: dict) -> "Table":
        names, cols = [], []
        for k, v in data.items():
            names.append(k)
            if isinstance(v, Column):
                cols.append(v)
            else:
                arr = np.asarray(v) if not _has_none(v) else np.asarray(v, dtype=object)
                if arr.dtype.kind == "O" and not _all_strings(arr):
                    arr2, mask = _denull(v)
                    cols.append(Column.from_numpy(arr2, mask=mask))
                else:
                    cols.append(Column.from_numpy(arr))
        return Table(names, cols)

    # -- basics ------------------------------------------------------------
    @property
    def num_rows(self) -> int:
        if not self.columns:
            return 0
        return len(self.columns[0])

    @property
    def num_columns(self) -> int:
        return len(self.columns)

    def column(self, name: str) -> Column:
        return self.columns[self.names.index(name)]

    def __contains__(self, name: str) -> bool:
        return name in self.names

    def limit_to(self, names: Iterable[str]) -> "Table":
        """Project to a subset/reordering of columns (reference:
        datacontainer.py:53 ColumnContainer.limit_to) — O(1), no device work."""
        names = list(names)
        return Table(names, [self.column(n) for n in names])

    def rename(self, mapping: dict) -> "Table":
        return Table([mapping.get(n, n) for n in self.names], self.columns)

    def with_names(self, names: Sequence[str]) -> "Table":
        assert len(names) == len(self.columns)
        return Table(list(names), self.columns)

    def add_column(self, name: str, col: Column) -> "Table":
        return Table(self.names + [name], self.columns + [col])

    def take(self, indices: jax.Array) -> "Table":
        return Table(self.names, [c.take(indices) for c in self.columns])

    def slice(self, start: int, stop: int) -> "Table":
        return Table(self.names, [c.slice(start, stop) for c in self.columns])

    def head(self, n: int) -> "Table":
        return self.slice(0, min(n, self.num_rows))

    def schema(self) -> list:
        return list(zip(self.names, [c.stype for c in self.columns]))

    # -- host conversion ---------------------------------------------------
    def to_pandas(self):
        import pandas as pd

        # fetch every device buffer in ONE transfer: per-column np.asarray
        # would pay a device round trip each; columns with
        # a host cache (compiled-executor results) need no fetch at all
        buffers = []
        for col in self.columns:
            if col.host_cache is not None:
                continue
            buffers.append(col.data)
            if col.mask is not None:
                buffers.append(col.mask)
        fetched = iter(jax.device_get(buffers) if buffers else [])
        data = {}
        for name, col in zip(self.names, self.columns):
            if col.host_cache is not None:
                data[name] = col.to_numpy()
                continue
            host_data = next(fetched)
            host_mask = next(fetched) if col.mask is not None else None
            host_col = Column(host_data, col.stype, host_mask, col.dictionary)
            data[name] = host_col.to_numpy()
        df = pd.DataFrame(data, columns=list(self.names))
        return df

    def to_pylist(self) -> list:
        cols = [c.to_pylist() for c in self.columns]
        return [list(row) for row in zip(*cols)] if cols else []

    def __repr__(self):
        parts = ", ".join(f"{n}: {c.stype}" for n, c in zip(self.names, self.columns))
        return f"Table[{self.num_rows} rows]({parts})"


#: a load works its columns side by side from this many rows on: hashing
#: strings to codes, casts and reductions over whole columns leave the
#: interpreter's lock, and below it the threads cost more than they save
PARALLEL_LOAD_ROWS = 1 << 20
_LOAD_WORKERS = 8


def map_columns(fn, columns, rows: int) -> list:
    """``[fn(c) for c in columns]``, the columns of a table of
    ``PARALLEL_LOAD_ROWS`` rows or more taken side by side."""
    if rows < PARALLEL_LOAD_ROWS or len(columns) < 2:
        return [fn(c) for c in columns]
    from concurrent.futures import ThreadPoolExecutor

    with ThreadPoolExecutor(min(_LOAD_WORKERS, len(columns))) as pool:
        return list(pool.map(fn, columns))


_PANDAS_NULLABLE_NUMPY = {
    "Int8": np.int8, "Int16": np.int16, "Int32": np.int32, "Int64": np.int64,
    "UInt8": np.uint8, "UInt16": np.uint16, "UInt32": np.uint32, "UInt64": np.uint64,
    "Float32": np.float32, "Float64": np.float64, "boolean": np.bool_,
}


def host_encode_numpy(values: np.ndarray, stype: Optional[SqlType] = None,
                      mask: Optional[np.ndarray] = None,
                      dictionary: Optional[np.ndarray] = None):
    """Ingestion encoding on HOST arrays: (data, mask, stype, dictionary).

    The single source of truth for ingestion semantics — `Column.from_numpy`
    is this plus a device upload, and the chunked/out-of-core reader
    (io/chunked.py) uses it directly so batches stay host-side until their
    turn to stream through the device. ``dictionary``: optional pre-built
    SORTED global dictionary for string columns (shared across batches so
    every batch compiles to the same program)."""
    values = np.asarray(values)
    if (values.dtype.kind == "O" and (stype is None or not stype.is_string)
            and _first_present_is_decimal(values)):
        import decimal as _decimal

        isna = np.array([_is_null_object(v) for v in values], dtype=bool)
        present = values[~isna]
        if len(present) and all(isinstance(v, _decimal.Decimal)
                                and v.is_finite() for v in present):
            # ALL-finite decimal.Decimal columns ingest as DECIMAL(p, s)
            # with p measured from the data: f64 storage + a typed scale, so
            # SUM/AVG take the exact scaled-int64 path when every value fits
            # the f64 mantissa exactly (types.exact_decimal_scale gates at
            # p<=15 since 10^15 < 2^53).  Mixed or non-finite object columns
            # keep the generic path.
            scale = 0
            int_digits = 1
            for v in present:
                t = v.as_tuple()
                scale = max(scale, -int(t.exponent))
                int_digits = max(int_digits, len(t.digits) + int(t.exponent))
            precision = int_digits + scale
            data = np.array([0.0 if na else float(v)
                             for v, na in zip(values, isna)], dtype=np.float64)
            m = (~isna if mask is None
                 else (np.asarray(mask, bool) & ~isna))
            if m.all():
                m = None
            from .types import decimal as _mk_decimal
            if scale > 9 or precision > 15:
                # outside the exact-int64/f64-mantissa envelope: typed
                # honestly (so the exact path declines), unquantized f64
                return data, m, _mk_decimal(max(precision, 16), scale), None
            return data, m, _mk_decimal(15, scale), None
    if stype is None:
        stype = sql_type_from_numpy(values.dtype)
    if values.dtype.kind in ("O", "U", "S") or stype.is_string:
        return _host_encode_strings(values, mask, dictionary)
    if values.dtype.kind == "M":
        vals = values.astype("datetime64[us]").astype(np.int64)
        na = np.isnat(values)
        if na.any():
            mask = ~na if mask is None else (mask & ~na)
        return vals, mask, stype, None
    if values.dtype.kind == "m":
        vals = values.astype("timedelta64[ms]").astype(np.int64)
        na = np.isnat(values)
        if na.any():
            mask = ~na if mask is None else (mask & ~na)
        return vals, mask, stype, None
    if values.dtype.kind == "f":
        # NaN means NULL on ingestion (pandas semantics: the reference's
        # dask frames treat NaN as missing, mappings.py:67-83)
        na = np.isnan(values)
        if na.any():
            mask = ~na if mask is None else (np.asarray(mask, bool) & ~na)
            values = np.where(na, 0.0, values)
    dtype = physical_dtype(stype)
    return values.astype(dtype, copy=False), mask, stype, None


def _is_null_object(v) -> bool:
    return v is None or (isinstance(v, float) and v != v)


def _first_present_is_decimal(values: np.ndarray) -> bool:
    """Whether the first non-NULL value of an object array is a
    ``decimal.Decimal``: the one look that decides whether the column is
    worth the per-element scan of the DECIMAL branch (a string column of
    sixty million rows is not)."""
    import decimal as _decimal

    for v in values:
        if not _is_null_object(v):
            return isinstance(v, _decimal.Decimal)
    return False


def _factorize_strings(values):
    """``(codes, uniques)`` of a string-ish column by hashing: ``codes`` are
    positions in ``uniques`` in order of first appearance (-1 for a NULL
    row), ``uniques`` an object array of ``str``.  ``values`` is an object
    ndarray or a pandas string array (pandas >= 3 hands out arrow-backed
    ones, which never become Python objects here).  Returns None where
    hashing cannot stand in for ``str()`` of every row: a value that is
    neither ``str`` nor ``bytes`` (1, 1.0 and True hash alike and print
    differently), or one that cannot be hashed."""
    import pandas as pd

    try:
        codes, uniques = pd.factorize(values, use_na_sentinel=True)
    except TypeError:
        return None
    uniques = np.asarray(uniques, dtype=object)
    kind = pd.api.types.infer_dtype(uniques, skipna=False)
    if kind not in ("string", "empty"):
        if not all(isinstance(u, (str, bytes)) for u in uniques):
            return None
        uniques = np.array(
            [u.decode("utf-8", "surrogateescape") if isinstance(u, bytes)
             else u for u in uniques], dtype=object)
    return codes, uniques


def _strings_by_element(values: np.ndarray):
    """``(safe, isna)`` of an object array, one Python step per row: bytes
    decode by utf-8/surrogateescape, NULL and NaN become "", anything
    else its ``str()``.  Only columns ``_factorize_strings`` declines."""
    values = np.array(
        [v.decode("utf-8", "surrogateescape")
         if isinstance(v, (bytes, bytearray)) else v for v in values],
        dtype=object)
    isna = np.array([_is_null_object(v) for v in values], dtype=bool)
    return np.where(isna, "", values).astype(str), isna


def string_uniques(values) -> np.ndarray:
    """Sorted unique strings of an object array (NULLs -> ""), the shared
    null-semantics for ingestion and the chunked reader's dictionary pass."""
    return _host_encode_strings(values, None)[3]


def _host_encode_strings(values, mask: Optional[np.ndarray],
                         dictionary: Optional[np.ndarray] = None):
    """Dictionary-encode a string-ish column on the host: int32 codes into
    a SORTED dictionary (``Column.dict_ranks``, ``dict_sort_order`` and every
    string comparison rest on the order), NULL and NaN as "" with the mask
    cleared, ``bytes`` decoded by utf-8/surrogateescape.  The rows are
    hashed to codes; what is sorted, decoded and searched is the
    dictionary, which is small."""
    if isinstance(values, np.ndarray) and values.dtype.kind != "O":
        values = values.astype(object)
    hashed = _factorize_strings(values)
    if hashed is None:
        safe, isna = _strings_by_element(np.asarray(values, dtype=object))
        uniques, codes = np.unique(safe, return_inverse=True)
    else:
        codes, uniques = hashed
        isna = codes < 0
        uniques = uniques.astype(str)
    has_null = bool(isna.any())
    if hashed is not None and has_null:
        codes = np.where(isna, len(uniques), codes)
        uniques = np.append(uniques, "")
    if dictionary is None:
        dictionary, renumber = np.unique(uniques, return_inverse=True)
        dictionary = dictionary.astype(object)
    else:
        # shared global dictionary (sorted): encode via binary search.  The
        # two-pass chunked reader guarantees membership; verify anyway — an
        # absent value would silently take a neighbor's code otherwise.
        dict_str = dictionary.astype(str)
        renumber = np.clip(np.searchsorted(dict_str, uniques), 0,
                           max(len(dict_str) - 1, 0))
        absent = (dict_str[renumber] != uniques if len(dict_str)
                  else np.ones(len(uniques), dtype=bool))
        if absent.any():
            missing = np.unique(uniques[absent])[:5]
            raise ValueError(
                "string batch contains values absent from the shared "
                f"dictionary (first few: {missing.tolist()!r}); the "
                "dictionary pass missed this column's values")
    codes = renumber.astype(np.int32)[codes]
    if has_null:
        m = ~isna if mask is None else (np.asarray(mask, bool) & ~isna)
    else:
        m = mask
    return codes, m, VARCHAR, dictionary


def host_encode_series(s, dictionary: Optional[np.ndarray] = None):
    """Host-side encoding of a pandas Series: (data, mask, stype, dict)."""
    import pandas as pd

    dtype = s.dtype
    # pandas nullable extension dtypes (Int64, boolean, Float64, ...)
    if str(dtype) in _PANDAS_NULLABLE_NUMPY:
        arr = s.array
        mask = ~np.asarray(arr.isna())
        vals = arr.to_numpy(dtype=_PANDAS_NULLABLE_NUMPY[str(dtype)], na_value=0)
        return host_encode_numpy(vals, mask=mask if not mask.all() else None,
                                 dictionary=dictionary)
    if str(dtype) in ("string", "str") or (
        hasattr(pd, "StringDtype") and isinstance(dtype, pd.StringDtype)
    ):
        return _host_encode_strings(s.array, None, dictionary)
    if isinstance(dtype, pd.CategoricalDtype):
        if dictionary is not None:
            # a shared global dictionary overrides the per-batch categories:
            # chunked sources must not mix batch-local codes with a global
            # dictionary (arrow row groups may carry differing categories)
            vals = s.astype(object).to_numpy()
            return host_encode_numpy(vals, dictionary=dictionary)
        cats = s.cat.categories.to_numpy(dtype=object)
        codes = s.cat.codes.to_numpy().astype(np.int32)
        mask = codes >= 0
        if mask.all():
            mask = None
        return np.where(codes < 0, 0, codes).astype(np.int32), mask, VARCHAR, cats
    if dtype.kind == "M":
        # tz-aware -> convert to UTC naive
        if getattr(dtype, "tz", None) is not None:
            s = s.dt.tz_convert("UTC").dt.tz_localize(None)
        return host_encode_numpy(s.to_numpy(), dictionary=dictionary)
    return host_encode_numpy(s.to_numpy(), dictionary=dictionary)


def _has_none(v) -> bool:
    try:
        return any(x is None for x in v)
    except TypeError:
        return False


def _all_strings(arr) -> bool:
    return all(isinstance(x, str) for x in arr.tolist())


def _denull(v):
    vals = list(v)
    mask = np.array([x is not None for x in vals])
    if all(isinstance(x, str) or x is None for x in vals):
        arr = np.array(["" if x is None else x for x in vals], dtype=object)
        return arr, mask
    arr = np.array([0 if x is None else x for x in vals])
    return arr, mask
