"""Out-of-HBM table source: host-resident encoded batches, device-streamed.

The reference's entire execution model is out-of-core partitioned dataframes
(dask_sql over dd.DataFrame; ingestion partitioning at
/root/reference/dask_sql/input_utils/pandaslike.py:22, cluster persist at
input_utils/convert.py:59-60).  The TPU-first analogue: a table larger than
HBM lives on the HOST as already-encoded columnar batches (numpy: numeric
data + int32 string codes), and the streaming executor
(physical/streaming.py) uploads one fixed-size batch at a time, running the
same compiled program per batch.

Two invariants make per-batch execution compile ONCE instead of per batch:

- every batch is padded to exactly ``batch_rows`` with a row-validity mask
  (same machinery as mesh-mode padding), so all batches share shapes;
- string dictionaries are GLOBAL across batches (two-pass: union the
  per-batch uniques, then encode against the sorted union), so the program
  cache's dictionary-content fingerprint matches for every batch.
"""
from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import numpy as np

from ..runtime import faults as _faults, telemetry as _tel
from ..runtime.resilience import UserError
from ..table import Column, Table, host_encode_series

DEFAULT_BATCH_ROWS = 1 << 22  # 4M rows/batch ~= a few hundred MB on device


class ChunkedInputError(UserError, ValueError):
    """Unrepresentable input shape (typed for the resilience taxonomy;
    still a ValueError for callers predating the taxonomy)."""


class ChunkedSource:
    """Host-side encoded columnar batches with a shared schema."""

    def __init__(self, names: Sequence[str], stypes, dictionaries,
                 batches: List[list], n_rows: int, batch_rows: int):
        self.names = list(names)
        self.stypes = list(stypes)
        self.dictionaries = list(dictionaries)
        self.batches = batches          # list of [(data, mask), ...] per col
        self.n_rows = n_rows
        self.batch_rows = batch_rows

    # ------------------------------------------------------------ building
    @staticmethod
    def from_pandas(df, batch_rows: int = DEFAULT_BATCH_ROWS,
                    _precomputed_dicts: Optional[dict] = None
                    ) -> "ChunkedSource":
        """Encode a pandas frame into host batches (shared dictionaries)."""
        import pandas as pd  # noqa: F401

        n = len(df)
        batch_rows = max(int(batch_rows), 1)
        dicts = {}
        if _precomputed_dicts:
            dicts.update(_precomputed_dicts)
        from ..table import string_uniques

        # pass 1: global sorted dictionary per string-ish column (including
        # categoricals — their per-batch category order must not leak)
        for name in df.columns:
            if name in dicts:
                continue
            s = df[name]
            is_cat = isinstance(s.dtype, pd.CategoricalDtype)
            if s.dtype == object or is_cat or str(s.dtype) in ("string", "str"):
                if str(s.dtype) in ("string", "str"):
                    vals = s.to_numpy(dtype=object, na_value=None)
                else:
                    vals = s.astype(object).to_numpy()
                dicts[name] = string_uniques(vals)
        # pass 2: encode per batch against the shared dictionaries
        starts = list(range(0, max(n, 1), batch_rows))
        batches: List[list] = []
        names = list(df.columns)
        stypes: list = [None] * len(names)
        dictionaries: list = [None] * len(names)
        for s0 in starts:
            chunk = df.iloc[s0:s0 + batch_rows]
            enc = []
            for ci, name in enumerate(names):
                data, mask, stype, dictionary = host_encode_series(
                    chunk[name], dictionary=dicts.get(name))
                stypes[ci] = stype
                if dictionary is not None:
                    dictionaries[ci] = dictionary
                enc.append((data, mask))
            batches.append(enc)
        return ChunkedSource(names, stypes, dictionaries, batches, n,
                             batch_rows)

    @staticmethod
    def from_parquet(path: str, batch_rows: int = DEFAULT_BATCH_ROWS
                     ) -> "ChunkedSource":
        """Two-pass parquet ingestion that never materializes the whole file
        as one pandas frame: pass 1 unions per-row-group string uniques into
        global dictionaries, pass 2 encodes row groups into host batches."""
        import pyarrow.parquet as pq

        import pyarrow.types as patypes

        def _needs_global_dict(t) -> bool:
            # Any arrow type whose pandas conversion yields object values
            # must share ONE dictionary across row groups, or merged batches
            # decode against piece-0 codes (silent wrong results).  Covers
            # string/large_string/string_view, binary/large_binary/
            # fixed_size_binary/binary_view, and dictionary-of-any.
            for pred in ("is_string", "is_large_string", "is_string_view",
                         "is_binary", "is_large_binary",
                         "is_fixed_size_binary", "is_binary_view",
                         "is_dictionary"):
                fn = getattr(patypes, pred, None)
                if fn is not None and fn(t):
                    return True
            return False

        pf = pq.ParquetFile(path)
        schema = pf.schema_arrow
        for f in schema:
            if patypes.is_nested(f.type):
                raise ChunkedInputError(
                    f"from_parquet: column {f.name!r} has nested arrow type "
                    f"{f.type} — not representable as a columnar SQL type")
        str_cols = [f.name for f in schema if _needs_global_dict(f.type)]
        from ..table import string_uniques

        uniques = {c: [] for c in str_cols}
        if str_cols:
            for rg in range(pf.num_row_groups):
                tbl = pf.read_row_group(rg, columns=str_cols)
                for c in str_cols:
                    vals = tbl.column(c).to_pandas().astype(object).to_numpy()
                    uniques[c].append(string_uniques(vals))
        dicts = {c: np.unique(np.concatenate(u)).astype(object)
                 for c, u in uniques.items() if u}

        pieces = []
        source = None
        for batch in pf.iter_batches(batch_size=batch_rows):
            df = batch.to_pandas()
            piece = ChunkedSource.from_pandas(df, batch_rows=batch_rows,
                                              _precomputed_dicts=dicts)
            pieces.append(piece)
        if not pieces:
            df = pf.read().to_pandas()
            return ChunkedSource.from_pandas(df, batch_rows=batch_rows)
        source = pieces[0]
        for extra in pieces[1:]:
            for ci, name in enumerate(source.names):
                a, b = source.dictionaries[ci], extra.dictionaries[ci]
                if a is b:
                    continue
                if (a is None) != (b is None) or (
                        a is not None and not np.array_equal(a, b)):
                    # A column type slipped past _needs_global_dict and got
                    # per-piece local dictionaries; mixing their codes would
                    # silently decode wrong values.
                    raise ChunkedInputError(
                        f"from_parquet: column {name!r} produced differing "
                        "per-piece dictionaries; its arrow type needs a "
                        "global dictionary pass")
            source.batches.extend(extra.batches)
            source.n_rows += extra.n_rows
        # iter_batches can emit a short non-final batch at row-group edges;
        # re-batching keeps the fixed-size invariant the compiler relies on
        source._rebatch()
        return source

    def _rebatch(self) -> None:
        """Normalize to fixed-size batches after concatenating pieces.

        Incremental: source pieces stream through a per-column carry
        buffer and are RELEASED as they are consumed, so the transient
        footprint is bounded by one output batch plus one input piece —
        the table is never materialized as full contiguous host arrays
        (which would defeat out-of-core parquet ingestion at exactly the
        table sizes chunking exists for)."""
        if all(len(b[0][0]) == self.batch_rows for b in self.batches[:-1]):
            return
        cols = len(self.names)
        has_mask = [any(b[ci][1] is not None for b in self.batches)
                    for ci in range(cols)]
        dtypes = [self.batches[0][ci][0].dtype for ci in range(cols)]
        out: List[list] = []
        pending: List[list] = [[] for _ in range(cols)]  # (data, mask)
        pending_rows = 0

        def emit(k: int) -> None:
            nonlocal pending_rows
            enc = []
            for ci in range(cols):
                frags = pending[ci]
                datas, masks, got = [], [], 0
                while got < k:
                    data, mask = frags[0]
                    take = min(k - got, len(data))
                    datas.append(data[:take])
                    if has_mask[ci]:
                        masks.append(mask[:take] if mask is not None
                                     else np.ones(take, dtype=bool))
                    if take == len(data):
                        frags.pop(0)
                    else:
                        frags[0] = (data[take:],
                                    None if mask is None else mask[take:])
                    got += take
                data = (datas[0] if len(datas) == 1
                        else np.concatenate(datas))
                mask = None
                if has_mask[ci]:
                    mask = (masks[0] if len(masks) == 1
                            else np.concatenate(masks))
                enc.append((data, mask))
            pending_rows -= k
            out.append(enc)

        src = self.batches
        for bi in range(len(src)):
            piece = src[bi]
            src[bi] = None  # release: the carry buffer bounds memory
            n = len(piece[0][0]) if piece else 0
            for ci in range(cols):
                pending[ci].append(piece[ci])
            pending_rows += n
            while pending_rows >= self.batch_rows:
                emit(self.batch_rows)
        if pending_rows:
            emit(pending_rows)
        if not out:
            # zero-row table: keep the one-empty-batch invariant
            out.append([(np.zeros(0, dtype=dtypes[ci]), None)
                        for ci in range(cols)])
        self.batches = out

    # ----------------------------------------------------------- consuming
    @property
    def n_batches(self) -> int:
        return len(self.batches)

    def schema_table(self) -> Table:
        """A 1-row stub carrying names/stypes/dictionaries for BINDING only —
        the streaming executor intercepts execution before any path could
        compute on it (context guards this)."""
        import jax.numpy as jnp

        cols = []
        for ci, stype in enumerate(self.stypes):
            dtype = (self.batches[0][ci][0].dtype if self.batches
                     else np.float64)
            dictionary = self.dictionaries[ci]
            if stype.is_string and dictionary is None:
                dictionary = np.array([""], dtype=object)
            cols.append(Column(jnp.zeros(1, dtype=dtype), stype, None,
                               dictionary))
        return Table(self.names, cols)

    def batch_table(self, i: int) -> Tuple[Table, Optional["object"]]:
        """Device Table for batch i, padded to batch_rows (+ row_valid).

        The host→device upload is the ``chunked_read`` fault site: the
        consumer (physical/streaming.py _run_batches) retries transients —
        the encoded host batch is immutable, so a re-upload is safe."""
        import jax.numpy as jnp

        _faults.maybe_fail("chunked_read")
        enc = self.batches[i]
        n = len(enc[0][0]) if enc else 0
        pad = self.batch_rows - n
        cols = []
        upload_bytes = 0
        for ci, (data, mask) in enumerate(enc):
            if pad:
                data = np.concatenate(
                    [data, np.zeros(pad, dtype=data.dtype)])
                if mask is not None:
                    mask = np.concatenate([mask, np.zeros(pad, dtype=bool)])
            upload_bytes += int(data.nbytes) + (
                int(mask.nbytes) if mask is not None else 0)
            dev = jnp.asarray(data)
            m = None if mask is None else jnp.asarray(mask)
            cols.append(Column(dev, self.stypes[ci], m,
                               self.dictionaries[ci]))
        row_valid = None
        if pad:
            row_valid = jnp.arange(self.batch_rows) < n
        # upload size rides the enclosing stream_batch span: per-batch
        # host→device traffic is the streaming mode's dominant cost,
        # so a slow batch should name its own byte count
        _tel.annotate(upload_bytes=upload_bytes)
        return Table(self.names, cols), row_valid
