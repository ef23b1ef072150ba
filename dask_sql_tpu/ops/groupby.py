"""Segmented aggregation kernels: SQL GROUP BY on device.

TPU-native replacement for the reference's groupby lowering
(/root/reference/dask_sql/physical/rel/logical/aggregate.py:19-361 and the
NULL-group trick in physical/utils/groupby.py:8-34): keys factorize to dense
codes (NULLs form their own group), then every aggregate is a
``jax.ops.segment_*`` reduction — no shuffle, no per-group python.
"""
from __future__ import annotations

from typing import List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..table import dict_sort_order, Column, Scalar, Table
from ..types import SqlType, exact_decimal_scale, physical_dtype
from .kernels import (comparable_data, compact_indices, decimal_unscale,
                      factorize_columns)


def group_codes(key_cols: List[Column], variant: str = "hash",
                dense_hint=None):
    """Factorize group keys into dense codes 0..G-1.

    Returns (codes, first_row_per_group, G, used_variant).  ``variant``
    comes from the stats crossover (runtime/statistics.py): "hash" is the
    status-quo ``factorize_columns`` (jnp.unique), "sorted" is one stable
    lexsort + boundary scan, "dense" is the direct-index path
    (``codes = key - min``, no hashing, no sort) for a single small-domain
    int key.  All three produce IDENTICAL group numbering (ascending key
    order, NULL groups first) and identical representative rows, so the
    dispatch can never change results — a variant that doesn't apply falls
    through to the next ("dense" → "sorted" needs a single int key;
    "sorted" and "hash" always apply)."""
    if not key_cols:
        return None, None, 1, "none"
    if variant == "dense":
        out = _dense_group_codes(key_cols, dense_hint)
        if out is not None:
            return (*out, "dense")
        variant = "sorted"
    if variant == "sorted":
        out = _sorted_group_codes(key_cols)
        if out is not None:
            return (*out, "sorted")
    return (*factorize_columns(key_cols, null_as_group=True), "hash")


#: hard ceiling on dense direct-index slots even under DSQL_FORCE_GROUPBY
_DENSE_HARD_CAP = 1 << 22


def _dense_group_codes(key_cols: List[Column], dense_hint=None):
    """Direct-index factorize for ONE integer key: slot = key - lo (+1
    when NULLs exist, which take slot 0 — matching factorize's NULL-first
    group order), occupied slots compact to dense codes via a cumsum
    remap.  O(n + domain), scatter-based — an eager-path variant (the
    compiled TPU path keeps its scatter-free sorted codes).  Returns None
    when not applicable (caller falls through)."""
    if len(key_cols) != 1:
        return None
    c = key_cols[0]
    if c.stype.is_string or not jnp.issubdtype(c.data.dtype, jnp.integer):
        return None
    n = len(c)
    if n == 0:
        return None
    data = c.data.astype(jnp.int64)
    # data under NULL rows is garbage — min/max must see valid rows only
    if c.mask is not None:
        if not bool(c.mask.any()):
            return None
        imax = jnp.iinfo(jnp.int64).max
        imin = jnp.iinfo(jnp.int64).min
        vlo = int(jnp.min(jnp.where(c.mask, data, imax)))
        vhi = int(jnp.max(jnp.where(c.mask, data, imin)))
    else:
        vlo = int(data.min())
        vhi = int(data.max())
    if dense_hint is not None:
        lo, hi = int(dense_hint[0]), int(dense_hint[1])
        # stale stats guard: rows outside the hinted domain void the hint
        if vlo < lo or vhi > hi:
            lo, hi = vlo, vhi
    else:
        lo, hi = vlo, vhi
    domain = hi - lo + 1
    if domain <= 0 or domain > _DENSE_HARD_CAP:
        return None
    has_null = c.mask is not None and bool((~c.mask).any())
    shift = 1 if has_null else 0
    slots = jnp.clip(data - lo, 0, domain - 1) + shift
    if has_null:
        slots = jnp.where(c.mask, slots, 0)
    occ = jnp.zeros(domain + shift, dtype=jnp.int64).at[slots].add(1)
    present = occ > 0
    # compact: occupied slot k -> dense code rank(k); ascending slot order
    # IS ascending key order (NULL slot 0 first) — factorize's numbering
    remap = jnp.cumsum(present.astype(jnp.int64)) - 1
    num_groups = int(remap[-1]) + 1
    codes = remap[slots]
    first = jnp.full(num_groups, n, dtype=jnp.int64).at[codes].min(
        jnp.arange(n, dtype=jnp.int64))
    return codes, first, num_groups


def _sorted_group_codes(key_cols: List[Column]):
    """Sort-based factorize: ONE stable lexsort over the key columns, then
    group boundaries fall out of adjacent-row comparisons — no hash table,
    no per-column unique.  Profitable when groups are few and fat (the
    hash/sort crossover).  Group numbering matches factorize exactly:
    per-column ordering is (null-flag, comparable value) with NULLs first,
    columns major-to-minor in key order, and the stable sort makes each
    group's first sorted row its minimum original row index.

    Returns None for floating-point keys (NaN != NaN would split NaN
    groups where unique's total order would not) — the caller falls back
    to factorize."""
    n = len(key_cols[0])
    if n == 0:
        return None
    keys = []  # significance order: col0 flag, col0 value, col1 flag, ...
    for c in key_cols:
        data = comparable_data(c)
        if jnp.issubdtype(data.dtype, jnp.floating):
            return None
        if c.mask is not None:
            keys.append(c.mask.astype(jnp.int8))      # NULL(0) first
            keys.append(jnp.where(c.mask, data, data[0]))
        else:
            keys.append(data)
    # jnp.lexsort sorts by the LAST key first -> pass minor-to-major
    order = jnp.lexsort(tuple(reversed(keys)))
    diff = jnp.zeros(max(n - 1, 0), dtype=bool)
    for k in keys:
        ks = k[order]
        diff = diff | (ks[1:] != ks[:-1])
    boundary = jnp.concatenate([jnp.ones(1, dtype=bool), diff])
    codes_sorted = jnp.cumsum(boundary.astype(jnp.int64)) - 1
    num_groups = int(codes_sorted[-1]) + 1
    codes = jnp.zeros(n, dtype=jnp.int64).at[order].set(codes_sorted)
    starts = jnp.nonzero(boundary, size=num_groups)[0]
    first = order[starts]
    return codes, first, num_groups


def _masked(col: Column, extra_mask: Optional[jax.Array]):
    data = col.data
    valid = col.valid_mask()
    if extra_mask is not None:
        valid = valid & extra_mask
    return data, valid


def _decimal_exact_result(op: str, s_int, count, dscale: int,
                          out_type: SqlType) -> Column:
    """Shared tail of the exact scaled-int64 SUM/$SUM0/AVG paths: unscale
    via the exact-quotient route and apply the SQL NULL rules (SUM over no
    rows -> NULL, $SUM0 -> 0, AVG -> NULL)."""
    has_any = count > 0
    if op in ("SUM", "$SUM0"):
        s = decimal_unscale(s_int, dscale).astype(physical_dtype(out_type))
        return Column(s, out_type, None if op == "$SUM0" else has_any)
    mean = s_int.astype(jnp.float64) / (jnp.maximum(count, 1) * 10.0 ** dscale)
    return Column(mean, out_type, has_any)


def _decimal_scaled_ints(data, dscale: int):
    """Round f64 decimal data onto its integer grid (int64 'cents')."""
    return jnp.round(data.astype(jnp.float64) * 10.0 ** dscale
                     ).astype(jnp.int64)


def segment_aggregate(op: str, col: Optional[Column], codes: Optional[jax.Array],
                      num_groups: int, out_type: SqlType,
                      filter_mask: Optional[jax.Array] = None,
                      n_rows: int = 0) -> Column:
    """One aggregate over segments. ``codes=None`` means whole-table (1 group)."""
    if codes is None:
        codes = jnp.zeros(n_rows if col is None else len(col), dtype=jnp.int64)
        num_groups = 1

    if op in ("COUNT", "REGR_COUNT"):
        if col is None:
            ones = jnp.ones(codes.shape[0], dtype=jnp.int64)
            if filter_mask is not None:
                ones = jnp.where(filter_mask, ones, 0)
            out = jax.ops.segment_sum(ones, codes, num_groups)
        else:
            data, valid = _masked(col, filter_mask)
            out = jax.ops.segment_sum(valid.astype(jnp.int64), codes, num_groups)
        return Column(out, out_type, None)

    assert col is not None, f"{op} requires an argument"
    data, valid = _masked(col, filter_mask)
    count = jax.ops.segment_sum(valid.astype(jnp.int64), codes, num_groups)
    has_any = count > 0

    if op in ("SUM", "$SUM0", "AVG", "STDDEV", "STDDEV_POP", "STDDEV_SAMP",
              "VAR_POP", "VAR_SAMP", "VARIANCE"):
        dscale = exact_decimal_scale(col.stype) if op in ("SUM", "$SUM0",
                                                          "AVG") else None
        if dscale is not None:
            # exact scaled-int64 money math: order-independent, bit-stable
            iwork = jnp.where(valid, _decimal_scaled_ints(data, dscale), 0)
            s_int = jax.ops.segment_sum(iwork, codes, num_groups)
            return _decimal_exact_result(op, s_int, count, dscale, out_type)
        work = data.astype(jnp.float64) if not jnp.issubdtype(data.dtype, jnp.integer) else data.astype(jnp.int64)
        work = jnp.where(valid, work, 0)
        s = jax.ops.segment_sum(work, codes, num_groups)
        if op == "SUM":
            return Column(s.astype(physical_dtype(out_type)), out_type,
                          has_any)
        if op == "$SUM0":
            return Column(s.astype(physical_dtype(out_type)), out_type, None)
        mean = s.astype(jnp.float64) / jnp.maximum(count, 1)
        if op == "AVG":
            return Column(mean, out_type, has_any)
        sq = jnp.where(valid, data.astype(jnp.float64) ** 2, 0.0)
        s2 = jax.ops.segment_sum(sq, codes, num_groups)
        var_pop = s2 / jnp.maximum(count, 1) - mean**2
        var_pop = jnp.maximum(var_pop, 0.0)
        if op == "VAR_POP":
            return Column(var_pop, out_type, has_any)
        denom = jnp.maximum(count - 1, 1)
        var_samp = (s2 - count * mean**2) / denom
        var_samp = jnp.maximum(var_samp, 0.0)
        ok = count > 1
        if op in ("VAR_SAMP", "VARIANCE"):
            return Column(var_samp, out_type, ok)
        if op == "STDDEV_POP":
            return Column(jnp.sqrt(var_pop), out_type,
                          has_any)
        return Column(jnp.sqrt(var_samp), out_type, ok)

    if op in ("MIN", "MAX"):
        if col.stype.is_string:
            ranked = col.dict_ranks()
            rdata = ranked.data.astype(jnp.int64)
            sentinel = jnp.iinfo(jnp.int64).max if op == "MIN" else jnp.iinfo(jnp.int64).min
            work = jnp.where(valid, rdata, sentinel)
            f = jax.ops.segment_min if op == "MIN" else jax.ops.segment_max
            out_ranks = f(work, codes, num_groups)
            # map ranks back to dictionary codes
            order = dict_sort_order(col.dictionary)
            inv = jnp.asarray(order.astype(np.int64))
            safe = jnp.clip(out_ranks, 0, len(order) - 1)
            out_codes = jnp.take(inv, safe).astype(jnp.int32)
            return Column(out_codes, out_type,
                          has_any, col.dictionary)
        if jnp.issubdtype(data.dtype, jnp.floating):
            sentinel = jnp.inf if op == "MIN" else -jnp.inf
        elif data.dtype == jnp.bool_:
            data = data.astype(jnp.int64)
            sentinel = 1 if op == "MIN" else 0
        else:
            info = jnp.iinfo(data.dtype)
            sentinel = info.max if op == "MIN" else info.min
        work = jnp.where(valid, data, sentinel)
        f = jax.ops.segment_min if op == "MIN" else jax.ops.segment_max
        out = f(work, codes, num_groups)
        out = out.astype(physical_dtype(out_type))
        return Column(out, out_type, has_any)

    if op in ("EVERY", "BOOL_AND"):
        work = jnp.where(valid, data.astype(bool), True)
        out = jax.ops.segment_min(work.astype(jnp.int32), codes, num_groups) > 0
        return Column(out, out_type, has_any)
    if op in ("BOOL_OR", "ANY"):
        work = jnp.where(valid, data.astype(bool), False)
        out = jax.ops.segment_max(work.astype(jnp.int32), codes, num_groups) > 0
        return Column(out, out_type, has_any)

    if op in ("ANY_VALUE", "SINGLE_VALUE", "FIRST_VALUE", "LAST_VALUE"):
        n = codes.shape[0]
        idx = jnp.arange(n)
        if op == "LAST_VALUE":
            work = jnp.where(valid, idx, -1)
            pick = jax.ops.segment_max(work, codes, num_groups)
        else:
            work = jnp.where(valid, idx, n)
            pick = jax.ops.segment_min(work, codes, num_groups)
        safe = jnp.clip(pick, 0, max(n - 1, 0))
        out = col.take(safe)
        return out.with_mask(out.valid_mask() & has_any)

    if op in ("BIT_AND", "BIT_OR", "BIT_XOR"):
        # no XLA segment primitive for bit ops: host reduceat over sorted codes
        np_codes = np.asarray(codes)
        np_data = np.asarray(data)
        np_valid = np.asarray(valid)
        order = np.argsort(np_codes, kind="stable")
        sc, sd, sv = np_codes[order], np_data[order], np_valid[order]
        ident = {"BIT_AND": -1, "BIT_OR": 0, "BIT_XOR": 0}[op]
        sd = np.where(sv, sd, ident)
        ufn = {"BIT_AND": np.bitwise_and, "BIT_OR": np.bitwise_or,
               "BIT_XOR": np.bitwise_xor}[op]
        starts = np.searchsorted(sc, np.arange(num_groups))
        out = np.full(num_groups, ident, dtype=np_data.dtype)
        present = np.zeros(num_groups, bool)
        if len(sd):
            seg = ufn.reduceat(sd, np.minimum(starts, len(sd) - 1))
            counts = np.diff(np.append(starts, len(sd)))
            present = counts > 0
            out = np.where(present, seg, ident)
        has = np.asarray(has_any)
        return Column(jnp.asarray(out).astype(physical_dtype(out_type)), out_type,
                      None if has.all() else jnp.asarray(has))

    if op == "LISTAGG":
        np_codes = np.asarray(codes)
        vals = col.decode() if col.stype.is_string else col.to_numpy().astype(object)
        np_valid = np.asarray(valid)
        outs = [[] for _ in range(num_groups)]
        for c, v, ok in zip(np_codes, vals, np_valid):
            if ok:
                outs[int(c)].append(str(v))
        strs = np.array([",".join(o) if o else None for o in outs], dtype=object)
        return Column._encode_strings(strs, None)

    raise NotImplementedError(f"Aggregate {op}")


# ---------------------------------------------------------------------------
# GROUP BY on a key column that never decreases in row order: the groups are
# the column's runs of equal neighbours, so nothing is hashed, and nothing
# scatters or gathers at the rows: elementwise passes over the n rows, one
# sort of their positions and gathers at the group capacity.  The compiled
# tier takes it on an ingest statistic's word (``statistics.grouped_by_runs``)
# and the program checks that word (``KeyRuns.ok``).
# ---------------------------------------------------------------------------

#: what ``run_aggregate`` covers; a node with any other aggregate keeps
#: ``segment_aggregate`` (``compiled._LogicalAggregate`` asks)
RUN_AGGREGATE_OPS = frozenset({"COUNT", "SUM", "$SUM0", "AVG", "MIN", "MAX"})


class KeyRuns(NamedTuple):
    """The runs of a key column of n rows at a capacity of ``cap`` groups.
    Group g is rows ``[starts[g], ends[g])``, in row order (the order of
    first occurrence, as ``hashing._group_hashed_codes`` numbers groups);
    slots past ``num_groups`` hold ``n`` twice, an empty run."""
    boundary: jax.Array     # [n] bool: the row opens a run
    starts: jax.Array       # [cap]
    ends: jax.Array         # [cap]
    num_groups: jax.Array   # the runs counted, over ``cap`` or not
    steps: jax.Array        # ceil(log2(the longest run held))
    ok: jax.Array           # the column never decreases


def key_runs(k: jax.Array, cap: int) -> KeyRuns:
    n = k.shape[0]
    boundary = jnp.concatenate([jnp.ones(min(n, 1), bool), k[1:] != k[:-1]])
    ok = (k[1:] >= k[:-1]).all()
    starts, num_groups = compact_indices(boundary, cap)
    starts = jnp.where(jnp.arange(cap) < num_groups, starts, n)
    ends = jnp.concatenate([starts[1:], jnp.full(min(cap, 1), n,
                                                 starts.dtype)])
    longest = jnp.max(ends - starts, initial=1).astype(jnp.int32)
    return KeyRuns(boundary, starts, ends, num_groups,
                   32 - jax.lax.clz(longest - 1), ok)


def _sum_over_runs(work: jax.Array, runs: KeyRuns) -> jax.Array:
    """Exact sums of an integer row a run: the running sum read at the runs'
    last rows, less the run's before.  int64 wraps, so the difference is
    right wherever the run's own sum fits, whatever the running total."""
    n = work.shape[0]
    at_end = jnp.cumsum(work)[jnp.clip(runs.ends - 1, 0, max(n - 1, 0))]
    return at_end - jnp.concatenate([jnp.zeros(min(len(at_end), 1),
                                               at_end.dtype), at_end[:-1]])


def _reduce_in_runs(work: jax.Array, runs: KeyRuns, combine) -> jax.Array:
    """``combine`` over each run, formed INSIDE the run: in step s a row
    takes in the row 2^s before it unless its own window already reaches
    its run's first row (``reached``, which doubles the same way), for as
    many steps as the longest run asks (3 for TPC-H's seven lines an order,
    ``ceil(log2 n)`` at the worst), and a run's result stands at its last
    row.  A floating sum is pairwise inside its run: never the difference
    of a running total, whose rounding is the column's and not the run's."""
    n = work.shape[0]
    pad = 1 << max((n - 1).bit_length() - 1, 0)

    def before(a, d):
        return jax.lax.dynamic_slice(
            jnp.concatenate([jnp.zeros(pad, a.dtype), a]), (pad - d,), (n,))

    def step(carry):
        s, x, reached = carry
        d = jnp.int32(1) << s
        return (s + 1, jnp.where(reached, x, combine(x, before(x, d))),
                reached | before(reached, d))

    _, x, _ = jax.lax.while_loop(lambda c: c[0] < runs.steps, step,
                                 (jnp.int32(0), work, runs.boundary))
    return x[jnp.clip(runs.ends - 1, 0, max(n - 1, 0))]


def run_aggregate(op: str, col: Optional[Column], runs: KeyRuns,
                  out_type: SqlType,
                  filter_mask: Optional[jax.Array] = None) -> Column:
    """``segment_aggregate`` for the ops of ``RUN_AGGREGATE_OPS`` over the
    runs of a key column, by its rules: the exact decimals, the NULL rules,
    string MIN / MAX by dictionary rank.  Invalid rows (NULLs, a FILTER's, a
    DISTINCT's keep mask) contribute the operator's neutral element."""
    sized = (runs.ends - runs.starts).astype(jnp.int64)
    if col is None and filter_mask is None:
        return Column(sized, out_type, None)
    valid = filter_mask
    if col is not None and col.mask is not None:
        valid = col.mask if valid is None else (valid & col.mask)
    # every row valid: a run's count is its length
    count = sized if valid is None else _sum_over_runs(
        valid.astype(runs.starts.dtype), runs).astype(jnp.int64)
    if op == "COUNT":
        return Column(count, out_type, None)
    has_any = count > 0

    def masked(work, neutral):
        return work if valid is None else jnp.where(valid, work, neutral)

    data = col.data
    if op in ("SUM", "$SUM0", "AVG"):
        dscale = exact_decimal_scale(col.stype)
        if dscale is not None:
            s_int = _sum_over_runs(
                masked(_decimal_scaled_ints(data, dscale), 0), runs)
            return _decimal_exact_result(op, s_int, count, dscale, out_type)
        if jnp.issubdtype(data.dtype, jnp.integer):
            s = _sum_over_runs(masked(data.astype(jnp.int64), 0), runs)
        else:
            s = _reduce_in_runs(masked(data.astype(jnp.float64), 0.0), runs,
                                jnp.add)
        if op == "AVG":
            return Column(s.astype(jnp.float64) / jnp.maximum(count, 1),
                          out_type, has_any)
        return Column(s.astype(physical_dtype(out_type)), out_type,
                      has_any if op == "SUM" else None)

    if op not in ("MIN", "MAX"):
        raise NotImplementedError(f"Aggregate {op} over runs")
    combine = jnp.minimum if op == "MIN" else jnp.maximum
    if col.stype.is_string:
        info = jnp.iinfo(jnp.int32)
        ranks = _reduce_in_runs(
            masked(col.dict_ranks().data.astype(jnp.int32),
                   info.max if op == "MIN" else info.min), runs, combine)
        order = dict_sort_order(col.dictionary)
        codes = jnp.take(jnp.asarray(order.astype(np.int32)),
                         jnp.clip(ranks, 0, len(order) - 1))
        return Column(codes, out_type, has_any, col.dictionary)
    if jnp.issubdtype(data.dtype, jnp.floating):
        neutral = jnp.inf if op == "MIN" else -jnp.inf
    elif data.dtype == jnp.bool_:
        data, neutral = data.astype(jnp.int64), int(op == "MIN")
    else:
        info = jnp.iinfo(data.dtype)
        neutral = info.max if op == "MIN" else info.min
    out = _reduce_in_runs(masked(data, neutral), runs, combine)
    return Column(out.astype(physical_dtype(out_type)), out_type, has_any)


def distinct_rows(cols: List[Column]) -> jax.Array:
    """Row indices of first occurrences of each distinct key combination."""
    codes, first, G = factorize_columns(cols, null_as_group=True)
    return jnp.sort(first)


def dedup_for_distinct_agg(group_codes_arr: jax.Array, value_col: Column,
                           filter_mask: Optional[jax.Array]):
    """Keep one row per (group, value) pair for DISTINCT aggregates.

    Returns (row_indices, new_codes) to aggregate over.
    """
    vals_codes, _, _ = factorize_columns([value_col], null_as_group=True)
    m = int(vals_codes.max()) + 1 if vals_codes.shape[0] else 1
    pair = group_codes_arr * m + vals_codes
    keep = value_col.valid_mask()
    if filter_mask is not None:
        keep = keep & filter_mask
    # make invalid rows unique-but-droppable: set pair=-1-row to dedupe safely
    n = pair.shape[0]
    pair = jnp.where(keep, pair, -1 - jnp.arange(n, dtype=pair.dtype))
    uniq, first_idx = np.unique(np.asarray(pair), return_index=True)
    rows = jnp.asarray(np.sort(first_idx[uniq >= 0]))
    return rows


def whole_table_aggregate(op: str, col: Optional[Column],
                          fmask: Optional[jax.Array], out_type: SqlType,
                          n_rows: int) -> Column:
    """Ungrouped aggregate as direct vector reductions — no segment ops.

    The eager path routes this through segment_sum with one segment, whose
    scatter lowering is pathological on TPU; a masked jnp.sum/min/max is a
    single fast reduction.
    """
    def _valid(c: Optional[Column]) -> jax.Array:
        v = jnp.ones(n_rows, dtype=bool) if fmask is None else fmask
        if c is not None and c.mask is not None:
            v = v & c.mask
        return v

    if op in ("COUNT", "REGR_COUNT"):
        v = _valid(col)
        return Column(jnp.sum(v.astype(jnp.int64)).reshape(1), out_type, None)

    assert col is not None, f"{op} requires an argument"
    valid = _valid(col)
    data = col.data
    count = jnp.sum(valid.astype(jnp.int64))
    has_any = (count > 0).reshape(1)

    if op in ("SUM", "$SUM0", "AVG", "STDDEV", "STDDEV_POP", "STDDEV_SAMP",
              "VAR_POP", "VAR_SAMP", "VARIANCE"):
        dscale = exact_decimal_scale(col.stype) if op in ("SUM", "$SUM0",
                                                          "AVG") else None
        if dscale is not None:
            iwork = jnp.where(valid, _decimal_scaled_ints(data, dscale), 0)
            s_int = jnp.sum(iwork).reshape(1)
            return _decimal_exact_result(op, s_int, count, dscale, out_type)
        if jnp.issubdtype(data.dtype, jnp.floating):
            work = jnp.where(valid, data.astype(jnp.float64), 0.0)
        else:
            work = jnp.where(valid, data.astype(jnp.int64), 0)
        s = jnp.sum(work).reshape(1)
        if op == "SUM":
            return Column(s.astype(physical_dtype(out_type)), out_type, has_any)
        if op == "$SUM0":
            return Column(s.astype(physical_dtype(out_type)), out_type, None)
        mean = s.astype(jnp.float64) / jnp.maximum(count, 1)
        if op == "AVG":
            return Column(mean, out_type, has_any)
        s2 = jnp.sum(jnp.where(valid, data.astype(jnp.float64) ** 2, 0.0)
                     ).reshape(1)
        var_pop = jnp.maximum(s2 / jnp.maximum(count, 1) - mean**2, 0.0)
        if op == "VAR_POP":
            return Column(var_pop, out_type, has_any)
        denom = jnp.maximum(count - 1, 1)
        var_samp = jnp.maximum((s2 - count * mean**2) / denom, 0.0)
        ok = (count > 1).reshape(1)
        if op in ("VAR_SAMP", "VARIANCE"):
            return Column(var_samp, out_type, ok)
        if op == "STDDEV_POP":
            return Column(jnp.sqrt(var_pop), out_type, has_any)
        return Column(jnp.sqrt(var_samp), out_type, ok)

    if op in ("MIN", "MAX"):
        if col.stype.is_string:
            ranked = col.dict_ranks().data.astype(jnp.int64)
            sent = jnp.iinfo(jnp.int64).max if op == "MIN" \
                else jnp.iinfo(jnp.int64).min
            work = jnp.where(valid, ranked, sent)
            r = (jnp.min(work) if op == "MIN" else jnp.max(work)).reshape(1)
            order = dict_sort_order(col.dictionary)
            inv = jnp.asarray(order.astype(np.int64))
            safe = jnp.clip(r, 0, len(order) - 1)
            return Column(jnp.take(inv, safe).astype(jnp.int32), out_type,
                          has_any, col.dictionary)
        if jnp.issubdtype(data.dtype, jnp.floating):
            sent = jnp.inf if op == "MIN" else -jnp.inf
        elif data.dtype == jnp.bool_:
            data = data.astype(jnp.int64)
            sent = 1 if op == "MIN" else 0
        else:
            info = jnp.iinfo(data.dtype)
            sent = info.max if op == "MIN" else info.min
        work = jnp.where(valid, data, sent)
        out = (jnp.min(work) if op == "MIN" else jnp.max(work)).reshape(1)
        return Column(out.astype(physical_dtype(out_type)), out_type, has_any)

    if op in ("EVERY", "BOOL_AND"):
        out = jnp.all(jnp.where(valid, data.astype(bool), True)).reshape(1)
        return Column(out, out_type, has_any)
    if op in ("BOOL_OR", "ANY"):
        out = jnp.any(jnp.where(valid, data.astype(bool), False)).reshape(1)
        return Column(out, out_type, has_any)

    if op in ("ANY_VALUE", "SINGLE_VALUE", "FIRST_VALUE", "LAST_VALUE"):
        idx = jnp.arange(n_rows, dtype=jnp.int64)
        if op == "LAST_VALUE":
            pos = jnp.max(jnp.where(valid, idx, -1)).reshape(1)
        else:
            pos = jnp.min(jnp.where(valid, idx, n_rows)).reshape(1)
        out = col.take(jnp.clip(pos, 0, max(n_rows - 1, 0)))
        return out.with_mask(out.valid_mask() & has_any)

    raise NotImplementedError(f"Whole-table aggregate {op}")
