"""The formulations of a grouped aggregate, each a function of the node, its
input stream and the trace's ledger (``traced.ProgramFlags``), and the masks
an aggregate's rows are chosen by (FILTER, DISTINCT).

``compiled._Tracer._LogicalAggregate`` chooses among them; nothing here
walks a plan or makes that choice:

- ``static_domain_aggregate``: the keys' domain is enumerable at trace time
  (dictionary strings, booleans); the reductions ride the MXU with no
  capacity at all (``dsql.groupby_limbs``);
- ``run_aggregate``: the key column never decreases in load order, so its
  runs are the groups: no table, no scatter;
- ``hashed_aggregate``: hash-table group codes with a static capacity and a
  segment scatter an aggregate, on every backend.

``first_occurrence_keep`` also serves UNION DISTINCT.  Imports nothing of
the compiled tier.
"""
from __future__ import annotations

import math
from typing import List, Optional

import jax
import jax.numpy as jnp

from ..ops import groupby as G, pallas_kernels as pk
from ..ops.hashing import (_decode_static_keys, _group_hashed_codes,
                           _try_static_codes)
from ..ops.kernels import decimal_unscale
from ..runtime.statistics import RUN_GROUPS_TAG
from ..table import Column, Table
from ..types import exact_decimal_scale, physical_dtype
from .traced import _VT, ProgramFlags


def agg_filter(agg, src: _VT):
    """Combined FILTER-clause + row-validity mask (None = all rows)."""
    fmask = src.valid
    if agg.filter_arg is not None:
        fc = src.table.columns[agg.filter_arg]
        fm = fc.data.astype(bool) & fc.valid_mask()
        fmask = fm if fmask is None else (fmask & fm)
    return fmask


def first_occurrence_keep(cols: List[Column],
                          row_valid: Optional[jax.Array],
                          flags: ProgramFlags) -> jax.Array:
    """Row-space mask: True on the first valid row of each distinct
    column-tuple (the shared dedup primitive for UNION DISTINCT and
    DISTINCT aggregates).  Its collision bit goes to the ledger."""
    n = len(cols[0])
    # codes per input row from the hash table, no sort.  No capacity
    # escalation here (a capacity of n is the worst case), so an
    # unresolved table folds into the collision flag and reruns eager
    codes, first, ng, coll = _group_hashed_codes(cols, row_valid, n)
    flags.fallback(coll | (ng > n))
    return jnp.clip(first, 0, max(n - 1, 0))[codes] == jnp.arange(n)


def distinct_keep(key_cols: List[Column], agg, src: _VT,
                  flags: ProgramFlags) -> jax.Array:
    """First occurrence of each (group keys, argument value) combo."""
    return first_occurrence_keep(
        list(key_cols) + [src.table.columns[agg.args[0]]], src.valid, flags)


def agg_inputs(rel, src: _VT, key_cols: List[Column], flags: ProgramFlags):
    """(aggregate, output field, argument column, row mask) of each
    aggregate of ``rel``: the mask is its FILTER, the rows' validity
    and, for a DISTINCT one, the first occurrences of its argument."""
    for j, agg in enumerate(rel.aggs):
        fmask = agg_filter(agg, src)
        if agg.distinct and agg.op not in ("MIN", "MAX"):
            keep = distinct_keep(key_cols, agg, src, flags)
            fmask = keep if fmask is None else (fmask & keep)
        yield (agg, rel.schema[len(rel.group_keys) + j],
               src.table.columns[agg.args[0]] if agg.args else None,
               fmask)


def run_aggregate(rel, src: _VT, key: Column, cap: int, tag: str,
                  flags: ProgramFlags) -> _VT:
    """GROUP BY a key column in load order (ops/groupby.py ``key_runs``):
    no table, no scatter; the groups in ``hashed_aggregate``'s order
    (first occurrence), and the check of the hint among the flags
    (``caps._check_ordered``: a refuted one never answers)."""
    runs = G.key_runs(key.data, cap)
    flags.hint(RUN_GROUPS_TAG, runs.ok)
    flags.site(tag, src.n, False, cap, runs.num_groups)
    cols = [key.take(jnp.minimum(runs.starts, src.n - 1))]
    for agg, f, col, fmask in agg_inputs(rel, src, [key], flags):
        cols.append(G.run_aggregate(agg.op, col, runs, f.stype, fmask))
    return _VT(Table([f.name for f in rel.schema], cols),
               jnp.arange(cap) < runs.num_groups)


def hashed_aggregate(rel, src: _VT, key_cols: List[Column], cap: int,
                     tag: str, flags: ProgramFlags) -> _VT:
    """General GROUP BY, on every backend (the group sort the TPU strategy
    had compiled for minutes above some tens of thousands of rows,
    ``compiled.SORT_ROWS_MAX``, and went in PR 27): hash-table group codes
    in original row order (no sort), then each aggregate is a segment_*
    scatter keyed on the dense codes — the same kernels the eager path
    uses (ops/groupby.py segment_aggregate), so semantics (exact decimals,
    NULL rules, string MIN/MAX ranks) are shared by construction.
    Invalid rows ride the trash segment ``cap``, sliced off afterwards.
    """
    n = src.n
    out_names = [f.name for f in rel.schema]
    codes, first_rows, num_groups, coll = _group_hashed_codes(
        key_cols, src.valid, cap)
    flags.fallback(coll)
    flags.site(tag, n, True, cap, num_groups)

    out_cols: List[Column] = []
    for ki in rel.group_keys:
        out_cols.append(src.table.columns[ki].take(first_rows))

    def _trim(col: Column) -> Column:
        return Column(col.data[:cap], col.stype,
                      None if col.mask is None else col.mask[:cap],
                      col.dictionary)

    for agg, f, col, fmask in agg_inputs(rel, src, key_cols, flags):
        out_cols.append(_trim(G.segment_aggregate(
            agg.op, col, codes, cap + 1, f.stype, filter_mask=fmask,
            n_rows=n)))
    row_valid = jnp.arange(cap) < num_groups
    return _VT(Table(out_names, out_cols), row_valid)


def static_domain_aggregate(rel, src: _VT, key_cols,
                            flags: ProgramFlags) -> Optional[_VT]:
    """GROUP BY over a statically-enumerable key domain (dict-encoded
    strings / booleans): codes come straight from dictionary ranks — no
    sort, no scatter, no capacity escalation — and all reductions ride
    the MXU one-hot kernel (ops/pallas_kernels.py) on TPU. Key output
    columns are decoded from the slot index, never gathered from the
    data. The kernel is named a value row and a count row an aggregate
    and sums each distinct one once (``rows_of``); what it reads of
    the data: a column once where the rows exist whole, and once more
    for a float row's largest magnitude where they are built a slab
    at a time. Returns None when the shape doesn't fit (non-MXU
    aggregates, non-enumerable keys, huge domains).

    This is the TPC-H Q1 shape: GROUP BY returnflag, linestatus.
    """
    static = _try_static_codes(key_cols)
    if static is None:
        return None
    codes, domain, key_meta = static
    if domain > 256:
        return None
    for agg in rel.aggs:
        col = src.table.columns[agg.args[0]] if agg.args else None
        if agg.op not in ("SUM", "$SUM0", "AVG", "COUNT") or agg.distinct:
            return None
        if col is not None and col.stype.is_string:
            return None
        if col is not None and col.data.dtype == jnp.bool_:
            return None

    n = src.n
    rv = src.valid
    kmask = jnp.ones(n, bool) if rv is None else rv

    out_names = [f.name for f in rel.schema]
    out_cols: List[Column] = _decode_static_keys(key_cols, key_meta,
                                                 domain)

    masks = {}  # full-length masks, one a (FILTER, column's NULLs)

    def mask_of(agg, col):
        """The rows an aggregate counts, the same object for the same
        rows: the row mask itself where the column has no NULLs and
        the aggregate no FILTER."""
        nulls = None if col is None else col.mask
        key = (agg.filter_arg, None if nulls is None else id(nulls))
        if key not in masks:
            rows = kmask if agg.filter_arg is None \
                else agg_filter(agg, src)
            masks[key] = rows if nulls is None else (nulls & rows)
        return masks[key]

    def rows_of(take, checks: bool):
        """The kernel's value rows, their classes and the aggregates'
        slots, built from ``take`` of every full-length input: the
        identity for the rows whole, a slab's slice inside the limb
        kernel's loop.  A row is made once and named wherever an
        aggregate reads it (one value row a (column, factor, mask),
        one count row a mask), each in the dtype it has: the kernel
        sums a row once however often it is named, and decides from
        class and dtype what a row can hold (``pk._row_plan``).
        ``checks``: send the int rows' magnitude checks, which read
        whole rows, to the ledger."""
        taken = {}  # id(full-length input) -> (it, its slice)
        values = {}  # (id(column data), factor, id(mask)) -> value row

        def part(whole):
            if id(whole) not in taken:
                taken[id(whole)] = (whole, take(whole))
            return taken[id(whole)][1]

        mxu_rows = [part(kmask)]  # row 0: occupancy counts
        row_classes = ["unit"]  # per-row grid for the limb MXU kernel
        slots = []
        for j, agg in enumerate(rel.aggs):
            f = rel.schema[len(rel.group_keys) + j]
            col = src.table.columns[agg.args[0]] if agg.args else None
            full_mask = mask_of(agg, col)
            vmask = part(full_mask)
            # exact decimal money math rides the MXU too: integer-valued
            # f64 matmuls are exact below 2^53 (SF100 cents sums ~6e15)
            factor = 1.0
            if col is not None and agg.op in ("SUM", "$SUM0", "AVG"):
                ds = exact_decimal_scale(col.stype)
                if ds is not None:
                    factor = 10.0 ** ds
            if col is None or agg.op == "COUNT":
                # COUNT(col): only the 0/1 count row is ever read — ship
                # it in the value slot too; no 2^53 magnitude guard (sums
                # are never used, so a huge BIGINT column must not fall
                # back)
                vrow = vmask
                rc = "unit"
            else:
                is_int = factor != 1.0 or jnp.issubdtype(col.data.dtype,
                                                         jnp.integer)
                rc = "int" if is_int else "float"
                key = (id(col.data), factor, id(full_mask))
                if key not in values:
                    data = part(col.data)
                    if factor != 1.0:
                        data = jnp.round(
                            data.astype(jnp.float64) * factor)
                    elif not is_int:
                        data = data.astype(jnp.float64)
                    # an integer column stays one: the kernel widens
                    # it, and knows by its dtype that it holds no NaN
                    values[key] = jnp.where(vmask, data,
                                            jnp.zeros((), data.dtype))
                    if is_int and checks:
                        # the int grid is bit-exact only below 2^53;
                        # decimal scales are pre-gated (p<=15) but a
                        # raw BIGINT column's magnitude is
                        # data-dependent (initial= keeps the trace
                        # alive on 0-row inputs)
                        flags.fallback(jnp.max(
                            jnp.abs(values[key].astype(jnp.float64)),
                            initial=0.0) >= 2.0 ** 53)
                vrow = values[key]
            slots.append((j, agg, f, len(mxu_rows), factor))
            mxu_rows.append(vrow)
            row_classes.append(rc)
            mxu_rows.append(vmask)
            row_classes.append("unit")
        return mxu_rows, row_classes, slots

    mxu_rows, row_classes, slots = rows_of(lambda whole: whole, True)
    with jax.named_scope("dsql.groupby_limbs"):
        if pk.stack_fits(mxu_rows, row_classes, n):
            red = pk.segmented_sums_dispatch(mxu_rows, codes, kmask,
                                             domain,
                                             row_classes=row_classes,
                                             counts=flags.limb_rows)
        else:
            # rows too many and too long to exist at once (TPC-H Q1 at
            # SF10: 17 named rows of 60 M, 5 float rows with their 15
            # indicator rows among the distinct ones): the kernel's
            # loop builds each slab's
            red = pk.segmented_sums_slabwise(
                lambda take: rows_of(take, False)[0], mxu_rows, codes,
                kmask, domain, row_classes, flags.limb_rows)
    occupancy = red[0] > 0

    results: List[Optional[Column]] = [None] * len(rel.aggs)
    for j, agg, f, row0, factor in slots:
        sums, counts = red[row0], red[row0 + 1]
        has = counts > 0
        if agg.op == "COUNT":
            results[j] = Column(counts.astype(jnp.int64), f.stype, None)
        elif agg.op in ("$SUM0", "SUM"):
            out = sums
            if factor != 1.0:
                # MXU sums of scaled decimals are integer-valued f64
                # (exact below 2^53): unscale via the exact-quotient
                # path, not a reciprocal-rewritten division
                out = decimal_unscale(
                    sums.astype(jnp.int64),
                    int(round(math.log10(factor))))
            results[j] = Column(
                out.astype(physical_dtype(f.stype)), f.stype,
                None if agg.op == "$SUM0" else has)
        else:  # AVG
            results[j] = Column(sums / (jnp.maximum(counts, 1.0) * factor),
                                f.stype, has)
    out_cols.extend(results)
    return _VT(Table(out_names, out_cols), occupancy)
