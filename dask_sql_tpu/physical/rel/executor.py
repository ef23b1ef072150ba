"""Physical executor: logical plan -> device Table, via a plugin registry.

Mirrors the reference's RelConverter dispatch
(/root/reference/dask_sql/physical/rel/convert.py:35-58): each plan-node class
name maps to a plugin whose ``convert(node, executor)`` lowers it; users can
register new lowerings with ``RelExecutor.add_plugin`` without touching core
(the Pluggable contract, SURVEY §1).  Execution is eager per stage — the host
"driver" sequences compiled device kernels, mirroring the reference's
client/scheduler split with XLA in place of the dask task graph.
"""
from __future__ import annotations

import logging
from typing import List

import jax.numpy as jnp
import numpy as np

from ...ops import groupby as G
from ...ops import join as J
from ...ops import sort as S
from ...ops import window as W
from ...ops.kernels import mask_to_indices
from ...plan.nodes import (
    AggCall, LogicalAggregate, LogicalExcept, LogicalFilter, LogicalIntersect,
    LogicalJoin, LogicalProject, LogicalSample, LogicalSort, LogicalTableScan,
    LogicalUnion, LogicalValues, LogicalWindow, RelNode, RexCall, RexInputRef,
    RexLiteral,
)
from ...table import Column, Scalar, Table
from ...types import physical_dtype
from ...utils import Pluggable
from ..rex.evaluate import evaluate_predicate, evaluate_rex
from ..shared import read_twice

logger = logging.getLogger(__name__)


class RelExecutor(Pluggable):
    """Plan-node class name -> physical plugin registry."""

    def __init__(self, context):
        self.context = context
        # id(node) -> canonical text of the aggregates and joins the plan
        # holds more than once (``shared.read_twice``, at the first
        # ``execute``), and the text -> the one result every copy hands on:
        # half the work, and the copies' floating sums are the SAME array,
        # where two runs of one kernel need not round alike on a TPU, whose
        # float64 is emulated (1 of 17 first arrivals of TPC-H Q15 at SF1
        # came back empty: PERF.md section 6, PR 43)
        self._twice = None
        self._once: dict = {}

    def execute(self, rel: RelNode) -> Table:
        if self._twice is None:
            self._twice = read_twice(rel)
        text = self._twice.get(id(rel))
        if text is None:
            return self._execute(rel)
        if text not in self._once:
            self._once[text] = self._execute(rel)
        return self._once[text]

    def _execute(self, rel: RelNode) -> Table:
        # per-node deadline/cancel checkpoint: the eager path is the
        # ladder's last compute rung, and a query must not run past its
        # budget there either (runtime/resilience.py; no-op outside a scope)
        from ...runtime import resilience as _res, telemetry as _tel
        _res.check("eager")
        plugin = RelExecutor.get_plugin(type(rel).__name__)
        logger.debug("Executing %s", rel.node_name())
        rec = _tel.active_node_recorder()
        if rec is not None:
            # EXPLAIN ANALYZE instrumentation: per-node wall (inclusive of
            # children — the renderer derives self-time) + output rows
            import time as _time
            t0 = _time.perf_counter()
            result = plugin(rel, self)
            rec.add(rel, (_time.perf_counter() - t0) * 1e3,
                    int(getattr(result, "num_rows", 0) or 0))
            return result
        result = plugin(rel, self)
        return result


# ---------------------------------------------------------------------------
# core plugins
# ---------------------------------------------------------------------------

def _table_scan(rel: LogicalTableScan, ex: RelExecutor) -> Table:
    # catalog_entry (not a direct dict read): inside a snapshot pin
    # (runtime/ingest.py) this serves the entry captured at admission
    entry = ex.context.catalog_entry(rel.schema_name, rel.table_name)
    if entry.table is not None:
        t = entry.table
        if entry.row_valid is not None:
            # mesh-mode table: drop the divisibility padding rows (the
            # compiled executor consumes the mask directly instead)
            t = t.take(mask_to_indices(entry.row_valid))
    else:
        t = ex.execute(entry.plan)
    return t.limit_to([f.name for f in rel.schema]) if t.names != [f.name for f in rel.schema] else t


def _project(rel: LogicalProject, ex: RelExecutor) -> Table:
    src = ex.execute(rel.input)
    cols: List[Column] = []
    for rex, f in zip(rel.exprs, rel.schema):
        v = evaluate_rex(rex, src, ex)
        if isinstance(v, Scalar):
            v = Column.from_scalar(v, src.num_rows)
        cols.append(v)
    return Table([f.name for f in rel.schema], cols)


def _filter(rel: LogicalFilter, ex: RelExecutor) -> Table:
    src = ex.execute(rel.input)
    mask = evaluate_predicate(rel.condition, src, ex)
    if isinstance(mask, bool):
        # scalar condition shortcut (reference filter.py:14-31)
        return src if mask else src.slice(0, 0)
    return src.take(mask_to_indices(mask))


def _values(rel: LogicalValues, ex: RelExecutor) -> Table:
    ncols = len(rel.schema)
    cols = []
    for j, f in enumerate(rel.schema):
        vals = [row[j].value for row in rel.rows]
        mask = np.array([v is not None for v in vals])
        if f.stype.is_string:
            arr = np.array([v if v is not None else "" for v in vals], dtype=object)
            cols.append(Column._encode_strings(arr, mask if not mask.all() else None))
        else:
            arr = np.array([v if v is not None else 0 for v in vals])
            col = Column(jnp.asarray(arr.astype(physical_dtype(f.stype))), f.stype,
                         None if mask.all() else jnp.asarray(mask))
            cols.append(col)
    return Table([f.name for f in rel.schema], cols)


def _aggregate(rel: LogicalAggregate, ex: RelExecutor) -> Table:
    from ...runtime import statistics as _stats

    src = ex.execute(rel.input)
    n = src.num_rows
    key_cols = [src.columns[i] for i in rel.group_keys]

    if rel.group_keys:
        # stats-driven dispatch (runtime/statistics.py): the hash/sort
        # crossover plus the dense direct-index path; DSQL_ADAPTIVE=0 and
        # unknown stats both yield "hash" — the pre-stats factorize.
        variant, info = _stats.groupby_decision(rel, ex.context)
        hint = (info["lo"], info["hi"]) if "lo" in info else None
        codes, first, num_groups, used = G.group_codes(
            key_cols, variant=variant, dense_hint=hint)
        if used != "hash" or info:
            _stats.record_choice("groupby", used, **{
                k: v for k, v in info.items() if k not in ("lo", "hi")})
    else:
        codes, first, num_groups = None, None, 1

    out_cols: List[Column] = []
    out_names: List[str] = []

    # group key outputs: representative rows
    if rel.group_keys:
        rep = first
        for i, ki in enumerate(rel.group_keys):
            out_cols.append(src.columns[ki].take(rep))
            out_names.append(rel.schema[i].name)

    for j, agg in enumerate(rel.aggs):
        f = rel.schema[len(rel.group_keys) + j]
        col = src.columns[agg.args[0]] if agg.args else None
        filter_mask = None
        if agg.filter_arg is not None:
            fc = src.columns[agg.filter_arg]
            filter_mask = fc.data.astype(bool) & fc.valid_mask()

        if agg.udaf is not None:
            out_cols.append(_run_udaf(agg, col, codes, num_groups, filter_mask, src))
            out_names.append(f.name)
            continue

        if agg.distinct and col is not None:
            base_codes = codes if codes is not None else jnp.zeros(n, dtype=jnp.int64)
            rows = G.dedup_for_distinct_agg(base_codes, col, filter_mask)
            sub_col = col.take(rows)
            sub_codes = base_codes[rows] if codes is not None else None
            out_cols.append(G.segment_aggregate(
                agg.op, sub_col, sub_codes, num_groups, f.stype,
                None, int(rows.shape[0])))
        else:
            out_cols.append(G.segment_aggregate(
                agg.op, col, codes, num_groups, f.stype, filter_mask, n))
        out_names.append(f.name)

    if not rel.group_keys and not rel.aggs:
        return Table([], [])
    # DISTINCT (aggregate with no aggs): groups only
    return Table(out_names, out_cols)


def _run_udaf(agg: AggCall, col, codes, num_groups, filter_mask, src: Table) -> Column:
    """Custom aggregation: host groupby-apply (reference registers dask
    Aggregations, context.py:312-377; arbitrary python runs on host here)."""
    vals = col.to_numpy() if col is not None else np.zeros(src.num_rows)
    np_codes = np.asarray(codes) if codes is not None else np.zeros(len(vals), dtype=np.int64)
    keep = np.ones(len(vals), bool)
    if filter_mask is not None:
        keep = np.asarray(filter_mask)
    import pandas as pd
    s = pd.Series(vals[keep])
    g = pd.Series(np_codes[keep])
    result = s.groupby(g).apply(agg.udaf.func)
    out = np.zeros(num_groups, dtype=object)
    out[:] = None
    for k, v in result.items():
        out[int(k)] = v
    mask = np.array([v is not None for v in out])
    if agg.stype.is_string:
        return Column._encode_strings(
            np.where(mask, out, "").astype(object), mask if not mask.all() else None)
    arr = np.array([v if v is not None else 0 for v in out])
    return Column(jnp.asarray(arr.astype(physical_dtype(agg.stype))), agg.stype,
                  None if mask.all() else jnp.asarray(mask))


# the splitter lives in the PLAN layer (optimizer passes need it too, and
# plan -> physical imports would invert the layering); aliased here for the
# physical-layer call sites
from ...plan.optimizer import split_join_condition as _extract_equi_keys  # noqa: E402,E501


def _join(rel: LogicalJoin, ex: RelExecutor) -> Table:
    from ...runtime import statistics as _stats

    left = ex.execute(rel.left)
    right = ex.execute(rel.right)
    nl = len(left.names)
    equi, residual = _extract_equi_keys(rel)
    jt = rel.join_type

    def _key_variant(lk, rk) -> str:
        # stats-driven dense direct-index coding (codes = key - min) for a
        # single int key pair; "hash" = the pre-stats shared factorize
        variant, info = _stats.join_decision(
            rel, [left.columns[i] for i in lk],
            [right.columns[i] for i in rk], ex.context)
        if variant != "hash" or info:
            _stats.record_choice("join", variant, **info)
        return variant

    # disambiguate duplicate column names across sides (schema names win)
    out_names = [f.name for f in rel.schema]

    if jt in ("SEMI", "ANTI"):
        null_aware = getattr(rel, "null_aware", False)
        if not equi and residual:
            # correlated EXISTS with only non-equi predicates: pair expansion
            li, ri = J.cross_join_pairs(left.num_rows, right.num_rows)
            return _semi_anti_pairs(ex, left, right, li, ri, residual, jt)
        if not equi:
            # EXISTS: keep all if right non-empty
            if jt == "SEMI":
                return left if right.num_rows else left.slice(0, 0)
            return left.slice(0, 0) if right.num_rows else left
        lk = [k for k, _ in equi]
        rk = [k for _, k in equi]
        if residual:
            # equi + residual (e.g. decorrelated EXISTS with an inequality):
            # expand equi matches, apply residual, reduce to row existence
            assert not null_aware
            from ...ops.kernels import join_key_codes
            lcodes, rcodes = join_key_codes([left.columns[i] for i in lk],
                                            [right.columns[i] for i in rk],
                                            variant=_key_variant(lk, rk))
            li, ri, _counts = J._expand_matches(lcodes, rcodes)
            return _semi_anti_pairs(ex, left, right, li, ri, residual, jt)
        out, _ = J.join_tables(left, right, lk, rk, jt, null_aware,
                               variant=_key_variant(lk, rk))
        return out

    if not equi:
        # cross join or pure non-equi: pair expansion + residual filter
        li, ri = J.cross_join_pairs(left.num_rows, right.num_rows)
        lt, rt = left.take(li), right.take(ri)
        pairs = Table(out_names, lt.columns + rt.columns)
        if residual:
            cond = _and_rex(residual)
            keep = evaluate_predicate(cond, pairs, ex)
            if isinstance(keep, bool):
                keep = jnp.full(pairs.num_rows, keep)
            if jt == "INNER" or jt == "CROSS":
                return pairs.take(mask_to_indices(keep))
            return J.rejoin_outer(left, right, pairs, keep, li, ri, jt)
        return pairs

    lk = [k for k, _ in equi]
    rk = [k for _, k in equi]

    if not residual:
        out, _ = J.join_tables(left, right, lk, rk, jt,
                               variant=_key_variant(lk, rk))
        return out.with_names(out_names)

    # equi + residual: build inner pairs, filter, then outer recovery
    from ...ops.kernels import join_key_codes
    lcodes, rcodes = join_key_codes([left.columns[i] for i in lk],
                                    [right.columns[i] for i in rk],
                                    variant=_key_variant(lk, rk))
    li, ri, counts = J._expand_matches(lcodes, rcodes)
    lt, rt = left.take(li), right.take(ri)
    pairs = Table(out_names, lt.columns + rt.columns)
    cond = _and_rex(residual)
    keep = evaluate_predicate(cond, pairs, ex)
    if isinstance(keep, bool):
        keep = jnp.full(pairs.num_rows, keep)
    if jt == "INNER":
        return pairs.take(mask_to_indices(keep))
    return J.rejoin_outer(left, right, pairs, keep, li, ri, jt).with_names(out_names)


def _semi_anti_pairs(ex, left: Table, right: Table, li, ri,
                     residual, jt: str) -> Table:
    """SEMI/ANTI with residual predicates: evaluate the condition over the
    candidate (left, right) row pairs, then keep left rows with (SEMI) or
    without (ANTI) any surviving match."""
    lt, rt = left.take(li), right.take(ri)
    pairs = Table(
        [f"l{i}" for i in range(len(lt.names))]
        + [f"r{i}" for i in range(len(rt.names))],
        lt.columns + rt.columns)
    keep = evaluate_predicate(_and_rex(residual), pairs, ex)
    if isinstance(keep, bool):
        keep = jnp.full(pairs.num_rows, keep)
    matched = np.zeros(left.num_rows, dtype=bool)
    matched[np.asarray(li)[np.asarray(keep)]] = True
    want = matched if jt == "SEMI" else ~matched
    return left.take(jnp.asarray(np.flatnonzero(want)))


def _and_rex(rexes):
    from ...types import BOOLEAN
    out = rexes[0]
    for r in rexes[1:]:
        out = RexCall("AND", [out, r], BOOLEAN)
    return out


def _sort(rel: LogicalSort, ex: RelExecutor) -> Table:
    src = ex.execute(rel.input)
    if rel.collation:
        keys = [(c.index, c.ascending, c.effective_nulls_first) for c in rel.collation]
        src = S.apply_sort(src, keys)
    return S.apply_offset_limit(src, rel.offset, rel.limit)


def _union(rel: LogicalUnion, ex: RelExecutor) -> Table:
    tables = [ex.execute(i) for i in rel.inputs_]
    # align names/types to output schema (reference union.py:30-45)
    out_names = [f.name for f in rel.schema]
    aligned = []
    from ..rex.cast import cast_column
    for t in tables:
        cols = []
        for j, f in enumerate(rel.schema):
            c = t.columns[j]
            if c.stype.name != f.stype.name:
                c = cast_column(c, f.stype)
            cols.append(c)
        aligned.append(Table(out_names, cols))
    out = J.concat_tables(aligned)
    if not rel.all:
        rows = G.distinct_rows(out.columns)
        out = out.take(rows)
    return out


def _intersect(rel: LogicalIntersect, ex: RelExecutor) -> Table:
    a = ex.execute(rel.inputs_[0])
    b = ex.execute(rel.inputs_[1])
    a = a.take(G.distinct_rows(a.columns))
    # set-op equality: NULL matches NULL (IS NOT DISTINCT FROM) — a plain
    # equi-join would silently drop every NULL-bearing row (r2 oracle find)
    out, _ = J.join_tables(a, b, list(range(a.num_columns)),
                           list(range(b.num_columns)), "SEMI",
                           null_equal=True)
    return out.with_names([f.name for f in rel.schema])


def _except(rel: LogicalExcept, ex: RelExecutor) -> Table:
    a = ex.execute(rel.inputs_[0])
    b = ex.execute(rel.inputs_[1])
    a = a.take(G.distinct_rows(a.columns))
    out, _ = J.join_tables(a, b, list(range(a.num_columns)),
                           list(range(b.num_columns)), "ANTI",
                           null_equal=True)
    return out.with_names([f.name for f in rel.schema])


def _window(rel: LogicalWindow, ex: RelExecutor) -> Table:
    src = ex.execute(rel.input)
    names = list(src.names)
    cols = list(src.columns)
    for call in rel.calls:
        order = [(c.index, c.ascending, c.effective_nulls_first) for c in call.order]
        col = W.compute_window(src, call.op, call.args, call.partition, order,
                               call.frame, call.stype)
        cols.append(col)
        names.append(call.name)
    return Table(names, cols)


def _sample(rel: LogicalSample, ex: RelExecutor) -> Table:
    src = ex.execute(rel.input)
    import jax
    seed = rel.seed if rel.seed is not None else np.random.randint(0, 2**31)
    key = jax.random.PRNGKey(seed)
    frac = rel.percentage / 100.0
    # single-device table: SYSTEM (block-level) == BERNOULLI here; the
    # sharded path samples whole shards for SYSTEM (see parallel/)
    mask = jax.random.uniform(key, (src.num_rows,)) < frac
    return src.take(mask_to_indices(mask))


def _predict(rel, ex: RelExecutor) -> Table:
    src = ex.execute(rel.input)
    model, training_columns = ex.context._get_model(rel.model_name)
    import numpy as np
    X = np.column_stack([src.column(c).to_numpy().astype(np.float64)
                         for c in training_columns]) if training_columns else src.to_pandas()
    pred = model.predict(X)
    out = Column.from_numpy(np.asarray(pred))
    from ..rex.cast import cast_value
    out = cast_value(out, rel.schema[-1].stype, src.num_rows)
    return src.add_column(rel.schema[-1].name, out)


RelExecutor.add_plugin("LogicalTableScan", _table_scan)
RelExecutor.add_plugin("LogicalProject", _project)
RelExecutor.add_plugin("LogicalFilter", _filter)
RelExecutor.add_plugin("LogicalValues", _values)
RelExecutor.add_plugin("LogicalAggregate", _aggregate)
RelExecutor.add_plugin("LogicalJoin", _join)
RelExecutor.add_plugin("LogicalSort", _sort)
RelExecutor.add_plugin("LogicalUnion", _union)
RelExecutor.add_plugin("LogicalIntersect", _intersect)
RelExecutor.add_plugin("LogicalExcept", _except)
RelExecutor.add_plugin("LogicalWindow", _window)
RelExecutor.add_plugin("LogicalSample", _sample)
RelExecutor.add_plugin("LogicalPredict", _predict)
