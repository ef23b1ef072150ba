"""Table statistics + adaptive operator selection (ROADMAP item 3).

One ``TableStats`` object — row count, per-column NDV estimate, min/max,
null fraction, dense-int detection — collected cheaply at ingest
(context.create_table) and refined by the runtime measurements already
flowing through the flight recorder's EWMA history, threaded through the
whole vertical:

- **operator dispatch** (physical/rel/executor.py → ops/groupby.py,
  ops/join.py, ops/kernels.py): the hash/sort crossover of "Hash-Based
  vs. Sort-Based Group-By-Aggregate" (PAPERS.md) picks sorted-segment vs
  hash aggregation from key NDV vs row count, and a dense-int
  direct-index path (``codes = key - min``, no hashing — "Fine-Tuning
  Data Structures for Analytical Query Processing", PAPERS.md) takes
  over when the observed key domain is small and dense;
- **planner** (plan/optimizer.py): join chains rank by estimated output
  cardinality (NDV-based equi-join selectivity), and group-capacity
  hints shrink the compiled executor's padded capacity classes toward
  measured cardinality (physical/compiled.py, physical/stages.py);
- **scheduler** (runtime/scheduler.py): ``estimate_plan_bytes`` consumes
  the same stats for the admission reservation (``est_source=stats``).

Every decision is advisory: the compiled path keeps its overflow-flag
escalation net (a wrong cap hint costs one recompile, never a wrong
result), the eager variants all produce the same group numbering as the
status-quo factorize, and ``DSQL_ADAPTIVE=0`` restores pre-stats
dispatch bit-for-bit.  ``DSQL_FORCE_GROUPBY=hash|sorted|dense`` pins the
group-by variant for testing; every choice is recorded on the current
span, a counter (``operator_choice_<op>_<variant>``), and EXPLAIN.
"""
from __future__ import annotations

import logging
import math
import os
import threading
import time
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from . import telemetry as _tel

logger = logging.getLogger(__name__)


# ---------------------------------------------------------------------------
# env gates
# ---------------------------------------------------------------------------

def adaptive_enabled() -> bool:
    """Master kill-switch: ``DSQL_ADAPTIVE=0`` restores pre-stats dispatch
    everywhere (collection still runs at ingest; it is pure metadata)."""
    return os.environ.get("DSQL_ADAPTIVE", "1") != "0"


def forced_groupby() -> Optional[str]:
    """``DSQL_FORCE_GROUPBY=hash|sorted|dense``: pin the eager group-by
    variant regardless of stats (testing/bench).  Unknown values → None."""
    v = os.environ.get("DSQL_FORCE_GROUPBY", "").strip().lower()
    return v if v in ("hash", "sorted", "dense") else None


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, "") or default)
    except ValueError:
        return default


def dense_domain_cap() -> int:
    """Largest key domain (max-min+1) the dense direct-index group-by will
    allocate slots for; beyond it the crossover table decides."""
    return _env_int("DSQL_DENSE_DOMAIN_CAP", 4096)


#: domain above which exact ingest-time NDV probing (bincount) is skipped
_NDV_PROBE_DOMAIN = 1 << 20
#: sample size for the strided NDV estimator on wide-domain columns
_NDV_SAMPLE = 65536
#: sorted-segment aggregation stays profitable up to this many groups …
SORT_NDV_CAP = 4096
#: … and only while groups stay "fat" (ndv <= rows / SORT_ROW_FRACTION)
SORT_ROW_FRACTION = 16


# ---------------------------------------------------------------------------
# the stats objects
# ---------------------------------------------------------------------------

@dataclass
class ColumnStats:
    """Per-column ingest statistics.  ``ndv`` is an ESTIMATE above
    ``_NDV_PROBE_DOMAIN``-sized domains (strided-sample extrapolation);
    exact (bincount over the domain) for narrow integer columns —
    exactly the columns the dense dispatch cares about."""

    name: str
    ndv: Optional[int] = None
    min: Optional[float] = None
    max: Optional[float] = None
    null_frac: float = 0.0
    is_int: bool = False
    #: int column whose domain (max-min+1) fits dense_domain_cap()
    dense: bool = False
    domain: Optional[int] = None
    #: integer column without NULLs whose values strictly increase in load
    #: order: the column is its own index (the compiled tier's ordered
    #: probe, physical/compiled.py), and ``ndv`` is its exact row count
    increasing: bool = False
    #: integer column without NULLs whose values never decrease in load
    #: order but repeat (a child table stored beside its parent's key:
    #: TPC-H's l_orderkey, ps_partkey): its runs of equal values, which IS
    #: its distinct count, exact where the strided sample behind ``ndv``
    #: sees nearly every value once and says "as many as rows".  ``ndv``
    #: itself stays the estimate it was (the joins' row estimates read it)
    runs: Optional[int] = None

    def to_row(self) -> dict:
        return {
            "column": self.name,
            "ndv": -1 if self.ndv is None else int(self.ndv),
            "min": float("nan") if self.min is None else float(self.min),
            "max": float("nan") if self.max is None else float(self.max),
            "null_frac": float(self.null_frac),
            "is_int": bool(self.is_int),
            "dense": bool(self.dense),
            "domain": -1 if self.domain is None else int(self.domain),
            "increasing": bool(self.increasing),
        }


@dataclass
class TableStats:
    rows: int = 0
    cols: Dict[str, ColumnStats] = field(default_factory=dict)
    collected_ms: float = 0.0

    def col(self, name: str) -> Optional[ColumnStats]:
        return self.cols.get(name)


def collect_table_stats(table, row_valid=None) -> Optional[TableStats]:
    """Cheap ingest-time collection over a resident device Table.

    One host pass per column (XLA:CPU arrays view for free; on TPU this
    runs once at create_table, not per query).  Never raises — a column
    that resists profiling is simply absent from the stats dict, and any
    failure returns None (the engine then behaves exactly as pre-stats).
    """
    from ..table import map_columns as _map_columns

    t0 = time.perf_counter()
    try:
        rows = int(table.num_rows)
        valid_rows = None
        if row_valid is not None:
            valid_rows = np.asarray(row_valid).reshape(-1)
            rows = int(valid_rows.sum())
        ts = TableStats(rows=rows)
        host = all(isinstance(c.data, np.ndarray) for c in table.columns)
        collected = _map_columns(
            lambda named: _collect_column(*named, rows, valid_rows),
            list(zip(table.names, table.columns)),
            # a device table's columns come to the host one at a time
            rows if host else 0)
        for name, cs in zip(table.names, collected):
            if cs is not None:
                ts.cols[name] = cs
        ts.collected_ms = (time.perf_counter() - t0) * 1e3
        _tel.inc("stats_tables_collected")
        return ts
    except (KeyboardInterrupt, SystemExit):
        raise
    except Exception:
        logger.debug("stats collection failed", exc_info=True)
        _tel.inc("stats_collect_errors")
        return None


def _collect_column(name, col, rows: int, valid_rows) -> Optional[ColumnStats]:
    try:
        mask = None if col.mask is None else np.asarray(col.mask).reshape(-1)
        if valid_rows is not None:
            mask = valid_rows if mask is None else (mask & valid_rows)
        n = rows if rows else 1
        nulls = 0 if mask is None else int(rows - mask.sum()) if valid_rows \
            is None else int(valid_rows.sum() - mask.sum())
        null_frac = max(0.0, min(1.0, nulls / n))

        if col.stype.is_string:
            # dictionary-encoded: the dictionary bounds NDV exactly
            ndv = int(len(col.dictionary)) if col.dictionary is not None \
                else None
            return ColumnStats(name=name, ndv=ndv, null_frac=null_frac)

        data = np.asarray(col.data).reshape(-1)
        vals = data if mask is None else data[mask.astype(bool)]
        if vals.size == 0:
            return ColumnStats(name=name, ndv=0, null_frac=null_frac,
                               is_int=bool(np.issubdtype(data.dtype,
                                                         np.integer)))
        if data.dtype == np.bool_:
            return ColumnStats(name=name, ndv=int(np.unique(vals).size),
                               min=float(vals.min()), max=float(vals.max()),
                               null_frac=null_frac)
        mn, mx = vals.min(), vals.max()
        is_int = bool(np.issubdtype(data.dtype, np.integer))
        domain = None
        ndv: Optional[int] = None
        increasing = bool(is_int and mask is None
                          and _ordered(vals, np.greater))
        if increasing:
            ndv = int(vals.size)  # every value once
        if is_int:
            domain = int(mx) - int(mn) + 1
            if ndv is None and 0 < domain <= _NDV_PROBE_DOMAIN:
                # exact NDV in O(n + domain): one bincount over the domain
                counts = np.bincount((vals.astype(np.int64) - int(mn)),
                                     minlength=domain)
                ndv = int(np.count_nonzero(counts))
        if ndv is None:
            ndv = _sampled_ndv(vals)
        runs = None
        if is_int and mask is None and not increasing \
                and _ordered(vals, np.greater_equal):
            runs = int(np.count_nonzero(vals[1:] != vals[:-1])) + 1
        dense = bool(is_int and domain is not None
                     and domain <= dense_domain_cap())
        mnf, mxf = float(mn), float(mx)
        if not (math.isfinite(mnf) and math.isfinite(mxf)):
            mnf = mxf = None  # type: ignore[assignment]
        return ColumnStats(name=name, ndv=ndv, min=mnf, max=mxf,
                           null_frac=null_frac, is_int=is_int, dense=dense,
                           domain=domain, increasing=increasing, runs=runs)
    except (KeyboardInterrupt, SystemExit):
        raise
    except Exception:
        logger.debug("column stats failed for %s", name, exc_info=True)
        return None


def _ordered(vals: np.ndarray, follows) -> bool:
    """``follows(vals[1:], vals[:-1])`` everywhere (``np.greater``: the
    column increases; ``np.greater_equal``: it never decreases): one pass
    beside the min / max pass, after a look at the head, where an unsorted
    column gives itself away before the whole of it is compared."""
    head = vals[:4096]
    return bool(follows(head[1:], head[:-1]).all()
                and follows(vals[1:], vals[:-1]).all())


def _sampled_ndv(vals: np.ndarray) -> int:
    """Strided-sample NDV estimator for wide domains.

    A high distinct fraction in the sample extrapolates linearly (key-like
    columns really do have ~n distinct values); a low fraction is reported
    as the sample's own count — a LOWER bound, which biases the crossover
    toward sorted aggregation only when groups genuinely looked fat."""
    n = vals.size
    if n <= _NDV_SAMPLE:
        return int(np.unique(vals).size)
    stride = max(1, n // _NDV_SAMPLE)
    sample = vals[::stride]
    d = int(np.unique(sample).size)
    s = sample.size
    if d >= 0.5 * s:
        return min(n, int(n * (d / s)))
    return d


# ---------------------------------------------------------------------------
# plan-level estimation: column stats + cardinality through operators
# ---------------------------------------------------------------------------

def _scan_entry(rel, context):
    schema = context.schema.get(rel.schema_name)
    if schema is None:
        return None
    return schema.tables.get(rel.table_name)


def table_stats_for_scan(rel, context) -> Optional[TableStats]:
    entry = _scan_entry(rel, context)
    return getattr(entry, "stats", None) if entry is not None else None


def column_stats_for(rel, ordinal: int, context) -> Optional[ColumnStats]:
    """Trace output ordinal ``ordinal`` of ``rel`` back to a base-table
    column and return its ingest stats (None when the column is computed
    or the lineage can't be followed — callers then use defaults)."""
    from ..plan import nodes as N

    if isinstance(rel, N.LogicalTableScan):
        ts = table_stats_for_scan(rel, context)
        if ts is None or ordinal >= len(rel.schema):
            return None
        return ts.col(rel.schema[ordinal].name)
    if isinstance(rel, N.LogicalProject):
        e = rel.exprs[ordinal] if ordinal < len(rel.exprs) else None
        if isinstance(e, N.RexInputRef):
            return column_stats_for(rel.input, e.index, context)
        return None
    if isinstance(rel, (N.LogicalFilter, N.LogicalSort)):
        # filters/sorts keep values; NDV/min/max stay valid upper bounds
        return column_stats_for(rel.input, ordinal, context)
    if isinstance(rel, N.LogicalAggregate):
        if ordinal < len(rel.group_keys):
            return column_stats_for(rel.input, rel.group_keys[ordinal],
                                    context)
        return None
    if isinstance(rel, N.LogicalJoin):
        nl = len(rel.left.schema)
        if rel.join_type in ("SEMI", "ANTI") or ordinal < nl:
            return column_stats_for(rel.left, ordinal, context)
        return column_stats_for(rel.right, ordinal - nl, context)
    return None


_DEFAULT_EQ_SEL = 0.1
_DEFAULT_RANGE_SEL = 0.3
_DEFAULT_SEL = 0.25
_MIN_SEL = 5e-4


def _literal_value(rex):
    from ..plan import nodes as N

    # RexParam carries its current literal value — selectivity estimates
    # use it exactly like an inline literal (estimates are advisory; only
    # program identity must be value-free)
    if isinstance(rex, (N.RexLiteral, N.RexParam)):
        v = rex.value
        if isinstance(v, bool):
            return float(v)
        if isinstance(v, (int, float)):
            return float(v)
    return None


def selectivity(rex, rel, context) -> float:
    """Fraction of ``rel``'s rows estimated to satisfy ``rex`` —
    textbook System-R style rules over the ingest min/max/NDV."""
    from ..plan import nodes as N

    if isinstance(rex, N.RexLiteral):
        if rex.value is True:
            return 1.0
        if rex.value is False:
            return 0.0
        return _DEFAULT_SEL
    if not isinstance(rex, N.RexCall):
        return _DEFAULT_SEL
    op = rex.op
    if op == "AND":
        s = 1.0
        for o in rex.operands:
            s *= selectivity(o, rel, context)
        return max(s, _MIN_SEL)
    if op == "OR":
        s = 0.0
        for o in rex.operands:
            s += selectivity(o, rel, context)
        return min(s, 1.0)
    if op == "NOT":
        return min(max(1.0 - selectivity(rex.operands[0], rel, context),
                       _MIN_SEL), 1.0)
    if op in ("IS NULL", "IS NOT NULL") and len(rex.operands) == 1:
        o = rex.operands[0]
        cs = column_stats_for(rel, o.index, context) \
            if isinstance(o, N.RexInputRef) else None
        nf = cs.null_frac if cs is not None else 0.05
        return max(nf if op == "IS NULL" else 1.0 - nf, _MIN_SEL)
    if op in ("=", "<>", "!=", "<", "<=", ">", ">=") \
            and len(rex.operands) == 2:
        a, b = rex.operands
        ref, lit = (a, b) if isinstance(a, N.RexInputRef) else (b, a)
        if not isinstance(ref, N.RexInputRef):
            return _DEFAULT_SEL
        cs = column_stats_for(rel, ref.index, context)
        if op == "=":
            if cs is not None and cs.ndv:
                return max(1.0 / cs.ndv, _MIN_SEL)
            return _DEFAULT_EQ_SEL
        if op in ("<>", "!="):
            if cs is not None and cs.ndv:
                return max(1.0 - 1.0 / cs.ndv, _MIN_SEL)
            return 1.0 - _DEFAULT_EQ_SEL
        lv = _literal_value(lit)
        if cs is None or lv is None or cs.min is None or cs.max is None \
                or cs.max <= cs.min:
            return _DEFAULT_RANGE_SEL
        frac = (lv - cs.min) / (cs.max - cs.min)
        if (op in ("<", "<=")) == (ref is a):
            s = frac          # col < lit  (or lit > col)
        else:
            s = 1.0 - frac    # col > lit  (or lit < col)
        return min(max(s, _MIN_SEL), 1.0)
    return _DEFAULT_SEL


def estimate_rows(rel, context, _depth: int = 0) -> Optional[float]:
    """Estimated output cardinality of a plan subtree; None = unknown.

    Ingest stats drive the base numbers; the flight recorder's EWMA
    history (keyed by canonical plan fingerprint) REFINES the root of
    each estimate with rows the engine actually measured for this exact
    subtree shape on earlier runs."""
    from ..plan import nodes as N

    if _depth == 0:
        measured = measured_rows(rel, context)
        if measured is not None:
            return float(measured)
    if _depth > 64:
        return None
    if isinstance(rel, N.LogicalTableScan):
        ts = table_stats_for_scan(rel, context)
        if ts is not None:
            return float(ts.rows)
        entry = _scan_entry(rel, context)
        if entry is None:
            return None
        chunked = getattr(entry, "chunked", None)
        if chunked is not None:
            return float(getattr(chunked, "n_rows", 0))
        table = getattr(entry, "table", None)
        return float(table.num_rows) if table is not None else None
    if isinstance(rel, N.LogicalValues):
        return float(len(rel.rows))
    if isinstance(rel, N.LogicalFilter):
        child = estimate_rows(rel.input, context, _depth + 1)
        if child is None:
            return None
        return child * selectivity(rel.condition, rel.input, context)
    if isinstance(rel, N.LogicalProject):
        return estimate_rows(rel.input, context, _depth + 1)
    if isinstance(rel, N.LogicalSort):
        child = estimate_rows(rel.input, context, _depth + 1)
        if child is None:
            return None
        if rel.limit is not None:
            return min(child, float(rel.limit))
        return child
    if isinstance(rel, N.LogicalAggregate):
        child = estimate_rows(rel.input, context, _depth + 1)
        if not rel.group_keys:
            return 1.0
        if child is None:
            return None
        prod = 1.0
        for k in rel.group_keys:
            cs = column_stats_for(rel.input, k, context)
            if cs is None or not cs.ndv:
                return child  # unknown key: no group reduction claimed
            prod *= cs.ndv
            if prod > child:
                return child
        return min(child, prod)
    if isinstance(rel, N.LogicalJoin):
        return _estimate_join_rows(rel, context, _depth)
    # set ops and anything else with inputs: sum of known inputs
    if rel.inputs:
        total = 0.0
        for i in rel.inputs:
            c = estimate_rows(i, context, _depth + 1)
            if c is None:
                return None
            total += c
        return total
    return None


def _equi_pairs(rel):
    from ..plan.optimizer import split_join_condition
    try:
        equi, _residual = split_join_condition(rel)
        return equi
    except (KeyboardInterrupt, SystemExit):
        raise
    except Exception:
        return []


def _estimate_join_rows(rel, context, _depth: int) -> Optional[float]:
    lrows = estimate_rows(rel.left, context, _depth + 1)
    rrows = estimate_rows(rel.right, context, _depth + 1)
    if lrows is None or rrows is None:
        return None
    jt = rel.join_type
    if jt == "SEMI":
        return lrows * 0.5
    if jt == "ANTI":
        return lrows * 0.5
    out = lrows * rrows
    for lk, rk in _equi_pairs(rel):
        lcs = column_stats_for(rel.left, lk, context)
        rcs = column_stats_for(rel.right, rk, context)
        ndv = max(lcs.ndv if lcs is not None and lcs.ndv else 0,
                  rcs.ndv if rcs is not None and rcs.ndv else 0)
        out /= max(ndv, 10) if ndv else 10
    if jt in ("LEFT", "FULL"):
        out = max(out, lrows)
    if jt in ("RIGHT", "FULL"):
        out = max(out, rrows)
    return max(out, 1.0)


def measured_rows(rel, context) -> Optional[float]:
    """EWMA-measured output rows for this exact subtree shape, when the
    flight recorder has seen it (env-gated; zero cost when off)."""
    if not os.environ.get("DSQL_HISTORY_FILE"):
        return None
    try:
        from . import flight_recorder as _fr
        fp = _fr.plan_fingerprint(rel, context)
        if fp is None:
            return None
        stats = _fr.get_stats(fp)
        if stats and stats.get("rows"):
            return float(stats["rows"])
    except (KeyboardInterrupt, SystemExit):
        raise
    except Exception:
        logger.debug("measured_rows failed", exc_info=True)
    return None


# ---------------------------------------------------------------------------
# the crossover decision table (group-by dispatch)
# ---------------------------------------------------------------------------

def choose_groupby_variant(rows: Optional[float], ndv: Optional[float],
                           dense_ok: bool) -> str:
    """The hash/sort/dense crossover:

    - ``dense``  — single int key over a small dense domain: direct index
      (``codes = key - min``), no hashing, no sort;
    - ``sorted`` — few fat groups (NDV <= min(SORT_NDV_CAP, rows/16)):
      one stable lexsort + boundary scan beats building a table whose
      size scales with NDV, and the sorted stream aggregates scatter-free;
    - ``hash``   — everything else (the status-quo factorize path), and
      the fallback whenever stats are unknown.
    """
    if dense_ok:
        return "dense"
    if rows is None or ndv is None:
        return "hash"
    if ndv <= min(SORT_NDV_CAP, rows / SORT_ROW_FRACTION):
        return "sorted"
    return "hash"


def groupby_decision(rel, context) -> Tuple[str, Dict[str, Any]]:
    """(variant, info) for a LogicalAggregate's eager dispatch.

    ``info`` carries the driving stats for spans/EXPLAIN and, for the
    dense variant, the (lo, hi) domain hint so the kernel skips its own
    min/max probe.  Forced (``DSQL_FORCE_GROUPBY``) overrides everything;
    adaptive off (or no usable stats) keeps the status quo ("hash")."""
    info: Dict[str, Any] = {}
    forced = forced_groupby()
    if forced is not None:
        info["forced"] = 1
        return forced, info
    if os.environ.get("DSQL_AUTOPILOT", "0").strip() not in ("", "0"):
        # an autopilot re-plan hint for this fingerprint overrides the
        # crossover (but never a forced pin); env checked before import
        from . import autopilot as _ap
        hinted = _ap.current_hint("groupby")
        if hinted in ("hash", "sorted", "dense"):
            info["autopilot"] = 1
            return hinted, info
    if not adaptive_enabled() or not rel.group_keys:
        return "hash", info
    rows = estimate_rows(rel.input, context)
    ndv: Optional[float] = 1.0
    dense_ok = False
    for k in rel.group_keys:
        cs = column_stats_for(rel.input, k, context)
        if cs is None or not cs.ndv:
            ndv = None
            break
        ndv *= cs.ndv
    if len(rel.group_keys) == 1:
        cs = column_stats_for(rel.input, rel.group_keys[0], context)
        if cs is not None and cs.dense and cs.min is not None \
                and cs.max is not None:
            dense_ok = True
            info["lo"] = int(cs.min)
            info["hi"] = int(cs.max)
    if rows is not None:
        info["rows"] = int(rows)
    if ndv is not None:
        info["ndv"] = int(ndv)
    return choose_groupby_variant(rows, ndv, dense_ok), info


def join_decision(rel, left_cols, right_cols, context
                  ) -> Tuple[str, Dict[str, Any]]:
    """(variant, info) for an equi join's key factorization: ``dense``
    skips the shared-domain sort entirely when the single key pair is
    integer-typed (``codes = key - min`` on both sides); anything else
    keeps the status-quo shared factorize ("hash")."""
    import jax.numpy as jnp

    info: Dict[str, Any] = {}
    if not adaptive_enabled() or len(left_cols) != 1:
        return "hash", info
    lc, rc = left_cols[0], right_cols[0]
    if lc.stype.is_string or rc.stype.is_string:
        return "hash", info
    if not (jnp.issubdtype(lc.data.dtype, jnp.integer)
            and jnp.issubdtype(rc.data.dtype, jnp.integer)):
        return "hash", info
    if context is not None and rel is not None:
        lrows = estimate_rows(rel.left, context)
        rrows = estimate_rows(rel.right, context)
        if lrows is not None:
            info["lrows"] = int(lrows)
        if rrows is not None:
            info["rrows"] = int(rrows)
    return "dense", info


# ---------------------------------------------------------------------------
# compiled-path capacity hints (physical/caps.py, physical/stages.py)
# ---------------------------------------------------------------------------

def _pad_pow2(n: int, lo: int = 64, hi: int = 1 << 20) -> int:
    n = max(int(n), 1)
    return min(max(1 << (n - 1).bit_length(), lo), hi)


def _grouped_aggregates(plan) -> List[Any]:
    """The grouped aggregates among the plan's inputs; one node reached
    twice (a CTE read twice, ``physical/shared.py``) is one aggregate of
    the trace."""
    from ..plan import nodes as N
    aggs: List[Any] = []

    def walk(rel) -> None:
        if isinstance(rel, N.LogicalAggregate) and rel.group_keys \
                and not any(rel is a for a in aggs):
            aggs.append(rel)
        for i in rel.inputs:
            walk(i)

    walk(plan)
    return aggs


def compiled_cap_hints(plan, context) -> Dict[str, int]:
    """Stats-derived starting caps for the compiled executor's padded
    group-capacity classes.

    Tags are assigned in trace order (``agg0``, ``agg1``, …), which this
    host-side walk cannot reproduce for arbitrary plans (scalar
    subqueries interleave), so hints are only offered when the plan holds
    EXACTLY ONE grouped aggregate — unambiguously ``agg0`` — which covers
    the single-agg stage programs the partitioner produces.  A wrong hint
    is always safe: too small trips the overflow flag into one
    capacity-escalation recompile, too large is just the old padding."""
    if not adaptive_enabled() or forced_groupby() is not None:
        return {}
    try:
        aggs = _grouped_aggregates(plan)
        if len(aggs) != 1:
            return {}
        rel = aggs[0]
        groups = estimate_rows(rel, context)
        if groups is None:
            return {}
        return {"agg0": _pad_pow2(int(groups * 1.25) + 1)}
    except (KeyboardInterrupt, SystemExit):
        raise
    except Exception:
        logger.debug("cap hints failed", exc_info=True)
        return {}


def _whole_table_key(rel, context) -> Optional[ColumnStats]:
    """The ingest statistics of a grouped aggregate's key where they speak
    of its groups: one key, a base column, over every row of its table
    (projects over a scan, no filter, no join).  None elsewhere."""
    if not adaptive_enabled() or forced_groupby() is not None \
            or len(rel.group_keys) != 1:
        return None
    from ..plan import nodes as N
    below = rel.input
    while isinstance(below, N.LogicalProject):
        below = below.input
    if not isinstance(below, N.LogicalTableScan):
        return None
    try:
        return column_stats_for(rel.input, rel.group_keys[0], context)
    except (KeyboardInterrupt, SystemExit):
        raise
    except Exception:
        logger.debug("group key statistics failed", exc_info=True)
        return None


def counted_groups(rel, context) -> Optional[int]:
    """The capacity class of a grouped aggregate whose group count the ingest
    statistics HOLD, for the tracer to start it from where nothing was
    learned or hinted (``compiled._LogicalAggregate``, which gives the
    aggregate its tag: no numbering is repeated here): one key, a base
    column, over every row of its table (``_whole_table_key``), and a
    distinct count that was counted and not sampled (``_counted_ndv``).
    TPC-H Q18's inner ``GROUP BY l_orderkey`` is 6 M
    rows into 1.5 M groups beside an outer aggregate, so
    ``compiled_cap_hints`` says nothing of it: from the default it climbs
    4 096 -> 65 536 -> 1 048 576 -> 8 388 608 (``caps._check_flags`` jumps
    x16 a saturated overflow), four whole-plan compiles and a capacity four
    times its class; its runs start it at 2 097 152.  An estimate (a sampled
    ``ndv``: 6 M for that column; a filter's selectivity, a join's fan-out)
    gives None: a group cap never shrinks."""
    groups = _counted_ndv(_whole_table_key(rel, context))
    # counted: its own class, no margin, no ceiling but the rows
    return max(64, 1 << (int(groups) - 1).bit_length()) if groups else None


def grouped_by_runs(rel, context) -> bool:
    """Whether the groups of a grouped aggregate are the RUNS of its key
    column: the key of ``_whole_table_key``, an integer column without NULLs
    that never decreases in load order (``runs``, or ``increasing``: every
    row a group).  The compiled tier then takes the groups from the column's
    boundaries and hashes nothing (``ops/groupby.py::key_runs``): TPC-H
    Q18's inner GROUP BY, per-session totals over events loaded in session
    order, any roll-up of a child table stored beside its parent's key.  A
    statistic that is data: the plan's count of such aggregates rides with
    the capacities (``run_group_hints``), the tracer asks here for the
    aggregate it stands at, and the program checks the column itself."""
    cs = _whole_table_key(rel, context)
    return cs is not None and (cs.increasing or cs.runs is not None)


#: the tag of ``run_group_hints``' word among a request's capacities
RUN_GROUPS_TAG = "runs"


def run_group_hints(plan, context) -> Dict[str, int]:
    """``{"runs": k}`` where ``k`` > 0 grouped aggregates among the plan's
    inputs are ``grouped_by_runs``: part of the program's key as the
    ``ord*`` hints are (other data of the same layout is another program),
    and 0 once a program's own check refuted it
    (``caps._check_ordered``).  A count, not a tag an aggregate: the
    tracer numbers aggregates, nothing else does."""
    runs = sum(grouped_by_runs(rel, context)
               for rel in _grouped_aggregates(plan))
    return {RUN_GROUPS_TAG: runs} if runs else {}


def _counted_ndv(cs: Optional[ColumnStats]) -> Optional[int]:
    """A column's distinct count where ``_collect_column`` counted it: a
    column that increases (every value once), a domain narrow enough to
    bincount, the runs of one that never decreases.  None where ``ndv`` is
    a sample's estimate."""
    if cs is None:
        return None
    if cs.increasing or (cs.is_int
                         and 0 < (cs.domain or 0) <= _NDV_PROBE_DOMAIN):
        return cs.ndv
    return cs.runs


#: What an ordered-probe hint says of a join's build key column (the
#: compiled tier's ``joins.hash_table``): it increases strictly in load
#: order, and beyond that nothing (the search gathers 64 bits), or its span
#: is under 2^31 (the search runs in 32), or it holds every integer of its
#: range (no search).  0 is a hint a program's own check refuted.
ORDERED_WIDE, ORDERED_NARROW, ORDERED_DENSE = 1, 2, 3


def _tagged_joins(plan) -> list:
    """``("ord<j>", join)`` for the plan's joins, each numbered after those
    of its left and of its right input.  A scalar subquery's are not among
    them, and a join the plan holds twice as one node
    (``physical/shared.py``) is numbered where it is met first."""
    from ..plan import nodes as N

    joins: list = []

    def walk(rel) -> None:
        for i in rel.inputs:
            walk(i)
        if isinstance(rel, N.LogicalJoin) \
                and not any(rel is j for _, j in joins):
            joins.append((f"ord{len(joins)}", rel))

    walk(plan)
    return joins


def join_tags(plan) -> Dict[int, str]:
    """``id(join) -> "ord<j>"`` for the plan's joins.  The hints' walk and
    the tracer's read the same numbering, so a scalar subquery (whose joins
    get no tag and no hint) cannot make the two disagree the way
    trace-order counters would."""
    return {id(rel): tag for tag, rel in _tagged_joins(plan)}


def _load_order_level(rel, ordinal: int, context) -> int:
    """The ``ORDERED_*`` level of output ``ordinal`` of ``rel`` where ``rel``
    hands on a scan's rows in load order (projects and filters over a
    scan) and the column's ingest statistics say it increases; else 0."""
    from ..plan import nodes as N

    below = rel
    while isinstance(below, (N.LogicalProject, N.LogicalFilter)):
        below = below.input
    if not isinstance(below, N.LogicalTableScan):
        return 0
    cs = column_stats_for(rel, ordinal, context)
    if cs is None or not cs.increasing or cs.domain is None:
        return 0
    if cs.domain == cs.ndv:
        return ORDERED_DENSE
    return ORDERED_NARROW if cs.domain <= 1 << 31 else ORDERED_WIDE


def _single_key_sides(plan):
    """``(tag, side, input, key ordinal)`` for both sides of every join of
    ``_tagged_joins`` on ONE key pair: what a hint about a join's key can
    speak of."""
    for tag, rel in _tagged_joins(plan):
        pairs = _equi_pairs(rel)
        if len(pairs) == 1:
            yield tag, "l", rel.left, pairs[0][0]
            yield tag, "r", rel.right, pairs[0][1]


def ordered_probe_hints(plan, context) -> Dict[str, int]:
    """Starting hints ``ord<j>l`` / ``ord<j>r`` for the joins on ONE key
    whose left / right input is a base table in the order of that key: the
    statistic is data, not layout, so it rides with the capacities into
    the program's key, and the program checks what it was told
    (``caps._check_ordered``).  Which side builds, and whether a search
    pays at the rows the join meets, is the tracer's to say."""
    if not adaptive_enabled():
        return {}
    hints: Dict[str, int] = {}
    try:
        for tag, side, input_, key in _single_key_sides(plan):
            level = _load_order_level(input_, key, context)
            if level:
                hints[tag + side] = level
    except (KeyboardInterrupt, SystemExit):
        raise
    except Exception:
        logger.debug("ordered-probe hints failed", exc_info=True)
        return {}
    return hints


def span_tag(side_tag: str) -> str:
    """``span<j>l`` / ``span<j>r`` of the join side an ordered-probe hint
    would call ``ord<j>l`` / ``ord<j>r`` (``join_tags`` and a side)."""
    return "span" + side_tag[3:]


def key_span_hints(plan, context) -> Dict[str, int]:
    """Starting hints ``span<j>l`` / ``span<j>r`` for the joins on ONE
    integer key that ``column_stats_for`` follows back to a base column:
    the class of that column's ingest span, the power of two at or above
    ``domain`` (max - min + 1).  A filter, a compaction or a join below
    the key keeps its values inside the span, so a hash table of that many
    slots can be direct-addressed whatever rows reach it; whether such a
    table pays at the rows the join meets is the tracer's to say
    (``hashing._hash_table_size``), and whether the keys lie inside it the
    program's own (``hashing._direct_info``).  A statistic that is data:
    it rides with the capacities into the program's key, as ``ord*``
    does.  A string key (its codes are unified a join), a computed key and
    a key of several parts get none."""
    if not adaptive_enabled():
        return {}
    hints: Dict[str, int] = {}
    try:
        for tag, side, input_, key in _single_key_sides(plan):
            cs = column_stats_for(input_, key, context)
            if cs is not None and cs.is_int and cs.domain:
                hints[span_tag(tag + side)] = _pad_pow2(cs.domain, 1,
                                                        1 << 62)
    except (KeyboardInterrupt, SystemExit):
        raise
    except Exception:
        logger.debug("key-span hints failed", exc_info=True)
        return {}
    return hints


def estimate_plan_bytes_stats(plan, context) -> Optional[int]:
    """Stats-driven working-set estimate for the scheduler: the resident
    scan bytes (they are touched regardless) plus every heavy operator's
    estimated output (rows × 9 bytes/column — 8 data + amortized mask).
    None when adaptive is off or the plan's cardinality can't be
    estimated — the caller keeps the shape heuristic."""
    if not adaptive_enabled():
        return None
    from ..plan import nodes as N

    try:
        scan_bytes = 0
        inter_bytes = 0.0
        ok = True
        stack = [plan]
        while stack:
            rel = stack.pop()
            if isinstance(rel, N.LogicalTableScan):
                entry = _scan_entry(rel, context)
                if entry is not None:
                    from .scheduler import _entry_bytes
                    scan_bytes += _entry_bytes(entry)
            elif isinstance(rel, (N.LogicalJoin, N.LogicalAggregate,
                                  N.LogicalWindow, N.LogicalSort)):
                est = estimate_rows(rel, context)
                if est is None:
                    ok = False
                    break
                inter_bytes += est * max(len(rel.schema), 1) * 9
            stack.extend(getattr(rel, "inputs", ()) or ())
        if not ok:
            return None
        return int(scan_bytes + inter_bytes)
    except (KeyboardInterrupt, SystemExit):
        raise
    except Exception:
        logger.debug("stats byte estimate failed", exc_info=True)
        return None


# ---------------------------------------------------------------------------
# choice recording: counters + spans + an optional thread-local capture
# ---------------------------------------------------------------------------

_tls = threading.local()


@contextmanager
def capture():
    """Collect every record_choice() on this thread (EXPLAIN ANALYZE's
    eager run uses it to print the choices the run actually took)."""
    prev = getattr(_tls, "capture", None)
    buf: List[Tuple[str, str, Dict[str, Any]]] = []
    _tls.capture = buf
    try:
        yield buf
    finally:
        _tls.capture = prev


def record_choice(op: str, variant: str, **info) -> None:
    """One dispatch decision: counter ``operator_choice_<op>_<variant>``,
    an ``operators`` list entry on the current span (flows into
    QueryReport / flight-recorder envelopes / system.queries / the wire),
    and the thread-local capture buffer when one is open."""
    _tel.inc(f"operator_choice_{op}_{variant}")
    line = format_choice(op, variant, info)
    span = _tel.current_span()
    if span is not None:
        span.attrs.setdefault("operators", []).append(line)
    buf = getattr(_tls, "capture", None)
    if buf is not None:
        buf.append((op, variant, dict(info)))


def format_choice(op: str, variant: str, info: Dict[str, Any]) -> str:
    parts = [f"{op}={variant}"]
    for k in sorted(info):
        parts.append(f"{k}={info[k]}")
    return " ".join(parts)


# ---------------------------------------------------------------------------
# EXPLAIN surface
# ---------------------------------------------------------------------------

def explain_lines(plan, context) -> List[str]:
    """``-- operator:`` trailer lines for plain EXPLAIN: the variant each
    group-by/join WOULD take under current stats (EXPLAIN ANALYZE prints
    the measured choices instead).  Silent when adaptive is off."""
    if not adaptive_enabled() and forced_groupby() is None:
        return []
    from ..plan import nodes as N

    lines: List[str] = []

    def walk(rel) -> None:
        for i in rel.inputs:
            walk(i)
        if isinstance(rel, N.LogicalAggregate) and rel.group_keys:
            try:
                variant, info = groupby_decision(rel, context)
            except (KeyboardInterrupt, SystemExit):
                raise
            except Exception:
                return
            lines.append("-- operator: "
                         + format_choice("groupby", variant, info))
        elif isinstance(rel, N.LogicalJoin):
            pairs = _equi_pairs(rel)
            if len(pairs) != 1:
                return
            try:
                lk, rk = pairs[0]
                lcs = column_stats_for(rel.left, lk, context)
                rcs = column_stats_for(rel.right, rk, context)
                dense = bool(lcs is not None and rcs is not None
                             and lcs.is_int and rcs.is_int
                             and adaptive_enabled())
                info: Dict[str, Any] = {}
                lrows = estimate_rows(rel.left, context)
                rrows = estimate_rows(rel.right, context)
                if lrows is not None:
                    info["lrows"] = int(lrows)
                if rrows is not None:
                    info["rrows"] = int(rrows)
                lines.append("-- operator: " + format_choice(
                    "join", "dense" if dense else "hash", info))
            except (KeyboardInterrupt, SystemExit):
                raise
            except Exception:
                return

    try:
        walk(plan)
    except (KeyboardInterrupt, SystemExit):
        raise
    except Exception:
        return []
    return lines


# ---------------------------------------------------------------------------
# system.table_stats export
# ---------------------------------------------------------------------------

def system_rows(context) -> List[dict]:
    """One row per (schema, table, column) with ingest stats — the
    ``system.table_stats`` builder's payload."""
    rows: List[dict] = []
    for schema_name, schema in sorted(context.schema.items()):
        for table_name, entry in sorted(schema.tables.items()):
            ts = getattr(entry, "stats", None)
            if ts is None:
                continue
            base = {"schema": schema_name, "table": table_name,
                    "rows": int(ts.rows),
                    "collected_ms": float(ts.collected_ms)}
            if not ts.cols:
                rows.append({**base, "column": "", "ndv": -1,
                             "min": float("nan"), "max": float("nan"),
                             "null_frac": 0.0, "is_int": False,
                             "dense": False, "domain": -1,
                             "increasing": False})
            for name in ts.cols:
                rows.append({**base, **ts.cols[name].to_row()})
    return rows
