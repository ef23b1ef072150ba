"""SPMD stage execution: whole query stages as explicit shard_map programs.

This is the multi-chip execution backend (ROADMAP item 1).  Where the
compiled executor traces a plan over GLOBAL arrays and lets GSPMD infer a
partitioning, this module lowers each stage of the stage graph
(physical/stages.py) into ONE ``shard_map`` program over the row mesh with
the collectives placed explicitly:

- base-table scans read the catalog's row-sharded columns as local shards
  (mesh-mode ``create_table`` pads + row-shards with a validity mask);
- Project/Filter run unchanged per shard — the rex evaluator operates on
  whatever arrays the Columns hold, local shards included;
- equi joins lower to a hash-partitioned ``all_to_all`` exchange + local
  probe, or to an ``all_gather`` broadcast of a small build side — chosen
  by TableStats cardinality estimates (parallel/exchange.py);
- GROUP BY / global aggregates lower to per-shard partial aggregates
  combined via ``psum`` trees (small static key domains) or via hash
  exchange + disjoint ``all_gather`` slot tables (parallel/partial_agg.py);
- stage boundaries stay row-sharded: every program output rides a uniform
  ``P(ROW_AXIS)`` out-spec (replicated values are emitted through
  ``shard_replicated``), so boundary temps are sharded arrays and the next
  stage scans them like any mesh table.

Correctness over silent degradation: anything the lowering cannot express
(multi-key equi joins, distinct aggregates, duplicate build keys, group
caps, radix overflow) either refuses up front (``spmd_unsupported``) or
raises a traced runtime flag checked after execution (``spmd_fallbacks``);
both return None so the caller's compiled/eager path serves the query.

Stage programs are AOT-compiled and persist to the cross-process program
store keyed by (canonical stage plan, input layout, mesh signature) — a
fresh process re-serves sharded queries with zero XLA compiles.

Env knobs: ``DSQL_MESH=0`` disables the backend; ``DSQL_SPMD_BROADCAST_ROWS``
(default 65536) is the build-side estimate at which joins switch from
broadcast to exchange; ``DSQL_SPMD_GROUP_CAP`` (default 8192) caps distinct
groups per device post-exchange; ``DSQL_SPMD_DENSE_CAP`` (default 4096)
caps the static key-domain product for the psum-tree group-by path.
"""
from __future__ import annotations

import logging
import os
import re
import threading
from collections import OrderedDict
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from jax import shard_map

from ..plan.nodes import (AggCall, Field, LogicalAggregate, LogicalFilter,
                          LogicalJoin, LogicalProject, LogicalSort,
                          LogicalTableScan, RelNode, RexScalarSubquery,
                          RexUdf)
from ..table import Column, Scalar, Table
from ..types import physical_dtype
from ..runtime import telemetry as _tel
from . import exchange as X, partial_agg as PA
from .mesh import ROW_AXIS

logger = logging.getLogger(__name__)

_SPMD_SCHEMA = "__spmd__"
_TEMP_NAME_RE = re.compile(r"__spmd__\.t[0-9a-f]{16}")
_SUPPORTED_AGGS = ("SUM", "$SUM0", "COUNT", "AVG", "MIN", "MAX")


class Unsupported(Exception):
    """Plan shape outside the SPMD lowering's envelope (clean refusal)."""


def spmd_enabled(context) -> bool:
    """The backend runs iff the context HAS a mesh of >= 2 devices and the
    kill switch (DSQL_MESH=0) is off.  Default-on with a mesh: passing
    ``Context(mesh=...)`` is itself the opt-in."""
    if getattr(context, "mesh", None) is None:
        return False
    if os.environ.get("DSQL_MESH", "1") == "0":
        return False
    return int(context.mesh.devices.size) >= 2


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, str(default)))
    except ValueError:
        return default


def _broadcast_rows_cap() -> int:
    return _env_int("DSQL_SPMD_BROADCAST_ROWS", 65536)


def _group_cap() -> int:
    return max(1, _env_int("DSQL_SPMD_GROUP_CAP", 8192))


def _dense_cap() -> int:
    return max(2, _env_int("DSQL_SPMD_DENSE_CAP", 4096))


# ---------------------------------------------------------------------------
# in-trace table wrapper
# ---------------------------------------------------------------------------

class _ST:
    """A traced table inside the shard_map body.

    ``sharded`` distinguishes per-device row shards (collectives required
    for any cross-row operation) from replicated tables (aggregate
    outputs — identical on every device, local ops suffice and psum-style
    combines must NOT run).  ``valid`` is the local row-validity mask
    (None = all rows live)."""

    __slots__ = ("table", "valid", "sharded")

    def __init__(self, table: Table, valid, sharded: bool):
        self.table = table
        self.valid = valid
        self.sharded = sharded

    @property
    def n(self) -> int:
        return self.table.num_rows

    def vmask(self) -> jax.Array:
        if self.valid is None:
            return jnp.ones(self.n, dtype=bool)
        return self.valid


# ---------------------------------------------------------------------------
# static support gate (no tracing, no side effects)
# ---------------------------------------------------------------------------

def _check_rex(rex) -> None:
    if isinstance(rex, (RexScalarSubquery, RexUdf)):
        raise Unsupported(type(rex).__name__)
    for o in getattr(rex, "operands", None) or ():
        _check_rex(o)


def _gate_plan(rel: RelNode) -> None:
    """Refuse plan shapes the walker cannot lower BEFORE any stage runs."""
    if isinstance(rel, LogicalTableScan):
        return
    if isinstance(rel, LogicalProject):
        for e in rel.exprs:
            _check_rex(e)
    elif isinstance(rel, LogicalFilter):
        _check_rex(rel.condition)
    elif isinstance(rel, LogicalJoin):
        if rel.join_type != "INNER":
            raise Unsupported(f"join type {rel.join_type}")
        from ..plan.optimizer import split_join_condition
        equi, residual = split_join_condition(rel)
        if residual or len(equi) != 1:
            raise Unsupported("non-single-key equi join")
        li, ri = equi[0]
        for side, i in ((rel.inputs[0], li), (rel.inputs[1], ri)):
            st = side.schema[i].stype
            if st.is_string or st.name in ("DOUBLE", "FLOAT", "REAL",
                                           "DECIMAL"):
                raise Unsupported(f"join key type {st.name}")
    elif isinstance(rel, LogicalAggregate):
        for agg in rel.aggs:
            if agg.udaf is not None or agg.distinct:
                raise Unsupported("distinct/udaf agg")
            if agg.op not in _SUPPORTED_AGGS:
                raise Unsupported(f"agg {agg.op}")
            if agg.op in ("MIN", "MAX") and agg.args:
                if rel.inputs[0].schema[agg.args[0]].stype.is_string:
                    raise Unsupported("string MIN/MAX")
        for k in rel.group_keys:
            st = rel.inputs[0].schema[k].stype
            if st.name in ("DOUBLE", "FLOAT", "REAL", "DECIMAL"):
                raise Unsupported(f"float group key {st.name}")
    else:
        # Sort inside the core (the root chain was peeled), Window, Union,
        # Values, set ops, samples: no SPMD lowering yet
        raise Unsupported(type(rel).__name__)
    for i in rel.inputs:
        _gate_plan(i)


# ---------------------------------------------------------------------------
# the stage walker (runs INSIDE the shard_map trace)
# ---------------------------------------------------------------------------

class _Walker:
    """Lowers one stage subtree over local shards.

    ``meta`` is shared across the (up to two) traces of one stage — the
    eval_shape structure pass records dispatch decisions, counters and
    output descriptors; the compile trace REPLAYS the recorded decisions so
    both traces build byte-identical programs even if statistics shift
    between them."""

    def __init__(self, context, n_dev: int, scan_tables: Dict, meta: Dict):
        self.context = context
        self.n_dev = n_dev
        self.scan_tables = scan_tables
        self.meta = meta
        self.record = not meta.get("recorded")
        self._decision_idx = 0
        self.flags: List[Tuple[str, jax.Array]] = []  # replicated bools

    # -- bookkeeping -------------------------------------------------------
    def _count(self, key: str, n: int = 1) -> None:
        if self.record:
            self.meta["counts"][key] = self.meta["counts"].get(key, 0) + n

    def _decide(self, op: str, variant: str, **info) -> str:
        """Record (first trace) or replay (later traces) one dispatch
        decision, keeping traces deterministic."""
        if self.record:
            self.meta["decisions"].append((op, variant, info))
            return variant
        op_, variant_, _ = self.meta["decisions"][self._decision_idx]
        self._decision_idx += 1
        assert op_ == op, f"decision replay drift: {op_} vs {op}"
        return variant_

    def _flag(self, label: str, replicated_bool: jax.Array) -> None:
        self.flags.append((label, replicated_bool))

    # -- dispatch ----------------------------------------------------------
    def walk(self, rel: RelNode) -> _ST:
        if isinstance(rel, LogicalTableScan):
            return self._scan(rel)
        if isinstance(rel, LogicalProject):
            return self._project(rel)
        if isinstance(rel, LogicalFilter):
            return self._filter(rel)
        if isinstance(rel, LogicalJoin):
            return self._join(rel)
        if isinstance(rel, LogicalAggregate):
            return self._aggregate(rel)
        raise Unsupported(type(rel).__name__)

    def _scan(self, rel: LogicalTableScan) -> _ST:
        st = self.scan_tables[(rel.schema_name, rel.table_name)]
        # the optimizer prunes/reorders scan schemas; honor it (the flat
        # arg list still carries the full table — selection is trace-time)
        want = [f.name for f in rel.schema]
        if st.table.names != want:
            st = _ST(st.table.limit_to(want), st.valid, sharded=st.sharded)
        return st

    def _project(self, rel: LogicalProject) -> _ST:
        src = self.walk(rel.inputs[0])
        cols = []
        for expr, f in zip(rel.exprs, rel.schema):
            v = evaluate_rex_local(expr, src.table)
            if isinstance(v, Scalar):
                v = Column.from_scalar(v, src.n)
            cols.append(v)
        return _ST(Table([f.name for f in rel.schema], cols), src.valid,
                   src.sharded)

    def _filter(self, rel: LogicalFilter) -> _ST:
        from ..physical.rex.evaluate import evaluate_predicate

        src = self.walk(rel.inputs[0])
        pred = evaluate_predicate(rel.condition, src.table)
        if isinstance(pred, bool):
            valid = src.valid if pred else jnp.zeros(src.n, dtype=bool)
        else:
            valid = src.vmask() & pred
        return _ST(src.table, valid, src.sharded)

    # -- joins -------------------------------------------------------------
    def _join_key(self, st: _ST, idx: int, sentinel: int
                  ) -> Tuple[jax.Array, jax.Array]:
        """(key int64 with ``sentinel`` for dead rows, live mask)."""
        col = st.table.columns[idx]
        if not (jnp.issubdtype(col.data.dtype, jnp.integer)
                or col.data.dtype == jnp.bool_):
            raise Unsupported(f"join key dtype {col.data.dtype}")
        live = st.vmask()
        if col.mask is not None:
            live = live & col.mask
        d = col.data.astype(jnp.int64)
        # a live key equal to the sentinel would silently drop its row
        self._flag("join_key_sentinel",
                   X.replicated_flag(jnp.any(live & (d == sentinel))))
        return jnp.where(live, d, sentinel), live

    def _join(self, rel: LogicalJoin) -> _ST:
        from ..plan.optimizer import split_join_condition
        from ..runtime import statistics as _stats

        left = self.walk(rel.inputs[0])
        right = self.walk(rel.inputs[1])
        equi, residual = split_join_condition(rel)
        if rel.join_type != "INNER" or residual or len(equi) != 1:
            raise Unsupported("join shape")
        li, ri = equi[0]

        # build/probe + broadcast/exchange dispatch: TableStats estimates
        # when available, physical (padded) row counts otherwise.  Recorded
        # once and replayed so re-traces can't flip sides.
        if self.record:
            def est(node, st):
                e = None
                try:
                    e = _stats.estimate_rows(node, self.context)
                except Exception:
                    e = None
                if e is None:
                    e = st.n * (self.n_dev if st.sharded else 1)
                return float(e)

            est_l, est_r = est(rel.inputs[0], left), est(rel.inputs[1], right)
            build_side = "right" if est_r <= est_l else "left"
            est_build = min(est_l, est_r)
            both_sharded = left.sharded and right.sharded
            variant = ("exchange" if both_sharded
                       and est_build > _broadcast_rows_cap() else "broadcast")
            if os.environ.get("DSQL_AUTOPILOT", "0").strip() not in ("", "0"):
                # autopilot re-plan hint flips the strategy for THIS
                # recording; the decision folds into the stage digest so
                # the hinted plan compiles its own program (env checked
                # before import).  "exchange" only applies when legal.
                from ..runtime import autopilot as _ap
                hj = _ap.current_hint("join")
                if hj == "broadcast" or (hj == "exchange" and both_sharded):
                    variant = hj
            di = len(self.meta["decisions"])
            self._decide("spmd_join", variant, build=build_side,
                         est_build=int(est_build),
                         est_probe=int(max(est_l, est_r)))
        else:
            di = self._decision_idx
            _, variant, info = self.meta["decisions"][self._decision_idx]
            self._decision_idx += 1
            build_side = info["build"]

        if build_side == "right":
            build, bi, probe, pi = right, ri, left, li
        else:
            build, bi, probe, pi = left, li, right, ri

        if variant == "exchange":
            out = self._join_exchange(rel, build, bi, probe, pi, di)
            self._count("spmd_exchange_joins")
        else:
            out = self._join_broadcast(rel, build, bi, probe, pi, di)
            self._count("spmd_broadcast_joins")
        # reassemble output columns in join-schema order (left then right)
        bcols, pcols = out
        if build_side == "right":
            cols = pcols + bcols
        else:
            cols = bcols + pcols
        names = [f.name for f in rel.schema]
        table = Table(names, [c for c, _ in cols])
        valid = cols[0][1]  # every entry carries the same out-valid
        return _ST(table, valid, probe.sharded)

    def _gather_cols(self, build: _ST, idx, hit, do_gather: bool):
        """Pick build-side columns at probe positions (post all_gather)."""
        out = []
        for c in build.table.columns:
            data = X.gather_build(c.data) if do_gather else c.data
            mask = None
            if c.mask is not None:
                mask = (X.gather_build(c.mask) if do_gather else c.mask)[idx]
                mask = mask & hit
            picked = data[idx]
            out.append(Column(picked, c.stype, mask, c.dictionary))
        return out

    def _join_broadcast(self, rel, build, bi, probe, pi, di):
        sent = X.BROADCAST_SENTINEL
        bkey, _ = self._join_key(build, bi, sent)
        pkey, _ = self._join_key(probe, pi, sent)
        if build.sharded:
            # collective accounting by kind: the key plus every build
            # column (data + mask) rides an all_gather in _gather_cols
            gathered = [bkey]
            for c in build.table.columns:
                gathered.append(c.data)
                if c.mask is not None:
                    gathered.append(c.mask)
            self._count("spmd_all_gather_bytes",
                        X.gather_bytes(gathered, self.n_dev))
            bkey = X.gather_build(bkey)
        idx, hit, dup = X.sorted_probe(bkey, pkey, sent)
        # tagged with the decision index so the stage runner can flip this
        # join's build side and retry instead of abandoning the whole query
        self._flag(f"dup_build_keys@{di}", X.replicated_flag(dup))
        out_valid = probe.vmask() & hit
        bcols = [(c, out_valid) for c in
                 self._gather_cols(build, idx, hit, build.sharded)]
        pcols = [(c, out_valid) for c in probe.table.columns]
        return bcols, pcols

    def _join_exchange(self, rel, build, bi, probe, pi, di):
        sent = X.BROADCAST_SENTINEL
        bkey, _ = self._join_key(build, bi, sent)
        pkey, _ = self._join_key(probe, pi, sent)
        # normalize to non-negative partition codes via the joint minimum
        both_min = jnp.minimum(
            jnp.min(jnp.where(bkey == sent, jnp.int64(1 << 62), bkey)),
            jnp.min(jnp.where(pkey == sent, jnp.int64(1 << 62), pkey)))
        gmin = jax.lax.pmin(both_min, ROW_AXIS)
        bcode = jnp.where(bkey == sent, jnp.int64(-1), bkey - gmin)
        pcode = jnp.where(pkey == sent, jnp.int64(-1), pkey - gmin)

        bpay, bspec = _flatten_st(build)
        ppay, pspec = _flatten_st(probe)
        self._count("spmd_exchanges", 2)
        self._count("spmd_exchange_bytes",
                    X.exchange_bytes(bcode, bpay, self.n_dev)
                    + X.exchange_bytes(pcode, ppay, self.n_dev))
        bcode2, bpay2 = X.exchange(bcode, bpay, self.n_dev)
        pcode2, ppay2 = X.exchange(pcode, ppay, self.n_dev)
        build2 = _unflatten_st(build, bpay2, bspec, bcode2 >= 0)
        probe2 = _unflatten_st(probe, ppay2, pspec, pcode2 >= 0)

        bkey2 = jnp.where(bcode2 >= 0, bcode2, sent)
        pkey2 = jnp.where(pcode2 >= 0, pcode2, sent)
        idx, hit, dup = X.sorted_probe(bkey2, pkey2, sent)
        self._flag(f"dup_build_keys@{di}", X.replicated_flag(dup))
        out_valid = probe2.vmask() & hit
        bcols = [(c, out_valid) for c in
                 self._gather_cols(build2, idx, hit, False)]
        pcols = [(c, out_valid) for c in probe2.table.columns]
        return bcols, pcols

    # -- aggregates --------------------------------------------------------
    def _agg_inputs(self, agg: AggCall, src: _ST):
        """(col|None, ok): the argument column and its live-row mask."""
        ok = src.vmask()
        col = src.table.columns[agg.args[0]] if agg.args else None
        if col is not None and col.mask is not None:
            ok = ok & col.mask
        if agg.filter_arg is not None:
            fc = src.table.columns[agg.filter_arg]
            fm = fc.data.astype(bool)
            if fc.mask is not None:
                fm = fm & fc.mask
            ok = ok & fm
        return col, ok

    def _aggregate(self, rel: LogicalAggregate) -> _ST:
        src = self.walk(rel.inputs[0])
        self._count("spmd_partial_aggs", max(1, len(rel.aggs)))
        if not rel.group_keys:
            return self._agg_global(rel, src)
        key_cols = [src.table.columns[i] for i in rel.group_keys]
        static_doms = _static_domains(key_cols)
        if static_doms is not None and int(np.prod(static_doms)) <= _dense_cap():
            variant = self._decide("spmd_groupby", "psum_tree",
                                   domain=int(np.prod(static_doms)))
            return self._agg_grouped_static(rel, src, key_cols, static_doms)
        self._decide("spmd_groupby", "exchange", cap=_group_cap())
        return self._agg_grouped_exchange(rel, src, key_cols)

    def _agg_global(self, rel: LogicalAggregate, src: _ST) -> _ST:
        cols = []
        for agg, f in zip(rel.aggs, rel.schema):
            col, ok = self._agg_inputs(agg, src)
            out_dt = physical_dtype(f.stype)
            if agg.op == "COUNT":
                c = PA.global_count(ok, src.sharded)
                cols.append(Column(c.reshape(1).astype(out_dt), f.stype, None))
                continue
            if col is None:
                raise Unsupported(f"{agg.op} without argument")
            if agg.op in ("SUM", "$SUM0", "AVG"):
                s, c = PA.global_sum(col.data, ok, src.sharded)
                has = (c > 0).reshape(1)
                if agg.op == "AVG":
                    mean = s.astype(jnp.float64) / jnp.maximum(c, 1)
                    cols.append(Column(mean.reshape(1).astype(out_dt),
                                       f.stype, has))
                else:
                    mask = None if agg.op == "$SUM0" else has
                    cols.append(Column(s.reshape(1).astype(out_dt),
                                       f.stype, mask))
                continue
            # MIN / MAX (non-string; gated)
            is_min = agg.op == "MIN"
            m = PA.global_minmax(col.data, ok, is_min, src.sharded)
            c = PA.global_count(ok, src.sharded)
            cols.append(Column(m.reshape(1).astype(out_dt), f.stype,
                               (c > 0).reshape(1)))
        if src.sharded:
            # global partials are scalar psums: tiny, but the per-kind
            # ledger stays complete
            self._count("spmd_psum_bytes",
                        X.psum_bytes([c.data for c in cols], self.n_dev))
        names = [f.name for f in rel.schema]
        return _ST(Table(names, cols), None, sharded=False)

    def _slot_agg_columns(self, rel, src, slot, cap, combine, counts_rows):
        """Shared slot-table aggregation for both grouped paths.

        ``combine(arr, is_minmax, is_min)`` folds per-device slot tables
        into the global group table (psum tree or disjoint all_gather)."""
        cols = []
        nk = len(rel.group_keys)
        for agg, f in zip(rel.aggs, rel.schema[nk:]):
            col, ok = self._agg_inputs(agg, src)
            ok = ok & (slot < cap)
            out_dt = physical_dtype(f.stype)
            if agg.op == "COUNT":
                c = combine(PA.slot_count(ok, slot, cap), False, False)
                cols.append(Column(c.astype(out_dt), f.stype, None))
                continue
            if col is None:
                raise Unsupported(f"{agg.op} without argument")
            if agg.op in ("SUM", "$SUM0", "AVG"):
                s, c = PA.slot_sum(col.data, ok, slot, cap)
                s, c = combine(s, False, False), combine(c, False, False)
                has = c > 0
                if agg.op == "AVG":
                    mean = s.astype(jnp.float64) / jnp.maximum(c, 1)
                    cols.append(Column(mean.astype(out_dt), f.stype, has))
                elif agg.op == "$SUM0":
                    cols.append(Column(s.astype(out_dt), f.stype, None))
                else:
                    cols.append(Column(s.astype(out_dt), f.stype, has))
                continue
            is_min = agg.op == "MIN"
            m = combine(PA.slot_minmax(col.data, ok, slot, cap, is_min),
                        True, is_min)
            c = combine(PA.slot_count(ok, slot, cap), False, False)
            cols.append(Column(m.astype(out_dt), f.stype, c > 0))
        return cols

    def _agg_grouped_static(self, rel, src: _ST, key_cols, doms) -> _ST:
        """Small static key domain (dict strings / bools): dense codes,
        local segment partials, psum-tree combine — no exchange at all."""
        G = int(np.prod(doms))
        rows_ok = src.vmask()
        code = jnp.zeros(src.n, dtype=jnp.int64)
        for col, dom in zip(key_cols, doms):
            d = col.data.astype(jnp.int64)
            if col.mask is not None:           # slot 0 = NULL
                d = jnp.where(col.mask, d + 1, 0)
            code = code * dom + d
        slot = jnp.where(rows_ok, code, G).astype(jnp.int32)

        def combine(arr, is_minmax, is_min):
            if src.sharded:
                # psum / pmin / pmax are all mesh reductions of the slot
                # table: account them under the psum kind
                self._count("spmd_psum_bytes",
                            X.psum_bytes([arr], self.n_dev))
            if not is_minmax:
                return PA.psum_table(arr, src.sharded)
            if not src.sharded:
                return arr
            return (jax.lax.pmin if is_min else jax.lax.pmax)(arr, ROW_AXIS)

        rows = combine(PA.slot_count(rows_ok, slot, G), False, False)
        acols = self._slot_agg_columns(rel, src, slot, G, combine, rows)
        kcols = _decode_static_keys(key_cols, doms, G)
        names = [f.name for f in rel.schema]
        return _ST(Table(names, kcols + acols), rows > 0, sharded=False)

    def _agg_grouped_exchange(self, rel, src: _ST, key_cols) -> _ST:
        """Arbitrary integer-typed keys: runtime mixed-radix codes from
        global pmin/pmax spans, hash exchange for disjoint ownership, local
        slot tables, all_gather combine, in-trace key decode."""
        cap = _group_cap()
        rows_ok = src.vmask()
        n = src.n

        # runtime spans (replicated) + packed codes
        gmins, spans = [], []
        code = jnp.zeros(n, dtype=jnp.int64)
        prod = jnp.float64(1.0)
        for col in key_cols:
            if not (jnp.issubdtype(col.data.dtype, jnp.integer)
                    or col.data.dtype == jnp.bool_):
                raise Unsupported(f"group key dtype {col.data.dtype}")
            d = col.data.astype(jnp.int64)
            ok = rows_ok if col.mask is None else (rows_ok & col.mask)
            big = jnp.int64(1 << 62)
            lo = jnp.min(jnp.where(ok, d, big))
            hi = jnp.max(jnp.where(ok, d, -big))
            if src.sharded:
                lo = jax.lax.pmin(lo, ROW_AXIS)
                hi = jax.lax.pmax(hi, ROW_AXIS)
            span = jnp.clip(hi - lo + 2, 2, None)   # +1 NULL slot, +1 range
            term = jnp.where(ok, d - lo + 1, 0)
            code = code * span + term
            prod = prod * span.astype(jnp.float64)
            gmins.append(lo)
            spans.append(span)
        self._flag("radix_overflow",
                   X.replicated_flag(prod > jnp.float64(2.0 ** 62)))
        codes = jnp.where(rows_ok, code, jnp.int64(-1))

        if src.sharded:
            pay, spec = _flatten_st(src)
            self._count("spmd_exchanges")
            self._count("spmd_exchange_bytes",
                        X.exchange_bytes(codes, pay, self.n_dev))
            codes, pay2 = X.exchange(codes, pay, self.n_dev)
            src = _unflatten_st(src, pay2, spec, codes >= 0)
            rows_ok = codes >= 0

        slot, slot_codes, overflow = PA.local_slots(codes, cap)
        self._flag("group_cap_overflow", X.replicated_flag(overflow))

        def combine(arr, is_minmax, is_min):
            if src.sharded:
                self._count("spmd_all_gather_bytes",
                            X.gather_bytes([arr], self.n_dev))
            return PA.gather_groups(arr, src.sharded)

        rows = combine(PA.slot_count(rows_ok, slot, cap), False, False)
        acols = self._slot_agg_columns(rel, src, slot, cap, combine, rows)
        gcodes = combine(slot_codes, False, False)
        kcols = _decode_runtime_keys(key_cols, gcodes, gmins, spans)
        names = [f.name for f in rel.schema]
        return _ST(Table(names, kcols + acols), rows > 0, sharded=False)


def evaluate_rex_local(expr, table: Table):
    from ..physical.rex.evaluate import evaluate_rex
    return evaluate_rex(expr, table)


def _flatten_st(st: _ST) -> Tuple[List[jax.Array], List[bool]]:
    """Flatten a traced table's arrays for an exchange ride: per column
    data (+ mask when present) then the validity mask; ``spec`` records
    mask presence for _unflatten_st."""
    pay: List[jax.Array] = []
    spec: List[bool] = []
    for c in st.table.columns:
        pay.append(c.data)
        spec.append(c.mask is not None)
        if c.mask is not None:
            pay.append(c.mask)
    pay.append(st.vmask())
    return pay, spec


def _unflatten_st(st: _ST, pay: List[jax.Array], spec: List[bool],
                  live: jax.Array) -> _ST:
    cols = []
    i = 0
    for c, has_mask in zip(st.table.columns, spec):
        data = pay[i]
        i += 1
        mask = None
        if has_mask:
            mask = pay[i]
            i += 1
        cols.append(Column(data, c.stype, mask, c.dictionary))
    valid = pay[i] & live
    return _ST(Table(list(st.table.names), cols), valid, st.sharded)


def _static_domains(key_cols) -> Optional[List[int]]:
    """Static per-key domain sizes when EVERY key is a dictionary-coded
    string or a bool (NULLs add one slot); None otherwise."""
    doms = []
    for c in key_cols:
        if c.stype.is_string and c.dictionary is not None:
            base = max(1, len(c.dictionary))
        elif c.data.dtype == jnp.bool_:
            base = 2
        else:
            return None
        doms.append(base + (1 if c.mask is not None else 0))
    return doms


def _decode_static_keys(key_cols, doms, G: int) -> List[Column]:
    """Slot index -> key columns, computed on HOST numpy and baked into the
    trace as constants (the domain is static)."""
    slots = np.arange(G, dtype=np.int64)
    cols = []
    rem = slots
    strides = []
    s = 1
    for dom in reversed(doms):
        strides.append(s)
        s *= dom
    strides = list(reversed(strides))
    for c, dom, stride in zip(key_cols, doms, strides):
        v = (slots // stride) % dom
        has_null = c.mask is not None
        if has_null:
            null = v == 0
            v = np.maximum(v - 1, 0)
        if c.stype.is_string:
            data = jnp.asarray(np.clip(v, 0, max(len(c.dictionary) - 1, 0))
                               .astype(np.int32))
        elif c.data.dtype == jnp.bool_:
            data = jnp.asarray(v.astype(bool))
        else:
            data = jnp.asarray(v.astype(np.int64)).astype(c.data.dtype)
        mask = jnp.asarray(~null) if has_null else None
        cols.append(Column(data, c.stype, mask, c.dictionary))
    return cols


def _decode_runtime_keys(key_cols, gcodes, gmins, spans) -> List[Column]:
    """Global slot codes -> key columns, in-trace (spans are traced)."""
    live = gcodes >= 0
    c0 = jnp.where(live, gcodes, 0)
    cols: List[Column] = []
    for col, lo, span in zip(reversed(key_cols), reversed(gmins),
                             reversed(spans)):
        v = c0 % span
        c0 = c0 // span
        null = v == 0
        data = (lo + jnp.maximum(v, 1) - 1)
        if col.stype.is_string:
            hi = max(len(col.dictionary) - 1, 0)
            data = jnp.clip(data, 0, hi).astype(jnp.int32)
        else:
            data = data.astype(col.data.dtype)
        mask = None
        if col.mask is not None:
            mask = (~null) & live
        cols.append(Column(data, col.stype, mask, col.dictionary))
    return list(reversed(cols))


# ---------------------------------------------------------------------------
# epilogue peel: terminal ORDER BY / LIMIT (+ projections above it) run on
# the HOST over the compacted result — a global sort inside the shard_map
# body would be a full repartition for rows the host materializes anyway
# ---------------------------------------------------------------------------

def _peel_epilogue(plan: RelNode) -> Tuple[RelNode, List[RelNode]]:
    """(core, epilogue): plan/optimizer.peel_root_epilogue — the terminal
    Project/Sort chain applies on the host, everything below runs sharded."""
    from ..plan.optimizer import peel_root_epilogue
    return peel_root_epilogue(plan)


def _apply_epilogue(table: Table, epilogue: List[RelNode]) -> Table:
    from ..ops.sort import apply_offset_limit, apply_sort

    for node in epilogue:
        if isinstance(node, LogicalSort):
            if node.collation:
                table = apply_sort(
                    table, [(c.index, c.ascending, c.effective_nulls_first)
                            for c in node.collation])
            if node.limit is not None or node.offset is not None:
                table = apply_offset_limit(table, node.offset, node.limit)
        else:
            cols = []
            for expr, f in zip(node.exprs, node.schema):
                v = evaluate_rex_local(expr, table)
                if isinstance(v, Scalar):
                    v = Column.from_scalar(v, table.num_rows)
                cols.append(v)
            table = Table([f.name for f in node.schema], cols)
    return table


# ---------------------------------------------------------------------------
# stage programs: build, cache, persist, execute
# ---------------------------------------------------------------------------

class _Fallback(Exception):
    """A runtime safety flag tripped — answers would be wrong; the caller
    falls back to the single-device path for this query (unless the stage
    runner can repair the plan, e.g. by flipping a join's build side)."""

    def __init__(self, tripped: List[str]):
        super().__init__(", ".join(tripped))
        self.tripped = list(tripped)


_prog_lock = threading.Lock()
_prog_cache: "OrderedDict[str, object]" = OrderedDict()  # digest -> compiled
_PROG_CACHE_CAP = 64


def _make_spmd_scan(node: RelNode, context) -> LogicalTableScan:
    from ..physical.stage_exec import _stage_table_name
    return LogicalTableScan(
        schema_name=_SPMD_SCHEMA,
        table_name=_stage_table_name(node, context),
        schema=[Field(f"c{i}", f.stype)
                for i, f in enumerate(node.schema)])


def _make_stage_body(stage_plan: RelNode, context, scans, n_dev: int,
                     meta: Dict):
    """The shard_map body: rebuild per-device local tables from the flat
    arg list (physical/identity._flatten_tables order), walk the stage
    plan, emit every output through the uniform P(ROW_AXIS) out-spec."""

    def body(*flat):
        scan_tables: Dict[Tuple[str, str], _ST] = {}
        i = 0
        for key, tbl, row_valid in scans:
            cols = []
            for c in tbl.columns:
                data = flat[i]
                i += 1
                mask = None
                if c.mask is not None:
                    mask = flat[i]
                    i += 1
                cols.append(Column(data, c.stype, mask, c.dictionary))
            valid = None
            if row_valid is not None:
                valid = flat[i]
                i += 1
            scan_tables[key] = _ST(Table(list(tbl.names), cols), valid,
                                   sharded=True)
        walker = _Walker(context, n_dev, scan_tables, meta)
        st = walker.walk(stage_plan)

        outs: List[jax.Array] = []
        if st.sharded:
            for c in st.table.columns:
                outs.append(c.data)
                if c.mask is not None:
                    outs.append(c.mask)
            outs.append(st.vmask())
            layout = {"sharded": True, "k": None, "kp": None}
        else:
            kp = None
            for c in st.table.columns:
                d, kp = X.shard_replicated(c.data, n_dev)
                outs.append(d)
                if c.mask is not None:
                    outs.append(X.shard_replicated(c.mask, n_dev)[0])
            v, kp = X.shard_replicated(st.vmask(), n_dev)
            outs.append(v)
            layout = {"sharded": False, "k": st.n, "kp": kp}
        if walker.flags:
            fl = jnp.stack([f.astype(jnp.int32).reshape(())
                            for _, f in walker.flags])
            outs.append(X.shard_replicated(fl, n_dev)[0])
        # out/layout/flags are a pure function of the (possibly edited)
        # decisions, so every trace re-records them: a dup-retry that flips
        # a join's build side may change the output sharding/layout
        meta["out"] = [(c.stype, c.mask is not None, c.dictionary)
                       for c in st.table.columns]
        meta["layout"] = layout
        meta["flags"] = [lbl for lbl, _ in walker.flags]
        meta["recorded"] = True
        return tuple(outs)

    return body


def _mesh_sig(mesh) -> str:
    return "x".join(f"{n}:{s}" for n, s in
                    zip(mesh.axis_names, mesh.devices.shape))


def _stage_digest(plan_fp: str, inputs_fp, mesh, meta: Dict) -> str:
    """Cross-process identity of one stage program: canonical plan (temp
    names -> position-stable placeholders, mirroring physical/identity.py), input
    layout, mesh signature, the recorded dispatch decisions (a different
    statistics state compiles its own variant instead of colliding), and
    the lowering knobs baked into the trace.  The program store digest
    additionally folds its runtime fingerprint (jax/device/devices)."""
    from ..runtime import program_store as _pstore

    mapping: Dict[str, str] = {}

    def sub(m):
        return mapping.setdefault(m.group(0), f"__spmd__.#{len(mapping)}")

    canon = _TEMP_NAME_RE.sub(sub, plan_fp)
    key = ("spmd1", canon, inputs_fp, _mesh_sig(mesh),
           repr(meta.get("decisions")),
           (_broadcast_rows_cap(), _group_cap(), _dense_cap()))
    return _pstore.get_store().digest(key)


def _pstore_load(digest: str, flat, n_outs: int):
    """Load + run this stage program from the persistent store (zero XLA
    compiles); None on miss/corruption — mirrors programs._pstore_attempt."""
    from ..runtime import program_store as _pstore

    store = _pstore.get_store()
    if not store.enabled():
        return None
    raw = store.load(digest)
    if raw is None:
        return None
    try:
        if (int(raw.get("v", 0)) != 1 or raw.get("kind") != "spmd"
                or int(raw["n_args"]) != len(flat)
                or int(raw["n_outs"]) != n_outs):
            raise ValueError("entry layout mismatch")
        fn = _pstore.load_program(raw, len(flat), n_outs)
        outs = fn(*flat)
    except (KeyboardInterrupt, SystemExit):
        raise
    except Exception as e:
        _tel.inc("program_store_errors")
        logger.warning("spmd store load failed (%s: %s); recompiling",
                       type(e).__name__, str(e)[:120])
        return None
    return fn, outs


def _pstore_save(digest: str, fn, n_args: int, n_outs: int) -> None:
    from ..runtime import program_store as _pstore

    store = _pstore.get_store()
    if not store.enabled():
        return
    try:
        program = _pstore.serialize_program(fn)
    except (KeyboardInterrupt, SystemExit):
        raise
    except Exception as e:
        _tel.inc("program_store_errors")
        logger.debug("spmd program serialize failed (%s); not persisted", e)
        return
    store.store(digest, {"v": 1, "kind": "spmd", **program,
                         "n_args": int(n_args), "n_outs": int(n_outs)})


def _annotate_stage_cost(fn) -> None:
    """Put the stage program's XLA cost prediction on the current span
    (EXPLAIN PROFILE and the query report's cost_err read it there).
    Env-gated before any profiler import; AOT/deserialized executables
    without a cost model just annotate nothing."""
    from ..physical.programs import _profile_on
    if not _profile_on():
        return
    try:
        from ..runtime import profiler as _prof
        cost = _prof.cost_summary(fn)
        if cost is not None:
            _tel.annotate(cost_flops=cost["flops"],
                          cost_bytes=cost["bytes"])
    except Exception:
        logger.debug("spmd cost capture failed", exc_info=True)


def _execute_stage_program(wrapped, flat, n_outs: int, digest: str,
                           counts: Dict[str, int]):
    """in-process cache -> persistent store -> AOT compile."""
    with _prog_lock:
        fn = _prog_cache.get(digest)
        if fn is not None:
            _prog_cache.move_to_end(digest)
    if fn is not None:
        _annotate_stage_cost(fn)
        return fn(*flat)

    hit = _pstore_load(digest, flat, n_outs)
    if hit is not None:
        fn, outs = hit
        counts["spmd_store_hits"] = counts.get("spmd_store_hits", 0) + 1
    else:
        fn = jax.jit(wrapped).lower(*flat).compile()
        counts["spmd_compiles"] = counts.get("spmd_compiles", 0) + 1
        _pstore_save(digest, fn, len(flat), n_outs)
        outs = fn(*flat)
    _annotate_stage_cost(fn)
    with _prog_lock:
        _prog_cache[digest] = fn
        while len(_prog_cache) > _PROG_CACHE_CAP:
            _prog_cache.popitem(last=False)
    return outs


def _parse_stage_outputs(stage_plan: RelNode, outs, meta: Dict):
    """(table, valid): reassemble global output arrays per the recorded
    layout and raise _Fallback if any runtime safety flag tripped."""
    outs = list(outs)
    if meta["flags"]:
        fl = np.asarray(outs.pop())[:len(meta["flags"])]
        tripped = [lbl for lbl, v in zip(meta["flags"], fl) if int(v) != 0]
        if tripped:
            raise _Fallback(tripped)
    layout = meta["layout"]
    k = layout["k"]
    sliced = not layout["sharded"]
    cols: List[Column] = []
    i = 0
    for (stype, has_mask, dictionary), f in zip(meta["out"],
                                                stage_plan.schema):
        data = outs[i]
        i += 1
        mask = None
        if has_mask:
            mask = outs[i]
            i += 1
        # replicated layouts keep their padded kp length (divisible by
        # n_dev, so a consumer stage can re-shard the temp); the validity
        # clamp below hides rows past k and _compact drops them at the root
        cols.append(Column(data, stype, mask, dictionary))
    valid = outs[i]
    if sliced:
        # the reassembled global arrays are kp long with pad garbage past
        # k: clamp validity so pad rows can never surface
        kp = layout["kp"]
        valid = jnp.where(jnp.arange(kp) < k, valid, False)
    table = Table([f.name for f in stage_plan.schema], cols)
    return table, valid


def _register_temp(context, name: str, table: Table, valid) -> None:
    from ..datacontainer import TableEntry

    if _SPMD_SCHEMA not in context.schema:
        context.create_schema(_SPMD_SCHEMA)
    table = Table([f"c{i}" for i in range(table.num_columns)],
                  list(table.columns))
    context.schema[_SPMD_SCHEMA].tables[name] = TableEntry(
        table=table, row_valid=valid)


def _unregister_temp(context, name: str) -> None:
    sch = context.schema.get(_SPMD_SCHEMA)
    if sch is not None:
        sch.tables.pop(name, None)


def _compact(table: Table, valid) -> Table:
    """Host-side compaction of the root stage output to its live rows."""
    idx = jnp.asarray(np.flatnonzero(np.asarray(valid)))
    cols = [Column(c.data[idx], c.stype,
                   None if c.mask is None else c.mask[idx], c.dictionary)
            for c in table.columns]
    return Table(list(table.names), cols)


def _run_stage(stage, context, mesh, counts: Dict[str, int]):
    """Execute one stage as a shard_map program; returns (table, valid,
    meta).  Raises Unsupported / identity.Unsupported / _Fallback."""
    from ..physical import identity as _I
    from ..physical.programs import _profile_on

    n_dev = int(mesh.devices.size)
    scans: list = []
    plan_fp = _I._fp_plan(stage.plan, context, scans)
    inputs_fp = _I._fp_inputs(scans)
    flat = _I._flatten_tables(scans)
    for a in flat:
        if a.shape[0] % n_dev:
            raise Unsupported(f"global length {a.shape[0]} not divisible "
                              f"by {n_dev} devices")

    meta: Dict = {"counts": {}, "decisions": []}
    flipped: set = set()
    while True:
        # a FRESH body closure per attempt: jax traces cache on function
        # identity, so re-tracing the same closure after a decision edit
        # would silently reuse the stale program
        body = _make_stage_body(stage.plan, context, scans, n_dev, meta)
        wrapped = shard_map(body, mesh=mesh, in_specs=P(ROW_AXIS),
                            out_specs=P(ROW_AXIS))
        # structure pass: fills meta (decisions, output descriptors,
        # flags) without paying an XLA compile
        out_shapes = jax.eval_shape(wrapped, *flat)
        n_outs = len(out_shapes)
        digest = _stage_digest(plan_fp, inputs_fp, mesh, meta)
        outs = _execute_stage_program(wrapped, flat, n_outs, digest, counts)
        try:
            table, valid = _parse_stage_outputs(stage.plan, outs, meta)
        except _Fallback as e:
            if not _flip_dup_joins(meta, e.tripped, flipped):
                raise
            counts["spmd_join_flips"] = (counts.get("spmd_join_flips", 0)
                                         + len(e.tripped))
            continue
        if valid is not None and _profile_on():
            # per-shard row counts -> skew ratio (max/mean): one host
            # fetch of the validity vector, paid only when profiling
            try:
                per = np.asarray(valid).reshape(n_dev, -1).sum(axis=1)
                mean = float(per.mean())
                if mean > 0:
                    meta["skew_ratio"] = round(float(per.max()) / mean, 3)
                    meta["shard_rows"] = [int(x) for x in per]
            except Exception:
                logger.debug("spmd skew probe failed", exc_info=True)
        return table, valid, meta


_DUP_FLAG_RE = re.compile(r"^dup_build_keys@(\d+)$")


def _flip_dup_joins(meta: Dict, tripped: List[str], flipped: set) -> bool:
    """Repair a dup_build_keys trip by flipping the offending joins' build
    side (probe-side duplicates are fine under sorted_probe; build-side
    ones would mean a many-to-many join, which we don't attempt).  True if
    EVERY tripped flag is such a join not yet flipped — the stage is then
    re-traced in replay mode against the edited decisions and recompiled
    under a new digest."""
    idxs = []
    for lbl in tripped:
        m = _DUP_FLAG_RE.match(lbl)
        if m is None or int(m.group(1)) in flipped:
            return False
        idxs.append(int(m.group(1)))
    for di in idxs:
        op, variant, info = meta["decisions"][di]
        info = dict(info,
                    build=("left" if info["build"] == "right" else "right"),
                    flip="dup_build_keys")
        meta["decisions"][di] = (op, variant, info)
        flipped.add(di)
        logger.info("spmd: dup build keys at join decision %d; retrying "
                    "with build=%s", di, info["build"])
    return True


def try_execute_spmd(plan: RelNode, context) -> Optional[Table]:
    """Execute ``plan`` sharded over the context's device mesh.

    Returns the result Table, or None when the plan is outside the SPMD
    envelope (``spmd_unsupported``) or a runtime safety flag tripped
    (``spmd_fallbacks``) — the caller then serves the query through the
    single-device compiled/eager path.  Never raises.
    """
    if not spmd_enabled(context):
        return None
    from ..physical import identity as _I
    from ..physical.stages import partition, stage_budget
    from ..runtime.statistics import record_choice

    mesh = context.mesh
    n_dev = int(mesh.devices.size)
    counts: Dict[str, int] = {}
    try:
        core, epilogue = _peel_epilogue(plan)
        _gate_plan(core)
        graph = partition(core, stage_budget(None),
                          lambda sub: _make_spmd_scan(sub, context))
    except (Unsupported, _I.Unsupported) as e:
        _tel.inc("spmd_unsupported")
        logger.debug("spmd: unsupported plan (%s)", e)
        return None
    except (KeyboardInterrupt, SystemExit):
        raise
    except Exception as e:  # pragma: no cover - gate must never fail a query
        _tel.inc("spmd_unsupported")
        logger.debug("spmd: gate error (%s: %s)", type(e).__name__, e)
        return None

    registered: List[str] = []
    metas: List[Dict] = []
    try:
        result = None
        for si, stage in enumerate(graph.stages):
            # one span per SPMD stage: the stage program's cost
            # annotations and the shard-skew probe land here, giving
            # EXPLAIN PROFILE its per-stage rows
            with _tel.span("spmd_stage", index=si):
                table, valid, meta = _run_stage(stage, context, mesh,
                                                counts)
                if meta.get("skew_ratio") is not None:
                    _tel.annotate(skew_ratio=meta["skew_ratio"],
                                  shard_rows=meta["shard_rows"])
            metas.append(meta)
            if stage.scan is not None:
                name = stage.scan.table_name
                _register_temp(context, name, table, valid)
                registered.append(name)
            else:
                result = _apply_epilogue(_compact(table, valid), epilogue)
    except (Unsupported, _I.Unsupported) as e:
        _tel.inc("spmd_unsupported")
        logger.debug("spmd: unsupported at trace (%s)", e)
        return None
    except _Fallback as e:
        _tel.inc("spmd_fallbacks")
        logger.info("spmd: runtime flag tripped (%s); falling back", e)
        return None
    except (KeyboardInterrupt, SystemExit):
        raise
    except Exception as e:
        _tel.inc("spmd_fallbacks")
        logger.warning("spmd: execution failed (%s: %s); falling back",
                       type(e).__name__, str(e)[:200])
        return None
    finally:
        for name in registered:
            _unregister_temp(context, name)

    # success: apply counters, dispatch choices and span telemetry ONCE
    _tel.inc("spmd_queries")
    _tel.inc("spmd_stages", len(graph.stages))
    for k, v in counts.items():
        _tel.inc(k, v)
    bytes_moved = 0
    gather_moved = 0
    psum_moved = 0
    skew = None
    for meta in metas:
        for k, v in meta["counts"].items():
            _tel.inc(k, v)
            if k == "spmd_exchange_bytes":
                bytes_moved += int(v)
            elif k == "spmd_all_gather_bytes":
                gather_moved += int(v)
            elif k == "spmd_psum_bytes":
                psum_moved += int(v)
        r = meta.get("skew_ratio")
        if r is not None:
            skew = max(skew, r) if skew is not None else r
        for op, variant, info in meta["decisions"]:
            try:
                record_choice(op, variant, **info)
            except Exception:  # pragma: no cover
                pass
    ann = dict(tier="spmd", spmd_devices=n_dev,
               spmd_stages=len(graph.stages),
               spmd_exchange_bytes=bytes_moved)
    # per-kind collective accounting + worst-stage shard skew annotate
    # ONLY here (the query report sums byte attrs over all spans, so the
    # per-stage spans deliberately do not repeat them)
    if gather_moved:
        ann["spmd_all_gather_bytes"] = gather_moved
    if psum_moved:
        ann["spmd_psum_bytes"] = psum_moved
    if skew is not None:
        ann["skew_ratio"] = skew
    _tel.annotate(**ann)
    if os.environ.get("DSQL_EVENTS", "0").strip() not in ("", "0"):
        try:
            from ..runtime import events as _ev
            _ev.publish("spmd.query", devices=n_dev,
                        stages=len(graph.stages),
                        exchange_bytes=bytes_moved,
                        skew_ratio=skew)
        except Exception:  # pragma: no cover - bus is advisory
            pass
    return result
