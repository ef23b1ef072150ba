"""The compiled tier: a query plan lowered to jitted programs with static
shapes, and the path a request takes through them.

The eager executor (physical/rel/executor.py) dispatches one XLA op at a
time; every dispatch is a host round trip and every data-dependent shape
(boolean compaction, ``jnp.unique``) a blocking sync.  This module is the
TPU-first answer (SURVEY §5, §7 "hard parts" item 2): a plan is traced
(``_Tracer``, ``_build``) into programs in which filters keep rows and flip
a validity mask, and every operator takes the formulation its static row
counts allow:

- an equi-join builds a hash table and probes it (``joins.hash_table``;
  kernels in ops/hashing.py) on every backend, or probes its build side's
  key column where a hint says that is in order (``joins.ordered``); under
  the TPU strategy only where its probe side has more than
  ``SORT_ROWS_MAX`` rows, and below that the merge join (``joins.merge``),
  whose sorts compile inside a set-up up to there and run faster on the
  chip;
- GROUP BY hashes its keys into group codes with a static capacity
  (``aggregates.hashed_aggregate``), takes the runs of a key column in load
  order for its groups (``run_aggregate``) or, over a statically enumerable
  domain, reduces on the MXU with no capacity at all
  (``static_domain_aggregate``);
- ORDER BY is one multi-key sort up to ``LEXSORT_ROWS_MAX`` rows under the
  TPU strategy and a single-key sort a key channel above it
  (``lexsort_by_passes``); off it a terminal ORDER BY runs on the host;
- a selective filter, a grouped aggregate's input straight over a join and
  a join's output that another join takes in are compacted to a learned
  capacity (``_maybe_compact``, ``_compact_eligible``).

The tracer CHOOSES a formulation (``_LogicalJoin``, ``_LogicalAggregate``,
``_maybe_compact``) and the modules beneath it lower one.  What XLA cannot
express statically (group-count overflow, non-unique build side, 64-bit
hash collision) surfaces through a flags vector, whose layout
``traced.ProgramFlags`` alone knows; the host recompiles with another
capacity or falls back to the eager executor.  Plan
shapes outside the subset (UDFs, host-bound string ops) are found at trace
time and cached as such.  Steady state is one dispatch and one fetch per
program, and fresh data of the same layout never recompiles.

A request's path is ``try_execute_compiled`` and ``_execute_single``, under
the spans ``lookup``, ``bind``, ``dispatch``, ``materialize``.  Each
decision beneath it has one module; this one imports them all and is
imported by none of them:

- ``identity``: what a program is (``program_key``), its digest, its name;
- ``traced``: the stream between two operators (``_VT``) and the ledger of
  what a trace owes the host (``ProgramFlags``: the flags vector, packed
  and read);
- ``joins``: the four formulations of an equi-join, each a function of its
  inputs and the ledger;
- ``aggregates``: the three of a grouped aggregate, and the masks of FILTER
  and DISTINCT;
- ``caps``: learned capacities and how a run's flags change them;
- ``programs``: a program's life (cache, in-flight claims, program store,
  quarantine and watchdog, compile retries, the degradation ladder);
- ``stage_exec``: a plan above the heavy-node budget (physical/stages.py)
  runs as a DAG of bounded programs;
- ``tiering``: a cold plan is answered eagerly while its programs compile;
- ``semijoin``: what only a SEMI / ANTI join needs (its scopes' names,
  ``NOT IN``'s three-valued logic, the residual exist-test);
- ``ops/hashing.py``: the hash kernels joins and group-bys lower to.
"""
from __future__ import annotations

import logging
import os
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops import groupby as G
from ..ops.hashing import _hash_parts, _join_key_parts, _keys_valid
from ..ops.kernels import (_INT64_MIN, canon_f64, compact_indices,
                           compact_slab_rows, comparable_data,
                           lexsort_by_passes, orderable_int64)
from ..ops.pallas_kernels import _strategy_on_tpu
from ..plan.nodes import (
    LogicalAggregate, LogicalFilter, LogicalJoin, LogicalProject, LogicalSort,
    LogicalTableScan, LogicalUnion, LogicalValues, LogicalWindow, RelNode,
)
from ..runtime import (faults as _faults, resilience as _res,
                       statistics as _stats, telemetry as _tel)
from ..table import Column, Scalar, Table
from . import (aggregates as _aggs, caps as _caps, joins as _joins,
               programs as _programs, tiering as _tiering)
from .caps import _NeedsRecompile, _check_flags, _learned_caps  # noqa: F401
from .identity import (Unsupported, _flatten_tables, _maybe_parameterize,
                       _program_name, program_key)
from .programs import _Compiled, _cache  # noqa: F401
from .rex.evaluate import evaluate_predicate, evaluate_rex
from .semijoin import _anti_keep, _residual_exist_test
from .stage_exec import _execute_stage_graph, _partition_plan
from .stages import heavy_count as _heavy_count, stage_budget
from .tiering import inflight_background_compiles  # noqa: F401
from .traced import _VT, ProgramFlags, read as _read_flags

logger = logging.getLogger(__name__)

# DEPRECATED read-through alias of the telemetry registry's counters
# (runtime/telemetry.py owns them; names and meanings are covered by its
# stability contract).  Reads and ``dict(stats)`` snapshots keep working;
# increments go through ``telemetry.inc`` (atomic), never ``stats[k] += 1``
# (an unlocked read-modify-write).
stats = _tel.CounterAlias()


#: The most rows at which a join traced for a TPU keeps its SORT
#: formulation (the merge join: sorts of one u64 key, the hash).  XLA:TPU's
#: compile time for one ``sort`` grows with its KEY channels after the x64
#: split and with its rows, not with its payload (AOT for a described v5e,
#: PR 27, seconds at 65 536 / 1.5 M / 6 M rows): one 32-bit key 15 / - /
#: 31-34; one u64 key 37 / 69 / 174; ``searchsorted(method="sort")``, two
#: such sorts, 328 at 6 M probes; the two keys (invalid, u64 hash) of the
#: group sort this strategy had until PR 27, 134 / - / 737.  A join sorts
#: three times and TPC-H Q3 / Q5 / Q10 join
#: two to five times at 1.5-6 M rows: 1127 s for Q3's program (PR 23), past
#: any set-up.  The scatter formulations (``joins.hash_table``,
#: ``aggregates.hashed_aggregate``) hold no sort and compile in seconds at
#: any size; on the chip they pay a serialized scatter per build row and a
#: gather per probe row instead: 186 ns a build row (Q3's trace on a v5e,
#: PR 27: 306.6 ms under ``dsql.join_build`` for 1.65 M rows) where the merge
#: join's build sort takes 20 (Q12: 30.2 ms for the same 1.5 M orders), so
#: Q12 under the hash table would cost some 320 ms for its 68 and the
#: merge join stays wherever its sorts compile.  The choice is made per
#: operator from the rows its sorts would see, and the limit is a budget
#: of compile time: at 262 144 rows a u64-key sort compiles in about 50 s
#: (between the 37 and the 69 above), a join's three in two and a half
#: minutes, one join's fair share of a 900 s set-up.  TPC-H Q12's and Q14's
#: probes (compacted to 65 536 / 262 144 rows) lie inside it; a capacity
#: class higher the join takes the hash table and pays on the chip, not
#: in the set-up.
SORT_ROWS_MAX = 1 << 18

#: The most 32-bit gathers a build row at which an ordered probe searches a
#: sparse key column in place of building a table (``_ordered_hint``).  On a
#: v5e (PERF.md section 6, PR 34) the insert it saves costs 198-211 ns a
#: build row (15 M rows: 2975 ms alone, 3167 in TPC-H Q12 at SF10) and a
#: gather at a probe row 7.3 ns where XLA places it well and 22.6 where it
#: does not (1 M rows out of 15 M int32 keys; a 64-bit one 26-41), so under
#: 200 / 23 of them the search cannot lose whatever the placement.
ORDERED_GATHERS_A_BUILD_ROW = 8

#: The most rows an ORDER BY is traced at as ONE multi-key sort
#: (``jnp.lexsort``) for a TPU.  Up to here it compiles in under a second
#: whatever its keys (TPC-H Q1's and Q12's ORDER BY over four to six rows);
#: above it a key channel costs minutes (169 s for Q3's ORDER BY at 16 384
#: rows), and the keys go through one single-key sort, a channel a pass
#: (``lexsort_by_passes``).
LEXSORT_ROWS_MAX = 1 << 10


#: The most rows a scan may hold for its plan's first arrival to be answered
#: by the eager tier while the program compiles.  ``RelExecutor`` holds
#: every intermediate of a scan whole (480 MB an f64 column of TPC-H SF10's
#: lineitem, beside 7.8 GB resident) and compiles a small program for each
#: size it meets (216 for the four shapes of the ``power`` mix, 1.5 s each
#: at SF1): above this the first arrival waits for its one program, as a
#: join-heavy plan's does (``_eager_bridge_sorts``).  SF1's lineitem is
#: 6 M rows, so nothing at SF1 moves.
EAGER_SCAN_ROWS_MAX = 1 << 24


def _sort_formulation(rows: int) -> bool:
    """True when a join whose sorts would see ``rows`` rows is traced in
    its sort formulation: the strategy is the TPU's and the sorts are small
    enough to compile inside a set-up (``SORT_ROWS_MAX``).  Off the TPU
    strategy nothing sorts."""
    return _strategy_on_tpu() and rows <= SORT_ROWS_MAX


# ---------------------------------------------------------------------------
# the tracer
# ---------------------------------------------------------------------------

class _Tracer:
    is_tracer = True   # routes RexScalarSubquery into traced_scalar_subquery

    def __init__(self, context, scan_tables: Dict[tuple, Table],
                 caps: Dict[str, int]):
        self.context = context
        self.scan_tables = scan_tables
        self.caps = caps
        # what the program tells the host: its flags and static counters
        self.flags = ProgramFlags()
        self._agg_counter = 0
        self._cmp_counter = 0
        self._join_site_counter = 0
        # id(node) -> its _VT (the plan outlives the trace, so an id stays
        # its node's): ``run`` traces a node once, and counts the
        # references it answered from here
        self._ran: Dict[int, _VT] = {}
        # id(join) -> "ord<j>" (``statistics.join_tags``, set by _build)
        self.join_tags: Dict[int, str] = {}
        # filter nodes (by id) eligible for learned-capacity compaction —
        # computed by _compact_eligible over the whole plan before tracing
        self.compact_ok: set = set()
        # id(RexParam) -> traced 0-d scalar for the plan's hoisted literals
        # (set by _build's fn from the trailing jit arguments); None on
        # unparameterized programs — evaluate._eval_param then reads the
        # node's carried value, which only happens outside a param trace
        self.param_values: Optional[Dict[int, jax.Array]] = None

    def traced_scalar_subquery(self, rex, outer_table: Table) -> Column:
        """Inline an uncorrelated scalar subquery into this trace.

        Only statically-1-row subplans qualify (an ungrouped aggregate, or
        projections over one); anything with a runtime row count can't
        deliver SQL's 0-rows->NULL / >1-rows->error semantics in-program.
        The single value broadcasts to the outer table's length so NULL-ness
        rides the validity mask like any other column."""
        vt = self.run(rex.plan)
        if vt.valid is not None or vt.n != 1:
            raise Unsupported("scalar subquery with runtime row count")
        self.flags.scalar_subqueries += 1
        col = vt.table.columns[0]
        n = outer_table.num_rows
        d0 = col.data[0]
        data = jnp.broadcast_to(d0, (n,))
        valid0 = None if col.mask is None else col.mask[0]
        if jnp.issubdtype(col.data.dtype, jnp.floating):
            # the eager path coerces a NaN subquery result to NULL
            # (evaluate.py _eval_scalar_subquery); match it
            notnan = ~jnp.isnan(d0)
            valid0 = notnan if valid0 is None else (valid0 & notnan)
        mask = None if valid0 is None else jnp.broadcast_to(valid0, (n,))
        return Column(data, col.stype, mask, col.dictionary)

    # -- dispatch ----------------------------------------------------------
    def run(self, rel: RelNode) -> _VT:
        """Lower ``rel``, once: a node the plan holds twice (a CTE read
        twice, ``shared.unify``) hands its second reference the first one's
        ``_VT``, so it has one ``agg*`` / ``cmp*`` site, one entry in every
        table keyed by ``id`` and one place in the program (both sides of
        TPC-H Q15's ``=`` are parts of one device array)."""
        vt = self._ran.get(id(rel))
        if vt is not None:
            self.flags.shared_subplans += 1
            return vt
        m = getattr(self, "_" + type(rel).__name__, None)
        if m is None:
            raise Unsupported(type(rel).__name__)
        # trace time only: every op this node lowers to carries the node's
        # type in its op_name, which is how a device trace names it
        with jax.named_scope("dsql." + type(rel).__name__):
            vt = self._ran[id(rel)] = m(rel)
        return vt

    # -- nodes -------------------------------------------------------------
    def _LogicalTableScan(self, rel: LogicalTableScan) -> _VT:
        t, valid = self.scan_tables[(rel.schema_name, rel.table_name)]
        want = [f.name for f in rel.schema]
        if t.names != want:
            t = t.limit_to(want)
        return _VT(t, valid, load_order=True)

    def _LogicalProject(self, rel: LogicalProject) -> _VT:
        src = self.run(rel.input)
        cols: List[Column] = []
        for rex, f in zip(rel.exprs, rel.schema):
            v = evaluate_rex(rex, src.table, self)
            if isinstance(v, Scalar):
                v = Column.from_scalar(v, src.n)
            cols.append(v)
        return src.carry(Table([f.name for f in rel.schema], cols), src.valid)

    def _LogicalFilter(self, rel: LogicalFilter) -> _VT:
        src = self.run(rel.input)
        mask = evaluate_predicate(rel.condition, src.table, self)
        if isinstance(mask, bool):
            if mask:
                return src
            return _VT(src.table, jnp.zeros(src.n, dtype=bool))
        valid = mask if src.valid is None else (mask & src.valid)
        out = src.carry(src.table, valid)
        if id(rel) in self.compact_ok:
            out = self._maybe_compact(out)
        return out

    def _maybe_compact(self, vt: _VT, after_join: bool = False) -> _VT:
        """Learned-capacity COMPACTION after a selective filter: static
        shapes mean a filter that drops 98% of lineitem still feeds all n
        masked rows into every join/sort above it — the single biggest
        steady-state tax vs the reference's dynamic partitions.  Compact to
        a power-of-2 capacity learned through the same flags/recompile
        machinery as group caps: the set rows' positions
        (``compact_indices``) and a gather per column, where every
        downstream sort then costs cap instead of n.  On a v5e under
        ``dsql.compact``, a request (TPC-H Q12 / Q14; PERF.md section 6):
        at n = 6.0 M (SF1, cap 65 536 / 262 144) 495 / 506 ms while the
        positions came from ``jnp.nonzero(size=cap)``, a scatter-add of
        all n rows, 13.1 / 23.9 ms from one sort of all n positions
        (PR 26), 3.4 / 16.5 ms since the sort runs inside slabs (PR 37);
        at n = 60 M (SF10, cap 1 048 576 / 2 097 152) 249 / 423 ms with
        the sort of all positions, 180 ms of each, and 83 / 261 ms with
        the slabs': what is left is the gathers, a column each.  Which of
        the two a site takes is ``kernels.compact_slab_rows``' to say,
        from n and the cap.  A learned cap >= n/2 disables the site
        (unselective filter).

        ``after_join``: the site is the output of a join that another join
        takes in (TPC-H Q5's joins three to five probed all six million
        lineitem rows with 15 %, then 0.6 % of them set).  Such sites stand
        in chains, and a site that overflows drops rows, so every count
        above it is too low for that round: default caps would cost a
        chain a recompile a site.  An unlearned one therefore only COUNTS
        (its cap is n), and ``_check_flags`` sizes every site of the chain
        from true counts in one round."""
        n = vt.n
        if after_join:
            # numbered apart and before the size is looked at: the sites
            # below decide this one's rows, and a site that came and went
            # with their caps would renumber the others between two rounds
            tag = f"cmpj{self._join_site_counter}"
            self._join_site_counter += 1
        if n < (1 << 16):
            return vt  # small inputs: gathers save nothing
        if not after_join:
            tag = f"cmp{self._cmp_counter}"
            self._cmp_counter += 1
        cap = self.caps.get(tag)
        if cap is None and after_join:
            self.flags.site(tag, n, False, n,
                            jnp.sum(vt.vmask(), dtype=jnp.int64))
            return vt
        if cap is None:
            cap = 1 << max(int((max(n // 4, 1) - 1)).bit_length(), 10)
        cap = min(cap, n)
        if cap * 2 >= n:
            return vt  # learned: not selective enough to pay the gathers
        with jax.named_scope("dsql.compact"):
            mask = vt.vmask()
            idx, count = compact_indices(mask, cap)
            row_valid = jnp.arange(cap) < count
            cols = [c.take(idx) for c in vt.table.columns]
        # count > cap rows were silently dropped: the flags check raises
        # _NeedsRecompile before any result materializes
        self.flags.site(tag, n, False, cap, count)
        return _VT(Table(list(vt.table.names), cols), row_valid,
                   weight=vt.weight, hash_joins=vt.hash_joins or after_join)

    def _LogicalValues(self, rel: LogicalValues) -> _VT:
        from .rel.executor import _values
        return _VT(_values(rel, None), None)

    def _LogicalAggregate(self, rel: LogicalAggregate) -> _VT:
        src = self.run(rel.input)
        n = src.n
        out_cols: List[Column] = []
        out_names = [f.name for f in rel.schema]

        if not rel.group_keys:
            for agg, f, col, fmask in _aggs.agg_inputs(rel, src, [],
                                                        self.flags):
                out_cols.append(G.whole_table_aggregate(
                    agg.op, col, fmask, f.stype, n))
            return _VT(Table(out_names, out_cols), None)

        key_cols = [src.table.columns[i] for i in rel.group_keys]
        static = _aggs.static_domain_aggregate(rel, src, key_cols,
                                               self.flags)
        if static is not None:
            return static

        if id(rel) in self.compact_ok:
            # the joins below left most rows unset (TPC-H Q3: 30 000 of
            # six million), and the group-by's kernels see every row, set
            # or not: 5.3 s of a 6.2 s Q3 on a v5e (PR 27).  Compact first,
            # to a learned capacity, as below a join
            src = self._maybe_compact(src)
            n = src.n
            key_cols = [src.table.columns[i] for i in rel.group_keys]

        tag = f"agg{self._agg_counter}"
        self._agg_counter += 1
        # learned or hinted; else counted at ingest (a key column of its
        # table, every row: ``statistics.counted_groups``); else the default
        cap = min(self.caps.get(tag) or _stats.counted_groups(
            rel, self.context) or _caps.DEFAULT_GROUP_CAP, n)

        # every row of a scan as loaded, grouped by one integer column that
        # the statistics say never decreases (the hint ``runs`` among the
        # capacities, 0 once a program refuted it): its runs are the groups
        key = key_cols[0]
        by_runs = bool(
            self.caps.get(_stats.RUN_GROUPS_TAG) and src.load_order
            and src.valid is None and len(key_cols) == 1
            and key.mask is None and not key.stype.is_string
            and jnp.issubdtype(key.data.dtype, jnp.integer)
            and all(a.op in G.RUN_AGGREGATE_OPS for a in rel.aggs)
            and _stats.grouped_by_runs(rel, self.context))
        # the dynamic-domain group-by: one scope on the device trace (beside
        # the static domain's dsql.groupby_limbs)
        with jax.named_scope("dsql.groupby_sorted"):
            if by_runs:
                return _aggs.run_aggregate(rel, src, key, cap, tag,
                                           self.flags)
            return _aggs.hashed_aggregate(rel, src, key_cols, cap, tag,
                                          self.flags)

    def _LogicalSort(self, rel: LogicalSort) -> _VT:
        src = self.run(rel.input)
        n = src.n
        valid = src.valid
        table = src.table
        need_compact = rel.offset is not None or rel.limit is not None
        if rel.collation or (need_compact and valid is not None):
            arrays = []
            for c in reversed(rel.collation):
                col = table.columns[c.index]
                raw = comparable_data(col)
                if jnp.issubdtype(raw.dtype, jnp.floating):
                    d = canon_f64(raw)
                    # NaN sorts last in BOTH directions (XLA/eager semantics:
                    # -NaN is still NaN) — the flag is never negated
                    nanflag = jnp.isnan(raw).astype(jnp.int8)
                    if not c.ascending:
                        d = -d
                    arrays.append(d)
                    arrays.append(nanflag)
                else:
                    d = orderable_int64(raw)
                    if not c.ascending:
                        # -INT64_MIN wraps; clamp before negating (merges the
                        # two most-negative keys — unobservable in practice)
                        d = -jnp.where(d == _INT64_MIN, _INT64_MIN + 1, d)
                    arrays.append(d)
                if col.mask is not None:
                    nullkey = (~col.mask).astype(jnp.int8)
                    if c.effective_nulls_first:
                        nullkey = -nullkey
                    arrays.append(nullkey)
            if valid is not None:
                arrays.append((~valid).astype(jnp.int8))  # valid rows first
            # one multi-key sort while it is small; above that its key
            # channels are what XLA:TPU does not compile in a set-up, and
            # the keys go through one single-key sort, a channel a pass
            if _strategy_on_tpu() and n > LEXSORT_ROWS_MAX:
                perm = lexsort_by_passes(arrays)
            else:
                perm = jnp.lexsort(arrays)
            table = table.take(perm)
            if valid is not None:
                count = jnp.sum(valid.astype(jnp.int64))
                valid = jnp.arange(n) < count
        start = rel.offset or 0
        stop = n if rel.limit is None else min(start + rel.limit, n)
        if start == 0 and stop == n:
            return _VT(table, valid)
        table = table.slice(start, stop)
        if valid is not None:
            count = jnp.sum(valid.astype(jnp.int64))
            valid = jnp.arange(stop - start) < (count - start)
        return _VT(table, valid)

    def _LogicalWindow(self, rel) -> _VT:
        from ..ops import window as W
        src = self.run(rel.input)
        names = list(src.table.names)
        cols = list(src.table.columns)
        for call in rel.calls:
            order = [(c.index, c.ascending, c.effective_nulls_first)
                     for c in call.order]
            col = W.compute_window(src.table, call.op, call.args,
                                   call.partition, order, call.frame,
                                   call.stype, row_valid=src.valid)
            cols.append(col)
            names.append(call.name)
        return _VT(Table(names, cols), src.valid)

    def _LogicalUnion(self, rel: LogicalUnion) -> _VT:
        from .rex.cast import cast_column
        parts = [self.run(i) for i in rel.inputs_]
        from ..ops.join import concat_columns
        out_names = [f.name for f in rel.schema]
        cols: List[Column] = []
        for j, f in enumerate(rel.schema):
            pieces = []
            for p in parts:
                c = p.table.columns[j]
                if c.stype.name != f.stype.name:
                    c = cast_column(c, f.stype)
                pieces.append(c)
            cols.append(concat_columns(pieces))
        valids = [p.vmask() for p in parts]
        valid = (None if all(p.valid is None for p in parts)
                 else jnp.concatenate(valids))
        out = _VT(Table(out_names, cols), valid)
        if rel.all:
            return out
        # UNION DISTINCT: keep first occurrence of each distinct row
        keep = _aggs.first_occurrence_keep(list(out.table.columns),
                                           out.valid, self.flags)
        return _VT(out.table, keep & out.vmask())

    def _LogicalJoin(self, rel: LogicalJoin) -> _VT:
        from .rel.executor import _and_rex, _extract_equi_keys
        left = self.run(rel.left)
        right = self.run(rel.right)
        equi, residual = _extract_equi_keys(rel)
        jt = rel.join_type
        if not equi:
            raise Unsupported("non-equi/cross join")

        lk = [k for k, _ in equi]
        rk = [k for _, k in equi]
        out_names = [f.name for f in rel.schema]

        if jt == "INNER":  # probe the bigger side (by pre-compaction weight)
            probe_is_left = left.weight >= right.weight
        else:
            probe_is_left = jt != "RIGHT"
        lk_cols = [left.table.columns[i] for i in lk]
        rk_cols = [right.table.columns[i] for i in rk]
        lparts, rparts = _join_key_parts(lk_cols, rk_cols)
        if probe_is_left:
            probe, build, pk_cols, bk_cols = left, right, lk_cols, rk_cols
            pparts, bparts = lparts, rparts
        else:
            probe, build, pk_cols, bk_cols = right, left, rk_cols, lk_cols
            pparts, bparts = rparts, lparts

        exist_test = None
        if residual and jt in ("SEMI", "ANTI"):
            # a single carried candidate can't decide a per-PAIR residual,
            # but one of the form  build.x OP probe.y  (OP comparison) only
            # needs per-key build aggregates: exists x<>y <=> cnt>0 and
            # (min!=y or max!=y); exists x<y <=> min<y; etc. (TPC-H Q21's
            # NOT EXISTS .. l3.l_suppkey <> l1.l_suppkey). Anything else —
            # or float operands, whose NaN comparison semantics the
            # min/max reduction can't reproduce — stays eager.
            exist_test = _residual_exist_test(rel, residual, probe.table,
                                              build.table)
            if exist_test is None:
                raise Unsupported("semi/anti join with general residual")

        pvalid = _keys_valid(pk_cols, probe.valid)
        bvalid = _keys_valid(bk_cols, build.valid)
        ph = _hash_parts(pparts, pvalid)
        bh = _hash_parts(bparts, bvalid)
        self.flags.join_rows += probe.n + build.n
        self.flags.semi_joins += jt in ("SEMI", "ANTI")

        # a side compacted at a join's output is small because the plan
        # chains joins under a hash-table join: were each join above to
        # take the sort formulation its rows now allow, the plan would pay
        # three more u64-key sorts a join at set-up (TPC-H Q5's and Q10's
        # last programs compile for a described v5e in 27 / 26 s with the
        # hash table above the sites and 72 / 73 s with the merge join,
        # PERF.md, PR 28), and on the device either is nearly free at
        # these sizes (build sides of 5 and 25 rows)
        hash_joins = probe.hash_joins or build.hash_joins
        sides = (jt, probe, build, pparts, bparts, pvalid, ph, bh)
        if _sort_formulation(probe.n) and not hash_joins:
            # the merge join: two of its three sorts see every probe row,
            # so the probe's rows decide (SORT_ROWS_MAX)
            if exist_test is None:
                match, gathered = _joins.merge(*sides, self.flags)
            else:
                match, gathered = _joins.merge_exists(*sides, exist_test,
                                                      self.flags)
        elif (ordered := self._ordered_hint(
                rel, probe_is_left, probe, build, bk_cols, bparts,
                exist_test)) is not None:
            # the build side's key column is its own index
            match, gathered = _joins.ordered(
                jt, probe, build, pparts[0][1], bparts[0][1], pvalid,
                *ordered, self.flags)
        else:
            # off the TPU strategy nothing sorts; under it above
            # SORT_ROWS_MAX probe rows the sorts are what does not compile
            match, gathered = _joins.hash_table(
                *sides, exist_test, self.caps.get(_stats.span_tag(
                    self._build_tag(rel, probe_is_left)), 0), self.flags)

        def _out(table: Table, valid) -> _VT:
            return _VT(table, valid, weight=probe.weight,
                       hash_joins=hash_joins)

        def _handed_on(table: Table, valid) -> _VT:
            if id(rel) in self.compact_ok:
                # another join takes this in, matched rows or not
                return self._maybe_compact(_out(table, valid),
                                           after_join=True)
            return _out(table, valid)

        if jt == "SEMI":
            return _handed_on(probe.table.with_names(out_names),
                              probe.vmask() & match)
        if jt == "ANTI":
            keep = _anti_keep(match, pvalid, bvalid, build.vmask(),
                              getattr(rel, "null_aware", False))
            return _out(probe.table.with_names(out_names),
                        probe.vmask() & keep)

        def _pairs(build_cols: List[Column]) -> Table:
            if probe_is_left:
                return Table(out_names,
                             list(probe.table.columns) + build_cols)
            return Table(out_names, build_cols + list(probe.table.columns))

        if residual:
            # ON-clause residual: evaluated on the candidate pair (real
            # probe values + the carried build candidate's values); where
            # the equi key already failed, the AND with match discards the
            # garbage verdict
            pred = evaluate_predicate(_and_rex(residual), _pairs(gathered),
                                      self)
            if isinstance(pred, bool):
                pred = jnp.full(probe.n, pred)
            match = match & pred

        if jt == "INNER":
            return _handed_on(_pairs(gathered), probe.vmask() & match)
        # LEFT/RIGHT: every (valid) probe row survives; the build side is
        # NULL wherever the full ON condition (equi + residual) failed
        gathered = [c.with_mask(c.valid_mask() & match) for c in gathered]
        return _out(_pairs(gathered), probe.valid)

    def _ordered_hint(self, rel, probe_is_left: bool, probe: _VT, build: _VT,
                      bk_cols: List[Column], bparts, exist_test):
        """(tag, level) where a hash-table join may probe its build side's
        key column in place of a table, else None.  The build side is a
        scan's rows in load order, its key one integer column without a
        mask, and the request's capacities carry a hint (``ord<j>l`` /
        ``ord<j>r``: ``statistics.ordered_probe_hints``, or a learned one)
        that the column increases strictly.  A dense column always pays: its
        probe is arithmetic.  A search pays where its gathers cost less
        than the inserts it saves, and the static row counts bound them:
        ``ceil(log2(nb))`` + 3 a probe row at the worst (evenly spread keys
        take 4 or 5, which only the data says), a 64-bit one counted as
        four.  TPC-H Q12 at SF10: 1 M compacted lineitem rows x 27 against
        ``ORDERED_GATHERS_A_BUILD_ROW`` x 15 M; its first-round program,
        whose probe side is still at the default cap (16.8 M rows), keeps
        the table."""
        key = bk_cols[0]
        if (exist_test is not None or not build.load_order
                or len(bparts) != 1 or key.mask is not None
                or key.stype.is_string
                or not jnp.issubdtype(bparts[0][1].dtype, jnp.integer)):
            return None
        tag = self._build_tag(rel, probe_is_left)
        level = self.caps.get(tag, 0)
        if not level:
            return None
        if level != _stats.ORDERED_DENSE:
            gathers = probe.n * ((build.n - 1).bit_length() + 3) \
                * (4 if level == _stats.ORDERED_WIDE else 1)
            if gathers >= ORDERED_GATHERS_A_BUILD_ROW * build.n:
                return None
        return tag, level

    def _build_tag(self, rel, probe_is_left: bool) -> str:
        """The tag of ``rel``'s build side (``ord<j>l`` / ``r``), or ""."""
        tag = self.join_tags.get(id(rel))
        return tag + ("r" if probe_is_left else "l") if tag else ""


# ---------------------------------------------------------------------------
# compile + execute
# ---------------------------------------------------------------------------

def _param_args(params) -> List[jax.Array]:
    """Bound-argument vector for a parameterized plan: one dtype-stable 0-d
    device scalar per hoisted literal, in FINGERPRINT order (``params`` is
    the list ``_fp_plan`` accumulated while serializing the plan — the
    ``P{i}`` positions in the key and these argument positions can never
    disagree).  The dtype comes from the declared SQL type, not the python
    value, so ``x > 5`` and ``x > 5000000000`` with the same declared type
    share a program while different declared types never do."""
    from ..types import physical_dtype
    return [jnp.asarray(p.value, dtype=physical_dtype(p.stype))
            for p in params]


def _build(plan: RelNode, context, scans, caps: Dict[str, int], key,
           origin=None, params=None):
    """Create the jitted program for this plan + input spec."""
    spec = []
    for skey, tbl, row_valid in scans:
        spec.append((skey, [(c.stype, c.mask is not None, c.dictionary)
                            for c in tbl.columns], tbl.names,
                     row_valid is not None))
    meta: dict = {}
    name = _program_name(plan, key[0])

    def fn(*dsql_input):
        # the parameters' name reaches the device trace too: the ops XLA
        # hangs on a program's parameters (on TPU, the f64 split of every
        # scanned column) read ``dsql_input[i]`` there, outside any scope
        flat = dsql_input
        i = 0
        tables: Dict[tuple, Tuple[Table, Optional[jax.Array]]] = {}
        for skey, colspec, names, has_valid in spec:
            cols = []
            for stype, has_mask, dictionary in colspec:
                data = flat[i]; i2 = i + 1
                mask = flat[i2] if has_mask else None
                i = i2 + 1 if has_mask else i2
                cols.append(Column(data, stype, mask, dictionary))
            valid = None
            if has_valid:
                valid = flat[i]; i += 1
            tables[skey] = (Table(names, cols), valid)
        tr = _Tracer(context, tables, caps)
        if params:
            # trailing args are the hoisted-literal scalars, in the same
            # order _fp_plan collected them; the rex evaluator resolves
            # each RexParam node to ITS traced scalar by node identity
            base = len(flat) - len(params)
            tr.param_values = {id(p): flat[base + j]
                               for j, p in enumerate(params)}
        if _strategy_on_tpu():
            # off the TPU strategy no operator sorts, and the hash kernels
            # cost by the rows set, not by the rows there
            tr.compact_ok = _compact_eligible(plan)
        tr.join_tags = _stats.join_tags(plan)
        out = tr.run(plan)
        n = out.n
        if out.valid is None:
            count = jnp.int64(n)
        else:
            count = jnp.sum(out.valid.astype(jnp.int64))
        meta["names"] = list(out.table.names)
        meta["cols"] = [(c.stype, c.mask is not None, c.dictionary)
                        for c in out.table.columns]
        meta["has_valid"] = out.valid is not None
        meta.update(tr.flags.meta())
        meta["n_out"] = n
        outs: List[jax.Array] = [tr.flags.pack(count)]
        for c in out.table.columns:
            outs.append(c.data)
            if c.mask is not None:
                outs.append(c.mask)
        if out.valid is not None:
            outs.append(out.valid)
        return tuple(outs)

    fn.__name__ = fn.__qualname__ = name
    return _Compiled(jax.jit(fn), name, spec, meta, dict(caps), key, origin)


SMALL_FETCH_BYTES = 8 << 20


def _compact_eligible(plan: RelNode) -> set:
    """ids of the nodes worth compacting at.  LogicalFilter: the TOPMOST
    filter of each filter chain with a SORT-SHAPED ancestor above — a join,
    window, or grouped aggregate, whose in-program sorts shrink with the row
    count.  A global aggregate is masked reductions only: compacting under
    it is pure gather overhead (TPC-H Q6 measured 0.15 s -> 0.61 s).
    LogicalAggregate: a grouped aggregate straight over a join (projects
    between them aside), whose INPUT is compacted where the aggregate has
    no static domain: a join hands on every probe row, matched or not.
    LogicalJoin: an INNER or SEMI join that another join takes in, on
    either side (projects and filters between them aside), whose OUTPUT is
    compacted for the same reason."""
    out: set = set()

    def walk(rel: RelNode, sorty_above: bool, parent_is_filter: bool,
             into_join: bool):
        is_filter = isinstance(rel, LogicalFilter)
        is_join = isinstance(rel, LogicalJoin)
        if is_filter and sorty_above and not parent_is_filter:
            out.add(id(rel))
        if is_join and into_join and rel.join_type in ("INNER", "SEMI"):
            out.add(id(rel))
        if isinstance(rel, LogicalAggregate) and rel.group_keys:
            below = rel.input
            while isinstance(below, LogicalProject):
                below = below.input
            if isinstance(below, LogicalJoin):
                out.add(id(rel))
        # global DISTINCT aggregates (except MIN/MAX, which are
        # dedup-invariant and skip ``aggregates.distinct_keep``) still
        # factorize every row in-program (``first_occurrence_keep``), so
        # they count
        sorty = sorty_above \
            or isinstance(rel, (LogicalJoin, LogicalWindow, LogicalSort)) \
            or (isinstance(rel, LogicalAggregate)
                and (rel.group_keys
                     or any(a.distinct and a.op not in ("MIN", "MAX")
                            for a in rel.aggs)))
        into_join = is_join or (
            into_join and isinstance(rel, (LogicalProject, LogicalFilter)))
        for i in rel.inputs:
            walk(i, sorty, is_filter, into_join)

    walk(plan, False, False, False)
    return out


def _compact_attrs(meta: dict) -> dict:
    """Whether a program compacts, and at what capacity: the ``cmp*`` sites
    live in it (a site whose learned cap says the filter is unselective
    leaves none, and one that only counts compacts nothing), how many of
    them find their rows inside slabs and not by a sort of all their input
    (``kernels.compact_slab_rows``: static, as everything here) and the
    largest of their caps; beside them the rows its joins take in, which is
    the work the sites between two joins remove, how many of their hash
    tables a ``span*`` hint sized (``joins.hash_table``), and what the
    program holds of subqueries: SEMI / ANTI joins, inlined scalar ones."""
    sites = [(n_rows, cap) for (n_rows, _, tag), cap in
             zip(meta["agg_sites"], meta["ngroup_caps"])
             if tag.startswith("cmp") and cap < n_rows]
    return {"compact_sites": len(sites),
            "compact_slab_sites": sum(
                compact_slab_rows(n_rows, cap) > 0 for n_rows, cap in sites),
            "compact_cap": max((cap for _, cap in sites), default=0),
            "join_rows": meta.get("join_rows", 0),
            "span_tables": meta.get("span_tables", 0),
            "semi_joins": meta.get("semi_joins", 0),
            "scalar_subqueries": meta.get("scalar_subqueries", 0),
            "shared_subplans": meta.get("shared_subplans", 0),
            "run_groupbys": meta.get("run_groupbys", 0)}


def _count_probes(meta: dict, direct_bits) -> None:
    """How the joins of the hash-table formulation probed, known when a
    program's flags are in.  ``hash_table_joins``: all of them, static.
    ``direct_probes``: those that addressed their build row directly,
    through a direct-addressed table (``fits`` is the data's:
    ``direct_bits``, one a table) or in a dense key column.
    ``ordered_probes``:
    those that built no table and probed the build side's key column, dense
    or searched; a searched one is neither direct nor looped."""
    tables = meta.get("hash_table_joins", 0)
    ordered = len(meta.get("ordered", ()))
    if not tables + ordered:
        return
    direct = int(direct_bits.sum()) if tables else 0
    dense = meta.get("ordered_dense", 0)
    _tel.annotate(hash_table_joins=tables + ordered,
                  direct_probes=direct + dense)
    _tel.inc("join_probes_direct", direct + dense)
    _tel.inc("join_probes_looped", tables - direct)
    if ordered:
        _tel.annotate(ordered_probes=ordered)
        _tel.inc("join_probes_ordered", ordered)


def _materialize(entry: _Compiled, outs) -> Table:
    _faults.maybe_fail("materialize")
    meta = entry.meta
    total_bytes = sum(int(getattr(o, "nbytes", 0)) for o in outs)
    small = total_bytes <= SMALL_FETCH_BYTES
    _tel.annotate(bytes=total_bytes, small_fetch=small)
    # small result: ONE blocking transfer for flags + all outputs, then
    # compact on host — each extra sync is a full device round trip, so
    # two-phase (flags, then data) costs double
    host = jax.device_get(list(outs)) if small else None
    flags = host[0] if small else np.asarray(outs[0])
    said = _read_flags(meta, flags)
    _caps._check_ordered(entry, flags)
    if said.eager:
        _tel.inc("fallbacks")
        return None
    _check_flags(entry, flags)
    _count_probes(meta, said.direct)
    if meta.get("run_groupbys"):
        _tel.inc("groupby_run_aggregates", meta["run_groupbys"])
    count = said.count
    cut = meta["has_valid"] and count < meta["n_out"]
    sel = np.nonzero(host[-1])[0] if small and cut else None
    idx = 1
    cols: List[Column] = []
    for stype, has_mask, dictionary in meta["cols"]:
        data = outs[idx]
        mask = outs[idx + 1] if has_mask else None
        if small:
            hdata = host[idx]
            hmask = host[idx + 1] if has_mask else None
            if sel is not None:
                # compaction changes the rows: host slices are authoritative
                # and the device copy is rebuilt lazily on upload
                hdata = hdata[sel]
                hmask = hmask[sel] if hmask is not None else None
                data = jnp.asarray(hdata)
                mask = None if hmask is None else jnp.asarray(hmask)
            cols.append(Column(data, stype, mask, dictionary,
                               host_cache=(hdata, hmask)))
        else:
            cols.append(Column(data, stype, mask, dictionary))
        idx += 2 if has_mask else 1
    t = Table(meta["names"], cols)
    if cut and not small:
        t = t.take(jnp.nonzero(outs[idx], size=count)[0])
    return t


def _eager_bridge_sorts(plan: RelNode, context, on_tpu: bool) -> bool:
    """True where the eager tier is the slower way over a compile: under
    the TPU strategy, a plan with a join both of whose sides scan more than
    ``SORT_ROWS_MAX`` rows, or with a scan of more than
    ``EAGER_SCAN_ROWS_MAX``.  The eager join (``ops/join.py``) has the sort
    formulation only, an ``argsort`` of the build side and a
    ``searchsorted`` per probe row, each a program of its own that XLA:TPU
    compiles for minutes at these sizes (``SORT_ROWS_MAX`` has the table),
    where the plan's own program takes the hash-table join and compiles in
    one or two.  On a v5e from an empty XLA cache (PR 27): TPC-H Q3 / Q5 /
    Q10 answered by the eager tier after 424 / 483 / 708 s (158-279 small
    programs a shape), their whole-plan programs ready after 105 / 56 /
    106 s.  Filters are not counted: a scan's rows are what the plan
    shows before it runs."""
    if not on_tpu:
        return False

    def rows(rel: RelNode) -> int:
        if isinstance(rel, LogicalTableScan):
            entry = context.catalog_entry(rel.schema_name, rel.table_name)
            return 0 if entry.table is None else entry.table.num_rows
        return max((rows(i) for i in rel.inputs), default=0)

    def walk(rel: RelNode) -> bool:
        if isinstance(rel, LogicalJoin) \
                and min(rows(rel.left), rows(rel.right)) > SORT_ROWS_MAX:
            return True
        return any(walk(i) for i in rel.inputs)

    return rows(plan) > EAGER_SCAN_ROWS_MAX or walk(plan)


def tier_probe(plan: RelNode, context) -> str:
    """Which tier would answer this plan NOW (``tiering.tier_probe``), with
    the tracer's word on where the eager tier is the slower way."""
    return _tiering.tier_probe(plan, context, _eager_bridge_sorts)


def _keyed(plan: RelNode, context):
    """``program_key``, or None (counted) for a plan that does not compile."""
    try:
        return program_key(plan, context)
    except Unsupported as e:
        logger.debug("not compilable: %s", e)
        _tel.inc("unsupported")
        return None


def try_execute_compiled(plan: RelNode, context,
                         _split_limit: Optional[int] = None
                         ) -> Optional[Table]:
    """Execute via the compiled pipeline; None => caller should run eager.

    Plans within the heavy-node budget compile as ONE program (the common
    case).  Larger plans run as a stage graph of bounded programs —
    ``_split_limit`` overrides the budget (the degradation ladder's
    whole→stages rung and tests use it; cache keys line up with an
    explicit ``DSQL_STAGE_HEAVY`` run at the same value).
    """
    if os.environ.get("DSQL_COMPILE", "1") == "0":
        return None
    _res.check("compile_entry")
    # parameterized plan identity: eligible literals hoist into runtime
    # arguments here, at the single entry of the compiled pipeline, so
    # every fingerprint below (whole-plan, stage subplans, program-store
    # digests, EWMA keys) sees the SHAPE while the values ride as trailing
    # jit args.  The eager/SPMD/result-cache paths never see this plan —
    # they key on values, which stays correct.
    with _tel.span("lookup"):
        plan = _maybe_parameterize(plan)
        pk = _keyed(plan, context)
        if pk is None:
            return None
        budget_override = _split_limit
        heavy = _heavy_count(plan)
        if budget_override is None and heavy > 1:
            budget_override = _caps.split_hint(pk.key)
        budget = stage_budget(budget_override)
        # tiered execution: a cold plan answers on the eager tier NOW while
        # its programs compile in the background; warm (or decided) plans
        # fall through to the normal compiled path
        serve_eager = _tiering._tier_serve_eager(
            plan, context, pk.key, budget, _split_limit,
            try_execute_compiled, _eager_bridge_sorts)
    if serve_eager:
        _tel.inc("served_eager_while_compiling")
        _tel.annotate(tier="eager-compiling")
        return None
    if heavy > budget:
        graph = _partition_plan(plan, budget, context)
        if len(graph.stages) > 1:
            return _execute_stage_graph(graph, context, pk.key[0],
                                        _split_limit, _execute_single)
        # degenerate: nothing cuttable (one oversized node) — run whole
    return _execute_single(plan, context, pk.key[0], _split_limit)


def _execute_single(plan: RelNode, context, query_fp: str,
                    split_limit: Optional[int] = None,
                    in_stage: bool = False) -> Optional[Table]:
    """Trace/compile/run ONE bounded program (a whole small plan or one
    stage of a graph); None => eager.  ``query_fp`` is the ROOT query's
    plan fingerprint (``programs.note_hit`` counts cross-query reuse by
    it).  What runs on every request is here, under its spans; what
    happens once a program is ``programs.obtain``."""
    # lookup: from the plan to the program's key and capacities; the cache
    # probe in the loop below is the same phase (phases sum by span name)
    with _tel.span("lookup"):
        pk = _keyed(plan, context)
        if pk is None:
            return None
        runtime_key = (pk.key, tuple(t.uid for _, t, _ in pk.scans))
        if _programs.runtime_exiled(runtime_key):
            _tel.inc("fallbacks")
            return None
        caps = _caps.starting_caps(pk, context)
    try_store = True  # one persistent-store attempt per call, tops
    # why this round's program is obtained, should it not be cached, and
    # the caps that changed for it (``programs.obtain``'s ``compile`` span)
    cause, changed = "first" if split_limit is None else "split", ""
    for round_ in range(8):  # capacity-escalation bound
        _res.check("execute")
        key = (pk.key, tuple(sorted(caps.items())))
        with _tel.span("lookup", params=len(pk.params)):
            entry, claim = _programs.lookup(key)
        if entry is _programs._UNSUPPORTED:
            _tel.inc("unsupported")
            return None
        with _tel.span("bind", params=len(pk.params)):
            flat = _flatten_tables(pk.scans)
            h2d = 0
            if pk.params:
                # bound-argument vector: the hoisted literals, after the
                # table arrays — arity and treedef stay consistent
                # everywhere flat flows (jit call, AOT lower, store n_args,
                # store replay)
                bound = _param_args(pk.params)
                h2d = sum(int(a.nbytes) for a in bound)
                flat = flat + bound
            _tel.annotate(args=len(flat), h2d_bytes=h2d)
        compiled = None  # the ``compile`` span's record, if this round had one
        if entry is None:
            got = _programs.obtain(
                pk, key, caps, claim, flat,
                lambda: _build(pk.plan, context, pk.scans, caps, key,
                               origin=query_fp, params=pk.params),
                query_fp=query_fp, in_stage=in_stage,
                split_limit=split_limit, try_store=try_store,
                why={"round": round_, "cause": cause, "caps": changed})
            try_store = False
            if got is _programs.EAGER:
                return None
            if got is _programs.STAGES:
                # ``plan``, not ``pk.plan``: the ORDER BY a host sort was
                # to apply goes with it
                return try_execute_compiled(plan, context, _split_limit=1)
            entry, outs, caps, compiled = got
        else:
            _programs.note_hit(entry, key, pk, query_fp, in_stage)
            # asynchronous: the span is the host's cost of launching the
            # program; the wait for the device is inside materialize
            with _tel.span("dispatch", program=entry.name,
                           **_compact_attrs(entry.meta),
                           **entry.meta.get("limb_rows", {})):
                outs = entry.fn(*flat)
        try:
            with (_tel.span("materialize") if compiled is None
                  else _tel.first_run_span(compiled)):
                result = _res.retry_transient(
                    lambda: _materialize(entry, outs),
                    site="materialize",
                    passthrough=(_NeedsRecompile,))
        except _NeedsRecompile as r:
            _tel.inc("recompiles")
            _tel.inc(_tel.RECOMPILE_COUNTERS[r.reason])
            cause, changed = r.reason, _caps.changed(entry, r.caps)
            caps = r.caps
            _caps._learned_caps_put(pk.key, caps)
            continue
        except _res.TransientError as e:
            # host decode failed even after retries: one rung down — the
            # eager executor recomputes from the source tables
            _tel.inc("degradations")
            _tel.annotate(degraded_to="eager")
            if os.environ.get("DSQL_EAGER_FALLBACK", "1") == "0":
                raise
            logger.warning("materialize failed (%s); using eager executor",
                           str(e)[:200])
            return None
        if result is None:
            _programs.exile_runtime(runtime_key)
        elif pk.host_sort is not None:
            from ..ops import sort as S
            if pk.host_sort.collation:
                keys = [(c.index, c.ascending, c.effective_nulls_first)
                        for c in pk.host_sort.collation]
                result = S.apply_sort(result, keys)
            result = S.apply_offset_limit(result, pk.host_sort.offset,
                                          pk.host_sort.limit)
        return result
    return None
