"""Stage-graph partitioning: bound the size of every compiled program.

XLA:TPU compile time grows superlinearly with the number of fused
join/aggregate pipelines in one program (~50 s at 2 heavy nodes, ~400 s at
6, never finishes at 8-9: measured before the v5e bring-up).  This module
partitions a logical plan into a DAG of **stages**, each holding at most
``budget`` heavy nodes; the compiled executor (physical/stage_exec.py)
traces and jits every stage as its own program, materializing stage outputs
into padded capacity-class temp tables between them.

What was measured for a v5e since (PR 27; AOT for a described chip, the
chip's host compiles 2-3x slower): those minutes were the heavy nodes'
SORTS, whose compile time is in their key channels and rows
(``compiled.SORT_ROWS_MAX`` has the table: 174 s for one u64-keyed sort at
six million rows, 737 s with a second key), not the number of nodes.  With
the sorts of big operators gone (``compiled._sort_formulation``) TPC-H Q3
(3 heavy nodes), Q10 (4) and Q5 (6, this module's whole budget) compile as
ONE program each in 75 / 88 / 94 s at SF1 shapes, against 1127 s for Q3
before (PR 23): the budget of 6 stands, and no TPC-H shape of the chip
benchmark is cut into stages.

The partitioner is a pure bottom-up greedy walk and therefore
**deterministic** and **ancestor-independent**: the cuts made inside a
subtree depend only on that subtree, so two queries sharing a subplan
produce byte-identical stage plans for the shared part — their stage
programs share one cache entry (the cross-query reuse the compiled
executor's ``stats["cross_query_hits"]`` counter observes).

Heavy-node weights mirror the compile-cost model the old binary splitter
used: joins, grouped aggregates and windows weigh 1; a SEMI/ANTI join with
a non-equi residual lowers through the payload exist-test formulation and
weighs 2.  A single node can therefore exceed a budget of 1 — the bound
every program actually satisfies is ``max(budget, max node weight)``.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Tuple

from ..plan.nodes import (LogicalAggregate, LogicalJoin, LogicalTableScan,
                          LogicalWindow, RelNode)

#: Heavy-node budget per compiled program.  The default sits at the
#: compile-time knee measured then (tens of seconds per program,
#: never minutes), and holds for a v5e: TPC-H Q5, six heavy nodes at SF1
#: shapes, is one program of 94 s (module docstring).  Override with
#: ``DSQL_STAGE_HEAVY`` (or the legacy ``DSQL_SPLIT_HEAVY``, kept for
#: compatibility with existing bench configs and learned "__split__" hints).
DEFAULT_STAGE_HEAVY = 6


def stage_budget(override: Optional[int] = None) -> int:
    """The heavy-node budget: explicit override > env knobs > default."""
    import os

    if override is not None:
        return max(1, int(override))
    for var in ("DSQL_STAGE_HEAVY", "DSQL_SPLIT_HEAVY"):
        v = os.environ.get(var)
        if v:
            return max(1, int(v))
    return DEFAULT_STAGE_HEAVY


def node_weight(rel: RelNode) -> int:
    """Compile-cost weight of ONE node (its subtree excluded)."""
    if isinstance(rel, LogicalJoin):
        # SEMI/ANTI with a non-equi residual lower through the payload
        # exist-test formulation whose compile cost dwarfs a plain
        # equi-join — TPC-H Q21 (two of them + two joins) SIGKILLed the
        # TPU compile helper as one program.  Plain equi SEMI/ANTI
        # (Q4/Q20) compile like ordinary joins and keep weight 1.  The
        # residual test is the SAME decomposition the lowering uses
        # (_extract_equi_keys), so heuristic and lowering cannot drift.
        if rel.join_type in ("SEMI", "ANTI") and rel.condition is not None:
            from .rel.executor import _extract_equi_keys
            _, residual = _extract_equi_keys(rel)
            if residual:
                return 2
        return 1
    if isinstance(rel, (LogicalAggregate, LogicalWindow)):
        return 1
    return 0


def heavy_count(rel: RelNode) -> int:
    """Total heavy weight of a subtree (the old compiled._heavy_count)."""
    return node_weight(rel) + sum(heavy_count(i) for i in rel.inputs)


@dataclass
class Stage:
    """One compiled program's plan plus its position in the DAG.

    ``plan`` is the stage subtree with deeper cuts replaced by boundary
    scans; ``scan`` is the boundary node CONSUMERS of this stage read
    through (None for the root stage, whose output is the query result);
    ``deps`` are indices into ``StageGraph.stages`` of the stages whose
    outputs this stage scans.

    The boundary scan's NAME is a content digest of the producing subtree
    (canonical shape + scanned-table uids, physical/stage_exec.py
    ``_stage_table_name``) and doubles as the stage output's **subplan
    result-cache key** (runtime/result_cache.py): equal names imply equal
    data, so an overlapping query sharing this subtree may replay the
    materialized output instead of re-executing the stage.
    """

    plan: RelNode
    deps: Tuple[int, ...]
    heavy: int
    scan: Optional[RelNode] = None
    #: statistics-estimated output rows (annotate_stats; None = unknown)
    est_rows: Optional[int] = None


@dataclass
class StageGraph:
    """Stages in topological order (every dep precedes its consumer);
    the last stage is the root and produces the query result."""

    stages: List[Stage]

    @property
    def root(self) -> Stage:
        return self.stages[-1]


def partition(plan: RelNode, budget: int,
              make_scan: Callable[[RelNode], RelNode]) -> StageGraph:
    """Cut ``plan`` into a StageGraph of stages of <= ``budget`` heavy nodes.

    ``make_scan(subtree)`` must return the boundary scan node consumers
    read the subtree's materialized output through (the compiled executor
    passes a ``__split__``-schema table scan named by a content digest of
    the subtree, which is what makes shared subtrees collide into shared
    stage programs across queries).

    Greedy bottom-up: children partition first; at each node, whole child
    subtrees are cut (largest heavy count first, index order on ties) until
    the enclosing count fits the budget.  Cuts never target weight-0
    subtrees — a pure scan/project chain compiles for free and cutting it
    would only pay a materialization round trip.
    """
    budget = max(1, int(budget))
    stages: List[Stage] = []
    scan_stage: Dict[int, int] = {}  # id(boundary scan node) -> stage index

    def stage_deps(rel: RelNode) -> Tuple[int, ...]:
        out: List[int] = []

        def w(r: RelNode) -> None:
            si = scan_stage.get(id(r))
            if si is not None:
                out.append(si)
                return  # a boundary scan is a leaf of THIS stage
            for i in r.inputs:
                w(i)

        w(rel)
        return tuple(dict.fromkeys(out))

    def cut(sub: RelNode, heavy: int) -> RelNode:
        scan = make_scan(sub)
        stages.append(Stage(plan=sub, deps=stage_deps(sub), heavy=heavy,
                            scan=scan))
        scan_stage[id(scan)] = len(stages) - 1
        return scan

    def walk(rel: RelNode) -> Tuple[RelNode, int]:
        kids = [walk(i) for i in rel.inputs]
        total = node_weight(rel) + sum(h for _, h in kids)
        if total > budget and kids:
            order = sorted(range(len(kids)), key=lambda j: (-kids[j][1], j))
            for j in order:
                if total <= budget:
                    break
                sub, h = kids[j]
                if h <= 0:
                    continue  # cutting free subtrees buys nothing
                kids[j] = (cut(sub, h), 0)
                total -= h
        if kids:
            rel = rel.with_inputs([k for k, _ in kids])
        return rel, total

    root_plan, root_heavy = walk(plan)
    stages.append(Stage(plan=root_plan, deps=stage_deps(root_plan),
                        heavy=root_heavy, scan=None))
    return StageGraph(stages)


def annotate_stats(graph: StageGraph, context) -> None:
    """Attach statistics-estimated output rows to every stage
    (runtime/statistics.py — filter selectivity from ingest min/max plus
    join/aggregate cardinality rules).  The estimate rides along to the
    stage spans and the flight recorder so padded-capacity waste
    (``stage_capacity`` vs ``stage_est_rows``) is visible before the
    first run ever measures it; unknown stays None and costs nothing.
    No-op when adaptive selection is off (DSQL_ADAPTIVE=0)."""
    from ..runtime import statistics as _stats

    if context is None or not _stats.adaptive_enabled():
        return
    for st in graph.stages:
        try:
            est = _stats.estimate_rows(st.plan, context)
        except Exception:
            est = None
        if est is not None:
            st.est_rows = int(est)
