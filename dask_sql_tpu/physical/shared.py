"""The subtrees a plan holds more than once, found and made one node.

The text writes a CTE once and reads it twice; the binder and the optimizer
hand on two equal subtrees (TPC-H Q15's ``revenue0``, once joined to supplier
and once below ``= (SELECT MAX(total_revenue) FROM revenue0)``), and whatever
walks the plan as a tree does the CTE's work twice.  Both tiers ask here:

- ``read_twice`` is the finder: the aggregates and joins with one canonical
  text by VALUE (``result_cache.canonical_plan``: literals, scalar
  subqueries' bodies included), nothing volatile.  The eager executor keeps
  a memo by it (``rel/executor.py``).
- ``unify`` replaces every copy by the first copy's node OBJECT, inside
  bodies too, so the plan is the DAG the text meant.  The compiled tier
  does that before it hoists literals (``identity._maybe_parameterize``),
  and the passes after it see one node: one set of ``RexParam`` slots
  (``plan/parameterize.py``), one serialization (``identity._fp_plan``), one
  trace (``compiled._Tracer.run``).  Two copies that differ in a literal are
  not equal by value and stay two: nothing rests on two slots happening to
  hold one value.

Lives under ``physical/`` because ``plan/`` must not import ``runtime/``.
"""
from __future__ import annotations

import copy
from typing import Dict, List, Tuple

from ..plan.nodes import (LogicalAggregate, LogicalJoin, LogicalTableScan,
                          RelNode, RexNode, RexScalarSubquery)


def _rexes(rel: RelNode) -> tuple:
    """The expressions a node holds: a project's, a filter's or a join's."""
    cond = getattr(rel, "condition", None)
    return (*getattr(rel, "exprs", ()), *(() if cond is None else (cond,)))


def read_twice(plan: RelNode) -> Dict[int, str]:
    """id(node) -> canonical text of the aggregates and joins whose subtree
    ``plan`` holds more than once.  A subtree can only repeat where one
    table is scanned twice, so a plan whose scans all differ (every flat
    ``SELECT``) leaves after one walk and serializes nothing.  Nothing
    volatile is shared.  A node that is one object reached twice is one
    subtree: a unified plan comes back empty."""
    from ..runtime.result_cache import canonical_plan

    found: List[RelNode] = []
    scans: List[Tuple[str, str]] = []
    seen: set = set()

    def of_rex(rex: RexNode) -> None:
        if isinstance(rex, RexScalarSubquery):
            walk(rex.plan)
        for o in getattr(rex, "operands", ()):
            of_rex(o)

    def walk(rel: RelNode) -> None:
        if id(rel) in seen:
            return
        seen.add(id(rel))
        if isinstance(rel, LogicalTableScan):
            scans.append((rel.schema_name, rel.table_name))
        elif isinstance(rel, (LogicalAggregate, LogicalJoin)):
            found.append(rel)
        for rex in _rexes(rel):
            of_rex(rex)
        for i in rel.inputs:
            walk(i)

    walk(plan)
    if len(found) < 2 or len(set(scans)) == len(scans):
        return {}
    texts: Dict[str, List[RelNode]] = {}
    for rel in found:
        text, volatile, _ = canonical_plan(rel)
        if not volatile:
            texts.setdefault(text, []).append(rel)
    return {id(rel): text for text, rels in texts.items() if len(rels) > 1
            for rel in rels}


def unify(plan: RelNode) -> Tuple[RelNode, int]:
    """(``plan`` with every copy of a repeated subtree replaced by the first
    copy's node object, how many references were replaced).  The nodes
    above a replaced one are copies; the caller's plan is untouched, and a
    plan without a repeat comes back as the object it went in."""
    twice = read_twice(plan)
    if not twice:
        return plan, 0
    first: Dict[str, RelNode] = {}
    replaced = 0

    def of_rex(rex: RexNode) -> RexNode:
        if isinstance(rex, RexScalarSubquery):
            body = of_rel(rex.plan)
            return rex if body is rex.plan else RexScalarSubquery(body,
                                                                  rex.stype)
        ops = getattr(rex, "operands", None)
        if not ops:
            return rex
        new = [of_rex(o) for o in ops]
        if all(n is o for n, o in zip(new, ops)):
            return rex
        out = copy.copy(rex)
        out.operands = new
        return out

    def of_rel(rel: RelNode) -> RelNode:
        nonlocal replaced
        text = twice.get(id(rel))
        if text in first:
            replaced += first[text] is not rel
            return first[text]
        kids = [of_rel(i) for i in rel.inputs]
        out = rel
        if any(k is not i for k, i in zip(kids, rel.inputs)):
            out = rel.with_inputs(kids)
        exprs = getattr(rel, "exprs", None)
        cond = getattr(rel, "condition", None)
        if exprs is not None:
            new = [of_rex(e) for e in exprs]
            if any(n is not o for n, o in zip(new, exprs)):
                out = copy.copy(out)
                out.exprs = new
        if cond is not None:
            new = of_rex(cond)
            if new is not cond:
                out = copy.copy(out)
                out.condition = new
        if text is not None:
            first[text] = out
        return out

    return of_rel(plan), replaced
