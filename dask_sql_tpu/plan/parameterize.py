"""Parameterized plan identity: hoist literals into runtime arguments.

The dominant production traffic pattern — the same query shape with
different constants — used to defeat every cache the engine has: each
literal minted a fresh canonical-plan fingerprint, so repeat arrivals
missed the program cache, the result cache AND the cross-process program
store, and paid the full XLA compile wall every time.  "Fine-Tuning Data
Structures" (PAPERS.md) frames the right split: specialize on STRUCTURE,
parameterize on VALUE.

This pass walks the OPTIMIZED plan and replaces eligible ``RexLiteral``
nodes with ``RexParam`` nodes.  A param fingerprints by position and type
only (``compiled._fp_rex`` emits ``P{i}:{TYPE}``), so every literal
variant of a shape shares one compiled program; the value rides as a
dtype-stable scalar jit argument appended after the table arrays.

Eligibility is deliberately narrow (v1):

- the literal is a DIRECT operand of a binary comparison
  (``= <> != < <= > >=``) whose other operand subtree contains at least
  one column reference — this guarantees the comparison broadcasts
  against a Column and never hits the both-scalar host branch
  (``ops.comparison``'s ``bool(fn(da, db))``), which would concretize a
  traced value;
- the literal's physical representation is numeric and non-NULL
  (integers, floats, DATE/TIMESTAMP/TIME micros/days).  Strings stay
  specialized: dictionary codes are resolved against the scan dictionary
  at trace time, so the code a string literal maps to is baked into the
  program.  Booleans and NULLs stay baked too (they steer trace-time
  simplifications).

Structure-changing literals are never touched: IN-list arity, LIMIT /
OFFSET counts (plain ints on LogicalSort, not rex), VALUES rows, anything
under a volatile call (RAND, CURRENT_TIMESTAMP, ...) or a UDF.  The pass
is idempotent — ``RexParam`` nodes pass through untouched — because the
compiled path's degradation ladder re-enters ``try_execute_compiled`` with
an already-parameterized plan.

The body of an uncorrelated scalar subquery (``RexScalarSubquery.plan``)
is a plan like any other and goes through the same walk under the same
rule: the tracer inlines it into the outer program
(``compiled._Tracer.traced_scalar_subquery``), ``identity._fp_plan``
serializes it with the outer plan's ``params`` list, and its scalars ride
among the same trailing arguments.  A report that reads a CTE twice, once
below ``= (SELECT MAX(..))``, therefore keeps one program whatever its
dates.

Such a plan reaches this pass as a DAG: ``physical/shared.unify`` has made
the copies of a subtree that are equal BY VALUE one node object
(``identity._maybe_parameterize``; TPC-H Q15's CTE, whose two dates stood in
both copies).  The walk rewrites a node it meets twice ONCE and hands every
reference the same rewritten node, so the CTE has one set of slots (Q15
hoists 2 literals, not 4), ``identity._fp_rex`` numbers each ``RexParam`` in
one place, and the tracer, which keeps a node's result by identity, traces
the CTE once.  Two copies that differ in a literal were not equal by value,
are two nodes here and get slots of their own: no program rests on two
slots happening to hold one value.  The counter
``param_plan_subquery_hoisted`` counts the slots a scalar subquery's body
reads, those it shares with the plan around it included.

``DSQL_PARAM_PLANS=0`` is the kill switch: the pass becomes the identity
and every fingerprint/cache key is bit-for-bit what it was before this
subsystem existed.
"""
from __future__ import annotations

import copy
import os
from typing import List, Tuple

from . import nodes as N

# binary comparisons whose literal operands are value-stable to hoist:
# the traced comparison is shape-generic in the scalar operand
PARAM_OPS = frozenset({"=", "<>", "!=", "<", "<=", ">", ">="})

# SqlType names whose physical representation is a plain numeric scalar
# (types.py): safe to pass as a 0-d jit argument with a stable dtype
PARAM_TYPE_NAMES = frozenset({
    "TINYINT", "SMALLINT", "INTEGER", "BIGINT",
    "FLOAT", "REAL", "DOUBLE", "DECIMAL",
    "DATE", "TIMESTAMP", "TIME",
})

# mirrors result_cache.VOLATILE_OPS (no import: plan/ must not depend on
# runtime/) — a literal adjacent to one of these stays specialized, so a
# volatile expression can never be partially hoisted into a shared shape
_VOLATILE_OPS = frozenset({
    "RAND", "RANDOM", "RAND_INTEGER",
    "CURRENT_DATE", "CURRENT_TIMESTAMP", "NOW", "LOCALTIMESTAMP",
    "CURRENT_TIME", "LOCALTIME",
})


def param_plans_enabled() -> bool:
    """DSQL_PARAM_PLANS kill switch; default ON."""
    return os.environ.get("DSQL_PARAM_PLANS", "1") != "0"


def _eligible_literal(rex: N.RexNode) -> bool:
    return (isinstance(rex, N.RexLiteral)
            and rex.value is not None
            and not isinstance(rex.value, (bool, str))
            and isinstance(rex.value, (int, float))
            and rex.stype is not None
            and rex.stype.name in PARAM_TYPE_NAMES)


def _has_column_ref(rex: N.RexNode) -> bool:
    if isinstance(rex, N.RexInputRef):
        return True
    if isinstance(rex, (N.RexCall, N.RexUdf)):
        return any(_has_column_ref(o) for o in rex.operands)
    return False


def _contains_volatile(rex: N.RexNode) -> bool:
    if isinstance(rex, N.RexUdf):
        return True
    if isinstance(rex, N.RexCall):
        if rex.op in _VOLATILE_OPS:
            return True
        return any(_contains_volatile(o) for o in rex.operands)
    return False


class _Hoist:
    __slots__ = ("next_slot", "in_bodies", "depth", "done")

    def __init__(self):
        self.next_slot = 0       # = the literals hoisted so far
        self.in_bodies = set()   # the slots a scalar subquery's body reads
        self.depth = 0           # scalar subqueries the walk is inside
        # id(node) -> (its rewrite, the slots hoisted beneath it as a
        # range): a node the plan holds twice is rewritten once
        self.done = {}

    def param(self, lit: N.RexLiteral) -> N.RexParam:
        p = N.RexParam(self.next_slot, lit.value, lit.stype)
        if self.depth:
            self.in_bodies.add(p.slot)
        self.next_slot += 1
        return p


def _walk_rex(rex: N.RexNode, acc: _Hoist) -> N.RexNode:
    """Rewrite eligible literals under this expression; returns ``rex``
    itself when nothing below changed."""
    if isinstance(rex, N.RexScalarSubquery):
        # the body is a plan of its own: the same walk, the same slots
        acc.depth += 1
        body = _walk_rel(rex.plan, acc)
        acc.depth -= 1
        return rex if body is rex.plan else N.RexScalarSubquery(body,
                                                                rex.stype)
    if not isinstance(rex, N.RexCall):
        # literals NOT in an eligible comparison position stay baked;
        # UDFs stay specialized wholesale
        return rex
    if rex.op in _VOLATILE_OPS:
        return rex
    if (rex.op in PARAM_OPS and len(rex.operands) == 2
            and not any(_contains_volatile(o) for o in rex.operands)):
        a, b = rex.operands
        new_a, new_b = a, b
        if _eligible_literal(a) and _has_column_ref(b):
            new_a = acc.param(a)
        else:
            new_a = _walk_rex(a, acc)
        if _eligible_literal(b) and _has_column_ref(a):
            new_b = acc.param(b)
        else:
            new_b = _walk_rex(b, acc)
        if new_a is a and new_b is b:
            return rex
        return N.RexCall(rex.op, [new_a, new_b], rex.stype, rex.info)
    new_ops = [_walk_rex(o, acc) for o in rex.operands]
    if all(n is o for n, o in zip(new_ops, rex.operands)):
        return rex
    return N.RexCall(rex.op, new_ops, rex.stype, rex.info)


def _walk_rel(rel: N.RelNode, acc: _Hoist) -> N.RelNode:
    """Rewrite ``rel`` once, however many references the plan holds to it
    (``copy.copy`` per reference would split a shared node again)."""
    hit = acc.done.get(id(rel))
    if hit is None:
        lo = acc.next_slot
        hit = acc.done[id(rel)] = (_rewrite_rel(rel, acc),
                                   range(lo, acc.next_slot))
    elif acc.depth:
        acc.in_bodies.update(hit[1])
    return hit[0]


def _rewrite_rel(rel: N.RelNode, acc: _Hoist) -> N.RelNode:
    kids = rel.inputs
    new_kids = [_walk_rel(k, acc) for k in kids]
    changed = any(n is not o for n, o in zip(new_kids, kids))

    # only these three node kinds carry hoistable expressions; everything
    # else (Aggregate args, Sort limits, Values rows, Window frames) is
    # structure and stays specialized
    if isinstance(rel, N.LogicalFilter):
        cond = _walk_rex(rel.condition, acc)
        if cond is not rel.condition or changed:
            out = copy.copy(rel)
            out.input = new_kids[0]
            out.condition = cond
            return out
        return rel
    if isinstance(rel, N.LogicalProject):
        exprs = [_walk_rex(e, acc) for e in rel.exprs]
        if changed or any(n is not o for n, o in zip(exprs, rel.exprs)):
            out = copy.copy(rel)
            out.input = new_kids[0]
            out.exprs = exprs
            return out
        return rel
    if isinstance(rel, N.LogicalJoin):
        cond = (None if rel.condition is None
                else _walk_rex(rel.condition, acc))
        if cond is not rel.condition or changed:
            # copy.copy keeps dynamically-attached verdicts (null_aware)
            out = copy.copy(rel)
            out.left, out.right = new_kids
            out.condition = cond
            return out
        return rel
    if changed:
        return rel.with_inputs(new_kids)
    return rel


def parameterize_plan(plan: N.RelNode) -> Tuple[N.RelNode, int, int]:
    """(rewritten plan, literals hoisted THIS call, those of them a scalar
    subquery's body reads).

    Idempotent, for a DAG as for a tree: a second pass over the result
    hoists nothing (RexParam is not RexLiteral) and returns the nodes it was
    given, so re-entrant callers (the whole→stages degradation rung) never
    double-count, renumber or split a shared node."""
    acc = _Hoist()
    new = _walk_rel(plan, acc)
    return new, acc.next_slot, len(acc.in_bodies)


def collect_params(plan: N.RelNode) -> List[N.RexParam]:
    """Every RexParam in this (sub)plan, ordered by slot.

    Diagnostic/introspection helper — the compiled path orders its
    bound-argument vector by FINGERPRINT traversal instead
    (``compiled._fp_plan`` collects params as it serializes), so the arg
    order and the ``P{i}`` positions in the key can never disagree."""
    out: List[N.RexParam] = []
    seen: set = set()

    def rex(r: N.RexNode):
        if isinstance(r, N.RexParam):
            if id(r) not in seen:
                seen.add(id(r))
                out.append(r)
        elif isinstance(r, (N.RexCall, N.RexUdf)):
            for o in r.operands:
                rex(o)
        elif isinstance(r, N.RexScalarSubquery):
            rel(r.plan)

    def rel(node: N.RelNode):
        if isinstance(node, N.LogicalProject):
            for e in node.exprs:
                rex(e)
        elif isinstance(node, N.LogicalFilter):
            rex(node.condition)
        elif isinstance(node, N.LogicalJoin) and node.condition is not None:
            rex(node.condition)
        for k in node.inputs:
            rel(k)

    rel(plan)
    out.sort(key=lambda p: p.slot)
    return out
