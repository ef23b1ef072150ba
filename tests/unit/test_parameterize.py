"""Unit: parameterized plan identity (plan/parameterize.py, ISSUE 16).

Pins the hoisting eligibility rules, idempotence, the DSQL_PARAM_PLANS
kill switch, fingerprint behavior (one program identity across literal
variants of a shape; distinct identities with the switch off), and the
result-cache canonicalization contract: RexParam is value-bearing by
default (result keys must distinguish literals) and slot+type in shape
mode (EWMA history must not).  Also audits _canon_rel literal coverage:
VALUES rows and scalar-subquery bodies participate in canonicalization
(a body's literals are hoisted like the outer plan's:
tests/unit/test_param_subquery.py), and volatile expressions are never
hoisted.
"""
import os

import pandas as pd
import pytest

from dask_sql_tpu import Context
from dask_sql_tpu.plan import nodes as N
from dask_sql_tpu.plan.parameterize import (
    collect_params, param_plans_enabled, parameterize_plan)
from dask_sql_tpu.runtime import result_cache as rc
from dask_sql_tpu.sql.parser import parse_sql


@pytest.fixture()
def ctx():
    c = Context()
    c.create_table("t", pd.DataFrame({
        "a": range(20), "b": [float(i) * 0.5 for i in range(20)],
        "s": [f"v{i % 3}" for i in range(20)]}))
    return c


def _plan(ctx, sql):
    return ctx._get_plan(parse_sql(sql)[0].query, sql)


def _rex_kinds(plan):
    """Flatten every expression node class name in the plan (recursive)."""
    out = []

    def rex(r):
        out.append(type(r).__name__)
        if isinstance(r, (N.RexCall, N.RexUdf)):
            for o in r.operands:
                rex(o)
        elif isinstance(r, N.RexScalarSubquery):
            rel(r.plan)

    def rel(node):
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
    return out


# ---------------------------------------------------------------------------
# hoisting eligibility
# ---------------------------------------------------------------------------

def test_comparison_literals_hoist(ctx):
    plan = _plan(ctx, "SELECT a FROM t WHERE a > 5 AND b <= 7.5")
    new, n, _ = parameterize_plan(plan)
    assert n == 2
    params = collect_params(new)
    assert [p.value for p in params] == [5, 7.5]
    assert [p.slot for p in params] == [0, 1]
    # original plan untouched (the pass copies rewritten nodes)
    assert collect_params(plan) == []


def test_string_bool_null_literals_stay_baked(ctx):
    # strings resolve to dictionary codes at trace time; bools/NULLs steer
    # trace-time simplification — none may become runtime arguments
    plan = _plan(ctx, "SELECT a FROM t WHERE s = 'v1'")
    _, n, _ = parameterize_plan(plan)
    assert n == 0
    plan = _plan(ctx, "SELECT a FROM t WHERE (a > 3) = TRUE")
    new, _, _ = parameterize_plan(plan)
    assert all(not (isinstance(p, N.RexParam)
                    and isinstance(p.value, bool))
               for p in collect_params(new))


def test_both_scalar_comparison_not_hoisted(ctx):
    # 1 < 2 has no column ref on either side: hoisting would push a traced
    # scalar through the host `bool()` branch of ops.comparison
    plan = _plan(ctx, "SELECT a FROM t WHERE 1 < 2 AND a > 5")
    new, n, _ = parameterize_plan(plan)
    assert n == 1
    assert [p.value for p in collect_params(new)] == [5]


def test_in_list_arity_stays_structural(ctx):
    # IN lowers to OR-of-equals or a structural op; its arity is program
    # STRUCTURE.  Equality arms that lower to plain `a = k` comparisons
    # may hoist — what must hold is that two IN lists of different LENGTH
    # never share a fingerprint (checked below via canonical text).
    p2 = _plan(ctx, "SELECT a FROM t WHERE a IN (1, 2)")
    p3 = _plan(ctx, "SELECT a FROM t WHERE a IN (1, 2, 3)")
    n2, _, _ = parameterize_plan(p2)
    n3, _, _ = parameterize_plan(p3)
    t2 = rc.canonical_plan(n2, ctx, shape=True)[0]
    t3 = rc.canonical_plan(n3, ctx, shape=True)[0]
    assert t2 != t3


def test_volatile_expressions_never_hoisted(ctx):
    plan = _plan(ctx, "SELECT a FROM t WHERE b > RAND(1) AND RAND(2) < 0.5")
    new, n, _ = parameterize_plan(plan)
    assert n == 0
    assert collect_params(new) == []


def test_values_rows_stay_baked(ctx):
    plan = _plan(ctx, "SELECT * FROM (VALUES (1, 2.0), (3, 4.0)) AS v(x, y)")
    new, n, _ = parameterize_plan(plan)
    assert n == 0
    # and VALUES literals participate in canonicalization: different rows,
    # different canonical text (the result cache must not cross-serve)
    other = _plan(ctx, "SELECT * FROM (VALUES (9, 2.0), (3, 4.0)) AS v(x, y)")
    assert (rc.canonical_plan(new, ctx)[0]
            != rc.canonical_plan(other, ctx)[0])


def test_scalar_subquery_body_is_hoisted_and_canonicalized(ctx):
    q = "SELECT a FROM t WHERE b > (SELECT AVG(b) FROM t WHERE a > {k})"
    p5 = _plan(ctx, q.format(k=5))
    p9 = _plan(ctx, q.format(k=9))
    n5, h5, _ = parameterize_plan(p5)
    n9, _, _ = parameterize_plan(p9)
    # the body is a plan like any other (PR 43): its literal is a param
    sub_lits = [k for k in _rex_kinds(n5) if k == "RexParam"]
    assert len(sub_lits) == h5 == 1
    assert [p.value for p in collect_params(n5)] == [5]
    # ... and its value stays visible to the canonicalizer, hoisted or not
    assert rc.canonical_plan(p5, ctx)[0] != rc.canonical_plan(p9, ctx)[0]
    assert rc.canonical_plan(n5, ctx)[0] != rc.canonical_plan(n9, ctx)[0]


def test_idempotent(ctx):
    plan = _plan(ctx, "SELECT a FROM t WHERE a > 5")
    once, n1, _ = parameterize_plan(plan)
    twice, n2, _ = parameterize_plan(once)
    assert n1 == 1 and n2 == 0
    assert twice is once


def test_kill_switch(monkeypatch):
    monkeypatch.setenv("DSQL_PARAM_PLANS", "0")
    assert not param_plans_enabled()
    monkeypatch.setenv("DSQL_PARAM_PLANS", "1")
    assert param_plans_enabled()
    monkeypatch.delenv("DSQL_PARAM_PLANS")
    assert param_plans_enabled()


# ---------------------------------------------------------------------------
# fingerprint identity (physical/identity._fp_plan)
# ---------------------------------------------------------------------------

def _fp(ctx, plan):
    from dask_sql_tpu.physical.identity import _fp_plan
    params = []
    return _fp_plan(plan, ctx, [], params), params


def test_shape_fingerprint_shared_across_literals(ctx):
    a = parameterize_plan(_plan(ctx, "SELECT a FROM t WHERE a > 5"))[0]
    b = parameterize_plan(_plan(ctx, "SELECT a FROM t WHERE a > 17"))[0]
    fa, pa = _fp(ctx, a)
    fb, pb = _fp(ctx, b)
    assert fa == fb
    assert [p.value for p in pa] == [5]
    assert [p.value for p in pb] == [17]
    assert "P0:INTEGER" in fa


def test_unparameterized_fingerprints_stay_distinct(ctx):
    fa, _ = _fp(ctx, _plan(ctx, "SELECT a FROM t WHERE a > 5"))
    fb, _ = _fp(ctx, _plan(ctx, "SELECT a FROM t WHERE a > 17"))
    assert fa != fb  # DSQL_PARAM_PLANS=0 behavior: value-baked identity


# ---------------------------------------------------------------------------
# result-cache canonicalization (runtime/result_cache._canon_rex)
# ---------------------------------------------------------------------------

def test_canon_default_is_value_bearing(ctx):
    a = parameterize_plan(_plan(ctx, "SELECT a FROM t WHERE a > 5"))[0]
    b = parameterize_plan(_plan(ctx, "SELECT a FROM t WHERE a > 17"))[0]
    ta, va, _ = rc.canonical_plan(a, ctx)
    tb, vb, _ = rc.canonical_plan(b, ctx)
    assert not va and not vb  # RexParam must not mark the plan volatile
    assert ta != tb
    assert "P0:INTEGER=5" in ta and "P0:INTEGER=17" in tb


def test_canon_shape_mode_is_value_free(ctx):
    a = parameterize_plan(_plan(ctx, "SELECT a FROM t WHERE a > 5"))[0]
    b = parameterize_plan(_plan(ctx, "SELECT a FROM t WHERE a > 17"))[0]
    assert (rc.canonical_plan(a, ctx, shape=True)[0]
            == rc.canonical_plan(b, ctx, shape=True)[0])


def test_flight_recorder_fingerprint_shared_across_literals(ctx):
    from dask_sql_tpu.runtime.flight_recorder import plan_fingerprint
    fa = plan_fingerprint(_plan(ctx, "SELECT a FROM t WHERE a > 5"), ctx)
    fb = plan_fingerprint(_plan(ctx, "SELECT a FROM t WHERE a > 17"), ctx)
    fc = plan_fingerprint(_plan(ctx, "SELECT a FROM t WHERE b > 1.0"), ctx)
    assert fa is not None and fa == fb
    assert fa != fc


def test_statistics_use_param_values():
    from dask_sql_tpu.runtime.statistics import _literal_value
    from dask_sql_tpu.types import INTEGER
    p = N.RexParam(0, 42, INTEGER)
    assert _literal_value(p) == 42.0
