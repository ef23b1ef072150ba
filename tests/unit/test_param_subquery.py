"""A scalar subquery's body is parameterized like the plan around it
(plan/parameterize.py, PR 43): eligible literals inside
``RexScalarSubquery.plan`` become ``RexParam`` nodes, ``identity._fp_plan``
numbers them in the same walk as the outer plan's, and the tracer hands
their values to the body it inlines.  TPC-H Q15 reads its CTE twice, once
below ``= (SELECT MAX(..))``: every date of it is a parameter and every
text of it one program (and since PR 44 the two copies are one node with
one set of slots: ``test_shared_subplans.py``)."""
import importlib

import numpy as np
import pandas as pd
import pytest

from chipbench.data import tpch_gen
from dask_sql_tpu import Context
from dask_sql_tpu.physical import compiled as cm, identity, programs
from dask_sql_tpu.physical.caps import _learned_caps
from dask_sql_tpu.plan import nodes as N
from dask_sql_tpu.plan.parameterize import collect_params, parameterize_plan
from dask_sql_tpu.runtime import telemetry as tel
from dask_sql_tpu.sql.parser import parse_sql


@pytest.fixture(scope="module")
def ctx():
    rng = np.random.RandomState(43)
    c = Context()
    c.create_table("t", pd.DataFrame({
        "a": np.arange(400), "b": np.round(rng.rand(400) * 100, 3),
        "d": pd.to_datetime("1995-01-01")
        + pd.to_timedelta(rng.randint(0, 700, 400), unit="D"),
        "s": [f"v{i % 3}" for i in range(400)]}))
    return c


@pytest.fixture(scope="module")
def tpch():
    frames = tpch_gen.generate(0.01, 43)
    c = Context()
    for name, frame in frames.items():
        c.create_table(name, frame)
    return c, frames


def _plan(context, sql):
    return context._get_plan(parse_sql(sql)[0].query, sql)


def _subqueries(rel, found=None):
    """Every RexScalarSubquery of a plan, bodies' own included."""
    found = [] if found is None else found

    def rex(r):
        if isinstance(r, N.RexScalarSubquery):
            found.append(r)
            _subqueries(r.plan, found)
        elif isinstance(r, (N.RexCall, N.RexUdf)):
            for o in r.operands:
                rex(o)

    if isinstance(rel, N.LogicalProject):
        for e in rel.exprs:
            rex(e)
    elif isinstance(rel, (N.LogicalFilter, N.LogicalJoin)) \
            and rel.condition is not None:
        rex(rel.condition)
    for k in rel.inputs:
        _subqueries(k, found)
    return found


BODY = "SELECT a FROM t WHERE b > (SELECT AVG(b) FROM t WHERE {predicate})"

#: predicate of the subquery's body: (values hoisted out of the body)
HOISTED = {
    "a > 7": [7],
    "b <= 12.5": [12.5],
    "d >= DATE '1995-06-01' AND d < DATE '1995-09-01'": [9282, 9374],
    "d >= DATE '1995-06-01' AND a <> 3": [9282, 3],
}
#: structure, strings and volatile calls stay in the program's text
BAKED = {
    "s = 'v1'": "a string is a dictionary code resolved at trace time",
    "a IN (1, 2, 3)": "an IN-list's arity is structure",
    "b > RAND() * 5": "nothing under a volatile call is hoisted",
    "a + 1 > b": "not a direct operand of the comparison",
}


@pytest.mark.parametrize("predicate", sorted(HOISTED))
def test_a_date_and_a_number_inside_the_body_are_hoisted(ctx, predicate):
    plan = _plan(ctx, BODY.format(predicate=predicate))
    new, hoisted, in_subqueries = parameterize_plan(plan)
    assert hoisted == in_subqueries == len(HOISTED[predicate])
    body, = _subqueries(new)
    assert [p.value for p in collect_params(body.plan)] == HOISTED[predicate]
    # the caller's plan is untouched: rewritten nodes are copies
    assert collect_params(plan) == []
    assert parameterize_plan(plan)[1] == hoisted


@pytest.mark.parametrize("predicate", sorted(BAKED))
def test_structure_strings_and_volatile_calls_stay_baked(ctx, predicate):
    plan = _plan(ctx, BODY.format(predicate=predicate))
    new, hoisted, in_subqueries = parameterize_plan(plan)
    assert (hoisted, in_subqueries) == (0, 0), BAKED[predicate]
    assert new is plan


def test_a_limit_inside_the_body_is_not_a_parameter(ctx):
    plan = _plan(ctx, "SELECT a FROM t WHERE b > (SELECT MAX(b) FROM "
                      "(SELECT b FROM t WHERE a > 5 ORDER BY b LIMIT 10) x)")
    new, hoisted, in_subqueries = parameterize_plan(plan)
    assert hoisted == in_subqueries == 1       # the 5, not the 10
    body, = _subqueries(new)
    sort, = [n for n in _walk(body.plan) if isinstance(n, N.LogicalSort)]
    assert sort.limit == 10


def _walk(rel):
    yield rel
    for k in rel.inputs:
        yield from _walk(k)


def test_outer_and_inner_literals_share_one_numbering(ctx):
    plan = _plan(ctx, "SELECT a FROM t WHERE a < 300 AND b > "
                      "(SELECT AVG(b) FROM t WHERE a > 7)")
    new, hoisted, in_subqueries = parameterize_plan(plan)
    assert (hoisted, in_subqueries) == (2, 1)
    params = collect_params(new)
    assert sorted(p.slot for p in params) == [0, 1]
    # the key's positions are the fingerprint walk's, body included
    pk = identity.program_key(new, ctx)
    assert {id(p) for p in pk.params} == {id(p) for p in params}
    assert pk.key[0].count("P0:") == 1 and pk.key[0].count("P1:") == 1


def test_the_pass_stays_idempotent_with_a_body(ctx):
    plan = _plan(ctx, BODY.format(predicate="a > 7"))
    once, n1, s1 = parameterize_plan(plan)
    twice, n2, s2 = parameterize_plan(once)
    assert (n1, s1, n2, s2) == (1, 1, 0, 0)
    assert twice is once


def test_the_values_reach_the_body_in_the_trace(ctx, monkeypatch):
    """One program, two values: each answer is its own value's."""
    programs._cache.clear()
    _learned_caps.clear()
    frame = ctx.sql("SELECT a, b FROM t", return_futures=False)
    compiles = None
    for k in (7, 250, 390):
        got = ctx.sql(BODY.format(predicate=f"a > {k}"),
                      return_futures=False)
        assert ctx.last_report.tier == "compiled"
        want = frame.loc[frame["b"] > frame.loc[frame["a"] > k, "b"].mean(),
                         "a"]
        assert sorted(got["a"]) == sorted(want)
        if compiles is None:
            compiles = cm.stats["compiles"] + cm.stats["recompiles"]
    assert cm.stats["compiles"] + cm.stats["recompiles"] == compiles


def test_the_counter_counts_what_came_out_of_bodies(ctx):
    before = tel.REGISTRY.counters()
    plan = _plan(ctx, "SELECT a FROM t WHERE a < 300 AND b > "
                      "(SELECT AVG(b) FROM t WHERE a > 7 AND b < 90.5)")
    identity._maybe_parameterize(plan)
    identity._maybe_parameterize(plan, count=False)   # a probe counts nothing
    after = tel.REGISTRY.counters()

    def delta(name):
        return after.get(name, 0) - before.get(name, 0)

    assert delta("param_literals_hoisted") == 3
    assert delta("param_plan_subquery_hoisted") == 2
    assert "param_plan_subquery_hoisted" in tel.STABLE_COUNTERS


# --- the shapes of tpch_sf1_subqueries: one key a shape ----------------

def _shape(name):
    return importlib.import_module("chipbench.shapes." + name)


def _key(context, shape, i):
    text = shape.SQL.format(**shape.params_at(i))
    plan = identity._maybe_parameterize(_plan(context, text), count=False)
    return identity.program_key(plan, context)


@pytest.mark.parametrize("name,hoisted,in_body", [
    ("q4", 2, 0), ("q15", 2, 2), ("q18", 1, 0)])
def test_two_texts_of_a_shape_have_one_program_key(tpch, name, hoisted,
                                                   in_body):
    """Q15's CTE is one node by the time it is hoisted (PR 44,
    ``physical/shared.py``): two slots, which its body reads too; the pass
    alone, over the tree, gives each copy its own."""
    from dask_sql_tpu.physical import shared

    context, _ = tpch
    shape = _shape(name)
    first, other = _key(context, shape, shape.FIRST), _key(context, shape, 3)
    assert first.key == other.key
    assert len(first.params) == hoisted
    assert [p.value for p in first.params] != [p.value for p in other.params]
    text = shape.SQL.format(**shape.params_at(shape.FIRST))
    one = shared.unify(_plan(context, text))[0]
    assert parameterize_plan(one)[1:] == (hoisted, in_body)
    assert parameterize_plan(_plan(context, text))[1:] == (
        (4, 2) if name == "q15" else (hoisted, in_body))


@pytest.mark.parametrize("name", ["q4", "q15", "q18"])
def test_with_the_switch_off_the_keys_are_the_unhoisted_plans(
        tpch, monkeypatch, name):
    """``DSQL_PARAM_PLANS=0``: the pass is the identity, so every key is the
    plan's own with its literals in it, as before there were parameters."""
    monkeypatch.setenv("DSQL_PARAM_PLANS", "0")
    context, _ = tpch
    shape = _shape(name)
    plans = [_plan(context, shape.SQL.format(**shape.params_at(i)))
             for i in (shape.FIRST, 3)]
    assert all(identity._maybe_parameterize(p) is p for p in plans)
    keys = [identity.program_key(p, context) for p in plans]
    assert keys[0].key != keys[1].key
    assert keys[0].params == [] and "P0:" not in keys[0].key[0]
