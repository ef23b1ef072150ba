"""A subtree the plan holds twice is one node of the compiled program
(``physical/shared.py``, PR 44): ``identity._maybe_parameterize`` makes the
copies that are equal by value one node object before it hoists literals,
``plan/parameterize.py`` rewrites that node once, ``identity._fp_plan``
writes it once and ``compiled._Tracer.run`` traces it once.  TPC-H Q15 reads
its CTE twice, once below ``= (SELECT MAX(..))``: two parameters, one
``agg*`` site, one program key whatever the dates.  Two copies that differ
in a literal stay two."""
import importlib

import numpy as np
import pandas as pd
import pytest

from chipbench.data import tpch_gen
from dask_sql_tpu import Context
from dask_sql_tpu.physical import (compiled as cm, identity, programs,
                                   shared)
from dask_sql_tpu.physical.caps import _learned_caps
from dask_sql_tpu.plan import nodes as N
from dask_sql_tpu.plan.parameterize import collect_params, parameterize_plan
from dask_sql_tpu.runtime import statistics, telemetry as tel
from dask_sql_tpu.sql.parser import parse_sql


@pytest.fixture(scope="module")
def ctx():
    rng = np.random.RandomState(44)
    c = Context()
    c.create_table("t", pd.DataFrame({
        "a": np.arange(400), "g": np.arange(400) % 17,
        "b": np.round(rng.rand(400) * 100, 3)}))
    return c


@pytest.fixture(scope="module")
def tpch():
    frames = tpch_gen.generate(0.01, 44)
    c = Context()
    for name, frame in frames.items():
        c.create_table(name, frame)
    return c, frames


@pytest.fixture
def fresh():
    programs._cache.clear()
    _learned_caps.clear()


def _plan(context, sql):
    return context._get_plan(parse_sql(sql)[0].query, sql)


def _shape(name):
    return importlib.import_module("chipbench.shapes." + name)


def _text(shape, i):
    return shape.SQL.format(**shape.params_at(i))


def _references(plan, kind):
    """Every reference the plan holds to a node of ``kind``, bodies'
    included: a node reached twice is listed twice."""
    found = []

    def of_rex(rex):
        if isinstance(rex, N.RexScalarSubquery):
            walk(rex.plan)
        for o in getattr(rex, "operands", ()):
            of_rex(o)

    def walk(rel):
        if isinstance(rel, kind):
            found.append(rel)
        for rex in shared._rexes(rel):
            of_rex(rex)
        for i in rel.inputs:
            walk(i)

    walk(plan)
    return found


def _grouped(plan):
    return [r for r in _references(plan, N.LogicalAggregate) if r.group_keys]


def _delta(before, name):
    return tel.REGISTRY.counters().get(name, 0) - before.get(name, 0)


def _dispatch(context):
    span, = [s for s in context.last_report.root.walk()
             if s.name == "dispatch"]
    return span


def _entry(span):
    entry, = [e for e in programs._cache.values()
              if getattr(e, "name", None) == span.attrs["program"]
              and e.meta.get("agg_sites") is not None]
    return entry


# --- Q15: the plan, its parameters, its key -----------------------------

def test_q15s_cte_is_one_node_reached_from_both_references(tpch):
    context, _ = tpch
    plan = _plan(context, _text(_shape("q15"), 1095))
    two = _grouped(plan)
    assert len(two) == 2 and two[0] is not two[1]
    one, replaced = shared.unify(plan)
    assert replaced == 1 and one is not plan
    both = _grouped(one)
    assert len(both) == 2 and both[0] is both[1]
    # the caller's plan is as it was: the eager tier still walks a tree
    assert _grouped(plan)[0] is not _grouped(plan)[1]
    # a unified plan has nothing left to find, and comes back itself
    assert shared.read_twice(one) == {}
    assert shared.unify(one) == (one, 0)


def test_q15_hoists_two_literals_and_both_references_hold_them(tpch):
    context, _ = tpch
    plan = _plan(context, _text(_shape("q15"), 1095))
    before = tel.REGISTRY.counters()
    new = identity._maybe_parameterize(plan)
    assert _delta(before, "param_plan_shared_subtrees") == 1
    assert _delta(before, "param_literals_hoisted") == 2
    # the body reads both slots, which it shares with the plan around it
    assert _delta(before, "param_plan_subquery_hoisted") == 2
    assert "param_plan_shared_subtrees" in tel.STABLE_COUNTERS
    both = _grouped(new)
    assert both[0] is both[1]
    params = collect_params(new)
    assert [p.slot for p in params] == [0, 1]
    pk = identity.program_key(new, context)
    assert [id(p) for p in pk.params] == [id(p) for p in params]
    assert pk.key[0].count("P0:") == 1 and pk.key[0].count("P1:") == 1
    # written once: the second reference is a back-reference, and
    # lineitem is listed, and bound, once
    assert pk.key[0].count("LogicalAggregate(g=[0]") == 1
    assert "<^" in pk.key[0]
    assert sorted(name for (_, name), _, _ in pk.scans) == ["lineitem",
                                                            "supplier"]
    # a probe counts nothing, and a re-entry finds nothing to do
    before = tel.REGISTRY.counters()
    identity._maybe_parameterize(plan, count=False)
    assert identity._maybe_parameterize(new) is new
    assert _delta(before, "param_plan_shared_subtrees") == 0
    assert _delta(before, "param_literals_hoisted") == 0


def test_q15_has_one_program_key_over_five_dates(tpch):
    context, _ = tpch
    shape = _shape("q15")
    keys = [identity.program_key(identity._maybe_parameterize(
        _plan(context, _text(shape, i)), count=False), context)
        for i in (0, 400, 1095, 1096, shape.SPACE - 1)]
    assert len({k.key for k in keys}) == 1
    assert len({tuple(p.value for p in k.params) for k in keys}) == 5
    assert all(len(k.params) == 2 for k in keys)


def test_hoisting_a_unified_plan_twice_hoists_nothing_the_second_time(tpch):
    context, _ = tpch
    plan, _ = shared.unify(_plan(context, _text(_shape("q15"), 1095)))
    once, n1, s1 = parameterize_plan(plan)
    twice, n2, s2 = parameterize_plan(once)
    assert (n1, s1, n2, s2) == (2, 2, 0, 0)
    assert twice is once
    both = _grouped(once)
    assert both[0] is both[1]
    # without the unifier the two copies get slots of their own
    tree = parameterize_plan(_plan(context, _text(_shape("q15"), 1095)))
    assert tree[1:] == (4, 2)
    assert _grouped(tree[0])[0] is not _grouped(tree[0])[1]


# --- what is not shared --------------------------------------------------

@pytest.mark.parametrize("name", ["q3", "q6", "q18"])
def test_a_plan_without_a_repeat_comes_out_as_it_went_in(tpch, name):
    """Q3 and Q6 scan no table twice and leave at the finder's first
    check; Q18 scans lineitem twice and holds no aggregate or join
    twice."""
    context, _ = tpch
    shape = _shape(name)
    plan = _plan(context, _text(shape, shape.FIRST))
    assert shared.read_twice(plan) == {}
    assert shared.unify(plan) == (plan, 0)
    assert shared.unify(plan)[0] is plan
    before = tel.REGISTRY.counters()
    identity._maybe_parameterize(plan)
    assert _delta(before, "param_plan_shared_subtrees") == 0


def test_no_text_is_made_for_a_plan_whose_scans_all_differ(tpch, monkeypatch):
    from dask_sql_tpu.runtime import result_cache

    context, _ = tpch
    made = []
    real = result_cache.canonical_plan
    monkeypatch.setattr(result_cache, "canonical_plan",
                        lambda *a, **k: made.append(a) or real(*a, **k))
    for name in ("q3", "q5", "q10", "q4"):
        shape = _shape(name)
        shared.read_twice(_plan(context, _text(shape, shape.FIRST)))
    assert made == []
    shape = _shape("q18")
    shared.read_twice(_plan(context, _text(shape, shape.FIRST)))
    assert len(made) == 5          # its three joins and two aggregates


CTE = "WITH r AS (SELECT g, SUM(b) AS total FROM t {where}GROUP BY g) "
TOP = CTE + "SELECT g, total FROM r WHERE total = (SELECT MAX(total) FROM r)"


def test_a_repeated_subtree_under_rand_is_not_shared(ctx):
    plan = _plan(ctx, TOP.format(where="WHERE b > RAND() "))
    assert shared.unify(plan) == (plan, 0)
    both = _grouped(identity._maybe_parameterize(plan, count=False))
    assert len(both) == 2 and both[0] is not both[1]


# --- Q15 written by hand: two CTEs, equal by value or not ------------------

BY_HAND = """
    WITH revenue0 AS (
        SELECT l_suppkey AS supplier_no,
               SUM(l_extendedprice * (1 - l_discount)) AS total_revenue
        FROM lineitem
        WHERE l_shipdate >= DATE '{outer_from}' AND l_shipdate < DATE '{outer_to}'
        GROUP BY l_suppkey),
    revenue1 AS (
        SELECT l_suppkey AS supplier_no,
               SUM(l_extendedprice * (1 - l_discount)) AS total_revenue
        FROM lineitem
        WHERE l_shipdate >= DATE '{body_from}' AND l_shipdate < DATE '{body_to}'
        GROUP BY l_suppkey)
    SELECT s_suppkey, s_name, total_revenue
    FROM supplier, revenue0
    WHERE s_suppkey = supplier_no
      AND total_revenue >= (SELECT MAX(total_revenue) FROM revenue1)
    ORDER BY s_suppkey
"""

#: the outer CTE sums a quarter, the body's one a month of it (so some
#: suppliers' quarters pass the best month) or the same quarter
MONTH = dict(outer_from="1996-01-01", outer_to="1996-04-01",
             body_from="1996-02-01", body_to="1996-03-01")
OTHER_MONTH = dict(outer_from="1995-03-05", outer_to="1995-06-05",
                   body_from="1995-04-01", body_to="1995-05-03")
SAME = dict(outer_from="1996-01-01", outer_to="1996-04-01",
            body_from="1996-01-01", body_to="1996-04-01")


def _by_hand_reference(frames, outer_from, outer_to, body_from, body_to):
    li, su = frames["lineitem"], frames["supplier"]

    def revenue(date_from, date_to):
        l = li.loc[(li["l_shipdate"] >= pd.Timestamp(date_from))
                   & (li["l_shipdate"] < pd.Timestamp(date_to))]
        l = l.assign(total_revenue=l["l_extendedprice"]
                     * (1 - l["l_discount"]))
        return l.groupby("l_suppkey", as_index=False)["total_revenue"].sum()

    outer = revenue(outer_from, outer_to)
    top = outer.loc[outer["total_revenue"]
                    >= revenue(body_from, body_to)["total_revenue"].max()]
    out = su.merge(top, left_on="s_suppkey", right_on="l_suppkey")
    return out[["s_suppkey", "s_name", "total_revenue"]].sort_values(
        "s_suppkey").reset_index(drop=True)


def _answers(got, want):
    assert got["s_suppkey"].tolist() == want["s_suppkey"].tolist()
    assert got["s_name"].tolist() == want["s_name"].tolist()
    np.testing.assert_allclose(got["total_revenue"].to_numpy(dtype=float),
                               want["total_revenue"].to_numpy(dtype=float),
                               rtol=1e-9)


def test_two_copies_that_differ_in_a_date_stay_two(tpch, fresh):
    """Four parameters, two traced aggregates, a key of its own, pandas'
    answer: nothing rests on two slots happening to hold one value."""
    context, frames = tpch
    plans = {k: identity._maybe_parameterize(
        _plan(context, BY_HAND.format(**dates)), count=False)
        for k, dates in (("month", MONTH), ("same", SAME))}
    keys = {k: identity.program_key(p, context) for k, p in plans.items()}
    assert len(keys["month"].params) == 4 and len(keys["same"].params) == 2
    assert keys["month"].key != keys["same"].key
    two = _grouped(plans["month"])
    assert len(two) == 2 and two[0] is not two[1]
    one = _grouped(plans["same"])
    assert len(one) == 2 and one[0] is one[1]
    for dates in (MONTH, OTHER_MONTH):
        before = tel.REGISTRY.counters()
        got = context.sql(BY_HAND.format(**dates), return_futures=False)
        assert context.last_report.tier == "compiled"
        assert _delta(before, "param_plan_shared_subtrees") == 0
        want = _by_hand_reference(frames, **dates)
        assert 1 < len(want) < 100
        _answers(got, want)
    # OTHER_MONTH ran the program MONTH compiled
    span = _dispatch(context)
    assert span.attrs["shared_subplans"] == 0
    assert span.attrs["scalar_subqueries"] == 1
    assert len([t for _, _, t in _entry(span).meta["agg_sites"]
                if t.startswith("agg")]) == 2


def test_two_ctes_equal_by_value_are_one_node(tpch, fresh):
    """Equality is the plan's, by value: it does not matter that the text
    wrote the CTE out twice."""
    context, frames = tpch
    same_too = dict(SAME, outer_to="1996-03-01", body_to="1996-03-01")
    for dates in (SAME, same_too):
        before = tel.REGISTRY.counters()
        got = context.sql(BY_HAND.format(**dates), return_futures=False)
        assert context.last_report.tier == "compiled"
        assert _delta(before, "param_plan_shared_subtrees") == 1
        _answers(got, _by_hand_reference(frames, **dates))
        assert len(got) == 1
    assert _dispatch(context).attrs["shared_subplans"] == 1


# --- the trace -------------------------------------------------------------

@pytest.mark.parametrize("strategy", ["cpu", "tpu"])
def test_q15_traces_its_cte_once_and_answers_as_pandas(tpch, fresh,
                                                        monkeypatch,
                                                        strategy):
    if strategy == "tpu":
        monkeypatch.setenv("DSQL_STRATEGY", "tpu")
        monkeypatch.delenv("DSQL_CAPS_FILE", raising=False)
    context, frames = tpch
    shape = _shape("q15")
    names = set()
    for i in (1095, 3, 700, 1500, shape.SPACE - 1):
        params = shape.params_at(i)
        before = tel.REGISTRY.counters()
        got = context.sql(shape.SQL.format(**params), return_futures=False)
        assert context.last_report.tier == "compiled"
        assert _delta(before, "param_plan_shared_subtrees") == 1
        want = shape.reference(frames, **params)
        assert got["s_suppkey"].tolist() == want["s_suppkey"].tolist()
        np.testing.assert_allclose(got["total_revenue"].to_numpy(),
                                   want["total_revenue"].to_numpy(),
                                   rtol=1e-9)
        if i == 1095:
            continue        # the arrival that compiled has no dispatch span
        span = _dispatch(context)
        assert span.attrs["shared_subplans"] == 1
        assert span.attrs["scalar_subqueries"] == 1
        sites = [t for _, _, t in _entry(span).meta["agg_sites"]]
        assert [t for t in sites if t.startswith("agg")] == ["agg0"]
        names.add(span.attrs["program"])
    assert len(names) == 1


@pytest.mark.parametrize("name", ["q4", "q18", "q3"])
def test_the_other_shapes_programs_answer_no_reference_from_the_memo(
        tpch, fresh, name):
    context, _ = tpch
    shape = _shape(name)
    for i in (shape.FIRST, shape.FIRST + 1):
        context.sql(_text(shape, i), return_futures=False)
        assert context.last_report.tier == "compiled"
    spans = [s for s in context.last_report.root.walk()
             if s.name == "dispatch"]
    assert spans and all(s.attrs["shared_subplans"] == 0 for s in spans)


def test_a_cte_joined_to_itself_is_traced_once(ctx, fresh):
    """Both references stand among the plan's inputs: one ``agg*`` site, one
    grouped aggregate for the capacity hint to speak of, one tag a join."""
    text = (CTE.format(where="WHERE a > 7 ") + "SELECT x.g AS xg, y.total "
            "FROM r x, r y WHERE x.g = y.g AND x.total >= y.total")
    plan = identity._maybe_parameterize(_plan(ctx, text), count=False)
    both = _grouped(plan)
    assert len(both) == 2 and both[0] is both[1]
    assert len(collect_params(plan)) == 1
    join, = _references(plan, N.LogicalJoin)
    assert statistics.join_tags(plan) == {id(join): "ord0"}
    ctx.sql(text.replace("a > 7", "a > 9"), return_futures=False)
    got = ctx.sql(text, return_futures=False)
    assert ctx.last_report.tier == "compiled"
    span = _dispatch(ctx)
    assert span.attrs["shared_subplans"] == 1
    sites = [t for _, _, t in _entry(span).meta["agg_sites"]]
    assert [t for t in sites if t.startswith("agg")] == ["agg0"]
    frame = ctx.sql("SELECT a, g, b FROM t", return_futures=False)
    want = frame.loc[frame["a"] > 7].groupby("g", as_index=False)["b"].sum()
    got = got.sort_values("xg")
    assert got["xg"].tolist() == want["g"].tolist()
    np.testing.assert_allclose(got["total"].to_numpy(), want["b"].to_numpy(),
                               rtol=1e-12)


def test_a_unified_plan_cut_into_stages_answers_the_same(tpch, fresh):
    """The degradation ladder re-enters with the plan it was given: a DAG,
    already hoisted."""
    context, frames = tpch
    shape = _shape("q15")
    params = shape.params_at(1200)
    plan = identity._maybe_parameterize(
        _plan(context, shape.SQL.format(**params)), count=False)
    got = cm.try_execute_compiled(plan, context, _split_limit=1)
    assert got is not None
    got = got.to_pandas()
    want = shape.reference(frames, **params)
    assert got["s_suppkey"].tolist() == want["s_suppkey"].tolist()
    np.testing.assert_allclose(got["total_revenue"].to_numpy(),
                               want["total_revenue"].to_numpy(), rtol=1e-9)
