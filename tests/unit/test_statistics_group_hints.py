"""Where a grouped aggregate's capacity starts when nothing was learned or
hinted (``statistics.counted_groups``, asked by the tracer that gives the
aggregate its tag: ``compiled._LogicalAggregate``, PR 43): from the group
count the ingest statistics hold, for a single base column grouped over
every row of its table.  TPC-H Q18's inner ``GROUP BY l_orderkey`` starts in
its class and not three overflows under it; an estimated count gives
nothing, because a group cap never shrinks.  (The file's name arms the
statistics: ``tests/conftest.py`` pins ``DSQL_ADAPTIVE=0`` elsewhere.)"""
import importlib

import numpy as np
import pandas as pd
import pytest

from chipbench.data import tpch_gen
from dask_sql_tpu import Context
from dask_sql_tpu.physical import compiled as cm, programs
from dask_sql_tpu.physical.caps import _learned_caps
from dask_sql_tpu.runtime import statistics as st
from dask_sql_tpu.sql.parser import parse_sql


@pytest.fixture(scope="module")
def tpch():
    frames = tpch_gen.generate(0.01, 43)
    ctx = Context()
    for name, frame in frames.items():
        ctx.create_table(name, frame)
    return ctx, frames


@pytest.fixture(scope="module")
def ctx():
    rng = np.random.RandomState(43)
    c = Context()
    c.create_table("fact", pd.DataFrame({
        # a child table beside its parent's key: never decreasing, four
        # rows a key, over a domain too wide to bincount
        "k": np.repeat(np.arange(5000) * 1000, 4),
        "g": rng.randint(0, 7, 20000),
        # a key-like column in no order: its distinct count is a sample's
        "w": rng.permutation(20000) * 977,
        "s": rng.choice(["a", "b", "c"], 20000), "v": rng.rand(20000)}))
    return c


def _plan(context, sql):
    return context._get_plan(parse_sql(sql)[0].query, sql)


def _shape(name):
    return importlib.import_module("chipbench.shapes." + name)


def _grouped(plan, context):
    """The plan's grouped aggregates by the base column of their first key
    (``?`` where the key is followed back to none)."""
    from dask_sql_tpu.plan import nodes as N
    found = {}

    def walk(rel):
        if isinstance(rel, N.LogicalAggregate) and rel.group_keys:
            cs = st.column_stats_for(rel.input, rel.group_keys[0], context)
            found["?" if cs is None else cs.name] = rel
        for i in rel.inputs:
            walk(i)

    walk(plan)
    return found


def test_q18s_inner_aggregate_starts_in_its_class(tpch):
    context, frames = tpch
    shape = _shape("q18")
    plan = _plan(context, shape.SQL.format(**shape.params_at(shape.FIRST)))
    orders = frames["lineitem"]["l_orderkey"].nunique()
    # several grouped aggregates: the host-side walk cannot number them and
    # hints none, as ever; the tracer asks for the one it stands at
    assert st.compiled_cap_hints(plan, context) == {}
    classes = {key: st.counted_groups(rel, context)
               for key, rel in _grouped(plan, context).items()}
    inner = classes.pop("l_orderkey")
    assert orders < inner <= 2 * orders and inner & (inner - 1) == 0
    # the outer one, over three joins, has an estimate and no count
    assert classes and set(classes.values()) == {None}


def test_the_count_is_the_capacity_the_program_runs_with(tpch, monkeypatch):
    monkeypatch.setenv("DSQL_STRATEGY", "tpu")
    context, frames = tpch
    programs._cache.clear()
    _learned_caps.clear()
    shape = _shape("q18")
    params = shape.params_at(shape.FIRST)
    plan = _plan(context, shape.SQL.format(**params))
    counted = st.counted_groups(_grouped(plan, context)["l_orderkey"], context)
    overflows = cm.stats["recompiles_overflow"]
    got = context.sql(shape.SQL.format(**params), return_futures=False)
    assert len(got) == len(shape.reference(frames, **params))
    final = [e for e in programs._cache.values()
             if e is not programs._UNSUPPORTED][-1]
    caps = {tag: cap for (_, _, tag), cap in zip(final.meta["agg_sites"],
                                                 final.meta["ngroup_caps"])}
    assert caps["agg0"] == counted
    assert caps["agg1"] <= cm._caps.DEFAULT_GROUP_CAP
    assert cm.stats["recompiles_overflow"] == overflows


def test_a_hinted_capacity_is_not_asked_over(tpch, monkeypatch):
    """What a hint gave, or a run learned, stays what the tracer reads."""
    monkeypatch.setenv("DSQL_STRATEGY", "tpu")
    context, _ = tpch
    asked = []
    real = st.counted_groups
    monkeypatch.setattr(st, "counted_groups",
                        lambda rel, c: asked.append(rel) or real(rel, c))
    text = ("SELECT o_orderpriority, COUNT(*) AS n FROM orders "
            "WHERE o_orderkey IN (SELECT l_orderkey FROM lineitem "
            "GROUP BY l_orderkey HAVING SUM(l_quantity) > {q}) "
            "GROUP BY o_orderpriority")

    def agg0_after(q):
        programs._cache.clear()
        _learned_caps.clear()
        del asked[:]
        context.sql(text.format(q=q), return_futures=False)
        final = [e for e in programs._cache.values()
                 if e is not programs._UNSUPPORTED][-1]
        return final.meta["ngroup_caps"][0]

    assert agg0_after(100) == real(asked[0], context) == 16384
    monkeypatch.setattr(st, "compiled_cap_hints",
                        lambda plan, c: {"agg0": 1 << 15})
    assert agg0_after(120) == 1 << 15 and asked == []


#: text: the class of each of its plan's grouped aggregates, by key; None
#: where the count is an estimate
CASES = {
    # counted: one base column over every row of its table
    "SELECT k, n FROM (SELECT k, COUNT(*) AS n FROM fact GROUP BY k) x "
    "WHERE k IN (SELECT g FROM fact GROUP BY g HAVING SUM(v) > 1)":
        {"k": 8192, "g": 64},
    # a filter below the aggregate: the count is an estimate
    "SELECT k, n FROM (SELECT k, COUNT(*) AS n FROM fact WHERE v > 0.5 "
    "GROUP BY k) x WHERE k IN (SELECT g FROM fact GROUP BY g)":
        {"k": None, "g": 64},
    # two keys: their product is a bound, not a count
    "SELECT k FROM (SELECT k, g, COUNT(*) AS n FROM fact GROUP BY k, g) x "
    "WHERE k IN (SELECT s FROM fact GROUP BY s)": {"k": None, "s": None},
    # a distinct count that a sample estimated is not a count
    "SELECT w, n FROM (SELECT w, COUNT(*) AS n FROM fact GROUP BY w) x "
    "WHERE w IN (SELECT g FROM fact GROUP BY g)": {"w": None, "g": 64},
}


@pytest.mark.parametrize("text", sorted(CASES))
def test_counted_aggregates_have_a_class_and_estimated_ones_none(ctx, text):
    assert {key: st.counted_groups(rel, ctx)
            for key, rel in _grouped(_plan(ctx, text), ctx).items()} == CASES[text]


def test_runs_are_counted_where_a_column_never_decreases(ctx, tpch):
    stats = ctx.schema[ctx.schema_name].tables["fact"].stats
    assert stats.col("k").runs == 5000 and not stats.col("k").increasing
    assert stats.col("g").runs is None and stats.col("w").runs is None
    context, frames = tpch
    lineitem = context.schema[context.schema_name].tables["lineitem"].stats
    orders = frames["orders"]["o_orderkey"].nunique()
    assert lineitem.col("l_orderkey").runs == orders
    assert lineitem.col("l_partkey").runs is None
    # what a strided sample makes of such a column once it has more rows
    # than the sample: nearly every value once, "as many as rows"
    wide = np.repeat(np.arange(50000) * 1000, 4)
    assert st._sampled_ndv(wide) > 2 * 50000


def test_a_single_aggregate_keeps_its_estimate(ctx):
    """One grouped aggregate is ``agg0`` whatever the plan: as before."""
    plan = _plan(ctx, "SELECT k, SUM(v) AS s FROM fact WHERE v > 0.5 "
                      "GROUP BY k")
    hints = st.compiled_cap_hints(plan, ctx)
    assert set(hints) == {"agg0"} and hints["agg0"] >= 64


def test_the_switch_off_counts_nothing(ctx, monkeypatch):
    plan = _plan(ctx, "SELECT k, COUNT(*) AS n FROM fact GROUP BY k")
    assert st.counted_groups(_grouped(plan, ctx)["k"], ctx) == 8192
    monkeypatch.setenv("DSQL_ADAPTIVE", "0")
    assert st.counted_groups(_grouped(plan, ctx)["k"], ctx) is None


#: text: whether each of its plan's grouped aggregates takes its groups from
#: the runs of its key (``statistics.grouped_by_runs``), by key; and the
#: word the plan's capacities carry (``run_group_hints``)
RUN_CASES = {
    # a base column that never decreases, over every row of its table
    "SELECT k, n FROM (SELECT k, COUNT(*) AS n FROM fact GROUP BY k) x "
    "WHERE k IN (SELECT g FROM fact GROUP BY g HAVING SUM(v) > 1)":
        ({"k": True, "g": False}, {"runs": 1}),
    # a filter: the runs survive it, the count of groups does not
    "SELECT k, n FROM (SELECT k, COUNT(*) AS n FROM fact WHERE v > 0.5 "
    "GROUP BY k) x": ({"k": False}, {}),
    # a join below the aggregate
    "SELECT a.k, COUNT(*) AS n FROM fact a JOIN fact b ON a.k = b.w "
    "GROUP BY a.k": ({"k": False}, {}),
    # two keys, the first in order
    "SELECT k, g, COUNT(*) AS n FROM fact GROUP BY k, g": ({"k": False}, {}),
    # a key in no order, and a string
    "SELECT w, n FROM (SELECT w, COUNT(*) AS n FROM fact GROUP BY w) x "
    "WHERE w IN (SELECT s FROM fact GROUP BY s)":
        ({"w": False, "s": False}, {}),
    # a computed key is no base column
    "SELECT k2, COUNT(*) AS n FROM (SELECT k + 1 AS k2 FROM fact) x "
    "GROUP BY k2": ({"?": False}, {}),
    # through a project that only renames, and strictly increasing: every
    # row a group
    "SELECT r, SUM(v) AS s FROM (SELECT u AS r, v FROM uniq) x GROUP BY r":
        ({"u": True}, {"runs": 1}),
    # a NULL in the key: no order was counted
    "SELECT kn, COUNT(*) AS n FROM uniq GROUP BY kn": ({"kn": False}, {}),
    # two such aggregates in one plan are two of the count
    "SELECT x.k, x.n, y.s FROM (SELECT k, COUNT(*) AS n FROM fact "
    "GROUP BY k) x JOIN (SELECT u, SUM(v) AS s FROM uniq GROUP BY u) y "
    "ON x.k = y.u": ({"k": True, "u": True}, {"runs": 2}),
}


@pytest.fixture(scope="module")
def run_ctx(ctx):
    rng = np.random.RandomState(45)
    uniq = pd.DataFrame({
        "u": np.arange(3000) * 5 + 1, "v": rng.rand(3000),
        "kn": pd.array(np.repeat(np.arange(1000), 3), dtype="Int64")})
    uniq.loc[17, "kn"] = pd.NA
    ctx.create_table("uniq", uniq)
    return ctx


@pytest.mark.parametrize("text", sorted(RUN_CASES))
def test_the_groups_are_runs_only_where_the_statistics_hold_it(run_ctx, text):
    by_key, word = RUN_CASES[text]
    plan = _plan(run_ctx, text)
    assert {key: st.grouped_by_runs(rel, run_ctx)
            for key, rel in _grouped(plan, run_ctx).items()} == by_key
    assert st.run_group_hints(plan, run_ctx) == word


def test_the_switch_off_hints_no_runs(ctx, monkeypatch):
    plan = _plan(ctx, "SELECT k, COUNT(*) AS n FROM fact GROUP BY k")
    assert st.run_group_hints(plan, ctx) == {st.RUN_GROUPS_TAG: 1}
    monkeypatch.setenv("DSQL_ADAPTIVE", "0")
    assert st.run_group_hints(plan, ctx) == {}
    assert not st.grouped_by_runs(_grouped(plan, ctx)["k"], ctx)
