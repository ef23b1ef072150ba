"""The eager executor and a CTE read twice, once below ``= (SELECT MAX(..))``
(TPC-H Q15, PR 43).  Two things hold its float equality together:

- a subtree the plan holds twice runs ONCE (``RelExecutor.execute``'s memo
  by ``physical/shared.py::read_twice``, the one finder of both tiers): the
  copies have one canonical text (``result_cache.canonical_plan``, by
  value) and every copy is handed the one result, so both sides of the
  ``=`` are read from the same array;
- a floating scalar subquery's value stays on the device
  (``rex/evaluate.py::_eval_scalar_subquery``): read back to the host and
  sent again as a constant it did not compare equal to the element it was
  taken from on a TPU, whose float64 is emulated (Q15's first text at SF1
  came back empty on one data set in seventeen, with the CTE made once
  too)."""
import importlib

import numpy as np
import pandas as pd
import pytest

from chipbench.data import tpch_gen
from dask_sql_tpu import Context
from dask_sql_tpu.physical import shared
from dask_sql_tpu.physical.rel import executor as ex
from dask_sql_tpu.plan import nodes as N
from dask_sql_tpu.sql.parser import parse_sql


@pytest.fixture(scope="module")
def ctx():
    rng = np.random.RandomState(43)
    c = Context()
    c.create_table("t", pd.DataFrame({
        "a": np.arange(400), "g": np.arange(400) % 17,
        "b": np.round(rng.rand(400) * 100, 3)}))
    return c


@pytest.fixture(scope="module")
def tpch():
    frames = tpch_gen.generate(0.01, 43)
    c = Context()
    for name, frame in frames.items():
        c.create_table(name, frame)
    return c, frames


@pytest.fixture
def executed(monkeypatch):
    """The node types the eager executor ran, a node each time it ran."""
    ran = []
    real = ex.RelExecutor._execute

    def spy(self, rel):
        ran.append(type(rel).__name__)
        return real(self, rel)

    monkeypatch.setattr(ex.RelExecutor, "_execute", spy)
    return ran


def _plan(context, sql):
    return context._get_plan(parse_sql(sql)[0].query, sql)


CTE = "WITH r AS (SELECT g, SUM(b) AS total FROM t {where}GROUP BY g) "

#: a CTE read twice, once inside a scalar subquery's body
TOP = (CTE.format(where="") + "SELECT g, total FROM r WHERE total = "
       "(SELECT MAX(total) FROM r)")

#: text: how many of its plan's aggregates and joins are copies of another
SHARED = {
    TOP: 2,
    # .. and with >= in place of =, or no subquery at all: whatever reads it
    CTE.format(where="") + "SELECT g FROM r WHERE total >= "
    "(SELECT MAX(total) FROM r)": 2,
    CTE.format(where="WHERE a > 7 ") + "SELECT x.g, y.g FROM r x, r y "
    "WHERE x.total = y.total AND x.g < y.g": 2,
    # one aggregate: nothing is held twice
    "SELECT g, SUM(b) AS total FROM t GROUP BY g": 0,
    # two that differ in a literal are two subtrees
    "SELECT g FROM (SELECT g, SUM(b) AS total FROM t WHERE a > 7 GROUP BY g) "
    "x WHERE total > (SELECT MAX(total) FROM (SELECT g, SUM(b) AS total "
    "FROM t WHERE a > 8 GROUP BY g) y)": 0,
    # nothing volatile is shared
    CTE.format(where="WHERE b > RAND() ") + "SELECT g FROM r WHERE total = "
    "(SELECT MAX(total) FROM r)": 0,
}


@pytest.mark.parametrize("text", sorted(SHARED))
def test_the_subtrees_a_plan_holds_twice(ctx, text):
    twice = shared.read_twice(_plan(ctx, text))
    assert len(twice) == SHARED[text]
    assert len(set(twice.values())) == (1 if twice else 0)


def test_a_cte_under_a_scalar_subquery_runs_once(ctx, executed, monkeypatch):
    monkeypatch.setenv("DSQL_COMPILE", "0")
    got = ctx.sql(TOP, return_futures=False)
    assert ctx.last_report.tier == "eager"
    # the grouped aggregate once, and the body's MAX over it
    assert executed.count("LogicalAggregate") == 2
    t = ctx.sql("SELECT g, SUM(b) AS total FROM t GROUP BY g",
                return_futures=False)
    want = t.loc[t["total"] == t["total"].max()]
    assert got["g"].tolist() == want["g"].tolist() and len(got) == 1
    assert got["total"].tolist() == want["total"].tolist()


def test_where_the_compiled_tier_refuses_the_plan(ctx, executed):
    """BIT_OR is no aggregate of the compiled tier's: the plan is the eager
    tier's whatever the engine would rather, and its float equality holds
    because both sides read one result."""
    text = ("WITH r AS (SELECT g, SUM(b) AS total, BIT_OR(a) AS bits FROM t "
            "GROUP BY g) SELECT g, total, bits FROM r WHERE total = "
            "(SELECT MAX(total) FROM r WHERE bits >= 0)")
    got = ctx.sql(text, return_futures=False)
    assert ctx.last_report.tier == "eager"
    assert executed.count("LogicalAggregate") == 2 and len(got) == 1


def test_q15_on_the_eager_tier_makes_its_revenue_once(tpch, executed,
                                                      monkeypatch):
    monkeypatch.setenv("DSQL_COMPILE", "0")
    context, frames = tpch
    shape = importlib.import_module("chipbench.shapes.q15")
    params = shape.params_at(shape.FIRST)
    plan = _plan(context, shape.SQL.format(**params))
    twice = shared.read_twice(plan)
    assert len(twice) == 2 and len(set(twice.values())) == 1
    got = context.sql(shape.SQL.format(**params), return_futures=False)
    assert context.last_report.tier == "eager"
    assert executed.count("LogicalAggregate") == 2
    want = shape.reference(frames, **params)
    assert got["s_suppkey"].tolist() == want["s_suppkey"].tolist()
    assert len(got) == 1


def test_every_copy_is_handed_the_same_table(ctx):
    plan = _plan(ctx, TOP)
    copies = []

    def walk(rel):
        if isinstance(rel, N.LogicalAggregate) and rel.group_keys:
            copies.append(rel)
        for i in rel.inputs:
            walk(i)
        for rex in (*getattr(rel, "exprs", ()),
                    getattr(rel, "condition", None)):
            for o in getattr(rex, "operands", ()):
                if isinstance(o, N.RexScalarSubquery):
                    walk(o.plan)

    walk(plan)
    assert len(copies) == 2 and copies[0] is not copies[1]
    executor = ex.RelExecutor(ctx)
    executor.execute(plan)
    assert executor.execute(copies[0]) is executor.execute(copies[1])


# --- and the scalar subquery's value stays on the device where it floats ----

def _scalar_of(ctx, text):
    """What the eager executor makes of the text's scalar subquery, beside a
    table of three rows."""
    from dask_sql_tpu.physical.rex import evaluate as ev
    from dask_sql_tpu.table import Table

    plan = _plan(ctx, "SELECT a FROM t WHERE b > (" + text + ")")
    flt = plan
    while not isinstance(flt, N.LogicalFilter):
        flt = flt.input
    rex, = [o for o in flt.condition.operands
            if isinstance(o, N.RexScalarSubquery)]
    outer = ex.RelExecutor(ctx).execute(flt.input)
    three = Table(outer.names, [c.take(np.arange(3)) for c in outer.columns])
    return ev.evaluate_rex(rex, three, ex.RelExecutor(ctx))


def test_a_floating_scalar_subquery_is_a_column_of_the_device_value(ctx):
    """Read back to the host and sent again as a constant, a float64 need
    not come back the same on a TPU: ``x = (SELECT MAX(x) ..)`` lost its
    row there (PR 43).  The value is broadcast from the device array, as
    the compiled tier's is."""
    from dask_sql_tpu.table import Column, Scalar

    got = _scalar_of(ctx, "SELECT MAX(b) FROM t")
    assert isinstance(got, Column) and got.data.shape == (3,)
    assert got.mask is None and got.stype.name == "DOUBLE"
    b = ctx.sql("SELECT b FROM t", return_futures=False)["b"]
    assert got.to_pylist() == [b.max()] * 3
    # what is exact on any device, and what is NULL, stays a Scalar
    whole = _scalar_of(ctx, "SELECT MAX(a) FROM t")
    assert isinstance(whole, Scalar) and whole.value == 399
    for text in ("SELECT MAX(b) FROM t WHERE a < 0",
                 "SELECT b FROM t WHERE a < 0",
                 "SELECT MAX(b / 0.0 - b / 0.0) FROM t WHERE a = 7"):
        none = _scalar_of(ctx, text)
        assert isinstance(none, Scalar) and none.is_null, text


@pytest.mark.parametrize("text, rows", [
    ("SELECT a FROM t WHERE b = (SELECT MAX(b) FROM t)", 1),
    ("SELECT a FROM t WHERE b >= (SELECT AVG(b) FROM t) "
     "AND b <= (SELECT MAX(b) FROM t WHERE a < 0)", 0),
    ("SELECT a, b - (SELECT MIN(b) FROM t) AS over FROM t WHERE a < 5", 5),
    ("SELECT (SELECT AVG(b) FROM t) AS mean", 1)])
def test_the_eager_tier_answers_with_it_as_sqlite_does(ctx, text, rows,
                                                      monkeypatch):
    import sqlite3

    monkeypatch.setenv("DSQL_COMPILE", "0")
    got = ctx.sql(text, return_futures=False)
    assert ctx.last_report.tier == "eager" and len(got) == rows
    db = sqlite3.connect(":memory:")
    ctx.sql("SELECT a, g, b FROM t", return_futures=False).to_sql(
        "t", db, index=False)
    want = pd.read_sql(text, db)
    assert len(want) == rows
    for column in want.columns:
        np.testing.assert_allclose(got[column].to_numpy(dtype=float),
                                   want[column].to_numpy(dtype=float),
                                   rtol=1e-12)
