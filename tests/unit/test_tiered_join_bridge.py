"""The tier decision for a cold plan that joins two big inputs under the TPU
strategy: its first arrival pays the compile and is not answered by the
eager tier, whose join has only the sort formulation
(``compiled._eager_bridge_sorts``)."""
import time

import numpy as np
import pandas as pd
import pytest

from dask_sql_tpu import Context
from dask_sql_tpu.physical import compiled
from dask_sql_tpu.runtime import telemetry as tel

BIG_BIG = ("SELECT a.k, SUM(x * y) AS v FROM a, b WHERE a.k = b.k "
           "GROUP BY a.k ORDER BY a.k")
BIG_SMALL = ("SELECT a.k, SUM(x * z) AS v FROM a, s WHERE a.k = s.k "
             "GROUP BY a.k ORDER BY a.k")


@pytest.fixture
def context(monkeypatch):
    monkeypatch.delenv("DSQL_TIERED", raising=False)
    monkeypatch.setattr(compiled, "SORT_ROWS_MAX", 100)
    c = Context()
    n = 1000
    c.create_table("a", pd.DataFrame({"k": np.arange(n) % 500,
                                      "x": np.arange(n) * 1.0}))
    c.create_table("b", pd.DataFrame({"k": np.arange(500),
                                      "y": np.arange(500) * 2.0}))
    c.create_table("s", pd.DataFrame({"k": np.arange(50),
                                      "z": np.arange(50) * 2.0}))
    yield c
    give_up = time.monotonic() + 60
    while compiled.inflight_background_compiles() \
            and time.monotonic() < give_up:
        time.sleep(0.05)


def _plan(c, text):
    from dask_sql_tpu.sql.parser import parse_sql

    return c._get_plan(parse_sql(text)[0].query, text)


def _ask(c, text):
    before = dict(tel.REGISTRY.counters())
    frame = c.sql(text, return_futures=False)
    now = tel.REGISTRY.counters()
    return frame, tel.last_report().tier, {
        k: v - before.get(k, 0) for k, v in now.items()
        if v != before.get(k, 0)}


@pytest.mark.parametrize("strategy, text, tier, probe", [
    ("tpu", BIG_BIG, "compiled", "compiled-cold"),
    ("tpu", BIG_SMALL, "eager-compiling", "eager-compiling"),
    ("host", BIG_BIG, "eager-compiling", "eager-compiling"),
])
def test_who_answers_a_cold_join(context, monkeypatch, strategy, text, tier,
                                 probe):
    monkeypatch.setenv("DSQL_STRATEGY", strategy)
    assert compiled.tier_probe(_plan(context, text), context) == probe
    frame, got, moved = _ask(context, text)
    assert got == tier
    assert moved.get("compiles", 0) >= 1
    assert moved.get("served_eager_while_compiling", 0) == (
        1 if tier == "eager-compiling" else 0)
    want = context.sql(text.replace("ORDER BY a.k", "ORDER BY 1"),
                       return_futures=False)
    pd.testing.assert_frame_equal(frame, want)


def test_the_second_arrival_of_a_bridged_join_is_a_hit(context, monkeypatch):
    monkeypatch.setenv("DSQL_STRATEGY", "tpu")
    _ask(context, BIG_BIG)
    _, tier, moved = _ask(context, BIG_BIG.replace("x * y", "x * y * 2"))
    assert tier == "compiled"
    assert moved.get("served_eager_while_compiling", 0) == 0


def test_scans_decide_not_filters(context):
    from dask_sql_tpu.plan.nodes import LogicalJoin

    def joins(rel):
        found = [rel] if isinstance(rel, LogicalJoin) else []
        for i in rel.inputs:
            found += joins(i)
        return found

    plan = _plan(context, BIG_BIG + " LIMIT 3")
    assert joins(plan)
    assert compiled._eager_bridge_sorts(plan, context, True)
    assert not compiled._eager_bridge_sorts(plan, context, False)
