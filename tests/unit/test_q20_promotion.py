"""TPC-H Q20, potential part promotion, on the compiled tier under the TPU
strategy: ``IN`` inside ``IN`` (two SEMI joins) and a correlated ``0.5 *
SUM(l_quantity)``, which the planner turns into a group-by on (l_partkey,
l_suppkey) joined back to partsupp on the two-part key.

The benchmark's generator draws ``p_name`` from five names, so on ITS data
every supplier holds enough of some part of any colour in every year and
the answer is the nation's suppliers whatever the date: a comparison that
cannot fail, which is why no cell runs Q20 (PERF.md section 7).  Here
``p_name`` is redrawn as dbgen makes it, five of its 92 colour words: a
supplier has one part of a colour or none, the answer moves with the date,
and a Q20 that drops its date, its colour or its correlated SUM does not
pass."""
import datetime

import numpy as np
import pandas as pd
import pytest

from chipbench.data import tpch_gen
from dask_sql_tpu import Context
from dask_sql_tpu.physical import compiled as cm, identity, programs
from dask_sql_tpu.physical.caps import _learned_caps
from dask_sql_tpu.sql.parser import parse_sql

#: lineitem has 360 000 rows here: joins and compaction sites engage
SF = 0.06

#: the specification's text (cl.2.4.20.2), its validation COLOR and NATION
SQL = """
    SELECT s_name, s_address
    FROM supplier, nation
    WHERE s_suppkey IN (
            SELECT ps_suppkey FROM partsupp
            WHERE ps_partkey IN (
                    SELECT p_partkey FROM part WHERE p_name LIKE 'forest%')
              AND ps_availqty > (
                    SELECT 0.5 * SUM(l_quantity)
                    FROM lineitem
                    WHERE l_partkey = ps_partkey
                      AND l_suppkey = ps_suppkey
                      AND l_shipdate >= DATE '{date_from}'
                      AND l_shipdate < DATE '{date_to}'))
      AND s_nationkey = n_nationkey
      AND n_name = 'CANADA'
    ORDER BY s_name
"""

#: dbgen's P_NAME words
COLOURS = (
    "almond antique aquamarine azure beige bisque black blanched blue blush "
    "brown burlywood burnished chartreuse chiffon chocolate coral cornflower "
    "cornsilk cream cyan dark deep dim dodger drab firebrick floral forest "
    "frosted gainsboro ghost goldenrod green grey honeydew hot indian ivory "
    "khaki lace lavender lawn lemon light lime linen magenta maroon medium "
    "metallic midnight mint misty moccasin navajo navy olive orange orchid "
    "pale papaya peach peru pink plum powder puff purple red rose rosy royal "
    "saddle salmon sandy seashell sienna sky slate smoke snow spring steel "
    "tan thistle tomato turquoise violet wheat white yellow").split()


def params_at(i: int) -> dict:
    """DATE any day from 1993-01-01 on, and the year that follows it."""
    start = datetime.date(1993, 1, 1) + datetime.timedelta(days=i)
    day = start.replace(day=28) if (start.month, start.day) == (2, 29) \
        else start
    return {"date_from": start.isoformat(),
            "date_to": day.replace(year=day.year + 1).isoformat()}


def reference(frames, date_from, date_to, colour="forest", half=0.5,
              dated=True, summed=True) -> pd.DataFrame:
    """Q20 in pandas; the keywords plant the faults a comparison has to
    catch: another colour, no date filter, no correlated SUM."""
    su, na, ps, pa, li = (frames["supplier"], frames["nation"],
                          frames["partsupp"], frames["part"],
                          frames["lineitem"])
    named = pa.loc[pa["p_name"].str.startswith(colour), "p_partkey"]
    stock = ps.loc[ps["ps_partkey"].isin(named),
                   ["ps_partkey", "ps_suppkey", "ps_availqty"]]
    l = li if not dated else li.loc[
        (li["l_shipdate"] >= pd.Timestamp(date_from))
        & (li["l_shipdate"] < pd.Timestamp(date_to))]
    shipped = l.groupby(["l_partkey", "l_suppkey"],
                        as_index=False)["l_quantity"].sum()
    # a pair that shipped nothing has no sum: the comparison is NULL, not true
    m = stock.merge(shipped, left_on=["ps_partkey", "ps_suppkey"],
                    right_on=["l_partkey", "l_suppkey"])
    held = m["ps_suppkey"] if not summed else m.loc[
        m["ps_availqty"] > half * m["l_quantity"], "ps_suppkey"]
    canada = na.loc[na["n_name"] == "CANADA", ["n_nationkey"]]
    s = su.merge(canada, left_on="s_nationkey", right_on="n_nationkey")
    s = s.loc[s["s_suppkey"].isin(held.unique())]
    return s.sort_values("s_name", ignore_index=True)[["s_name", "s_address"]]


@pytest.fixture(scope="module")
def tpch():
    frames = tpch_gen.generate(SF, 43)
    rng = np.random.RandomState(43)
    words = np.array(COLOURS)
    part = frames["part"].copy()
    part["p_name"] = [" ".join(words[rng.choice(len(words), 5, False)])
                      for _ in range(len(part))]
    # dbgen draws ps_availqty from 1..9999, under which half a year's
    # shipments of a pair never reach: scaled to theirs, the SUM decides
    partsupp = frames["partsupp"].copy()
    partsupp["ps_availqty"] = rng.randint(1, 60, len(partsupp))
    frames = {**frames, "part": part, "partsupp": partsupp}
    ctx = Context()
    for name, frame in frames.items():
        ctx.create_table(name, frame)
    return ctx, frames


@pytest.fixture
def tpu_strategy(monkeypatch):
    monkeypatch.setenv("DSQL_STRATEGY", "tpu")
    monkeypatch.delenv("DSQL_CAPS_FILE", raising=False)
    programs._cache.clear()
    _learned_caps.clear()


def _names(frame):
    return frame["s_name"].astype(str).tolist()


def _plan(context, sql):
    return context._get_plan(parse_sql(sql)[0].query, sql)


DAYS = (365, 0, 730, 1461)


def test_the_answer_moves_with_every_part_of_the_text(tpch):
    """What makes the comparisons below worth making: on this data the
    reference's answer depends on the date, the colour, the SUM and its
    half."""
    _, frames = tpch
    answers = {i: _names(reference(frames, **params_at(i))) for i in DAYS}
    assert all(answers.values())
    assert len({tuple(a) for a in answers.values()}) == len(DAYS)
    first = params_at(DAYS[0])
    for fault in ({"colour": "ivory"}, {"dated": False}, {"summed": False},
                  {"half": 5.0}):
        assert _names(reference(frames, **first, **fault)) \
            != answers[DAYS[0]], fault


@pytest.mark.parametrize("day", DAYS)
def test_q20_answers_as_its_reference(tpch, tpu_strategy, day):
    ctx, frames = tpch
    params = params_at(day)
    got = ctx.sql(SQL.format(**params), return_futures=False)
    assert ctx.last_report.tier == "compiled"
    want = reference(frames, **params)
    assert _names(got) == _names(want)
    assert got["s_address"].astype(str).tolist() \
        == want["s_address"].astype(str).tolist()


def test_one_program_serves_new_dates_and_says_what_it_holds(
        tpch, tpu_strategy):
    ctx, frames = tpch
    ctx.sql(SQL.format(**params_at(DAYS[0])), return_futures=False)
    compiles = cm.stats["compiles"] + cm.stats["recompiles"]
    for day in (DAYS[0] + 1, DAYS[0] + 2):
        got = ctx.sql(SQL.format(**params_at(day)), return_futures=False)
        assert _names(got) == _names(reference(frames, **params_at(day)))
        report = ctx.last_report
        assert report.tier == "compiled"
        span, = [s for s in report.root.walk() if s.name == "dispatch"]
        assert (span.attrs["semi_joins"],
                span.attrs["scalar_subqueries"]) == (2, 0)
    assert cm.stats["compiles"] + cm.stats["recompiles"] == compiles


def test_two_dates_have_one_program_key(tpch, monkeypatch):
    context, _ = tpch
    plans = [_plan(context, SQL.format(**params_at(i))) for i in (365, 3)]
    hoisted = [identity._maybe_parameterize(p, count=False) for p in plans]
    keys = [identity.program_key(p, context) for p in hoisted]
    assert keys[0].key == keys[1].key and len(keys[0].params) == 2
    monkeypatch.setenv("DSQL_PARAM_PLANS", "0")
    assert all(identity._maybe_parameterize(p) is p for p in plans)
    keys = [identity.program_key(p, context) for p in plans]
    assert keys[0].key != keys[1].key and keys[0].params == []
