"""TPC-H Q3, shipping priority: the customers of one market segment joined
to their orders before a date and to the line items shipped after it,
grouped by order (about 11 600 groups at SF1: a dynamic domain, not a
static one), the ten largest revenues.  Two joins whose build sides are
30 000 and 1.5 million rows, a grouped aggregate, a top-N."""
import datetime

import pandas as pd

from chipbench import ready_limit

NAME = "q3"

SQL = """
    SELECT l_orderkey,
           SUM(l_extendedprice * (1 - l_discount)) AS revenue,
           o_orderdate, o_shippriority
    FROM customer, orders, lineitem
    WHERE c_mktsegment = 'BUILDING'
      AND c_custkey = o_custkey
      AND l_orderkey = o_orderkey
      AND o_orderdate < DATE '{date}'
      AND l_shipdate > DATE '{date}'
    GROUP BY l_orderkey, o_orderdate, o_shippriority
    ORDER BY revenue DESC, o_orderdate, l_orderkey
    LIMIT 10
"""

SCAN_COLUMNS = {"customer": ("c_mktsegment", "c_custkey"),
                "orders": ("o_custkey", "o_orderkey", "o_orderdate",
                           "o_shippriority"),
                "lineitem": ("l_orderkey", "l_extendedprice", "l_discount",
                             "l_shipdate")}

#: TPC-H cl.2.4.3.3: SEGMENT one of five, DATE a day of March 1995 (31
#: texts).  The engine bakes string literals into the compiled program
#: (plan/parameterize.py hoists numeric and date operands only), so the
#: segment stays the validation run's.  31 dates alone would be
#: result-cache replays, so DATE is any day from 1994-09-01 to 1995-09-30:
#: the orders a date selects (those placed in the 121 days before it that
#: ship after it) stay between 11 159 and 11 655 groups at SF1, one capacity
#: class.  A departure from the spec's text: ORDER BY ends in l_orderkey,
#: a unique key, so that two answers can be compared position by position.
SPACE = 395
#: the spec's validation parameters (1995-03-15): every run's first text
FIRST = 195


def params_at(i: int) -> dict:
    day = datetime.date(1994, 9, 1) + datetime.timedelta(days=i)
    return {"date": day.isoformat()}


def sql(params: dict) -> str:
    ready_limit.asked(NAME, "joins")  # ends a run that set-up got no program for
    return SQL.format(**params)


def reference(frames: dict, date) -> pd.DataFrame:
    cu, od, li = frames["customer"], frames["orders"], frames["lineitem"]
    day = pd.Timestamp(date)
    c = cu.loc[cu["c_mktsegment"] == "BUILDING", ["c_custkey"]]
    o = od.loc[od["o_orderdate"] < day,
               ["o_orderkey", "o_custkey", "o_orderdate", "o_shippriority"]]
    l = li.loc[li["l_shipdate"] > day,
               ["l_orderkey", "l_extendedprice", "l_discount"]]
    m = c.merge(o, left_on="c_custkey", right_on="o_custkey").merge(
        l, left_on="o_orderkey", right_on="l_orderkey")
    m = m.assign(revenue=m["l_extendedprice"] * (1 - m["l_discount"]))
    g = m.groupby(["l_orderkey", "o_orderdate", "o_shippriority"],
                  as_index=False)["revenue"].sum()
    g = g.sort_values(["revenue", "o_orderdate", "l_orderkey"],
                      ascending=[False, True, True],
                      ignore_index=True).head(10)
    return g[["l_orderkey", "revenue", "o_orderdate", "o_shippriority"]]
