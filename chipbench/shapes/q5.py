"""TPC-H Q5, local supplier volume: six tables, five joins whose build
sides run from the 5 rows of region to the 1.5 million of orders, one of
them on two keys (the customer's nation is the supplier's), grouped by the
nation's name: a string column carried through every join to the
group-by."""
import datetime

import pandas as pd

from chipbench import ready_limit

NAME = "q5"

SQL = """
    SELECT n_name,
           SUM(l_extendedprice * (1 - l_discount)) AS revenue
    FROM customer, orders, lineitem, supplier, nation, region
    WHERE c_custkey = o_custkey
      AND l_orderkey = o_orderkey
      AND l_suppkey = s_suppkey
      AND c_nationkey = s_nationkey
      AND s_nationkey = n_nationkey
      AND n_regionkey = r_regionkey
      AND r_name = 'ASIA'
      AND o_orderdate >= DATE '{date_from}'
      AND o_orderdate < DATE '{date_to}'
    GROUP BY n_name
    ORDER BY revenue DESC, n_name
"""

SCAN_COLUMNS = {"customer": ("c_custkey", "c_nationkey"),
                "orders": ("o_custkey", "o_orderkey", "o_orderdate"),
                "lineitem": ("l_orderkey", "l_suppkey", "l_extendedprice",
                             "l_discount"),
                "supplier": ("s_suppkey", "s_nationkey"),
                "nation": ("n_nationkey", "n_regionkey", "n_name"),
                "region": ("r_regionkey", "r_name")}

#: TPC-H cl.2.4.5.3: REGION one of five, DATE the first of January of
#: 1993..1997 (25 texts).  The region stays the validation run's: a string
#: literal is baked into the program.  Five dates alone would be
#: result-cache replays, so DATE is any day from 1993-01-01 to 1997-01-01,
#: and the year that follows it stays inside the data.  A departure from
#: the spec's text: ORDER BY ends in n_name, a unique key, so that two
#: answers can be compared position by position.
SPACE = 1462
#: the spec's validation parameters (1994-01-01): every run's first text
FIRST = 365


def _a_year_on(day: datetime.date) -> datetime.date:
    if (day.month, day.day) == (2, 29):
        day = day.replace(day=28)
    return day.replace(year=day.year + 1)


def params_at(i: int) -> dict:
    start = datetime.date(1993, 1, 1) + datetime.timedelta(days=i)
    return {"date_from": start.isoformat(),
            "date_to": _a_year_on(start).isoformat()}


def sql(params: dict) -> str:
    ready_limit.asked(NAME, "joins")  # ends a run that set-up got no program for
    return SQL.format(**params)


def reference(frames: dict, date_from, date_to) -> pd.DataFrame:
    cu, od, li = frames["customer"], frames["orders"], frames["lineitem"]
    su, na, re = frames["supplier"], frames["nation"], frames["region"]
    asia = na[["n_nationkey", "n_name", "n_regionkey"]].merge(
        re.loc[re["r_name"] == "ASIA", ["r_regionkey"]],
        left_on="n_regionkey", right_on="r_regionkey")
    o = od.loc[(od["o_orderdate"] >= pd.Timestamp(date_from))
               & (od["o_orderdate"] < pd.Timestamp(date_to)),
               ["o_orderkey", "o_custkey"]]
    m = (o.merge(cu[["c_custkey", "c_nationkey"]], left_on="o_custkey",
                 right_on="c_custkey")
          .merge(li[["l_orderkey", "l_suppkey", "l_extendedprice",
                     "l_discount"]], left_on="o_orderkey",
                 right_on="l_orderkey")
          .merge(su[["s_suppkey", "s_nationkey"]], left_on="l_suppkey",
                 right_on="s_suppkey"))
    m = m[m["c_nationkey"] == m["s_nationkey"]]
    m = m.merge(asia, left_on="s_nationkey", right_on="n_nationkey")
    m = m.assign(revenue=m["l_extendedprice"] * (1 - m["l_discount"]))
    out = m.groupby("n_name", as_index=False)["revenue"].sum()
    return out.sort_values(["revenue", "n_name"], ascending=[False, True],
                           ignore_index=True)[["n_name", "revenue"]]
