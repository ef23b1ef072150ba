"""TPC-H Q10, returned item reporting: three months of orders joined to
their customers, to the returned line items and to the nations, grouped by
customer (about 37 000 groups at SF1) on seven keys, five of them strings
that go through the joins, the group-by and the top-N out to the caller."""
import datetime

import pandas as pd

from chipbench import ready_limit

NAME = "q10"

SQL = """
    SELECT c_custkey, c_name,
           SUM(l_extendedprice * (1 - l_discount)) AS revenue,
           c_acctbal, n_name, c_address, c_phone, c_comment
    FROM customer, orders, lineitem, nation
    WHERE c_custkey = o_custkey
      AND l_orderkey = o_orderkey
      AND o_orderdate >= DATE '{date_from}'
      AND o_orderdate < DATE '{date_to}'
      AND l_returnflag = 'R'
      AND c_nationkey = n_nationkey
    GROUP BY c_custkey, c_name, c_acctbal, c_phone, n_name, c_address, c_comment
    ORDER BY revenue DESC, c_custkey
    LIMIT 20
"""

SCAN_COLUMNS = {"customer": ("c_custkey", "c_name", "c_acctbal", "c_address",
                             "c_phone", "c_comment", "c_nationkey"),
                "orders": ("o_custkey", "o_orderkey", "o_orderdate"),
                "lineitem": ("l_orderkey", "l_extendedprice", "l_discount",
                             "l_returnflag"),
                "nation": ("n_nationkey", "n_name")}

#: TPC-H cl.2.4.10.3: DATE the first day of a month from 1993-02 to
#: 1995-01 (24 texts).  A text seen before is a result-cache replay, so
#: DATE is any day from 1993-02-01 to 1995-01-01, and the three months that
#: follow it (35 773 to 38 277 groups at SF1, one capacity class).  A departure from the spec's text: ORDER BY ends in
#: c_custkey, a unique key, so that two answers can be compared position by
#: position.
SPACE = 700
#: the spec's validation parameters (1993-10-01): every run's first text
FIRST = 242


def _three_months_on(day: datetime.date) -> datetime.date:
    month = day.month + 3
    year, month = day.year + (month - 1) // 12, (month - 1) % 12 + 1
    last = (datetime.date(year + month // 12, month % 12 + 1, 1)
            - datetime.timedelta(days=1)).day
    return datetime.date(year, month, min(day.day, last))


def params_at(i: int) -> dict:
    start = datetime.date(1993, 2, 1) + datetime.timedelta(days=i)
    return {"date_from": start.isoformat(),
            "date_to": _three_months_on(start).isoformat()}


def sql(params: dict) -> str:
    ready_limit.asked(NAME, "joins")  # ends a run that set-up got no program for
    return SQL.format(**params)


def reference(frames: dict, date_from, date_to) -> pd.DataFrame:
    cu, od, li, na = (frames["customer"], frames["orders"],
                      frames["lineitem"], frames["nation"])
    o = od.loc[(od["o_orderdate"] >= pd.Timestamp(date_from))
               & (od["o_orderdate"] < pd.Timestamp(date_to)),
               ["o_orderkey", "o_custkey"]]
    l = li.loc[li["l_returnflag"] == "R",
               ["l_orderkey", "l_extendedprice", "l_discount"]]
    keys = ["c_custkey", "c_name", "c_acctbal", "c_phone", "n_name",
            "c_address", "c_comment"]
    m = (cu[["c_custkey", "c_name", "c_acctbal", "c_phone", "c_address",
             "c_comment", "c_nationkey"]]
         .merge(o, left_on="c_custkey", right_on="o_custkey")
         .merge(l, left_on="o_orderkey", right_on="l_orderkey")
         .merge(na[["n_nationkey", "n_name"]], left_on="c_nationkey",
                right_on="n_nationkey"))
    m = m.assign(revenue=m["l_extendedprice"] * (1 - m["l_discount"]))
    g = m.groupby(keys, as_index=False)["revenue"].sum()
    g = g.sort_values(["revenue", "c_custkey"], ascending=[False, True],
                      ignore_index=True).head(20)
    return g[["c_custkey", "c_name", "revenue", "c_acctbal", "n_name",
              "c_address", "c_phone", "c_comment"]]
