"""TPC-H Q4, order priority checking: the orders of one quarter that have at
least one line item received after its commit date, counted by priority.
An ``EXISTS`` over lineitem: the planner makes it a SEMI join whose build
side is the late line items (3.8 M of lineitem's 6 M rows at SF1) and whose
probe side the quarter's orders; the five counts are a static-domain
group-by."""
import datetime

import pandas as pd

from chipbench import ready_limit, warm_limit

NAME = "q4"

SQL = """
    SELECT o_orderpriority, COUNT(*) AS order_count
    FROM orders
    WHERE o_orderdate >= DATE '{date_from}'
      AND o_orderdate < DATE '{date_to}'
      AND EXISTS (
            SELECT * FROM lineitem
            WHERE l_orderkey = o_orderkey
              AND l_commitdate < l_receiptdate)
    GROUP BY o_orderpriority
    ORDER BY o_orderpriority
"""

SCAN_COLUMNS = {"orders": ("o_orderkey", "o_orderdate", "o_orderpriority"),
                "lineitem": ("l_orderkey", "l_commitdate", "l_receiptdate")}

#: TPC-H cl.2.4.4.3: DATE the first day of a month from 1993-01 to 1997-10
#: (58 texts), and the three months that follow it.  A text seen before is
#: a result-cache replay, so DATE is any day from 1993-01-01 to 1997-10-01.
#: A quarter holds 54 967 to 57 930 orders at SF1 (1993-07-01: 57 575), of
#: which 50 466 to 53 121 have a late line item (3 794 579 of lineitem's
#: rows are late, in 1 375 351 orders): one capacity class.  The
#: text is the spec's (the end of the quarter written out as a date, as
#: benchmarks/tpch.py has it); its ORDER BY key is unique already.
SPACE = 1735
#: the spec's validation parameters (1993-07-01): every run's first text
FIRST = 181


def _three_months_on(day: datetime.date) -> datetime.date:
    month = day.month + 3
    year, month = day.year + (month - 1) // 12, (month - 1) % 12 + 1
    last = (datetime.date(year + month // 12, month % 12 + 1, 1)
            - datetime.timedelta(days=1)).day
    return datetime.date(year, month, min(day.day, last))


def params_at(i: int) -> dict:
    start = datetime.date(1993, 1, 1) + datetime.timedelta(days=i)
    return {"date_from": start.isoformat(),
            "date_to": _three_months_on(start).isoformat()}


def sql(params: dict) -> str:
    # end a run that set-up got no program for, or whose program new
    # parameters do not get
    ready_limit.asked(NAME, "subqueries")
    warm_limit.asked(NAME, "subqueries")
    return SQL.format(**params)


def reference(frames: dict, date_from, date_to) -> pd.DataFrame:
    od, li = frames["orders"], frames["lineitem"]
    late = li.loc[li["l_commitdate"] < li["l_receiptdate"], "l_orderkey"]
    o = od.loc[(od["o_orderdate"] >= pd.Timestamp(date_from))
               & (od["o_orderdate"] < pd.Timestamp(date_to)),
               ["o_orderkey", "o_orderpriority"]]
    o = o.loc[o["o_orderkey"].isin(late.unique())]
    g = o.groupby("o_orderpriority", as_index=False).size()
    g = g.rename(columns={"size": "order_count"})
    return g.sort_values("o_orderpriority", ignore_index=True)[
        ["o_orderpriority", "order_count"]]
