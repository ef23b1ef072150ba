"""TPC-H Q15, top supplier: the revenue each supplier shipped in one
quarter, and the supplier (or suppliers) whose revenue is the largest.  In
the specification's approved variant that writes the view ``revenue`` as a
common table expression: the CTE is read twice, once joined to supplier and
once below ``= (SELECT MAX(..))``, an uncorrelated scalar subquery that the
compiled tier inlines into the one program.  Its two dates stand in both
copies; all four are parameters of the program."""
import datetime

import pandas as pd

from chipbench import ready_limit, warm_limit

NAME = "q15"

SQL = """
    WITH revenue0 AS (
        SELECT l_suppkey AS supplier_no,
               SUM(l_extendedprice * (1 - l_discount)) AS total_revenue
        FROM lineitem
        WHERE l_shipdate >= DATE '{date_from}'
          AND l_shipdate < DATE '{date_to}'
        GROUP BY l_suppkey
    )
    SELECT s_suppkey, s_name, s_address, s_phone, total_revenue
    FROM supplier, revenue0
    WHERE s_suppkey = supplier_no
      AND total_revenue = (SELECT MAX(total_revenue) FROM revenue0)
    ORDER BY s_suppkey
"""

#: every column the text names, once: the CTE is written once and read twice
SCAN_COLUMNS = {"supplier": ("s_suppkey", "s_name", "s_address", "s_phone"),
                "lineitem": ("l_suppkey", "l_extendedprice", "l_discount",
                             "l_shipdate")}

#: TPC-H cl.2.4.15.3: DATE the first day of a month from 1993-01 to 1997-10
#: (58 texts), and the three months that follow it.  A text seen before is
#: a result-cache replay, so DATE is any day from 1993-01-01 to 1997-10-01:
#: a quarter holds 221 222 to 230 985 shipped line items at SF1 (1996-01-01:
#: 227 817), of all 10 000 suppliers: one capacity class.  The text is the
#: spec's CTE variant (cl.2.4.15.2's view, written WITH); its ORDER BY key is
#: unique already.  The equality is on DOUBLE: the engine compares two sums
#: it made the same way, the reference its own, and both find the supplier
#: with the largest (a tie in every bit between two suppliers returns both,
#: in either).
SPACE = 1735
#: the spec's validation parameters (1996-01-01): every run's first text
FIRST = 1095


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
    li, su = frames["lineitem"], frames["supplier"]
    l = li.loc[(li["l_shipdate"] >= pd.Timestamp(date_from))
               & (li["l_shipdate"] < pd.Timestamp(date_to)),
               ["l_suppkey", "l_extendedprice", "l_discount"]]
    l = l.assign(total_revenue=l["l_extendedprice"] * (1 - l["l_discount"]))
    revenue0 = l.groupby("l_suppkey", as_index=False)["total_revenue"].sum()
    top = revenue0.loc[revenue0["total_revenue"]
                       == revenue0["total_revenue"].max()]
    m = su[["s_suppkey", "s_name", "s_address", "s_phone"]].merge(
        top, left_on="s_suppkey", right_on="l_suppkey")
    m = m.sort_values("s_suppkey", ignore_index=True)
    return m[["s_suppkey", "s_name", "s_address", "s_phone", "total_revenue"]]
