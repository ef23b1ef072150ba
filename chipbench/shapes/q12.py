"""TPC-H Q12, shipping modes and order priority: lineitem filtered to about
one row in a hundred, joined to orders on the order key, grouped by ship
mode.  The shape that drives the merge join and its sorts."""
import datetime

import pandas as pd

NAME = "q12"

SQL = """
    SELECT l_shipmode,
           SUM(CASE WHEN o_orderpriority = '1-URGENT'
                     OR o_orderpriority = '2-HIGH' THEN 1 ELSE 0 END) AS high_line_count,
           SUM(CASE WHEN o_orderpriority <> '1-URGENT'
                    AND o_orderpriority <> '2-HIGH' THEN 1 ELSE 0 END) AS low_line_count
    FROM orders, lineitem
    WHERE o_orderkey = l_orderkey
      AND l_shipmode IN ('MAIL', 'SHIP')
      AND l_commitdate < l_receiptdate
      AND l_shipdate < l_commitdate
      AND l_receiptdate >= DATE '{date_from}'
      AND l_receiptdate < DATE '{date_to}'
    GROUP BY l_shipmode
    ORDER BY l_shipmode
"""

SCAN_COLUMNS = {"lineitem": ("l_orderkey", "l_shipmode", "l_commitdate",
                             "l_receiptdate", "l_shipdate"),
                "orders": ("o_orderkey", "o_orderpriority")}

#: TPC-H cl.2.4.12.3: two ship modes and the first of January of
#: 1993..1997.  The engine bakes string literals and IN-lists into the
#: compiled program (plan/parameterize.py hoists numeric and date operands
#: only), so a new pair of modes is a new program and minutes of compiling:
#: the modes stay the spec's validation pair.  Five dates alone would be
#: result-cache replays, so DATE is any day from 1993-01-01 to 1997-01-01.
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
    return SQL.format(**params)


def reference(frames: dict, date_from, date_to) -> pd.DataFrame:
    od, li = frames["orders"], frames["lineitem"]
    sel = li[li["l_shipmode"].isin(["MAIL", "SHIP"])
             & (li["l_commitdate"] < li["l_receiptdate"])
             & (li["l_shipdate"] < li["l_commitdate"])
             & (li["l_receiptdate"] >= pd.Timestamp(date_from))
             & (li["l_receiptdate"] < pd.Timestamp(date_to))]
    m = sel[["l_orderkey", "l_shipmode"]].merge(
        od[["o_orderkey", "o_orderpriority"]],
        left_on="l_orderkey", right_on="o_orderkey")
    high = m["o_orderpriority"].isin(["1-URGENT", "2-HIGH"])
    m = m.assign(high_line=high.astype("int64"),
                 low_line=(~high).astype("int64"))
    out = m.groupby("l_shipmode", as_index=False).agg(
        high_line_count=("high_line", "sum"),
        low_line_count=("low_line", "sum"))
    return out.sort_values("l_shipmode", ignore_index=True)
