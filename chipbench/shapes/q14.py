"""TPC-H Q14, promotion effect: one month of lineitem joined to part on
the part key, a LIKE on a dictionary column, a ratio of two sums."""
import datetime

import pandas as pd

NAME = "q14"

SQL = """
    SELECT 100.00 * SUM(CASE WHEN p_type LIKE 'PROMO%'
                             THEN l_extendedprice * (1 - l_discount)
                             ELSE 0 END) / SUM(l_extendedprice * (1 - l_discount)) AS promo_revenue
    FROM lineitem, part
    WHERE l_partkey = p_partkey
      AND l_shipdate >= DATE '{date_from}'
      AND l_shipdate < DATE '{date_to}'
"""

SCAN_COLUMNS = {"lineitem": ("l_partkey", "l_shipdate", "l_extendedprice",
                             "l_discount"),
                "part": ("p_partkey", "p_type")}

#: TPC-H cl.2.4.14.3: the first day of a month of 1993..1997, 60 texts.
#: A text seen before is a result-cache replay, so DATE is any day from
#: 1993-01-01 to 1997-12-01, and the month that follows it is 30 days.
SPACE = 1796
#: the spec's validation parameters (1995-09-01): every run's first text
FIRST = 973


def params_at(i: int) -> dict:
    start = datetime.date(1993, 1, 1) + datetime.timedelta(days=i)
    return {"date_from": start.isoformat(),
            "date_to": (start + datetime.timedelta(days=30)).isoformat()}


def sql(params: dict) -> str:
    return SQL.format(**params)


def reference(frames: dict, date_from, date_to) -> pd.DataFrame:
    li, part = frames["lineitem"], frames["part"]
    sel = li.loc[(li["l_shipdate"] >= pd.Timestamp(date_from))
                 & (li["l_shipdate"] < pd.Timestamp(date_to)),
                 ["l_partkey", "l_extendedprice", "l_discount"]]
    m = sel.merge(part[["p_partkey", "p_type"]], left_on="l_partkey",
                  right_on="p_partkey")
    revenue = m["l_extendedprice"] * (1 - m["l_discount"])
    promo = revenue.where(m["p_type"].str.startswith("PROMO"), 0.0)
    return pd.DataFrame(
        {"promo_revenue": [100.0 * promo.sum() / revenue.sum()]})
