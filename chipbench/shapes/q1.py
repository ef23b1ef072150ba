"""TPC-H Q1, pricing summary: one scan of lineitem (98 % of its rows pass),
a group-by on two dictionary columns with a static domain, eight
aggregates.  The shape that drives the group-by reduction kernel
(``ops/pallas_kernels.py``: f64 sums as limbs through the MXU)."""
import datetime

import pandas as pd

NAME = "q1"

SQL = """
    SELECT l_returnflag, l_linestatus,
           SUM(l_quantity) AS sum_qty,
           SUM(l_extendedprice) AS sum_base_price,
           SUM(l_extendedprice * (1 - l_discount)) AS sum_disc_price,
           SUM(l_extendedprice * (1 - l_discount) * (1 + l_tax)) AS sum_charge,
           AVG(l_quantity) AS avg_qty,
           AVG(l_extendedprice) AS avg_price,
           AVG(l_discount) AS avg_disc,
           COUNT(*) AS count_order
    FROM lineitem
    WHERE l_shipdate <= DATE '{shipdate}'
    GROUP BY l_returnflag, l_linestatus
    ORDER BY l_returnflag, l_linestatus
"""

SCAN_COLUMNS = {"lineitem": ("l_shipdate", "l_returnflag", "l_linestatus",
                             "l_quantity", "l_extendedprice", "l_discount",
                             "l_tax")}

#: TPC-H cl.2.4.1.3: DELTA 60..120 days before 1998-12-01.  61 texts are
#: fewer than one window executes once Q1 is a little faster than today,
#: and a text seen before is a result-cache replay.  So DELTA runs on to
#: 480: the predicate still passes over 92 % of the rows, and the scan and
#: the reduction read every row whatever passes.
SPACE = 421
#: the spec's validation parameters (DELTA 90): every run's first text
FIRST = 30


def params_at(i: int) -> dict:
    day = datetime.date(1998, 12, 1) - datetime.timedelta(days=60 + i)
    return {"shipdate": day.isoformat()}


def sql(params: dict) -> str:
    return SQL.format(**params)


def reference(frames: dict, shipdate) -> pd.DataFrame:
    li = frames["lineitem"]
    x = li.loc[li["l_shipdate"] <= pd.Timestamp(shipdate),
               ["l_returnflag", "l_linestatus", "l_quantity",
                "l_extendedprice", "l_discount", "l_tax"]].copy()
    x["disc_price"] = x["l_extendedprice"] * (1 - x["l_discount"])
    x["charge"] = x["disc_price"] * (1 + x["l_tax"])
    out = x.groupby(["l_returnflag", "l_linestatus"], as_index=False).agg(
        sum_qty=("l_quantity", "sum"),
        sum_base_price=("l_extendedprice", "sum"),
        sum_disc_price=("disc_price", "sum"),
        sum_charge=("charge", "sum"),
        avg_qty=("l_quantity", "mean"),
        avg_price=("l_extendedprice", "mean"),
        avg_disc=("l_discount", "mean"),
        count_order=("l_quantity", "count"))
    return out.sort_values(["l_returnflag", "l_linestatus"],
                           ignore_index=True)
