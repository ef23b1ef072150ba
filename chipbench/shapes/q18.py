"""TPC-H Q18, large volume customer: the orders whose line items add up to
more than a quantity, with their customers, the hundred dearest.  ``IN``
over a grouped subquery with ``HAVING``: lineitem's 6 M rows grouped into
1.5 M orders (a quarter of its rows are groups, forty times the largest
group count of any other shape), the sums filtered, and the order keys that
pass the build side of a SEMI join whose probe side is customer joined to
orders joined to lineitem again."""
import pandas as pd

from chipbench import ready_limit, warm_limit

NAME = "q18"

SQL = """
    SELECT c_name, c_custkey, o_orderkey, o_orderdate, o_totalprice,
           SUM(l_quantity) AS total_qty
    FROM customer, orders, lineitem
    WHERE o_orderkey IN (
            SELECT l_orderkey FROM lineitem
            GROUP BY l_orderkey HAVING SUM(l_quantity) > {quantity})
      AND c_custkey = o_custkey
      AND o_orderkey = l_orderkey
    GROUP BY c_name, c_custkey, o_orderkey, o_orderdate, o_totalprice
    ORDER BY o_totalprice DESC, o_orderdate, o_orderkey
    LIMIT 100
"""

#: every column the text names, once: it names lineitem twice
SCAN_COLUMNS = {"customer": ("c_name", "c_custkey"),
                "orders": ("o_orderkey", "o_custkey", "o_orderdate",
                           "o_totalprice"),
                "lineitem": ("l_orderkey", "l_quantity")}

#: TPC-H cl.2.4.18.3: QUANTITY a whole number from 312 to 315 (4 texts).  A
#: text seen before is a result-cache replay, so QUANTITY is any multiple of
#: 0.05 from 300.00 to 319.95: ``l_quantity`` is DOUBLE in
#: ``chipbench/data/tpch_gen.py``, so 312.25 is a new text with a
#: well-defined answer (that of 312).  An order has one to seven lines of 1
#: to 50 units, 328 at the most at SF1: 55 orders pass 300 (385 lines), 8
#: pass 312, 7 pass 315 and 2 pass 319.95, so the orders that pass, their
#: lines and their groups stay under a thousand: one capacity class (a
#: site's least capacity is 2 048), and fewer than LIMIT's hundred rows come
#: back, as at the spec's own values (57 at 300 on dbgen's data).  A departure from
#: the spec's text: ORDER BY ends in o_orderkey, a unique key, so that two
#: answers can be compared position by position.
SPACE = 400
#: the spec's validation parameter (cl.2.4.18.4: 300): every run's first text
FIRST = 0


def params_at(i: int) -> dict:
    return {"quantity": f"{300 + i // 20}.{5 * (i % 20):02d}"}


def sql(params: dict) -> str:
    # end a run that set-up got no program for, or whose program new
    # parameters do not get
    ready_limit.asked(NAME, "subqueries")
    warm_limit.asked(NAME, "subqueries")
    return SQL.format(**params)


def reference(frames: dict, quantity) -> pd.DataFrame:
    cu, od, li = frames["customer"], frames["orders"], frames["lineitem"]
    volume = li.groupby("l_orderkey")["l_quantity"].sum()
    large = volume.index[volume > float(quantity)]
    o = od.loc[od["o_orderkey"].isin(large),
               ["o_orderkey", "o_custkey", "o_orderdate", "o_totalprice"]]
    m = (cu[["c_name", "c_custkey"]]
         .merge(o, left_on="c_custkey", right_on="o_custkey")
         .merge(li[["l_orderkey", "l_quantity"]], left_on="o_orderkey",
                right_on="l_orderkey"))
    keys = ["c_name", "c_custkey", "o_orderkey", "o_orderdate",
            "o_totalprice"]
    g = m.groupby(keys, as_index=False)["l_quantity"].sum()
    g = g.rename(columns={"l_quantity": "total_qty"})
    g = g.sort_values(["o_totalprice", "o_orderdate", "o_orderkey"],
                      ascending=[False, True, True],
                      ignore_index=True).head(100)
    return g[keys + ["total_qty"]]
