"""The SQL semantics TPC-H's nested-subquery shapes lean on (Q4, Q15, Q18,
Q20: ``chipbench/shapes``), each against sqlite as
``test_compatibility.py`` does, on the host strategy and on the TPU's
(merge joins, compaction sites): ``IN`` / ``NOT IN`` / ``EXISTS`` where keys
are NULL, a scalar subquery of no rows, a correlated aggregate over no
rows, ``IN`` over a grouped subquery with ``HAVING``, and Q15's equality on
a DOUBLE when two suppliers tie."""
import numpy as np
import pandas as pd
import pytest

from tests.conftest import eq_sqlite


def _frames():
    rng = np.random.RandomState(43)
    n = 120
    orders = pd.DataFrame({
        "o_key": np.arange(n, dtype="float64"),
        "o_pri": rng.choice(["1-URGENT", "2-HIGH", "3-LOW"], n),
        "o_qty": rng.randint(1, 50, n)})
    orders.loc[rng.choice(n, 9, replace=False), "o_key"] = np.nan
    lines = pd.DataFrame({
        "l_key": rng.randint(0, 160, 400).astype("float64"),
        "l_qty": rng.randint(1, 51, 400).astype("float64"),
        "l_commit": rng.randint(0, 100, 400),
        "l_receipt": rng.randint(0, 100, 400)})
    lines.loc[rng.choice(400, 17, replace=False), "l_key"] = np.nan
    no_nulls = lines.dropna(subset=["l_key"]).reset_index(drop=True)
    # halves add up exactly, in any order: suppliers 3 and 7 tie at the top
    supplier = pd.DataFrame({"s_key": np.arange(10),
                             "s_name": [f"S{i}" for i in range(10)]})
    shipped = pd.DataFrame({
        "sh_supp": np.repeat(np.arange(10), 4),
        "sh_rev": np.tile([10.5, 20.0, 30.5, 1.0], 10)
        + np.repeat([0, 1, 2, 9, 3, 4, 5, 9, 6, 7], 4) * 0.5,
        "sh_day": np.tile([1, 2, 3, 4], 10)})
    return {"orders": orders, "lines": lines, "no_nulls": no_nulls,
            "supplier": supplier, "shipped": shipped}


QUERIES = {
    "exists_with_null_keys": """
        SELECT o_pri, COUNT(*) AS n FROM orders
        WHERE EXISTS (SELECT * FROM lines WHERE l_key = o_key
                      AND l_commit < l_receipt)
        GROUP BY o_pri ORDER BY o_pri""",
    "not_exists_with_null_keys": """
        SELECT o_pri, COUNT(*) AS n FROM orders
        WHERE NOT EXISTS (SELECT * FROM lines WHERE l_key = o_key)
        GROUP BY o_pri ORDER BY o_pri""",
    "in_with_null_keys_on_both_sides": """
        SELECT o_key, o_qty FROM orders
        WHERE o_key IN (SELECT l_key FROM lines WHERE l_qty > 25)""",
    "not_in_a_subquery_that_holds_a_null": """
        SELECT o_key, o_qty FROM orders
        WHERE o_key NOT IN (SELECT l_key FROM lines)""",
    "not_in_without_nulls_in_the_subquery": """
        SELECT o_key, o_qty FROM orders
        WHERE o_key NOT IN (SELECT l_key FROM no_nulls WHERE l_qty > 10)""",
    "not_in_an_empty_subquery_keeps_null_keys": """
        SELECT o_key, o_qty FROM orders
        WHERE o_key NOT IN (SELECT l_key FROM no_nulls WHERE l_qty > 1000)""",
    "in_over_a_grouped_subquery_with_having": """
        SELECT o_key, o_pri, SUM(l_qty) AS total FROM orders, no_nulls
        WHERE o_key IN (SELECT l_key FROM no_nulls GROUP BY l_key
                        HAVING SUM(l_qty) > 90.5)
          AND o_key = l_key
        GROUP BY o_key, o_pri ORDER BY total DESC, o_key""",
    "in_inside_in_with_a_correlated_aggregate": """
        SELECT s_name FROM supplier
        WHERE s_key IN (
            SELECT o_qty FROM orders
            WHERE o_key IN (SELECT l_key FROM lines WHERE l_commit > 40)
              AND o_qty > (SELECT 0.5 * SUM(l_qty) FROM lines
                           WHERE l_key = o_key AND l_receipt >= 20
                             AND l_receipt < 70))
        ORDER BY s_name""",
    "a_scalar_subquery_of_no_rows_is_null": """
        SELECT o_key FROM orders
        WHERE o_qty > (SELECT MAX(l_qty) FROM lines WHERE l_qty > 1000)""",
    "a_scalar_subquery_of_no_rows_selected": """
        SELECT COUNT(*) AS n,
               (SELECT MAX(l_qty) FROM lines WHERE l_qty > 1000) AS top
        FROM orders""",
    "the_top_supplier_when_two_tie": """
        WITH revenue0 AS (
            SELECT sh_supp AS supplier_no, SUM(sh_rev) AS total_revenue
            FROM shipped WHERE sh_day >= 1 AND sh_day < 4 GROUP BY sh_supp)
        SELECT s_key, s_name, total_revenue FROM supplier, revenue0
        WHERE s_key = supplier_no
          AND total_revenue = (SELECT MAX(total_revenue) FROM revenue0)
        ORDER BY s_key""",
    "the_top_supplier_of_days_nothing_shipped": """
        WITH revenue0 AS (
            SELECT sh_supp AS supplier_no, SUM(sh_rev) AS total_revenue
            FROM shipped WHERE sh_day >= 7 AND sh_day < 9 GROUP BY sh_supp)
        SELECT s_key, s_name, total_revenue FROM supplier, revenue0
        WHERE s_key = supplier_no
          AND total_revenue = (SELECT MAX(total_revenue) FROM revenue0)
        ORDER BY s_key""",
}


@pytest.mark.parametrize("strategy", ["host", "tpu"])
@pytest.mark.parametrize("case", sorted(QUERIES))
def test_as_sqlite_answers(case, strategy, monkeypatch):
    monkeypatch.setenv("DSQL_STRATEGY", strategy)
    eq_sqlite(QUERIES[case], check_row_order="ORDER BY" in QUERIES[case],
              **_frames())


def test_the_tie_returns_both_suppliers():
    """What the case above compares is not an empty answer twice."""
    from dask_sql_tpu import Context
    ctx = Context()
    for name, frame in _frames().items():
        ctx.create_table(name, frame)
    got = ctx.sql(QUERIES["the_top_supplier_when_two_tie"],
                  return_futures=False)
    assert list(got["s_key"]) == [3, 7]
    assert got["total_revenue"].nunique() == 1
