"""Exact decimal aggregation (review r1 item 6).

DECIMAL(p<=18, s<=9) SUM/AVG accumulate in scaled int64 — order-independent
(bit-stable across runs and row orders) and exactly equal to true decimal
arithmetic, where the reference's f64 fold (mappings.py:64) drifts.
Storage stays f64 (values with <=15 significant digits round-trip f64
uniquely, so comparisons/grouping are already exact); only the ACCUMULATION
changes representation.
"""
import decimal

import numpy as np
import pandas as pd
import pytest

from dask_sql_tpu import Context


@pytest.fixture()
def c():
    return Context()


def test_cast_sum_is_exact(c):
    # 0.1 is the classic f64 repeating binary fraction: naive f64 summation
    # of 1000 copies gives 99.9999999999986; exact decimal gives 100.0
    c.create_table("t", pd.DataFrame({"x": [0.1] * 1000}))
    r = c.sql("SELECT SUM(CAST(x AS DECIMAL(10, 1))) AS s FROM t",
              return_futures=False)
    assert float(r["s"][0]) == 100.0
    naive = c.sql("SELECT SUM(x) AS s FROM t", return_futures=False)
    # document why the decimal path exists (pairwise f64 may or may not
    # drift depending on the reduction shape; the decimal result is EXACT
    # by construction either way)
    assert abs(float(naive["s"][0]) - 100.0) < 1e-9


def test_decimal_object_ingestion(c):
    d = decimal.Decimal
    df = pd.DataFrame({"g": ["a", "b", "a", "b"],
                       "m": [d("1.01"), d("2.02"), d("3.03"), None]})
    c.create_table("t", df)
    entry = c.schema["root"].tables["t"]
    col = entry.table.column("m")
    assert col.stype.name == "DECIMAL" and col.stype.scale == 2
    r = c.sql("SELECT g, SUM(m) AS s, AVG(m) AS a FROM t GROUP BY g "
              "ORDER BY g", return_futures=False)
    assert float(r["s"][0]) == 4.04        # 1.01 + 3.03, exact
    assert float(r["s"][1]) == 2.02
    assert float(r["a"][0]) == 2.02


def test_bit_stable_across_row_orders(c):
    # cents that sum to an exact dollar amount; f64 accumulation order
    # changes the bits, int64 accumulation cannot
    rng = np.random.RandomState(0)
    cents = rng.randint(1, 100000, 50000)
    vals = cents / 100.0
    want = decimal.Decimal(int(cents.sum())) / 100

    sums = set()
    for seed in range(3):
        order = np.random.RandomState(seed).permutation(len(vals))
        ctx = Context()
        ctx.create_table("t", pd.DataFrame({"x": vals[order]}))
        r = ctx.sql("SELECT SUM(CAST(x AS DECIMAL(12, 2))) AS s FROM t",
                    return_futures=False)
        sums.add(float(r["s"][0]).hex())
    assert len(sums) == 1, f"not bit-stable: {sums}"
    assert float.fromhex(next(iter(sums))) == float(want)


def test_grouped_exactness_vs_python_decimal(c):
    d = decimal.Decimal
    rng = np.random.RandomState(1)
    g = rng.randint(0, 7, 5000)
    cents = rng.randint(-500000, 500000, 5000)
    df = pd.DataFrame({"g": g, "x": cents / 100.0})
    c.create_table("t", df)
    r = c.sql("SELECT g, SUM(CAST(x AS DECIMAL(14, 2))) AS s FROM t "
              "GROUP BY g ORDER BY g", return_futures=False)
    for gi in range(7):
        want = d(int(cents[g == gi].sum())) / 100
        got = d(repr(float(r["s"][gi])))
        assert got == want, (gi, got, want)


def test_decimal_compiled_and_eager_agree(c):
    import os

    df = pd.DataFrame({"g": ["x", "y"] * 500, "m": [0.1, 0.3] * 500})
    c.create_table("t", df)
    q = ("SELECT g, SUM(CAST(m AS DECIMAL(10, 1))) AS s FROM t GROUP BY g "
         "ORDER BY g")
    comp = c.sql(q, return_futures=False)
    os.environ["DSQL_COMPILE"] = "0"
    try:
        eager = c.sql(q, return_futures=False)
    finally:
        del os.environ["DSQL_COMPILE"]
    assert comp["s"].tolist() == eager["s"].tolist() == [50.0, 150.0]


def test_large_precision_falls_back_to_f64(c):
    # DECIMAL(38, 10) is outside the exact-int64 envelope: documented f64
    from dask_sql_tpu.types import decimal as mk, exact_decimal_scale

    assert exact_decimal_scale(mk(38, 10)) is None
    # p>15 stores values that can't be exact in the f64 mantissa: declined
    assert exact_decimal_scale(mk(18, 2)) is None
    assert exact_decimal_scale(mk(15, 2)) == 2
    assert exact_decimal_scale(mk(12, 0)) == 0


def test_mixed_and_nonfinite_object_columns_keep_generic_path(c):
    d = decimal.Decimal
    # mixed Decimal + float: NOT typed DECIMAL (no crash, generic path)
    c.create_table("mx", pd.DataFrame({"x": np.array([d("1.5"), 2.5],
                                                     dtype=object)}))
    col = c.schema["root"].tables["mx"].table.column("x")
    assert col.stype.name != "DECIMAL"
    # non-finite Decimal: same
    c.create_table("nf", pd.DataFrame({"x": np.array([d("NaN"), d("1")],
                                                     dtype=object)}))
    assert c.schema["root"].tables["nf"].table.column("x").stype.name != "DECIMAL"
    # scale > 9: typed DECIMAL(38, s) but NOT quantized (f64 fallback)
    c.create_table("hs", pd.DataFrame({
        "x": np.array([d("0.0123456789012"), d("1")], dtype=object)}))
    col = c.schema["root"].tables["hs"].table.column("x")
    assert col.stype.name == "DECIMAL" and col.stype.scale == 13
    from dask_sql_tpu.types import exact_decimal_scale
    assert exact_decimal_scale(col.stype) is None
