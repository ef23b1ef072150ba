"""SQLite differential-oracle tests.

The reference's most important test pattern (SURVEY §4): run the same SQL
through the engine and through in-memory sqlite3 and compare frames
(/root/reference/tests/integration/test_compatibility.py:22-67, with
make_rand_df seeded generators).
"""
import numpy as np
import pandas as pd
import pytest

from tests.conftest import eq_sqlite, make_rand_df


def test_basic_select():
    a = make_rand_df(30, a=int, b=float, c=str)
    eq_sqlite("SELECT a, b, c FROM a", a=a)
    eq_sqlite("SELECT a+1 AS a1, b*2 AS b2 FROM a", a=a)


def test_where():
    a = make_rand_df(30, a=(int, 5), b=(float, 5), c=(str, 5))
    eq_sqlite("SELECT * FROM a WHERE a < 5", a=a)
    eq_sqlite("SELECT * FROM a WHERE a < 5 AND b > 2", a=a)
    eq_sqlite("SELECT * FROM a WHERE a IS NULL OR b > 2", a=a)
    eq_sqlite("SELECT * FROM a WHERE c IS NOT NULL", a=a)


def test_arithmetic():
    a = make_rand_df(20, a=int, b=float)
    eq_sqlite("SELECT a+b AS x, a-b AS y, a*b AS z, b/2 AS w FROM a", a=a)
    eq_sqlite("SELECT -a AS na, ABS(a-5) AS ab FROM a", a=a)


def test_case_when():
    a = make_rand_df(30, a=(int, 5), b=(float, 5))
    eq_sqlite(
        """SELECT CASE WHEN a IS NULL THEN -1 WHEN a < 5 THEN a*10 ELSE b END AS x
           FROM a""", a=a)


def test_group_by_agg():
    a = make_rand_df(50, a=(int, 10), b=(float, 10), c=(str, 10))
    eq_sqlite(
        """SELECT c, SUM(a) AS sa, COUNT(*) AS n, COUNT(a) AS ca,
                  AVG(b) AS ab, MIN(a) AS mi, MAX(a) AS ma
           FROM a GROUP BY c""", a=a)


def test_group_by_multiple_keys():
    a = make_rand_df(60, a=(int, 10), c=(str, 10), d=(str, 10))
    eq_sqlite("SELECT c, d, COUNT(*) AS n, SUM(a) AS s FROM a GROUP BY c, d", a=a)


def test_distinct():
    a = make_rand_df(50, a=(int, 10), c=(str, 10))
    eq_sqlite("SELECT DISTINCT a, c FROM a", a=a)
    eq_sqlite("SELECT COUNT(DISTINCT a) AS n FROM a", a=a)


def test_order_by_limit():
    a = make_rand_df(40, a=(int, 5), b=float, c=(str, 5))
    eq_sqlite("SELECT * FROM a ORDER BY b LIMIT 10", check_row_order=True, a=a)
    eq_sqlite("SELECT * FROM a ORDER BY a NULLS FIRST, b DESC LIMIT 10",
              check_row_order=True, a=a)
    eq_sqlite("SELECT * FROM a ORDER BY c NULLS LAST, b LIMIT 5 OFFSET 3",
              check_row_order=True, a=a)


def test_join_inner():
    a = make_rand_df(30, k=int, va=float)
    b = make_rand_df(20, k=int, vb=float)
    eq_sqlite("SELECT a.k, va, vb FROM a JOIN b ON a.k = b.k", a=a, b=b)


def test_join_left():
    a = make_rand_df(30, k=(int, 5), va=float)
    b = make_rand_df(20, k=(int, 3), vb=float)
    eq_sqlite("SELECT a.k, va, vb FROM a LEFT JOIN b ON a.k = b.k", a=a, b=b)


def test_join_multi_key():
    a = make_rand_df(40, k1=int, k2=(str, 5), va=float)
    b = make_rand_df(30, k1=int, k2=(str, 5), vb=float)
    eq_sqlite(
        """SELECT a.k1, a.k2, va, vb FROM a
           JOIN b ON a.k1 = b.k1 AND a.k2 = b.k2""", a=a, b=b)


def test_union_compat():
    a = make_rand_df(20, a=int, b=str)
    b = make_rand_df(20, a=int, b=str)
    eq_sqlite("SELECT * FROM a UNION SELECT * FROM b", a=a, b=b)
    eq_sqlite("SELECT * FROM a UNION ALL SELECT * FROM b", a=a, b=b)
    eq_sqlite("SELECT * FROM a EXCEPT SELECT * FROM b", a=a, b=b)
    eq_sqlite("SELECT * FROM a INTERSECT SELECT * FROM b", a=a, b=b)


def test_in_subquery():
    a = make_rand_df(30, k=int, v=float)
    b = make_rand_df(10, k=int)
    eq_sqlite("SELECT * FROM a WHERE k IN (SELECT k FROM b)", a=a, b=b)
    eq_sqlite("SELECT * FROM a WHERE k NOT IN (SELECT k FROM b)", a=a, b=b)


def test_scalar_subquery_compat():
    a = make_rand_df(30, k=int, v=float)
    eq_sqlite("SELECT * FROM a WHERE v > (SELECT AVG(v) FROM a)", a=a)


def test_having_compat():
    a = make_rand_df(50, g=(str, 5), v=float)
    eq_sqlite(
        "SELECT g, SUM(v) AS s FROM a GROUP BY g HAVING COUNT(*) > 5", a=a)


def test_string_funcs_compat():
    a = make_rand_df(30, s=(str, 5))
    eq_sqlite("SELECT UPPER(s) AS u, LOWER(s) AS l, LENGTH(s) AS n FROM a", a=a)
    eq_sqlite("SELECT * FROM a WHERE s LIKE 's1%'", a=a)


def test_cte_compat():
    a = make_rand_df(30, k=int, v=float)
    eq_sqlite(
        """WITH big AS (SELECT * FROM a WHERE v > 5),
                agg AS (SELECT k, COUNT(*) AS n FROM big GROUP BY k)
           SELECT * FROM agg""", a=a)


def test_outer_order_limit_over_setop_and_raw():
    """ORDER BY/LIMIT/OFFSET outside CTE+set-op or parenthesized bodies must
    apply exactly once (regression: OFFSET was applied twice)."""
    a = pd.DataFrame({"x": [1, 2, 3, 4, 5]})
    eq_sqlite(
        "WITH c AS (SELECT x FROM a) "
        "SELECT x FROM c UNION ALL SELECT 99 ORDER BY 1 LIMIT 3 OFFSET 1",
        a=a)
    eq_sqlite("SELECT x FROM a UNION SELECT x + 10 FROM a ORDER BY 1 LIMIT 4",
              a=a)
    # sqlite cannot parse these two shapes; assert directly
    from dask_sql_tpu import Context
    c = Context()
    c.create_table("a", a)
    got = c.sql("VALUES (3), (1), (2) ORDER BY 1 LIMIT 2").to_pandas()
    assert got.iloc[:, 0].tolist() == [1, 2]
    got = c.sql("(SELECT x FROM a ORDER BY x DESC LIMIT 4) LIMIT 2").to_pandas()
    assert sorted(got["x"].tolist()) == [4, 5]


def test_window_compat():
    a = make_rand_df(30, g=(str, 3), v=float)
    eq_sqlite(
        """SELECT g, v,
                  ROW_NUMBER() OVER (PARTITION BY g ORDER BY v) AS r,
                  SUM(v) OVER (PARTITION BY g ORDER BY v) AS s
           FROM a""", a=a)


def test_complex_query():
    a = make_rand_df(60, g=(str, 10), k=int, v=(float, 10))
    b = make_rand_df(20, k=int, w=float)
    eq_sqlite(
        """SELECT a.g, COUNT(*) AS n, SUM(a.v * b.w) AS dot
           FROM a JOIN b ON a.k = b.k
           WHERE a.v IS NOT NULL
           GROUP BY a.g
           HAVING COUNT(*) > 1
           ORDER BY dot DESC
           LIMIT 5""", check_row_order=False, a=a, b=b)


# ---------------------------------------------------------------------------
# randomized scenario classes mirroring the rest of the reference suite
# (test_compatibility.py:98-920): dedup, in/between, cross join, typed agg
# matrices, window frames, nested queries, CTE integration
# ---------------------------------------------------------------------------

def test_drop_duplicates_rand():
    a = make_rand_df(100, a=int, b=(str, 30))
    eq_sqlite("SELECT DISTINCT b, a FROM a", a=a)
    eq_sqlite("SELECT DISTINCT a FROM a", a=a)


def test_order_by_no_limit_rand():
    a = make_rand_df(100, a=(int, 40), b=(str, 40))
    eq_sqlite("SELECT * FROM a ORDER BY a NULLS FIRST, b NULLS LAST",
              check_row_order=True, a=a)


def test_in_between_rand():
    a = make_rand_df(50, a=(int, 10), b=(str, 10))
    eq_sqlite("SELECT * FROM a WHERE a IN (2, 4, 6)", a=a)
    eq_sqlite("SELECT * FROM a WHERE a BETWEEN 3 AND 7", a=a)
    eq_sqlite("SELECT * FROM a WHERE a NOT BETWEEN 3 AND 7", a=a)


def test_join_cross_rand():
    a = make_rand_df(10, a=int, b=(str, 3))
    b = make_rand_df(5, c=float, d=(int, 2))
    eq_sqlite("SELECT * FROM a CROSS JOIN b", a=a, b=b)


def test_agg_count_typed_rand():
    a = make_rand_df(
        100, a=int, b=str, c=float, d=(int, 50), e=(str, 50), f=(float, 50))
    eq_sqlite(
        """
        SELECT a, b, COUNT(c) AS c_ct, COUNT(d) AS d_ct, COUNT(e) AS e_ct,
               COUNT(f) AS f_ct, COUNT(*) AS n
        FROM a GROUP BY a, b
        """, a=a)


def test_agg_sum_avg_typed_rand():
    a = make_rand_df(100, a=int, b=str, c=float, d=(int, 50), f=(float, 50))
    eq_sqlite(
        """
        SELECT a, b, SUM(c) AS sc, SUM(d) AS sd, SUM(f) AS sf,
               AVG(c) AS ac, AVG(d) AS ad, AVG(f) AS af
        FROM a GROUP BY a, b
        """, a=a)
    eq_sqlite("SELECT SUM(c) AS sc, AVG(d) AS ad FROM a", a=a)


def test_agg_min_max_typed_rand():
    a = make_rand_df(
        100, a=int, b=str, c=float, d=(int, 50), e=(str, 50), f=(float, 50))
    eq_sqlite(
        """
        SELECT a, b, MIN(c) AS mc, MAX(c) AS xc, MIN(d) AS md, MAX(d) AS xd,
               MIN(e) AS me, MAX(e) AS xe, MIN(f) AS mf, MAX(f) AS xf
        FROM a GROUP BY a, b
        """, a=a)
    eq_sqlite("SELECT MIN(c) AS mc, MAX(e) AS xe FROM a", a=a)


def test_window_row_number_rand():
    a = make_rand_df(10, a=int, b=(float, 5))
    eq_sqlite(
        """
        SELECT *,
            ROW_NUMBER() OVER (ORDER BY a ASC, b DESC NULLS FIRST) AS a1,
            ROW_NUMBER() OVER (ORDER BY a ASC, b ASC NULLS LAST) AS a2,
            ROW_NUMBER() OVER (PARTITION BY a ORDER BY b DESC NULLS FIRST) AS a3
        FROM a
        ORDER BY a, b NULLS FIRST
        """, check_row_order=True, a=a)


def test_window_row_number_partition_rand():
    a = make_rand_df(100, a=(int, 50), b=(str, 50), c=(int, 30), e=float)
    eq_sqlite(
        """
        SELECT *,
            ROW_NUMBER() OVER (ORDER BY a ASC NULLS LAST, b DESC NULLS FIRST, e) AS a1,
            ROW_NUMBER() OVER (PARTITION BY a, c ORDER BY b DESC NULLS LAST, e) AS a2
        FROM a
        ORDER BY a NULLS FIRST, b NULLS FIRST, c NULLS FIRST, e
        """, check_row_order=True, a=a)


def test_window_sum_avg_frames_rand():
    a = make_rand_df(100, a=float, b=(int, 50), c=(str, 50))
    for func in ["SUM", "AVG"]:
        eq_sqlite(
            f"""
            SELECT a, b,
                {func}(b) OVER () AS a1,
                {func}(b) OVER (PARTITION BY c) AS a2,
                {func}(b+a) OVER (PARTITION BY c, b) AS a3,
                {func}(b+a) OVER (PARTITION BY b ORDER BY a NULLS FIRST
                    ROWS BETWEEN UNBOUNDED PRECEDING AND CURRENT ROW) AS a4,
                {func}(b+a) OVER (PARTITION BY b ORDER BY a DESC NULLS FIRST
                    ROWS BETWEEN 2 PRECEDING AND CURRENT ROW) AS a5
            FROM a
            ORDER BY a NULLS FIRST, b NULLS FIRST, c NULLS FIRST
            """, a=a)


def test_window_irregular_frames_rand():
    a = make_rand_df(100, a=float, b=(int, 50), c=(str, 50))
    eq_sqlite(
        """
        SELECT a, b,
            SUM(b) OVER (PARTITION BY b ORDER BY a DESC NULLS FIRST
                ROWS BETWEEN 2 PRECEDING AND 1 PRECEDING) AS a6,
            SUM(b) OVER (PARTITION BY b ORDER BY a DESC NULLS FIRST
                ROWS BETWEEN 2 PRECEDING AND 1 FOLLOWING) AS a7,
            SUM(b) OVER (PARTITION BY b ORDER BY a DESC NULLS FIRST
                ROWS BETWEEN 2 PRECEDING AND UNBOUNDED FOLLOWING) AS a8
        FROM a
        ORDER BY a NULLS FIRST, b NULLS FIRST, c NULLS FIRST
        """, a=a)


def test_window_min_max_rand():
    a = make_rand_df(100, a=float, b=(int, 50), c=(str, 50))
    for func in ["MIN", "MAX"]:
        eq_sqlite(
            f"""
            SELECT a, b,
                {func}(b) OVER () AS a1,
                {func}(b) OVER (PARTITION BY c) AS a2,
                {func}(b+a) OVER (PARTITION BY b ORDER BY a NULLS FIRST
                    ROWS BETWEEN UNBOUNDED PRECEDING AND CURRENT ROW) AS a4
            FROM a
            ORDER BY a NULLS FIRST, b NULLS FIRST, c NULLS FIRST
            """, a=a)


def test_window_count_rand():
    a = make_rand_df(100, a=float, b=(int, 50), c=(str, 50))
    eq_sqlite(
        """
        SELECT a, b,
            COUNT(b) OVER () AS a1,
            COUNT(b) OVER (PARTITION BY c) AS a2,
            COUNT(b) OVER (PARTITION BY b ORDER BY a NULLS FIRST
                ROWS BETWEEN UNBOUNDED PRECEDING AND CURRENT ROW) AS a4
        FROM a
        ORDER BY a NULLS FIRST, b NULLS FIRST, c NULLS FIRST
        """, a=a)


def test_nested_query_rand():
    a = make_rand_df(100, a=(int, 40), b=(str, 40), c=(float, 40))
    eq_sqlite(
        """
        SELECT b, AVG(c) AS cc FROM
            (SELECT * FROM a WHERE a >= 2) t
        GROUP BY b
        """, a=a)


def test_integration_cte_join_rand():
    a = make_rand_df(100, a=int, b=str, c=float, d=int, e=bool, f=str, h=float)
    eq_sqlite(
        """
        WITH
            a1 AS (SELECT a+1 AS a, b, c FROM a),
            a2 AS (SELECT a, MAX(b) AS b_max, AVG(c) AS c_avg FROM a GROUP BY a),
            a3 AS (SELECT d+2 AS d, f, h FROM a WHERE e)
        SELECT a1.a, b, c, b_max, c_avg, f, h FROM a1
            INNER JOIN a2 ON a1.a = a2.a
            LEFT JOIN a3 ON a1.a = a3.d
        ORDER BY a1.a NULLS FIRST, b NULLS FIRST, c NULLS FIRST,
                 f NULLS FIRST, h NULLS FIRST
        """, check_row_order=True, a=a)


# ---------------------------------------------------------------------------
# r2 additions: the reference scenario classes review r1 flagged as missing
# (test_compatibility.py:98-920): randomized nullable joins over many key
# types, ORDER BY NULL permutations at scale, randomized INTERSECT/EXCEPT,
# and the agg-over-empty-group edge matrix
# ---------------------------------------------------------------------------

def test_join_nullable_int_keys_rand():
    a = make_rand_df(60, k=(int, 20), va=float)
    b = make_rand_df(40, k=(int, 15), vb=float)
    # NULL keys join nothing (inner) / NULL-extend (left) — both oracles
    eq_sqlite("SELECT a.k, va, vb FROM a JOIN b ON a.k = b.k", a=a, b=b)
    eq_sqlite("SELECT a.k, va, vb FROM a LEFT JOIN b ON a.k = b.k", a=a, b=b)


def test_join_nullable_string_keys_rand():
    a = make_rand_df(60, k=(str, 20), va=float)
    b = make_rand_df(40, k=(str, 15), vb=float)
    eq_sqlite("SELECT a.k, va, vb FROM a JOIN b ON a.k = b.k", a=a, b=b)
    eq_sqlite("SELECT a.k, va, vb FROM a LEFT JOIN b ON a.k = b.k", a=a, b=b)


def test_join_nullable_float_keys_rand():
    a = make_rand_df(50, k=(float, 15), va=int)
    b = make_rand_df(30, k=(float, 10), vb=int)
    eq_sqlite("SELECT a.k, va, vb FROM a JOIN b ON a.k = b.k", a=a, b=b)


def test_join_nullable_bool_keys_rand():
    a = make_rand_df(30, k=(bool, 8), va=float)
    b = make_rand_df(20, k=(bool, 5), vb=float)
    eq_sqlite("SELECT a.k, va, vb FROM a JOIN b ON a.k = b.k", a=a, b=b)


def test_join_mixed_nullable_multi_key_rand():
    a = make_rand_df(80, k1=(int, 25), k2=(str, 25), va=float)
    b = make_rand_df(60, k1=(int, 20), k2=(str, 20), vb=float)
    eq_sqlite(
        """SELECT a.k1, a.k2, va, vb FROM a
           JOIN b ON a.k1 = b.k1 AND a.k2 = b.k2""", a=a, b=b)
    eq_sqlite(
        """SELECT a.k1, a.k2, va, vb FROM a
           LEFT JOIN b ON a.k1 = b.k1 AND a.k2 = b.k2""", a=a, b=b)


def test_order_by_null_permutations_at_scale():
    a = make_rand_df(300, a=(int, 100), b=(str, 100), c=(float, 100))
    for mods in ("a NULLS FIRST, b NULLS FIRST, c NULLS FIRST",
                 "a NULLS LAST, b NULLS FIRST, c NULLS LAST",
                 "a DESC NULLS FIRST, b NULLS LAST, c DESC NULLS LAST",
                 "a DESC NULLS LAST, b DESC NULLS FIRST, c NULLS FIRST"):
        eq_sqlite(f"SELECT * FROM a ORDER BY {mods}",
                  check_row_order=True, a=a)


def test_intersect_except_rand():
    a = make_rand_df(60, x=(int, 10), y=(str, 10))
    b = make_rand_df(60, x=(int, 10), y=(str, 10))
    eq_sqlite("SELECT x, y FROM a INTERSECT SELECT x, y FROM b", a=a, b=b)
    eq_sqlite("SELECT x, y FROM a EXCEPT SELECT x, y FROM b", a=a, b=b)
    eq_sqlite("SELECT x FROM a EXCEPT SELECT x FROM b", a=a, b=b)
    eq_sqlite("SELECT y FROM a INTERSECT SELECT y FROM b", a=a, b=b)


def test_agg_over_empty_group_matrix():
    a = make_rand_df(40, g=(str, 10), i=(int, 10), f=(float, 10), s=(str, 15))
    # empty input (WHERE FALSE): global aggs -> one row of NULLs/zero
    eq_sqlite(
        """SELECT SUM(i) AS si, AVG(f) AS af, MIN(s) AS ms, MAX(i) AS xi,
                  COUNT(i) AS ci, COUNT(*) AS n
           FROM a WHERE i > 1000""", a=a)
    # groups whose every member is NULL in the aggregated column
    eq_sqlite(
        """SELECT g, SUM(i) AS si, AVG(f) AS af, COUNT(i) AS ci,
                  COUNT(*) AS n, MIN(f) AS mf, MAX(s) AS xs
           FROM a GROUP BY g""", a=a)
    # HAVING over an empty grouping
    eq_sqlite(
        """SELECT g, COUNT(*) AS n FROM a WHERE i > 1000
           GROUP BY g HAVING COUNT(*) > 0""", a=a)


def test_self_join_rand():
    a = make_rand_df(40, k=(int, 10), v=float)
    eq_sqlite(
        """SELECT x.k, x.v AS xv, y.v AS yv
           FROM a x JOIN a y ON x.k = y.k WHERE x.v < y.v""", a=a)


def test_anti_semi_rand():
    a = make_rand_df(60, k=(int, 15), v=float)
    b = make_rand_df(30, k=(int, 10))
    eq_sqlite("SELECT * FROM a WHERE EXISTS "
              "(SELECT 1 FROM b WHERE b.k = a.k)", a=a, b=b)
    eq_sqlite("SELECT * FROM a WHERE NOT EXISTS "
              "(SELECT 1 FROM b WHERE b.k = a.k)", a=a, b=b)
