"""Differential tests: native C++ parser vs the pure-Python parser.

Every statement in the corpus must produce structurally identical ASTs from
both front-ends (dataclass equality), including positions — the strongest
oracle available for the native planner (mirrors the reference's strategy of
validating its native planner through the Python integration suite).
"""
import pytest

from dask_sql_tpu import native
from dask_sql_tpu.sql import native_bridge
from dask_sql_tpu.sql.parser import Parser
from dask_sql_tpu.utils import ParsingException

pytestmark = pytest.mark.skipif(
    native.load() is None, reason="native parser library unavailable")

CORPUS = [
    # projections / expressions
    "SELECT 1",
    "SELECT 1 + 1 AS two, -3.5e2, .5, 'it''s', NULL, TRUE, FALSE",
    "SELECT a, b AS c, t.*, * FROM t",
    "SELECT DISTINCT a FROM t",
    "SELECT a + b * c - d / e % f, a || b || 'x' FROM t",
    "SELECT (a + b) * (c - d) FROM t",
    "SELECT CASE WHEN a > 1 THEN 'x' WHEN a > 0 THEN 'y' ELSE 'z' END FROM t",
    "SELECT CASE a WHEN 1 THEN 'one' ELSE 'many' END FROM t",
    "SELECT CAST(a AS DOUBLE), CAST(b AS DECIMAL(10, 2)), a :: VARCHAR FROM t",
    "SELECT CAST(a AS DOUBLE PRECISION) FROM t",
    "SELECT a IS NULL, b IS NOT NULL, c IS TRUE, d IS NOT FALSE, e IS UNKNOWN FROM t",
    "SELECT a IS DISTINCT FROM b, a IS NOT DISTINCT FROM b FROM t",
    "SELECT a BETWEEN 1 AND 10, b NOT BETWEEN SYMMETRIC 2 AND 0 FROM t",
    "SELECT a IN (1, 2, 3), b NOT IN ('x', 'y') FROM t",
    "SELECT a LIKE 'x%', b NOT LIKE '_y' ESCAPE '\\', c ILIKE '%Z%' FROM t",
    "SELECT a SIMILAR TO 'x|y', b NOT SIMILAR TO '[0-9]*' FROM t",
    "SELECT NOT a OR b AND NOT c FROM t",
    "SELECT a = 1, b <> 2, c != 3, d < 4, e <= 5, f > 6, g >= 7 FROM t",
    "SELECT -a, +b, -(-c) FROM t",
    "SELECT SUM(x), COUNT(*), COUNT(DISTINCT y), AVG(ALL z) FROM t",
    "SELECT SUM(x) FILTER (WHERE y > 0) FROM t",
    'SELECT "Quoted Col", `backtick`, "with""quote" FROM "My Table"',
    "SELECT f(a, b, c), g(), my_udf(x + 1) FROM t",
    # string/date builtins with special syntax
    "SELECT SUBSTRING('hello' FROM 2 FOR 3), SUBSTRING(s, 1, 2), SUBSTRING(s, 5) FROM t",
    "SELECT TRIM(s), TRIM(BOTH 'x' FROM s), TRIM(LEADING FROM s), TRIM(TRAILING 'y' FROM s) FROM t",
    "SELECT POSITION('a' IN s), OVERLAY(s PLACING 'xx' FROM 2 FOR 3), OVERLAY(s PLACING 'y' FROM 1) FROM t",
    "SELECT EXTRACT(YEAR FROM d), EXTRACT(DOW FROM d) FROM t",
    "SELECT CEIL(x), CEILING(y), FLOOR(z), CEIL(d TO MONTH), FLOOR(d TO DAY) FROM t",
    "SELECT CURRENT_DATE, CURRENT_TIMESTAMP, LOCALTIMESTAMP FROM t",
    "SELECT DATE '2020-01-01', TIMESTAMP '2020-01-01 10:00:00', TIME '10:11:12'",
    "SELECT INTERVAL '3' DAY, INTERVAL 5 HOURS, INTERVAL - 2 MINUTE, INTERVAL '1-2' YEAR TO MONTH",
    "SELECT ROW(1, 'x'), (a, b) = (1, 2) FROM t",
    # FROM / joins
    "SELECT * FROM a, b, c",
    "SELECT * FROM a JOIN b ON a.x = b.y",
    "SELECT * FROM a INNER JOIN b ON a.x = b.y LEFT JOIN c ON b.z = c.w",
    "SELECT * FROM a LEFT OUTER JOIN b ON a.x = b.y",
    "SELECT * FROM a RIGHT JOIN b USING (x, y)",
    "SELECT * FROM a FULL OUTER JOIN b ON a.x = b.y OR a.z < b.w",
    "SELECT * FROM a CROSS JOIN b",
    "SELECT * FROM a NATURAL JOIN b",
    "SELECT * FROM (SELECT x FROM t) AS sub (col1)",
    "SELECT * FROM (SELECT x FROM t) sub",
    "SELECT * FROM schema1.table1 AS t1 (a, b)",
    "SELECT * FROM t TABLESAMPLE SYSTEM (20)",
    "SELECT * FROM t TABLESAMPLE BERNOULLI (50.5) REPEATABLE (42)",
    "SELECT * FROM (a JOIN b ON a.x = b.y) JOIN c ON b.z = c.w",
    # grouping / having / sorting / limits
    "SELECT a, SUM(b) FROM t GROUP BY a HAVING SUM(b) > 10",
    "SELECT a, b, COUNT(*) FROM t GROUP BY (a, b)",
    "SELECT a FROM t GROUP BY ()",
    "SELECT a FROM t ORDER BY a DESC, b ASC NULLS FIRST, c NULLS LAST LIMIT 10 OFFSET 5",
    "SELECT a FROM t ORDER BY 1 FETCH FIRST 3 ROWS ONLY",
    "SELECT a FROM t LIMIT 2 + 3",
    # set ops / CTEs / values
    "SELECT a FROM t UNION SELECT b FROM u",
    "SELECT a FROM t UNION ALL SELECT b FROM u INTERSECT SELECT c FROM v",
    "SELECT a FROM t EXCEPT DISTINCT SELECT b FROM u ORDER BY a LIMIT 1",
    "SELECT a FROM t MINUS SELECT b FROM u",
    "WITH x AS (SELECT 1 AS a), y AS (SELECT a + 1 AS b FROM x) SELECT * FROM y",
    "WITH x AS (SELECT 1 AS a) SELECT a FROM x UNION SELECT a FROM x",
    "VALUES (1, 'a'), (2, 'b')",
    "SELECT * FROM (VALUES (1, 2), (3, 4)) AS v (x, y)",
    "(SELECT a FROM t) UNION (SELECT b FROM u)",
    # subqueries
    "SELECT (SELECT MAX(x) FROM t) AS m",
    "SELECT a FROM t WHERE a IN (SELECT b FROM u)",
    "SELECT a FROM t WHERE a NOT IN (SELECT b FROM u WHERE c > 0)",
    "SELECT a FROM t WHERE EXISTS (SELECT 1 FROM u WHERE u.x = t.a)",
    "SELECT a FROM t WHERE a > ANY (SELECT b FROM u)",
    "SELECT a FROM t WHERE a <= ALL (SELECT b FROM u)",
    "SELECT a FROM t WHERE a = SOME (SELECT b FROM u)",
    # window functions
    "SELECT ROW_NUMBER() OVER (PARTITION BY a ORDER BY b DESC) FROM t",
    "SELECT SUM(x) OVER (PARTITION BY a, b ORDER BY c ROWS BETWEEN 2 PRECEDING AND CURRENT ROW) FROM t",
    "SELECT SUM(x) OVER (ROWS BETWEEN UNBOUNDED PRECEDING AND UNBOUNDED FOLLOWING) FROM t",
    "SELECT COUNT(*) OVER (ORDER BY a RANGE UNBOUNDED PRECEDING) FROM t",
    "SELECT FIRST_VALUE(x) OVER (PARTITION BY g ORDER BY o ROWS BETWEEN 1 PRECEDING AND 1 FOLLOWING) FROM t",
    # custom statements (reference grammar: create/model/show ftl)
    "CREATE TABLE t2 WITH (location = 'data.csv', format = 'csv', persist = True)",
    "CREATE OR REPLACE TABLE t2 WITH (gpu = False, x = 3, y = -1.5, z = NULL)",
    "CREATE TABLE IF NOT EXISTS t2 AS (SELECT * FROM t)",
    "CREATE VIEW v AS (SELECT a FROM t WHERE a > 0)",
    "CREATE OR REPLACE VIEW v AS SELECT 1",
    "CREATE SCHEMA myschema",
    "CREATE SCHEMA IF NOT EXISTS other",
    "DROP SCHEMA IF EXISTS other",
    "DROP TABLE IF EXISTS t2",
    "DROP MODEL IF EXISTS m",
    "USE SCHEMA myschema",
    "SHOW SCHEMAS",
    "SHOW SCHEMAS LIKE 'foo'",
    "SHOW TABLES",
    "SHOW TABLES FROM myschema",
    "SHOW COLUMNS FROM t",
    "SHOW COLUMNS FROM myschema.t",
    "SHOW MODELS",
    "DESCRIBE MODEL m",
    "DESCRIBE t",
    "ANALYZE TABLE t COMPUTE STATISTICS FOR ALL COLUMNS",
    "ANALYZE TABLE t COMPUTE STATISTICS FOR COLUMNS a, b",
    "CREATE MODEL m WITH (model_class = 'sklearn.linear_model.LinearRegression', "
    "target_column = 'y', wrap_predict = True, n = 3, f = 1.5, "
    "tags = ARRAY ['a', 'b'], nested = (x = 1), m2 = MAP ['k', 'v']) AS (SELECT 1 AS y)",
    "CREATE EXPERIMENT e WITH (automl_class = 'x.Y') AS (SELECT a, y FROM t)",
    "EXPORT MODEL m WITH (format = 'pickle', location = '/tmp/m.pkl')",
    "SELECT * FROM PREDICT(MODEL m, SELECT a, b FROM t)",
    "SELECT * FROM PREDICT(MODEL s.m, SELECT a FROM t) AS p",
    "EXPLAIN SELECT a FROM t WHERE a > 0",
    # multiple statements
    "SELECT 1; SELECT 2;",
    "CREATE SCHEMA s1; USE SCHEMA s1; SELECT 1",
    # outer ORDER BY/LIMIT over raw bodies (must wrap, not merge/drop)
    "VALUES (1), (2), (3) LIMIT 2",
    "VALUES (1), (2), (3) ORDER BY 1 DESC LIMIT 1 OFFSET 1",
    "(SELECT a FROM t) ORDER BY a",
    "(SELECT a FROM t ORDER BY a LIMIT 5) LIMIT 2",
    "(SELECT a FROM t UNION SELECT b FROM s ORDER BY 1) LIMIT 2",
    "WITH c AS (SELECT a FROM t) SELECT a FROM c UNION ALL SELECT 9"
    " ORDER BY 1 LIMIT 3 OFFSET 1",
    "SELECT a FROM t UNION SELECT b FROM s ORDER BY 1 LIMIT 3",
]


@pytest.mark.parametrize("sql", CORPUS, ids=range(len(CORPUS)))
def test_native_matches_python(sql):
    envelope = native.parse_to_json(sql)
    assert envelope is not None
    native_ast = native_bridge.json_to_statements(envelope, sql)
    python_ast = Parser(sql).parse_statements()
    assert native_ast == python_ast


def test_original_name_preserved():
    sql = "SELECT MyUdf(x) FROM t"
    native_ast = native_bridge.json_to_statements(native.parse_to_json(sql), sql)
    python_ast = Parser(sql).parse_statements()
    n_call = native_ast[0].query.projections[0][0]
    p_call = python_ast[0].query.projections[0][0]
    assert n_call.original_name == p_call.original_name == "MyUdf"


ERROR_CORPUS = [
    "SELECT FROM FROM t",
    "SELECT (a FROM t",
    "SELECT * FROM",
    "CREATE TABLE",
    "SELECT a FROM t WHERE",
    "SELECT 'unterminated",
    "SELECT a FROM t GROUP",
    "FROB THE KNOB",
    "SELECT a b c, FROM t",
    # truncated statements must error cleanly, not read past the END token
    "SHOW SCHEMAS LIKE",
    "SELECT CAST(a AS DECIMAL(",
    "SELECT a FROM t ORDER BY",
    "SELECT INTERVAL",
]


def test_interval_nonfinite_value():
    """Overflowing interval strings survive the JSON round trip (inf/nan)."""
    for sql in ("SELECT INTERVAL '1e400' DAY", "SELECT INTERVAL '-1e400' DAY"):
        n = native_bridge.json_to_statements(native.parse_to_json(sql), sql)
        p = Parser(sql).parse_statements()
        assert n == p


@pytest.mark.parametrize("sql", ERROR_CORPUS, ids=range(len(ERROR_CORPUS)))
def test_native_errors_match_python_positions(sql):
    """Both parsers must reject, reporting the same error position."""
    with pytest.raises(ParsingException) as native_exc:
        stmts = native_bridge.json_to_statements(native.parse_to_json(sql), sql)
        assert stmts is None, f"native parser accepted: {sql}"
    with pytest.raises(ParsingException):
        Parser(sql).parse_statements()
    assert "^" in str(native_exc.value) or "Unterminated" in str(native_exc.value)


@pytest.mark.parametrize("sql", [
    "PREPARE above AS SELECT a, b FROM t WHERE a > ?",
    "EXECUTE above (5, 'x')",
    "DEALLOCATE above",
    "SELECT a FROM t WHERE a > $1 AND b < $2",
    "SELECT a FROM t WHERE a > ? AND b < ?",
])
def test_statements_native_grammar_lacks_still_parse(sql):
    """With the native library LOADED, ``parse_sql`` must serve prepared
    statements and parameter markers from the Python parser: the C++
    grammar has no PREPARE/EXECUTE/DEALLOCATE, refuses ``$n`` and numbers
    every ``?`` as parameter 0, and its parse errors are raised, not
    bounced."""
    from dask_sql_tpu.sql.parser import parse_sql
    assert native.load() is not None
    assert parse_sql(sql) == Parser(sql).parse_statements()
