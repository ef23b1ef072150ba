"""EXPLAIN ANALYZE + QueryReport integration tests.

Pins the observability surface the ISSUE 3 acceptance criteria name:
EXPLAIN ANALYZE over a join+groupby returns a plan tree where EVERY
executed node carries wall-time and row counts, and every Context.sql call
attaches a QueryReport whose invariants (phase sums <= wall, stage spans
matching the stage_graphs counter) hold.
"""
import os

import pandas as pd
import pytest

from dask_sql_tpu import Context


@pytest.fixture
def ctx():
    c = Context()
    c.create_table("t", pd.DataFrame({
        "a": [1, 2, 3, 1, 2, 1], "k": [10, 20, 30, 10, 20, 30]}))
    c.create_table("u", pd.DataFrame({
        "k": [10, 20, 30], "name": list("xyz")}))
    return c


JOIN_GROUPBY = ("SELECT name, SUM(a) AS s FROM t "
                "JOIN u ON t.k = u.k GROUP BY name")


def test_explain_analyze_annotates_every_executed_node(ctx):
    out = ctx.sql("EXPLAIN ANALYZE " + JOIN_GROUPBY, return_futures=False)
    lines = list(out["PLAN"])
    plan_lines = [l for l in lines if not l.startswith("--")]
    # join + groupby plan: scan x2, join, aggregate at minimum
    assert len(plan_lines) >= 4
    assert any("LogicalJoin" in l for l in plan_lines)
    assert any("LogicalAggregate" in l for l in plan_lines)
    for line in plan_lines:
        assert "rows=" in line, f"node missing row count: {line}"
        assert "time=" in line and "ms" in line, \
            f"node missing wall time: {line}"
        assert "self=" in line
    # summary trailer names the run
    assert any(l.startswith("-- analyzed:") and "wall=" in l
               for l in lines)


def test_explain_analyze_row_counts_are_real(ctx):
    out = ctx.sql("EXPLAIN ANALYZE " + JOIN_GROUPBY, return_futures=False)
    lines = list(out["PLAN"])
    # 3 distinct names -> the aggregate (and the root) output 3 rows
    agg = next(l for l in lines if "LogicalAggregate" in l)
    assert "rows=3" in agg
    # the join output carries all 6 probe rows
    join = next(l for l in lines if "LogicalJoin" in l)
    assert "rows=6" in join
    trailer = next(l for l in lines if l.startswith("-- analyzed:"))
    assert "rows_out=3" in trailer


def test_explain_analyze_tier_line(ctx):
    """The ``-- tier:`` trailer mirrors ``-- cache:``: the execution tier
    a PLAIN run of this plan would answer on (the analyzed run itself is
    always eager, per-node instrumentation being the point)."""
    out = ctx.sql("EXPLAIN ANALYZE " + JOIN_GROUPBY, return_futures=False)
    lines = list(out["PLAN"])
    tier_line = next(l for l in lines if l.startswith("-- tier:"))
    tier = tier_line.split()[2]
    assert tier in ("eager", "compiled", "eager-compiling", "compiled-cold")
    # tests pin tiering off and DSQL_COMPILE stays on: a cold plan would
    # pay the compile on arrival
    if os.environ.get("DSQL_COMPILE") != "0":
        assert tier in ("compiled", "compiled-cold")
    assert any(l.startswith("-- cache:") for l in lines)  # both trailers


def test_plain_explain_unchanged(ctx):
    out = ctx.sql("EXPLAIN " + JOIN_GROUPBY, return_futures=False)
    lines = list(out["PLAN"])
    assert any("LogicalJoin" in l for l in lines)
    assert not any("rows=" in l or "time=" in l for l in lines)


def test_explain_analyze_python_parser_gate(ctx):
    """EXPLAIN ANALYZE must parse regardless of the native parser (whose
    grammar predates ANALYZE) — the parse_sql gate routes it to the
    Python parser."""
    from dask_sql_tpu.sql import parser as P

    stmts = P.parse_sql("EXPLAIN ANALYZE SELECT 1 + 1")
    assert len(stmts) == 1
    assert type(stmts[0]).__name__ == "ExplainStatement"
    assert stmts[0].analyze is True
    stmts = P.parse_sql("EXPLAIN SELECT 1 + 1")
    assert stmts[0].analyze is False


# ---------------------------------------------------------------------------
# QueryReport invariants
# ---------------------------------------------------------------------------

def test_query_report_attached_and_invariants(ctx):
    df = ctx.sql(JOIN_GROUPBY, return_futures=False)
    rep = ctx.last_report
    assert rep is not None
    assert rep.query == JOIN_GROUPBY
    assert rep.wall_ms > 0
    # the top-level phases partition the wall: their sum can never exceed it
    top = sum(rep.phases.get(k, 0.0)
              for k in ("parse", "plan", "execute", "fetch"))
    assert top <= rep.wall_ms + 1e-6
    # nested phases are bounded by their parent
    assert rep.phases.get("compile", 0.0) + rep.phases.get(
        "materialize", 0.0) <= rep.phases.get("execute", 0.0) + 1e-6
    assert rep.rows_out == len(df)
    assert rep.bytes_out > 0


def test_query_report_cache_hit_second_run(ctx):
    if os.environ.get("DSQL_COMPILE") == "0":
        pytest.skip("asserts compiled-path spans")
    ctx.sql(JOIN_GROUPBY, return_futures=False)
    ctx.sql(JOIN_GROUPBY, return_futures=False)
    rep = ctx.last_report
    assert rep.counters.get("hits", 0) >= 1
    assert "compiles" not in rep.counters  # steady state: no new compile
    # the cache hit is annotated on a span in the tree
    assert any(s.attrs.get("cache_hit") for s in rep.root.walk())


def test_query_report_stage_spans_match_stage_graphs(ctx, monkeypatch):
    """Report invariant: the span tree records exactly as many stage_graph
    spans as the stage_graphs counter delta, and at least 2 stages per
    graph (a 1-stage partition would have run whole)."""
    if os.environ.get("DSQL_COMPILE") == "0":
        pytest.skip("asserts compiled-path spans")
    monkeypatch.setenv("DSQL_STAGE_HEAVY", "1")
    c = Context()
    c.create_table("t", pd.DataFrame({
        "a": [1, 2, 3, 1, 2, 1], "k": [10, 20, 30, 10, 20, 30]}))
    c.create_table("u", pd.DataFrame({
        "k": [10, 20, 30], "name": list("xyz")}))
    c.sql(JOIN_GROUPBY, return_futures=False)
    rep = c.last_report
    graphs = rep.counters.get("stage_graphs", 0)
    assert graphs >= 1, "DSQL_STAGE_HEAVY=1 must stage a join+groupby plan"
    assert rep.span_count("stage_graph") == graphs
    assert rep.span_count("stage") >= 2


def test_report_survives_query_error(ctx):
    with pytest.raises(Exception):
        ctx.sql("SELECT * FROM missing_table", return_futures=False)
    rep = ctx.last_report
    assert rep is not None
    assert rep.root.attrs.get("error")


def test_explain_analyze_returns_meta_table(ctx):
    """EXPLAIN ANALYZE is plain SQL returning a meta Table with a PLAN
    column — the shape the server's wire encoder (and any client) already
    understands."""
    table = ctx.sql("EXPLAIN ANALYZE SELECT a FROM t WHERE a > 1")
    assert table.names == ["PLAN"]
    assert table.num_rows >= 2
