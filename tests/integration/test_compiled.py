"""Compiled-pipeline executor tests: equivalence with the eager path,
capacity escalation, runtime fallback, and plan caching.

The compiled executor (physical/compiled.py) traces whole plans into one
jitted program; these tests pin its semantics to the eager executor's over
the shared fixture catalog (conftest.py) — the same differential strategy the
reference uses between dask-sql and sqlite (test_compatibility.py:22-67).
"""
import os

import pandas as pd
import pytest

from dask_sql_tpu.physical import caps, compiled, identity, programs


_needs_compiled = pytest.mark.skipif(
    os.environ.get("DSQL_COMPILE") == "0",
    reason="asserts compiled-path usage; meaningless with DSQL_COMPILE=0")


def _both_paths(c, query):
    """Run query compiled and eager; return (compiled_df, eager_df)."""
    comp = c.sql(query, return_futures=False)
    prev = os.environ.get("DSQL_COMPILE")
    os.environ["DSQL_COMPILE"] = "0"
    try:
        eager = c.sql(query, return_futures=False)
    finally:
        if prev is None:
            del os.environ["DSQL_COMPILE"]
        else:
            os.environ["DSQL_COMPILE"] = prev
    return comp, eager


def _assert_same(comp: pd.DataFrame, eager: pd.DataFrame, ordered: bool):
    if not ordered:
        cols = list(comp.columns)
        comp = comp.sort_values(cols, ignore_index=True)
        eager = eager.sort_values(cols, ignore_index=True)
    pd.testing.assert_frame_equal(comp.reset_index(drop=True),
                                  eager.reset_index(drop=True),
                                  check_dtype=False)


QUERIES = [
    ("SELECT * FROM df_simple", False),
    ("SELECT a + b AS s, a * b AS p FROM df_simple WHERE a > 1", False),
    ("SELECT a, SUM(b) AS sb, COUNT(*) AS n, AVG(b) AS ab FROM df GROUP BY a", False),
    ("SELECT a, SUM(b) FILTER (WHERE b > 5) AS sb FROM df GROUP BY a", False),
    ("SELECT SUM(b) AS sb, MIN(a) AS ma, MAX(b) AS mb FROM df", False),
    ("SELECT user_id, SUM(b) AS x FROM user_table_1 GROUP BY user_id "
     "HAVING SUM(b) > 2", False),
    ("SELECT * FROM df WHERE b BETWEEN 2 AND 6 ORDER BY b DESC LIMIT 7", True),
    ("SELECT * FROM df ORDER BY a ASC, b DESC LIMIT 5 OFFSET 3", True),
    ("SELECT u1.user_id, u2.c FROM user_table_1 u1 "
     "JOIN user_table_2 u2 ON u1.user_id = u2.user_id", False),
    ("SELECT u1.user_id, u2.c FROM user_table_1 u1 "
     "LEFT JOIN user_table_2 u2 ON u1.user_id = u2.user_id", False),
    ("SELECT user_id FROM user_table_1 WHERE user_id IN "
     "(SELECT user_id FROM user_table_2)", False),
    ("SELECT lk_nullint FROM user_table_lk WHERE lk_nullint IS NOT NULL", False),
    ("SELECT a FROM string_table WHERE a LIKE '%normal%'", False),
    ("SELECT user_id FROM user_table_1 UNION SELECT user_id FROM user_table_2",
     False),
    ("SELECT user_id FROM user_table_1 UNION ALL "
     "SELECT user_id FROM user_table_2", False),
    ("SELECT CASE WHEN a > 1 THEN b ELSE -b END AS x FROM df_simple", False),
    ("SELECT lk_nullint, COUNT(*) AS n FROM user_table_lk GROUP BY lk_nullint",
     False),
    ("SELECT c FROM user_table_nan WHERE c IS NOT NULL ORDER BY c", True),
]


@pytest.mark.parametrize("query,ordered", QUERIES)
def test_compiled_matches_eager(c, query, ordered):
    comp, eager = _both_paths(c, query)
    _assert_same(comp, eager, ordered)


@_needs_compiled
def test_compiled_path_used(c):
    before = compiled.stats["compiles"] + compiled.stats["hits"]
    c.sql("SELECT a, SUM(b) AS s FROM df GROUP BY a")
    after = compiled.stats["compiles"] + compiled.stats["hits"]
    assert after == before + 1


@_needs_compiled
def test_left_join_actually_compiles(c):
    """LEFT joins must run compiled (guards against trace-breaking syncs in
    the masked-gather path). The build side needs UNIQUE keys: a duplicate
    build key (user_table_2 has one) is a legitimate runtime fallback, and
    this test must observe a clean compile-and-run, not that fallback."""
    c.create_table("lj_build", pd.DataFrame({"user_id": [1, 2, 4],
                                             "c": [10, 20, 40]}))
    before_uns = compiled.stats["unsupported"]
    before = compiled.stats["compiles"] + compiled.stats["hits"]
    fb = compiled.stats["fallbacks"]
    c.sql("SELECT u1.user_id, u2.c FROM user_table_1 u1 "
          "LEFT JOIN lj_build u2 ON u1.user_id = u2.user_id")
    assert compiled.stats["compiles"] + compiled.stats["hits"] == before + 1
    assert compiled.stats["unsupported"] == before_uns
    assert compiled.stats["fallbacks"] == fb
    c.drop_table("lj_build")


@_needs_compiled
def test_cache_hit_on_repeat(c):
    q = "SELECT a, COUNT(*) AS n FROM df WHERE b < 9 GROUP BY a"
    c.sql(q)
    hits = compiled.stats["hits"]
    c.sql(q)
    assert compiled.stats["hits"] == hits + 1


@_needs_compiled
def test_group_capacity_escalation(c, monkeypatch):
    # force a tiny initial capacity: the first run overflows, the host
    # recompiles with a doubled capacity, the result is still exact
    monkeypatch.setattr(caps, "DEFAULT_GROUP_CAP", 2)
    rec = compiled.stats["recompiles"]
    comp, eager = _both_paths(
        c, "SELECT b, COUNT(*) AS n FROM df GROUP BY b")
    _assert_same(comp, eager, ordered=False)
    assert compiled.stats["recompiles"] > rec


@_needs_compiled
def test_group_caps_persist_to_file(c, monkeypatch, tmp_path):
    # DSQL_CAPS_FILE write-through: an escalation learned by this "process"
    # must be found by a cold one (simulated by clearing every in-memory
    # cache), so the first compile already uses the right capacity — a
    # recompile costs a whole program compile
    caps_file = tmp_path / "caps.json"
    monkeypatch.setenv("DSQL_CAPS_FILE", str(caps_file))
    monkeypatch.setattr(caps, "DEFAULT_GROUP_CAP", 2)
    monkeypatch.setattr(caps, "_caps_disk", None)
    # distinct from the escalation test's query: the learned cap survives in
    # the restored in-memory dict after this test, and sharing a fingerprint
    # would rob that test of its recompile
    q = "SELECT b, SUM(a) AS s FROM df GROUP BY b"
    rec = compiled.stats["recompiles"]
    c.sql(q)
    assert compiled.stats["recompiles"] > rec
    assert caps_file.exists()
    # cold process: no programs, no in-memory caps — only the file
    monkeypatch.setattr(programs, "_cache", type(programs._cache)())
    monkeypatch.setattr(caps, "_learned_caps",
                        type(caps._learned_caps)())
    monkeypatch.setattr(caps, "_caps_disk", None)
    rec = compiled.stats["recompiles"]
    comp, eager = _both_paths(c, q)
    _assert_same(comp, eager, ordered=False)
    assert compiled.stats["recompiles"] == rec


@_needs_compiled
def test_runtime_fallback_nonunique_build(c):
    # both sides have duplicate keys -> the unique-build invariant fails at
    # runtime; the flags vector reroutes to the eager executor, which handles
    # many-to-many joins
    fb = compiled.stats["fallbacks"]
    comp, eager = _both_paths(
        c, "SELECT u1.b, u2.b AS b2 FROM user_table_1 u1 "
           "JOIN user_table_1 u2 ON u1.user_id = u2.user_id")
    _assert_same(comp, eager, ordered=False)
    assert compiled.stats["fallbacks"] > fb


@_needs_compiled
@pytest.mark.parametrize("strategy", ["merge", "gather"])
def test_semi_join_heavy_duplicate_build(c, strategy, monkeypatch):
    # a SEMI join build side with one key repeated 200x: duplicates are
    # legal for SEMI/ANTI and BOTH join strategies must handle them
    # in-program (merge: the carried build row has the same raw key;
    # gather: the leftmost equal-hash candidate does), with no runtime
    # fallback. The merge path is TPU-preferred, so force it explicitly —
    # off-TPU the default would quietly test only the gather path.
    import numpy as np
    from dask_sql_tpu.ops import pallas_kernels
    monkeypatch.setattr(pallas_kernels, "_on_tpu",
                        lambda: strategy == "merge")
    big = pd.DataFrame({"k": np.r_[np.full(200, 7), np.arange(50)].astype(np.int64)})
    probe = pd.DataFrame({"k": np.arange(20).astype(np.int64)})
    # strategy-specific table names: the compiled-program cache keys on the
    # plan, and a cache hit would silently reuse the other strategy's program
    c.create_table(f"bucket_build_{strategy}", big)
    c.create_table(f"bucket_probe_{strategy}", probe)
    fb = compiled.stats["fallbacks"]
    comp, eager = _both_paths(
        c, f"SELECT k FROM bucket_probe_{strategy} WHERE k IN "
           f"(SELECT k FROM bucket_build_{strategy})")
    _assert_same(comp, eager, ordered=False)
    assert compiled.stats["fallbacks"] == fb
    c.drop_table(f"bucket_build_{strategy}")
    c.drop_table(f"bucket_probe_{strategy}")


@_needs_compiled
def test_unsupported_plan_falls_back(c):
    # LAG reads its offset constant on the host: outside the compiled subset
    uns = compiled.stats["unsupported"]
    r = c.sql("SELECT b, LAG(b, 1) OVER (ORDER BY b) AS lb FROM df_simple",
              return_futures=False)
    assert r["lb"].tolist()[1:] == [1.1, 2.2]
    assert compiled.stats["unsupported"] > uns


@_needs_compiled
def test_window_compiles(c):
    before = compiled.stats["compiles"] + compiled.stats["hits"]
    r = c.sql("SELECT b, ROW_NUMBER() OVER (ORDER BY b DESC) AS rn, "
              "SUM(b) OVER (PARTITION BY a) AS sb FROM df_simple",
              return_futures=False)
    assert compiled.stats["compiles"] + compiled.stats["hits"] == before + 1
    assert sorted(r["rn"].tolist()) == [1, 2, 3]


def test_compiled_disabled_by_env(c, monkeypatch):
    monkeypatch.setenv("DSQL_COMPILE", "0")
    n = compiled.stats["compiles"] + compiled.stats["hits"]
    r = c.sql("SELECT SUM(a) AS s FROM df_simple", return_futures=False)
    assert r["s"][0] == 6
    assert compiled.stats["compiles"] + compiled.stats["hits"] == n


def test_nan_join_key_matches_nothing(c):
    """NaN join keys must not match 0.0 (or other NaNs) on the compiled path
    (the hash canonicalizes NaN but match verification must not)."""
    import pandas as pd
    c.create_table("nan_l", pd.DataFrame({"x": [0.0, 1.0], "y": [0.0, 1.0]}))
    c.create_table("nan_r", pd.DataFrame({"f": [0.0, 1.0], "tag": [10, 20]}))
    comp, eager = _both_paths(
        c, "SELECT t.f2, r.tag FROM (SELECT x / y AS f2 FROM nan_l) t "
           "JOIN nan_r r ON t.f2 = r.f")
    _assert_same(comp, eager, ordered=False)
    assert len(comp) == 1  # only the 1.0 row; 0/0 -> NaN matches nothing


def test_desc_sort_nan_last_both_paths(c):
    """ORDER BY ... DESC keeps NaN last (XLA semantics) on both executors."""
    import pandas as pd
    c.create_table("nan_s", pd.DataFrame({"x": [0.0, 2.0, 1.0],
                                          "y": [0.0, 1.0, 1.0]}))
    comp, eager = _both_paths(
        c, "SELECT x / y AS r FROM nan_s ORDER BY r DESC")
    import numpy as np
    assert np.isnan(comp["r"].iloc[-1]) and np.isnan(eager["r"].iloc[-1])
    _assert_same(comp, eager, ordered=True)


@_needs_compiled
def test_distinct_aggregate_compiles(c, user_table_1):
    before = compiled.stats["compiles"] + compiled.stats["hits"]
    comp, eager = _both_paths(
        c, "SELECT user_id, COUNT(DISTINCT b) AS n, SUM(DISTINCT b) AS s "
           "FROM user_table_1 GROUP BY user_id")
    _assert_same(comp, eager, ordered=False)
    assert compiled.stats["compiles"] + compiled.stats["hits"] == before + 1
    comp, eager = _both_paths(
        c, "SELECT COUNT(DISTINCT b) AS n FROM user_table_1")
    _assert_same(comp, eager, ordered=True)


@_needs_compiled
def test_scalar_subquery_compiles(c, user_table_1):
    before = compiled.stats["compiles"] + compiled.stats["hits"]
    comp, eager = _both_paths(
        c, "SELECT user_id, b FROM user_table_1 "
           "WHERE b > (SELECT AVG(b) FROM user_table_1)")
    _assert_same(comp, eager, ordered=False)
    assert compiled.stats["compiles"] + compiled.stats["hits"] == before + 1


@_needs_compiled
def test_left_join_residual_compiles(c, user_table_1, user_table_2):
    # LEFT JOIN with a non-equi ON conjunct: the residual must knock out
    # pairs (NULL build side) without dropping probe rows
    before = compiled.stats["compiles"] + compiled.stats["hits"]
    # the cross-side conjunct survives push_join_side_conditions (a
    # build-only one would be rewritten into a pre-join filter and never
    # reach the compiled residual path)
    comp, eager = _both_paths(
        c, "SELECT u2.user_id, u2.c, u1.b FROM user_table_2 u2 "
           "LEFT JOIN user_table_1 u1 "
           "ON u2.user_id = u1.user_id AND u1.b > u2.user_id")
    _assert_same(comp, eager, ordered=False)
    assert compiled.stats["compiles"] + compiled.stats["hits"] == before + 1


@_needs_compiled
def test_anti_join_comparison_residual_compiles(c, monkeypatch):
    # NOT EXISTS with a build-vs-probe comparison residual (TPC-H Q21's
    # l3.l_suppkey <> l1.l_suppkey): per-hash-run build min/max/count decide
    # existence in-program on the merge path
    from dask_sql_tpu.ops import pallas_kernels
    monkeypatch.setattr(pallas_kernels, "_on_tpu", lambda: True)
    orders_df = pd.DataFrame({"ok": [1, 1, 1, 2, 2, 3],
                              "sk": [10, 11, 10, 20, 20, 30]})
    c.create_table("resid_li", orders_df)
    before = compiled.stats["compiles"] + compiled.stats["hits"]
    comp, eager = _both_paths(
        c, "SELECT l1.ok, l1.sk FROM resid_li l1 WHERE NOT EXISTS ("
           "SELECT * FROM resid_li l2 WHERE l2.ok = l1.ok AND l2.sk <> l1.sk)")
    _assert_same(comp, eager, ordered=False)
    # order 1 has two distinct suppliers -> excluded; orders 2,3 survive
    assert sorted(comp.ok.unique().tolist()) == [2, 3]
    assert compiled.stats["compiles"] + compiled.stats["hits"] == before + 1
    c.drop_table("resid_li")


@_needs_compiled
def test_cache_hit_on_reloaded_identical_data(c):
    """Reloading the same data (new Table objects, equal content) must HIT
    the program cache: the key is shapes/dtypes + dictionary content, not
    table identity — the reference recompiles nothing on new partitions
    either, and a per-load recompile would dwarf query time in any
    load-query-drop loop."""
    from dask_sql_tpu import Context

    def make_df():
        return pd.DataFrame({"k": ["x", "y", "x", "z"] * 5,
                             "v": list(range(20))})

    c1 = Context()
    c1.create_table("reload_t", make_df())
    q = "SELECT k, SUM(v) AS s FROM reload_t GROUP BY k"
    r1 = c1.sql(q, return_futures=False)
    compiles = compiled.stats["compiles"]
    hits = compiled.stats["hits"]

    c2 = Context()  # fresh context, freshly-built identical frame
    c2.create_table("reload_t", make_df())
    r2 = c2.sql(q, return_futures=False)
    assert compiled.stats["compiles"] == compiles, "recompiled on reload"
    assert compiled.stats["hits"] == hits + 1
    pd.testing.assert_frame_equal(
        r1.sort_values("k", ignore_index=True),
        r2.sort_values("k", ignore_index=True), check_dtype=False)

    # different dictionary content => different program (string constants
    # are baked in), so this must NOT hit the stale entry
    c3 = Context()
    df3 = make_df()
    df3.loc[3, "k"] = "w"  # same shape, same dtypes, new dictionary
    c3.create_table("reload_t", df3)
    r3 = c3.sql(q, return_futures=False)
    assert compiled.stats["compiles"] == compiles + 1
    assert set(r3["k"]) == {"w", "x", "y", "z"}
    assert int(r3.set_index("k").loc["w", "s"]) == 3


@_needs_compiled
def test_wide_build_side_merge_join(c, monkeypatch):
    """Wide build sides ride the sorted-probe join directly: its channel
    count is constant (columns arrive by row-id gathers), so the r1/r2
    width-triggered strategy switch no longer exists and width must not
    change results or the single-program property."""
    from dask_sql_tpu.ops import pallas_kernels
    monkeypatch.setattr(pallas_kernels, "_on_tpu", lambda: True)
    monkeypatch.delenv("DSQL_STRATEGY", raising=False)
    wide = pd.DataFrame({"user_id": [1, 2, 3],
                         **{f"w{i}": [i, i + 1, i + 2] for i in range(6)}})
    c.create_table("wide_build", wide)
    before = compiled.stats["compiles"] + compiled.stats["hits"]
    comp, eager = _both_paths(
        c, "SELECT u1.user_id, w.w0, w.w5 FROM user_table_1 u1 "
           "JOIN wide_build w ON u1.user_id = w.user_id")
    _assert_same(comp, eager, ordered=False)
    assert compiled.stats["compiles"] + compiled.stats["hits"] == before + 1
    c.drop_table("wide_build")


@_needs_compiled
def test_runtime_verdict_not_inherited_by_reloaded_data(c):
    """A duplicate-build-key fallback is pinned to the exact tables (uid),
    NOT the layout fingerprint: reloading corrected data with the same
    shapes/dtypes must get the compiled path back."""
    from dask_sql_tpu import Context

    q = ("SELECT p.k, b.v FROM rv_probe p JOIN rv_build b ON p.k = b.k")
    c1 = Context()
    c1.create_table("rv_probe", pd.DataFrame({"k": [1, 2, 3, 4]}))
    c1.create_table("rv_build", pd.DataFrame({"k": [1, 1, 2, 4],
                                              "v": [9, 8, 7, 6]}))
    fb = compiled.stats["fallbacks"]
    c1.sql(q, return_futures=False)
    assert compiled.stats["fallbacks"] > fb  # non-unique build -> eager

    c2 = Context()  # same layout, corrected (unique) keys
    c2.create_table("rv_probe", pd.DataFrame({"k": [1, 2, 3, 4]}))
    c2.create_table("rv_build", pd.DataFrame({"k": [1, 3, 2, 4],
                                              "v": [9, 8, 7, 6]}))
    fb2 = compiled.stats["fallbacks"]
    r = c2.sql(q, return_futures=False)
    assert compiled.stats["fallbacks"] == fb2, "inherited stale exile"
    assert sorted(r["k"].tolist()) == [1, 2, 3, 4]


@_needs_compiled
def test_compiled_path_uses_device_string_bitmap(monkeypatch):
    """Above the dictionary-cardinality threshold the COMPILED path picks
    the device bytes-matrix LIKE bitmap (r2 left it eager-only): the bitmap
    computes eagerly at trace time and bakes into the program as a
    constant, keyed by dictionary content."""
    import pandas as pd

    from dask_sql_tpu import Context
    from dask_sql_tpu.ops import strings_fast
    from dask_sql_tpu.physical import caps, compiled, identity, programs

    monkeypatch.setattr(strings_fast, "DEVICE_STRING_THRESHOLD", 1)
    c = Context()
    c.create_table("t", pd.DataFrame(
        {"s": ["special requests", "plain", "very special requests here",
               "nothing"] * 50}))
    before_dev = strings_fast.stats["device_bitmaps"]
    before = dict(compiled.stats)
    out = c.sql("SELECT COUNT(*) AS n FROM t WHERE s LIKE "
                "'%special%requests%'", return_futures=False)
    assert out["n"].tolist() == [100]
    assert compiled.stats["compiles"] > before["compiles"]  # compiled ran
    assert strings_fast.stats["device_bitmaps"] > before_dev  # device path


@pytest.mark.parametrize("workers", ["1", "4"])
def test_plan_splitting_matches_whole(monkeypatch, workers):
    """Plans above the heavy-node budget execute as a stage graph of
    bounded compiled programs with materialized temps between them (XLA:TPU
    compile time grows superlinearly with fused join count; TPC-H Q2's
    9-heavy program never finished compiling before stages).  Forced low
    budget via the legacy DSQL_SPLIT_HEAVY knob (compat path): the staged
    answer must agree with the eager answer and leave no temp schema
    behind — in both the serial and the worker-pool executor."""
    import pandas as pd

    from benchmarks.tpch import QUERIES, generate_tpch
    from dask_sql_tpu import Context
    from dask_sql_tpu.physical import compiled as cm

    monkeypatch.setenv("DSQL_SPLIT_HEAVY", "3")
    monkeypatch.setenv("DSQL_COMPILE_WORKERS", workers)
    monkeypatch.delenv("DSQL_STRATEGY", raising=False)
    data = generate_tpch(0.005)
    c1 = Context()
    for n, f in data.items():
        c1.create_table(n, f)
    graphs = cm.stats["stage_graphs"]
    for q in (2, 21, 18):
        got = c1.sql(QUERIES[q], return_futures=False)
        monkeypatch.setenv("DSQL_COMPILE", "0")
        want = c1.sql(QUERIES[q], return_futures=False)
        monkeypatch.setenv("DSQL_COMPILE", "1")
        pd.testing.assert_frame_equal(
            got.reset_index(drop=True), want.reset_index(drop=True),
            check_dtype=False, rtol=1e-5, atol=1e-8)
        split_schema = c1.schema.get("__split__")
        assert not (split_schema and split_schema.tables), \
            "split temps must be cleaned up"
    assert cm.stats["stage_graphs"] > graphs, "no plan was staged"


def test_learned_split_hint(monkeypatch, tmp_path):
    """A persisted "__split__" caps hint makes the plan execute as a stage
    graph (same answer), without any env knob — the mechanism that stops a
    plan whose whole program crashes the TPU compiler from
    re-crashing it in every process."""
    import pandas as pd

    from benchmarks.tpch import QUERIES, generate_tpch
    from dask_sql_tpu import Context
    from dask_sql_tpu.physical import compiled as cm

    monkeypatch.setenv("DSQL_CAPS_FILE", str(tmp_path / "caps.json"))
    monkeypatch.setattr(caps, "_caps_disk", None)
    monkeypatch.setattr(caps, "_learned_caps", type(caps._learned_caps)())
    data = generate_tpch(0.005)
    c = Context()
    for n, f in data.items():
        c.create_table(n, f)

    staged = []  # stage counts of each graph execution
    orig = cm._execute_stage_graph

    def spy(graph, context, query_fp, split_limit, run_program):
        staged.append(len(graph.stages))
        return orig(graph, context, query_fp, split_limit, run_program)

    monkeypatch.setattr(cm, "_execute_stage_graph", spy)

    # no hint: Q3 (3 heavy nodes, default budget 6) runs as one program
    got1 = c.sql(QUERIES[3], return_futures=False)
    assert staged == []

    # write the hint for this exact plan shape, as the failure path would
    # (which fingerprints the PARAMETERIZED plan — literals hoisted)
    from dask_sql_tpu.sql.parser import parse_sql
    plan = identity._maybe_parameterize(
        c._get_plan(parse_sql(QUERIES[3])[0].query), count=False)
    caps._learned_caps_put(identity.program_key(plan, c).key,
                           {"__split__": 1})

    got2 = c.sql(QUERIES[3], return_futures=False)
    assert staged and staged[0] >= 2, "hint must force the staged path"
    pd.testing.assert_frame_equal(got1.reset_index(drop=True),
                                  got2.reset_index(drop=True),
                                  check_dtype=False, rtol=1e-5, atol=1e-8)

    # a FRESH process state (cleared memo) still reads the hint from disk
    monkeypatch.setattr(caps, "_caps_disk", None)
    monkeypatch.setattr(caps, "_learned_caps", type(caps._learned_caps)())
    staged.clear()
    c.sql(QUERIES[3], return_futures=False)
    assert staged and staged[0] >= 2


@_needs_compiled
def test_cross_query_stage_cache_hit(monkeypatch):
    """Two queries sharing a subplan must share the shared stage's compiled
    program: the second query's stage comes back as a cache hit from a
    DIFFERENT origin query — observable as stats["cross_query_hits"]."""
    import numpy as np

    from dask_sql_tpu import Context
    from dask_sql_tpu.physical import compiled as cm

    monkeypatch.setenv("DSQL_STAGE_HEAVY", "1")
    rng = np.random.RandomState(0)
    c = Context()
    c.create_table("xq_fact", pd.DataFrame(
        {"k": rng.randint(0, 50, 1000), "v": rng.rand(1000)}))
    c.create_table("xq_dim", pd.DataFrame(
        {"k": np.arange(50), "w": np.arange(50) * 0.5}))
    shared = "(SELECT k, SUM(v) AS s FROM xq_fact GROUP BY k) x"
    before = dict(cm.stats)
    c.sql(f"SELECT x.k, x.s, d.w FROM {shared} "
          "JOIN xq_dim d ON x.k = d.k", return_futures=False)
    assert cm.stats["stage_graphs"] > before["stage_graphs"]
    assert cm.stats["cross_query_hits"] == before["cross_query_hits"]
    c.sql(f"SELECT x.k, x.s * 2 AS s2, d.w FROM {shared} "
          "JOIN xq_dim d ON x.k = d.k WHERE d.w > 5", return_futures=False)
    assert cm.stats["cross_query_hits"] > before["cross_query_hits"], \
        "shared subplan stage did not hit across queries"


def test_stage_temps_cleaned_on_exception(monkeypatch):
    """__split__ temp tables must be unregistered even when a stage raises
    mid-graph (the exception path of _execute_stage_graph's cleanup)."""
    import numpy as np

    from dask_sql_tpu import Context
    from dask_sql_tpu.physical import compiled as cm
    from dask_sql_tpu.sql.parser import parse_sql

    monkeypatch.setenv("DSQL_STAGE_HEAVY", "1")
    monkeypatch.setenv("DSQL_COMPILE_WORKERS", "1")  # deterministic order
    rng = np.random.RandomState(0)
    c = Context()
    c.create_table("exc_fact", pd.DataFrame(
        {"k": rng.randint(0, 20, 500), "v": rng.rand(500)}))
    c.create_table("exc_dim", pd.DataFrame(
        {"k": np.arange(20), "w": np.arange(20) * 1.5}))
    plan = c._get_plan(parse_sql(
        "SELECT x.k, x.s, d.w FROM (SELECT k, SUM(v) AS s FROM exc_fact "
        "GROUP BY k) x JOIN exc_dim d ON x.k = d.k")[0].query)

    graphs = []
    orig_part = cm._partition_plan

    def part_spy(p, budget, context):
        g = orig_part(p, budget, context)
        graphs.append(g)
        return g

    orig_single = cm._execute_single

    def boom(p, context, query_fp, split_limit=None, in_stage=False):
        if graphs and p is graphs[-1].stages[-1].plan:
            raise RuntimeError("injected root-stage failure")
        return orig_single(p, context, query_fp, split_limit,
                           in_stage=in_stage)

    monkeypatch.setattr(cm, "_partition_plan", part_spy)
    monkeypatch.setattr(cm, "_execute_single", boom)
    with pytest.raises(RuntimeError, match="injected"):
        cm.try_execute_compiled(plan, c)
    assert graphs, "plan was not staged"
    split_schema = c.schema.get("__split__")
    assert not (split_schema and split_schema.tables), \
        "exception path leaked __split__ temps"


def test_filter_compaction_learned_caps(monkeypatch):
    """Learned-capacity compaction after selective filters (TPU strategy):
    the compiled result must match eager, engage only above the size
    threshold, learn a tight cap via one shrink recompile, and not flip
    join build sides onto duplicate-key fact streams (the weight
    mechanism)."""
    import numpy as np

    from dask_sql_tpu.physical import compiled as cm

    monkeypatch.setenv("DSQL_STRATEGY", "tpu")
    monkeypatch.delenv("DSQL_CAPS_FILE", raising=False)
    rng = np.random.RandomState(0)
    n = 1 << 17  # above the compaction threshold
    fact = pd.DataFrame({
        "k": rng.randint(0, 5000, n),
        "sel": rng.randint(0, 100, n),
        "v": rng.randn(n),
    })
    dim = pd.DataFrame({"k": np.arange(5000),
                        "name": [f"d{i}" for i in range(5000)]})
    from dask_sql_tpu import Context
    ctx = Context()
    ctx.create_table("fact", fact)
    ctx.create_table("dim", dim)
    q = ("SELECT name, SUM(v) AS s, COUNT(*) AS c FROM fact "
         "JOIN dim ON fact.k = dim.k WHERE sel < 3 GROUP BY name")
    rec = cm.stats["recompiles"]
    fb = cm.stats["fallbacks"]
    got = ctx.sql(q, return_futures=False)
    monkeypatch.setenv("DSQL_COMPILE", "0")
    want = ctx.sql(q, return_futures=False)
    monkeypatch.setenv("DSQL_COMPILE", "1")
    cols = list(got.columns)
    pd.testing.assert_frame_equal(
        got.sort_values(cols, ignore_index=True),
        want.sort_values(cols, ignore_index=True),
        check_dtype=False, rtol=1e-6, atol=1e-9)
    assert cm.stats["fallbacks"] == fb, "compaction must not cause fallback"
    assert cm.stats["recompiles"] > rec, "shrink recompile expected"

    def dispatch_of(sql):
        """The ``dispatch`` span of ``sql``, served by a program that is
        there already (a first arrival runs its program inside ``compile``)."""
        ctx.sql(sql, return_futures=False)
        span, = [s for s in ctx.last_report.root.walk()
                 if s.name == "dispatch"]
        return span

    # the report says whether a query compacted and at what capacity: here
    # the tight cap the shrink learned, far under the default n/4
    attrs = dispatch_of(q.replace("sel < 3", "sel < 2")).attrs
    assert attrs["compact_sites"] >= 1
    assert 1024 <= attrs["compact_cap"] <= n // 8
    # n is under the rows from which a site sorts inside slabs
    assert attrs["compact_slab_sites"] == 0
    from dask_sql_tpu.ops import kernels
    monkeypatch.setattr(kernels, "COMPACT_SLAB_ROWS_MIN", n)
    assert cm._compact_attrs(
        {"agg_sites": [(n, False, "cmp0"), (n, False, "cmpj1"),
                       (n, False, "agg0")],
         "ngroup_caps": [n // 8, n // 4, 64]}) == {
        "compact_sites": 2, "compact_slab_sites": 1,
        "compact_cap": n // 4, "join_rows": 0, "span_tables": 0,
        "semi_joins": 0, "scalar_subqueries": 0, "shared_subplans": 0,
        "run_groupbys": 0}
    # a filter under a global aggregate never compacts
    ctx.sql("SELECT SUM(v) AS s FROM fact WHERE sel < 3", return_futures=False)
    attrs = dispatch_of("SELECT SUM(v) AS s FROM fact WHERE sel < 2").attrs
    assert attrs["compact_sites"] == 0 and attrs["compact_cap"] == 0
