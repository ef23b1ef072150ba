"""Unit tests for runtime/statistics.py: ingest collection, NDV
estimation, dense-domain detection, the hash/sort crossover table,
selectivity/cardinality rules, and the scheduler's stats estimate slot.

The module name contains "statistic", so conftest's _adaptive_off pin
leaves DSQL_ADAPTIVE at its production default (on) here.
"""
import numpy as np
import pandas as pd
import pytest

from dask_sql_tpu import Context
from dask_sql_tpu.runtime import statistics as stats
from dask_sql_tpu.runtime import telemetry as _tel


def _ctx(**frames):
    c = Context()
    for name, frame in frames.items():
        c.create_table(name, frame)
    return c


# ---------------------------------------------------------------------------
# collection
# ---------------------------------------------------------------------------

def test_collect_basic_int_column():
    c = _ctx(t=pd.DataFrame({"k": [3, 1, 2, 3, 1], "v": [1.0, 2, 3, 4, 5]}))
    ts = c.schema["root"].tables["t"].stats
    assert ts is not None and ts.rows == 5
    k = ts.col("k")
    assert k.ndv == 3 and k.min == 1 and k.max == 3
    assert k.is_int and k.dense and k.domain == 3
    assert k.null_frac == 0.0


def test_collect_null_fraction():
    c = _ctx(t=pd.DataFrame({"k": pd.array([1, None, 3, None], "Int64")}))
    k = c.schema["root"].tables["t"].stats.col("k")
    assert k.null_frac == pytest.approx(0.5)
    # min/max are over VALID rows only
    assert k.min == 1 and k.max == 3


def test_collect_string_ndv_from_dictionary():
    c = _ctx(t=pd.DataFrame({"s": ["a", "b", "a", "c", "b"]}))
    s = c.schema["root"].tables["t"].stats.col("s")
    assert s.ndv == 3 and not s.is_int and not s.dense


def test_collect_wide_domain_not_dense():
    c = _ctx(t=pd.DataFrame({"k": np.arange(0, 10**7, 1000)}))
    k = c.schema["root"].tables["t"].stats.col("k")
    assert k.is_int and not k.dense
    assert k.domain > stats.dense_domain_cap()


@pytest.mark.parametrize("name,values,increasing", [
    ("arange", np.arange(5000), True),
    ("arange_times_4", np.arange(5000) * 4, True),
    ("from_a_negative", np.arange(-70, 70, 7), True),
    ("int32", np.arange(100, dtype=np.int32), True),
    ("one_row", np.array([9]), True),
    ("a_repeat", np.array([1, 2, 2, 3]), False),
    ("a_repeat_beyond_the_head", np.r_[np.arange(9000), 8999], False),
    ("a_descent", np.array([1, 3, 2, 4]), False),
    ("decreasing", np.arange(50)[::-1].copy(), False),
    ("nullable", pd.array([1, 2, None, 4], "Int64"), False),
    # loads without a mask
    ("a_nullable_type_without_a_null", pd.array([1, 2, 3, 4], "Int64"), True),
    ("float", np.arange(100) * 1.0, False),
    ("bool", np.array([False, True]), False),
    ("string", np.array(["a", "b", "c"]), False)])
def test_increasing_is_a_strictly_rising_integer_column_without_a_mask(
        name, values, increasing):
    c = _ctx(t=pd.DataFrame({"k": values}))
    k = c.schema["root"].tables["t"].stats.col("k")
    assert k.increasing is increasing
    assert k.to_row()["increasing"] is increasing
    if increasing:
        # every value once: the count is the NDV, exactly
        assert k.ndv == len(values)
    system = c.sql("SELECT \"column\", increasing FROM system.table_stats "
                   "WHERE \"table\" = 't'", return_futures=False)
    assert list(system["increasing"]) == [increasing]


def test_increasing_is_collected_again_when_a_table_is_replaced():
    c = _ctx(t=pd.DataFrame({"k": np.arange(100) * 3}))
    assert c.schema["root"].tables["t"].stats.col("k").increasing
    c.create_table("t", pd.DataFrame({"k": np.arange(100)[::-1] * 3}))
    assert not c.schema["root"].tables["t"].stats.col("k").increasing
    c.create_table("t", pd.DataFrame({"k": np.arange(100) + 5}))
    k = c.schema["root"].tables["t"].stats.col("k")
    assert k.increasing and k.domain == k.ndv == 100


@pytest.mark.parametrize("keys,level", [
    (np.arange(100) + 5, "ORDERED_DENSE"),
    (np.arange(100) * 4, "ORDERED_NARROW"),
    (np.r_[np.arange(99), 2 ** 31 - 1], "ORDERED_NARROW"),
    (np.r_[np.arange(99), 2 ** 31], "ORDERED_WIDE"),
    (np.array([np.iinfo(np.int64).min, 0, np.iinfo(np.int64).max]),
     "ORDERED_WIDE"),
    (np.arange(100)[::-1].copy(), None),
    (np.arange(100) // 2, None)])
def test_ordered_probe_hints_grade_a_join_s_build_key(keys, level):
    rng = np.random.default_rng(0)
    c = _ctx(p=pd.DataFrame({"k": rng.integers(0, 100, 300)}),
             b=pd.DataFrame({"k": keys, "v": np.arange(len(keys)) * 1.0}))
    plan = _plan(c, "SELECT p.k, b.v FROM p JOIN b ON p.k = b.k")
    want = {} if level is None else {"ord0r": getattr(stats, level)}
    assert stats.ordered_probe_hints(plan, c) == want
    # the side is the plan's, not the size's
    plan = _plan(c, "SELECT p.k, b.v FROM b JOIN p ON p.k = b.k")
    want = {} if level is None else {"ord0l": getattr(stats, level)}
    assert stats.ordered_probe_hints(plan, c) == want


def test_ordered_probe_hints_silent_when_off(monkeypatch):
    c = _ctx(p=pd.DataFrame({"k": [1, 2, 2]}),
             b=pd.DataFrame({"k": [1, 2, 3]}))
    plan = _plan(c, "SELECT p.k FROM p JOIN b ON p.k = b.k")
    assert stats.ordered_probe_hints(plan, c) == {
        "ord0r": stats.ORDERED_DENSE}
    monkeypatch.setenv("DSQL_ADAPTIVE", "0")
    assert stats.ordered_probe_hints(plan, c) == {}


def test_dense_domain_cap_env(monkeypatch):
    monkeypatch.setenv("DSQL_DENSE_DOMAIN_CAP", "8")
    assert stats.dense_domain_cap() == 8
    c = _ctx(t=pd.DataFrame({"k": [0, 100]}))
    assert not c.schema["root"].tables["t"].stats.col("k").dense


def test_sampled_ndv_exact_when_small():
    assert stats._sampled_ndv(np.array([1, 2, 2, 3])) == 3


def test_sampled_ndv_extrapolates_keylike():
    # a key-like column (all distinct) extrapolates to ~n
    n = 200_000
    est = stats._sampled_ndv(np.arange(n, dtype=np.int64))
    assert est >= 0.9 * n


def test_sampled_ndv_lower_bound_when_fat():
    # few distinct values: reported count stays near the true NDV, never
    # extrapolated past it
    n = 200_000
    est = stats._sampled_ndv(np.arange(n, dtype=np.int64) % 7)
    assert est <= 7


def test_collection_counter_and_never_raises():
    before = _tel.REGISTRY.counters().get("stats_tables_collected", 0)
    _ctx(t=pd.DataFrame({"a": [1]}))
    after = _tel.REGISTRY.counters().get("stats_tables_collected", 0)
    assert after == before + 1
    assert stats.collect_table_stats(object()) is None  # junk, no raise


# ---------------------------------------------------------------------------
# crossover table
# ---------------------------------------------------------------------------

def test_crossover_dense_small_domain():
    assert stats.choose_groupby_variant(10**6, 100, dense_ok=True) == "dense"


def test_crossover_sorted_fat_groups():
    assert stats.choose_groupby_variant(10**6, 1000,
                                        dense_ok=False) == "sorted"


def test_crossover_hash_high_ndv():
    assert stats.choose_groupby_variant(10**6, 500_000,
                                        dense_ok=False) == "hash"


def test_crossover_hash_when_groups_thin():
    # ndv below SORT_NDV_CAP but groups too thin (rows/ndv < fraction)
    assert stats.choose_groupby_variant(1000, 900, dense_ok=False) == "hash"


def test_crossover_unknown_stats_status_quo():
    assert stats.choose_groupby_variant(None, None, dense_ok=False) == "hash"


def test_crossover_forced_override(monkeypatch):
    monkeypatch.setenv("DSQL_FORCE_GROUPBY", "sorted")
    assert stats.forced_groupby() == "sorted"
    monkeypatch.setenv("DSQL_FORCE_GROUPBY", "bogus")
    assert stats.forced_groupby() is None


def test_adaptive_kill_switch(monkeypatch):
    monkeypatch.setenv("DSQL_ADAPTIVE", "0")
    assert not stats.adaptive_enabled()
    monkeypatch.setenv("DSQL_ADAPTIVE", "1")
    assert stats.adaptive_enabled()


# ---------------------------------------------------------------------------
# selectivity + cardinality
# ---------------------------------------------------------------------------

def _plan(c, sql):
    from dask_sql_tpu.sql.parser import parse_sql
    stmt = parse_sql(sql)[0]
    return c._get_plan(getattr(stmt, "query", stmt), sql)


def test_estimate_rows_scan_and_filter():
    n = 1000
    c = _ctx(t=pd.DataFrame({"k": np.arange(n), "v": np.random.rand(n)}))
    scan = _plan(c, "SELECT * FROM t")
    assert stats.estimate_rows(scan, c) == pytest.approx(n, rel=0.01)
    # range predicate over a uniform domain: min/max interpolation
    filt = _plan(c, "SELECT * FROM t WHERE k < 100")
    est = stats.estimate_rows(filt, c)
    assert est is not None and 20 <= est <= 400


def test_estimate_rows_equality_uses_ndv():
    c = _ctx(t=pd.DataFrame({"k": np.arange(1000) % 10}))
    filt = _plan(c, "SELECT * FROM t WHERE k = 3")
    est = stats.estimate_rows(filt, c)
    assert est == pytest.approx(100, rel=0.5)


def test_estimate_rows_aggregate_ndv_product():
    c = _ctx(t=pd.DataFrame({"k": np.arange(5000) % 25,
                             "v": np.random.rand(5000)}))
    agg = _plan(c, "SELECT k, SUM(v) FROM t GROUP BY k")
    est = stats.estimate_rows(agg, c)
    assert est == pytest.approx(25, rel=0.3)


def test_estimate_join_rows_equi_selectivity():
    nl, d = 10_000, 100
    c = _ctx(l=pd.DataFrame({"k": np.arange(nl) % d}),
             r=pd.DataFrame({"k": np.arange(d)}))
    j = _plan(c, "SELECT * FROM l, r WHERE l.k = r.k")
    est = stats.estimate_rows(j, c)
    # |l| * |r| / max-ndv = 10000 * 100 / 100 = 10000
    assert est == pytest.approx(nl, rel=0.5)


def test_estimate_plan_bytes_stats_and_scheduler_source(monkeypatch):
    from dask_sql_tpu.runtime import scheduler as sched
    c = _ctx(t=pd.DataFrame({"k": np.arange(1000) % 10,
                             "v": np.random.rand(1000)}))
    plan = _plan(c, "SELECT k, SUM(v) FROM t GROUP BY k")
    est = stats.estimate_plan_bytes_stats(plan, c)
    assert est is not None and est > 0
    nbytes, source = sched.estimate_working_set(plan, c)
    assert source == "stats" and nbytes >= est
    # kill switch restores the heuristic source
    monkeypatch.setenv("DSQL_ADAPTIVE", "0")
    _, source = sched.estimate_working_set(plan, c)
    assert source == "heuristic"


# ---------------------------------------------------------------------------
# cap hints + stats join reorder
# ---------------------------------------------------------------------------

def test_compiled_cap_hints_single_aggregate():
    c = _ctx(t=pd.DataFrame({"k": np.arange(4000) % 40,
                             "v": np.random.rand(4000)}))
    plan = _plan(c, "SELECT k, SUM(v) FROM t GROUP BY k")
    hints = stats.compiled_cap_hints(plan, c)
    assert set(hints) == {"agg0"}
    cap = hints["agg0"]
    assert cap >= 40 and cap & (cap - 1) == 0  # power of two, fits groups


def test_compiled_cap_hints_silent_when_off(monkeypatch):
    c = _ctx(t=pd.DataFrame({"k": [1, 2], "v": [1.0, 2.0]}))
    plan = _plan(c, "SELECT k, SUM(v) FROM t GROUP BY k")
    monkeypatch.setenv("DSQL_ADAPTIVE", "0")
    assert stats.compiled_cap_hints(plan, c) == {}


def test_reorder_joins_stats_smaller_build_first():
    np.random.seed(0)
    big = pd.DataFrame({"k": np.random.randint(0, 50, 20_000)})
    dim = pd.DataFrame({"k": np.arange(50), "d": np.arange(50) % 5})
    tiny = pd.DataFrame({"d": np.arange(5)})
    c = _ctx(big=big, dim=dim, tiny=tiny)
    text = c.sql(
        "EXPLAIN SELECT COUNT(*) FROM big, dim, tiny "
        "WHERE big.k = dim.k AND dim.d = tiny.d"
    ).to_pandas()["PLAN"].str.cat(sep="\n")
    # the 20k-row fact table must not be the build start of the chain:
    # stats ordering joins dim x tiny first, then attaches big
    assert text.index("big") > text.index("dim")


def test_reorder_joins_stats_disabled_keeps_plan(monkeypatch):
    monkeypatch.setenv("DSQL_ADAPTIVE", "0")
    from dask_sql_tpu.plan.optimizer import reorder_joins_stats
    c = _ctx(t=pd.DataFrame({"k": [1]}))
    plan = _plan(c, "SELECT * FROM t")
    assert reorder_joins_stats(plan, c) is plan


# ---------------------------------------------------------------------------
# explain surface + system rows
# ---------------------------------------------------------------------------

def test_explain_lines_groupby():
    c = _ctx(t=pd.DataFrame({"k": np.arange(2000) % 20,
                             "v": np.random.rand(2000)}))
    plan = _plan(c, "SELECT k, SUM(v) FROM t GROUP BY k")
    lines = stats.explain_lines(plan, c)
    assert any(ln.startswith("-- operator: groupby=") for ln in lines)
    assert any("ndv=20" in ln and "rows=2000" in ln for ln in lines)


def test_system_rows_shape():
    c = _ctx(t=pd.DataFrame({"k": [1, 2, 2], "s": ["x", "y", "x"]}))
    rows = stats.system_rows(c)
    by_col = {(r["table"], r["column"]): r for r in rows}
    assert by_col[("t", "k")]["ndv"] == 2
    assert by_col[("t", "s")]["ndv"] == 2
    assert by_col[("t", "k")]["rows"] == 3


def test_format_choice_stable():
    line = stats.format_choice("groupby", "dense", {"rows": 7, "ndv": 3})
    assert line == "groupby=dense ndv=3 rows=7"
