"""Unit tests for runtime/result_cache.py: byte-accounted LRU + eviction
ladder, device->host spill round trips, catalog epochs, the volatility gate
on plan keys, and the telemetry name-stability contract additions."""
import numpy as np
import pandas as pd
import pytest

from dask_sql_tpu import Context
from dask_sql_tpu.runtime import result_cache as rc
from dask_sql_tpu.runtime import telemetry as tel
from dask_sql_tpu.sql.parser import parse_sql
from dask_sql_tpu.table import Table


@pytest.fixture()
def cache(monkeypatch):
    """A fresh, generously-budgeted cache for each test."""
    monkeypatch.setenv("DSQL_RESULT_CACHE_MB", "64")
    monkeypatch.setenv("DSQL_RESULT_CACHE_HOST_MB", "64")
    c = rc.ResultCache()
    yield c
    c.clear()


def _table(n_rows: int, fill: int = 0, with_mask: bool = False,
           with_strings: bool = False) -> Table:
    data = {"a": np.full(n_rows, fill, dtype=np.int64)}
    if with_strings:
        data["s"] = np.array(["ab", "cd"] * (n_rows // 2), dtype=object)
    t = Table.from_pydict(data)
    if with_mask:
        import jax.numpy as jnp
        col = t.columns[0]
        t.columns[0] = col.with_mask(jnp.arange(n_rows) % 2 == 0)
    return t


def _key(name: str, tables=()) -> rc.CacheKey:
    return rc.CacheKey(name, tuple(tables))


# ---------------------------------------------------------------------------
# byte accounting + LRU + the eviction ladder
# ---------------------------------------------------------------------------

def test_byte_accounting_accuracy(cache):
    t1 = _table(1024)                      # 8 KiB of int64
    t2 = _table(2048, with_mask=True)      # 16 KiB data + 2 KiB mask
    assert cache.put(_key("k1"), t1)
    assert cache.put(_key("k2"), t2)
    expected = rc._table_nbytes(t1) + rc._table_nbytes(t2)
    assert cache.device_bytes == expected
    assert cache.host_bytes == 0
    # gauge mirrors the accounting
    assert tel.REGISTRY.get_gauge("result_cache_bytes") == expected
    # replacing a key re-accounts instead of double-counting
    assert cache.put(_key("k1"), _table(512))
    assert cache.device_bytes == rc._table_nbytes(_table(512)) + \
        rc._table_nbytes(t2)


def test_lru_order_under_budget_pressure(cache, monkeypatch):
    # budget fits two 8 KiB entries; host tier off => evictions DROP
    monkeypatch.setenv("DSQL_RESULT_CACHE_MB", str(20 / 1024))
    monkeypatch.setenv("DSQL_RESULT_CACHE_HOST_MB", "0")
    cache.put(_key("a"), _table(1024))
    cache.put(_key("b"), _table(1024))
    assert cache.get(_key("a")) is not None   # touch: a becomes MRU
    cache.put(_key("c"), _table(1024))        # over budget: LRU (b) drops
    assert cache.probe(_key("b")) is None
    assert cache.probe(_key("a")) == "device"
    assert cache.probe(_key("c")) == "device"
    assert cache.device_bytes <= cache.device_budget()


def test_spill_ladder_and_round_trip_equality(cache, monkeypatch):
    monkeypatch.setenv("DSQL_RESULT_CACHE_MB", str(20 / 1024))
    monkeypatch.setenv("DSQL_RESULT_CACHE_HOST_MB", "1")
    spills0 = tel.REGISTRY.get("result_cache_spills")
    orig = _table(1024, fill=7, with_mask=True, with_strings=True)
    expected = orig.to_pandas()
    cache.put(_key("a"), orig)
    cache.put(_key("b"), _table(1024))
    cache.put(_key("c"), _table(1024))
    # the ladder spilled (not dropped) the LRU device entries to host
    assert cache.probe(_key("a")) == "host"
    assert tel.REGISTRY.get("result_cache_spills") > spills0
    assert cache.host_bytes > 0
    # host hit: re-uploaded, bit-identical, and promoted back to device
    got, tier = cache.get(_key("a"))
    assert tier == "host"
    pd.testing.assert_frame_equal(got.to_pandas(), expected)
    assert cache.probe(_key("a")) == "device"


def test_host_budget_overflow_drops(cache, monkeypatch):
    monkeypatch.setenv("DSQL_RESULT_CACHE_MB", str(10 / 1024))
    monkeypatch.setenv("DSQL_RESULT_CACHE_HOST_MB", str(10 / 1024))
    ev0 = tel.REGISTRY.get("result_cache_evictions")
    cache.put(_key("a"), _table(1024))
    cache.put(_key("b"), _table(1024))   # a spills to host
    cache.put(_key("c"), _table(1024))   # b spills; host over budget: a drops
    assert cache.probe(_key("a")) is None
    assert tel.REGISTRY.get("result_cache_evictions") > ev0
    assert cache.host_bytes <= cache.host_budget()


def test_oversized_entry_is_not_stored(cache, monkeypatch):
    monkeypatch.setenv("DSQL_RESULT_CACHE_MB", str(4 / 1024))
    assert not cache.put(_key("big"), _table(1024))
    assert cache.stats()["entries"] == 0


def test_zero_budget_disables_cleanly(cache, monkeypatch):
    cache.put(_key("a"), _table(128))
    assert cache.stats()["entries"] == 1
    monkeypatch.setenv("DSQL_RESULT_CACHE_MB", "0")
    assert not cache.enabled()
    # disabling released what was held, and get/put are no-ops
    assert cache.stats()["entries"] == 0
    assert cache.get(_key("a")) is None
    assert not cache.put(_key("a"), _table(128))


def test_cached_table_is_isolated_from_caller_mutation(cache):
    t = _table(64)
    cache.put(_key("a"), t)
    t.names[0] = "mutated"                   # caller vandalizes its copy
    got, _ = cache.get(_key("a"))
    assert got.names == ["a"]
    got.names[0] = "other"                   # hit copies are private too
    again, _ = cache.get(_key("a"))
    assert again.names == ["a"]


def test_invalidate_table_drops_referencing_entries(cache):
    inv0 = tel.REGISTRY.get("result_cache_invalidations")
    cache.put(_key("a", tables=[("root", "t1")]), _table(64))
    cache.put(_key("b", tables=[("root", "t1"), ("root", "t2")]), _table(64))
    cache.put(_key("c", tables=[("root", "t2")]), _table(64))
    assert cache.invalidate_table("root", "t1") == 2
    assert cache.probe(_key("a")) is None
    assert cache.probe(_key("b")) is None
    assert cache.probe(_key("c")) == "device"
    assert tel.REGISTRY.get("result_cache_invalidations") == inv0 + 2


# ---------------------------------------------------------------------------
# plan keys: canonicalization, epochs, volatility
# ---------------------------------------------------------------------------

def _plan(ctx, sql):
    return ctx._get_plan(parse_sql(sql)[0].query, sql)


@pytest.fixture()
def ctx():
    c = Context()
    c.create_table("t", pd.DataFrame({"a": [1, 2, 3], "b": [1.0, 2.0, 3.0]}))
    return c


def test_plan_key_stable_and_distinct(ctx):
    k1 = rc.plan_key(_plan(ctx, "SELECT a FROM t"), ctx)
    k2 = rc.plan_key(_plan(ctx, "SELECT a FROM t"), ctx)
    k3 = rc.plan_key(_plan(ctx, "SELECT b FROM t"), ctx)
    assert k1.digest == k2.digest
    assert k1.digest != k3.digest
    assert k1.tables == (("root", "t"),)


def test_plan_key_distinguishes_values_rows(ctx):
    # RelNode.explain() elides VALUES contents; the canonical serializer
    # must not (this also guards the stage-boundary digest)
    k1 = rc.plan_key(_plan(ctx, "SELECT * FROM (VALUES (1), (2)) AS v(x)"),
                     ctx)
    k2 = rc.plan_key(_plan(ctx, "SELECT * FROM (VALUES (3), (4)) AS v(x)"),
                     ctx)
    assert k1.digest != k2.digest


def test_plan_key_folds_epoch_and_uid(ctx):
    k1 = rc.plan_key(_plan(ctx, "SELECT SUM(a) AS s FROM t"), ctx)
    ctx.create_table("t", pd.DataFrame({"a": [9], "b": [9.0]}))
    k2 = rc.plan_key(_plan(ctx, "SELECT SUM(a) AS s FROM t"), ctx)
    assert k1.digest != k2.digest


def test_plan_key_volatile_ops_refuse(ctx):
    assert rc.plan_key(_plan(ctx, "SELECT RAND() AS r FROM t"), ctx) is None
    assert rc.plan_key(
        _plan(ctx, "SELECT CURRENT_TIMESTAMP AS ts FROM t"), ctx) is None


def test_plan_key_udf_refuses(ctx):
    ctx.register_function(lambda x: x + 1, "f", [("x", np.int64)], np.int64)
    assert rc.plan_key(_plan(ctx, "SELECT f(a) AS y FROM t"), ctx) is None


def test_epoch_bumps_on_every_mutation_path(ctx):
    e0 = ctx.table_epoch("root", "t")
    ctx.create_table("t", pd.DataFrame({"a": [1], "b": [1.0]}))
    e1 = ctx.table_epoch("root", "t")
    assert e1 > e0
    ctx.sql("CREATE TABLE u AS SELECT a FROM t")
    assert ctx.table_epoch("root", "u") > 0
    ctx.alter_table("u", "u2")
    assert ctx.table_epoch("root", "u2") > ctx.table_epoch("root", "u") > e1
    ctx.drop_table("u2")
    e_drop = ctx.table_epoch("root", "u2")
    assert e_drop > e1
    ctx.create_schema("s2")
    ctx.create_table("x", pd.DataFrame({"a": [1]}), schema_name="s2")
    ex = ctx.table_epoch("s2", "x")
    ctx.alter_schema("s2", "s3")
    assert ctx.table_epoch("s3", "x") > ex
    ctx.drop_schema("s3")
    assert ctx.table_epoch("s3", "x") > ex


def test_stage_table_name_uses_canonical_shape(ctx):
    """Two subplans differing only in VALUES contents must get distinct
    stage-boundary digests (the subplan cache replays by that name)."""
    from dask_sql_tpu.physical import stage_exec

    p1 = _plan(ctx, "SELECT * FROM (VALUES (1), (2)) AS v(x)")
    p2 = _plan(ctx, "SELECT * FROM (VALUES (3), (4)) AS v(x)")
    assert stage_exec._stage_table_name(p1, ctx) != \
        stage_exec._stage_table_name(p2, ctx)


# ---------------------------------------------------------------------------
# telemetry contract
# ---------------------------------------------------------------------------

def test_result_cache_metric_names_are_registered():
    """Append-only name-stability contract: the result-cache counters and
    gauges are part of the public metrics surface from this PR on."""
    for name in ("result_cache_hits", "result_cache_misses",
                 "result_cache_stores", "result_cache_evictions",
                 "result_cache_spills", "result_cache_invalidations",
                 "result_cache_subplan_hits", "fault_cache_populate"):
        assert name in tel.STABLE_COUNTERS
        assert tel.REGISTRY.get(name) is not None
    for name in ("result_cache_bytes", "result_cache_host_bytes"):
        assert name in tel.STABLE_GAUGES
    text = tel.REGISTRY.render_prometheus()
    assert "# TYPE dsql_result_cache_bytes gauge" in text
    assert "dsql_result_cache_hits_total" in text
