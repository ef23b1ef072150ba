"""Integration: parameterized plan identity + PREPARE/EXECUTE (ISSUE 16).

Covers the acceptance surface:
- repeated arrivals of one query shape with different literals compile
  ONCE and then hit the in-memory program cache;
- the result cache stays literal-isolated: distinct literal sets never
  share a cached answer, while repeats of the same literals still hit;
- PREPARE / EXECUTE / DEALLOCATE end to end, the per-context registry
  surfaced as system.prepared, and the ``params=`` client API;
- a FRESH interpreter (and its in-process simulation) serves a
  never-seen literal of a previously-seen shape from the persistent
  program store with zero XLA compiles;
- DSQL_PARAM_PLANS=0 restores value-baked program identity.
"""
import json
import os
import subprocess
import sys

import pandas as pd
import pytest

import jax

from dask_sql_tpu import Context
from dask_sql_tpu.physical import caps, programs, tiering
from dask_sql_tpu.runtime import program_store as ps
from dask_sql_tpu.runtime import result_cache as rc
from dask_sql_tpu.runtime import telemetry as tel


def _deltas(c0):
    now = tel.REGISTRY.counters()
    return {k: v - c0.get(k, 0) for k, v in now.items() if v != c0.get(k, 0)}


def _forget_programs():
    programs._cache.clear()
    caps._learned_caps.clear()
    programs._runtime_eager.clear()
    with tiering._tier_lock:
        tiering._tier_done.clear()
        tiering._tier_inflight.clear()
    jax.clear_caches()


@pytest.fixture(autouse=True)
def _quiet(monkeypatch):
    monkeypatch.setenv("DSQL_TIERED", "0")
    monkeypatch.setenv("DSQL_MAX_CONCURRENT_QUERIES", "0")
    monkeypatch.delenv("DSQL_FAULT_INJECT", raising=False)


@pytest.fixture()
def ctx():
    c = Context()
    c.create_table("t", pd.DataFrame({
        "a": range(200), "b": [float(i) * 0.5 for i in range(200)]}))
    return c


def _oracle(df, lit):
    return df[(df.a > lit)][["a", "b"]].reset_index(drop=True)


# ---------------------------------------------------------------------------
# one compile per shape
# ---------------------------------------------------------------------------

def test_one_compile_many_literals(ctx):
    df = ctx.sql("SELECT a, b FROM t", return_futures=False)
    c0 = tel.REGISTRY.counters()
    for lit in (3, 17, 42, 99, 150):
        got = ctx.sql(f"SELECT a, b FROM t WHERE a > {lit}",
                      return_futures=False)
        pd.testing.assert_frame_equal(got.reset_index(drop=True),
                                      _oracle(df, lit), check_dtype=False)
    d = _deltas(c0)
    assert d.get("compiles", 0) == 1, d
    assert d.get("param_plan_hits", 0) >= 4, d
    assert d.get("param_plans", 0) >= 5, d


def test_kill_switch_restores_value_baked_identity(ctx, monkeypatch):
    monkeypatch.setenv("DSQL_PARAM_PLANS", "0")
    c0 = tel.REGISTRY.counters()
    for lit in (3, 17, 42):
        ctx.sql(f"SELECT a, b FROM t WHERE a > {lit}")
    d = _deltas(c0)
    assert d.get("compiles", 0) == 3, d
    assert d.get("param_plans", 0) == 0, d
    assert d.get("param_plan_hits", 0) == 0, d


# ---------------------------------------------------------------------------
# result-cache isolation
# ---------------------------------------------------------------------------

def test_result_cache_never_shares_across_literals(ctx, monkeypatch):
    monkeypatch.setenv("DSQL_RESULT_CACHE_MB", "64")
    monkeypatch.setenv("DSQL_RESULT_CACHE_HOST_MB", "64")
    rc.get_cache().clear()
    try:
        r10 = ctx.sql("SELECT a, b FROM t WHERE a > 10",
                      return_futures=False)
        r50 = ctx.sql("SELECT a, b FROM t WHERE a > 50",
                      return_futures=False)
        assert len(r10) != len(r50)  # distinct literals, distinct answers
        c0 = tel.REGISTRY.counters()
        r10b = ctx.sql("SELECT a, b FROM t WHERE a > 10",
                       return_futures=False)
        d = _deltas(c0)
        assert d.get("result_cache_hits", 0) == 1, d  # same literal hits
        pd.testing.assert_frame_equal(r10, r10b)
        c1 = tel.REGISTRY.counters()
        r99 = ctx.sql("SELECT a, b FROM t WHERE a > 99",
                      return_futures=False)
        d2 = _deltas(c1)
        assert d2.get("result_cache_hits", 0) == 0, d2  # new literal misses
        pd.testing.assert_frame_equal(
            r99, _oracle(ctx.sql("SELECT a, b FROM t",
                                 return_futures=False), 99),
            check_dtype=False)
    finally:
        rc.get_cache().clear()


# ---------------------------------------------------------------------------
# PREPARE / EXECUTE / params=
# ---------------------------------------------------------------------------

def test_prepare_execute_roundtrip(ctx):
    df = ctx.sql("SELECT a, b FROM t", return_futures=False)
    ctx.sql("PREPARE above AS SELECT a, b FROM t WHERE a > ?")
    c0 = tel.REGISTRY.counters()
    for lit in (5, 25, 125):
        got = ctx.sql(f"EXECUTE above ({lit})", return_futures=False)
        pd.testing.assert_frame_equal(got.reset_index(drop=True),
                                      _oracle(df, lit), check_dtype=False)
    d = _deltas(c0)
    assert d.get("prepared_executes", 0) == 3, d
    assert d.get("compiles", 0) <= 1, d

    sysp = ctx.sql("SELECT * FROM system.prepared", return_futures=False)
    assert list(sysp["name"]) == ["above"]
    assert int(sysp["num_params"][0]) == 1

    ctx.sql("DEALLOCATE above")
    with pytest.raises(RuntimeError, match="does not exist"):
        ctx.sql("EXECUTE above (1)")
    sysp = ctx.sql("SELECT * FROM system.prepared", return_futures=False)
    assert len(sysp) == 0


def test_execute_arity_checked(ctx):
    ctx.sql("PREPARE two AS SELECT a FROM t WHERE a > $1 AND b < $2")
    with pytest.raises(RuntimeError, match="requires 2 parameters"):
        ctx.sql("EXECUTE two (1)")
    got = ctx.sql("EXECUTE two (1, 5.0)", return_futures=False)
    assert len(got) > 0


def test_params_api_shares_program_with_inline_literals(ctx):
    df = ctx.sql("SELECT a, b FROM t", return_futures=False)
    _forget_programs()  # isolate from shapes other tests already compiled
    c0 = tel.REGISTRY.counters()
    inline = ctx.sql("SELECT a, b FROM t WHERE a > 30",
                     return_futures=False)
    marked = ctx.sql("SELECT a, b FROM t WHERE a > ?", params=[60],
                     return_futures=False)
    dollar = ctx.sql("SELECT a, b FROM t WHERE a > $1", params=[90],
                     return_futures=False)
    d = _deltas(c0)
    assert d.get("compiles", 0) == 1, d  # one shape, three spellings
    for lit, got in ((30, inline), (60, marked), (90, dollar)):
        pd.testing.assert_frame_equal(got.reset_index(drop=True),
                                      _oracle(df, lit), check_dtype=False)


def test_unbound_marker_is_a_clear_error(ctx):
    from dask_sql_tpu.utils import ValidationException
    with pytest.raises(ValidationException,
                       match="[Pp]ositional parameter"):
        ctx.sql("SELECT a FROM t WHERE a > ?")


# ---------------------------------------------------------------------------
# cross-process program store: same shape, NEVER-SEEN literal
# ---------------------------------------------------------------------------

def test_store_serves_fresh_process_with_new_literal(ctx, tmp_path,
                                                     monkeypatch):
    monkeypatch.setenv("DSQL_PROGRAM_STORE", str(tmp_path / "programs"))
    _forget_programs()
    try:
        c0 = tel.REGISTRY.counters()
        cold = ctx.sql("SELECT a, b FROM t WHERE a > 10",
                       return_futures=False)
        d1 = _deltas(c0)
        assert d1.get("compiles", 0) == 1
        assert d1.get("program_store_stores", 0) >= 1

        _forget_programs()  # what a fresh process starts from
        c1 = tel.REGISTRY.counters()
        warm = ctx.sql("SELECT a, b FROM t WHERE a > 120",  # new literal
                       return_futures=False)
        d2 = _deltas(c1)
        assert d2.get("compiles", 0) == 0, d2
        assert d2.get("program_store_hits", 0) >= 1, d2
        assert d2.get("param_plan_hits", 0) >= 1, d2
        df = ctx.sql("SELECT a, b FROM t", return_futures=False)
        pd.testing.assert_frame_equal(warm.reset_index(drop=True),
                                      _oracle(df, 120), check_dtype=False)
        assert len(cold) != len(warm)
    finally:
        _forget_programs()


_CHILD = """
import json, os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["DSQL_MAX_CONCURRENT_QUERIES"] = "0"
os.environ["DSQL_TIERED"] = "0"
import pandas as pd
from dask_sql_tpu import Context
from dask_sql_tpu.runtime import telemetry as tel

lit = sys.argv[2]
data = pd.read_feather(sys.argv[1])
c = Context()
c.create_table("t", data)
out = c.sql(f"SELECT a, b FROM t WHERE a > {lit}", return_futures=False)
snap = tel.REGISTRY.counters()
print(json.dumps({
    "rows": len(out),
    "compiles": snap["compiles"],
    "program_store_hits": snap["program_store_hits"],
    "program_store_stores": snap["program_store_stores"],
    "param_plan_hits": snap["param_plan_hits"],
}))
"""


@pytest.mark.slow  # two real interpreter launches; the in-process variant
# above proves the same seam on the tier-1 box, and scripts/param_smoke.py
# gates the cross-process version in CI
def test_fresh_interpreter_new_literal_zero_compiles(tmp_path):
    data_path = str(tmp_path / "t.feather")
    pd.DataFrame({"a": range(200),
                  "b": [float(i) * 0.5 for i in range(200)]}
                 ).to_feather(data_path)
    env = dict(os.environ,
               DSQL_PROGRAM_STORE=str(tmp_path / "programs"),
               JAX_PLATFORMS="cpu")
    env.pop("DSQL_FAULT_INJECT", None)

    outs = []
    for lit in ("10", "120"):  # DIFFERENT literal in the second process
        r = subprocess.run([sys.executable, "-c", _CHILD, data_path, lit],
                           capture_output=True, text=True, env=env,
                           timeout=240)
        assert r.returncode == 0, r.stderr[-2000:]
        outs.append(json.loads(r.stdout.strip().splitlines()[-1]))
    first, second = outs
    assert first["compiles"] >= 1
    assert first["program_store_stores"] >= 1
    assert second["compiles"] == 0, second
    assert second["program_store_hits"] >= 1, second
    assert second["param_plan_hits"] >= 1, second
    assert second["rows"] != first["rows"]
