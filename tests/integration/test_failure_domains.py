"""Failure-domain recovery (ISSUE 6 tentpole proofs).

**Stage replay.**  With ``stage_exec:2`` injected on a >=3-stage TPC-H
query, the retry re-executes exactly ONE stage: the ``stage_execs``
counter shows N+1 total stage executions (not 2N), the replay counters
fire, stages below the failed one are never re-run, and the answer still
matches the eager oracle.

**Cross-process quarantine.**  A plan whose compile FATALs in "process A"
is served via the eager fallback immediately — no compile attempt — in a
fresh "process B" sharing the quarantine file (process B modeled by
clearing every in-process compiled cache; the store's file is the only
carrier).  After expiry a half-open probe re-attempts the compile and a
success lifts the verdict.
"""
import os

import pandas as pd
import pytest

from benchmarks.tpch import QUERIES, generate_tpch
from dask_sql_tpu import Context
from dask_sql_tpu.physical import caps, compiled, programs
from dask_sql_tpu.runtime import faults, quarantine as Q
from dask_sql_tpu.runtime import resilience as R
from tests.conftest import assert_eq

_needs_compiled = pytest.mark.skipif(
    os.environ.get("DSQL_COMPILE") == "0",
    reason="stage replay / quarantine live on the compiled path")

AGG_Q = "SELECT user_id, SUM(b) AS sb FROM user_table_1 GROUP BY user_id"


@pytest.fixture(autouse=True)
def _fresh(monkeypatch):
    programs._cache.clear()
    caps._learned_caps.clear()
    programs._runtime_eager.clear()
    faults.reset()
    monkeypatch.setenv("DSQL_RETRY_BASE_MS", "1")
    monkeypatch.delenv("DSQL_QUARANTINE_FILE", raising=False)
    monkeypatch.delenv("DSQL_COMPILE_WATCHDOG_S", raising=False)
    yield
    faults.reset()


def _eager_oracle(c, query) -> pd.DataFrame:
    prev = os.environ.get("DSQL_COMPILE")
    os.environ["DSQL_COMPILE"] = "0"
    try:
        return c.sql(query, return_futures=False)
    finally:
        if prev is None:
            del os.environ["DSQL_COMPILE"]
        else:
            os.environ["DSQL_COMPILE"] = prev


# ---------------------------------------------------------------------------
# checkpointed stage replay
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tpch_ctx():
    data = generate_tpch(0.002)
    ctx = Context()
    for name, df in data.items():
        ctx.create_table(name, df)
    return ctx, data


@_needs_compiled
def test_stage_replay_reexecutes_exactly_one_stage(tpch_ctx, monkeypatch):
    """The acceptance proof: stage k fails transiently once; the retry
    re-runs ONLY stage k from the already-materialized boundary temps."""
    from benchmarks.pandas_tpch import q3 as _pandas_q3

    tpch_ctx, data = tpch_ctx
    monkeypatch.setenv("DSQL_STAGE_HEAVY", "1")
    monkeypatch.setenv("DSQL_COMPILE_WORKERS", "1")   # deterministic order
    q = QUERIES[3]                                    # 3 heavy nodes: >=3 stages
    expected = _pandas_q3(data)                       # pandas oracle

    c0 = dict(compiled.stats)
    with faults.inject("stage_exec:2"):
        got = tpch_ctx.sql(q, return_futures=False)
    assert_eq(got, expected, check_row_order=False)

    graphs = compiled.stats["stage_graphs"] - c0["stage_graphs"]
    assert graphs >= 1, "plan did not stage"
    n_stages = compiled.stats["stage_execs"] - c0["stage_execs"]
    # one injected failure -> exactly ONE extra stage execution: N+1, not 2N
    assert compiled.stats["fault_stage_exec"] - c0["fault_stage_exec"] == 1
    assert compiled.stats["stage_replays"] - c0["stage_replays"] == 1
    n_distinct = n_stages - 1                          # N attempts + 1 replay
    assert n_distinct >= 3, f"want >=3 stages, saw {n_distinct}"
    # the failed stage was the 2nd: exactly one completed stage was saved
    saved = (compiled.stats["stage_replay_saved_stages"]
             - c0["stage_replay_saved_stages"])
    assert saved == 1
    # no degradations: the graph never fell back to eager
    assert compiled.stats["degradations"] == c0["degradations"]


@_needs_compiled
def test_stage_replay_of_root_saves_all_materialized_deps(c, monkeypatch):
    """Failing the LAST stage preserves every dependency's output."""
    monkeypatch.setenv("DSQL_STAGE_HEAVY", "1")
    monkeypatch.setenv("DSQL_COMPILE_WORKERS", "1")
    q = ("SELECT u1.user_id, SUM(u2.c) AS s FROM user_table_1 u1 "
         "JOIN user_table_2 u2 ON u1.user_id = u2.user_id "
         "GROUP BY u1.user_id")
    expected = _eager_oracle(c, q)
    c0 = dict(compiled.stats)
    # the 2-heavy-node plan stages into 2; fail the second (root) attempt
    with faults.inject("stage_exec:2"):
        got = c.sql(q, return_futures=False)
    assert_eq(got, expected, check_row_order=False)
    assert compiled.stats["stage_replays"] - c0["stage_replays"] == 1
    assert (compiled.stats["stage_replay_saved_stages"]
            - c0["stage_replay_saved_stages"]) == 1
    sch = c.schema.get("__split__")
    assert sch is None or not sch.tables, "leaked __split__ temps"


@_needs_compiled
def test_sabotaged_replay_still_degrades_cleanly(c, monkeypatch):
    """A fault on the replay path itself (the new stage_replay site) walks
    the ordinary ladder: the graph degrades to eager, answer correct."""
    monkeypatch.setenv("DSQL_STAGE_HEAVY", "1")
    q = ("SELECT u1.user_id, SUM(u2.c) AS s FROM user_table_1 u1 "
         "JOIN user_table_2 u2 ON u1.user_id = u2.user_id "
         "GROUP BY u1.user_id")
    expected = _eager_oracle(c, q)
    d0 = compiled.stats["degradations"]
    with faults.inject("stage_exec:1+,stage_replay:1+"):
        got = c.sql(q, return_futures=False)
    assert_eq(got, expected, check_row_order=False)
    assert compiled.stats["degradations"] >= d0 + 1
    assert compiled.stats["fault_stage_replay"] >= 1


# ---------------------------------------------------------------------------
# cross-process quarantine
# ---------------------------------------------------------------------------

def _fresh_process():
    """Model a process restart: every in-memory verdict dies; only the
    quarantine FILE (and the catalog data) survives."""
    programs._cache.clear()
    caps._learned_caps.clear()
    programs._runtime_eager.clear()


@_needs_compiled
def test_fatal_compile_quarantines_across_processes(c, tmp_path,
                                                    monkeypatch):
    qfile = str(tmp_path / "quarantine.json")
    monkeypatch.setenv("DSQL_QUARANTINE_FILE", qfile)
    monkeypatch.setenv("DSQL_QUARANTINE_TTL_S", "3600")
    expected = _eager_oracle(c, AGG_Q)

    # process A: the compile FATALs -> eager answer, exiled, verdict persisted
    e0 = compiled.stats["exiled"]
    with faults.inject("compile:1+:fatal"):
        got = c.sql(AGG_Q, return_futures=False)
    assert_eq(got, expected, check_row_order=False)
    assert compiled.stats["exiled"] == e0 + 1
    assert os.path.exists(qfile)
    entries = Q.QuarantineStore(qfile).entries()
    assert entries and all(v["verdict"] == "fatal" for v in entries.values())

    # process B (fresh caches, same file, fault GONE): served eager
    # immediately — zero compile attempts
    _fresh_process()
    n0, s0 = compiled.stats["compiles"], compiled.stats["quarantine_skips"]
    got = c.sql(AGG_Q, return_futures=False)
    assert_eq(got, expected, check_row_order=False)
    assert compiled.stats["compiles"] == n0, "quarantined plan was compiled"
    assert compiled.stats["quarantine_skips"] == s0 + 1

    # after expiry: ONE half-open probe re-attempts the compile; the fixed
    # engine compiles fine and the verdict is lifted.  Expiry is baked
    # into the persisted entry at mark time, so "time passing" is modeled
    # by rewinding the file's expires_at.
    import json as _json
    with open(qfile) as f:
        data = _json.load(f)
    for v in data.values():
        v["expires_at"] = 0.0
    with open(qfile, "w") as f:
        _json.dump(data, f)
    _fresh_process()
    p0 = compiled.stats["quarantine_probes"]
    got = c.sql(AGG_Q, return_futures=False)
    assert_eq(got, expected, check_row_order=False)
    assert compiled.stats["compiles"] == n0 + 1, "probe did not compile"
    assert compiled.stats["quarantine_probes"] == p0 + 1
    assert Q.QuarantineStore(qfile).entries() == {}, "verdict not lifted"

    # and the un-quarantined program serves from cache from now on
    h0 = compiled.stats["hits"]
    got = c.sql(AGG_Q, return_futures=False)
    assert_eq(got, expected, check_row_order=False)
    assert compiled.stats["hits"] == h0 + 1


@_needs_compiled
def test_transient_compile_failure_never_quarantines(c, tmp_path,
                                                     monkeypatch):
    """Transient means exactly that: exhausted transient retries degrade
    but leave NO cross-process verdict behind."""
    qfile = str(tmp_path / "quarantine.json")
    monkeypatch.setenv("DSQL_QUARANTINE_FILE", qfile)
    with faults.inject("compile:1+"):
        c.sql(AGG_Q, return_futures=False)
    assert Q.QuarantineStore(qfile).entries() == {}


@_needs_compiled
def test_watchdog_marks_wedged_compile(c, tmp_path, monkeypatch):
    """A compile stalled past DSQL_COMPILE_WATCHDOG_S gets its fingerprint
    marked suspect by the MONITOR thread (no cooperative checkpoint
    involved), and a 'fresh process' then skips the compile."""
    qfile = str(tmp_path / "quarantine.json")
    monkeypatch.setenv("DSQL_QUARANTINE_FILE", qfile)
    monkeypatch.setenv("DSQL_QUARANTINE_TTL_S", "3600")
    monkeypatch.setenv("DSQL_COMPILE_WATCHDOG_S", "0.2")
    expected = _eager_oracle(c, AGG_Q)
    t0 = compiled.stats["watchdog_trips"]
    # the stall sits between maybe_fail (inside the watched section's
    # retry loop) — sleep 900 ms >> 200 ms budget, then the fault raises
    # transiently and the ladder answers eager (retries exhausted)
    monkeypatch.setenv("DSQL_RETRY_MAX", "0")
    with faults.inject("compile:1+:sleep=900"):
        got = c.sql(AGG_Q, return_futures=False)
    assert_eq(got, expected, check_row_order=False)
    assert compiled.stats["watchdog_trips"] > t0
    entries = Q.QuarantineStore(qfile).entries()
    assert entries and any(v["verdict"] == "hang" for v in entries.values())
    _fresh_process()
    n0 = compiled.stats["compiles"]
    got = c.sql(AGG_Q, return_futures=False)
    assert_eq(got, expected, check_row_order=False)
    assert compiled.stats["compiles"] == n0, "hang-marked plan recompiled"
