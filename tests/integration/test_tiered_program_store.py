"""Integration: tiered eager-first execution + the persistent program store.

Covers the acceptance surface of ISSUE 7:
- a cold query answers on the eager tier WITHOUT blocking on stage
  compilation, oracle-correct, while the programs build in the background;
  the next arrival of the same plan runs compiled;
- a fresh process (simulated by clearing every in-memory program cache,
  and proven for real with a subprocess) serves a previously-seen query
  from the persistent store with ZERO XLA stage compiles;
- store safety: corrupt entries and fingerprint mismatches fall back to a
  normal compile (never a crash), and DDL can never surface stale data
  (programs are data-independent — fresh inputs flow through them).
"""
import os
import pickle
import subprocess
import sys
import time

import pandas as pd
import pytest

import jax

from dask_sql_tpu.physical import caps, compiled, programs, tiering
from dask_sql_tpu.runtime import program_store as ps
from dask_sql_tpu.runtime import telemetry as tel


def _deltas(c0):
    now = tel.REGISTRY.counters()
    return {k: v - c0.get(k, 0) for k, v in now.items() if v != c0.get(k, 0)}


def _forget_programs():
    """Drop every in-memory trace of compiled programs — the same state a
    fresh process starts from (the subprocess test proves the real thing)."""
    programs._cache.clear()
    caps._learned_caps.clear()
    programs._runtime_eager.clear()
    with tiering._tier_lock:
        tiering._tier_done.clear()
        tiering._tier_inflight.clear()
    jax.clear_caches()


@pytest.fixture()
def pstore(tmp_path, monkeypatch):
    monkeypatch.setenv("DSQL_PROGRAM_STORE", str(tmp_path / "programs"))
    monkeypatch.setenv("DSQL_TIERED", "0")
    _forget_programs()
    yield ps.get_store()
    _forget_programs()


QUERY = ("SELECT a, SUM(b) AS sb, COUNT(*) AS n FROM df "
         "GROUP BY a ORDER BY a")


def _eager_oracle(c, query):
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
# persistent store
# ---------------------------------------------------------------------------

def test_fresh_load_executes_with_zero_compiles(c, pstore):
    c0 = tel.REGISTRY.counters()
    cold = c.sql(QUERY, return_futures=False)
    d1 = _deltas(c0)
    assert d1.get("compiles", 0) >= 1
    assert d1.get("program_store_stores", 0) >= 1

    _forget_programs()
    c1 = tel.REGISTRY.counters()
    warm = c.sql(QUERY, return_futures=False)
    d2 = _deltas(c1)
    assert d2.get("compiles", 0) == 0, d2
    assert d2.get("program_store_hits", 0) >= 1, d2
    pd.testing.assert_frame_equal(cold, warm)
    pd.testing.assert_frame_equal(warm, _eager_oracle(c, QUERY),
                                  check_dtype=False)


def test_one_device_program_reloads_in_an_8_device_process(c, pstore):
    """The load must name the devices the program was compiled for: left
    to jax 0.9's default (every local device) it died here with "Expected
    args ... to have 8 shards", was counted as a store ERROR and silently
    recompiled — the store never hit on any multi-device host."""
    assert len(jax.devices()) == 8
    c.sql(QUERY, return_futures=False)
    _forget_programs()
    c1 = tel.REGISTRY.counters()
    c.sql(QUERY, return_futures=False)
    d = _deltas(c1)
    assert d.get("program_store_hits", 0) >= 1, d
    assert d.get("program_store_errors", 0) == 0, d
    assert d.get("compiles", 0) == 0, d


def test_store_caps_survive_fresh_process(c, pstore):
    # long_table overflows the default group cap? No — 3 groups.  Force an
    # escalation instead via a tiny learned cap, then prove the RE-stored
    # program (escalated caps) is what a fresh process loads: no
    # recompile, no _NeedsRecompile loop.
    cold = c.sql(QUERY, return_futures=False)
    _forget_programs()
    c1 = tel.REGISTRY.counters()
    warm = c.sql(QUERY, return_futures=False)
    d = _deltas(c1)
    assert d.get("recompiles", 0) == 0 and d.get("compiles", 0) == 0, d
    pd.testing.assert_frame_equal(cold, warm)


def test_corrupt_entry_falls_back_to_compile(c, pstore):
    c.sql(QUERY, return_futures=False)
    store_dir = pstore.path()
    progs = [f for f in os.listdir(store_dir) if f.endswith(".prog")]
    assert progs
    for f in progs:
        with open(os.path.join(store_dir, f), "wb") as fh:
            fh.write(b"\x80corrupt")
    _forget_programs()
    c1 = tel.REGISTRY.counters()
    out = c.sql(QUERY, return_futures=False)
    d = _deltas(c1)
    assert d.get("program_store_errors", 0) >= 1, d
    assert d.get("compiles", 0) >= 1, d  # recompiled, didn't crash
    pd.testing.assert_frame_equal(out, _eager_oracle(c, QUERY),
                                  check_dtype=False)


def test_fingerprint_mismatch_falls_back_to_compile(c, pstore):
    c.sql(QUERY, return_futures=False)
    store_dir = pstore.path()
    for f in os.listdir(store_dir):
        if not f.endswith(".prog"):
            continue
        path = os.path.join(store_dir, f)
        with open(path, "rb") as fh:
            raw = pickle.load(fh)
        raw["fingerprint"] = dict(raw["fingerprint"], jax="0.0.0")
        with open(path, "wb") as fh:
            pickle.dump(raw, fh)
    _forget_programs()
    c1 = tel.REGISTRY.counters()
    out = c.sql(QUERY, return_futures=False)
    d = _deltas(c1)
    assert d.get("program_store_rejects", 0) >= 1, d
    assert d.get("compiles", 0) >= 1, d
    pd.testing.assert_frame_equal(out, _eager_oracle(c, QUERY),
                                  check_dtype=False)


def test_ddl_same_layout_serves_fresh_data(c, pstore, df):
    """A stored program must never pin stale DATA: after DROP + re-create
    with same-layout different contents, the loaded program computes the
    NEW answer (inputs are runtime arguments, not baked constants)."""
    old = c.sql(QUERY, return_futures=False)
    df2 = df.copy()
    df2["b"] = df2["b"] * 3.0
    c.drop_table("df")
    c.create_table("df", df2)
    _forget_programs()
    c1 = tel.REGISTRY.counters()
    new = c.sql(QUERY, return_futures=False)
    d = _deltas(c1)
    assert d.get("compiles", 0) == 0, d  # layout unchanged: store hit
    assert d.get("program_store_hits", 0) >= 1
    assert not new["sb"].equals(old["sb"])  # fresh data, fresh answer
    pd.testing.assert_frame_equal(new, _eager_oracle(c, QUERY),
                                  check_dtype=False)


def test_ddl_layout_change_misses_cleanly(c, pstore, df):
    """A changed plan shape/layout must address a DIFFERENT store entry —
    the old program can never be served for the new shape."""
    c.sql(QUERY, return_futures=False)
    df3 = df.copy()
    df3["a"] = df3["a"].astype("int64")  # dtype change reshapes the layout
    c.drop_table("df")
    c.create_table("df", df3)
    _forget_programs()
    c1 = tel.REGISTRY.counters()
    out = c.sql(QUERY, return_futures=False)
    d = _deltas(c1)
    assert d.get("program_store_hits", 0) == 0, d
    assert d.get("compiles", 0) >= 1
    pd.testing.assert_frame_equal(out, _eager_oracle(c, QUERY),
                                  check_dtype=False)


def test_stage_graph_programs_persist(c, pstore, monkeypatch):
    """A multi-stage plan persists one entry per stage program and a fresh
    process replays ALL of them with zero compiles."""
    monkeypatch.setenv("DSQL_STAGE_HEAVY", "1")
    query = ("SELECT u1.user_id, SUM(u2.c) AS s FROM user_table_1 u1 "
             "JOIN user_table_2 u2 ON u1.user_id = u2.user_id "
             "GROUP BY u1.user_id ORDER BY u1.user_id")
    c0 = tel.REGISTRY.counters()
    cold = c.sql(query, return_futures=False)
    d1 = _deltas(c0)
    assert d1.get("stage_graphs", 0) >= 1
    assert d1.get("program_store_stores", 0) >= 2  # one per stage program

    _forget_programs()
    c1 = tel.REGISTRY.counters()
    warm = c.sql(query, return_futures=False)
    d2 = _deltas(c1)
    assert d2.get("compiles", 0) == 0, d2
    assert d2.get("program_store_hits", 0) >= 2, d2
    pd.testing.assert_frame_equal(cold, warm)


# ---------------------------------------------------------------------------
# tiered execution
# ---------------------------------------------------------------------------

@pytest.fixture()
def tiered(monkeypatch):
    monkeypatch.setenv("DSQL_TIERED", "1")
    monkeypatch.delenv("DSQL_PROGRAM_STORE", raising=False)
    _forget_programs()
    yield
    _forget_programs()


def _wait_background(c0, timeout=30.0):
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:
        done = tel.REGISTRY.get("background_compiles_done") \
            - c0.get("background_compiles_done", 0)
        err = tel.REGISTRY.get("background_compile_errors") \
            - c0.get("background_compile_errors", 0)
        if done + err >= 1:
            return done, err
        time.sleep(0.05)
    return 0, 0


def test_tiered_first_arrival_serves_eager_then_compiled(c, tiered,
                                                         monkeypatch):
    # prime the eager executor's op programs (cleared per module) so the
    # eager-tier answer below is comfortably faster than the slowed build
    _eager_oracle(c, QUERY)
    real_build = compiled._build

    def slow_build(*a, **k):
        time.sleep(4.0)
        return real_build(*a, **k)

    monkeypatch.setattr(compiled, "_build", slow_build)
    c0 = tel.REGISTRY.counters()
    first = c.sql(QUERY, return_futures=False)
    d1 = _deltas(c0)
    # answered on the eager tier, with the compile NOT yet landed: the
    # query did not block on the (slowed) build
    assert d1.get("served_eager_while_compiling", 0) == 1, d1
    assert d1.get("compiles", 0) == 0, d1
    assert c.last_report.tier == "eager-compiling"
    pd.testing.assert_frame_equal(first, _eager_oracle(c, QUERY),
                                  check_dtype=False)

    done, err = _wait_background(c0)
    assert done == 1 and err == 0, (done, err)
    c1 = tel.REGISTRY.counters()
    second = c.sql(QUERY, return_futures=False)
    d2 = _deltas(c1)
    assert d2.get("served_eager_while_compiling", 0) == 0, d2
    assert d2.get("hits", 0) >= 1, d2  # ran the compiled program
    assert c.last_report.tier == "compiled"
    pd.testing.assert_frame_equal(first, second, check_dtype=False)


def test_tiered_concurrent_arrivals_stay_eager_until_ready(c, tiered,
                                                           monkeypatch):
    _eager_oracle(c, QUERY)  # prime eager op programs (see above)
    real_build = compiled._build
    monkeypatch.setattr(
        compiled, "_build",
        lambda *a, **k: (time.sleep(3.0), real_build(*a, **k))[1])
    c0 = tel.REGISTRY.counters()
    r1 = c.sql(QUERY, return_futures=False)
    r2 = c.sql(QUERY, return_futures=False)  # bg compile still in flight
    d = _deltas(c0)
    assert d.get("served_eager_while_compiling", 0) == 2, d
    # one background compile for the plan, not one per arrival
    _wait_background(c0)
    assert tel.REGISTRY.get("background_compiles_done") \
        - c0.get("background_compiles_done", 0) == 1
    pd.testing.assert_frame_equal(r1, r2)


def test_tiered_off_compiles_synchronously(c, monkeypatch):
    monkeypatch.setenv("DSQL_TIERED", "0")
    _forget_programs()
    c0 = tel.REGISTRY.counters()
    c.sql(QUERY, return_futures=False)
    d = _deltas(c0)
    assert d.get("served_eager_while_compiling", 0) == 0
    assert d.get("compiles", 0) >= 1
    assert c.last_report.tier == "compiled"


def test_tiered_respects_eager_fallback_off(c, tiered, monkeypatch):
    # the degradation ladder forbids the eager tier: compiles must be
    # synchronous again (no tier to serve from)
    monkeypatch.setenv("DSQL_EAGER_FALLBACK", "0")
    c0 = tel.REGISTRY.counters()
    c.sql(QUERY, return_futures=False)
    d = _deltas(c0)
    assert d.get("served_eager_while_compiling", 0) == 0, d
    assert d.get("compiles", 0) >= 1


def test_tiered_unsupported_plans_never_spawn_background(c, tiered):
    # RAND() is in the deny-set: permanently eager, no tier churn
    c0 = tel.REGISTRY.counters()
    c.sql("SELECT a, RAND(0) AS r FROM df_simple", return_futures=False)
    d = _deltas(c0)
    assert d.get("served_eager_while_compiling", 0) == 0, d
    assert d.get("background_compiles_done", 0) == 0


def test_tiered_with_store_serves_warm_without_eager_tier(c, tiered,
                                                          tmp_path,
                                                          monkeypatch):
    """Tier decision consults the persistent store: a fresh 'process' with
    a populated store runs compiled immediately — no eager tier, no
    background work, zero compiles."""
    monkeypatch.setenv("DSQL_PROGRAM_STORE", str(tmp_path / "programs"))
    c0 = tel.REGISTRY.counters()
    c.sql(QUERY, return_futures=False)
    _wait_background(c0)
    assert tel.REGISTRY.counters().get("program_store_stores", 0) \
        - c0.get("program_store_stores", 0) >= 1
    _forget_programs()
    c1 = tel.REGISTRY.counters()
    out = c.sql(QUERY, return_futures=False)
    d = _deltas(c1)
    assert d.get("served_eager_while_compiling", 0) == 0, d
    assert d.get("compiles", 0) == 0, d
    assert d.get("program_store_hits", 0) >= 1, d
    assert c.last_report.tier == "compiled"
    pd.testing.assert_frame_equal(out, _eager_oracle(c, QUERY),
                                  check_dtype=False)


# ---------------------------------------------------------------------------
# the real cross-process proof (a true fresh interpreter)
# ---------------------------------------------------------------------------

_CHILD = r"""
import json, os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["DSQL_RESULT_CACHE_MB"] = "0"
os.environ["DSQL_MAX_CONCURRENT_QUERIES"] = "0"
os.environ["DSQL_TIERED"] = "0"
import pandas as pd
from dask_sql_tpu import Context
from dask_sql_tpu.runtime import telemetry as tel

data = pd.read_feather(sys.argv[1])
c = Context()
c.create_table("t", data)
q = ("SELECT k, SUM(v) AS s, COUNT(*) AS n FROM t "
     "GROUP BY k ORDER BY k")
out = c.sql(q, return_futures=False)
snap = tel.REGISTRY.counters()
print(json.dumps({
    "result": out.to_dict("list"),
    "compiles": snap["compiles"],
    "program_store_hits": snap["program_store_hits"],
    "program_store_stores": snap["program_store_stores"],
}))
"""


@pytest.mark.slow  # two real interpreter launches; the tier-1 box runs the
# same proof in-process above, and scripts/warmstart_smoke.py gates the
# cross-process version in CI
def test_fresh_process_serves_warm(tmp_path):
    """Two real interpreters sharing only DSQL_PROGRAM_STORE: the second
    answers with zero XLA compiles and store hits == programs executed."""
    data_path = str(tmp_path / "t.feather")
    pd.DataFrame({"k": [1, 2, 1, 3, 2, 1] * 50,
                  "v": [1.0, 2.0, 3.0, 4.0, 5.0, 6.0] * 50}
                 ).to_feather(data_path)
    env = dict(os.environ,
               DSQL_PROGRAM_STORE=str(tmp_path / "programs"),
               JAX_PLATFORMS="cpu")
    env.pop("DSQL_FAULT_INJECT", None)

    import json
    outs = []
    for _ in range(2):
        r = subprocess.run([sys.executable, "-c", _CHILD, data_path],
                           capture_output=True, text=True, env=env,
                           timeout=240)
        assert r.returncode == 0, r.stderr[-2000:]
        outs.append(json.loads(r.stdout.strip().splitlines()[-1]))
    first, second = outs
    assert first["compiles"] >= 1
    assert first["program_store_stores"] >= 1
    assert second["compiles"] == 0, second
    assert second["program_store_hits"] >= 1, second
    assert second["result"] == first["result"]
