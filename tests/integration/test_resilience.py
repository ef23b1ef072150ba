"""End-to-end resilience: the fault-injection matrix over every named site
× {retry-succeeds, degrades-one-rung / typed failure, deadline-exceeded},
plus server-level cancellation and timeout payloads.

The acceptance bar (ISSUE 2): with a fault injected at any site, affected
queries still return ORACLE-CORRECT results via the degradation ladder and
``compiled.stats`` records the retry/degradation; with the eager rung
disabled a typed TransientError surfaces — never a wrong answer, never a
hang past the deadline, never a leaked ``__split__`` temp."""
import os
import time

import numpy as np
import pandas as pd
import pytest

from dask_sql_tpu import Context
from dask_sql_tpu.physical import caps, compiled, programs
from dask_sql_tpu.runtime import faults, resilience as R
from tests.conftest import assert_eq

AGG_Q = "SELECT user_id, SUM(b) AS sb FROM user_table_1 GROUP BY user_id"
JOIN_Q = ("SELECT u1.user_id, SUM(u2.c) AS s FROM user_table_1 u1 "
          "JOIN user_table_2 u2 ON u1.user_id = u2.user_id "
          "GROUP BY u1.user_id")

_needs_compiled = pytest.mark.skipif(
    os.environ.get("DSQL_COMPILE") == "0",
    reason="fault sites live on the compiled path")


@pytest.fixture(autouse=True)
def _fresh(monkeypatch):
    """Per-test isolation: cached programs would bypass the compile site,
    and an armed spec must never leak into the next test."""
    programs._cache.clear()
    caps._learned_caps.clear()
    programs._runtime_eager.clear()
    faults.reset()
    monkeypatch.setenv("DSQL_RETRY_BASE_MS", "1")
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


def _no_split_leak(c):
    sch = c.schema.get("__split__")
    assert sch is None or not sch.tables, "leaked __split__ temp tables"


@pytest.fixture()
def chunked_ctx():
    df = pd.DataFrame({"k": [1, 2, 1, 2, 1, 2, 1, 2],
                       "v": [1.0, 2.0, 3.0, 4.0, 5.0, 6.0, 7.0, 8.0]})
    ctx = Context()
    ctx.create_table("t", df, chunked=True, batch_rows=3)
    expected = (df.groupby("k", as_index=False).agg(s=("v", "sum"))
                  .rename(columns={"k": "k"}))
    return ctx, expected


CHUNK_Q = "SELECT k, SUM(v) AS s FROM t GROUP BY k"


# ---------------------------------------------------------------------------
# retry-succeeds: one injected blip, same answer, retries counted
# ---------------------------------------------------------------------------

@_needs_compiled
@pytest.mark.parametrize("site", ["compile", "materialize"])
def test_single_fault_retries_and_succeeds(c, site):
    expected = _eager_oracle(c, AGG_Q)
    r0, f0 = compiled.stats["retries"], compiled.stats[f"fault_{site}"]
    with faults.inject(f"{site}:1"):
        got = c.sql(AGG_Q, return_futures=False)
    assert_eq(got, expected, check_row_order=False)
    assert compiled.stats[f"fault_{site}"] == f0 + 1
    assert compiled.stats["retries"] >= r0 + 1


@_needs_compiled
def test_stage_exec_fault_retries_and_succeeds(c, monkeypatch):
    monkeypatch.setenv("DSQL_STAGE_HEAVY", "1")
    expected = _eager_oracle(c, JOIN_Q)
    g0 = compiled.stats["stage_graphs"]
    r0, f0 = compiled.stats["retries"], compiled.stats["fault_stage_exec"]
    with faults.inject("stage_exec:1"):
        got = c.sql(JOIN_Q, return_futures=False)
    assert_eq(got, expected, check_row_order=False)
    assert compiled.stats["stage_graphs"] > g0, "plan did not stage"
    assert compiled.stats["fault_stage_exec"] == f0 + 1
    assert compiled.stats["retries"] >= r0 + 1
    _no_split_leak(c)


@pytest.mark.parametrize("site", ["chunked_read", "host_transfer"])
def test_streaming_fault_retries_and_succeeds(chunked_ctx, site):
    ctx, expected = chunked_ctx
    r0, f0 = compiled.stats["retries"], compiled.stats[f"fault_{site}"]
    with faults.inject(f"{site}:1"):
        got = ctx.sql(CHUNK_Q, return_futures=False)
    assert_eq(got, expected, check_row_order=False)
    assert compiled.stats[f"fault_{site}"] == f0 + 1
    assert compiled.stats["retries"] >= r0 + 1


# ---------------------------------------------------------------------------
# degrades-one-rung: persistent fault, answer still oracle-correct via a
# lower rung (stages → eager), degradation recorded
# ---------------------------------------------------------------------------

@_needs_compiled
@pytest.mark.parametrize("site", ["compile", "materialize"])
def test_persistent_fault_degrades_to_eager(c, site):
    expected = _eager_oracle(c, AGG_Q)
    d0 = compiled.stats["degradations"]
    with faults.inject(f"{site}:1+"):
        got = c.sql(AGG_Q, return_futures=False)
    assert_eq(got, expected, check_row_order=False)
    assert compiled.stats["degradations"] >= d0 + 1


@_needs_compiled
def test_persistent_compile_fault_walks_whole_stages_eager(c, monkeypatch):
    """A heavy plan walks the DECLARED ladder: whole-plan jit fails →
    bounded stages (split hint) → stages fail → eager — still correct."""
    expected = _eager_oracle(c, JOIN_Q)
    d0, h0 = compiled.stats["degradations"], compiled.stats["split_hints"]
    with faults.inject("compile:1+"):
        got = c.sql(JOIN_Q, return_futures=False)
    assert_eq(got, expected, check_row_order=False)
    assert compiled.stats["split_hints"] >= h0 + 1, "whole→stages rung"
    assert compiled.stats["degradations"] >= d0 + 2, "stages→eager rung"
    _no_split_leak(c)


@_needs_compiled
def test_whole_to_stages_keeps_the_order_by_the_host_was_to_apply():
    """Off the TPU a terminal ORDER BY / LIMIT is no part of the program
    (``identity.program_key``); the plan that re-enters as stages after the
    whole program failed to compile has to be the one that still holds it."""
    rng = np.random.RandomState(3)
    ctx = Context()
    ctx.create_table("a", pd.DataFrame({"k": rng.randint(0, 40, 400)}))
    ctx.create_table("b", pd.DataFrame({"k": np.arange(40),
                                        "w": np.arange(40) * 0.5}))
    query = ("SELECT a.k, SUM(b.w) AS s FROM a JOIN b ON a.k = b.k "
             "GROUP BY a.k ORDER BY s DESC, a.k LIMIT 2")
    expected = _eager_oracle(ctx, query)
    h0, g0 = compiled.stats["split_hints"], compiled.stats["stage_graphs"]
    with faults.inject("compile:1:fatal"):
        got = ctx.sql(query, return_futures=False)
    assert compiled.stats["split_hints"] == h0 + 1
    assert compiled.stats["stage_graphs"] == g0 + 1
    assert len(got) == 2
    assert_eq(got, expected)
    _no_split_leak(ctx)


@_needs_compiled
def test_persistent_stage_fault_degrades_graph_to_eager(c, monkeypatch):
    monkeypatch.setenv("DSQL_STAGE_HEAVY", "1")
    expected = _eager_oracle(c, JOIN_Q)
    d0 = compiled.stats["degradations"]
    with faults.inject("stage_exec:1+"):
        got = c.sql(JOIN_Q, return_futures=False)
    assert_eq(got, expected, check_row_order=False)
    assert compiled.stats["degradations"] >= d0 + 1
    _no_split_leak(c)


@pytest.mark.parametrize("site", ["chunked_read", "host_transfer"])
def test_streaming_persistent_fault_surfaces_typed(chunked_ctx, site):
    """The streaming sites have no lower rung (the data IS the input):
    exhausted retries surface the typed TransientError — never a partial
    or wrong result."""
    ctx, _ = chunked_ctx
    with faults.inject(f"{site}:1+"):
        with pytest.raises(R.TransientError):
            ctx.sql(CHUNK_Q)


@_needs_compiled
def test_eager_disabled_surfaces_typed_error(c, monkeypatch):
    """DSQL_EAGER_FALLBACK=0 turns the ladder's last rung into a TYPED
    failure (the acceptance criterion's fail-fast mode)."""
    monkeypatch.setenv("DSQL_EAGER_FALLBACK", "0")
    with faults.inject("compile:1+"):
        with pytest.raises(R.TransientError):
            c.sql(AGG_Q)


@_needs_compiled
def test_transient_failure_does_not_exile(c):
    """A transient-exhausted degrade must NOT poison the program cache:
    the next call (fault disarmed) compiles and serves compiled."""
    with faults.inject("compile:1+"):
        c.sql(AGG_Q, return_futures=False)
    n0 = compiled.stats["compiles"]
    c.sql(AGG_Q, return_futures=False)
    assert compiled.stats["compiles"] == n0 + 1, "plan was wrongly exiled"


# ---------------------------------------------------------------------------
# deadline-exceeded: a stalled site must surface the typed verdict well
# before the stall ends — never a hang past the deadline
# ---------------------------------------------------------------------------

@_needs_compiled
@pytest.mark.parametrize("site,query_fixture", [
    ("compile", "resident"), ("materialize", "resident"),
    ("stage_exec", "resident_staged"),
    ("chunked_read", "chunked"), ("host_transfer", "chunked"),
])
def test_stalled_site_hits_deadline(c, chunked_ctx, monkeypatch, site,
                                    query_fixture):
    if query_fixture == "resident":
        ctx, query = c, AGG_Q
    elif query_fixture == "resident_staged":
        monkeypatch.setenv("DSQL_STAGE_HEAVY", "1")
        ctx, query = c, JOIN_Q
    else:
        ctx, query = chunked_ctx[0], CHUNK_Q
    dl0 = compiled.stats["deadline_exceeded"]
    t0 = time.monotonic()
    with faults.inject(f"{site}:1:sleep=60000"):
        with pytest.raises(R.DeadlineExceeded):
            ctx.sql(query, timeout=0.5)
    assert time.monotonic() - t0 < 30.0, "ran far past the deadline"
    assert compiled.stats["deadline_exceeded"] > dl0


def test_sql_timeout_zero_is_immediate(c):
    with pytest.raises(R.DeadlineExceeded):
        c.sql(AGG_Q, timeout=0.0)


def test_deadline_applies_to_eager_path_too(c, monkeypatch):
    monkeypatch.setenv("DSQL_COMPILE", "0")
    with pytest.raises(R.DeadlineExceeded):
        c.sql(AGG_Q, timeout=0.0)


# ---------------------------------------------------------------------------
# server: typed payloads, timeout shape, cancel-while-compiling
# ---------------------------------------------------------------------------

@pytest.fixture()
def server():
    from dask_sql_tpu.server.app import run_server

    context = Context()
    context.create_table(
        "df", pd.DataFrame({"a": [1, 2, 3], "b": [1.5, 2.5, 3.5]}))
    srv = run_server(context=context, host="127.0.0.1", port=0,
                     blocking=False)
    yield srv, f"http://127.0.0.1:{srv.server_port}"
    srv.shutdown()


def _post(url, body):
    import json
    import urllib.request
    req = urllib.request.Request(url, data=body.encode(), method="POST")
    with urllib.request.urlopen(req) as r:
        return json.loads(r.read())


def _get(url):
    import json
    import urllib.request
    with urllib.request.urlopen(url) as r:
        return json.loads(r.read())


def _poll(base, payload, timeout=60):
    deadline = time.time() + timeout
    while "nextUri" in payload and time.time() < deadline:
        time.sleep(0.05)
        payload = _get(payload["nextUri"])
    return payload


@_needs_compiled
def test_server_timeout_payload_shape(server, monkeypatch):
    srv, base = server
    monkeypatch.setenv("DSQL_QUERY_TIMEOUT_MS", "400")
    with faults.inject("compile:1:sleep=60000"):
        payload = _poll(base, _post(
            f"{base}/v1/statement", "SELECT a, SUM(b) AS s FROM df GROUP BY a"))
    err = payload["error"]
    assert payload["stats"]["state"] == "FAILED"
    assert err["errorType"] == "INSUFFICIENT_RESOURCES"
    assert err["errorName"] == "EXCEEDED_TIME_LIMIT"
    assert err["errorCode"] == R.DeadlineExceeded("x").error_code


@_needs_compiled
def test_server_cancel_while_compiling(server):
    """DELETE /v1/cancel must abort a query stuck in compile: the cancel
    token (not fut.cancel(), a no-op on started futures) makes the worker
    raise QueryCancelled at its next checkpoint."""
    srv, base = server
    f0 = compiled.stats["fault_compile"]
    with faults.inject("compile:1:sleep=60000"):
        payload = _post(f"{base}/v1/statement",
                        "SELECT a, SUM(b) AS s FROM df GROUP BY a")
        uid = payload["id"]
        # wait until the worker is inside the stalled compile
        deadline = time.time() + 30
        while (compiled.stats["fault_compile"] == f0
               and time.time() < deadline):
            time.sleep(0.02)
        fut = srv.app_state.future_list[uid]
        import urllib.request
        req = urllib.request.Request(payload["partialCancelUri"],
                                     method="DELETE")
        with urllib.request.urlopen(req) as r:
            assert r.status == 200
        t0 = time.monotonic()
        exc = fut.exception(timeout=30)
    assert isinstance(exc, R.QueryCancelled)
    assert time.monotonic() - t0 < 30.0, "cancel did not interrupt compile"


def test_server_internal_error_payload(server):
    """An engine-side transient that exhausts the ladder with eager
    disabled maps to INTERNAL_ERROR — not a stringified USER_ERROR."""
    srv, base = server
    os.environ["DSQL_EAGER_FALLBACK"] = "0"
    try:
        with faults.inject("compile:1+"):
            payload = _poll(base, _post(
                f"{base}/v1/statement",
                "SELECT a, SUM(b) AS s FROM df GROUP BY a"))
    finally:
        del os.environ["DSQL_EAGER_FALLBACK"]
    err = payload["error"]
    assert err["errorType"] == "INTERNAL_ERROR"
    assert err["errorName"] == "FAULT_INJECTED"
    assert err["errorCode"] == R.TransientError("x").error_code


def test_server_user_error_still_user_error(server):
    srv, base = server
    payload = _poll(base, _post(f"{base}/v1/statement",
                                "SELECT * FROM missing_table"))
    assert payload["error"]["errorType"] == "USER_ERROR"
    assert "errorLocation" in payload["error"]
