"""Presto-protocol server tests (reference: tests/integration/test_server.py —
route codes, async polling loop, cancellation, error shape)."""
import json
import time
import urllib.error
import urllib.request

import pandas as pd
import pytest


@pytest.fixture(scope="module")
def server():
    from dask_sql_tpu.context import Context
    from dask_sql_tpu.server.app import run_server

    context = Context()
    context.create_table("df", pd.DataFrame({"a": [1, 2, 3], "b": list("xyz")}))
    srv = run_server(context=context, host="127.0.0.1", port=0, blocking=False)
    yield f"http://127.0.0.1:{srv.server_port}"
    srv.shutdown()


def _post(url, body):
    req = urllib.request.Request(url, data=body.encode(), method="POST")
    with urllib.request.urlopen(req) as r:
        return json.loads(r.read())


def _get(url):
    with urllib.request.urlopen(url) as r:
        return json.loads(r.read())


def _run_to_completion(server, sql, timeout=30):
    payload = _post(f"{server}/v1/statement", sql)
    deadline = time.time() + timeout
    while "nextUri" in payload and time.time() < deadline:
        time.sleep(0.05)
        payload = _get(payload["nextUri"])
    return payload


def test_empty(server):
    payload = _get(f"{server}/v1/empty")
    assert payload["columns"] == [] and payload["data"] == []


def test_query(server):
    payload = _run_to_completion(server, "SELECT * FROM df ORDER BY a")
    assert [c["name"] for c in payload["columns"]] == ["a", "b"]
    assert [c["type"] for c in payload["columns"]] == ["bigint", "varchar"]
    assert payload["data"] == [[1, "x"], [2, "y"], [3, "z"]]
    assert payload["stats"]["state"] == "FINISHED"


def test_error_shape(server):
    payload = _run_to_completion(server, "SELECT * FROM missing_table")
    assert "error" in payload
    # reference QueryError: errorName = str(type(error)) (responses.py:126)
    assert "ValidationException" in payload["error"]["errorName"]
    assert "errorLocation" in payload["error"]


def test_unknown_id(server):
    with pytest.raises(urllib.error.HTTPError) as exc:
        _get(f"{server}/v1/status/nope")
    assert exc.value.code == 404


def test_cancel(server):
    payload = _post(f"{server}/v1/statement", "SELECT 1 + 1")
    cancel = payload["partialCancelUri"]
    req = urllib.request.Request(cancel, method="DELETE")
    with urllib.request.urlopen(req) as r:
        assert r.status == 200
    # the id is gone afterwards
    with pytest.raises(urllib.error.HTTPError):
        _get(payload["nextUri"])


def test_aggregate_via_server(server):
    payload = _run_to_completion(server, "SELECT SUM(a) AS s FROM df")
    assert payload["data"] == [[6]]


def test_stats_filled(server):
    """The reference returns hardcoded zero stats (responses.py:11-49);
    ours must carry real execution telemetry (review r1 item 7)."""
    payload = _run_to_completion(server, "SELECT a, COUNT(*) AS n FROM df "
                                         "GROUP BY a")
    stats = payload["stats"]
    assert stats["state"] == "FINISHED"
    assert stats["processedRows"] == 3
    assert stats["processedBytes"] > 0
    assert stats["elapsedTimeMillis"] >= stats["wallTimeMillis"] >= 0
    assert stats["cpuTimeMillis"] >= 0
    # compile/cache split is present and consistent: the query ran through
    # the compiled pipeline exactly once (either fresh compile or hit)
    assert stats["compiledPrograms"] + stats["programCacheHits"] >= 1


def test_column_shape_matches_reference(server):
    """Field-by-field column description shape the reference's server test
    pins (/root/reference/tests/integration/test_server.py:50-57 and
    responses.py:67-77): name + lowercase type + typeSignature with
    rawType and empty arguments."""
    payload = _run_to_completion(server, "SELECT 1 + 1 AS x")
    assert payload["columns"] == [{
        "name": "x", "type": "integer",
        "typeSignature": {"rawType": "integer", "arguments": []},
    }]
    assert payload["data"] == [[2]]
    assert "error" not in payload
    assert "nextUri" not in payload

    payload = _run_to_completion(
        server, "SELECT a, b, a * 0.5 AS h FROM df ORDER BY a")
    shapes = [(c["name"], c["type"], c["typeSignature"]["rawType"],
               c["typeSignature"]["arguments"]) for c in payload["columns"]]
    assert shapes == [("a", "bigint", "bigint", []),
                      ("b", "varchar", "varchar", []),
                      ("h", "double", "double", [])]


def _get_metrics(server):
    with urllib.request.urlopen(f"{server}/metrics") as r:
        return r.status, r.headers.get("Content-Type"), r.read().decode()


def _metric_value(text, name):
    for line in text.splitlines():
        if line.startswith(name + " "):
            return float(line.rsplit(" ", 1)[1])
    raise AssertionError(f"metric {name} not in /metrics output")


def test_metrics_endpoint_content_type_and_counters(server):
    """GET /metrics: prometheus text exposition of the telemetry registry
    — the counters previously only reachable via physical.compiled.stats."""
    status, ctype, text = _get_metrics(server)
    assert status == 200
    assert ctype.startswith("text/plain")
    assert "version=0.0.4" in ctype
    # the stable counter names export under the dsql_ prefix
    for name in ("dsql_compiles_total", "dsql_hits_total",
                 "dsql_fallbacks_total", "dsql_server_queries_total",
                 "dsql_queries_total"):
        assert f"# TYPE {name} counter" in text
        assert _metric_value(text, name) >= 0


def test_metrics_counters_are_monotonic(server):
    """Counters only move up: running a query strictly increases the
    server-query and engine-query counters and never decreases any."""
    _, _, before = _get_metrics(server)
    payload = _run_to_completion(server, "SELECT COUNT(*) AS n FROM df")
    assert payload["stats"]["state"] == "FINISHED"
    _, _, after = _get_metrics(server)
    assert (_metric_value(after, "dsql_server_queries_total")
            >= _metric_value(before, "dsql_server_queries_total") + 1)
    assert (_metric_value(after, "dsql_queries_total")
            >= _metric_value(before, "dsql_queries_total") + 1)
    for line in before.splitlines():
        if line.startswith("dsql_") and "_total " in line:
            name = line.split(" ")[0]
            assert _metric_value(after, name) >= _metric_value(before, name)


def test_metrics_histograms_present(server):
    _run_to_completion(server, "SELECT 1 + 1")
    _, _, text = _get_metrics(server)
    assert "# TYPE dsql_query_wall_ms histogram" in text
    assert 'dsql_query_wall_ms_bucket{le="+Inf"}' in text
    assert _metric_value(text, "dsql_query_wall_ms_count") >= 1


def test_stats_phase_breakdown(server):
    """Per-query wire stats carry the query's OWN phase split (from its
    thread-local QueryReport, not a racy process-global)."""
    payload = _run_to_completion(server, "SELECT SUM(a) AS s FROM df")
    phases = payload["stats"].get("phaseMillis")
    assert phases, "phaseMillis missing from finished-query stats"
    assert "parse" in phases and "execute" in phases
    assert all(v >= 0 for v in phases.values())


def test_stats_phase_breakdown_carries_encode(server):
    """The rows' way onto the wire is a phase of the query that produced
    them: the GET that serves the page times ``encode`` and folds it into
    ``phaseMillis`` (the query's own trace closed on the worker thread)."""
    payload = _run_to_completion(server, "SELECT a, b FROM df")
    assert payload["data"], "the page has to carry rows"
    phases = payload["stats"]["phaseMillis"]
    assert phases["encode"] > 0
    # encode lies outside execute, after it
    assert set(phases) >= {"parse", "plan", "execute", "encode"}


def test_error_location_matches_reference(server):
    """The reference asserts the exact parse position in errorLocation
    (test_server.py:60-74: 'SELECT 1 + ' -> line 1, column 10+); ours
    carries the native parser's 1-based position instead of a hardcoded
    1,1."""
    payload = _run_to_completion(server, "SELECT 1 + ")
    assert "columns" not in payload
    err = payload["error"]
    assert "message" in err
    loc = err["errorLocation"]
    assert loc["lineNumber"] == 1
    assert loc["columnNumber"] >= 10
    payload = _run_to_completion(server, "SELECT nope FROM df\nWHERE boom")
    # the binder reports the unresolvable column at line 1; a multi-line
    # position must survive to the wire (verified: line=1 col=8 for nope)
    loc2 = payload["error"]["errorLocation"]
    assert (loc2["lineNumber"], loc2["columnNumber"]) != (1, 1)
