"""Unit tests for runtime/telemetry.py: span nesting + exception paths,
registry atomicity/snapshot/reset, prometheus rendering, chrome-trace
export, the deprecated dict aliases, worker-thread re-entry, the spans on
the profiler's trace, and the executor's spans and names on TPC-H shapes."""
import json
import os
import threading

import pytest

from dask_sql_tpu.runtime import telemetry as tel


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------

def test_span_nesting_builds_tree():
    with tel.trace_scope("SELECT 1") as trace:
        assert trace is not None
        with tel.span("parse"):
            pass
        with tel.span("execute"):
            with tel.span("compile"):
                pass
            with tel.span("materialize"):
                pass
    names = [s.name for s in trace.root.walk()]
    assert names == ["query", "parse", "execute", "compile", "materialize"]
    execute = trace.root.children[1]
    assert [c.name for c in execute.children] == ["compile", "materialize"]
    # every span closed with a wall time
    for s in trace.root.walk():
        assert s.t1 is not None
        assert s.wall_ms >= 0.0


def test_span_exception_path_marks_and_reraises():
    with pytest.raises(ValueError):
        with tel.trace_scope("boom") as trace:
            with tel.span("execute"):
                raise ValueError("boom")
    # the span AND the root both closed and carry the error class
    execute = trace.root.children[0]
    assert execute.t1 is not None
    assert execute.attrs["error"] == "ValueError"
    assert trace.root.attrs["error"] == "ValueError"
    # the report still exists for a failed query
    assert trace.report is not None
    assert trace.report.phases["execute"] >= 0.0
    # and telemetry state fully unwound: no trace leaks to the next query
    assert tel.current_trace() is None
    assert tel.current_span() is None


def test_span_outside_trace_is_noop():
    assert tel.current_trace() is None
    with tel.span("orphan") as s:
        assert s is None
    tel.annotate(ignored=True)  # must not raise


def test_nested_trace_scope_rides_outer():
    with tel.trace_scope("outer") as outer:
        with tel.trace_scope("inner") as inner:
            assert inner is None  # one trace per outermost query
            with tel.span("execute"):
                pass
    assert outer.report.phases["execute"] >= 0.0


def test_annotate_targets_innermost_open_span():
    with tel.trace_scope("q") as trace:
        with tel.span("execute"):
            with tel.span("stage"):
                tel.annotate(index=3, cache_hit=True)
    stage = trace.root.children[0].children[0]
    assert stage.attrs == {"index": 3, "cache_hit": True}


def test_scoped_reentry_attaches_worker_spans():
    """Worker threads re-enter the trace via scoped() (the stage-graph
    pool pattern); their spans land under the chosen parent."""
    with tel.trace_scope("q") as trace:
        with tel.span("execute") as parent:
            caught = []

            def worker(i):
                with tel.scoped(trace, parent):
                    with tel.span("stage", index=i):
                        caught.append(tel.current_trace() is trace)

            threads = [threading.Thread(target=worker, args=(i,))
                       for i in range(8)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
    assert caught == [True] * 8
    stages = [s for s in trace.root.walk() if s.name == "stage"]
    assert len(stages) == 8
    assert sorted(s.attrs["index"] for s in stages) == list(range(8))
    # concurrent child append lost nothing
    assert trace.report.span_count("stage") == 8


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

def test_registry_increments_are_atomic_across_threads():
    reg = tel.MetricsRegistry()
    N, T = 2000, 8

    def bump():
        for _ in range(N):
            reg.inc("c")

    threads = [threading.Thread(target=bump) for _ in range(T)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert reg.get("c") == N * T


def test_registry_snapshot_and_reset():
    reg = tel.MetricsRegistry(seed=("a", "b"))
    reg.inc("a", 3)
    reg.observe("h_ms", 12.0)
    snap = reg.snapshot()
    assert snap["counters"] == {"a": 3, "b": 0}
    assert snap["histograms"]["h_ms"]["count"] == 1
    assert snap["histograms"]["h_ms"]["sum"] == 12.0
    reg.reset()
    snap = reg.snapshot()
    # seeded keys survive a reset at zero; histograms clear
    assert snap["counters"] == {"a": 0, "b": 0}
    assert snap["histograms"] == {}


def test_histogram_is_bounded_and_buckets_correctly():
    reg = tel.MetricsRegistry()
    for v in (0.5, 3.0, 3.0, 40.0, 10 ** 9):
        reg.observe("h", v)
    h = reg.snapshot()["histograms"]["h"]
    assert h["count"] == 5
    buckets = dict(h["buckets"])
    assert buckets[1] == 1          # 0.5
    assert buckets[5] == 2          # 3.0 x2
    assert buckets[50] == 1         # 40.0
    assert h["overflow"] == 1       # 1e9 beyond the last bound
    # bounded: observing more values never grows the structure
    assert len(h["buckets"]) == len(tel._BUCKETS_MS)


def test_prometheus_render_shape():
    reg = tel.MetricsRegistry(seed=("compiles",))
    reg.inc("compiles", 2)
    reg.observe("query_wall_ms", 7.0)
    text = reg.render_prometheus()
    assert "# TYPE dsql_compiles_total counter" in text
    assert "dsql_compiles_total 2" in text
    assert "# TYPE dsql_query_wall_ms histogram" in text
    assert 'dsql_query_wall_ms_bucket{le="+Inf"} 1' in text
    assert "dsql_query_wall_ms_sum 7" in text
    assert "dsql_query_wall_ms_count 1" in text
    # cumulative le-buckets are monotone
    counts = [int(line.rsplit(" ", 1)[1]) for line in text.splitlines()
              if line.startswith("dsql_query_wall_ms_bucket")]
    assert counts == sorted(counts)


def test_global_registry_seeds_stable_names():
    counters = tel.REGISTRY.counters()
    for name in tel.STABLE_COUNTERS:
        assert name in counters, f"stable counter {name} not seeded"


# ---------------------------------------------------------------------------
# deprecated aliases
# ---------------------------------------------------------------------------

def test_compiled_stats_alias_reads_registry():
    from dask_sql_tpu.physical import compiled
    before = compiled.stats["compiles"]
    tel.inc("compiles")
    try:
        assert compiled.stats["compiles"] == before + 1
        snap = dict(compiled.stats)
        assert snap["compiles"] == before + 1
        assert "stage_graphs" in snap
        with pytest.raises(KeyError):
            compiled.stats["no_such_counter"]
    finally:
        tel.REGISTRY.set("compiles", before)


def test_root_carries_a_process_wide_sequence_number():
    """The root span's ``seq`` identifies a request inside the process: it
    rises with every trace opened, on any thread, and the report and the
    chrome trace carry it."""
    seen = {}

    def other():
        with tel.trace_scope("theirs") as t:
            pass
        seen["seq"] = t.root.attrs["seq"]

    with tel.trace_scope("mine") as first:
        pass
    t = threading.Thread(target=other)
    t.start()
    t.join()
    with tel.trace_scope("mine again") as last:
        pass
    assert first.root.attrs["seq"] < seen["seq"] < last.root.attrs["seq"]
    assert last.report.seq == last.root.attrs["seq"]
    assert last.report.to_dict()["spans"]["attrs"]["seq"] == last.report.seq


def test_span_times_are_on_the_monotonic_clock():
    """``t0``/``t1`` are ``time.monotonic_ns()`` readings, so a span tree
    lies beside a client's record or a trace without an anchor."""
    import time
    before = time.monotonic_ns()
    with tel.trace_scope("q") as trace:
        with tel.span("execute"):
            with tel.span("lookup"):
                pass
            with tel.span("bind"):
                pass
    after = time.monotonic_ns()
    spans = list(trace.root.walk())
    assert all(isinstance(s.t0, int) and isinstance(s.t1, int)
               for s in spans)
    assert all(before <= s.t0 <= s.t1 <= after for s in spans)
    # depth first, in the order things happened: starts never go back
    starts = [s.t0 for s in spans]
    assert starts == sorted(starts)
    d = trace.report.to_dict()["spans"]
    assert d["t0_ns"] == trace.root.t0
    assert d["children"][0]["children"][1]["t0_ns"] == spans[3].t0
    events = trace.report.to_chrome_trace()["traceEvents"]
    assert [e["t0_ns"] for e in events] == starts
    assert events[0]["ts"] == trace.root.t0 / 1e3


def _dsql_events(trace_dir):
    """{line index: [(name, {stat: value})]} of the ``dsql:`` events on
    the host's lines of the one trace under ``trace_dir``."""
    import glob

    from jax.profiler import ProfileData
    path, = glob.glob(str(trace_dir / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            found = [(e.name, dict(e.stats)) for e in line.events
                     if e.name.startswith("dsql:")]
            if found:
                out[i] = found
    return out


def test_spans_are_trace_annotations_on_the_thread_that_ran_them(tmp_path):
    """With a profiler session on, every span is a ``dsql:<name>`` event;
    the root carries ``seq``; a span opened on a ``scoped()`` worker opens
    and closes its annotation on the worker's own thread line."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with tel.trace_scope("q") as trace:
            with tel.span("execute") as execute:
                with tel.span("lookup"):
                    pass

                def worker():
                    with tel.scoped(trace, execute), \
                            tel.span("stage", index=0):
                        with tel.span("dispatch"):
                            pass

                t = threading.Thread(target=worker)
                t.start()
                t.join()
        with tel.annotation("encode", seq=trace.report.seq, rows=3):
            pass
    finally:
        jax.profiler.stop_trace()
    by_line = _dsql_events(tmp_path)
    assert len(by_line) == 2, by_line
    main = next(v for v in by_line.values() if v[0][0] == "dsql:query")
    other = next(v for v in by_line.values() if v is not main)
    assert [n for n, _ in main] == ["dsql:query", "dsql:execute",
                                    "dsql:lookup", "dsql:encode"]
    assert main[0][1] == {"seq": trace.report.seq}
    assert main[3][1] == {"seq": trace.report.seq, "rows": 3}
    assert [n for n, _ in other] == ["dsql:stage", "dsql:dispatch"]
    # and the worker's spans still hang under the query's tree
    assert [s.name for s in trace.root.walk()] == [
        "query", "execute", "lookup", "stage", "dispatch"]


# ---------------------------------------------------------------------------
# reports
# ---------------------------------------------------------------------------

def test_report_phase_aggregation_and_counters_delta():
    with tel.trace_scope("q") as trace:
        tel.inc("compiles")
        with tel.span("parse"):
            pass
        with tel.span("execute"):
            with tel.span("compile"):
                pass
    try:
        rep = trace.report
        assert rep is not None
        assert set(rep.phases) >= {"parse", "execute", "compile"}
        # phases measured from spans can never exceed the query wall
        assert rep.phases["parse"] + rep.phases["execute"] <= rep.wall_ms
        assert rep.counters.get("compiles") == 1
        # the trace's own bookkeeping (queries/query_wall_ms) lands AFTER
        # the report snapshot: the per-query delta is engine work only
        assert "queries" not in rep.counters
    finally:
        tel.REGISTRY.inc("compiles", -1)


def test_report_render_and_dict():
    with tel.trace_scope("SELECT x FROM t") as trace:
        with tel.span("execute"):
            tel.annotate(cache_hit=True)
        trace.root.attrs["rows_out"] = 7
    rep = trace.report
    assert rep.rows_out == 7
    d = rep.to_dict()
    assert d["query"] == "SELECT x FROM t"
    assert d["spans"]["children"][0]["attrs"] == {"cache_hit": True}
    text = rep.render()
    assert "SELECT x FROM t" in text
    assert "execute" in text and "cache_hit=True" in text


def test_chrome_trace_export_shape():
    with tel.trace_scope("q") as trace:
        with tel.span("execute"):
            with tel.span("stage", index=0):
                pass
    blob = trace.report.to_chrome_trace()
    events = blob["traceEvents"]
    assert [e["name"] for e in events] == ["query", "execute", "stage"]
    for e in events:
        assert e["ph"] == "X"
        assert e["ts"] >= 0 and e["dur"] >= 0
    assert events[2]["args"] == {"index": 0}
    json.dumps(blob)  # must be JSON-serializable as-is


def test_chrome_trace_file_export(tmp_path, monkeypatch):
    monkeypatch.setenv("DSQL_CHROME_TRACE_DIR", str(tmp_path))
    with tel.trace_scope("q"):
        with tel.span("execute"):
            pass
    files = list(tmp_path.glob("*.trace.json"))
    assert len(files) == 1
    blob = json.loads(files[0].read_text())
    assert blob["traceEvents"][0]["name"] == "query"


def test_slow_query_log_counter(monkeypatch, caplog):
    import logging
    before = tel.REGISTRY.get("slow_queries")
    monkeypatch.setenv("DSQL_SLOW_QUERY_MS", "0")
    with caplog.at_level(logging.WARNING,
                         logger="dask_sql_tpu.runtime.telemetry"):
        with tel.trace_scope("SELECT slow"):
            pass
    assert tel.REGISTRY.get("slow_queries") == before + 1
    assert any("slow query" in r.message for r in caplog.records)


def test_slow_query_log_is_self_contained(monkeypatch, caplog):
    """A slow-log line carries tier, cacheHit and priority so triage
    needs no query replay."""
    import logging
    monkeypatch.setenv("DSQL_SLOW_QUERY_MS", "0")
    with caplog.at_level(logging.WARNING,
                         logger="dask_sql_tpu.runtime.telemetry"):
        with tel.trace_scope("SELECT triage"):
            with tel.span("queued", priority="batch"):
                pass
            with tel.span("execute", tier="compiled"):
                pass
    msg = next(r.message for r in caplog.records
               if "SELECT triage" in r.message)
    assert "tier: compiled" in msg
    assert "cacheHit: False" in msg
    assert "priority: batch" in msg


def test_last_report_is_thread_local():
    with tel.trace_scope("mine"):
        pass
    assert tel.last_report().query == "mine"
    seen = {}

    def other():
        with tel.trace_scope("theirs"):
            pass
        seen["q"] = tel.last_report().query

    t = threading.Thread(target=other)
    t.start()
    t.join()
    assert seen["q"] == "theirs"
    assert tel.last_report().query == "mine"  # not clobbered


# ---------------------------------------------------------------------------
# node recorder
# ---------------------------------------------------------------------------

def test_node_recorder_accumulates_per_node():
    class N:  # stand-in plan node
        pass

    a, b = N(), N()
    with tel.record_nodes() as rec:
        assert tel.active_node_recorder() is rec
        rec.add(a, 1.0, 10)
        rec.add(a, 2.0, 10)
        rec.add(b, 5.0, 3)
    assert tel.active_node_recorder() is None
    assert rec.get(a) == [3.0, 20, 2]
    assert rec.get(b) == [5.0, 3, 1]
    assert rec.get(N()) is None


# ---------------------------------------------------------------------------
# the executor's spans, and the names the device sees (TPC-H at SF0.005)
# ---------------------------------------------------------------------------

_EXECUTOR_PHASES = ("lookup", "bind", "dispatch", "materialize")


@pytest.fixture(scope="module")
def tpch():
    from benchmarks.tpch import QUERIES, generate_tpch
    from dask_sql_tpu import Context

    context = Context()
    for name, frame in generate_tpch(0.005).items():
        context.create_table(name, frame)
    return context, QUERIES


def _children(span, name):
    return [c for c in span.children if c.name == name]


def test_compiled_report_opens_execute_into_its_phases(tpch):
    """A query served by a cached program: ``lookup``, ``bind``,
    ``dispatch`` and ``materialize`` are children of ``execute``, carry
    their counts, and together lie within it."""
    context, queries = tpch
    context.sql(queries[6], return_futures=False)      # compiles
    context.sql(queries[6], return_futures=False)      # served from cache
    rep = context.last_report
    assert rep.tier == "compiled" and rep.counters.get("hits") == 1
    execute, = _children(rep.root, "execute")
    for name in _EXECUTOR_PHASES:
        assert _children(execute, name), f"no {name} under execute"
    inside = sum(rep.phases[name] for name in _EXECUTOR_PHASES)
    assert 0 < inside <= rep.phases["execute"]
    assert any(s.attrs.get("cache_hit") for s in _children(execute, "lookup"))
    bind, = _children(execute, "bind")
    assert bind.attrs["params"] == rep.counters["param_literals_hoisted"] > 0
    assert bind.attrs["args"] > bind.attrs["params"]
    assert bind.attrs["h2d_bytes"] > 0
    dispatch, = _children(execute, "dispatch")
    assert dispatch.attrs["program"].startswith("dsql_Logical")
    materialize, = _children(execute, "materialize")
    assert materialize.attrs["bytes"] > 0
    assert materialize.attrs["small_fetch"] is True


def test_staged_report_sums_the_phases_over_its_stages(tpch, monkeypatch):
    """A plan run as a stage graph: each stage has its own lookup, bind,
    dispatch and materialize, and the report's phases are their sums."""
    context, queries = tpch
    monkeypatch.setenv("DSQL_STAGE_HEAVY", "1")
    context.sql(queries[12], return_futures=False)
    context.sql(queries[12], return_futures=False)
    rep = context.last_report
    stages = [s for s in rep.root.walk() if s.name == "stage"]
    assert len(stages) >= 2
    for name in _EXECUTOR_PHASES:
        in_stages = [c for s in stages for c in _children(s, name)]
        assert len(in_stages) >= len(stages), name
        everywhere = [s for s in rep.root.walk() if s.name == name]
        assert rep.phases[name] == pytest.approx(
            sum(s.wall_ms for s in everywhere))
    programs = {c.attrs["program"] for s in stages
                for c in _children(s, "dispatch")}
    assert len(programs) == len(stages)
    assert sum(rep.phases[n] for n in _EXECUTOR_PHASES) \
        <= rep.phases["execute"]


def _program(context, sql):
    """(entry, lowered text with locations) of the program that serves
    ``sql`` whole."""
    from dask_sql_tpu.physical import programs

    context.sql(sql, return_futures=False)      # compiles, or is served
    context.sql(sql, return_futures=False)      # served from the cache
    dispatch, = [s for s in context.last_report.root.walk()
                 if s.name == "dispatch"]
    entry, = [e for e in programs._cache.values()
              if e is not programs._UNSUPPORTED
              and e.name == dispatch.attrs["program"]]
    return entry, entry.fn.lower(*_flat_shapes(context, sql)).as_text(
        debug_info=True)


def _flat_shapes(context, sql):
    """The argument list ``_execute_single`` would bind, as shapes."""
    import jax

    from dask_sql_tpu.physical import compiled, identity
    from dask_sql_tpu.sql.parser import parse_sql
    pk = identity.program_key(identity._maybe_parameterize(
        context._get_plan(parse_sql(sql)[0].query), count=False), context)
    flat = identity._flatten_tables(pk.scans) \
        + compiled._param_args(pk.params)
    return [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in flat]


@pytest.mark.parametrize("query, scopes", [
    (6, ("dsql.LogicalAggregate", "dsql.LogicalFilter")),
    (12, ("dsql.LogicalAggregate", "dsql.LogicalJoin", "dsql.LogicalFilter",
          "dsql.join_build", "dsql.join_probe")),
])
def test_lowered_programs_carry_the_engine_names(tpch, query, scopes):
    """What the chip's trace will read: the module is named after the
    program, and every op's location nests the plan nodes it came from."""
    context, queries = tpch
    entry, text = _program(context, queries[query])
    assert entry.name.startswith("dsql_Logical")
    assert f"@jit_{entry.name}" in text
    assert "jit_fn" not in text
    for scope in scopes:
        assert scope in text, scope
    if query == 12:
        assert "dsql.LogicalJoin/dsql.join_build" in text.replace(
            '"', "")


_NAME_SCRIPT = """
import sys
import numpy as np, pandas as pd
from dask_sql_tpu import Context
from dask_sql_tpu.physical import compiled as cm
c = Context()
n = int(sys.argv[1])
c.create_table('t', pd.DataFrame({'a': np.arange(n), 'b': np.arange(n) % 7,
                                  'x': np.arange(n) * 0.5}))
for sql in sys.argv[2:]:
    c.sql(sql, return_futures=False)
    r = c.last_report
    print(next((s.attrs['program'] for s in r.root.walk()
                if s.name == 'dispatch'), '-'))
"""


def test_module_names_repeat_across_processes_and_differ_by_plan():
    """The name joins XLA's persistent-cache key: two processes given the
    same plan and layout must produce the same name (whatever the literal,
    the table uid or the data), and another plan or layout another."""
    import subprocess
    import sys

    a = "SELECT SUM(x) FROM t WHERE a > 5"
    b = "SELECT b, SUM(x) FROM t WHERE a > 5 GROUP BY b"

    def names(rows, *sqls):
        out = subprocess.run(
            [sys.executable, "-c", _NAME_SCRIPT, str(rows), *sqls],
            capture_output=True, text=True, timeout=300,
            env={**os.environ, "DSQL_TIERED": "0", "DSQL_RESULT_CACHE_MB": "0",
                 "JAX_PLATFORMS": "cpu"})
        assert out.returncode == 0, out.stderr[-2000:]
        return out.stdout.split()

    # each query runs twice in a process: the first compiles, the second
    # is dispatched from the cache and names its program
    first = names(1000, a, a, b, b)
    second = names(1000, a.replace("5", "77"), a.replace("5", "78"), b, b)
    assert first[1] == second[1] and first[3] == second[3]
    assert first[1] != first[3]
    assert first[0] == "-" and first[1].startswith("dsql_Logical")
    other_layout = names(2000, a, a)
    assert other_layout[1] != first[1]
