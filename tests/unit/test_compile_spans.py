"""The once-a-program path itemized (``telemetry.compile_span`` /
``first_run_span``, opened by ``programs.obtain`` and
``compiled._execute_single``): a program obtained by compiling is a
``compile`` span that says which program, in which round and why, whose
children are JAX's own clocks for trace, lowering and XLA (or the read of its
persistent cache); the counters and ``compile_log()`` / ``system.compiles``
add them up, foreground and background; a warm request pays none of it."""
import json
import os
import subprocess
import sys
import time

import numpy as np
import pandas as pd
import pytest
from jax._src import monitoring

from dask_sql_tpu import Context
from dask_sql_tpu.physical import caps, compiled as cm, programs, tiering
from dask_sql_tpu.runtime import telemetry as tel

NEW_COUNTERS = ("compile_trace_ms", "compile_lower_ms", "compile_xla_ms",
                "compile_cache_load_ms", "compile_first_run_ms",
                "compile_recompile_ms", "recompiles_overflow",
                "recompiles_tighten", "recompiles_hint")
PHASES = ["compile_trace", "compile_lower", "compile_xla"]
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


@pytest.fixture(autouse=True)
def fresh():
    programs._cache.clear()
    caps._learned_caps.clear()
    with tel._compile_log_lock:
        tel._compile_log.clear()


def _context(rows=2000, groups=40):
    rng = np.random.default_rng(rows)
    ctx = Context()
    ctx.create_table("t", pd.DataFrame({
        "g": rng.integers(0, groups, rows), "x": rng.random(rows),
        "v": np.round(rng.random(rows), 6)}))
    return ctx


def _moved(before):
    now = tel.REGISTRY.counters()
    return {k: now[k] - before.get(k, 0) for k in now
            if now[k] != before.get(k, 0)}


def _run(ctx, query):
    """The answer, the report, what the counters moved by, and the records
    the ring gained."""
    before, logged = tel.REGISTRY.counters(), len(tel.compile_log())
    got = ctx.sql(query, return_futures=False)
    return got, ctx.last_report, _moved(before), tel.compile_log()[logged:]


def _spans(report, name):
    return [s for s in report.root.walk() if s.name == name]


QUERY = "SELECT g, SUM(v) AS s FROM t WHERE x < {x} GROUP BY g"


def test_a_compile_span_holds_jax_s_phases_in_order_and_says_why():
    ctx = _context()
    hist = tel.REGISTRY.snapshot()["histograms"].get(
        "compile_ms", {"count": 0, "sum": 0.0})
    _, report, moved, (record,) = _run(ctx, QUERY.format(x=0.5))
    (compile_,) = _spans(report, "compile")
    kids = compile_.children
    assert [k.name for k in kids] == PHASES
    for k in kids:
        assert compile_.t0 <= k.t0 <= k.t1 <= compile_.t1
    assert [k.t1 for k in kids] == sorted(k.t1 for k in kids)
    assert [k.t0 for k in kids] == sorted(k.t0 for k in kids)
    # tests keep XLA's persistent cache off (conftest)
    assert kids[2].attrs == {"xla_cache": "off"}
    (dispatched,) = [e.name for e in programs._cache.values()]
    assert compile_.attrs == {"program": dispatched, "round": 0,
                              "cause": "first", "caps": "",
                              "background": False}
    # the phases, by name, as every span's; their sum lies inside compile
    for name in PHASES:
        assert report.phases[name] > 0
    assert sum(report.phases[n] for n in PHASES) <= report.phases["compile"]
    # the materialize of the same round is the program's first run
    (materialize,) = _spans(report, "materialize")
    assert materialize.attrs["first_run"] is True
    # counters: whole milliseconds, added where the spans closed
    assert moved["compile_trace_ms"] == round(kids[0].wall_ms)
    assert moved["compile_lower_ms"] == round(kids[1].wall_ms)
    assert moved["compile_xla_ms"] == round(kids[2].wall_ms)
    assert "compile_cache_load_ms" not in moved
    assert "compile_recompile_ms" not in moved
    assert moved.get("compile_first_run_ms", 0) == round(materialize.wall_ms)
    # the ring's record is the span's
    assert record == {
        "t0_ns": compile_.t0, "program": dispatched, "cause": "first",
        "round": 0, "caps": "", "xla_cache": "off", "background": False,
        "trace_ms": round(kids[0].wall_ms, 3),
        "lower_ms": round(kids[1].wall_ms, 3),
        "xla_ms": round(kids[2].wall_ms, 3),
        "first_run_ms": round(materialize.wall_ms, 3),
        "wall_ms": round(compile_.wall_ms, 3), "error": ""}
    # /metrics does not move: compile_ms still observes the whole span
    after = tel.REGISTRY.snapshot()["histograms"]["compile_ms"]
    assert after["count"] == hist["count"] + 1
    assert after["sum"] - hist["sum"] == pytest.approx(compile_.wall_ms)


def _fake(event, seconds):
    tel._on_jax_duration(event, seconds, fun_name="f")


TRACE, LOWER, XLA = tel._COMPILE_PHASES
RETRIEVAL = tel._CACHE_RETRIEVAL


def test_a_jit_traced_inside_another_s_trace_is_not_counted_twice():
    """Every ``jnp`` function of a program's body reports its own trace,
    inside the program's; a helper program built while another traces
    reports all three phases.  Only what lies inside a same-named child
    goes."""
    with tel.trace_scope("q") as trace:
        with tel.compile_span(program="p", round=0, cause="first",
                              caps="") as compile_:
            time.sleep(0.05)
            _fake(TRACE, 0.001)           # jnp.where, say
            _fake(TRACE, 0.002)           # ... which held a broadcast
            _fake(LOWER, 0.0005)          # a helper program, lowered
            _fake(XLA, 0.0005)            # and compiled
            _fake(TRACE, 0.040)           # the program's own trace
            time.sleep(0.002)
            _fake(LOWER, 0.001)
            _fake(RETRIEVAL, 0.001)
            _fake(XLA, 0.0005)
            _fake("/jax/compilation_cache/compile_time_saved_sec", 9.0)
    names = [(k.name, k.attrs.get("xla_cache")) for k in compile_.children]
    assert names == [("compile_lower", None), ("compile_xla", "off"),
                     ("compile_trace", None), ("compile_lower", None),
                     ("compile_xla", "hit")]
    assert compile_.children[2].wall_ms == pytest.approx(40.0)
    report = trace.report
    assert report.phases["compile_trace"] == pytest.approx(40.0)
    (record,) = tel.compile_log()
    assert record["xla_cache"] == "hit"
    assert record["trace_ms"] == pytest.approx(40.0)


def test_the_listener_is_silent_outside_a_compile_span():
    before = tel.REGISTRY.counters()
    _fake(XLA, 1.0)                      # no trace at all
    with tel.trace_scope("q") as trace:
        _fake(XLA, 1.0)                  # a trace, no compile span
        with tel.span("bind"):
            _fake(TRACE, 1.0)
            _fake(RETRIEVAL, 1.0)
    assert [s.name for s in trace.root.walk()] == ["query", "bind"]
    assert not tel._tls.xla_cache_hit
    assert {k for k in _moved(before)} <= {"queries"}
    assert tel.compile_log() == []


_CHILD = """
import json, sys
import numpy as np, pandas as pd
from dask_sql_tpu import Context
from dask_sql_tpu.runtime import telemetry as tel
ctx = Context()
ctx.create_table("t", pd.DataFrame({"g": np.arange(500) % 7,
                                    "v": np.arange(500) * 0.5}))
ctx.sql("SELECT g, SUM(v) AS s FROM t WHERE v > 3 GROUP BY g",
        return_futures=False)
kids = [(s.name, s.attrs.get("xla_cache"))
        for s in ctx.last_report.root.walk() if s.name.startswith("compile_")]
print(json.dumps({"kids": kids, "log": tel.compile_log(),
                  "counters": {k: v for k, v in tel.REGISTRY.counters().items()
                               if k.startswith("compile_")}}))
"""


def test_xla_s_persistent_cache_misses_in_one_process_and_hits_in_the_next(
        tmp_path):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "DSQL_TIERED": "0",
           "JAX_ENABLE_COMPILATION_CACHE": "true",
           "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "xla"),
           "PYTHONPATH": ROOT}
    runs = []
    for _ in range(2):
        done = subprocess.run([sys.executable, "-c", _CHILD], env=env,
                              capture_output=True, text=True, timeout=300,
                              cwd=ROOT)
        assert done.returncode == 0, done.stderr[-2000:]
        runs.append(json.loads(done.stdout.strip().splitlines()[-1]))
    first, second = runs
    assert first["kids"] == [["compile_trace", None], ["compile_lower", None],
                             ["compile_xla", "miss"]]
    assert second["kids"][2] == ["compile_xla", "hit"]
    assert [r["xla_cache"] for r in first["log"]] == ["miss"]
    assert [r["xla_cache"] for r in second["log"]] == ["hit"]
    # a miss is compile_xla_ms, a hit compile_cache_load_ms, never both
    assert first["counters"]["compile_xla_ms"] > 0
    assert first["counters"]["compile_cache_load_ms"] == 0
    assert second["counters"]["compile_xla_ms"] == 0
    assert second["counters"]["compile_cache_load_ms"] \
        == round(second["log"][0]["xla_ms"])


def _overflowing(monkeypatch):
    """A group cap forced too small."""
    monkeypatch.setattr(caps, "DEFAULT_GROUP_CAP", 2)
    return _context(), QUERY.format(x=0.9), "agg0:"


def _tightening(monkeypatch):
    """A ``cmp*`` site (the TPU's strategy has them) that starts at a
    quarter of its input's rows and counts one in a hundred."""
    monkeypatch.setenv("DSQL_STRATEGY", "tpu")
    monkeypatch.delenv("DSQL_CAPS_FILE", raising=False)
    return _context(rows=1 << 17), QUERY.format(x=0.01), "cmp0:32768>4096"


def _refuted(monkeypatch):
    """An ``ord*`` hint learned for a build side in key order, met by a
    table of the same layout that is not
    (``tests/unit/test_ordered_probe.py``)."""
    monkeypatch.setenv("DSQL_ADAPTIVE", "1")
    monkeypatch.setattr(cm, "ORDERED_GATHERS_A_BUILD_ROW", 1 << 30)
    rng = np.random.default_rng(3)
    b = pd.DataFrame({"k": np.arange(100, 1100),
                      "w": np.round(rng.random(1000), 6)})
    ctx = Context()
    ctx.create_table("p", pd.DataFrame({"k": rng.integers(0, 1200, 4000)}))
    ctx.create_table("b", b)
    query = "SELECT p.k, b.w FROM p JOIN b ON p.k = b.k"
    ctx.sql(query, return_futures=False)
    (entry,) = programs._cache.values()
    caps._learned_caps_put(entry.key[0], dict(entry.caps))
    ctx.create_table("b", b.assign(k=rng.permutation(b["k"].to_numpy())))
    return ctx, query, "ord0r:"


@pytest.mark.parametrize("make,cause,counter", [
    (_overflowing, "cap_overflow", "recompiles_overflow"),
    (_tightening, "cap_tighten", "recompiles_tighten"),
    (_refuted, "hint_refuted", "recompiles_hint")])
def test_each_cause_of_a_recompile_is_produced_and_named(monkeypatch, make,
                                                         cause, counter):
    ctx, query, changed = make(monkeypatch)
    _, report, moved, records = _run(ctx, query)
    again = [r for r in records if r["cause"] != "first"]
    assert again and {r["cause"] for r in again} == {cause}
    # the refuted hint's first round ran a cached program: nothing to compile
    assert [r["cause"] for r in records if r["cause"] == "first"] \
        == ([] if cause == "hint_refuted" else ["first"])
    assert moved["recompiles"] == moved[counter] == len(again)
    assert sum(moved.get(c, 0) for c in tel.RECOMPILE_COUNTERS.values()) \
        == moved["recompiles"]
    # only the tags that changed, old > new
    assert all(r["caps"].startswith(changed) for r in again), again
    assert all(r["round"] >= 1 for r in again)
    spans = {s.t0: s for s in _spans(report, "compile")}
    assert [spans[r["t0_ns"]].attrs["cause"] for r in again] \
        == [cause] * len(again)
    # the price of the ladder: compile + first run of those rounds
    price = sum(round(r["wall_ms"]) + round(r["first_run_ms"])
                for r in again)
    assert moved.get("compile_recompile_ms", 0) == price
    assert all(r["first_run_ms"] is not None for r in records)


def test_an_overflow_and_a_tighten_in_one_round_is_an_overflow():
    from types import SimpleNamespace
    entry = SimpleNamespace(
        caps={}, meta={"agg_sites": [(1 << 20, False, "cmp0"),
                                     (1 << 20, False, "cmp1")],
                       "ngroup_caps": [1 << 18, 1 << 12]})
    for counts, reason in (([100, 5000], "cap_overflow"),
                           ([100, 4000], "cap_tighten")):
        with pytest.raises(caps._NeedsRecompile) as again:
            caps._check_flags(entry, np.array([0, 0] + counts))
        assert again.value.reason == reason


def test_a_background_compile_is_logged_and_counted(monkeypatch):
    monkeypatch.setenv("DSQL_TIERED", "1")
    tiering._tier_done.clear()
    ctx = _context()
    before = tel.REGISTRY.counters()
    got = ctx.sql(QUERY.format(x=0.3), return_futures=False)
    assert ctx.last_report.tier == "eager-compiling"
    assert _spans(ctx.last_report, "compile") == []
    give_up = time.monotonic() + 120
    while cm.inflight_background_compiles() and time.monotonic() < give_up:
        time.sleep(0.05)
    assert not cm.inflight_background_compiles()
    (record,) = tel.compile_log()
    assert record["background"] is True and record["cause"] == "first"
    assert record["first_run_ms"] is not None and record["error"] == ""
    moved = _moved(before)
    assert moved["compile_xla_ms"] == round(record["xla_ms"])
    assert moved["compile_trace_ms"] == round(record["trace_ms"])
    assert moved["background_compiles_done"] == 1
    # and the next arrival runs it, compiling nothing
    _, report, moved, records = _run(ctx, QUERY.format(x=0.31))
    assert report.tier == "compiled" and records == []
    assert not set(moved) & set(NEW_COUNTERS)
    assert len(got) > 0


def test_a_warm_request_adds_no_span_no_counter_and_no_record():
    ctx = _context()
    ctx.sql(QUERY.format(x=0.5), return_futures=False)
    _, report, moved, records = _run(ctx, QUERY.format(x=0.25))
    assert moved["hits"] == 1 and "compiles" not in moved
    assert [s.name for s in report.root.walk()] == [
        "query", "parse", "plan", "execute", "lookup", "lookup", "lookup",
        "bind", "dispatch", "materialize", "fetch"]
    (materialize,) = _spans(report, "materialize")
    assert "first_run" not in materialize.attrs
    assert not set(moved) & set(NEW_COUNTERS)
    assert not set(report.phases) & set(PHASES)
    assert records == []


def test_the_listener_is_registered_once_whatever_is_made_and_compiled():
    for x in (0.5, 0.6):
        ctx = _context()
        for groups in ("g", "g, x"):
            ctx.sql(f"SELECT {groups}, SUM(v) AS s FROM t WHERE x < {x} "
                    f"GROUP BY {groups}", return_futures=False)
    assert len(tel.compile_log()) >= 2
    listeners = monitoring.get_event_duration_listeners()
    assert listeners.count(tel._on_jax_duration) == 1
    # the engine hangs nothing else on JAX
    assert not [f for f in monitoring.get_event_listeners()
                + monitoring.get_event_time_span_listeners()
                if getattr(f, "__module__", "").startswith("dask_sql_tpu")]


COLUMNS = ["t0_ns", "program", "cause", "round", "caps", "xla_cache",
           "background", "trace_ms", "lower_ms", "xla_ms", "first_run_ms",
           "wall_ms", "error"]


def test_system_compiles_binds_at_zero_rows_and_serves_the_ring():
    ctx = _context()
    empty = ctx.sql("SELECT * FROM system.compiles", return_futures=False)
    assert list(empty.columns) == COLUMNS and len(empty) == 0
    again = ctx.sql("SELECT program, COUNT(*) AS n FROM system.compiles "
                    "WHERE cause <> 'first' GROUP BY program",
                    return_futures=False)
    assert len(again) == 0
    # (those two statements compiled programs of their own: the ring is the
    # process's)
    ctx.sql(QUERY.format(x=0.5), return_futures=False)
    log = tel.compile_log()
    served = ctx.sql("SELECT * FROM system.compiles", return_futures=False)
    assert len(served) == len(log) >= 1
    assert served["program"].tolist() == [r["program"] for r in log]
    assert served["cause"].tolist() == [r["cause"] for r in log]
    assert served["wall_ms"].tolist() == [r["wall_ms"] for r in log]
    assert served["background"].tolist() == [False] * len(log)
    # read-only snapshot: compile_log() hands out copies
    log[0]["cause"] = "edited"
    assert tel.compile_log()[0]["cause"] != "edited"


def test_the_ring_keeps_the_last_256():
    with tel.trace_scope("q"):
        for i in range(300):
            with tel.compile_span(program=f"p{i}", round=0, cause="first",
                                  caps=""):
                pass
    log = tel.compile_log()
    assert len(log) == 256
    assert [r["program"] for r in (log[0], log[-1])] == ["p44", "p299"]


def test_a_compile_that_raises_is_logged_with_its_error():
    with tel.trace_scope("q"):
        with pytest.raises(ValueError):
            with tel.compile_span(program="p", round=2, cause="cap_overflow",
                                  caps="agg0:2>64"):
                raise ValueError("no")
    (record,) = tel.compile_log()
    assert record["error"] == "ValueError" and record["first_run_ms"] is None
    assert (record["round"], record["cause"], record["caps"]) \
        == (2, "cap_overflow", "agg0:2>64")


def test_every_new_counter_is_a_stable_one():
    assert set(NEW_COUNTERS) <= set(tel.STABLE_COUNTERS)
    assert all(tel.REGISTRY.get(name) is not None for name in NEW_COUNTERS)
