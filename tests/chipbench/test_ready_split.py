"""The six per-layer metrics that itemize ``ready_s`` from inside (PR 38):
each reads the engine's counters of set-up (``run["setup"]["counters"]``,
one ``Meter.since_start()``), 0 for a counter that did not move, nothing on
an engine without the counter; the benchmark lists each for every cell but
``short``."""
import os

import pytest

from chipbench import run
from dask_sql_tpu.runtime import telemetry

HERE = os.path.dirname(os.path.abspath(run.__file__))
CELLS = ["tpch_sf1_embedded.power", "tpch_sf1_served.streams2",
         "tpch_sf1_joins.power", "tpch_sf1_served.dashboard",
         "tpch_sf10_embedded.power"]
#: metric -> (unit, the engine's counters it adds up)
METRICS = {
    "ready_trace_lower_s": ("s", ["compile_trace_ms", "compile_lower_ms"]),
    "ready_xla_compile_s": ("s", ["compile_xla_ms"]),
    "ready_cache_load_s": ("s", ["compile_cache_load_ms"]),
    "ready_first_run_s": ("s", ["compile_first_run_ms"]),
    "ready_recompile_s": ("s", ["compile_recompile_ms"]),
    "setup_recompiles": ("count", ["recompiles"]),
}
#: a first run's set-up as ``Meter.since_start()`` hands it over: the
#: counters that did not move are not there
SETUP = {"compiles": 9, "recompiles": 4, "recompiles_overflow": 3,
         "recompiles_tighten": 1, "compile_trace_ms": 2100,
         "compile_lower_ms": 900, "compile_xla_ms": 431250,
         "compile_first_run_ms": 17500, "compile_recompile_ms": 150400,
         "hits": 12, "xla_cache_misses": 9}
WANT = {"ready_trace_lower_s": 3.0, "ready_xla_compile_s": 431.25,
        "ready_cache_load_s": 0.0, "ready_first_run_s": 17.5,
        "ready_recompile_s": 150.4, "setup_recompiles": 4}


def canned(counters):
    return {"setup": {"counters": dict(counters), "ready_s": 500.0},
            "window": {"counters": {}}, "ready_records": [], "trace": None}


@pytest.mark.parametrize("name", list(METRICS))
def test_a_metric_reads_the_counters_of_set_up(name):
    module = run.load_by_path("metrics", name)
    assert module.read(canned(SETUP)) == pytest.approx(WANT[name])
    # a warm run: everything was read, nothing compiled again
    warm = {"compiles": 5, "compile_cache_load_ms": 20400, "hits": 12}
    assert module.read(canned(warm)) == (
        20.4 if name == "ready_cache_load_s" else 0)
    assert module.read(canned({})) == 0


@pytest.mark.parametrize("name", list(METRICS))
def test_an_engine_without_the_counter_leaves_nothing_to_read(name,
                                                             monkeypatch):
    """The parent of PR 38, under this benchmark: no key, and not a stable
    counter either.  The reader returns nothing and does not raise, and
    ``read_metrics`` leaves the metric out of the line."""
    _, counters = METRICS[name]
    monkeypatch.setattr(telemetry, "STABLE_COUNTERS", tuple(
        c for c in telemetry.STABLE_COUNTERS if c not in counters))
    module = run.load_by_path("metrics", name)
    assert module.read(canned({"compiles": 9, "hits": 3})) is None
    entry = {"name": name, "unit": METRICS[name][0]}
    assert run.read_metrics({name: (entry, module)}, canned({})) == {}


def test_the_harness_keeps_every_counter_the_metrics_read():
    """``Meter`` keeps the counters whose prefix ``run.py`` lists: each
    ``ready`` line's ``counters`` then shows the split a shape."""
    for _, counters in METRICS.values():
        for counter in counters:
            assert counter.startswith(run._COUNTER_PREFIXES), counter
            assert counter in telemetry.STABLE_COUNTERS
    for counter in telemetry.RECOMPILE_COUNTERS.values():
        assert counter.startswith(run._COUNTER_PREFIXES)


def test_the_benchmark_lists_each_for_every_cell_but_short(bench):
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name, (unit, _) in METRICS.items():
        entry = by_name[name]
        assert os.path.isfile(os.path.join(HERE, "metrics", name + ".py"))
        assert entry == {
            "name": name, "unit": unit, "better": "lower",
            "source": "program_counter", "layer": "executor",
            "moves": "setup_s", "workloads": CELLS}
    # the six, in the table's order, behind everything that was there
    assert [m["name"] for m in bench["per_layer"][-6:]] == list(METRICS)
    cells = [w["name"] for w in bench["workloads"]]
    assert set(CELLS) == set(cells) - {"tpch_sf1_embedded.short"}
    for cell in CELLS:
        assert set(METRICS) <= set(run.load_cell(cell)["per_layer"])
    assert not set(METRICS) & set(
        run.load_cell("tpch_sf1_embedded.short")["per_layer"])


def test_a_meter_s_delta_feeds_them(monkeypatch):
    """End to end on this engine, no chip: what a ``Meter`` takes round a
    compile span is what the readers read."""
    meter = run.Meter()
    with telemetry.trace_scope("set-up"):
        with telemetry.compile_span(program="p", round=1,
                                    cause="cap_overflow", caps="agg0:2>64"):
            telemetry._on_jax_duration(
                "/jax/core/compile/backend_compile_duration", 0.0)
    telemetry.inc("compile_xla_ms", 1500)
    setup = {"counters": meter.since_start()}
    made = {"setup": setup}
    assert run.load_by_path("metrics", "ready_xla_compile_s").read(made) \
        == pytest.approx(1.5)
    assert run.load_by_path("metrics", "ready_cache_load_s").read(made) == 0
    assert run.load_by_path("metrics", "setup_recompiles").read(made) == 0
