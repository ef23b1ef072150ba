"""The join-heavy deployment (``tpch_sf1_joins``) and the dashboard cell:
the three shapes' substitution, bytes and references, the engine against
them on both strategies and both formulations, the stage partition, the
cells rehearsed whole, the control, and the new metrics' readers."""
import datetime
import json
import os
import re
import shutil

import pytest

from chipbench import compare, control, roofline, run
from chipbench.reduce import spans, stages

SHAPES = ["q3", "q5", "q10"]
JOINS = "tpch_sf1_joins.power"
DASHBOARD = "tpch_sf1_served.dashboard"
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
#: eight Q6 requests of the short cell (my chip run, PR 25)
RECORDED = os.path.join(DATA, "short_v5e_spans.xplane.pb")
PREFIX = {"lineitem": "l_", "orders": "o_", "customer": "c_",
          "supplier": "s_", "nation": "n_", "region": "r_"}


def _shape(name):
    return run.load_by_path("shapes", name)


def _all_params(shape):
    return [shape.params_at(i) for i in range(shape.SPACE)]


def _day(text):
    return datetime.date.fromisoformat(text)


def result_line(capsys) -> dict:
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


# --- the shapes -------------------------------------------------------------

@pytest.mark.parametrize("name", SHAPES)
def test_every_parameter_set_is_a_text_of_its_own(name):
    shape = _shape(name)
    texts = {shape.sql(p) for p in _all_params(shape)}
    assert len(texts) == shape.SPACE
    assert shape.NAME == name


def test_q3_substitution_range():
    shape = _shape("q3")
    days = [_day(p["date"]) for p in _all_params(shape)]
    # cl.2.4.3.3 has the 31 days of March 1995; here, a year round them
    assert min(days) <= datetime.date(1995, 3, 1)
    assert max(days) >= datetime.date(1995, 3, 31)
    assert (max(days) - min(days)).days >= 365
    assert shape.params_at(shape.FIRST) == {"date": "1995-03-15"}
    assert "'BUILDING'" in shape.SQL


def test_q5_substitution_range():
    shape = _shape("q5")
    for p in _all_params(shape):
        start, end = _day(p["date_from"]), _day(p["date_to"])
        assert datetime.date(1993, 1, 1) <= start <= datetime.date(1997, 1, 1)
        assert 365 <= (end - start).days <= 366
    assert shape.params_at(shape.FIRST) == {"date_from": "1994-01-01",
                                            "date_to": "1995-01-01"}
    assert "'ASIA'" in shape.SQL


def test_q10_substitution_range():
    shape = _shape("q10")
    for p in _all_params(shape):
        start, end = _day(p["date_from"]), _day(p["date_to"])
        assert datetime.date(1993, 2, 1) <= start <= datetime.date(1995, 1, 1)
        assert 89 <= (end - start).days <= 92
        assert (end.year * 12 + end.month) - (start.year * 12
                                              + start.month) == 3
    assert shape.params_at(shape.FIRST) == {"date_from": "1993-10-01",
                                            "date_to": "1994-01-01"}


@pytest.mark.parametrize("name, unique_key", [
    ("q3", "l_orderkey"), ("q5", "n_name"), ("q10", "c_custkey")])
def test_each_order_by_ends_in_a_unique_key(name, unique_key):
    order_by = _shape(name).SQL.split("ORDER BY")[1].split("LIMIT")[0]
    assert order_by.split(",")[-1].split()[0] == unique_key


@pytest.mark.parametrize("name", SHAPES)
def test_scan_columns_are_the_columns_the_text_names(name):
    shape = _shape(name)
    named = set(re.findall(r"\b[locsnr]_[a-z]+\b", shape.SQL))
    listed = {c for columns in shape.SCAN_COLUMNS.values() for c in columns}
    assert listed == named
    for table, columns in shape.SCAN_COLUMNS.items():
        assert all(c.startswith(PREFIX[table]) for c in columns)


@pytest.mark.parametrize("name", SHAPES)
def test_scan_bytes_are_rows_times_itemsize(name, small):
    frames, context = small
    shape = _shape(name)
    catalog = roofline.catalog_columns(context)
    want = 0
    entries = context.schema[context.schema_name].tables
    for table, columns in shape.SCAN_COLUMNS.items():
        held = dict(zip(entries[table].table.names,
                        entries[table].table.columns))
        for column in columns:
            data = held[column].data
            assert data.shape[0] == len(frames[table])
            want += len(frames[table]) * data.dtype.itemsize
    assert roofline.scan_bytes(shape.SCAN_COLUMNS, catalog) == want > 0


def _against_the_reference(context, frames, name, index):
    shape = _shape(name)
    params = shape.params_at(index % shape.SPACE)
    record = run.Embedded(context).execute(
        {"shape": name, "params": params, "sql": shape.sql(params)}, 60.0)
    assert record["error"] is None, record["error"]
    want = shape.reference(frames, **params)
    assert len(want) > 0
    gap, mismatched = compare.compare_frames(record["frame"], want)
    assert mismatched == 0
    assert gap <= compare.LIMITS["max_rel_gap"]
    return record


@pytest.mark.parametrize("name", SHAPES)
@pytest.mark.parametrize("index", [0, -1])
def test_reference_agrees_with_the_engine_embedded(name, index, small):
    frames, context = small
    record = _against_the_reference(context, frames, name, index)
    assert {"parse", "plan", "fetch"} <= set(record["phases"])


@pytest.mark.parametrize("name", SHAPES)
@pytest.mark.parametrize("programs", ["whole", "staged"])
@pytest.mark.parametrize("formulation", ["as_at_sf1", "as_at_sf001"])
def test_the_path_the_chip_runs_agrees_with_the_reference(
        name, programs, formulation, small, monkeypatch):
    """The TPU strategy forced on the CPU.  At SF0.01 every operator is
    under ``SORT_ROWS_MAX`` rows and keeps its sort formulation; with the
    limits lowered the joins, the group-by and the ORDER BY take the
    formulations they take at SF1 on the chip."""
    from dask_sql_tpu import Context
    from dask_sql_tpu.ops import pallas_kernels
    from dask_sql_tpu.physical import compiled

    monkeypatch.delenv("DSQL_STRATEGY", raising=False)
    monkeypatch.setattr(pallas_kernels, "_on_tpu", lambda: True)
    if formulation == "as_at_sf1":
        monkeypatch.setattr(compiled, "SORT_ROWS_MAX", 256)
        monkeypatch.setattr(compiled, "LEXSORT_ROWS_MAX", 8)
    if programs == "staged":
        monkeypatch.setenv("DSQL_STAGE_HEAVY", "1")
    frames, _ = small
    # the program cache keys on plan and layout, not on the limits above:
    # what another case traced is not what this one is here to run
    compiled._cache.clear()
    compiled._learned_caps.clear()
    context = Context()
    for table, frame in frames.items():
        context.create_table(table, frame)
    before = dict(compiled.stats)
    for index in (_shape(name).FIRST, -1):
        _against_the_reference(context, frames, name, index)
    delta = {k: compiled.stats[k] - before.get(k, 0)
             for k in ("compiles", "hits", "fallbacks", "unsupported",
                       "stage_graphs")}
    assert delta["fallbacks"] == 0 and delta["unsupported"] == 0
    assert delta["compiles"] >= 1 and delta["hits"] >= 1
    assert (delta["stage_graphs"] > 0) == (programs == "staged")


@pytest.mark.parametrize("name, heavy, stages_at_1", [
    ("q3", 3, 3), ("q5", 6, 6), ("q10", 4, 4)])
def test_the_stage_partition_is_what_perf_md_says(name, heavy, stages_at_1,
                                                  small):
    """One program a shape under the shipped budget of six heavy nodes (a
    node's weight counts the node, not its rows, so the partition is the
    same at SF1); a program a heavy node under a budget of one."""
    from dask_sql_tpu.physical import compiled, stages as stage_graphs
    from dask_sql_tpu.sql.parser import parse_sql

    _, context = small
    shape = _shape(name)
    text = shape.sql(shape.params_at(shape.FIRST))
    plan = context._get_plan(parse_sql(text)[0].query, text)
    assert stage_graphs.heavy_count(plan) == heavy
    assert heavy <= stage_graphs.DEFAULT_STAGE_HEAVY
    whole = compiled._partition_plan(plan, stage_graphs.stage_budget(),
                                     context)
    assert len(whole.stages) == 1 and whole.root.heavy == heavy
    cut = compiled._partition_plan(plan, 1, context)
    assert len(cut.stages) == stages_at_1
    assert all(stage.heavy == 1 for stage in cut.stages)


def _imports(path):
    import ast

    with open(path) as f:
        tree = ast.parse(f.read())
    imported = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            imported |= {alias.name for alias in node.names}
        elif isinstance(node, ast.ImportFrom):
            imported |= {f"{node.module}.{alias.name}" for alias in node.names}
    return imported


@pytest.mark.parametrize("name", SHAPES)
def test_a_reference_needs_pandas_and_nothing_of_the_engine(name):
    """The shape files hold the plain references: they import pandas, the
    standard library and the run's own clock (``chipbench/ready_limit.py``,
    which imports nothing of the engine either), so ``chipbench.control``
    and the comparison load them whatever engine is under test."""
    here = os.path.dirname(run.__file__)
    assert _imports(os.path.join(here, "shapes", f"{name}.py")) <= {
        "datetime", "pandas", "chipbench.ready_limit"}
    assert _imports(os.path.join(here, "ready_limit.py")) <= {
        "__future__.annotations", "json", "os", "sys", "threading",
        "chipbench.traffic"}


# --- a run that set-up got no program for ends at that shape -----------------

@pytest.fixture
def clocked(monkeypatch):
    """``ready_limit`` as in a process started as ``chipbench/run.py``, with
    clocks the test strikes itself and the exit recorded, not taken."""
    from chipbench import ready_limit

    class Clock:
        made = []

        def __init__(self, interval, function, args):
            self.interval, self.function, self.args = interval, function, args
            self.started = self.cancelled = False
            Clock.made.append(self)

        def start(self):
            self.started = True

        def cancel(self):
            self.cancelled = True

        def strike(self):
            self.function(*self.args)

    class Left(Exception):
        pass

    def leave(code):
        raise Left(code)

    Clock.as_shipped = staticmethod(ready_limit._under_the_harness)
    monkeypatch.setattr(ready_limit, "_under_the_harness", lambda: True)
    monkeypatch.setattr(ready_limit.threading, "Timer", Clock)
    monkeypatch.setattr(ready_limit.os, "_exit", leave)
    monkeypatch.setattr(ready_limit, "_texts", {})
    monkeypatch.setattr(ready_limit, "_clocks", {})
    Clock.Left = Left
    return Clock


def test_a_shapes_first_text_starts_the_deadline_of_its_mix(clocked):
    with open(os.path.join(os.path.dirname(run.__file__), "traffic",
                           "joins.json")) as f:
        deadline = json.load(f)["ready_deadline_s"]
    q3 = _shape("q3")
    q3.sql(q3.params_at(q3.FIRST))
    (clock,) = clocked.made
    assert clock.started and clock.daemon and not clock.cancelled
    assert clock.interval == deadline - 1.0
    assert clock.args == ("q3", float(deadline))


def test_the_second_text_in_time_stops_the_clock(clocked):
    """Each shape has its own, and the window's texts start none."""
    q3, q5 = _shape("q3"), _shape("q5")
    q3.sql(q3.params_at(1))
    q3.sql(q3.params_at(2))
    q5.sql(q5.params_at(1))
    assert [c.cancelled for c in clocked.made] == [True, False]
    for i in range(3, 9):
        q3.sql(q3.params_at(i))
        q5.sql(q5.params_at(i))
    assert [c.cancelled for c in clocked.made] == [True, True]


def test_the_deadline_ends_the_run_with_exit_code_1(clocked, capsys):
    q10 = _shape("q10")
    q10.sql(q10.params_at(q10.FIRST))
    with pytest.raises(clocked.Left) as left:
        clocked.made[0].strike()
    assert left.value.args == (1,)
    out, err = capsys.readouterr()
    assert json.loads(out.strip().splitlines()[-1]) == {
        "phase": "refused", "shape": "q10", "ready_deadline_s": 240.0}
    assert "q10 was not served by a compiled program within 240 s" in err


def test_a_mix_that_warms_with_one_text_has_no_clock(clocked, monkeypatch):
    from chipbench import ready_limit

    monkeypatch.setattr(ready_limit.traffic, "load_mix", lambda name: {
        "ready_deadline_s": 240, "warm_extra": 0})
    q5 = _shape("q5")
    q5.sql(q5.params_at(0))
    assert clocked.made == []


def test_outside_the_harness_no_text_is_timed(clocked, monkeypatch):
    from chipbench import ready_limit

    monkeypatch.setattr(ready_limit, "_under_the_harness", clocked.as_shipped)
    q10 = _shape("q10")
    q10.sql(q10.params_at(0))
    assert clocked.made == [] and ready_limit._texts == {}


@pytest.mark.parametrize("started_as", [["chipbench/run.py"],
                                        ["-m", "chipbench.run"]])
def test_a_run_past_its_deadline_ends_by_itself(started_as, tmp_path):
    """The harness itself, either way ``run.py``'s docstring starts it, rehearsed in a process of its own with the
    deadline at 1 s, which is none: the run ends inside Q3's first arrival
    instead of going on to a line that says ``correct: false``."""
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(run.__file__))
    copy = tmp_path / "repo"
    shutil.copytree(os.path.join(root, "chipbench"), copy / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(root, "BENCHMARK.json"), copy)
    mix = copy / "chipbench" / "traffic" / "joins.json"
    spec = json.loads(mix.read_text())
    spec["ready_deadline_s"] = 1
    mix.write_text(json.dumps(spec))
    done = subprocess.run(
        [sys.executable, *started_as, "--workload", JOINS, "--seed",
         "5", "--seconds", "1", "--trace", "0", "--allow-cpu"], cwd=copy,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": root,
             "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")},
        capture_output=True, text=True, timeout=120)
    assert done.returncode == 1
    last = json.loads(done.stdout.strip().splitlines()[-1])
    assert last == {"phase": "refused", "shape": "q3",
                    "ready_deadline_s": 1.0}
    assert "the run ends here" in done.stderr


def test_the_joins_cell_lists_no_metric_that_moves_p90(bench):
    """``join_device_ms`` and ``compact_device_ms`` move ``query_p90_ms``,
    which this cell does not report: they wait for a benchmark PR to widen
    what they move, and ``python3 -m chipbench.reduce.spans`` prints both
    from any trace of the cell meanwhile."""
    for metric in bench["per_layer"]:
        if JOINS in metric.get("workloads", ()):
            assert metric["moves"] in ("query_geomean_ms", "setup_s")
    assert "join_device_ms" not in run.load_cell(JOINS)["per_layer"]


# --- the cells --------------------------------------------------------------

def test_the_joins_cell_resolves_to_its_files(bench):
    loaded = run.load_cell(JOINS)
    assert loaded["config"]["name"] == "tpch_sf1_joins"
    assert loaded["config"]["environment"] == {}
    assert loaded["config"]["surface"] == "embedded"
    assert list(loaded["mix"]["shapes"]) == SHAPES
    assert loaded["mix"]["ready_deadline_s"] <= 900
    embedded = run.load_cell("tpch_sf1_embedded.power")["config"]
    for key in ("schema", "generator", "tables", "scale_factor",
                "rehearsal_scale_factor", "guarantees", "chips", "layout"):
        assert loaded["config"][key] == embedded[key]
    # a window of these shapes completes fewer than 100 requests: no p90
    assert set(loaded["end_to_end"]) == {"query_geomean_ms", "setup_s"}
    assert {"stages_per_query", "stage_handoff_ms",
            "groupby_sorted_device_ms", "q3_scan_roofline", "q5_scan_roofline",
            "q10_scan_roofline"} <= set(loaded["per_layer"])


def test_the_dashboard_cell_resolves_to_its_files(bench):
    loaded = run.load_cell(DASHBOARD)
    assert loaded["config"]["name"] == "tpch_sf1_served"
    mix, control_mix = loaded["mix"], run.load_cell(
        "tpch_sf1_served.streams2")["mix"]
    assert mix["repeat_share"] == 0.8 and mix["repeat_texts"] == 8
    assert mix["max_per_client_per_s"] == 60
    for key in set(control_mix) - {"repeat_share", "repeat_texts",
                                   "max_per_client_per_s"}:
        assert mix[key] == control_mix[key], key
    assert set(loaded["end_to_end"]) == {"query_geomean_ms", "setup_s"}
    assert {"result_cache_hit_share", "result_cache_hit_ms",
            "parse_plan_ms"} <= set(loaded["per_layer"])


def test_the_joins_cell_rehearses(engine_as_shipped, capsys, bench):
    assert run.main(["--workload", JOINS, "--seed", "2147483659",
                     "--seconds", "2", "--trace", "0", "--allow-cpu"]) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    result = json.loads(lines[-1])
    assert result["correct"] is True and result["failed"] == 0
    assert result["rehearsal"] is True
    assert set(result["metrics"]) == {"query_geomean_ms", "setup_s"}
    ready = [json.loads(l) for l in lines if '"phase": "ready"' in l]
    assert [r["shape"] for r in ready] == SHAPES
    for r in ready:
        assert r["tiers"][0].startswith("eager")
        assert r["tiers"][-3:] == ["compiled"] * 3
    window = next(json.loads(l) for l in lines if '"phase": "window"' in l)
    assert window["texts_sent_twice"] == 0
    assert all(n >= 1 for n in window["by_shape"].values())


def test_the_dashboard_cell_rehearses(engine_as_shipped, capsys, bench):
    assert run.main(["--workload", DASHBOARD, "--seed", "2147483693",
                     "--seconds", "1", "--trace", "1", "--allow-cpu"]) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    result = json.loads(lines[-1])
    assert result["correct"] is True and result["failed"] == 0
    host_side = {m["name"] for m in bench["per_layer"]
                 if DASHBOARD in m.get("workloads", [DASHBOARD])
                 and m["source"] != "device_trace"}
    assert set(result["metrics"]) == host_side
    assert {"result_cache_hit_share", "result_cache_hit_ms"} <= host_side
    # four requests in five re-issue one of eight texts; the first of each
    # text is a miss
    assert result["metrics"]["result_cache_hit_share"]["value"] > 40
    assert result["metrics"]["result_cache_hit_ms"]["value"] > 0
    window = next(json.loads(l) for l in lines if '"phase": "window"' in l)
    assert window["texts_sent_twice"] > 0


# --- the comparison ---------------------------------------------------------

def test_the_float32_control_fails_the_joins_mix(capsys):
    assert control.main(["--mix", "joins", "--scale", "0.01", "--seeds",
                         "21", "22", "--per-shape", "4"]) == 0
    lines = [json.loads(l) for l in
             capsys.readouterr().out.strip().splitlines()]
    assert {l["shape"] for l in lines} == set(SHAPES)
    for seed in (21, 22):
        # a run compares answers of every shape and reads the widest gap
        widest = max(l["min_gap"] for l in lines if l["seed"] == seed)
        assert widest > compare.LIMITS["max_rel_gap"]
        assert not compare.verdict(widest, 0, 0)[0]


@pytest.mark.parametrize("column", ["c_name", "c_address", "c_phone",
                                    "n_name", "c_comment"])
def test_an_answer_altered_in_one_string_cell_is_not_correct(column, small):
    frames, _ = small
    shape = _shape("q10")
    want = shape.reference(frames, **shape.params_at(shape.FIRST))
    assert compare.compare_frames(want.copy(), want) == (0.0, 0)
    got = want.copy()
    got.loc[len(got) // 2, column] = str(got.loc[len(got) // 2, column]) + "x"
    gap, mismatched = compare.compare_frames(got, want)
    assert (gap, mismatched) == (0.0, 1)
    assert not compare.verdict(gap, mismatched, 0)[0]


# --- the new metrics' readers ------------------------------------------------

def _traced():
    return {"surface": "embedded", "trace": {"busy_s": 1.0},
            "window": {"records": [], "counters": {}}}


@pytest.fixture
def recorded(tmp_path, monkeypatch):
    where = tmp_path / ".chipbench_trace" / "plugins" / "profile" / "2026"
    where.mkdir(parents=True)
    shutil.copy(RECORDED, where / "host.xplane.pb")
    monkeypatch.setattr(spans, "_ROOT", str(tmp_path))
    return str(where / "host.xplane.pb")


TRACE_METRICS = ["stages_per_query", "stage_handoff_ms",
                 "groupby_sorted_device_ms",
                 "q3_scan_roofline", "q5_scan_roofline", "q10_scan_roofline"]


@pytest.mark.parametrize("name", TRACE_METRICS)
def test_a_trace_metric_is_none_without_a_trace(name, tmp_path, monkeypatch):
    metric = run.load_by_path("metrics", name)
    assert metric.read({"surface": "embedded", "trace": None,
                        "window": {"records": [], "counters": {}}}) is None
    if "roofline" not in name:
        # a traced run whose trace directory holds no file
        monkeypatch.setattr(spans, "_ROOT", str(tmp_path))
        assert metric.read(_traced()) is None


def test_a_whole_plan_program_has_no_stages(recorded):
    run_ = _traced()
    requests = spans.of_run(run_)["requests"]
    assert len(requests) == 8
    assert stages.stages_per_request(recorded, requests) == [0] * 8
    assert run.load_by_path("metrics", "stages_per_query").read(run_) == 0
    # Q6 runs no dynamic-domain group-by: nothing to read
    assert run.load_by_path(
        "metrics", "groupby_sorted_device_ms").read(run_) is None


def test_the_hand_over_is_the_idle_time_between_first_and_last_op(recorded):
    run_ = _traced()
    requests = spans.of_run(run_)["requests"]
    values = [stages.handoff_ns(r) for r in requests]
    for r, v in zip(requests, values):
        busy = sum(r["device_ns_by_scope"].values())
        extent = r["end_ns"] - r["start_ns"]
        assert 0 <= v <= extent - busy
        assert v == pytest.approx(
            extent - r["idle_pre_ns"] - r["idle_post_ns"] - busy, abs=1e-6)
    read = run.load_by_path("metrics", "stage_handoff_ms").read(run_)
    # six programs a Q6 request (one per literal): the gaps between them
    assert 0.5 < read < 5.0
    assert stages.handoff_ns({"idle_pre_ns": None}) is None


@pytest.mark.parametrize("starts, want", [
    ([], [0, 0]), ([5.0, 15.0, 16.0, 30.0], [1, 2]), ([10.0, 20.0], [0, 1])])
def test_stages_are_counted_inside_their_request(starts, want, monkeypatch):
    monkeypatch.setattr(stages, "_stage_starts",
                        lambda path, mtime: tuple(starts))
    requests = [{"start_ns": 0.0, "end_ns": 10.0},
                {"start_ns": 10.0, "end_ns": 20.0}]
    assert stages.stages_per_request(__file__, requests) == want


@pytest.mark.parametrize("scope_ns, want", [
    ([], None), ([30e6], 30.0), ([300e6, 20e6, 100e6], 100.0)])
def test_groupby_sorted_is_the_median_over_the_requests_that_ran_it(
        scope_ns, want):
    metric = run.load_by_path("metrics", "groupby_sorted_device_ms")
    requests = [{"device_ns_by_scope": {"dsql.join_probe": 40e6,
                                        "dsql.groupby_sorted": ns}}
                for ns in scope_ns]
    requests.append({"device_ns_by_scope": {"dsql.groupby_limbs": 90e6}})
    assert metric.median_ms(requests) == want


@pytest.mark.parametrize("name", SHAPES)
def test_a_scan_roofline_is_least_time_over_busy_time(name):
    metric = run.load_by_path("metrics", f"{name}_scan_roofline")
    run_ = {"trace": {"median_busy_s_by_shape": {name: 0.5}},
            "scan_bytes": {name: 819e6},
            "peaks": {"hbm_bytes_per_s": 819e9}}
    assert metric.read(run_) == pytest.approx(0.2)
    run_["trace"]["median_busy_s_by_shape"] = {"q6": 0.5}
    assert metric.read(run_) is None


@pytest.mark.parametrize("counters, want", [
    ({}, None), ({"result_cache_misses": 5}, 0.0),
    ({"result_cache_hits": 8, "result_cache_misses": 2}, 80.0),
    ({"result_cache_hits": 3, "result_cache_stores": 9}, 100.0)])
def test_result_cache_hit_share(counters, want):
    metric = run.load_by_path("metrics", "result_cache_hit_share")
    assert metric.read({"window": {"counters": counters}}) == want


def test_result_cache_hit_ms_is_the_replays_median():
    metric = run.load_by_path("metrics", "result_cache_hit_ms")
    records = [{"cache_hit": True, "phases": {"result_cache": ms}}
               for ms in (0.2, 0.4, 0.9)]
    records += [{"cache_hit": False, "phases": {"result_cache": 7.0}},
                {"cache_hit": True, "phases": {}}]
    assert metric.read({"window": {"records": records}}) == 0.4
    assert metric.read({"window": {"records": records[3:]}}) is None


def test_the_new_entries_name_their_cells(bench):
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in TRACE_METRICS:
        assert by_name[name]["workloads"] == [JOINS]
        assert by_name[name]["moves"] == "query_geomean_ms"
        assert by_name[name]["source"] == "device_trace"
    for name in ("result_cache_hit_share", "result_cache_hit_ms"):
        assert by_name[name]["workloads"] == [DASHBOARD]
        assert by_name[name]["moves"] == "query_geomean_ms"
    cells = [w["name"] for w in bench["workloads"]]
    assert cells[-2:] == [JOINS, DASHBOARD] and len(cells) <= 24
    assert all(w["chips"] == 1 for w in bench["workloads"])
