"""The nested-subquery deployment (``tpch_sf1_subqueries``, cell
``tpch_sf1_subqueries.power``): the three shapes' substitution, bytes and
references (each against sqlite, a third implementation), the engine
against them, the clock that ends a run whose programs new parameters do
not get, the cell rehearsed whole, the control, and the new metrics'
readers."""
import datetime
import json
import os
import re
import sqlite3

import pandas as pd
import pytest

from chipbench import compare, control, roofline, run
from chipbench.reduce import spans

SHAPES = ["q4", "q15", "q18"]
CELL = "tpch_sf1_subqueries.power"
PREFIX = {"lineitem": "l_", "orders": "o_", "customer": "c_",
          "supplier": "s_", "nation": "n_", "part": "p_", "partsupp": "ps_"}
NEW_METRICS = ["q4_scan_roofline", "q15_scan_roofline", "q18_scan_roofline",
               "semi_join_device_ms", "subquery_compiles_in_window"]


def _shape(name):
    return run.load_by_path("shapes", name)


def _all_params(shape):
    return [shape.params_at(i) for i in range(shape.SPACE)]


def _day(text):
    return datetime.date.fromisoformat(text)


# --- the shapes -------------------------------------------------------------

@pytest.mark.parametrize("name", SHAPES)
def test_every_parameter_set_is_a_text_of_its_own(name):
    shape = _shape(name)
    texts = {shape.sql(p) for p in _all_params(shape)}
    assert len(texts) == shape.SPACE >= 400
    assert shape.NAME == name


@pytest.mark.parametrize("name, first", [
    ("q4", "1993-07-01"), ("q15", "1996-01-01")])
def test_a_quarter_from_any_day_of_the_specs_months(name, first):
    shape = _shape(name)
    for p in _all_params(shape):
        start, end = _day(p["date_from"]), _day(p["date_to"])
        # cl.2.4.4.3 / cl.2.4.15.3: the first of a month, 1993-01..1997-10
        assert datetime.date(1993, 1, 1) <= start <= datetime.date(1997, 10, 1)
        assert 89 <= (end - start).days <= 92
        assert (end.year * 12 + end.month) - (start.year * 12
                                              + start.month) == 3
    assert shape.params_at(shape.FIRST)["date_from"] == first


def test_q18_substitution_range():
    shape = _shape("q18")
    values = [float(p["quantity"]) for p in _all_params(shape)]
    # cl.2.4.18.3 has 312..315; here, steps of 0.05 round them
    assert min(values) == 300.0 and max(values) == 319.95
    assert {312.0, 313.0, 314.0, 315.0} <= set(values)
    assert values == sorted(values) and len(set(values)) == shape.SPACE
    assert shape.params_at(shape.FIRST) == {"quantity": "300.00"}


def test_q20_is_no_shape_of_the_mix_on_this_generators_data(small):
    """``tpch_gen`` draws ``p_name`` from five names: one part in five has a
    colour, every supplier holds enough of some such part in every year, and
    Q20's answer would be its nation's suppliers whatever its date, colour
    or SUM.  A comparison that cannot fail gates nothing: the mix leaves the
    shape out until the generator has dbgen's 92 colours."""
    frames, _ = small
    assert frames["part"]["p_name"].nunique() == 5
    assert "q20" not in _mix()["shapes"]
    assert not os.path.exists(os.path.join(
        os.path.dirname(run.__file__), "shapes", "q20.py"))


@pytest.mark.parametrize("name, unique_key", [
    ("q4", "o_orderpriority"), ("q15", "s_suppkey"), ("q18", "o_orderkey")])
def test_each_order_by_ends_in_a_unique_key(name, unique_key, small):
    frames, _ = small
    shape = _shape(name)
    order_by = shape.SQL.split("ORDER BY")[-1].split("LIMIT")[0]
    assert order_by.split(",")[-1].split()[0] == unique_key
    answer = shape.reference(frames, **shape.params_at(shape.FIRST))
    assert answer[unique_key].is_unique and len(answer) > 0


@pytest.mark.parametrize("name", SHAPES)
def test_scan_columns_are_the_columns_the_text_names_once(name):
    shape = _shape(name)
    named = set(re.findall(r"\b(?:ps|[locsnp])_[a-z]+\b", shape.SQL))
    listed = [c for columns in shape.SCAN_COLUMNS.values() for c in columns]
    assert set(listed) == named and len(listed) == len(named)
    for table, columns in shape.SCAN_COLUMNS.items():
        assert all(c.startswith(PREFIX[table]) for c in columns)


@pytest.mark.parametrize("name", SHAPES)
def test_scan_bytes_are_rows_times_itemsize(name, small):
    frames, context = small
    shape = _shape(name)
    catalog = roofline.catalog_columns(context)
    want = sum(len(frames[table]) * catalog[table][column][1]
               for table, columns in shape.SCAN_COLUMNS.items()
               for column in columns)
    assert roofline.scan_bytes(shape.SCAN_COLUMNS, catalog) == want > 0


# --- the references: against sqlite, then the engine against them ------------

@pytest.fixture(scope="module")
def sqlite_tpch(small):
    frames, _ = small
    conn = sqlite3.connect(":memory:")
    for name, frame in frames.items():
        frame = frame.copy()
        for column in frame.columns:
            if pd.api.types.is_datetime64_any_dtype(frame[column]):
                frame[column] = frame[column].dt.strftime("%Y-%m-%d")
        frame.to_sql(name, conn, index=False)
    yield conn
    conn.close()


@pytest.mark.parametrize("name", SHAPES)
@pytest.mark.parametrize("index", ["first", 0, -1])
def test_the_reference_agrees_with_sqlite(name, index, small, sqlite_tpch):
    frames, _ = small
    shape = _shape(name)
    i = shape.FIRST if index == "first" else index % shape.SPACE
    params = shape.params_at(i)
    text = re.sub(r"DATE '([0-9-]+)'", r"'\1'", shape.SQL.format(**params))
    got = pd.read_sql(text, sqlite_tpch)
    want = shape.reference(frames, **params)
    for column in want.columns:
        if pd.api.types.is_datetime64_any_dtype(want[column]):
            got[column] = pd.to_datetime(got[column])
    gap, mismatched = compare.compare_frames(got, want)
    assert mismatched == 0 and gap <= 1e-12
    # a hundredth of SF1: no order passes Q18's quantities from 304 on
    assert len(want) > 0 or (name, index) == ("q18", -1)


def _against_the_reference(context, frames, name, index):
    shape = _shape(name)
    params = shape.params_at(index % shape.SPACE)
    record = run.Embedded(context).execute(
        {"shape": name, "params": params, "sql": shape.sql(params)}, 60.0)
    assert record["error"] is None, record["error"]
    want = shape.reference(frames, **params)
    gap, mismatched = compare.compare_frames(record["frame"], want)
    assert mismatched == 0
    assert gap <= compare.LIMITS["max_rel_gap"]
    return record, want


@pytest.mark.parametrize("name", SHAPES)
@pytest.mark.parametrize("index", [0, 7])
def test_reference_agrees_with_the_engine_embedded(name, index, small):
    frames, context = small
    record, want = _against_the_reference(context, frames, name, index)
    assert {"parse", "plan", "fetch"} <= set(record["phases"])
    assert len(want) > 0


@pytest.mark.parametrize("name", SHAPES)
@pytest.mark.parametrize("formulation", ["as_at_sf1", "as_at_sf001"])
def test_the_path_the_chip_runs_agrees_with_the_reference(
        name, formulation, small, monkeypatch):
    """The TPU strategy forced on the CPU; with the limits lowered the
    joins, the group-bys and the ORDER BY take the formulations they take
    at SF1 on the chip (``test_chipbench_joins.py`` has the same for its
    three shapes)."""
    from dask_sql_tpu import Context
    from dask_sql_tpu.ops import pallas_kernels
    from dask_sql_tpu.physical import compiled

    monkeypatch.delenv("DSQL_STRATEGY", raising=False)
    monkeypatch.setattr(pallas_kernels, "_on_tpu", lambda: True)
    if formulation == "as_at_sf1":
        monkeypatch.setattr(compiled, "SORT_ROWS_MAX", 256)
        monkeypatch.setattr(compiled, "LEXSORT_ROWS_MAX", 8)
    frames, _ = small
    compiled._cache.clear()
    compiled._learned_caps.clear()
    context = Context()
    for table, frame in frames.items():
        context.create_table(table, frame)
    before = dict(compiled.stats)
    for index in (_shape(name).FIRST, 7):
        _against_the_reference(context, frames, name, index)
    delta = {k: compiled.stats[k] - before.get(k, 0)
             for k in ("compiles", "hits", "fallbacks", "unsupported")}
    assert delta["fallbacks"] == 0 and delta["unsupported"] == 0
    assert delta["compiles"] >= 1 and delta["hits"] >= 1


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
    here = os.path.dirname(run.__file__)
    assert _imports(os.path.join(here, "shapes", f"{name}.py")) <= {
        "datetime", "pandas", "chipbench.ready_limit", "chipbench.warm_limit"}
    assert _imports(os.path.join(here, "warm_limit.py")) <= {
        "__future__.annotations", "json", "os", "sys", "threading",
        "chipbench.ready_limit", "chipbench.traffic"}


# --- a run whose programs new parameters do not get ends at that shape ------

@pytest.fixture
def clocked(monkeypatch):
    """``warm_limit`` (and ``ready_limit``) as in a process started as
    ``chipbench/run.py``, with clocks the test strikes itself and the exit
    recorded, not taken."""
    from chipbench import ready_limit, warm_limit

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

    Clock.made = []
    Clock.Left = Left
    monkeypatch.setattr(ready_limit, "_under_the_harness", lambda: True)
    monkeypatch.setattr(ready_limit, "asked", lambda shape, mix: None)
    monkeypatch.setattr(warm_limit.threading, "Timer", Clock)
    monkeypatch.setattr(warm_limit.os, "_exit", leave)
    monkeypatch.setattr(warm_limit, "_texts", {})
    monkeypatch.setattr(warm_limit, "_armed", [])
    return Clock


def _mix():
    with open(os.path.join(os.path.dirname(run.__file__), "traffic",
                           "subqueries.json")) as f:
        return json.load(f)


def test_a_shapes_second_and_third_text_start_a_clock_the_next_stops(
        clocked):
    q15, q18 = _shape("q15"), _shape("q18")
    q15.sql(q15.params_at(q15.FIRST))
    assert clocked.made == []                 # ready_limit's part
    q15.sql(q15.params_at(1))
    (second,) = clocked.made
    assert second.started and second.daemon and not second.cancelled
    assert second.interval == float(_mix()["deadline_s"])
    assert second.args == ("q15", 2, float(_mix()["deadline_s"]))
    q15.sql(q15.params_at(2))
    third = clocked.made[1]
    assert second.cancelled and not third.cancelled
    # a fourth text (the first was the eager tier's) stops it and starts none
    q15.sql(q15.params_at(3))
    assert third.cancelled and len(clocked.made) == 2
    # and so does the next shape's first, where three were enough; the
    # mix's last shape's last text may be set-up's last: what follows it is
    # ``ready_phase``'s wait for the compiles in flight, then the window
    q18.sql(q18.params_at(q18.FIRST))
    q18.sql(q18.params_at(1))
    q18.sql(q18.params_at(2))
    assert list(_mix()["shapes"])[-1] == "q18"
    last = clocked.made[-1]
    assert last.args[:2] == ("q18", 3) and last.interval == float(
        _mix()["deadline_s"]) + float(_mix()["ready_deadline_s"])
    assert clocked.made[-2].interval == float(_mix()["deadline_s"])
    q4 = _shape("q4")
    q4.sql(q4.params_at(q4.FIRST))
    assert [c.cancelled for c in clocked.made] == [True] * 4


def test_the_windows_texts_start_no_clock(clocked):
    q4 = _shape("q4")
    for i in range(9):
        q4.sql(q4.params_at(i))
    assert len(clocked.made) == int(_mix()["warm_extra"])
    assert all(c.cancelled for c in clocked.made)


def test_a_text_no_other_follows_ends_the_run_with_exit_code_1(clocked,
                                                              capsys):
    q15 = _shape("q15")
    q15.sql(q15.params_at(q15.FIRST))
    q15.sql(q15.params_at(1))
    with pytest.raises(clocked.Left) as left:
        clocked.made[0].strike()
    assert left.value.args == (1,)
    out, err = capsys.readouterr()
    assert json.loads(out.strip().splitlines()[-1]) == {
        "phase": "refused", "shape": "q15", "text": 2, "deadline_s": 60.0}
    assert "no compiled program served it" in err


def test_outside_the_harness_no_text_is_timed(clocked, monkeypatch):
    from chipbench import ready_limit, warm_limit

    monkeypatch.setattr(ready_limit, "_under_the_harness", lambda: False)
    q18 = _shape("q18")
    for i in range(4):
        q18.sql(q18.params_at(i))
    assert clocked.made == [] and warm_limit._texts == {}


# --- the cell ---------------------------------------------------------------

def test_the_cell_resolves_to_its_files(bench):
    loaded = run.load_cell(CELL)
    assert loaded["config"]["name"] == "tpch_sf1_subqueries"
    assert loaded["config"]["environment"] == {}
    assert loaded["config"]["surface"] == "embedded"
    assert list(loaded["mix"]["shapes"]) == SHAPES
    joins = run.load_cell("tpch_sf1_joins.power")
    for key in ("schema", "generator", "tables", "scale_factor",
                "rehearsal_scale_factor", "guarantees", "chips", "layout",
                "surface", "environment"):
        assert loaded["config"][key] == joins["config"][key]
    for key in ("loop", "clients", "repeat_share", "repeat_texts",
                "poll_interval_ms", "deadline_s", "warm_extra",
                "compare_per_shape", "trace_after_s"):
        assert loaded["mix"][key] == joins["mix"][key], key
    assert set(loaded["mix"]) == set(joins["mix"])
    # a window of these shapes completes far fewer than 100 requests: no p90
    assert set(loaded["end_to_end"]) == {"query_geomean_ms", "setup_s"}
    assert set(NEW_METRICS) <= set(loaded["per_layer"])
    assert "join_device_ms" not in loaded["per_layer"]
    assert "compiles_in_window" not in loaded["per_layer"]


def test_the_benchmark_lists_the_cell_and_its_metrics(bench):
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW_METRICS:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "query_geomean_ms"
    assert by_name["semi_join_device_ms"]["source"] == "device_trace"
    assert by_name["subquery_compiles_in_window"]["source"] \
        == "program_counter"
    for metric in bench["per_layer"]:
        if CELL in metric.get("workloads", ()):
            assert metric["moves"] in ("query_geomean_ms", "setup_s")
    cell, = [w for w in bench["workloads"] if w["name"] == CELL]
    assert cell["chips"] == 1 and cell["traffic"] == "subqueries"
    config, = [c for c in bench["configs"] if c["name"] == cell["config"]]
    assert config["reduced"] == ["scale_factor", "query_shapes",
                                 "comment_columns"]
    assert all(w["chips"] == 1 for w in bench["workloads"])


def test_the_configuration_says_what_it_cut_and_assumed():
    config = run.load_cell(CELL)["config"]
    assert set(config["reduced"]) == {"scale_factor", "query_shapes",
                                      "comment_columns"}
    assumed = " ".join(config["assumed"])
    for said in ("clause numbers", "o_orderkey", "common table expression",
                 "319.95"):
        assert said in assumed
    # the shape the source has and the mix leaves out, and why
    assert "Q20" in config["reduced"]["query_shapes"]
    assert "cannot fail" in config["reduced"]["query_shapes"]


def test_the_cell_rehearses(engine_as_shipped, capsys, bench):
    assert run.main(["--workload", CELL, "--seed", "2147483743",
                     "--seconds", "2", "--trace", "1", "--allow-cpu"]) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    result = json.loads(lines[-1])
    assert result["correct"] is True and result["failed"] == 0
    assert result["rehearsal"] is True
    # lookup, bind and dispatch are read from the spans of a device trace
    host_side = {m["name"] for m in bench["per_layer"]
                 if CELL in m.get("workloads", [CELL])
                 and m["source"] != "device_trace"} - {
                     "lookup_ms", "bind_ms", "dispatch_ms"}
    assert set(result["metrics"]) == host_side
    assert result["metrics"]["subquery_compiles_in_window"]["value"] == 0
    ready = [json.loads(l) for l in lines if '"phase": "ready"' in l]
    assert [r["shape"] for r in ready] == SHAPES
    for r in ready:
        assert r["tiers"][-3:] == ["compiled"] * 3
    # two of Q15's four dates stand inside its scalar subquery's body
    q15 = ready[1]["counters"]
    assert q15["param_plan_subquery_hoisted"] == 2 * len(ready[1]["tiers"])
    window = next(json.loads(l) for l in lines if '"phase": "window"' in l)
    assert window["texts_sent_twice"] == 0
    assert all(n >= 1 for n in window["by_shape"].values())
    assert not any(k in window["counters"]
                   for k in ("compiles", "recompiles"))


# --- the comparison ---------------------------------------------------------

def test_the_float32_control_fails_the_mix_on_its_float_answers(capsys):
    """Q15 (one sum of prices an answer) and Q18 (``o_totalprice``, a row an
    order) carry the float check; Q4 answers counts, which float32 columns
    leave as they are.  A run compares seven answers or
    more of every shape and reads the widest gap of them all."""
    assert control.main(["--mix", "subqueries", "--scale", "0.01", "--seeds",
                         "21", "22", "--per-shape", "4"]) == 0
    lines = [json.loads(l) for l in
             capsys.readouterr().out.strip().splitlines()]
    assert {l["shape"] for l in lines} == set(SHAPES)
    for seed in (21, 22):
        of_seed = {l["shape"]: l for l in lines if l["seed"] == seed}
        assert of_seed["q4"]["max_gap"] == 0.0
        assert of_seed["q15"]["max_gap"] > compare.LIMITS["max_rel_gap"]
        widest = max(l["max_gap"] for l in of_seed.values())
        assert not compare.verdict(widest, 0, 0)[0]


@pytest.mark.parametrize("name, column", [
    ("q4", "order_count"), ("q4", "o_orderpriority"), ("q15", "s_phone"),
    ("q15", "total_revenue"), ("q18", "o_orderdate"), ("q18", "c_name")])
def test_an_answer_altered_in_one_cell_is_not_correct(name, column, small):
    frames, _ = small
    shape = _shape(name)
    want = shape.reference(frames, **shape.params_at(shape.FIRST))
    assert compare.compare_frames(want.copy(), want) == (0.0, 0)
    got = want.copy()
    cell = got.loc[len(got) // 2, column]
    if isinstance(cell, str):
        got.loc[len(got) // 2, column] = cell + "x"
    elif isinstance(cell, pd.Timestamp):
        got.loc[len(got) // 2, column] = cell + pd.Timedelta(days=1)
    else:
        got.loc[len(got) // 2, column] = cell + 1
    gap, mismatched = compare.compare_frames(got, want)
    assert mismatched == 1 or gap > compare.LIMITS["max_rel_gap"]
    assert not compare.verdict(gap, mismatched, 0)[0]


# --- the new metrics' readers ------------------------------------------------

def _traced():
    return {"surface": "embedded", "trace": {"busy_s": 1.0},
            "window": {"records": [], "counters": {}}}


@pytest.mark.parametrize("name", NEW_METRICS[:4])
def test_a_trace_metric_is_none_without_a_trace(name, tmp_path, monkeypatch):
    metric = run.load_by_path("metrics", name)
    assert metric.read({"surface": "embedded", "trace": None,
                        "window": {"records": [], "counters": {}}}) is None
    if "roofline" not in name:
        # a traced run whose trace directory holds no file
        monkeypatch.setattr(spans, "_ROOT", str(tmp_path))
        assert metric.read(_traced()) is None


@pytest.mark.parametrize("name", SHAPES)
def test_a_scan_roofline_is_least_time_over_busy_time(name):
    metric = run.load_by_path("metrics", f"{name}_scan_roofline")
    run_ = {"trace": {"median_busy_s_by_shape": {name: 0.5}},
            "scan_bytes": {name: 819e6},
            "peaks": {"hbm_bytes_per_s": 819e9}}
    assert metric.read(run_) == pytest.approx(0.2)
    run_["trace"]["median_busy_s_by_shape"] = {"q6": 0.5}
    assert metric.read(run_) is None


@pytest.mark.parametrize("requests, want", [
    ([], None),
    ([("q4", {"dsql.semi_build": 30e6})], 30.0),
    # the medians of the shapes that ran one, added up: one cycle's
    ([("q4", {"dsql.semi_build": 300e6, "dsql.semi_probe": 40e6}),
      ("q18", {"dsql.semi_probe": 20e6}),
      ("q4", {"dsql.semi_build": 290e6, "dsql.semi_probe": 30e6}),
      ("q18", {"dsql.semi_build": 1e6, "dsql.semi_probe": 29e6}),
      ("q4", {"dsql.semi_build": 90e6, "dsql.semi_probe": 10e6})], 345.0)])
def test_semi_join_is_one_cycles_device_time_of_them(requests, want):
    metric = run.load_by_path("metrics", "semi_join_device_ms")
    requests = [{"shape": shape, "device_ns_by_scope": {
        "dsql.join_probe": 40e6, **scopes}} for shape, scopes in requests]
    # a program from before the engine named the scopes, and a Q15
    requests.append({"shape": "q15", "device_ns_by_scope": {
        "dsql.join_build": 90e6, "dsql.groupby_sorted": 70e6}})
    assert metric.cycle_ms(requests) == want


def test_the_largest_semi_join_moves_the_metric():
    """What a median over all requests did not do: Q4's semi join fell from
    1 148 to 311 ms between two chip calls of PR 43 and that reading stayed
    at Q20's 60."""
    metric = run.load_by_path("metrics", "semi_join_device_ms")

    def cycle(q4_ms):
        return [{"shape": s, "device_ns_by_scope": {"dsql.semi_build": ms * 1e6}}
                for s, ms in (("q4", q4_ms), ("q18", 40.0)) for _ in range(3)]

    assert metric.cycle_ms(cycle(1148.0)) - metric.cycle_ms(cycle(311.0)) \
        == pytest.approx(837.0)


@pytest.mark.parametrize("counters, want", [
    ({}, 0), ({"hits": 40, "param_plan_hits": 40}, 0),
    ({"compiles": 9, "recompiles": 2, "hits": 3}, 11)])
def test_subquery_compiles_in_window_counts_the_programs_compiled(counters,
                                                                  want):
    metric = run.load_by_path("metrics", "subquery_compiles_in_window")
    assert metric.read({"window": {"counters": counters}}) == want
