"""The SF10 deployment (``tpch_sf10_embedded``) and its cell: the files it
resolves to, the generator that arms the load's deadline, the deadline
itself, the cell rehearsed whole, the three load metrics, and the four
shapes on the formulations they take at 60 M rows."""
import json
import os

import pytest

from chipbench import compare, load_limit, run

CELL = "tpch_sf10_embedded.power"
SHAPES = ["q1", "q6", "q12", "q14"]
LOAD_METRICS = ["load_encode_s", "load_stats_s", "load_transfer_s"]


def result_line(capsys) -> dict:
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


# --- the cell and its files -------------------------------------------------

def test_the_cell_resolves_to_its_files(bench):
    loaded = run.load_cell(CELL)
    config, mix = loaded["config"], loaded["mix"]
    assert config["name"] == "tpch_sf10_embedded"
    assert config["scale_factor"] == 10.0
    assert config["rehearsal_scale_factor"] == 0.01
    assert config["generator"] == "tpch_resident"
    assert config["load_deadline_s"] == 180
    assert set(config["reduced"]) == {"scale_factor", "query_shapes",
                                      "comment_columns"}
    sf1 = run.load_cell("tpch_sf1_embedded.power")["config"]
    for key in ("schema", "tables", "surface", "environment", "chips",
                "layout", "guarantees"):
        assert config[key] == sf1[key], key
    assert set(sf1["assumed"]) < set(config["assumed"])
    assert list(mix["shapes"]) == SHAPES
    assert mix["loop"] == "closed" and mix["clients"] == 1
    assert mix["repeat_share"] == 0 and mix["repeat_texts"] == 0
    assert mix["compare_per_shape"] == 2 and mix["trace_seconds"] == 14
    assert mix["ready_deadline_s"] <= 600 and mix["deadline_s"] == 60
    # about 35 requests a window: three beyond p90, all of one shape
    assert set(loaded["end_to_end"]) == {"query_geomean_ms", "setup_s"}
    assert {"q6_scan_roofline", "q1_groupby_roofline", "lookup_ms",
            "bind_ms", "dispatch_ms", "fetch_ms", "parse_plan_ms",
            "device_programs_per_query", "idle_pre_dispatch_ms",
            "idle_post_device_ms", "create_table_s",
            *LOAD_METRICS} <= set(loaded["per_layer"])
    for metric in bench["per_layer"]:
        if CELL in metric.get("workloads", ()):
            assert metric["moves"] in ("query_geomean_ms", "setup_s")


def test_the_new_entries_name_the_cell(bench):
    """By name, not by place: the next PR's entries go behind these."""
    assert "tpch_sf10_embedded" in [c["name"] for c in bench["configs"]]
    cells = {w["name"]: w for w in bench["workloads"]}
    assert cells[CELL]["config"] == "tpch_sf10_embedded"
    assert cells[CELL]["traffic"] == "power10" and cells[CELL]["chips"] == 1
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in LOAD_METRICS:
        metric = by_name[name]
        assert metric["layer"] == "load" and metric["moves"] == "setup_s"
        assert metric["source"] == "program_counter" and metric["unit"] == "s"
        # every cell but ``short``, whose traced rehearsal holds its list
        # of metrics to what it was (test_chipbench_rehearsal.py)
        assert CELL in metric["workloads"]
        assert "tpch_sf1_embedded.short" not in metric["workloads"]
    assert len(cells) <= 24 and all(w["chips"] == 1 for w in cells.values())


# --- the generator and the load's deadline ----------------------------------

def blocks_of(frame) -> list:
    """[(dtype, the columns' places)] of a frame's blocks."""
    return sorted((str(block.dtype), block.mgr_locs.as_array.tolist())
                  for block in frame._mgr.blocks)


@pytest.mark.parametrize("sf, seed", [
    (0.01, 2147483777), (0.01, 5), (0.00001, 3), (0.05, 4294967301),
    (0.002, 0)])
def test_the_resident_generator_makes_tpch_gens_frames(sf, seed):
    """Value for value and dtype for dtype, at every seed: ``tpch_resident``
    draws ``tpch_gen``'s two streams in its order and builds the columns
    another way (its docstring says why), so the two are held side by
    side here, down to the type arrow keeps a string column in and the
    blocks a frame keeps its numbers in (a Q1 reference whose frame holds
    a column each spends seconds copying them together)."""
    import pandas as pd

    from chipbench.data import tpch_gen

    resident = run.load_by_path("data", "tpch_resident")
    got = resident.generate(sf, seed)
    want = tpch_gen.generate(sf, seed)
    assert list(got) == list(want)
    for name in want:
        pd.testing.assert_frame_equal(got[name], want[name],
                                      check_exact=True)
        assert got[name].equals(want[name]), name
        assert list(got[name].dtypes) == list(want[name].dtypes)
        assert blocks_of(got[name]) == blocks_of(want[name]), name
        for column in want[name]:
            mine, theirs = got[name][column].array, want[name][column].array
            assert type(mine) is type(theirs), (name, column)
            if hasattr(theirs, "_pa_array"):
                assert mine._pa_array.type == theirs._pa_array.type
                assert mine._pa_array.equals(theirs._pa_array)


def test_the_resident_generator_draws_what_tpch_gen_draws():
    """The same calls on the same streams: after a table set, the next
    number of each stream is the one ``tpch_gen`` would draw next.  (A
    column drawn out of order, or by a call that takes more of the stream,
    would still give plausible tables.)"""
    import numpy as np

    from chipbench.data import tpch_gen, tpch_resident

    taken = {}
    real = np.random.RandomState

    class Stream(real):
        def __init__(self, seed):
            super().__init__(seed)
            taken.setdefault(seed, []).append(self)

    for module in (tpch_gen, tpch_resident):
        taken.clear()
        np.random.RandomState = Stream
        try:
            module.generate(0.01, 77)
        finally:
            np.random.RandomState = real
        after = {seed: [s.randint(0, 1 << 30) for s in streams]
                 for seed, streams in taken.items()}
        if module is tpch_gen:
            want = after
    assert after == want and set(want) == {77, tpch_gen.STRUCTURE_STREAM,
                                            tpch_gen.LINES_STREAM}


class Left(Exception):
    pass


def leave(*args):
    raise Left(*args)


def as_the_harness_loads(frames, tables=("region", "lineitem")):
    """``run.py::load_phase``: every table taken out to be loaded, then
    every table once more for the ``load`` line's row counts."""
    for name in tables:
        frames[name]
    for name in tables:
        len(frames[name])


@pytest.mark.parametrize("load_s, refused", [(36.9, False), (179.0, False),
                                             (181.0, True), (414.5, True)])
def test_a_load_is_over_when_a_table_is_taken_out_again(load_s, refused):
    """Fakes: the clock is the test's, the exit recorded and not taken."""
    now = [1000.0]
    held = load_limit.Watched({"region": "r", "lineitem": "l"}, 180.0,
                              refuse=leave, clock=lambda: now[0])
    assert held == {"region": "r", "lineitem": "l"}
    held["region"]
    now[0] += load_s
    held["lineitem"]
    assert held.loaded_s is None
    if refused:
        # on the harness's own thread, whatever became of the timer's
        with pytest.raises(Left) as left:
            as_the_harness_loads(held, ["region"])
        assert left.value.args == (pytest.approx(load_s), 180.0)
    else:
        as_the_harness_loads(held, ["region"])
        assert held.loaded_s == pytest.approx(load_s)
        assert held.at_the_deadline() is True
        # the references read the frames long after: nothing is timed twice
        now[0] += 900.0
        assert held["lineitem"] == "l" and held.loaded_s == pytest.approx(
            load_s)


def test_the_timer_refuses_a_load_that_is_still_going():
    held = load_limit.Watched({"region": "r", "lineitem": "l"}, 180.0,
                              refuse=leave)
    held["region"]
    held["lineitem"]        # inside ``create_table`` when the timer strikes
    with pytest.raises(Left) as left:
        held.at_the_deadline()
    assert left.value.args == (None, 180.0)


@pytest.mark.parametrize("loaded_s, said", [
    (None, "not on the device 180 s after"),
    (414.5, "on the device only 414.5 s, not 180 s after")])
def test_a_refusal_is_a_line_and_exit_code_1(loaded_s, said, monkeypatch,
                                             capsys):
    monkeypatch.setattr(load_limit.os, "_exit", leave)
    with pytest.raises(Left) as left:
        load_limit._refuse(loaded_s, 180.0)
    assert left.value.args == (1,)
    out, err = capsys.readouterr()
    assert json.loads(out.strip().splitlines()[-1]) == {
        "phase": "refused", "load_deadline_s": 180.0,
        "loaded_after_s": loaded_s}
    assert said in err and "the run ends here" in err


def test_the_clock_starts_under_the_harness_only(monkeypatch, small):
    frames, _ = small
    assert load_limit.watched(frames, 180.0) is frames

    made = []

    class Clock:
        def __init__(self, interval, function):
            self.interval, self.function = interval, function
            made.append(self)

        def start(self):
            self.started = True

    monkeypatch.setattr(load_limit, "_under_the_harness", lambda: True)
    monkeypatch.setattr(load_limit.threading, "Timer", Clock)
    held = load_limit.watched(frames, 180.0)
    (clock,) = made
    assert clock.started and clock.daemon and clock.interval == 180.0
    assert clock.function == held.at_the_deadline
    assert isinstance(held, dict) and list(held) == list(frames)
    assert all(held[name] is frames[name] for name in frames)


def test_a_load_past_its_deadline_ends_the_run_by_itself(tmp_path):
    """The harness in a process of its own, rehearsed with the deadline at
    0 s, which no load meets: the run ends inside ``create_table`` or as
    the load is over, with exit code 1 and no result line."""
    import shutil
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(run.__file__))
    copy = tmp_path / "repo"
    shutil.copytree(os.path.join(root, "chipbench"), copy / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(root, "BENCHMARK.json"), copy)
    held = copy / "chipbench" / "configs" / "tpch_sf10_embedded.json"
    config = json.loads(held.read_text())
    config["load_deadline_s"] = 0
    held.write_text(json.dumps(config))
    done = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload", CELL, "--seed",
         "5", "--seconds", "1", "--trace", "0", "--allow-cpu"], cwd=copy,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": root,
             "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")},
        capture_output=True, text=True, timeout=120)
    assert done.returncode == 1
    last = json.loads(done.stdout.strip().splitlines()[-1])
    assert last["phase"] == "refused" and last["load_deadline_s"] == 0.0
    assert "the run ends here" in done.stderr


# --- the cell, rehearsed ----------------------------------------------------

def test_the_cell_rehearses(engine_as_shipped, capsys):
    assert run.main(["--workload", CELL, "--seed", "2147483869",
                     "--seconds", "2", "--trace", "0", "--allow-cpu"]) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    result = json.loads(lines[-1])
    assert result["correct"] is True and result["failed"] == 0
    assert result["rehearsal"] is True
    assert set(result["metrics"]) == {"query_geomean_ms", "setup_s"}
    assert all(m["value"] > 0 for m in result["metrics"].values())
    ready = [json.loads(l) for l in lines if '"phase": "ready"' in l]
    assert [r["shape"] for r in ready] == SHAPES
    window = next(json.loads(l) for l in lines if '"phase": "window"' in l)
    assert window["texts_sent_twice"] == 0
    assert all(n >= 1 for n in window["by_shape"].values())


def test_a_traced_rehearsal_reports_the_three_load_metrics(
        engine_as_shipped, capsys, bench):
    from dask_sql_tpu.runtime import telemetry

    # a run is a process of its own; here other tests have loaded before
    before = sum(telemetry.REGISTRY.get(name[:-2] + "_ms", 0)
                 for name in LOAD_METRICS) / 1e3
    assert run.main(["--workload", CELL, "--seed", "2147483873",
                     "--seconds", "1", "--trace", "1", "--allow-cpu"]) == 0
    result = result_line(capsys)
    assert result["correct"] is True and result["failed"] == 0
    metrics = result["metrics"]
    assert set(LOAD_METRICS) <= set(metrics)
    assert all(metrics[name]["unit"] == "s" and metrics[name]["value"] >= 0
               for name in LOAD_METRICS)
    # the three steps are the load: nothing else of weight is inside it
    steps = sum(metrics[name]["value"] for name in LOAD_METRICS) - before
    assert 0 <= steps <= metrics["create_table_s"]["value"] + 0.01
    host_side = {m["name"] for m in bench["per_layer"]
                 if CELL in m.get("workloads", [CELL])
                 and m["source"] not in ("device_trace", "program_span")}
    assert host_side <= set(metrics)


@pytest.mark.parametrize("name", LOAD_METRICS)
def test_a_load_metric_reads_its_counter_or_nothing(name, monkeypatch):
    from dask_sql_tpu.runtime import telemetry

    reader = run.load_by_path("metrics", name)
    counter = name[:-2] + "_ms"
    monkeypatch.setattr(telemetry, "REGISTRY", telemetry.MetricsRegistry())
    # an engine that has no such counter, as the parent of PR 31
    assert reader.read({}) is None
    telemetry.REGISTRY.inc(counter, 1250)
    telemetry.REGISTRY.inc(counter, 250)
    assert reader.read({}) == 1.5


# --- the shapes on the formulations they take at SF10 -----------------------

@pytest.mark.parametrize("name", SHAPES)
def test_the_path_the_chip_runs_at_sf10_agrees_with_the_reference(
        name, small, engine_as_shipped, monkeypatch):
    """The TPU strategy forced on the CPU, with the limits lowered by the
    factor of a thousand between SF0.01 and SF10: a scan over
    ``EAGER_SCAN_ROWS_MAX`` rows, so the first arrival waits for its
    program; Q12's and Q14's compacted probe sides over ``SORT_ROWS_MAX``,
    so both joins take the hash table; orders' table over
    ``_TABLE_BYTES_MAX`` at 16 slots a row, so it gets four, which still
    direct-addresses the order keys; Q1's 17 value rows over
    ``STACK_BYTES_MAX``, so the limb kernel's loop builds each slab's."""
    from dask_sql_tpu import Context
    from dask_sql_tpu.ops import hashing, pallas_kernels
    from dask_sql_tpu.physical import compiled
    from dask_sql_tpu.runtime import telemetry

    monkeypatch.delenv("DSQL_STRATEGY", raising=False)
    monkeypatch.setattr(pallas_kernels, "_on_tpu", lambda: True)
    monkeypatch.setattr(compiled, "SORT_ROWS_MAX", 256)
    monkeypatch.setattr(compiled, "EAGER_SCAN_ROWS_MAX", 1 << 14)
    monkeypatch.setattr(hashing, "_TABLE_BYTES_MAX", 1 << 20)
    monkeypatch.setattr(pallas_kernels, "STACK_BYTES_MAX", 1 << 21)
    monkeypatch.setattr(pallas_kernels, "SLAB_EXACT",
                        2 * pallas_kernels.BLOCK_EXACT)
    slabwise = []
    shipped = pallas_kernels.segmented_sums_slabwise
    monkeypatch.setattr(
        pallas_kernels, "segmented_sums_slabwise",
        lambda *args: slabwise.append(len(args[1])) or shipped(*args))
    frames, _ = small
    assert hashing._hash_table_size(len(frames["orders"])) == 1 << 16
    assert len(frames["lineitem"]) > compiled.EAGER_SCAN_ROWS_MAX
    compiled._cache.clear()
    compiled._learned_caps.clear()
    context = Context()
    for table, frame in frames.items():
        context.create_table(table, frame)
    shape = run.load_by_path("shapes", name)
    surface = run.Embedded(context)
    joins = {"q12": 1, "q14": 1}.get(name, 0)
    for n, index in enumerate((shape.FIRST, 3, shape.SPACE - 1)):
        params = shape.params_at(index)
        record = surface.execute(
            {"shape": name, "params": params, "sql": shape.sql(params)}, 60.0)
        assert record["error"] is None, record["error"]
        # no arrival is the eager tier's, the first pays its compile
        assert record["tier"] == "compiled"
        gap, mismatched = compare.compare_frames(
            record["frame"], shape.reference(frames, **params))
        assert mismatched == 0 and gap <= compare.LIMITS["max_rel_gap"]
        spans = {s.name: s for s in telemetry.last_report().root.walk()}
        attrs = spans["materialize"].attrs
        assert attrs.get("hash_table_joins", 0) == joins
        assert attrs.get("direct_probes", 0) == joins
    # Q1's programs sum their 17 rows a slab at a time
    assert (17 in slabwise) == (name == "q1")
