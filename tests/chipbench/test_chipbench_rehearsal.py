"""A whole run of a cell, rehearsed on the CPU at SF0.01 with the engine as
shipped: the result line's contract, and a timed path broken underneath."""
import json

import pandas as pd
import pytest

from chipbench import run

CONTRACT = {"correct", "attempted", "failed", "metrics", "device"}
SHORT = ["--workload", "tpch_sf1_embedded.short", "--seconds", "1",
         "--allow-cpu"]


def result_line(capsys) -> dict:
    """The last line of what a run printed."""
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_the_result_line_has_the_contracts_keys(engine_as_shipped, capsys,
                                                bench):
    assert run.main(SHORT + ["--seed", "2147483999", "--trace", "0"]) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    result = json.loads(lines[-1])
    # a rehearsal says so, and never under a TPU's name
    assert set(result) == CONTRACT | {"rehearsal"}
    assert result["device"]["platform"] == "cpu"
    assert set(result["device"]) == {"platform", "kind", "count",
                                     "memory_peak_bytes"}
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 4
    cell_metrics = {m["name"] for m in bench["end_to_end"]
                    if "tpch_sf1_embedded.short" in m.get(
                        "workloads", ["tpch_sf1_embedded.short"])}
    assert set(result["metrics"]) == cell_metrics
    units = {m["name"]: m["unit"] for m in bench["end_to_end"]}
    for name, metric in result["metrics"].items():
        assert set(metric) == {"value", "unit"}
        assert metric["unit"] == units[name] and metric["value"] > 0
    # every number compared stands beside its limit
    compared = next(json.loads(l) for l in lines if '"compare"' in l)
    assert {n["name"] for n in compared["numbers"]} == {
        "max_rel_gap", "mismatched_cells", "errors"}
    assert all(n["value"] <= n["limit"] for n in compared["numbers"])
    # the first arrival came from the eager tier, the window from programs
    ready = next(json.loads(l) for l in lines if '"ready"' in l)
    assert ready["tiers"][0].startswith("eager")
    assert ready["tiers"][-3:] == ["compiled"] * 3
    window = next(json.loads(l) for l in lines if '"phase": "window"' in l)
    assert window["texts_sent_twice"] == 0


def test_a_traced_rehearsal_reports_no_device_number(engine_as_shipped,
                                                     capsys, bench):
    assert run.main(SHORT + ["--seed", "11", "--trace", "1"]) == 0
    result = result_line(capsys)
    device_metrics = {m["name"] for m in bench["per_layer"]
                      if m["source"] == "device_trace"}
    assert not device_metrics & set(result["metrics"])
    assert "busy_s" not in result["device"] and "breakdown" not in result
    assert set(result["metrics"]) == {
        "parse_plan_ms", "fetch_ms", "ready_s", "setup_xla_cache_misses",
        "generate_s", "create_table_s"}


def test_an_answer_altered_where_it_is_produced_is_not_correct(
        engine_as_shipped, capsys, monkeypatch):
    from dask_sql_tpu import Context

    shipped = Context.sql

    def a_little_off(self, sql, *args, **kwargs):
        frame = shipped(self, sql, *args, **kwargs)
        if isinstance(frame, pd.DataFrame) and "revenue" in frame:
            frame["revenue"] = frame["revenue"] * (1 + 1e-7)
        return frame

    monkeypatch.setattr(Context, "sql", a_little_off)
    assert run.main(SHORT + ["--seed", "12", "--trace", "0"]) == 0
    result = result_line(capsys)
    assert result["correct"] is False and result["failed"] > 0


def test_without_a_tpu_there_is_no_result(capsys):
    with pytest.raises(SystemExit) as e:
        run.main(["--workload", "tpch_sf1_embedded.short", "--seed", "1",
                  "--seconds", "1", "--trace", "0"])
    assert e.value.code not in (0, None)
    assert '"correct"' not in capsys.readouterr().out
