"""``BENCHMARK.json`` against the contract, and every name against a file."""
import json
import os
import re

import pytest

from chipbench import run

ROOT = run._ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys_are_the_contracts():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024
    for path in BENCH["paths"]:
        assert os.path.isdir(os.path.join(ROOT, path))


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_entry(metric):
    end_to_end = metric in BENCH["end_to_end"]
    keys = {"name", "unit", "better", "source"} | (
        {"bound"} if end_to_end else {"layer", "moves"})
    assert keys <= set(metric) <= keys | {"workloads"}
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in SOURCES
    if end_to_end:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.25
    else:
        assert metric["moves"] in {m["name"] for m in BENCH["end_to_end"]}
        assert 1 <= len(metric["layer"]) <= 200
    assert set(metric.get("workloads", CELLS)) <= set(CELLS)
    # the reader is found by the metric's name
    assert callable(run.load_by_path("metrics", metric["name"]).read)


def test_names_are_unique_and_setup_s_is_there():
    names = [m["name"] for m in METRICS]
    assert len(names) == len(set(names))
    assert "setup_s" in names
    assert len(CELLS) == len(set(CELLS))


@pytest.mark.parametrize("config", BENCH["configs"], ids=lambda c: c["name"])
def test_config_entry(config):
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(config["name"])
    assert config["file"].startswith(tuple(BENCH["paths"]))
    for text in (config["source"], config["why"]):
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text
    with open(os.path.join(ROOT, config["file"])) as f:
        held = json.load(f)
    assert held["source"] == config["source"]
    assert set(config["reduced"]) == set(held["reduced"])
    assert all(NAME.match(k) for k in config["reduced"])
    assert held["surface"] in ("embedded", "served")
    # the engine runs as shipped: a config that sets a variable lists it
    assert held["environment"] == {}
    assert held["guarantees"] and held["assumed"]


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_cell_resolves(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(cell["name"]) and NAME.match(cell["traffic"])
    assert cell["chips"] == 1 and 1 <= len(cell["why"]) <= 200
    loaded = run.load_cell(cell["name"])
    assert loaded["config"]["name"] == cell["config"]
    assert set(loaded["shapes"]) == set(loaded["mix"]["shapes"])
    assert "setup_s" in loaded["end_to_end"]
    assert len(loaded["end_to_end"]) >= 2 and loaded["per_layer"]
    # every per-layer metric of the cell moves a metric the cell reports
    for entry, _ in loaded["per_layer"].values():
        assert entry["moves"] in loaded["end_to_end"]


def test_a_name_with_no_file_is_an_error():
    with pytest.raises(SystemExit, match="no file"):
        run.load_by_path("metrics", "no_such_metric")
    with pytest.raises(SystemExit, match="no workload"):
        run.load_cell("no.such.cell")


def test_the_command_names_no_file_outside_paths():
    assert BENCH["command"] == ["python3", "-m", "chipbench.run"]
    assert os.path.isfile(os.path.join(ROOT, "chipbench", "run.py"))


def test_peaks_name_their_source():
    from chipbench import roofline

    v5e = roofline.peaks_for("TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9 and v5e["bf16_flops_per_s"] == 197e12
    assert v5e["source"]
    with pytest.raises(SystemExit, match="no peaks"):
        roofline.peaks_for("TPU v9 imagined")
