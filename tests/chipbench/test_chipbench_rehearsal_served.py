"""The served cell rehearsed whole: server, clients' process, wire metrics."""
import json

from chipbench import run

CELL = "tpch_sf1_served.streams2"


def result_line(capsys) -> dict:
    """The last line of what a run printed."""
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_the_served_cell_rehearses(engine_as_shipped, capsys, bench):
    assert run.main(["--workload", CELL, "--seed", "2147483651",
                     "--seconds", "1", "--trace", "1", "--allow-cpu"]) == 0
    result = result_line(capsys)
    assert result["correct"] is True and result["failed"] == 0
    assert result["rehearsal"] is True
    host_side = {m["name"] for m in bench["per_layer"]
                 if CELL in m.get("workloads", [CELL])
                 and m["source"] != "device_trace"}
    assert set(result["metrics"]) == host_side
    assert {"wire_overhead_ms", "queued_ms"} <= host_side
    assert result["metrics"]["wire_overhead_ms"]["value"] > 0
    assert result["metrics"]["compiles_in_window"]["value"] == 0
