"""The served surface at SF0.01: the wire's answers against the references,
and the clients' process against a real server."""
import itertools

import pytest

from chipbench import compare, run, traffic

SHAPES = ["q1", "q6", "q12", "q14"]
MIX = {"loop": "closed", "clients": 2, "shapes": {n: 1 for n in SHAPES},
       "poll_interval_ms": 5, "deadline_s": 60, "max_per_client_per_s": 50}


@pytest.fixture(scope="module")
def served(small):
    frames, context = small
    surface = run.Served(context, MIX)
    yield frames, surface
    surface.close()


@pytest.mark.parametrize("name", SHAPES)
def test_reference_agrees_with_the_engine_served(name, served):
    frames, surface = served
    shape = run.load_by_path("shapes", name)
    params = shape.params_at(shape.SPACE // 2)
    record = surface.execute(
        {"shape": name, "params": params, "sql": shape.sql(params)}, 60.0)
    assert record["error"] is None, record["error"]
    assert record["tier"] and "parse" in record["phases"]
    assert record["engine_wall_ms"] is not None
    gap, mismatched = compare.compare_frames(
        record["frame"], shape.reference(frames, **params))
    assert mismatched == 0 and gap <= compare.LIMITS["max_rel_gap"]


def test_a_statement_that_fails_is_an_error_not_an_exception(served):
    _, surface = served
    record = surface.execute({"shape": "q6", "params": {},
                              "sql": "SELECT no_such FROM lineitem"}, 60.0)
    assert record["error"] and record["frame"] is None


def test_the_clients_process_sends_what_it_was_given(served):
    import time

    frames, surface = served
    shapes = {n: run.load_by_path("shapes", n) for n in SHAPES}
    draws = traffic.Draws(shapes, 5)
    requests = [list(itertools.islice(rs, 40))
                for rs in traffic.requests_for_window(MIX, draws, 5)]
    surface.start_clients(requests)
    start_ns = time.monotonic_ns() + 50_000_000
    surface.go(start_ns, 1.0)
    records = surface.collect()
    assert {r["client"] for r in records} == {0, 1}
    assert all(r["error"] is None for r in records)
    assert all(r["t0_ns"] >= start_ns for r in records)
    # a closed loop: a client's requests do not overlap, and go in order
    for c in (0, 1):
        mine = [r for r in records if r["client"] == c]
        assert [r["id"] for r in mine] == [r["id"] for r in requests[c]][:len(mine)]
        assert all(a["t1_ns"] <= b["t0_ns"] for a, b in zip(mine, mine[1:]))
    last = records[-1]
    gap, mismatched = compare.compare_frames(
        last["frame"], shapes[last["shape"]].reference(frames, **last["params"]))
    assert mismatched == 0 and gap <= compare.LIMITS["max_rel_gap"]
