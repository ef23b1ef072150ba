"""The reduction from a trace to numbers, on events worked out by hand."""
import os

import pytest

from chipbench.reduce import trace


OPS = {"/device:TPU:0": [("while", 10.0, 50.0), ("fusion.1", 12.0, 20.0),
                         ("fusion.2", 25.0, 45.0), ("copy", 70.0, 80.0),
                         ("before", -5.0, 2.0)]}
REQUESTS = [("q6", 5.0, 60.0), ("q1", 65.0, 90.0), ("q1", 95.0, 130.0)]


def test_union_merges_and_cuts():
    assert trace.union([(0, 5), (3, 8), (10, 12), (20, 30)], 1, 25) == [
        [1, 8], [10, 12], [20, 25]]
    assert trace.union([], 0, 10) == []


def test_self_time_leaves_out_nested_events():
    own = trace.self_times(OPS["/device:TPU:0"])
    assert own["while"] == 40 - 8 - 20 and own["fusion.2"] == 20
    assert own["copy"] == 10


def test_reduction_by_hand():
    out = trace.reduce_events(OPS, REQUESTS, 0.0, 100.0)
    # busy: [0,2) + [10,50) + [70,80) = 52 of 100 ns
    assert out["busy_s"] == pytest.approx(52e-9)
    assert out["window_s"] == pytest.approx(100e-9)
    assert out["idle_share"] == pytest.approx(0.48)
    gaps = dict(out["breakdown"]["idle_gaps"])
    # [2,10): 3 before q6 opens, 5 in it; [50,70): 10 in q6, 5 between,
    # 5 in q1; [80,100): 10 in q1, 5 between, 5 in the third request
    assert gaps == {"q6": pytest.approx(15e-9), "q1": pytest.approx(20e-9),
                    trace.BETWEEN: pytest.approx(13e-9)}
    ops = dict(out["breakdown"]["device_ops"])
    assert ops["fusion.2"] == pytest.approx(20e-9)
    assert ops["before"] == pytest.approx(7e-9)  # by event, not cut
    # a request's busy time; the third request leaves the window: left out
    assert out["busy_s_by_shape"] == {"q6": [pytest.approx(40e-9)],
                                      "q1": [pytest.approx(10e-9)]}


def test_two_chips_are_averaged():
    two = dict(OPS, **{"/device:TPU:1": [("copy", 0.0, 100.0)]})
    out = trace.reduce_events(two, REQUESTS, 0.0, 100.0)
    assert out["busy_s"] == pytest.approx((52e-9 + 100e-9) / 2)
    assert out["busy_s_by_shape"]["q6"] == [pytest.approx(40e-9 + 55e-9)]


def test_a_trace_without_device_operations_is_refused():
    with pytest.raises(SystemExit, match="no device operation"):
        trace.reduce_events({}, REQUESTS, 0.0, 100.0)


# --- a trace recorded on the chip --------------------------------------
# tests/chipbench/data/short_v5e_100ms.xplane.pb: 55 ms of the short cell on
# one TPU v5e (my chip run, PR 24), eight Q6 requests between the window's
# two annotations, 200 device operations.

RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        "short_v5e_100ms.xplane.pb")


@pytest.fixture(scope="module")
def recorded():
    from jax.profiler import ProfileData

    data = ProfileData.from_file(RECORDED)
    marks = trace.annotations(data)
    ops = trace.device_ops(data)
    return marks, ops


def test_the_recorded_trace_holds_what_the_reduction_looks_for(recorded):
    marks, ops = recorded
    names = [name for name, _, _ in marks]
    assert names[0] == trace.WINDOW_START and names[-1] == trace.WINDOW_END
    assert names[1:-1] == ["shape:q6"] * 8
    assert list(ops) == ["/device:TPU:0"] and len(ops["/device:TPU:0"]) == 200
    assert all(not name.startswith("%") or " = " not in name
               for name, _, _ in ops["/device:TPU:0"])


def test_recorded_busy_idle_and_gaps_by_hand(recorded):
    marks, ops = recorded
    lo, hi = marks[0][1], marks[-1][1]
    requests = [("q6", s, e) for name, s, e in marks if name == "shape:q6"]
    out = trace.reduce_events(ops, requests, lo, hi)
    # by hand: a timeline of the window in 100 ns steps, painted op by op
    steps = int((hi - lo) / 100) + 1
    busy = [False] * steps
    for _, s, e in ops["/device:TPU:0"]:
        for i in range(int((max(s, lo) - lo) / 100),
                       int((min(e, hi) - lo) / 100)):
            busy[i] = True
    inside = [False] * steps
    for _, s, e in requests:
        for i in range(int((s - lo) / 100), min(int((e - lo) / 100), steps)):
            inside[i] = True
    busy_s = sum(busy) * 100e-9
    assert out["window_s"] == pytest.approx(0.055421058)
    assert out["busy_s"] == pytest.approx(busy_s, rel=2e-3)
    assert out["busy_s"] == pytest.approx(0.005211901)
    assert out["idle_share"] == pytest.approx(1 - busy_s / out["window_s"],
                                              rel=1e-3)
    gaps = dict(out["breakdown"]["idle_gaps"])
    idle_in_requests = sum(1 for b, i in zip(busy, inside) if i and not b)
    idle_between = sum(1 for b, i in zip(busy, inside) if not i and not b)
    assert gaps["q6"] == pytest.approx(idle_in_requests * 100e-9, rel=2e-3)
    assert gaps[trace.BETWEEN] == pytest.approx(idle_between * 100e-9,
                                                rel=2e-2)
    assert gaps["q6"] == pytest.approx(0.049571475)
    # one Q6 keeps the chip busy for 0.65 ms of its 6.8 ms
    assert len(out["busy_s_by_shape"]["q6"]) == 8
    assert all(0.00064 < b < 0.00066 for b in out["busy_s_by_shape"]["q6"])
    top = dict(out["breakdown"]["device_ops"])
    assert top["%custom-call.8[X64SplitHigh]"] == pytest.approx(0.001130032)
    assert sum(trace.self_times(ops["/device:TPU:0"]).values()) \
        == pytest.approx(sum(e - s for s, e in trace.union(
            [(s, e) for _, s, e in ops["/device:TPU:0"]], -1e18, 1e18)))


def test_idle_under_two_clients_names_both():
    out = trace.idle_by_label([(0.0, 10.0)], [("q1", 2.0, 8.0),
                                              ("q6", 4.0, 12.0)])
    assert out == {trace.BETWEEN: 2.0, "q1": 2.0, "q1+q6": 4.0, "q6": 2.0}
