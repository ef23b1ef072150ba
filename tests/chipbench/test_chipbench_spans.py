"""The reduction from a trace to the engine's own breakdown: on events
worked out by hand, on a file written here field by field, and on two
traces recorded on the chip (before and after the engine wrote its names
there)."""
import os
import shutil
import struct

import pytest

from chipbench import run
from chipbench.reduce import spans, trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
#: 55 ms of the short cell at PR 24: no ``dsql:`` event, modules ``jit_fn``
BEFORE = os.path.join(DATA, "short_v5e_100ms.xplane.pb")
#: the same at PR 25 (my chip run, PR 25): eight Q6 requests, each a
#: ``shape:q6`` round a ``dsql:query`` with its spans
RECORDED = os.path.join(DATA, "short_v5e_spans.xplane.pb")

NEW_METRICS = ("lookup_ms", "bind_ms", "dispatch_ms", "encode_ms",
               "device_programs_per_query", "idle_pre_dispatch_ms",
               "idle_post_device_ms", "join_device_ms")


# --- by hand ----------------------------------------------------------------

def test_scope_path_reads_the_engine_names_of_an_op_name():
    assert spans.plan_node(("dsql.LogicalJoin", "dsql.join_build")) \
        == "dsql.LogicalJoin"
    assert spans.plan_node(("dsql.LogicalJoin", "dsql.LogicalFilter",
                            "dsql.compact")) == "dsql.LogicalFilter"
    assert spans.plan_node(("dsql.input",)) is None
    assert spans.scope_path(
        "jit(dsql_LogicalAggregate_0a1b2c3d)/dsql.LogicalAggregate/"
        "dsql.LogicalJoin/dsql.join_build/sort:") == (
            "dsql.LogicalAggregate", "dsql.LogicalJoin", "dsql.join_build")
    assert spans.scope_path("dsql_input[4]:") == ("dsql.input",)
    assert spans.scope_path("jit(fn)/and:") == ()
    assert spans.scope_path("args[0]:") == () and spans.scope_path(None) == ()


def test_self_time_of_nested_ops():
    own = spans.self_times([(10.0, 50.0, ("a",)), (12.0, 20.0, ("a", "b")),
                            (25.0, 45.0, ()), (70.0, 80.0, ("c",))])
    assert [(o[0], o[2]) for o in own] == [
        (10.0, 40.0 - 8.0 - 20.0), (12.0, 8.0), (25.0, 20.0), (70.0, 10.0)]


def test_idle_goes_to_exactly_one_span_the_one_opened_last():
    out = spans.idle_by_span(
        [(0.0, 30.0), (40.0, 100.0)],
        [("query", 5.0, 90.0), ("execute", 10.0, 80.0),
         ("bind", 12.0, 20.0), ("materialize", 50.0, 70.0),
         # another thread's span, open while ``execute`` is
         ("stage", 60.0, 75.0)])
    assert out == {spans.BETWEEN: 5.0 + 10.0, "query": 5.0 + 10.0,
                   "execute": 2.0 + 10.0 + 10.0 + 5.0, "bind": 8.0,
                   "materialize": 10.0, "stage": 15.0}
    assert sum(out.values()) == 30.0 + 60.0


def _planes(ops, modules, host):
    return [{"name": "/device:TPU:0", "lines": {
                (spans.OPS_LINE, 3): [("%op", s, e, {"tf_op": name})
                                      for s, e, name in ops],
                (spans.MODULES_LINE, 2): [(name, s, e, {})
                                          for s, e, name in modules]}},
            {"name": spans.HOST_PLANE, "lines": {("main", 1): host}}]


def test_reduction_by_hand():
    join = "jit(dsql_x)/dsql.LogicalAggregate/dsql.LogicalJoin/"
    planes = _planes(
        ops=[(110.0, 150.0, join + "dsql.join_build/sort:"),
             (120.0, 130.0, join + "dsql.join_build/while/gt:"),
             # the join's input, lowered inside its scope: not the join
             (140.0, 145.0, join + "dsql.LogicalFilter/dsql.compact/gather:"),
             (150.0, 160.0, "dsql_input[1]:"),
             (300.0, 310.0, "jit(dsql_y)/dsql.LogicalFilter/and:"),
             (480.0, 490.0, "args[0]:")],
        modules=[(105.0, 106.0, "jit_convert_element_type(7)"),
                 (110.0, 160.0, "jit_dsql_x(8)"),
                 (300.0, 310.0, "jit_dsql_y(9)")],
        host=[(spans.WINDOW_START, 0.0, 1.0, {}),
              ("shape:q12", 95.0, 205.0, {}),
              ("dsql:query", 100.0, 200.0, {"seq": 4}),
              ("dsql:execute", 102.0, 190.0, {}),
              ("dsql:bind", 103.0, 108.0, {}),
              ("dsql:materialize", 140.0, 185.0, {}),
              ("shape:q6", 280.0, 330.0, {}),
              ("dsql:query", 290.0, 320.0, {"seq": 5}),
              # a request that leaves the window is left out
              ("dsql:query", 470.0, 520.0, {"seq": 6}),
              (spans.WINDOW_END, 500.0, 501.0, {})])
    out = spans.reduce_planes(planes)
    assert out["window_s"] == pytest.approx(500e-9)
    assert out["busy_s"] == pytest.approx((50 + 10 + 10) * 1e-9)
    assert [r["seq"] for r in out["requests"]] == [4, 5]
    first, second = out["requests"]
    assert first["shape"] == "q12" and second["shape"] == "q6"
    assert first["programs"] == ["jit_convert_element_type", "jit_dsql_x"]
    assert first["idle_pre_ns"] == 10.0 and first["idle_post_ns"] == 40.0
    assert first["device_ns_by_scope"] == {"dsql.join_build": 35.0,
                                           "dsql.compact": 5.0,
                                           "dsql.input": 10.0}
    assert first["join_ns"] == 35.0 and second["join_ns"] == 0
    assert out["device_programs_per_query"] == 1.5
    assert out["idle_pre_dispatch_ms"] == pytest.approx(10e-6)
    assert out["idle_post_device_ms"] == pytest.approx((40 + 10) / 2 * 1e-6)
    assert out["join_device_ms"] == pytest.approx(35e-6)   # of one request
    assert out["scoped_share"] == pytest.approx(60.0 / 70.0)
    assert out["modules"] == ["jit_convert_element_type", "jit_dsql_x",
                              "jit_dsql_y"]
    # idle is told from the first recorded op to the last: [160,300) and
    # [310,480); inside q12's request that is [160,200), span by span
    assert out["covered_s"] == pytest.approx((490 - 110) * 1e-9)
    assert out["idle_s_by_span"]["q12"] == {
        "materialize": pytest.approx(25e-9),
        "execute": pytest.approx(5e-9),
        "query": pytest.approx(10e-9)}
    assert out["idle_s_by_span"]["q6"] == {"query": pytest.approx(20e-9)}
    assert out["idle_s_by_span"][None] == {
        spans.BETWEEN: pytest.approx((90 + 150) * 1e-9)}
    assert out["device_s_by_scope"]["q6"] == {
        "dsql.LogicalFilter": pytest.approx(10e-9)}
    text = spans.render(out)
    assert "shape q12: 1 whole requests" in text and "dsql.join_build" in text


def test_a_trace_without_the_engine_names_reduces_to_no_request():
    out = spans.reduce_planes(_planes(
        ops=[(10.0, 20.0, "jit(fn)/and:")], modules=[(10.0, 20.0, "jit_fn(1)")],
        host=[("shape:q6", 5.0, 30.0, {})]))
    assert out["requests"] == [] and out["scoped_share"] == 0.0
    for name in NEW_METRICS[4:]:
        assert out[name] is None


def test_a_trace_without_device_operations_is_refused():
    with pytest.raises(SystemExit, match="no device operation"):
        spans.reduce_planes(_planes([], [], []))


# --- the wire format: a file written here, field by field --------------------

def _varint(value: int) -> bytes:
    out = b""
    while True:
        out += bytes([(value & 0x7F) | (0x80 if value > 0x7F else 0)])
        value >>= 7
        if not value:
            return out


def _field(number: int, value) -> bytes:
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    if isinstance(value, float):
        return _varint(number << 3 | 1) + struct.pack("<d", value)
    value = value.encode() if isinstance(value, str) else value
    return _varint(number << 3 | 2) + _varint(len(value)) + value


def _entry(key: int, message: bytes) -> bytes:
    return _field(1, key) + _field(2, message)


def test_the_reader_reads_a_file_written_field_by_field(tmp_path):
    stat_names = _field(5, _entry(1, _field(1, 1) + _field(2, "tf_op"))) \
        + _field(5, _entry(2, _field(1, 2) + _field(2, "seq"))) \
        + _field(5, _entry(3, _field(1, 3) + _field(2, "share")))
    op = _field(1, 7) + _field(2, "%fusion.1 = f32[8]{0} fusion(...)") \
        + _field(5, _field(1, 1) + _field(5, "jit(dsql_x)/dsql.LogicalFilter/and:"))
    module = _field(1, 8) + _field(2, "jit_dsql_x(123)")
    device = _field(2, "/device:TPU:0") + stat_names \
        + _field(4, _entry(7, op)) + _field(4, _entry(8, module)) \
        + _field(3, _field(1, 3) + _field(2, "XLA Ops") + _field(3, 1000)
                 + _field(4, _field(1, 7) + _field(2, 2_500_000)
                          + _field(3, 1_000_000)
                          + _field(4, _field(1, 3) + _field(2, 0.5)))) \
        + _field(3, _field(1, 2) + _field(2, "XLA Modules") + _field(3, 1000)
                 + _field(4, _field(1, 8) + _field(2, 2_000_000)
                          + _field(3, 2_000_000))) \
        + _field(3, _field(1, 4) + _field(2, "Async XLA Ops"))
    query = _field(1, 1) + _field(2, "dsql:query")
    other = _field(1, 2) + _field(2, "PjitFunction(fn)")
    host = _field(2, "/host:CPU") + stat_names \
        + _field(4, _entry(1, query)) + _field(4, _entry(2, other)) \
        + _field(3, _field(1, 22515) + _field(2, "main/22515")
                 + _field(3, 900)
                 + _field(4, _field(1, 2) + _field(2, 0) + _field(3, 5))
                 + _field(4, _field(1, 1) + _field(2, 50_000)
                          + _field(3, 6_000_000)
                          # seq rides as an int64; -1 takes ten bytes
                          + _field(4, _field(1, 2) + _field(4, 41))
                          + _field(4, _field(1, 3)
                                   + _field(4, (1 << 64) - 1))))
    path = tmp_path / "by_hand.xplane.pb"
    path.write_bytes(_field(1, device) + _field(1, host)
                     + _field(1, _field(2, "Task Environment"))
                     + _field(4, "some-host"))
    device_plane, host_plane = spans.read_planes(str(path))
    assert device_plane["lines"] == {
        ("XLA Ops", 3): [("%fusion.1 = f32[8]{0} fusion(...)", 3500.0, 4500.0,
                          {"tf_op": "jit(dsql_x)/dsql.LogicalFilter/and:",
                           "share": 0.5})],
        ("XLA Modules", 2): [("jit_dsql_x(123)", 3000.0, 5000.0, {})]}
    assert host_plane["lines"] == {("main/22515", 22515): [
        ("dsql:query", 950.0, 6950.0, {"seq": 41, "share": -1})]}
    out = spans.reduce(str(path))
    assert out["device_programs_per_query"] == 1
    assert out["requests"][0]["device_ns_by_scope"] == {
        "dsql.LogicalFilter": 1000.0}
    assert spans.reduce(str(path)) is out       # read once for the file


# --- metrics of a run -------------------------------------------------------

def _run(records=(), traced=None):
    return {"surface": "embedded", "trace": traced,
            "window": {"records": list(records)}}


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_new_metric_reads_nothing_where_there_is_nothing(name, tmp_path,
                                                            monkeypatch):
    """The rehearsal path and the parent's program: no trace, records
    without the phase; a reader returns None and does not raise."""
    read = run.load_by_path("metrics", name).read
    old = [{"phases": {"parse": 0.2, "execute": 5.0}}] * 3
    assert read(_run()) is None
    assert read(_run(old)) is None
    # a traced run whose trace directory holds no file
    monkeypatch.setattr(spans, "_ROOT", str(tmp_path))
    assert read(_run(old, traced={"busy_s": 1.0})) is None


def test_phase_metrics_take_the_median_of_the_records_that_have_it():
    records = [{"phases": {"bind": v, "encode": 2 * v}} for v in (3.0, 1.0, 2.0)]
    records.append({"phases": {"parse": 9.0}})
    traced = _run(records, traced={"busy_s": 1.0})
    assert run.load_by_path("metrics", "bind_ms").read(traced) == 2.0
    assert run.load_by_path("metrics", "encode_ms").read(traced) == 4.0
    assert run.load_by_path("metrics", "lookup_ms").read(traced) is None
    # a rehearsal (no trace) prints the wire's phase and not the executor's
    assert run.load_by_path("metrics", "bind_ms").read(_run(records)) is None
    assert run.load_by_path("metrics", "encode_ms").read(_run(records)) == 4.0


def test_trace_metrics_find_the_trace_where_the_harness_leaves_it(
        tmp_path, monkeypatch):
    where = tmp_path / ".chipbench_trace" / "plugins" / "profile" / "2026"
    where.mkdir(parents=True)
    shutil.copy(RECORDED, where / "host.xplane.pb")
    monkeypatch.setattr(spans, "_ROOT", str(tmp_path))
    traced = _run(traced={"busy_s": 1.0})
    by_name = {name: run.load_by_path("metrics", name).read(traced)
               for name in NEW_METRICS[4:]}
    assert by_name["device_programs_per_query"] == 6
    assert 0 < by_name["idle_pre_dispatch_ms"] < 10
    assert 0 < by_name["idle_post_device_ms"] < 10
    assert by_name["join_device_ms"] is None            # Q6 joins nothing
    # the parent's program under the same readers: nothing, and no error
    shutil.copy(BEFORE, where / "host.xplane.pb")
    os.utime(where / "host.xplane.pb", ns=(1, 1))
    assert all(run.load_by_path("metrics", name).read(traced) is None
               for name in NEW_METRICS[4:])


# --- the traces recorded on the chip ----------------------------------------

@pytest.fixture(scope="module")
def recorded():
    return spans.reduce(RECORDED), spans.read_planes(RECORDED)


def test_the_reader_agrees_with_profile_data(recorded):
    """The same events as ``jax.profiler.ProfileData`` gives ``trace.py``
    (which cuts starts and durations to whole nanoseconds; the file holds
    picoseconds), and the stats it does not give."""
    from jax.profiler import ProfileData

    _, planes = recorded
    data = ProfileData.from_file(RECORDED)
    ops = trace.device_ops(data)["/device:TPU:0"]
    mine = planes[0]["lines"][(spans.OPS_LINE, 3)]
    assert len(mine) == len(ops) > 100
    for (name, start, end, stats), (short, s, e) in zip(mine, ops):
        assert trace.op_name(name) == short
        assert 0 <= start - s < 1 and 0 <= end - e < 2
    marks = [a for a in trace.annotations(data)]
    host = [e for events in planes[1]["lines"].values() for e in events
            if e[0].startswith(("shape:", "chipbench:"))]
    assert sorted((n, round(s)) for n, s, _ in marks) \
        == sorted((n, round(s)) for n, s, _, _ in host)
    assert any(stats.get("tf_op", "").startswith("jit(dsql_")
               for _, _, _, stats in mine)


def test_recorded_device_time_lies_under_the_engine_scopes(recorded):
    out, _ = recorded
    assert out["scoped_share"] >= 0.95
    by_scope = out["device_s_by_scope"]["q6"]
    assert set(by_scope) >= {"dsql.input", "dsql.LogicalFilter",
                             "dsql.LogicalAggregate"}
    # most of Q6's device time splits f64 columns into f32 pairs
    assert by_scope["dsql.input"] > 0.5 * sum(by_scope.values())
    assert any(m.startswith("jit_dsql_Logical") for m in out["modules"])
    assert "jit_fn" not in out["modules"]


def test_recorded_idle_inside_a_request_goes_to_exactly_one_span(recorded):
    out, planes = recorded
    assert len(out["requests"]) == 8
    assert {r["shape"] for r in out["requests"]} == {"q6"}
    seqs = [r["seq"] for r in out["requests"]]
    assert seqs == list(range(seqs[0], seqs[0] + 8))
    # by hand: the window in 100 ns steps, painted op by op, then request
    # by request; what is idle and inside a request has to equal, to the
    # rounding of the steps, what the spans were given together
    host = {n: s for events in planes[1]["lines"].values()
            for n, s, _, _ in events}
    lo, hi = host[spans.WINDOW_START], host[spans.WINDOW_END]
    steps = int((hi - lo) / 100) + 1
    busy, inside = [False] * steps, [False] * steps
    for _, s, e, _ in planes[0]["lines"][(spans.OPS_LINE, 3)]:
        for i in range(int((max(s, lo) - lo) / 100),
                       int((min(e, hi) - lo) / 100)):
            busy[i] = True
    for r in out["requests"]:
        for i in range(int((r["start_ns"] - lo) / 100),
                       int((r["end_ns"] - lo) / 100)):
            inside[i] = True
    ops = planes[0]["lines"][(spans.OPS_LINE, 3)]
    first = int((min(s for _, s, _, _ in ops) - lo) / 100)
    last = int((max(e for _, _, e, _ in ops) - lo) / 100)
    by_hand = sum(1 for k, (b, i) in enumerate(zip(busy, inside))
                  if i and not b and first <= k < last) * 100e-9
    given = out["idle_s_by_span"]["q6"]
    assert sum(given.values()) == pytest.approx(by_hand, rel=2e-3)
    # the phases a request passes through, each with a share of the wait
    assert set(given) >= {"parse", "plan", "lookup", "bind", "dispatch",
                          "materialize", "fetch", "result_cache"}
    assert given["bind"] > given["lookup"]
    total = out["covered_s"] - out["busy_s"]
    everything = sum(v for spans_ in out["idle_s_by_span"].values()
                     for v in spans_.values())
    assert everything == pytest.approx(total)


def test_recorded_programs_per_query_are_borne_out_by_the_modules_line(recorded):
    out, planes = recorded
    modules = planes[0]["lines"][(spans.MODULES_LINE, 2)]
    per_query = out["device_programs_per_query"]
    assert per_query == int(per_query) == 6
    in_requests = sum(len(r["programs"]) for r in out["requests"])
    assert in_requests == 8 * 6 <= len(modules)
    for r in out["requests"]:
        names = r["programs"]
        assert sum(n.startswith("jit_dsql_Logical") for n in names) == 1
        assert names.count("jit_convert_element_type") == 5


def test_the_trace_of_the_parent_reads_as_before(recorded):
    """What the benchmark adds has to run on a program that lacks what
    this PR adds to the program: busy time and window as ``trace.py`` has
    them, no request, nothing under a scope."""
    out = spans.reduce(BEFORE)
    assert out["window_s"] == pytest.approx(0.055421058)
    assert out["busy_s"] == pytest.approx(0.005211901, rel=1e-4)
    assert out["requests"] == [] and out["scoped_share"] == 0.0
    assert out["modules"] == ["jit_convert_element_type", "jit_fn"]
    assert out["idle_s_by_span"] == {None: {spans.BETWEEN: pytest.approx(
        out["covered_s"] - out["busy_s"])}}
    assert 0.0554 - 0.007 < out["covered_s"] < 0.0554
