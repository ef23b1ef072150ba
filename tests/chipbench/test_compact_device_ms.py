"""``compact_device_ms``: the device time of the compaction below a join,
by request, from the trace's ``dsql.compact`` scope."""
import os
import shutil

import pytest

from chipbench import run
from chipbench.reduce import spans

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
#: eight Q6 requests of the short cell (my chip run, PR 25): none compacts
RECORDED = os.path.join(DATA, "short_v5e_spans.xplane.pb")


@pytest.fixture(scope="module")
def metric():
    return run.load_by_path("metrics", "compact_device_ms")


def _traced():
    return {"surface": "embedded", "trace": {"busy_s": 1.0},
            "window": {"records": []}}


def test_the_benchmark_lists_it_where_a_request_compacts(bench):
    entry, = [m for m in bench["per_layer"] if m["name"] == "compact_device_ms"]
    assert entry == {"name": "compact_device_ms", "unit": "ms",
                     "better": "lower", "source": "device_trace",
                     "layer": "kernels", "moves": "query_p90_ms",
                     "workloads": ["tpch_sf1_embedded.power"]}
    assert bench["per_layer"][-1] is entry       # appended, nothing moved


def test_none_without_a_trace(metric, tmp_path, monkeypatch):
    assert metric.read({"surface": "embedded", "trace": None,
                        "window": {"records": []}}) is None
    # a traced run whose trace directory holds no file
    monkeypatch.setattr(spans, "_ROOT", str(tmp_path))
    assert metric.read(_traced()) is None


def test_none_on_the_recorded_short_trace(metric, tmp_path, monkeypatch):
    where = tmp_path / ".chipbench_trace" / "plugins" / "profile" / "2026"
    where.mkdir(parents=True)
    shutil.copy(RECORDED, where / "host.xplane.pb")
    monkeypatch.setattr(spans, "_ROOT", str(tmp_path))
    assert len(spans.of_run(_traced())["requests"]) == 8
    assert metric.read(_traced()) is None


@pytest.mark.parametrize("compact_ns, want", [
    ([], None),
    ([30e6], 30.0),
    ([495e6, 20e6, 506e6], 495.0),
    ([20e6, 30e6, 22e6, 40e6], 26.0),
])
def test_median_over_the_requests_that_compacted(metric, compact_ns, want):
    requests = [{"device_ns_by_scope": {"dsql.join_probe": 40e6,
                                        "dsql.compact": ns}}
                for ns in compact_ns]
    # Q1 and Q6 between them: they run no compaction and count for nothing
    requests[1:1] = [{"device_ns_by_scope": {"dsql.groupby_limbs": 420e6}},
                     {"device_ns_by_scope": {}}]
    assert metric.median_ms(requests) == want
