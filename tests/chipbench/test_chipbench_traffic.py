"""The one traffic generator: what the seed changes and what it does not."""
import itertools
import json
import threading
from http.server import BaseHTTPRequestHandler, HTTPServer

import pytest

from chipbench import client, run, traffic

SHAPES = {n: run.load_by_path("shapes", n) for n in ("q1", "q6", "q12", "q14")}


def _take(mix, seed, n):
    draws = traffic.Draws({k: SHAPES[k] for k in mix["shapes"]}, seed)
    return [list(itertools.islice(rs, n))
            for rs in traffic.requests_for_window(mix, draws, seed)]


@pytest.mark.parametrize("name", ["power", "streams2", "short"])
def test_mix_files_load_and_draw(name):
    mix = traffic.load_mix(name)
    assert mix["loop"] == "closed" and mix["repeat_share"] == 0.0
    lists = _take(mix, 2147483659, 40)
    assert len(lists) == mix["clients"]
    texts = [r["sql"] for rs in lists for r in rs]
    assert len(set(texts)) == len(texts)  # no text twice: no cache replay
    ids = [r["id"] for rs in lists for r in rs]
    assert len(set(ids)) == len(ids)


def test_no_mix_file_is_an_error():
    with pytest.raises(SystemExit, match="no traffic mix"):
        traffic.load_mix("no_such_mix")


def test_the_seed_changes_order_and_parameters_not_the_work():
    mix = traffic.load_mix("power")
    a, b, again = (_take(mix, s, 16)[0] for s in (1, 2, 1))
    assert [r["sql"] for r in a] == [r["sql"] for r in again]
    assert [r["sql"] for r in a] != [r["sql"] for r in b]
    for requests in (a, b):  # whole cycles: every shape once in each four
        for i in range(0, 16, 4):
            assert sorted(r["shape"] for r in requests[i:i + 4]) == sorted(
                mix["shapes"])


def test_weights_repeat_a_shape_within_a_cycle():
    mix = dict(traffic.load_mix("power"), shapes={"q6": 3, "q1": 1})
    shapes = [r["shape"] for r in _take(mix, 3, 8)[0]]
    assert shapes[:4].count("q6") == 3 and shapes[4:].count("q6") == 3


def test_repeat_share_reissues_fixed_texts():
    mix = dict(traffic.load_mix("streams2"), repeat_share=0.8, repeat_texts=8)
    requests = [r for rs in _take(mix, 4, 200) for r in rs]
    repeats = [r for r in requests if r["repeat"]]
    assert 0.7 < len(repeats) / len(requests) < 0.9
    assert len({r["sql"] for r in repeats}) <= 8
    fresh = [r["sql"] for r in requests if not r["repeat"]]
    assert len(set(fresh)) == len(fresh)


def test_an_open_loop_draws_due_times():
    mix = dict(traffic.load_mix("streams2"), loop="open", rate_per_s=50.0)
    (requests,) = _take(mix, 5, 500)
    due = [r["due_s"] for r in requests]
    assert due == sorted(due) and 8.0 < due[-1] < 12.0


class _Finished(BaseHTTPRequestHandler):
    def log_message(self, *args):
        pass

    def do_POST(self):
        body = json.dumps({"columns": [{"name": "x"}], "data": [[1]],
                           "stats": {"state": "FINISHED", "phaseMillis": {},
                                     "wallTimeMillis": 1}}).encode()
        self.send_response(200)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)


def test_the_open_loop_times_a_request_from_when_it_was_due():
    import time

    server = HTTPServer(("127.0.0.1", 0), _Finished)
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    try:
        job = {"base": f"http://127.0.0.1:{server.server_port}",
               "loop": "open", "clients": 2, "poll_interval_s": 0.001,
               "deadline_s": 5,
               "requests": [[{"id": i, "shape": "q6", "sql": "x",
                              "due_s": 0.02 * i} for i in range(20)]]}
        start = time.monotonic_ns() + 10_000_000
        records = client.run_clients(job, start, 0.3)
    finally:
        server.shutdown()
        server.server_close()
        thread.join(timeout=5)
    assert not thread.is_alive()
    assert len(records) == 15  # those due inside 0.3 s
    assert all(r["error"] is None and r["rows"] == [[1]] for r in records)
    assert all(r["late_ms"] >= 0 and r["latency_ms"] >= r["late_ms"]
               for r in records)


def _records(client, shapes, step_ms, start=0):
    out, t = [], start
    for shape in shapes:
        out.append({"client": client, "shape": shape, "error": None,
                    "t0_ns": t, "t1_ns": t + step_ms[shape] * 1_000_000})
        t = out[-1]["t1_ns"]
    return out


def test_queries_per_s_counts_whole_cycles():
    reader = run.load_by_path("metrics", "queries_per_s")
    cost = {"q1": 400, "q6": 10, "q12": 500, "q14": 500}  # 1.41 s a cycle
    whole = ["q1", "q6", "q12", "q14"] * 3
    window = {"start_ns": 0, "loop": "closed", "cycle": 4}
    for tail in ([], ["q6", "q1"], ["q12", "q14", "q1"]):
        window["records"] = _records(0, whole + tail, cost)
        assert reader.read({"window": window}) == pytest.approx(12 / 4.23)
    # two clients: each its own whole cycles, summed
    window["records"] = (_records(0, whole + ["q6"], cost)
                         + _records(1, whole[:8] + ["q12"], cost))
    assert reader.read({"window": window}) == pytest.approx(
        12 / 4.23 + 8 / 2.82)
    # a failed request ends its client's count
    window["records"] = _records(0, whole, cost)
    window["records"][9]["error"] = "refused"
    assert reader.read({"window": window}) == pytest.approx(8 / 2.82)
    # an open loop has no cycles
    window.update(loop="open", records=_records(0, whole[:6], cost))
    assert reader.read({"window": window}) == pytest.approx(6 / 1.82)
