"""The load generator of the served surface: clients that speak the Presto
wire protocol to a server on 127.0.0.1.

Runs as a child process of ``chipbench.run`` and imports nothing but the
standard library, so it never touches JAX (one process holds the chip) and
does not share the server's interpreter lock.  The parent hands it the
requests it has drawn from the seed; this file only sends them and keeps
the clock.  Protocol on stdin/stdout, one JSON document per line:

    parent -> child   the job: base url, loop kind, clients, requests
    child  -> parent  {"ready": true}
    parent -> child   {"start_ns": <CLOCK_MONOTONIC>, "seconds": <s>}
    child  -> parent  {"records": [...]}

Times are ``time.monotonic_ns()``: CLOCK_MONOTONIC is one clock for every
process of the machine, so the parent can lay them beside its own.
"""
from __future__ import annotations

import json
import sys
import threading
import time
import urllib.error
import urllib.request


def http_sql(base: str, sql: str, poll_s: float, deadline_s: float) -> dict:
    """POST /v1/statement and follow every ``nextUri`` until the last page
    is in: {"columns", "rows", "stats", "error"}.  ``stats`` is the stats
    object of the first FINISHED page, which carries the engine's own
    report of the query (tier, phases, result-cache verdict)."""
    give_up = time.monotonic() + deadline_s
    request = urllib.request.Request(f"{base}/v1/statement",
                                     data=sql.encode(), method="POST")
    columns, rows, stats = None, [], None
    while True:
        try:
            with urllib.request.urlopen(request, timeout=deadline_s) as resp:
                body = json.load(resp)
        except (urllib.error.URLError, OSError, ValueError) as e:
            return {"columns": columns, "rows": rows, "stats": stats,
                    "error": f"{type(e).__name__}: {e}"}
        if "error" in body:
            return {"columns": columns, "rows": rows, "stats": stats,
                    "error": json.dumps(body["error"])[:500]}
        if body.get("columns"):
            columns = [c["name"] for c in body["columns"]]
        rows += body.get("data") or []
        page_stats = body.get("stats") or {}
        if stats is None and "phaseMillis" in page_stats:
            stats = page_stats
        if not body.get("nextUri"):
            return {"columns": columns, "rows": rows, "stats": stats,
                    "error": None}
        if time.monotonic() > give_up:
            return {"columns": columns, "rows": rows, "stats": stats,
                    "error": f"no answer within {deadline_s} s"}
        if page_stats.get("state") in ("QUEUED", "RUNNING"):
            time.sleep(poll_s)
        request = urllib.request.Request(body["nextUri"])


def _send(job: dict, client: int, request: dict, due_ns) -> dict:
    t0 = time.monotonic_ns()
    answer = http_sql(job["base"], request["sql"], job["poll_interval_s"],
                      job["deadline_s"])
    t1 = time.monotonic_ns()
    return {"id": request["id"], "client": client, "shape": request["shape"],
            "t0_ns": t0, "t1_ns": t1,
            # an open loop times a request from when it was due
            "latency_ms": (t1 - (t0 if due_ns is None else due_ns)) / 1e6,
            "late_ms": 0.0 if due_ns is None else (t0 - due_ns) / 1e6,
            **answer}


def _closed_loop(job, client, requests, start_ns, end_ns, out):
    """One client: the next request goes out when the last is answered."""
    while time.monotonic_ns() < start_ns:
        time.sleep(0.0005)
    for request in requests:
        if time.monotonic_ns() >= end_ns:
            break
        out.append(_send(job, client, request, None))


def _open_loop(job, client, requests, start_ns, end_ns, out, cursor, lock):
    """One of a pool of senders: requests go out when they are due
    (``due_s`` after the start), whether or not earlier ones are back."""
    while True:
        with lock:
            i = cursor[0]
            cursor[0] += 1
        if i >= len(requests):
            return
        due_ns = start_ns + int(requests[i]["due_s"] * 1e9)
        if due_ns >= end_ns:
            return
        while time.monotonic_ns() < due_ns:
            time.sleep(0.0005)
        out.append(_send(job, client, requests[i], due_ns))


def run_clients(job: dict, start_ns: int, seconds: float) -> list:
    end_ns = start_ns + int(seconds * 1e9)
    outs = [[] for _ in range(job["clients"])]
    if job["loop"] == "closed":
        threads = [threading.Thread(
            target=_closed_loop,
            args=(job, c, job["requests"][c], start_ns, end_ns, outs[c]))
            for c in range(job["clients"])]
    elif job["loop"] == "open":
        cursor, lock = [0], threading.Lock()
        threads = [threading.Thread(
            target=_open_loop,
            args=(job, c, job["requests"][0], start_ns, end_ns, outs[c],
                  cursor, lock)) for c in range(job["clients"])]
    else:
        raise SystemExit(f"chipbench.client: unknown loop {job['loop']!r}")
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return sorted((r for out in outs for r in out), key=lambda r: r["t0_ns"])


def main() -> int:
    job = json.loads(sys.stdin.readline())
    print(json.dumps({"ready": True}), flush=True)
    go = json.loads(sys.stdin.readline())
    records = run_clients(job, go["start_ns"], go["seconds"])
    print(json.dumps({"records": records}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
