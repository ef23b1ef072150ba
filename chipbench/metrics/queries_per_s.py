"""Requests answered, over the time they took, in whole cycles of the mix.

For each client of a closed loop: the requests of its whole cycles (a cycle
holds every shape ``weight`` times), over the span from the window's start
to the answer that ends its last whole cycle; summed over the clients.  The
request in flight when ``--seconds`` are up is finished, so the last cycle
is cut where the window is and not where a second falls.  Every counted
cycle is the same work: a count cut at the second would take two cheap
requests of a cycle in one run and two heavy ones in the next, and read
1.3 % apart on the same engine (PERF.md, Findings, PR 24).  An open loop
has no cycles: its requests answered, over the span to the last answer
(host clock).  A failed request ends the count of its client there.
"""


def read(run):
    window = run["window"]
    cycle = window["cycle"] if window["loop"] == "closed" else 1
    rate = 0.0
    for client in sorted({r["client"] for r in window["records"]}):
        mine = sorted((r for r in window["records"] if r["client"] == client),
                      key=lambda r: r["t0_ns"])
        good = next((i for i, r in enumerate(mine) if r["error"] is not None),
                    len(mine))
        whole = good - good % cycle
        if whole:
            span_s = (mine[whole - 1]["t1_ns"] - window["start_ns"]) / 1e9
            rate += whole / span_s
    return rate or None
