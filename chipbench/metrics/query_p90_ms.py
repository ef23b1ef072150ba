"""90th percentile of client-side latency over every request of the window
(host clock).  p90 and not p95: the heavy shapes take about half a second,
a window completes some 150 requests, and ten samples have to lie beyond
the percentile."""
import statistics


def read(run):
    latencies = [r["latency_ms"] for r in run["window"]["records"]]
    if len(latencies) < 2:
        return None
    return statistics.quantiles(latencies, n=10, method="inclusive")[8]
