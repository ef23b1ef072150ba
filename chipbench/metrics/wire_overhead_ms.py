"""Median, over the window's requests, of the client's latency less the
engine's own wall time of that statement (the wire's ``wallTimeMillis``):
what the server, the protocol, polling and JSON add.  Only the served
surface has a wire."""
import statistics


def read(run):
    if run["surface"] != "served":
        return None
    over = [r["latency_ms"] - r["engine_wall_ms"]
            for r in run["window"]["records"]
            if r["error"] is None and r["engine_wall_ms"] is not None]
    return statistics.median(over) if over else None
