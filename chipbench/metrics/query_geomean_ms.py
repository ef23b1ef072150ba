"""Geometric mean, over the mix's shapes, of each shape's median client-side
latency in the window: every shape weighs alike, as in TPC-H's power
metric.  Host clock, all of the window's requests."""
import math
import statistics


def read(run):
    by_shape = {}
    for r in run["window"]["records"]:
        by_shape.setdefault(r["shape"], []).append(r["latency_ms"])
    if not by_shape:
        return None
    medians = [statistics.median(v) for v in by_shape.values()]
    return math.exp(sum(math.log(m) for m in medians) / len(medians))
