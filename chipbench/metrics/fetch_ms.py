"""Median per request of the engine's ``fetch`` span: the result table
from the device into a pandas frame.  The served surface hands rows to the
wire without such a span, so there is nothing to read there."""
import statistics


def read(run):
    values = [r["phases"]["fetch"] for r in run["window"]["records"]
              if "fetch" in r["phases"]]
    return statistics.median(values) if values else None
