"""Median per request of the engine's ``parse`` and ``plan`` spans."""
import statistics


def read(run):
    values = [r["phases"]["parse"] + r["phases"]["plan"]
              for r in run["window"]["records"]
              if "parse" in r["phases"] and "plan" in r["phases"]]
    return statistics.median(values) if values else None
