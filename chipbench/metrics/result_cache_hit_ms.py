"""Median, over the window's statements the result cache answered, of the
engine's ``result_cache`` span: the key (a canonical fingerprint of the
plan and the epochs of the tables it reads) and the probe that found the
answer.  None where no statement of the window was a replay."""
import statistics


def read(run):
    values = [r["phases"]["result_cache"] for r in run["window"]["records"]
              if r["cache_hit"] and "result_cache" in r["phases"]]
    return statistics.median(values) if values else None
