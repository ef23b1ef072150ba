"""90th percentile per request of the workload manager's ``queued`` span
(admission and pool wait), as the served surface reports it."""
import statistics


def read(run):
    values = [r["phases"]["queued"] for r in run["window"]["records"]
              if "queued" in r["phases"]]
    if len(values) < 2:
        return None
    return statistics.quantiles(values, n=10, method="inclusive")[8]
