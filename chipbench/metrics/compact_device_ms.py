"""Median, over the traced window's requests that compacted (Q12's and
Q14's: their ``device_ns_by_scope`` has ``dsql.compact``), of the device
self time of the ops whose innermost scope is ``dsql.compact``: the
learned-capacity compaction of the filtered rows below a join
(``_Tracer._maybe_compact``), index build and per-column gathers.
One caller at a time: requests that overlap would each be given the
other's device work.  None without a trace, with one that holds no
``dsql:query``, or where no request compacted."""
import statistics

from chipbench.reduce import spans

SCOPE = "dsql.compact"


def read(run):
    reduced = spans.of_run(run)
    if reduced is None:
        return None
    return median_ms(reduced["requests"])


def median_ms(requests):
    values = [r["device_ns_by_scope"][SCOPE] for r in requests
              if SCOPE in r["device_ns_by_scope"]]
    return statistics.median(values) / 1e6 if values else None
