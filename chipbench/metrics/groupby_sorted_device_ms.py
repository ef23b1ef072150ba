"""Median, over the traced window's requests that ran a dynamic-domain
grouped aggregate (Q3's and Q10's: their ``device_ns_by_scope`` has
``dsql.groupby_sorted``), of the device self time of the ops whose
innermost scope it is: the group codes (a hash table) and the segment
aggregates.  A static-domain group-by (Q1's, Q5's) is ``dsql.groupby_limbs``
and is not counted.  One caller at a time.  None without a trace, with one
that holds no ``dsql:query``, or where no request ran the scope (a program
from before the engine named it)."""
import statistics

from chipbench.reduce import spans

SCOPE = "dsql.groupby_sorted"


def read(run):
    reduced = spans.of_run(run)
    if reduced is None:
        return None
    return median_ms(reduced["requests"])


def median_ms(requests):
    values = [r["device_ns_by_scope"][SCOPE] for r in requests
              if SCOPE in r["device_ns_by_scope"]]
    return statistics.median(values) / 1e6 if values else None
