"""Device time of the SEMI and ANTI joins (the planner's form of ``EXISTS``
and ``IN (SELECT ..)``) in one cycle of the mix: for each shape whose traced
requests ran one (their ``device_ns_by_scope`` has ``dsql.semi_build`` or
``dsql.semi_probe``: Q4's, Q18's), the median over its requests of the
device self time of the ops whose innermost scope is one of the two (the
build side's table or sort, and the probe), and the sum of those medians
over the shapes.  A sum and not a median over all requests: the shapes'
semi joins differ by a factor of ten (Q4 builds over lineitem, Q18 over a
few orders), and a median over them all reads the smaller whatever the
larger does.  A join that fetches columns is ``dsql.join_build`` /
``dsql.join_probe`` and is not counted; nor are the inputs lowered inside
the join's node.  One caller at a time.  None without a trace, with one
that holds no ``dsql:query``, or where no request ran the scopes (a program
from before the engine named them: there a SEMI join reads as
``dsql.join_*``)."""
import statistics

from chipbench.reduce import spans

SCOPES = ("dsql.semi_build", "dsql.semi_probe")


def read(run):
    reduced = spans.of_run(run)
    if reduced is None:
        return None
    return cycle_ms(reduced["requests"])


def cycle_ms(requests):
    by_shape = {}
    for r in requests:
        if any(s in r["device_ns_by_scope"] for s in SCOPES):
            by_shape.setdefault(r["shape"], []).append(
                sum(r["device_ns_by_scope"].get(s, 0) for s in SCOPES))
    if not by_shape:
        return None
    return sum(statistics.median(v) for v in by_shape.values()) / 1e6
