"""Median, over the traced window's requests that ran a join (Q12's and
Q14's), of the device self time of the ops lowered from the
``LogicalJoin`` node itself: ``dsql.LogicalJoin`` is the innermost plan
node of the op_name (``dsql.join_build`` and ``dsql.join_probe`` are
kernels inside it).  The join's inputs are lowered inside its scope too,
and are not the join: a filter's compaction below it is not counted.
One caller at a time: requests that overlap would each be given the
other's device work.  None without a trace, or with one that holds no
``dsql:query`` (a program from before the engine wrote any)."""
from chipbench.reduce import spans


def read(run):
    return spans.metric(run, "join_device_ms")
