"""Median per request of the traced window: from ``dsql:query`` opening to
the start of the first device op of the request.  Parse, plan, lookup and
bind happen here, and the chip waits.
One caller at a time: requests that overlap would each be given the
other's device work.  None without a trace, or with one that holds no
``dsql:query`` (a program from before the engine wrote any)."""
from chipbench.reduce import spans


def read(run):
    return spans.metric(run, "idle_pre_dispatch_ms")
