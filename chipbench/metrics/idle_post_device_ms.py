"""Median per request of the traced window: from the end of the request's
last device op to ``dsql:query`` closing (the root includes ``fetch``).
The answer's way back to the caller, and the chip waits.
One caller at a time: requests that overlap would each be given the
other's device work.  None without a trace, or with one that holds no
``dsql:query`` (a program from before the engine wrote any)."""
from chipbench.reduce import spans


def read(run):
    return spans.metric(run, "idle_post_device_ms")
