"""Median over the whole requests of the traced window of the programs
the chip ran inside one (``XLA Modules`` events that start inside the
request's ``dsql:query``): the compiled program, and every small one the
host launches round it.
One caller at a time: requests that overlap would each be given the
other's device work.  None without a trace, or with one that holds no
``dsql:query`` (a program from before the engine wrote any)."""
from chipbench.reduce import spans


def read(run):
    return spans.metric(run, "device_programs_per_query")
