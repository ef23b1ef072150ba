"""Median per request of the traced window of the chip's idle time between
the request's first and last device op: the host between a staged query's
stage programs, and in a whole-plan program the gaps between the small
programs launched for its literals and the program itself
(``chipbench/reduce/stages.py``).  One caller at a time.  None without a
trace, or with one that holds no ``dsql:query``."""
from chipbench.reduce import stages


def read(run):
    return stages.stage_handoff_ms(run)
