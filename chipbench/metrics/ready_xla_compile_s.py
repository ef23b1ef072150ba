"""Seconds of set-up inside XLA's compiler: the engine's ``compile_xla_ms``
counter, the ``compile_xla`` children of set-up's ``compile`` spans whose
program XLA's persistent cache did not hold (``xla_cache`` ``miss``, or
``off``).  A warm run reads 0: every program was read
(``ready_cache_load_s``); ``setup_xla_cache_misses`` counts the same
programs from outside.  An engine without the counter (before PR 38) has
nothing to read."""
from chipbench.metrics.ready_trace_lower_s import setup_seconds


def read(run):
    return setup_seconds(run, "compile_xla_ms")
