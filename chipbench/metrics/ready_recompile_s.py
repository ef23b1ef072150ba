"""Seconds of set-up that the capacities' ladder cost: the engine's
``compile_recompile_ms`` counter, ``compile`` span + first run of every
round whose ``cause`` is not ``first`` (a group cap that overflowed, a
compaction site tightened or gone live, a refuted ordered-probe hint, a
plan split after its program failed).  It overlaps the four terms by phase
(``ready_trace_lower_s``, ``ready_xla_compile_s``, ``ready_cache_load_s``,
``ready_first_run_s``): they say where the time went, this one why.  An
engine without the counter (before PR 38) has nothing to read."""
from chipbench.metrics.ready_trace_lower_s import setup_seconds


def read(run):
    return setup_seconds(run, "compile_recompile_ms")
