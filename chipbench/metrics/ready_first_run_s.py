"""Seconds of set-up spent waiting for programs' first runs: the engine's
``compile_first_run_ms`` counter, the ``materialize`` span (``first_run``)
of every round that compiled its program: the device's time for a run
whose answer may only say that a capacity was too small.  An engine
without the counter (before PR 38) has nothing to read."""
from chipbench.metrics.ready_trace_lower_s import setup_seconds


def read(run):
    return setup_seconds(run, "compile_first_run_ms")
