"""Seconds of set-up that JAX spent tracing and lowering the programs the
engine compiled: its ``compile_trace_ms`` + ``compile_lower_ms`` counters,
the ``compile_trace`` and ``compile_lower`` children of every ``compile``
span that closed before the window opened, foreground or background.  In a
cell whose first arrivals the eager tier answers the compiles run beside the
arrivals, so the term overlaps ``ready_s`` there and is no part of it.  An
engine without the counters (before PR 38) has nothing to read."""


def setup_counters(run, *names):
    """The named engine counters summed over set-up: ``Meter._delta`` leaves
    out a counter that did not move, so a missing key reads 0 where the
    engine has the counter, and None on an engine without it."""
    from dask_sql_tpu.runtime import telemetry

    if not all(name in telemetry.STABLE_COUNTERS for name in names):
        return None
    counters = run["setup"]["counters"]
    return sum(counters.get(name, 0) for name in names)


def setup_seconds(run, *names):
    """The same for counters of whole milliseconds, in seconds."""
    ms = setup_counters(run, *names)
    return None if ms is None else ms / 1e3


def read(run):
    return setup_seconds(run, "compile_trace_ms", "compile_lower_ms")
