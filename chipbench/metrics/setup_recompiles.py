"""Programs compiled again at set-up because a run's flags asked for other
capacities: the engine's ``recompiles`` counter before the window opened
(since PR 38 the sum of ``recompiles_overflow``, ``recompiles_tighten`` and
``recompiles_hint``, which a ``ready`` line's counters show a shape).
``compiles_in_window`` counts the window's."""
from chipbench.metrics.ready_trace_lower_s import setup_counters


def read(run):
    return setup_counters(run, "recompiles")
