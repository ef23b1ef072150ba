"""Programs obtained by compiling while the window was open, in a cell whose
every shape holds a subquery: ``compiles_in_window``'s reading (the engine's
``compiles``, ``recompiles`` and ``spmd_compiles`` counters, after less
before) under a name that moves ``query_geomean_ms``: that metric moves
``query_p90_ms``, which a cell of four shapes and some forty requests a
window does not report.  0 where every literal a request moves is a
parameter of its shape's program; one a request where a literal inside a
scalar subquery's body is part of the program's key (a Q15 before PR 43:
each new date a new whole-plan compile)."""
from chipbench.metrics.compiles_in_window import read  # noqa: F401
