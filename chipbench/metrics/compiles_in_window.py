"""Programs compiled while the window was open: the engine's ``compiles``,
``recompiles`` and ``spmd_compiles`` counters, after less before.  A steady
engine reads 0; anything else names a shape whose program was not ready
or whose capacity class moved."""


def read(run):
    counters = run["window"]["counters"]
    return sum(counters.get(k, 0)
               for k in ("compiles", "recompiles", "spmd_compiles"))
