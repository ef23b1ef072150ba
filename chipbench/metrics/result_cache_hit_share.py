"""Share (%) of the window's statements the result cache answered: the
engine's ``result_cache_hits`` over hits plus misses, after less before.
A mix that re-issues texts (``repeat_share``) reads about that share; a mix
of new texts reads 0.  None where the window probed the cache for nothing
(it is off, or every statement bypassed it)."""


def read(run):
    counters = run["window"]["counters"]
    hits = counters.get("result_cache_hits", 0)
    probes = hits + counters.get("result_cache_misses", 0)
    return 100.0 * hits / probes if probes else None
