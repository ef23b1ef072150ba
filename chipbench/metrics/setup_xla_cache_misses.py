"""JAX persistent-compile-cache misses during set-up: programs compiled
although the cache directory is kept.  On the first run in a checkout that
is every program of the cell; on a later run each one is something a
restarted deployment compiles again."""


def read(run):
    return run["setup"]["counters"].get("xla_cache_misses", 0)
