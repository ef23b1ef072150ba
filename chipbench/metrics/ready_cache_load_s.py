"""Seconds of set-up spent reading executables out of XLA's persistent
cache: the engine's ``compile_cache_load_ms`` counter, the ``compile_xla``
children of set-up's ``compile`` spans whose ``xla_cache`` is ``hit`` (the
file's read, its decompression and the executable's deserialization onto the
device).  What a restarted deployment pays in place of the compile; 0 on a
tree's first run.  An engine without the counter (before PR 38) has nothing
to read."""
from chipbench.metrics.ready_trace_lower_s import setup_seconds


def read(run):
    return setup_seconds(run, "compile_cache_load_ms")
