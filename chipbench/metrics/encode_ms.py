"""Median per request of ``encode``, as the wire's ``phaseMillis`` carries
it: the result table into the rows and columns of the page a GET serves.
Only the served surface encodes; a server from before the phase existed
leaves nothing to read."""
from chipbench.reduce import spans


def read(run):
    return spans.phase_median(run, "encode", rehearsed=True)
