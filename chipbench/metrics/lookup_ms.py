"""Median per request of the engine's ``lookup`` span: from the plan to
the program in hand (literals hoisted, plan and input fingerprints, key
build, the program cache's probe, any wait for a compile in flight).  Sums
over a staged query's stages.  A program from before the span existed
leaves nothing to read."""
from chipbench.reduce import spans


def read(run):
    return spans.phase_median(run, "lookup")
