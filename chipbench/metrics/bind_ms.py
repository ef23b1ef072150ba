"""Median per request of the engine's ``bind`` span: from the program in
hand to its argument list complete (the resident columns gathered, every
hoisted literal put on the device).  Sums over a staged query's stages.  A
program from before the span existed leaves nothing to read."""
from chipbench.reduce import spans


def read(run):
    return spans.phase_median(run, "bind")
