"""Median per request of the engine's ``dispatch`` span: the host's cost
of launching the compiled program (the call returns before the device is
done).  Sums over a staged query's stages.  A program from before the span
existed leaves nothing to read."""
from chipbench.reduce import spans


def read(run):
    return spans.phase_median(run, "dispatch")
