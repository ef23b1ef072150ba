"""From the first statement's submission at set-up until every shape of the
mix has been served by a compiled program on ``1 + warm_extra`` new
parameter sets, shapes one at a time, and no compile is left in flight.
The first run in a checkout pays the cold compile wall here; later runs
the restart path (host clock)."""


def read(run):
    return run["setup"]["ready_s"]
