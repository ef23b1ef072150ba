"""Median over the whole requests of the traced window of the stage
programs one ran: ``dsql:stage`` events that start inside the request's
``dsql:query``.  0 where every shape runs as one whole-plan program (the
executor cuts a plan into stages above its heavy-node budget,
``physical/stages.py``).  None without a trace, or with one that holds no
``dsql:query``."""
from chipbench.reduce import stages


def read(run):
    return stages.stages_per_query(run)
