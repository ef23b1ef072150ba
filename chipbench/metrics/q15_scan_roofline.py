"""Q15's share of its roofline: the bytes its scans have to read
(``shapes/q15.py``, every row of every column the text names, once: the
CTE is written once, whatever reads it twice) over the peak HBM bandwidth,
against the device-busy time of one Q15 request.  Memory-bound by
construction; what the share leaves is what the two copies of the grouped
aggregate, the scalar subquery's MAX and the join to supplier cost above
one pass over their inputs."""
from chipbench import roofline


def read(run):
    return roofline.scan_roofline_share(run, "q15")
