"""Q18's share of its roofline: the bytes its scans have to read
(``shapes/q18.py``, every row of every column the text names, once: the
text names lineitem twice) over the peak HBM bandwidth, against the
device-busy time of one Q18 request.  Memory-bound by construction; what the
share leaves is what the group-by of 6 M rows into 1.5 M groups, the two
joins and the SEMI join cost above one pass over their inputs."""
from chipbench import roofline


def read(run):
    return roofline.scan_roofline_share(run, "q18")
