"""Q6's share of its roofline: the bytes its scan has to read
(``shapes/q6.py``) over the peak HBM bandwidth, against the device-busy
time of one Q6 request.  Memory-bound by construction: a few operations
for each byte."""
from chipbench import roofline


def read(run):
    return roofline.scan_roofline_share(run, "q6")
