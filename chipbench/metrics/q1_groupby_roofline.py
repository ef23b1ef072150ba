"""Q1's share of its roofline: the bytes of the seven columns its scan reads
(``shapes/q1.py``) over the peak HBM bandwidth, against the device-busy
time of one Q1 request.  The bound that applies is memory's: eight sums
over six million rows are some tens of operations for each row read."""
from chipbench import roofline


def read(run):
    return roofline.scan_roofline_share(run, "q1")
