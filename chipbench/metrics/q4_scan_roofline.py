"""Q4's share of its roofline: the bytes its scans have to read
(``shapes/q4.py``, every row of every column the text names, once: three
columns of orders, three of lineitem) over the peak HBM bandwidth, against
the device-busy time of one Q4 request.  Memory-bound by construction; what
the share leaves is what the SEMI join (3.8 M late line items on its build
side) costs above one pass over its inputs."""
from chipbench import roofline


def read(run):
    return roofline.scan_roofline_share(run, "q4")
