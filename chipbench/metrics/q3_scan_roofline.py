"""Q3's share of its roofline: the bytes its scans have to read
(``shapes/q3.py``, every row of every column the text names, once) over
the peak HBM bandwidth, against the device-busy time of one Q3 request.
Memory-bound by construction: a join or a group-by does a few operations
for each byte it reads; what the share leaves is what the joins, the
group-by and the top-N cost above one pass over their inputs."""
from chipbench import roofline


def read(run):
    return roofline.scan_roofline_share(run, "q3")
