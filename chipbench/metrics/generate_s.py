"""Host clock round ``chipbench/data/<generator>.py::generate``."""


def read(run):
    return run["setup"]["generate_s"]
