"""Process start to the window's start: device, generate, create_table,
ready (host clock)."""


def read(run):
    return run["setup"]["setup_s"]
