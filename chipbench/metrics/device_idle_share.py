"""The share of the traced window in which no operation ran on the chip:
1 - union of the device's op intervals over the window's length."""


def read(run):
    trace = run["trace"]
    return None if trace is None else 100.0 * trace["idle_share"]
