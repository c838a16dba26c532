"""Seconds per cycle in which an operation ran on the device (the union
of the trace's operation intervals), averaged over the cell's chips."""


def read(run):
    tr = run["trace"]
    if tr is None or not tr.devices:
        return None
    return tr.busy_s() / run["cycles"]
