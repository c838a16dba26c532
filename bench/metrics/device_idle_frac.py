"""Share of the traced window in which no operation ran on the device,
averaged over the cell's chips."""


def read(run):
    tr = run["trace"]
    if tr is None or not tr.devices:
        return None
    return 1.0 - tr.busy_s() / tr.window_s
