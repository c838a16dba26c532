"""Peak device memory of the fullest chip after the window, in GB."""


def read(run):
    return run["memory_peak_bytes"] / 1e9 or None
