"""Wall seconds per simulated cycle: the window's seconds over its cycles."""


def read(run):
    return run["seconds"] / run["cycles"]
