"""Host seconds per cycle in which the host control plane worked: the
engine's ``cycle`` span less its ``wait`` span (the boundary pull, where the
host blocks on the device's cycle program), over the window's cycles.
Recorded with ``observe`` on. A span on every rank's row is counted once;
a program without ``wait`` spans gives nothing."""


def read(run):
    spans = set(run["spans"])
    cycle = sum(t1 - t0 for name, t0, t1 in spans if name == "cycle")
    wait = [t1 - t0 for name, t0, t1 in spans if name == "wait"]
    if not cycle or not wait:
        return None
    return (cycle - sum(wait)) / run["cycles"]
