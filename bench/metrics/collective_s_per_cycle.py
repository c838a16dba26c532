"""Device seconds per cycle in collective operations (all-gather,
all-reduce, collective-permute, ...), averaged over the cell's chips.
Nothing to read on one chip."""


def read(run):
    tr = run["trace"]
    if tr is None or run["chips"] < 2:
        return None
    return tr.collective_s() / run["cycles"]
