"""Share of the cycle scan's ladder trips that did force work: each
cycle's ``force_substeps`` over its ``substeps`` (2**depth trips)."""


def read(run):
    stats = run["cycle_stats"]
    if not all("substeps" in s and "force_substeps" in s for s in stats):
        return None
    trips = sum(s["substeps"] for s in stats)
    if not trips:
        return None
    return sum(s["force_substeps"] for s in stats) / trips
