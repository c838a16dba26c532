"""Particle updates (force evaluations received) per wall second of the
window, from each cycle's ``updates``."""


def read(run):
    return sum(s["updates"] for s in run["cycle_stats"]) / run["seconds"]
