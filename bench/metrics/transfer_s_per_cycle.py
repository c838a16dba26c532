"""Host seconds per cycle that move the cycle's state and tables: the
engine's ``scatter`` (global state onto the ranks' device buffers),
``tables`` (segment control tables and scalars uploaded) and ``gather``
(owned rows back to the host) spans, over the window's cycles. Recorded
with ``observe`` on. A span on every rank's row is counted once; a program
without all three spans gives nothing."""

PHASES = ("scatter", "tables", "gather")


def read(run):
    spans = [(name, t1 - t0) for name, t0, t1 in set(run["spans"])
             if name in PHASES]
    if {name for name, _ in spans} != set(PHASES):
        return None
    return sum(s for _, s in spans) / run["cycles"]
