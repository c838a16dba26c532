"""Host seconds per cycle in the engine's own ``rebin`` span (binning the
particles into cells again), recorded with ``observe`` on."""


def read(run):
    spans = [t1 - t0 for name, t0, t1 in run["spans"] if name == "rebin"]
    if not spans:
        return None
    return sum(spans) / run["cycles"]
