"""Seconds from process start to the first timed cycle: initial condition,
build, binning and partition, compile or cache load, one warm episode."""


def read(run):
    return run["setup_s"]
