"""The host control plane's per-layer metrics, read from span lists as the
harness hands them over (``(name, t0, t1)``, one copy per rank's row), and
from a traced run of a small cell on the CPU."""

import time

import pytest

from harness import trace as tracing
from harness.cell import load_module
from harness.runner import TRACE_DIR, run_cell

from test_check import small_cell

host_s = load_module("metrics", "host_s_per_cycle").read
transfer_s = load_module("metrics", "transfer_s_per_cycle").read
rebin_s = load_module("metrics", "rebin_s_per_cycle").read

PHASES = [("plan", 0.00, 0.05), ("scatter", 0.05, 0.10),
          ("tables", 0.10, 0.12), ("launch", 0.12, 0.13),
          ("wait", 0.13, 0.53), ("gather", 0.53, 0.60),
          ("repartition", 0.60, 0.61), ("stats", 0.90, 0.91)]


def cycle_spans(t, ranks):
    """One device-scheduled cycle starting at ``t``: the phases on every
    rank's row, ``rebin`` and its parts and ``cycle`` on rank 0's."""
    out = [(name, t + a, t + b) for name, a, b in PHASES
           for _ in range(ranks)]
    out += [("rebin", t + 0.61, t + 0.90), ("rebin.unbin", t + 0.61, t + 0.7),
            ("cycle", t, t + 0.92), ("observe", t + 0.92, t + 0.93)]
    return out


@pytest.mark.parametrize("ranks", [1, 4])
def test_readers_count_each_span_once(ranks):
    run = {"cycles": 2, "spans": cycle_spans(0.0, ranks)
           + cycle_spans(1.0, ranks)}
    # cycle 0.92 less wait 0.40
    assert host_s(run) == pytest.approx(0.52)
    # scatter 0.05 + tables 0.02 + gather 0.07
    assert transfer_s(run) == pytest.approx(0.14)
    assert rebin_s(run) == pytest.approx(0.29)


def test_readers_give_nothing_without_the_spans():
    # the spans of a program that records only the umbrella cycle, the
    # plan and the rebin
    run = {"cycles": 1, "spans": [("plan", 0.0, 0.1), ("plan", 0.0, 0.1),
                                  ("rebin", 0.5, 0.8), ("cycle", 0.0, 0.9)]}
    assert host_s(run) is None
    assert transfer_s(run) is None
    assert rebin_s(run) == pytest.approx(0.3)
    assert host_s({"cycles": 0, "spans": []}) is None


def test_traced_run_reports_the_host_metrics():
    """A traced window of the one-chip cell, small: the program's spans
    reach the profile as ``engine:<phase>`` host events, and the readers
    find them."""
    cell = small_cell("sedov3d_n60")
    result = run_cell(cell, 5, 0.5, True, t_start=time.perf_counter(),
                      log=lambda m: None)
    profile = tracing.summarize(tracing.find_xplane(str(TRACE_DIR
                                                        / cell.name)))
    names = {name for _, _, name in profile.host}
    for phase in [p[0] for p in PHASES] + ["rebin", "rebin.unbin",
                                           "rebin.bin_particles",
                                           "rebin.pair_list",
                                           "rebin.upload", "cycle"]:
        assert f"engine:{phase}" in names, phase
    metrics = result["metrics"]
    for name in ("host_s_per_cycle", "transfer_s_per_cycle",
                 "rebin_s_per_cycle"):
        assert metrics[name]["value"] > 0, name
    assert metrics["transfer_s_per_cycle"]["value"] \
        < metrics["host_s_per_cycle"]["value"]
    assert "collective_s_per_cycle" not in metrics
