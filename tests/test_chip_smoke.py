"""``chip_smoke.py``: it refuses the CPU, and its phases agree on the CPU.

The script itself only reports on a TPU; here its phase functions run at
the smallest lattice, where the production path and the host-ladder oracle
agree bitwise.
"""

import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402


def test_chip_smoke_refuses_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py"),
         "--n-side", "4"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    assert "needs a TPU" in proc.stderr
    # it stops before any phase: the device line is all it prints
    assert proc.stdout.strip().splitlines() == [
        line for line in proc.stdout.strip().splitlines()
        if line.startswith("device:")]


@pytest.fixture(scope="module")
def smoke_runs():
    clock = chip_smoke.CompileClock()
    spec = chip_smoke.production_spec(6, 1)
    res = chip_smoke.run_production(spec, clock)
    ref = chip_smoke.run_reference(spec, res["cycles"])
    return res, ref, clock


def test_chip_smoke_production_phase(smoke_runs):
    res, _, clock = smoke_runs
    chip_smoke.check_production(res)
    sanity = chip_smoke.check_sanity(res)
    assert sanity["momentum_drift"] <= chip_smoke.MOMENTUM_DRIFT_MAX
    assert res["cycles"] == chip_smoke.SEGMENTS * chip_smoke.SEGMENT_CYCLES
    assert res["compile_s"] > 0 and clock.compiles > 0
    # every program compiles in the first segment; the rest reuse them
    assert res["segment_compiles"][-1] == 0


def test_chip_smoke_oracle_is_bitwise_on_cpu(smoke_runs):
    res, ref, _ = smoke_runs
    diffs = chip_smoke.field_diffs(res["state"], ref["state"])
    assert all(d["max_abs"] == 0.0 for d in diffs.values()), diffs
    assert res["energy"][1] == ref["energy"]


def test_chip_smoke_density_matches_nsquared(smoke_runs):
    res, _, _ = smoke_runs
    rel = chip_smoke.density_error(res)
    assert np.isfinite(rel) and rel <= chip_smoke.DENSITY_RTOL
