"""The device cycle scan's per-trip branches against the host ladder.

Each trip of ``build_cycle_scan_program`` skips (nothing active anywhere),
runs its pair passes over the live pairs compacted into a bucket of 1/32 of
the padded table, or runs them over the whole table (the cycle's last trip,
or more live pairs than the bucket holds). All three fold the same sums as
the host-scheduled ladder, so the contract stays ``assert_array_equal`` at
every cycle boundary, with the same work counted.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.sph import SimulationSpec, SPHConfig, build_simulation
from repro.sph.collectives import compact_bucket

NCYCLES = 2
STATE_CELL = ("pos", "vel", "u", "h", "mass", "mask")
STATE_AUX = ("accel", "dudt", "rho", "omega", "bins", "t_start")
WORK = ("updates", "pair_tasks", "force_substeps", "substeps", "depth")

CASES = {
    # a 27-cell hot core in bin 2 of a depth-3 ladder: the first cycle
    # skips two trips and compacts five (571 live pairs a trip against a
    # 1,024-slot bucket of the 32,768-slot table); in the second the
    # woken ring outgrows the bucket on two trips
    "sedov_sparse": dict(
        scenario="sedov",
        scenario_params={"n_side": 26, "e0": 0.3, "r_inject": 0.03,
                         "seed": 0},
        physics=SPHConfig(alpha_visc=1.0, cfl=0.15), dt_max=5e-4,
        max_depth=3),
    # every particle in bin 2: all four trips update all 1,728 particles,
    # so each interior trip overflows the 64-slot bucket
    "kh_dense": dict(
        scenario="kelvin_helmholtz",
        scenario_params={"n_side": 12, "v_shear": 0.5, "seed": 0},
        physics=SPHConfig(alpha_visc=1.0, cfl=0.2), dt_max=0.05,
        max_depth=2),
    # the shock tube's 2 x 0.5 x 0.5 box, 12 x 3 x 3 cells: the dense half
    # in bin 2, the sparse half in bin 1; each live trip holds the dense
    # half's pairs, far more than the 64-slot bucket, the rest skip
    "sod_box": dict(
        scenario="sodshock", scenario_params={"n_side": 14, "seed": 0},
        physics=SPHConfig(alpha_visc=0.8, cfl=0.1), dt_max=0.02,
        max_depth=4),
}


def _snapshot(engine) -> dict:
    out = {k: np.asarray(getattr(engine.state.cells, k)) for k in STATE_CELL}
    out.update({k: np.asarray(getattr(engine.state, k)) for k in STATE_AUX})
    out["time"] = np.float64(engine.state.time)
    return out


def _run(spec: SimulationSpec) -> tuple:
    sim = build_simulation(spec)
    stats, snaps = [], []
    for _ in range(NCYCLES):
        stats.append(sim.step())
        snaps.append(_snapshot(sim.engine))
    return stats, snaps


def _assert_same_run(got, want, label: str) -> None:
    for c, ((gs, gsnap), (ws, wsnap)) in enumerate(zip(zip(*got),
                                                       zip(*want))):
        for k in WORK:
            assert gs[k] == ws[k], (label, c, k, gs[k], ws[k])
        for name in wsnap:
            np.testing.assert_array_equal(
                gsnap[name], wsnap[name], err_msg=f"{label} cycle {c}: {name}")


@pytest.mark.parametrize("case", sorted(CASES))
def test_cycle_scan_branches_bitwise_one_rank(case):
    kw = dict(CASES[case], integrator="timebin")
    want = _run(SimulationSpec(**kw, backend="local"))
    got = _run(SimulationSpec(
        **kw, backend="distributed", ranks=1, transport="collective",
        residency="device", schedule="device", segment_cycles=1))
    _assert_same_run(got, want, case)
    stats = got[0]
    assert all(s["schedule"] == "device" and not s.get("replayed")
               for s in stats)
    table = stats[0]["pair_table_slots"]
    bucket = compact_bucket(table)
    assert bucket == table // 32 > 0
    full = 0
    for s in stats:
        # the last trip and every live trip that did not compact ran the
        # whole table; pair_slots counts exactly those and the buckets
        trips_full = s["substeps"] - s["skipped_trips"] - s["compact_trips"]
        assert s["pair_slots"] == trips_full * table \
            + s["compact_trips"] * bucket
        assert s["compact_trips"] + trips_full == s["force_substeps"]
        # the closing trip's density entries with W > 0: at most the
        # entries of the whole table it evaluated
        assert 0 < s["pair_hits"] \
            <= 2 * s["pair_table_slots"] * s["capacity"] ** 2
        full += trips_full
    compact = sum(s["compact_trips"] for s in stats)
    skipped = sum(s["skipped_trips"] for s in stats)
    if case == "sedov_sparse":
        assert compact >= 1 and skipped >= 1
        assert full > NCYCLES          # an interior trip overflowed
    elif case == "kh_dense":
        assert compact == 0 and skipped == 0
        assert full == sum(s["substeps"] for s in stats)
    else:
        assert stats[0]["bin_hist"].tolist() == [0, 686, 5488, 0, 0]
        assert compact == 0 and skipped >= NCYCLES
        assert full == sum(s["force_substeps"] for s in stats)


_FOUR_RANK_BRANCHES = """
import json, os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path[:0] = [{src!r}, {tests!r}]
import numpy as np
import jax
jax.config.update("jax_default_matmul_precision", "float32")
from repro.sph import SimulationSpec, build_simulation
from repro.sph import collectives
from repro.sph.dist_timebins import DistTimeBinSimulation
from test_cycle_scan_branches import CASES, NCYCLES, _snapshot
collectives._COMPACT_SHIFT = 2
ranks = []
real = DistTimeBinSimulation._segment_stats
def stats(self, ctx, plan, npairs, cnt, *rest):
    ranks.extend({{k: np.asarray(c[k]).tolist() for k in (
        "pair_slots", "compact_trips", "skipped_trips", "live_trips")}}
        for c in cnt)
    return real(self, ctx, plan, npairs, cnt, *rest)
DistTimeBinSimulation._segment_stats = stats
kw = dict(CASES[{case!r}], integrator="timebin")
out = {{"ranks": ranks, "runs": [], "unequal": []}}
snaps = []
for spec in (SimulationSpec(**kw, backend="local"),
             SimulationSpec(**kw, backend="distributed", ranks=4,
                            transport="collective", residency="device",
                            schedule="device", segment_cycles=1)):
    sim = build_simulation(spec)
    run, snap = [], []
    for _ in range(NCYCLES):
        s = sim.step()
        snap.append(_snapshot(sim.engine))
        run.append({{k: s.get(k) for k in (
            "updates", "pair_tasks", "force_substeps", "compact_trips",
            "skipped_trips")}})
    out["runs"].append(run)
    snaps.append(snap)
for c, (want, got) in enumerate(zip(*snaps)):
    for name in want:
        try:
            np.testing.assert_array_equal(got[name], want[name])
        except AssertionError:
            out["unequal"].append([c, name])
print(json.dumps(out))
"""


def _four_ranks(case: str) -> dict:
    here = os.path.dirname(os.path.abspath(__file__))
    script = _FOUR_RANK_BRANCHES.format(
        src=os.path.join(here, "..", "src"), tests=here, case=case)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.splitlines()[-1])


def test_cycle_scan_branches_four_ranks():
    """Four ranks on four emulated devices, the bucket widened to a quarter
    of each rank's table (the production 1/32 would need n_side ~40 to hold
    the 27-cell hot core): every rank takes the same branch on every trip,
    the compacted trips carry cut pairs too (the hot core straddles the
    ranks), and the state at each boundary is bitwise the host ladder's."""
    out = _four_ranks("sedov_sparse")
    assert len(out["ranks"]) == NCYCLES
    for per_rank in out["ranks"]:
        for k, vals in per_rank.items():
            assert len(vals) == 4 and len(set(vals)) == 1, (k, vals)
    assert out["unequal"] == []
    want, got = out["runs"]
    for c, (w, g) in enumerate(zip(want, got)):
        for k in ("updates", "pair_tasks", "force_substeps"):
            assert g[k] == w[k], (c, k)
    assert sum(g["compact_trips"] for g in got) >= 1
    assert sum(g["skipped_trips"] for g in got) >= 1


def test_cycle_scan_branches_four_ranks_long_box():
    """The shock tube's 2 x 0.5 x 0.5 box over four ranks of its 12 x 3 x 3
    cells: the partition, halos and cut pairs of a grid that is not a cube,
    bitwise the host ladder's at each boundary, every rank on one branch."""
    out = _four_ranks("sod_box")
    assert len(out["ranks"]) == NCYCLES
    for per_rank in out["ranks"]:
        for k, vals in per_rank.items():
            assert len(vals) == 4 and len(set(vals)) == 1, (k, vals)
    assert out["unequal"] == []
    want, got = out["runs"]
    for c, (w, g) in enumerate(zip(want, got)):
        for k in ("updates", "pair_tasks", "force_substeps"):
            assert g[k] == w[k], (c, k)
    assert all(g["skipped_trips"] >= 1 for g in got)
