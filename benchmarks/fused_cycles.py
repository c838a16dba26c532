"""Host-scheduled vs device-scheduled fused cycles (Sedov, 4 ranks).

PR 4's fused path (``residency="device"``) made each force sub-step one
compiled program, but the *cycle* control plane stayed on host: ladder
planning, per-sub-step activity masks, pair-subset dispatch. The
device-scheduled path (``schedule="device"``) compiles whole cycles — and
with ``segment_cycles=K`` whole K-cycle segments — into one program, so
the host is consulted once per segment. This benchmark measures what that
buys on identical physics, in two regimes:

* ``small`` — n_side=4, max_depth=1: per-cycle compute is tiny, so host
  dispatch + planning dominate. This is the regime device scheduling
  exists for (the SWIFT strong-scaling limit, where control-plane
  overhead per step is the whole game) — expect multi-× speedups.
* ``deep`` — n_side=6, max_depth=4: a real ladder. The compiled scan
  runs every trip over the full-touch pair table (dead trips compute and
  discard), while the host scheduler dispatches per-level *compacted*
  programs — so on a compute-bound CPU the host path stays ahead. The
  regime is reported, not hidden: it bounds where ``schedule="device"``
  should be switched on today.

Within each regime the paths are:

* ``host_sched``  — ``residency="device"``, per-sub-step dispatch;
* ``device_K1``   — ``schedule="device"``, one compiled cycle per step;
* ``device_K4``   — ``schedule="device", segment_cycles=4``.

All paths run the same warm-up then the same measured window, and their
final states are asserted bit-for-bit identical (the window is
segment-aligned, so every path ends at a defined state). Reported per
path: wall per cycle, host↔device bytes per cycle (boundary + intra), the
intra-segment state-byte ledger (must be 0), and compile residue in the
measured window (must be 0). The headline artifact lands at the repo root
as ``BENCH_fused_cycles.json`` with ``_env`` provenance; CSV rows go to
``benchmarks/results/fused_cycles.json``.

Under ``JAX_PLATFORMS=cpu`` the measurement runs in a subprocess with
4 emulated CPU devices; on an accelerator it runs in this process,
which holds the chip (``common.run_worker``).

Run:  PYTHONPATH=src python benchmarks/fused_cycles.py [ncycles]
"""

from __future__ import annotations

import json
import os
import sys

try:                                    # runnable as module or script
    from .common import emit, run_worker
except ImportError:                     # pragma: no cover
    from common import emit, run_worker

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))

REGIMES = {
    # dispatch-bound: the device scheduler's home turf
    "small": {"n_side": 4, "max_depth": 1, "dt_max": 0.005},
    # compute-bound ladder: the host scheduler's per-level compaction wins
    "deep": {"n_side": 6, "max_depth": 4, "dt_max": 0.02},
}

_WORKER = """
import os
import sys, time, json
sys.path.insert(0, %(src)r)
import numpy as np
import jax
from repro.sph import SimulationSpec, SPHConfig, build_simulation

base = SimulationSpec(
    scenario="sedov",
    scenario_params={"n_side": %(n_side)d, "e0": 1.0, "seed": 0},
    physics=SPHConfig(alpha_visc=1.0, cfl=0.15),
    dt_max=%(dt_max)r, max_depth=%(max_depth)d, integrator="timebin",
    backend="distributed", ranks=%(nranks)d,
    transport="collective", residency="device")

PATHS = {
    "host_sched": base,
    "device_K1": base.with_(schedule="device", segment_cycles=1),
    "device_K4": base.with_(schedule="device", segment_cycles=4),
}

ncycles = %(ncycles)d
warm = %(max_warm)d
out = {}
states = {}
for label, spec in PATHS.items():
    sim = build_simulation(spec)
    eng = sim.engine
    for _ in range(warm):
        sim.step()
    compiles0 = eng.probe.total_compiles()
    tp0 = eng.transfers.stats()
    bytes0 = (sum(tp0["boundary_bytes"].values())
              + sum(eng.transfers.intra_bytes.values()))
    walls, subs = [], 0
    for _ in range(ncycles):
        t0 = time.perf_counter()
        stats = sim.step()
        walls.append(time.perf_counter() - t0)
        subs += stats["force_substeps"]
    tp = eng.transfers.stats()
    host_bytes = (sum(tp["boundary_bytes"].values())
                  + sum(eng.transfers.intra_bytes.values()) - bytes0)
    out[label] = {
        "wall_per_cycle_s": float(np.sum(walls)) / ncycles,
        "force_substeps": subs,
        "warmup_cycles": warm,
        "measured_cycles": ncycles,
        "compiles_during_measurement":
            eng.probe.total_compiles() - compiles0,
        "host_bytes_per_cycle": host_bytes / ncycles,
        "intra_state_bytes": tp["intra_state_bytes"],
        "segments": getattr(eng, "segments", 0),
        "segment_aborts": getattr(eng, "segment_aborts", 0),
    }
    states[label] = (np.asarray(eng.state.cells.pos),
                     np.asarray(eng.state.cells.u),
                     np.asarray(eng.state.bins))
ref = states["host_sched"]
for label in ("device_K1", "device_K4"):
    for a, b in zip(ref, states[label]):
        np.testing.assert_array_equal(a, b)
for label in PATHS:
    assert out[label]["intra_state_bytes"] == 0, (label, out[label])
    assert out[label]["compiles_during_measurement"] == 0, (label, out[label])
out["identical_physics"] = True
out["_env"] = {"python": sys.version.split()[0],
               "jax": jax.__version__,
               "backend": jax.default_backend(),
               "device_count": jax.device_count(),
               "xla_flags": os.environ.get("XLA_FLAGS", "")}
print("RESULT_JSON=" + json.dumps(out, default=str))
"""


def _measure(regime: dict, ncycles: int, nranks: int, max_warm: int) -> dict:
    # the measured window must be a multiple of every segment length so
    # all paths end segment-aligned (bitwise-comparable final states)
    script = _WORKER % {"nranks": nranks, "ncycles": ncycles,
                        "max_warm": max_warm,
                        "src": os.path.join(ROOT, "src"), **regime}
    return run_worker(script, nranks, "fused_cycles")


def run(ncycles=4, nranks=4, max_warm=4) -> list:
    rows, doc_regimes = [], {}
    env = None
    for rname, regime in REGIMES.items():
        res = _measure(regime, ncycles, nranks, max_warm)
        env = res["_env"]
        doc_regimes[rname] = {
            "config": regime,
            "paths": {k: res[k] for k in
                      ("host_sched", "device_K1", "device_K4")},
            "speedup_vs_host_sched": {
                k: res["host_sched"]["wall_per_cycle_s"]
                / max(res[k]["wall_per_cycle_s"], 1e-12)
                for k in ("device_K1", "device_K4")},
            "identical_physics": res["identical_physics"],
        }
        for label in ("host_sched", "device_K1", "device_K4"):
            r = res[label]
            rows.append({
                "name": f"fused_cycles/{rname}/{label}/us_per_cycle",
                "us_per_call": round(1e6 * r["wall_per_cycle_s"], 1),
                "derived":
                    f"host_B_per_cycle={r['host_bytes_per_cycle']:.0f};"
                    f"intra_state_bytes={r['intra_state_bytes']};"
                    f"measure_compiles="
                    f"{r['compiles_during_measurement']};"
                    f"segments={r['segments']};"
                    f"aborts={r['segment_aborts']}"})
        for label in ("device_K1", "device_K4"):
            speed = doc_regimes[rname]["speedup_vs_host_sched"][label]
            rows.append({
                "name": f"fused_cycles/{rname}/{label}"
                        f"_speedup_vs_host_sched",
                "us_per_call": round(speed, 3),
                "derived": f"identical_physics="
                           f"{res['identical_physics']};"
                           f"nranks={nranks};ncycles={ncycles};"
                           + ";".join(f"{k}={v}"
                                      for k, v in regime.items())})
    emit(rows, "fused_cycles")

    bench = {"benchmark": "fused_cycles",
             "nranks": nranks, "ncycles": ncycles,
             "regimes": doc_regimes,
             # the headline: the dispatch-bound regime device scheduling
             # was built for; the deep regime bounds its applicability
             "speedup_vs_host_sched":
                 doc_regimes["small"]["speedup_vs_host_sched"],
             "_env": env}               # provenance from the worker,
                                        # where the 4-device flag is real
    with open(os.path.join(ROOT, "BENCH_fused_cycles.json"), "w") as f:
        json.dump(bench, f, indent=1, default=str)
    return rows


if __name__ == "__main__":
    ncycles = int(sys.argv[1]) if len(sys.argv) > 1 else 4
    run(ncycles=ncycles)
