"""Host vs collective vs device-resident wire cost per force sub-step (Sedov).

The distributed time-bin engine runs the same physics over three execution
paths (bit-for-bit identical states, asserted below):

* ``transport="host"`` — numpy row copies between per-rank phase programs;
* ``transport="collective"`` — shard_map/ppermute exchange programs, but
  rank states still round-trip through host between the phase programs;
* ``transport="collective", residency="device"`` — the fused path, the
  device cycle scan: states stay resident on the mesh for the whole cycle
  and each cycle is one compiled program that schedules its own sub-steps.

For each path the benchmark reports wall time per cycle / per force
sub-step and the **host-transfer bytes** per force sub-step: for the first
two, the full-field device→host→device round trips their wires pay
(``transport.stats()["host_bytes"]``); for the fused path, the transfer
probe's intra-cycle ledger — empty, with ``state_bytes`` asserted 0.

Every path gets the same fixed warm-up (``max_warm`` cycles — enough for
the program caches to quiesce at the default size), so all paths are
measured at the same simulation epoch and the final states can be compared
bitwise; ``measure_compiles`` reports any compile residue in the measured
window.

Under ``JAX_PLATFORMS=cpu`` the measurement runs in a subprocess with
4 emulated CPU devices; on an accelerator it runs in this process,
which holds the chip (``common.run_worker``).

Run:  PYTHONPATH=src python benchmarks/halo_transport.py [n_side] [ncycles]
"""

from __future__ import annotations

import os
import sys

try:                                    # runnable as module or script
    from .common import emit, run_worker
except ImportError:                     # pragma: no cover
    from common import emit, run_worker

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))

_WORKER = """
import os
import sys, time, json
sys.path.insert(0, %(src)r)
import numpy as np
import jax
from repro.sph import SimulationSpec, SPHConfig, build_simulation

base = SimulationSpec(
    scenario="sedov",
    scenario_params={"n_side": %(n_side)d, "e0": 1.0, "seed": 0,
                     "n_target": 16.0, "r_inject": 0.5 / %(n_side)d},
    physics=SPHConfig(alpha_visc=1.0, cfl=0.15, n_target=16.0),
    integrator="timebin", backend="distributed", ranks=%(nranks)d,
    max_depth=6)

PATHS = {
    "host": base,
    "collective": base.with_(transport="collective"),
    "fused": base.with_(transport="collective", residency="device"),
}

out = {}
states = {}
for label, spec in PATHS.items():
    sim = build_simulation(spec)
    eng = sim.engine
    # identical fixed warm-up for every path (the physics comparison needs
    # all paths at the same simulation epoch); long enough that the
    # program caches quiesce, so the measurement is steady-state reuse —
    # compiles_during_measurement reports any residue
    warm = %(max_warm)d
    for _ in range(warm):
        sim.step()
    compiles0 = eng.probe.total_compiles()
    host_bytes0 = eng._transport.stats().get("host_bytes", 0)
    intra0 = dict(eng.transfers.intra_bytes)
    walls, subs = [], 0
    for _ in range(%(ncycles)d):
        t0 = time.perf_counter()
        stats = sim.step()
        walls.append(time.perf_counter() - t0)
        subs += stats["force_substeps"]
    tstats = eng.transport_stats()
    host_bytes = tstats.get("host_bytes", 0) - host_bytes0
    intra = {k: v - intra0.get(k, 0)
             for k, v in eng.transfers.intra_bytes.items()}
    out[label] = {
        "wall_per_cycle_s": float(np.mean(walls)),
        "wall_per_force_substep_us": 1e6 * float(np.sum(walls)) / subs,
        "force_substeps": subs,
        "warmup_cycles": warm,
        "compiles_during_measurement":
            eng.probe.total_compiles() - compiles0,
        "exported_slots": int(eng.halo_exported_slots),
        "host_bytes_per_force_substep": host_bytes / subs,
        "intra_cycle_bytes_per_force_substep":
            sum(intra.values()) / subs,
        "intra_cycle_state_bytes": eng.transfers.stats()[
            "intra_state_bytes"],
        "transport": tstats,
    }
    states[label] = (np.asarray(eng.state.cells.pos),
                     np.asarray(eng.state.cells.u))
ref = states["host"]
for label in ("collective", "fused"):
    for a, b in zip(ref, states[label]):
        np.testing.assert_array_equal(a, b)
assert out["fused"]["intra_cycle_state_bytes"] == 0
out["identical_physics"] = True
print("RESULT_JSON=" + json.dumps(out, default=str))
"""


def run(n_side=8, ncycles=3, nranks=4, max_warm=8) -> list:
    script = _WORKER % {"nranks": nranks, "n_side": n_side,
                        "ncycles": ncycles, "max_warm": max_warm,
                        "src": os.path.join(ROOT, "src")}
    res = run_worker(script, nranks, "halo_transport")

    rows = []
    for label in ("host", "collective", "fused"):
        r = res[label]
        t = r["transport"]
        extra = ""
        if label != "host":
            extra = (f";mode={t['mode']};rounds={t['rounds']};"
                     f"programs={t['programs']}")
        if label == "fused":
            extra += f";intra_state_bytes={r['intra_cycle_state_bytes']}"
        rows.append({
            "name": f"transport/{label}/us_per_force_substep",
            "us_per_call": round(r["wall_per_force_substep_us"], 1),
            "derived": f"wall_per_cycle_s={r['wall_per_cycle_s']:.4f};"
                       f"force_substeps={r['force_substeps']};"
                       f"measure_compiles="
                       f"{r['compiles_during_measurement']};"
                       f"exported_slots={r['exported_slots']};"
                       f"host_B_per_substep="
                       f"{r['host_bytes_per_force_substep']:.0f};"
                       f"intra_B_per_substep="
                       f"{r['intra_cycle_bytes_per_force_substep']:.0f}"
                       f"{extra}"})
    for num, den, name in (("collective", "host",
                            "collective_over_host_ratio"),
                           ("fused", "collective",
                            "fused_over_collective_ratio")):
        ratio = (res[num]["wall_per_force_substep_us"]
                 / max(res[den]["wall_per_force_substep_us"], 1e-9))
        rows.append({
            "name": f"transport/{name}",
            "us_per_call": round(ratio, 3),
            "derived": f"identical_physics={res['identical_physics']};"
                       f"nranks={nranks};n_side={n_side};"
                       f"ncycles={ncycles}"})
    emit(rows, "halo_transport")
    return rows


if __name__ == "__main__":
    n_side = int(sys.argv[1]) if len(sys.argv) > 1 else 8
    ncycles = int(sys.argv[2]) if len(sys.argv) > 2 else 3
    run(n_side=n_side, ncycles=ncycles)
