"""Sedov–Taylor point explosion with hierarchical time bins.

The scenario the time-bin subsystem exists for: a blast wave in a cold
uniform gas produces a CFL time-step contrast of >3 decades between the
hot centre and the quiescent background. The multi-dt engine integrates
each particle at its own power-of-two step — only the blast region burns
compute — while the global-dt engine would grind everything at the
minimum.

Prints per cycle: the time-bin histogram, the fraction of particle
updates actually performed vs the global-dt equivalent, energy drift,
and the shock radius against the analytic Sedov solution
r_s(t) = ξ (E t² / ρ)^{1/5}, ξ ≈ 1.15 for γ = 5/3.

Run:  PYTHONPATH=src python examples/sedov_blast.py [n_side] [ncycles]
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np


def main():
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    n_side = int(sys.argv[1]) if len(sys.argv) > 1 else 12
    ncycles = int(sys.argv[2]) if len(sys.argv) > 2 else 4

    from repro.sph import (SPHConfig, SimulationSpec, assign_bins,
                           build_simulation, sedov_ic)
    from repro.sph.physics import cfl_timestep_block

    ic = sedov_ic(n_side, e0=1.0, seed=0)
    n = len(ic["pos"])
    cfg = SPHConfig(alpha_visc=1.0, cfl=0.15)
    spec = SimulationSpec(
        scenario="sedov", scenario_params={"n_side": n_side, "e0": 1.0,
                                           "seed": 0},
        physics=cfg, integrator="timebin", backend="local",
        dt_max=0.02, max_depth=10)
    sim = build_simulation(spec, ic=ic).engine

    # raw CFL spread of the IC — the dynamic range the bins quantise
    cells = sim.state.cells
    dts = np.asarray(cfl_timestep_block(cells.h, cells.u, cells.vel,
                                        cells.mask, gamma=cfg.gamma,
                                        cfl=cfg.cfl))
    live = dts[np.asarray(cells.mask) > 0]
    spread = float(live.max() / live.min())
    raw_bins = assign_bins(live, float(live.max()), 32)
    print(f"N = {n}, CFL dt spread = {spread:.1e} "
          f"({np.log10(spread):.1f} decades, "
          f"{int(raw_bins.max()) + 1} power-of-two bins)")

    e_start, _ = sim.diagnostics()
    print("\ncycle       t  depth  upd_frac  dE_rel   r_shock  r_sedov")
    for c in range(ncycles):
        stats = sim.run_cycle()
        e_now, _ = sim.diagnostics()
        frac = stats["updates"] / stats["global_equiv_updates"]
        # shock radius: mass-weighted radius of the fastest decile
        st = sim.state.cells
        m = np.asarray(st.mask) > 0
        pos = np.asarray(st.pos)[m]
        v = np.linalg.norm(np.asarray(st.vel)[m], axis=-1)
        d = pos - ic["box"] / 2.0
        d -= ic["box"] * np.round(d / ic["box"])
        r = np.linalg.norm(d, axis=-1)
        fast = v > max(np.percentile(v, 90), 1e-6)
        r_shock = float(np.median(r[fast])) if fast.any() else 0.0
        t = stats["t"]
        r_sedov = 1.15 * (1.0 * t * t) ** 0.2
        print(f"{c:5d}  {t:6.3f}  {stats['depth']:5d}  {frac:8.3f}  "
              f"{(e_now - e_start) / abs(e_start):+.2e}  {r_shock:7.3f}  "
              f"{r_sedov:7.3f}")
        print(f"       bins: {[int(x) for x in stats['bin_hist']]}")

    print(f"\ntotal particle updates: {sim.particle_updates} "
          f"(global-dt equivalent: {sim.global_equiv_updates}, "
          f"saved {1 - sim.particle_updates / sim.global_equiv_updates:.1%})")


if __name__ == "__main__":
    main()
