"""Quickstart: the paper's pipeline end-to-end at laptop scale.

1. Build a clustered SPH initial condition (EAGLE-like density contrast).
2. Decompose into cells; build the SWIFT task graph (sort → density →
   ghost → force → kick) with dependencies and conflicts.
3. Compile the graph into a wave schedule, partition the cell graph over 4
   simulated ranks, insert send/recv tasks (§3.3), and compare the async
   executor against the bulk-synchronous baseline.
4. Run the real SPH engine for a few steps and verify conservation.

Run:  PYTHONPATH=src python examples/quickstart.py
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np

from repro.core import (AsyncExecutorSim, decompose_with_comm,
                        wave_schedule)
from repro.sph import SPHConfig, clustered_ic
from repro.sph.cellgrid import bin_particles, build_pair_list, choose_grid
from repro.sph.engine import build_taskgraph


def main():
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    print("=== 1. clustered initial conditions")
    ic = clustered_ic(3000, seed=0)
    print(f"    {len(ic['pos'])} particles, h ∈ "
          f"[{ic['h'].min():.4f}, {ic['h'].max():.4f}] "
          f"({ic['h'].max()/ic['h'].min():.0f}× dynamic range)")

    print("=== 2. cell decomposition + task graph")
    from repro.core import CostModel
    spec = choose_grid(ic["box"], float(np.percentile(ic["h"], 95)), 3000)
    cells, _ = bin_particles(spec, ic["pos"], ic["vel"], ic["mass"],
                             ic["u"], ic["h"])
    pairs = build_pair_list(spec)
    occupancy = np.asarray(cells.mask.sum(axis=1))
    cm = CostModel(rates={})
    g = build_taskgraph(spec, pairs, occupancy, cm)
    # calibrate task costs to seconds (≈2 ns per pair interaction, the
    # measured-cost refinement of §3.2)
    for t in g.tasks.values():
        object.__setattr__(t, "cost", max(t.cost * 2e-9, 1e-8))
    print(f"    {spec.ncells} cells, {len(pairs.ci)} pair tasks, "
          f"{len(g)} tasks total")

    waves = wave_schedule(g)
    cp, _ = g.critical_path()
    print(f"    wave schedule: {len(waves)} waves, critical path "
          f"{cp*1e3:.3g} ms")

    print("=== 3. graph partition + async communication (4 ranks)")
    cell_bytes = [float(max(o, 1)) * 64.0 for o in occupancy]
    dist, dec = decompose_with_comm(
        g, spec.ncells, 4, cell_bytes=cell_bytes,
        phases={"sort": "p0", "density_self": "p1", "density_pair": "p1",
                "ghost": "p2", "force_self": "p3", "force_pair": "p3",
                "kick": "p4"})
    print(f"    partition: {dec.partition.summary()}")
    print(f"    messages: {dec.comm.messages} "
          f"(mean {dec.comm.mean_message_bytes/1024:.2f} kB)")
    kw = dict(ranks=4, threads=2, latency=1.5e-5, bandwidth=5e9)
    a = AsyncExecutorSim(dist, **kw).run()
    s = AsyncExecutorSim(dist, synchronous=True, **kw).run()
    print(f"    async makespan {a.makespan*1e3:.3f} ms "
          f"(eff {a.efficiency:.2f})  vs  sync {s.makespan*1e3:.3f} ms "
          f"(eff {s.efficiency:.2f})  → {s.makespan/a.makespan:.2f}× faster")

    print("=== 4. real SPH integration (conservation check)")
    from repro.sph import SimulationSpec, build_simulation, uniform_ic
    rng = np.random.default_rng(1)
    ic2 = uniform_ic(8, seed=2)                  # 512 particles: fast on CPU
    ic2["vel"] = (ic2["vel"]
                  + 0.2 * rng.standard_normal(ic2["vel"].shape)
                  ).astype(np.float32)
    spec = SimulationSpec(scenario="uniform",
                          physics=SPHConfig(alpha_visc=0.8),
                          integrator="global", backend="local",
                          dt=0.004, rebin_every=5)
    sim = build_simulation(spec, ic=ic2)
    e0, p0 = sim.diagnostics()
    sim.run(10 * 0.004)
    e1, p1 = sim.diagnostics()
    print(f"    10 steps: |ΔE|/E = {abs(e1-e0)/abs(e0):.2e}, "
          f"|Δp| = {np.abs(p1-p0).max():.2e}")
    print("done.")


if __name__ == "__main__":
    main()
