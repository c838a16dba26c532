"""Serve a fleet of SPH simulation requests as batched mesh programs.

A request-driven tour of :mod:`repro.fleet`: heterogeneous Sedov and
Kelvin–Helmholtz requests (different blast energies, shear speeds, seeds —
but the same *shapes*) arrive in wobbling bursts, are grouped by
compiled-program signature, and each group runs as ONE vmapped program.
Completion callbacks fire per request; the exported Chrome trace shows
every request on its own timeline row (open at https://ui.perfetto.dev).

Run:  PYTHONPATH=src python examples/fleet_serve.py
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


def main():
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    from repro.fleet import FleetRunner
    from repro.sph import SimulationSpec

    runner = FleetRunner(observe=True)

    def on_done(req):
        r = req.result
        print(f"  done {req.request_id}  [{req.spec.scenario:>16}] "
              f"E={r.energy:.6f}  batch={r.batch_size}/{r.bucket} "
              f"latency={req.latency * 1e3:.1f} ms")

    # wobbling bursts of value-heterogeneous requests: two signatures
    # (sedov, kelvin_helmholtz shapes), many parameter values
    bursts = [3, 5, 4]
    i = 0
    for burst in bursts:
        for _ in range(burst):
            if i % 2 == 0:
                spec = SimulationSpec(
                    scenario="sedov",
                    scenario_params={"n_side": 4, "seed": i,
                                     "e0": 1.0 + 0.05 * i})
            else:
                spec = SimulationSpec(
                    scenario="kelvin_helmholtz",
                    scenario_params={"n_side": 4, "seed": i,
                                     "v_shear": 0.3 + 0.02 * i})
            runner.submit(spec, n_steps=4, callback=on_done)
            i += 1
        print(f"burst of {burst} submitted; draining…")
        runner.drain()

    stats = runner.stats()
    print(f"\nfleet: {stats['queue']['done']} requests in "
          f"{stats['batches']} batches, {stats['compiles']} compiles "
          f"({stats['programs']} entry points), "
          f"{stats['particle_steps']} particle-steps")
    runner.assert_compile_discipline()
    doc = runner.export_trace("fleet_trace_example.json")
    print(f"trace: fleet_trace_example.json "
          f"({len(doc['traceEvents'])} events; rows are request_ids)")


if __name__ == "__main__":
    main()
