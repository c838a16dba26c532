"""``python -m repro.fleet`` — the serving entry point.

Drives a fleet of simulation requests through the batched runner:

    python -m repro.fleet --scenario sedov --requests 64
    python -m repro.fleet --scenario mixed --requests 8 \
        --check-parity --assert-compiles --trace-out fleet_trace.json

Requests are heterogeneous in *values* (seed, blast energy, shear speed —
the spec fields a program signature deliberately ignores) and homogeneous
in *shape* per scenario, so a mixed fleet exercises exactly the grouping
the subsystem exists for: one compiled program per (signature, batch
bucket), every request bitwise identical to running it alone.

``--waves`` splits the submissions into bursts with SWIFT-ishly wobbling
sizes so the no-shrink bucket policy is exercised; ``--check-parity``
re-runs every request on the single-simulation path and compares bitwise;
``--assert-compiles`` fails the process if any entry point compiled more
than once. Exit status is nonzero on any failed request or failed check —
this is the CI smoke contract.
"""

from __future__ import annotations

import argparse
import json
import sys


def _specs(args):
    from ..sph.api import SimulationSpec
    scenarios = {
        "sedov": lambda i: SimulationSpec(
            scenario="sedov",
            scenario_params={"n_side": args.n_side, "seed": i,
                             "e0": 1.0 + 0.1 * (i % 4)}),
        "kelvin_helmholtz": lambda i: SimulationSpec(
            scenario="kelvin_helmholtz",
            scenario_params={"n_side": args.n_side, "seed": i,
                             "v_shear": 0.4 + 0.05 * (i % 3)}),
    }
    if args.scenario == "mixed":
        names = sorted(scenarios)
        return [scenarios[names[i % len(names)]](i)
                for i in range(args.requests)]
    return [scenarios[args.scenario](i) for i in range(args.requests)]


def _waves(n, nwaves):
    """Split n submissions into nwaves bursts with wobbling sizes."""
    if nwaves <= 1:
        return [n]
    wobble = [3, 7, 5, 8]
    sizes, left, i = [], n, 0
    while left > 0 and len(sizes) < nwaves - 1:
        take = min(wobble[i % len(wobble)], left)
        sizes.append(take)
        left -= take
        i += 1
    if left:
        sizes.append(left)
    return sizes


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.fleet",
        description="Serve a fleet of SPH simulation requests as batched, "
                    "signature-grouped mesh programs.")
    ap.add_argument("--scenario", default="sedov",
                    choices=["sedov", "kelvin_helmholtz", "mixed"])
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--steps", type=int, default=4,
                    help="steps each request runs")
    ap.add_argument("--n-side", type=int, default=5,
                    help="IC lattice side (shape param: sets the signature)")
    ap.add_argument("--batch-max", type=int, default=64)
    ap.add_argument("--waves", type=int, default=1,
                    help="submit in this many wobbling-size bursts")
    ap.add_argument("--fleet-devices", type=int, default=None,
                    help="devices to shard the fleet axis over (default: "
                         "all local devices if a power of two, else 1)")
    ap.add_argument("--check-parity", action="store_true",
                    help="compare every request against the single-"
                         "simulation path: bitwise on the vmap path "
                         "(--fleet-devices 1), ulp tolerance when the "
                         "fleet axis is sharded (per-device program "
                         "partitioning reassociates reductions)")
    ap.add_argument("--assert-compiles", action="store_true",
                    help="fail if any entry point compiled more than once")
    ap.add_argument("--trace-out", default=None,
                    help="write the multi-request Chrome trace here")
    args = ap.parse_args(argv)

    from ..compile_cache import enable_compile_cache
    enable_compile_cache()
    from .queue import RequestState
    from .runner import FleetRunner, sequential_reference

    runner = FleetRunner(max_batch=args.batch_max,
                         fleet_devices=args.fleet_devices,
                         observe=args.trace_out is not None)
    specs = _specs(args)
    served = []
    it = iter(specs)
    for size in _waves(len(specs), args.waves):
        for _ in range(size):
            runner.submit(next(it), n_steps=args.steps)
        served.extend(runner.drain())

    failed = [r for r in served if r.state is not RequestState.DONE]
    for r in failed:
        print(f"FAILED {r.request_id}: {r.error!r}", file=sys.stderr)

    parity = None
    if args.check_parity:
        import numpy as np
        exact = runner.fleet_devices == 1
        parity = {"mode": "bitwise" if exact else "ulp",
                  "checked": 0, "mismatches": []}
        for r in served:
            if r.result is None or not r.result.particles:
                continue
            ref = sequential_reference(r.spec, r.n_steps)
            parity["checked"] += 1
            for k, a in r.result.particles.items():
                a, b = np.asarray(a), np.asarray(ref.particles[k])
                ok = np.array_equal(a, b) if exact \
                    else np.allclose(a, b, rtol=1e-4, atol=1e-5)
                if not ok:
                    parity["mismatches"].append(
                        {"request": r.request_id, "field": k,
                         "max_abs": float(np.max(np.abs(a - b)))})

    out = {
        "requests": len(specs),
        "scenario": args.scenario,
        "steps": args.steps,
        "stats": runner.stats(),
        "compile_counts": runner.compile_counts(),
        "latencies": {r.request_id: r.latency for r in served},
        "parity": parity,
    }
    if args.trace_out:
        import os
        parent = os.path.dirname(args.trace_out)
        if parent:
            os.makedirs(parent, exist_ok=True)
        doc = runner.export_trace(args.trace_out)
        out["trace"] = {"path": args.trace_out,
                        "events": len(doc["traceEvents"])}
    json.dump(out, sys.stdout, indent=2, default=str)
    print()

    rc = 0
    if failed:
        rc = 1
    if parity is not None and (parity["mismatches"] or not parity["checked"]):
        print(f"PARITY FAILED: {parity}", file=sys.stderr)
        rc = 1
    if args.assert_compiles:
        try:
            runner.assert_compile_discipline()
        except AssertionError as e:
            print(str(e), file=sys.stderr)
            rc = 1
    return rc


if __name__ == "__main__":
    sys.exit(main())
