#!/usr/bin/env python3
"""Run the production SPH path on the chip once and check what comes out.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the 4-rank path against 1 rank

The production path is what a user builds with ``build_simulation``: the
time-bin integrator on the distributed backend with collective transport,
device-resident state and the device schedule. The default run, all in one
process on one chip:

1. Sedov at ``n_side=N_SIDE`` for 4 one-cycle device segments. Prints the
   compile seconds, the steady per-cycle wall, the particle updates, the
   device's peak bytes and the segment aborts, which must be 0.
2. The oracle: the single-rank host ladder (``backend="local"``, the spec
   otherwise unchanged) for the same 4 cycles on the same chip. ``pos``,
   ``vel`` and ``u`` must agree within ``ORACLE_RTOL``; energy and momentum
   must stay within the sanity bounds.
3. Absolute density at ``n_side=12`` against the O(N²) reference
   ``sph/ref_nsquared.nsq_density`` within ``DENSITY_RTOL``.

``--chips 4`` runs only the production spec at ``ranks=4`` and the
``ranks=1`` run it is compared with, at ``n_side=N_SIDE_FOUR``, and checks
that the state is sharded over 4 devices that each hold memory.

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``,
printed only when every check passed. Anywhere but on a TPU the script
exits non-zero before it compiles anything. It starts no process.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))

N_SIDE = 60                 # particles = N_SIDE³; see CHANGES.md for the size
# --chips 4: the 4-rank graph partition of the n_side=60 grid takes ~200 s
# on the host before the first program, at four chips' charge
N_SIDE_FOUR = 32
N_SIDE_DENSITY = 12
# One cycle per device segment. A longer segment plans its later cycles on
# the device and must abort to the host ladder when a particle leaves its
# cell, which Sedov's blast particles do within every cycle at n_side >= 12:
# the cycle span is 2**MAX_DEPTH of their CFL steps.
SEGMENTS = 4
SEGMENT_CYCLES = 1
# the CI Sedov runs dt_max=0.02 at n_side=6; the cycle span scales with the
# lattice spacing so the ladder stays within MAX_DEPTH at every n_side
CI_DT_MAX, CI_N_SIDE = 0.02, 6
MAX_DEPTH = 4

# Production path vs the host ladder on the same chip. On the CPU the two
# agree bitwise. On the chip XLA fuses the one-program cycle scan and the
# per-phase ladder programs differently, so their f32 sums may round
# differently (~1e-7 per operation) and the differences grow over 4 cycles
# of ≤16 sub-steps. 1e-4 of each field's largest magnitude leaves about
# three decades above that, while a dropped pair, a missed halo row or a
# wrong kick moves a field by ≥1e-3 of its scale.
ORACLE_RTOL = 1e-4
# Engine density vs the O(N²) reference: the same f32 kernel sums in
# another order (relative error ~1e-6 for ~50 neighbours). Distances
# through bf16 passes would be off by ~1e-3.
DENSITY_RTOL = 1e-4
# Sanity bounds. Momentum drift ≳1e-3 means an integrator bug on a mild
# initial condition. Energy drift is a property of this
# ladder, not of the device path: on the CPU the host ladder drifts 1.8%,
# 4.2%, 5.3% and 11.7% over 4 cycles at n_side 8, 16, 24 and 32, because
# the blast's energy contrast grows as n_side³ while MAX_DEPTH caps the
# ladder. So the production path's energy must match the oracle's within
# ORACLE_RTOL, and the absolute bound only catches a blow-up.
ENERGY_DRIFT_MAX = 0.5
MOMENTUM_DRIFT_MAX = 1e-3


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


# ------------------------------------------------------------ measurement
class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling, and persistent
    cache hits, from JAX's own monitoring events."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax.monitoring as mon
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event in self.EVENTS:
            self.seconds += duration
        if event == self.EVENTS[-1]:
            self.compiles += 1

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def peak_bytes(device) -> int | None:
    stats = device.memory_stats()
    return None if stats is None else int(stats.get("peak_bytes_in_use", 0))


# ----------------------------------------------------------------- phases
def production_spec(n_side: int, ranks: int):
    from repro.sph import SimulationSpec, SPHConfig
    return SimulationSpec(
        scenario="sedov",
        scenario_params={"n_side": n_side, "e0": 1.0, "seed": 0},
        physics=SPHConfig(alpha_visc=1.0, cfl=0.15),
        dt_max=CI_DT_MAX * CI_N_SIDE / n_side, max_depth=MAX_DEPTH,
        integrator="timebin", backend="distributed", ranks=ranks,
        transport="collective", residency="device", schedule="device",
        segment_cycles=SEGMENT_CYCLES)


def _flat_state(sim) -> dict:
    from repro.sph.cellgrid import unbin
    eng = sim.engine
    out = unbin(eng.state.cells, eng.perm, eng.n)
    out["rho"] = eng._flatten_aux(eng.state.rho, 1.0)
    return out


def run_production(spec, clock: CompileClock) -> dict:
    """Build the spec and run ``SEGMENTS`` device segments. The first
    segment carries the compiles; the last one is the steady window."""
    import jax
    from repro.sph import build_simulation

    t0 = time.perf_counter()
    c0 = clock.seconds
    sim = build_simulation(spec)
    jax.block_until_ready(sim.engine.state.cells.pos)
    e0, p0 = sim.diagnostics()
    walls, compiles = [], []
    for _ in range(SEGMENTS):
        n0 = clock.compiles
        t = time.perf_counter()
        for _ in range(spec.segment_cycles):
            sim.step()
        jax.block_until_ready(sim.engine.state.cells.pos)
        walls.append(time.perf_counter() - t)
        compiles.append(clock.compiles - n0)
    e1, p1 = sim.diagnostics()
    eng = sim.engine
    return {
        "sim": sim,
        "state": _flat_state(sim),
        "setup_s": time.perf_counter() - t0 - sum(walls),
        "compile_s": clock.seconds - c0,
        "segment_walls_s": walls,
        "segment_compiles": compiles,
        "cycle_wall_s": walls[-1] / spec.segment_cycles,
        "cycles": SEGMENTS * spec.segment_cycles,
        "particles": eng.n,
        "particle_updates": eng.particle_updates,
        "segment_aborts": eng.segment_aborts,
        "segments": eng.segments,
        "energy": (e0, e1),
        "momentum": (p0.tolist(), p1.tolist()),
    }


def run_reference(spec, cycles: int) -> dict:
    """The oracle: the single-rank host ladder on the same spec."""
    import jax
    from repro.sph import build_simulation

    sim = build_simulation(spec.with_(backend="local"))
    t = time.perf_counter()
    for _ in range(cycles):
        sim.step()
    jax.block_until_ready(sim.engine.state.cells.pos)
    return {"state": _flat_state(sim), "wall_s": time.perf_counter() - t,
            "energy": sim.diagnostics()[0]}


def field_diffs(got: dict, want: dict) -> dict:
    """Max abs and scale-relative differences of pos, vel and u."""
    import numpy as np
    out = {}
    for name in ("pos", "vel", "u"):
        a = np.asarray(got[name], np.float64)
        b = np.asarray(want[name], np.float64)
        diff = float(np.max(np.abs(a - b)))
        scale = float(np.max(np.abs(b)))
        out[name] = {"max_abs": diff,
                     "max_rel": diff / scale if scale > 0 else diff}
    return out


def check_sanity(res: dict) -> dict:
    import numpy as np
    (e0, e1), (p0, p1) = res["energy"], res["momentum"]
    drift_e = abs(e1 - e0) / abs(e0)
    drift_p = float(np.max(np.abs(np.subtract(p1, p0))))
    check(np.isfinite(e1) and np.all(np.isfinite(p1)),
          "energy or momentum is not finite")
    check(drift_e <= ENERGY_DRIFT_MAX,
          f"energy drift {drift_e} > {ENERGY_DRIFT_MAX}")
    check(drift_p <= MOMENTUM_DRIFT_MAX,
          f"momentum drift {drift_p} > {MOMENTUM_DRIFT_MAX}")
    return {"energy_drift": drift_e, "momentum_drift": drift_p}


def check_production(res: dict) -> None:
    import numpy as np
    check(res["segment_aborts"] == 0,
          f"{res['segment_aborts']} device segments aborted to the host "
          f"ladder")
    check(res["segments"] == SEGMENTS,
          f"ran {res['segments']} device segments, expected {SEGMENTS}")
    for name in ("pos", "vel", "u", "rho"):
        check(bool(np.all(np.isfinite(res["state"][name]))),
              f"non-finite {name}")
    check(res["particle_updates"] > 0, "no particle was updated")


def density_error(res: dict) -> float:
    """Max relative difference of a production run's final density against
    the O(N²) reference on the same positions."""
    import jax.numpy as jnp
    import numpy as np
    from repro.sph.ref_nsquared import nsq_density

    st, sim = res["state"], res["sim"]
    rho_ref, _, _ = nsq_density(jnp.asarray(st["pos"]), jnp.asarray(
        st["mass"]), jnp.asarray(st["h"]), sim.engine.box,
        kernel=sim.engine.cfg.kernel)
    rho_ref = np.asarray(rho_ref, np.float64)
    return float(np.max(np.abs(st["rho"] - rho_ref) / np.abs(rho_ref)))


def report(name: str, value) -> None:
    print(f"{name}: {value}", flush=True)


def report_production(tag: str, res: dict, devices) -> None:
    report(f"{tag} particles", res["particles"])
    report(f"{tag} compile_s", res["compile_s"])
    report(f"{tag} setup_s (build + first state, compiles included)",
           res["setup_s"])
    report(f"{tag} segment_walls_s", res["segment_walls_s"])
    report(f"{tag} compiles_per_segment", res["segment_compiles"])
    report(f"{tag} steady_cycle_wall_s", res["cycle_wall_s"])
    report(f"{tag} particle_updates", res["particle_updates"])
    report(f"{tag} peak_bytes_in_use", [peak_bytes(d) for d in devices])
    report(f"{tag} memory_stats[0]", devices[0].memory_stats())
    report(f"{tag} segment_aborts", res["segment_aborts"])


# ------------------------------------------------------------------ modes
def smoke_one_chip(n_side: int, clock: CompileClock, devices) -> None:
    spec = production_spec(n_side, 1)
    res = run_production(spec, clock)
    report_production("production", res, devices[:1])
    check_production(res)
    report("production sanity", check_sanity(res))

    ref = run_reference(spec, res["cycles"])
    report("oracle wall_s", ref["wall_s"])
    diffs = field_diffs(res["state"], ref["state"])
    report("oracle diffs", diffs)
    for name, d in diffs.items():
        check(d["max_rel"] <= ORACLE_RTOL,
              f"{name} differs from the host ladder by {d['max_rel']} "
              f"of its scale (> {ORACLE_RTOL})")
    e_rel = abs(res["energy"][1] - ref["energy"]) / abs(ref["energy"])
    report("oracle energy max_rel", e_rel)
    check(e_rel <= ORACLE_RTOL, f"energy differs from the host ladder's by "
                                f"{e_rel} (> {ORACLE_RTOL})")

    dens = run_production(production_spec(N_SIDE_DENSITY, 1), clock)
    check_production(dens)
    rel = density_error(dens)
    report(f"density vs n² at n_side={N_SIDE_DENSITY} max_rel", rel)
    check(rel <= DENSITY_RTOL, f"density off the n² reference by {rel}")
    report("compile cache hits", clock.cache_hits)


def smoke_four_chips(n_side: int, clock: CompileClock, devices) -> None:
    check(len(devices) >= 4, f"--chips 4 needs 4 devices, "
                             f"have {len(devices)}")
    res4 = run_production(production_spec(n_side, 4), clock)
    report_production("ranks=4", res4, devices[:4])
    check_production(res4)
    report("ranks=4 sanity", check_sanity(res4))

    eng = res4["sim"].engine
    # the engine's own placement of the stacked per-rank state
    resident = eng._scatter_resident(eng._get_plan())
    shards = resident["pos"].addressable_shards
    held = sorted({str(s.device) for s in shards})
    report("ranks=4 state shards on", held)
    check(len(held) == 4, f"stacked state spans {len(held)} devices, not 4")
    peaks = [peak_bytes(d) for d in devices[:4]]
    check(all(p for p in peaks), f"a device holds no memory: {peaks}")

    res1 = run_production(production_spec(n_side, 1), clock)
    report_production("ranks=1", res1, devices[:1])
    check_production(res1)
    diffs = field_diffs(res4["state"], res1["state"])
    report("ranks=4 vs ranks=1 diffs", diffs)
    for name, d in diffs.items():
        check(d["max_rel"] <= ORACLE_RTOL,
              f"{name}: 4 ranks differ from 1 rank by {d['max_rel']} of "
              f"its scale (> {ORACLE_RTOL})")
    e4, e1 = res4["energy"][1], res1["energy"][1]
    report("ranks=4 vs ranks=1 energy max_rel", abs(e4 - e1) / abs(e1))
    check(abs(e4 - e1) <= ORACLE_RTOL * abs(e1),
          f"energy: 4 ranks {e4} vs 1 rank {e1}")
    report("compile cache hits", clock.cache_hits)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--n-side", type=int, default=None,
                    help=f"lattice side (default {N_SIDE}, {N_SIDE_FOUR} "
                         f"with --chips 4)")
    args = ap.parse_args(argv)
    if args.n_side is None:
        args.n_side = N_SIDE_FOUR if args.chips == 4 else N_SIDE

    import jax
    devices = jax.devices()
    dev = devices[0]
    info = {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}
    report("device", info)
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 2

    sys.path.insert(0, os.path.join(REPO, "src"))
    from repro.compile_cache import enable_compile_cache
    report("compile cache", enable_compile_cache())
    clock = CompileClock()
    report("n_side", args.n_side)
    try:
        if args.chips == 4:
            smoke_four_chips(args.n_side, clock, devices)
        else:
            smoke_one_chip(args.n_side, clock, devices)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": info}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
