"""One traced Sedov run → validated ``trace.json`` + ``metrics.jsonl``.

The acceptance harness and the CI artifact step:

    PYTHONPATH=src python -m repro.observability --ranks 4 --cycles 1 \
        --out-dir observability-artifacts

runs the time-bin × distributed engine (collective transport,
device-resident by default) with tracing on, exports the Chrome trace and
the per-cycle metrics log, validates the trace against the minimal schema,
and asserts the record's byte/compile counters agree exactly with the
engine's ``TransferProbe``/``CompileProbe``. With device metrics enabled
(the default) it additionally checks the in-program telemetry row: per-rank
per-phase work present, exactly one ledgered pull per cycle (the segment's
``segment_stats`` pull on the device-resident cycle scan, ``metrics`` on
the host-resident ladder).
Exit status 0 means every check passed.

The ``dump`` subcommand exercises the flight recorder end-to-end:

    python -m repro.observability dump --inject-nan --out-dir flight-dumps

runs the same scenario, optionally corrupts one velocity component with a
NaN mid-run (tripping the NaN sentinel), and validates the post-mortem
bundle that results. ``dump --validate PATH`` just validates an existing
bundle.

The ``advise`` subcommand is the offline what-if repartition analysis:

    python -m repro.observability advise --metrics metrics.jsonl
    python -m repro.observability advise --ranks 4 --cycles 2

renders the per-rank cost-attribution table and the repartition advisor's
current-vs-advised imbalance trend, either from an existing metrics log
or from a fresh short clustered run (host transport — emulated ranks).

Must run before jax is imported elsewhere: under ``JAX_PLATFORMS=cpu`` it
sets ``XLA_FLAGS`` to emulate the requested rank count when the environment
hasn't already.
"""

from __future__ import annotations

import argparse
import json
import os
import sys


def _prepare_jax(ranks: int, collective: bool) -> None:
    """Emulate ``ranks`` CPU devices for the collective transport when JAX
    runs on the CPU (the flag must be set before JAX starts; on a chip the
    real devices serve), then turn the persistent compilation cache on."""
    on_cpu = os.environ.get("JAX_PLATFORMS") == "cpu"
    if collective and on_cpu and "jax" not in sys.modules:
        flags = os.environ.get("XLA_FLAGS", "")
        if "host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count="
                f"{ranks}").strip()
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()


def _spec(args):
    from repro.sph import SimulationSpec, SPHConfig
    return SimulationSpec(
        scenario="sedov",
        scenario_params={"n_side": args.n_side, "e0": 1.0, "seed": 0},
        physics=SPHConfig(alpha_visc=1.0, cfl=0.15),
        integrator="timebin", backend="distributed", ranks=args.ranks,
        dt_max=0.02, max_depth=4,
        transport=args.transport, residency=args.residency,
        observe={"flight_dir": args.out_dir})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.observability",
        description="traced Sedov run + trace/metrics export & validation")
    ap.add_argument("--ranks", type=int, default=4)
    ap.add_argument("--cycles", type=int, default=1)
    ap.add_argument("--out-dir", default=".")
    ap.add_argument("--residency", default="device",
                    choices=("host", "device"))
    ap.add_argument("--transport", default="collective",
                    choices=("host", "collective"))
    ap.add_argument("--n-side", type=int, default=6)
    ap.add_argument("--no-device-metrics", action="store_true",
                    help="disable the per-cycle telemetry pull (the row is "
                         "still computed in-program)")
    args = ap.parse_args(argv)

    _prepare_jax(args.ranks, args.transport == "collective")

    from repro.sph import build_simulation
    from repro.observability import jsonify, validate_chrome_trace

    spec = _spec(args)
    if args.no_device_metrics:
        spec = spec.with_(observe={"device_metrics": False,
                                   "flight_dir": args.out_dir})
    sim = build_simulation(spec)
    for _ in range(args.cycles):
        sim.step()
    obs = sim.observer
    eng = sim.engine

    os.makedirs(args.out_dir, exist_ok=True)
    trace_path = os.path.join(args.out_dir, "trace.json")
    metrics_path = os.path.join(args.out_dir, "metrics.jsonl")
    doc = obs.export_chrome_trace(trace_path, process_name="sedov traced run")
    obs.write_metrics_jsonl(metrics_path)
    # cost-attribution table + repartition-advisor trend, uploaded with
    # the trace artifacts by the CI acceptance step
    from repro.analysis.report import advisor_trend, attribution_table
    trend_path = os.path.join(args.out_dir, "advisor_trend.txt")
    with open(trend_path, "w") as f:
        f.write(attribution_table(obs.records) + "\n\n"
                + advisor_trend(obs.records) + "\n")

    failures = []
    errors = validate_chrome_trace(doc)
    if errors:
        failures.append(f"trace schema: {errors[:5]}")

    xs = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    rows = {e["tid"] for e in xs}
    if rows != set(range(args.ranks)):
        failures.append(f"expected one row per rank 0..{args.ranks - 1}, "
                        f"got {sorted(rows)}")
    nsub = sum(r["force_substeps"] for r in obs.records)
    for r in sorted(rows):
        if args.residency == "device":
            # one ``launch`` of the cycle_scan program per cycle
            got = sum(1 for e in xs if e["tid"] == r
                      and e["name"] == "launch")
            if got != args.cycles:
                failures.append(f"rank {r}: {got} cycle_scan launches != "
                                f"{args.cycles} cycles")
            continue
        # one phase-program slice per force sub-step
        got = sum(1 for e in xs
                  if e["tid"] == r and e["name"] in ("density", "force"))
        if got < nsub:
            failures.append(f"rank {r}: {got} phase slices < "
                            f"{nsub} force sub-steps")

    # JSONL counters agree exactly with the live probes
    rec = obs.records[-1]
    if rec["compiles"] != jsonify(eng.probe.counts()):
        failures.append(f"compile counters diverged: {rec['compiles']} != "
                        f"{eng.probe.counts()}")
    if rec["total_compiles"] != eng.probe.total_compiles():
        failures.append("total_compiles diverged")
    if rec["transfers"] != jsonify(eng.transfers.stats()):
        failures.append(f"transfer ledger diverged: {rec['transfers']} != "
                        f"{eng.transfers.stats()}")

    # device metrics: in-program per-rank rows, one ledgered pull per cycle
    if not args.no_device_metrics:
        dmx = rec.get("device_metrics")
        if not dmx:
            failures.append("no device_metrics in the cycle record")
        else:
            # per-cell attribution sums exactly to the device phase-unit
            # totals (owned rows only — halo replicas fold onto their
            # owner cell, so nothing is double-counted)
            cw = getattr(eng, "device_cell_work_last", None)
            if cw is None:
                failures.append("no device_cell_work_last on the engine")
            else:
                import numpy as np
                cells = np.asarray(cw["cells"])
                per_rank = np.asarray(cw["per_rank"])
                cols = list(cw["columns"])
                du = rec.get("device_phase_units") or {}
                # exchange exactness is a device-path identity (the host
                # ladder's value column splits shipped slots evenly per
                # rank; its per-cell column is the receiver-side truth)
                exact = ("density", "force") + (
                    ("exchange",) if args.residency == "device" else ())
                for kind in exact:
                    tot = float(cells[:, cols.index(kind)].sum())
                    want = float(du.get(kind, 0.0))
                    if abs(tot - want) > 1e-6 * max(want, 1.0):
                        failures.append(
                            f"per-cell {kind} units {tot} != device "
                            f"phase total {want}")
                if not np.allclose(cells.sum(axis=0), per_rank.sum(axis=0)):
                    failures.append(
                        "per-cell column sums disagree with per-rank "
                        f"attribution: {cells.sum(axis=0)} vs "
                        f"{per_rank.sum(axis=0)}")
            if len(dmx["per_rank_work"]) != args.ranks:
                failures.append(
                    f"device per_rank_work has "
                    f"{len(dmx['per_rank_work'])} rows != {args.ranks}")
            if not all(w > 0 for w in dmx["per_rank_work"]):
                failures.append(f"device per-rank work not all positive: "
                                f"{dmx['per_rank_work']}")
            if rec.get("device_imbalance") is None \
                    and sum(dmx["per_rank_work"]) > 0:
                failures.append("device_imbalance missing")
            if "health" not in rec:
                failures.append("health block missing")
        # the device path's row rides in each segment's one stats pull
        pull = "segment_stats" if args.residency == "device" else "metrics"
        pulls = eng.transfers.stats()["boundary_events"].get(pull, 0)
        if pulls != args.cycles:
            failures.append(f"{pulls} ledgered {pull} pulls != "
                            f"{args.cycles} cycles (pull-once contract)")

    summary = {
        "ranks": args.ranks, "cycles": args.cycles,
        "residency": args.residency, "spans": len(xs),
        "force_substeps": nsub,
        "imbalance": rec.get("imbalance"),
        "device_imbalance": rec.get("device_imbalance"),
        "device_phase_units": rec.get("device_phase_units"),
        "health": rec.get("health"),
        "dead_frac": rec.get("dead_frac"),
        "bin_occupancy_imbalance": rec.get("bin_occupancy_imbalance"),
        "total_compiles": rec.get("total_compiles"),
        "cell_work": rec.get("cell_work"),
        "cost_calibration": rec.get("cost_calibration"),
        "advisor": rec.get("advisor"),
        "trace": trace_path, "metrics": metrics_path,
        "advisor_trend": trend_path,
        "ok": not failures,
    }
    print(json.dumps(jsonify(summary), indent=1))
    if failures:
        for f in failures:
            print(f"FAIL: {f}", file=sys.stderr)
        return 1
    return 0


def dump_main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.observability dump",
        description="produce (and validate) a flight-recorder post-mortem "
                    "bundle; --inject-nan trips the NaN sentinel on purpose")
    ap.add_argument("--ranks", type=int, default=4)
    ap.add_argument("--cycles", type=int, default=2)
    ap.add_argument("--out-dir", default="flight-dumps")
    ap.add_argument("--residency", default="device",
                    choices=("host", "device"))
    ap.add_argument("--transport", default="collective",
                    choices=("host", "collective"))
    ap.add_argument("--n-side", type=int, default=6)
    ap.add_argument("--inject-nan", action="store_true",
                    help="corrupt one velocity component before the last "
                         "cycle so the NaN sentinel trips")
    ap.add_argument("--validate", metavar="PATH",
                    help="only validate an existing bundle directory")
    args = ap.parse_args(argv)

    from repro.observability.flight import validate_bundle

    if args.validate:
        manifest = validate_bundle(args.validate)
        print(json.dumps({"bundle": args.validate,
                          "manifest": manifest, "ok": True}, indent=1))
        return 0

    _prepare_jax(args.ranks, args.transport == "collective")

    import numpy as np
    from repro.sph import build_simulation
    from repro.observability import jsonify

    sim = build_simulation(_spec(args))
    eng = sim.engine
    for n in range(args.cycles):
        if args.inject_nan and n == args.cycles - 1:
            # poison one alive particle's velocity on the global mirror —
            # the next cycle's scatter carries it onto the mesh and the
            # in-program sentinel must catch it
            cells = eng.state.cells
            vel = np.asarray(cells.vel).copy()
            alive = np.argwhere(np.asarray(cells.mask) > 0)
            c, p = alive[0]
            vel[c, p, 0] = np.nan
            import jax.numpy as jnp
            eng.state = eng.state._replace(
                cells=cells._replace(vel=jnp.asarray(vel)))
        sim.step()
    obs = sim.observer

    dumps = list(obs.flight.dumps)
    if not dumps:
        # no sentinel tripped (healthy run without --inject-nan): dump the
        # ring explicitly so the bundle path is exercised either way
        dumps = [obs.dump_flight(reason="manual")]

    out = []
    for path in dumps:
        manifest = validate_bundle(path)
        out.append({"bundle": path, "reason": manifest["reason"],
                    "cycle": manifest["cycle"],
                    "records": manifest["records"]})
    tripped = bool(obs.records and obs.records[-1]
                   .get("health", {}).get("tripped"))
    print(json.dumps(jsonify({"dumps": out, "tripped": tripped,
                              "ok": True}), indent=1))
    if args.inject_nan and not tripped:
        print("FAIL: NaN injected but no sentinel tripped", file=sys.stderr)
        return 1
    return 0


def advise_main(argv=None) -> int:
    """Offline what-if repartition analysis (schema v3).

    With ``--metrics`` renders the cost-attribution table and advisor
    trend from an existing per-cycle JSONL (any supported schema —
    pre-v3 logs render '-' markers). Without it, runs a short clustered
    scenario on an emulated rank partition (host transport — no real
    devices needed) and advises on its *measured* cell weights.
    """
    ap = argparse.ArgumentParser(
        prog="python -m repro.observability advise",
        description="offline what-if repartition analysis: attribution "
                    "table + advisor trend from a metrics.jsonl, or from "
                    "a fresh short clustered run")
    ap.add_argument("--metrics", metavar="PATH",
                    help="existing metrics.jsonl to analyse")
    ap.add_argument("--scenario", default="clustered")
    ap.add_argument("--n", type=int, default=96,
                    help="particle count for the fresh-run mode")
    ap.add_argument("--ranks", type=int, default=4)
    ap.add_argument("--cycles", type=int, default=2)
    ap.add_argument("--out", metavar="PATH",
                    help="also write the rendered report here")
    args = ap.parse_args(argv)

    from repro.analysis.report import advisor_trend, attribution_table

    if args.metrics:
        from repro.observability import read_metrics_jsonl
        records = read_metrics_jsonl(args.metrics)
    else:
        _prepare_jax(args.ranks, collective=False)
        from repro.sph import SimulationSpec, build_simulation
        spec = SimulationSpec(
            scenario=args.scenario,
            scenario_params={"n": args.n, "seed": 0},
            integrator="timebin", backend="distributed", ranks=args.ranks,
            transport="host", observe=True)
        sim = build_simulation(spec)
        for _ in range(args.cycles):
            sim.step()
        records = sim.observer.records
    report = attribution_table(records) + "\n\n" + advisor_trend(records)
    print(report)
    if args.out:
        with open(args.out, "w") as f:
            f.write(report + "\n")
    return 0


if __name__ == "__main__":
    _argv = sys.argv[1:]
    if _argv and _argv[0] == "dump":
        raise SystemExit(dump_main(_argv[1:]))
    if _argv and _argv[0] == "advise":
        raise SystemExit(advise_main(_argv[1:]))
    raise SystemExit(main(_argv))
