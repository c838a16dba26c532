"""Per-phase timings of one traced Sedov run + tracer overhead (task plots).

Runs the distributed time-bin engine (4 emulated ranks, collective
transport, device residency: the cycle scan) with ``observe=True`` for a
few cycles and
reports the median per-span wall time of every traced phase — the numbers
behind the task-timeline plot — plus the cost of the tracer itself
(median seconds per recorded span, measured over 20k no-payload spans).

Results land in ``benchmarks/results/observability_bench.json`` and, as
the repo-level benchmark artifact, in ``BENCH_observability.json`` at the
repo root (per-phase medians, run provenance, metrics schema version).
Since schema v3 the artifact also carries the attribution column: what
the single per-cycle pull carries beyond the metrics row (the per-cell
work vectors and the scan's counters — on the cycle scan the row rides in
each segment's ``segment_stats`` pull), the cost-calibration fit residual,
and the repartition advisor's advised-vs-current imbalance (which must
never regress).

Under ``JAX_PLATFORMS=cpu`` the measurement runs in a subprocess with
4 emulated CPU devices; on an accelerator it runs in this process,
which holds the chip (``common.run_worker``).

Run:  PYTHONPATH=src python benchmarks/observability_bench.py [n_side] [ncycles]
"""

from __future__ import annotations

import json
import os
import sys
import time

try:                                    # runnable as module or script
    from .common import emit, env_provenance, run_worker
except ImportError:                     # pragma: no cover
    from common import emit, env_provenance, run_worker

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))

_WORKER = """
import os
import sys, json
sys.path.insert(0, %(src)r)
import numpy as np
import jax
from repro.sph import SimulationSpec, SPHConfig, build_simulation
from repro.observability import UMBRELLA_SPANS

spec = SimulationSpec(
    scenario="sedov",
    scenario_params={"n_side": %(n_side)d, "e0": 1.0, "seed": 0},
    physics=SPHConfig(alpha_visc=1.0, cfl=0.15),
    integrator="timebin", backend="distributed", ranks=%(nranks)d,
    dt_max=0.02, max_depth=4,
    transport="collective", residency="device", observe=True)
sim = build_simulation(spec)
for _ in range(%(warm)d):                         # compile + bucket settle
    sim.step()
mark = len(sim.observer.tracer.spans)
for _ in range(%(ncycles)d):
    sim.step()
spans = sim.observer.tracer.spans[mark:]

per = {}
for s in spans:
    if s.name in UMBRELLA_SPANS:
        continue
    per.setdefault(s.name, []).append(s.dur * 1e6)
rec = sim.observer.records[-1]
tstats = sim.engine.transfers.stats()
out = {
    "phases": {k: {"median_us": float(np.median(v)), "count": len(v)}
               for k, v in sorted(per.items())},
    "imbalance": rec.get("imbalance"),
    "dead_frac": rec.get("dead_frac"),
    "total_compiles": rec.get("total_compiles"),
    "force_substeps": rec.get("force_substeps"),
    "device_imbalance": rec.get("device_imbalance"),
    "device_phase_units": rec.get("device_phase_units"),
    # the metrics row rides in each segment's one stats pull
    "metrics_pulls": tstats["boundary_events"].get("segment_stats", 0),
    "metrics_pull_bytes": tstats["boundary_bytes"].get("segment_stats", 0),
    "cell_work": rec.get("cell_work"),
    "cost_calibration": rec.get("cost_calibration"),
    "advisor": rec.get("advisor"),
    "metrics_row_bytes": int(sum(np.asarray(a).nbytes
                                 for a in sim.engine.device_metrics_last)),
    "cycles_total": %(warm)d + %(ncycles)d,
    "backend": jax.default_backend(),
    "device_count": jax.device_count(),
    "jax": jax.__version__,
}
print("RESULT_JSON=" + json.dumps(out, default=str))
"""


def _tracer_overhead_us(n: int = 20000) -> float:
    """Median seconds per recorded span, enabled tracer, no payload."""
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.observability import Tracer
    tr = Tracer()
    samples = []
    for _ in range(20):
        t0 = time.perf_counter()
        for _ in range(n // 20):
            with tr.span("bench", rank=0):
                pass
        samples.append((time.perf_counter() - t0) / (n // 20))
    samples.sort()
    return 1e6 * samples[len(samples) // 2]


def run(n_side=6, ncycles=3, nranks=4, warm=2) -> list:
    script = _WORKER % {"nranks": nranks, "n_side": n_side,
                        "ncycles": ncycles, "warm": warm,
                        "src": os.path.join(ROOT, "src")}
    res = run_worker(script, nranks, "observability_bench")
    overhead_us = _tracer_overhead_us()

    rows = []
    for name, ph in res["phases"].items():
        rows.append({
            "name": f"observability/phase/{name}/median_us",
            "us_per_call": round(ph["median_us"], 1),
            "derived": f"count={ph['count']};nranks={nranks};"
                       f"n_side={n_side};ncycles={ncycles}"})
    rows.append({
        "name": "observability/tracer_span_overhead/median_us",
        "us_per_call": round(overhead_us, 3),
        "derived": "enabled tracer, empty span body"})
    rows.append({
        "name": "observability/run/imbalance",
        "us_per_call": round(res.get("imbalance") or 0.0, 4),
        "derived": f"dead_frac={res.get('dead_frac'):.4f};"
                   f"total_compiles={res.get('total_compiles')}"})
    # device telemetry pull cost: the contract is ONE host<->device
    # transfer per cycle, regardless of rank count or phase count
    cyc = res.get("cycles_total") or (ncycles + warm)
    pulls = res.get("metrics_pulls", 0)
    pulls_per_cycle = pulls / cyc if cyc else 0.0
    rows.append({
        "name": "observability/device_metrics/pulls_per_cycle",
        "us_per_call": round(pulls_per_cycle, 3),
        "derived": f"pulls={pulls};cycles={cyc};"
                   f"bytes={res.get('metrics_pull_bytes', 0)};"
                   f"device_imbalance={res.get('device_imbalance')}"})
    if pulls_per_cycle > 1.0:
        raise RuntimeError(
            f"device-metrics pull cost exceeds one transfer per cycle: "
            f"{pulls} pulls over {cyc} cycles")
    # attribution column (schema v3): the per-cell vectors' share of the
    # single metrics pull, the calibration fit residual, and the
    # repartition advisor's advised-vs-current imbalance
    adv = res.get("advisor") or {}
    cal = res.get("cost_calibration") or {}
    row_bytes = res.get("metrics_row_bytes") or 0
    cell_pull_bytes = (res.get("metrics_pull_bytes", 0) / pulls - row_bytes
                       if pulls else 0.0)
    resid = cal.get("residual")
    rows.append({
        "name": "observability/attribution/cell_pull_bytes_per_cycle",
        "us_per_call": round(cell_pull_bytes, 1),
        "derived": f"calibration_residual="
                   f"{'-' if resid is None else round(resid, 4)};"
                   f"advised={adv.get('advised_imbalance')};"
                   f"current={adv.get('current_imbalance')}"})
    if adv and adv.get("advised_imbalance", 0.0) \
            > adv.get("current_imbalance", 0.0) + 1e-9:
        raise RuntimeError(
            f"advisor regressed the partition: advised "
            f"{adv['advised_imbalance']} > current "
            f"{adv['current_imbalance']}")
    emit(rows, "observability_bench")

    from repro.observability import METRICS_SCHEMA_VERSION
    bench = {
        "benchmark": "observability",
        "scenario": "sedov",
        "nranks": nranks, "n_side": n_side,
        "ncycles": ncycles, "warmup_cycles": warm,
        "residency": "device", "transport": "collective",
        "metrics_schema_version": METRICS_SCHEMA_VERSION,
        "env": {"python": sys.version.split()[0],
                "jax": res.get("jax"),
                "backend": res.get("backend"),
                "device_count": res.get("device_count")},
        "phase_median_us": {k: v["median_us"]
                            for k, v in res["phases"].items()},
        "phase_counts": {k: v["count"] for k, v in res["phases"].items()},
        "tracer_span_overhead_us": overhead_us,
        "imbalance": res.get("imbalance"),
        "dead_frac": res.get("dead_frac"),
        "total_compiles": res.get("total_compiles"),
        "device_metrics": {
            "pulls": pulls,
            "cycles": cyc,
            "pulls_per_cycle": pulls_per_cycle,
            "pull_bytes": res.get("metrics_pull_bytes", 0),
            "device_imbalance": res.get("device_imbalance"),
            "device_phase_units": res.get("device_phase_units"),
        },
        "attribution": {
            "cell_pull_bytes_per_cycle": cell_pull_bytes,
            "cell_work": res.get("cell_work"),
            "cost_calibration": res.get("cost_calibration"),
            "advisor": res.get("advisor"),
        },
        "_env": env_provenance(),
    }
    with open(os.path.join(ROOT, "BENCH_observability.json"), "w") as f:
        json.dump(bench, f, indent=1, default=str)
    return rows


if __name__ == "__main__":
    n_side = int(sys.argv[1]) if len(sys.argv) > 1 else 6
    ncycles = int(sys.argv[2]) if len(sys.argv) > 2 else 3
    run(n_side=n_side, ncycles=ncycles)
