"""Shared helpers for the benchmark harness.

Standalone-runnable: ``python -m benchmarks.common`` (or ``python
benchmarks/common.py``) validates the repo-root ``BENCH_*.json``
artifacts and prints the trajectory + environment provenance as JSON —
the same blocks ``benchmarks/run.py`` embeds in ``summary.json``.
"""

from __future__ import annotations

import contextlib
import io
import json
import os
import subprocess
import sys
import time
from typing import Callable, Dict, List

import numpy as np

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "results")
REPO_ROOT = os.path.join(os.path.dirname(__file__), "..")


def timeit(fn: Callable, *args, repeats: int = 5, warmup: int = 1) -> float:
    """Median wall seconds per call."""
    import jax
    for _ in range(warmup):
        out = fn(*args)
        jax.block_until_ready(out)
    ts = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def run_worker(script: str, nranks: int, name: str) -> Dict:
    """Run a benchmark worker script; return its ``RESULT_JSON=`` payload.

    Under ``JAX_PLATFORMS=cpu`` the worker runs in a child process with
    ``nranks`` emulated CPU devices, a flag that must be set before JAX
    starts. On an accelerator a chip belongs to one process, so the worker
    runs in this one.
    """
    if os.environ.get("JAX_PLATFORMS") == "cpu":
        env = dict(os.environ, XLA_FLAGS="--xla_force_host_platform_"
                                         f"device_count={nranks}")
        proc = subprocess.run([sys.executable, "-c", script], env=env,
                              capture_output=True, text=True, timeout=3600)
        if proc.returncode != 0:
            raise RuntimeError(
                f"{name} worker failed:\n{proc.stderr[-3000:]}")
        stdout = proc.stdout
    else:
        buf = io.StringIO()
        with contextlib.redirect_stdout(buf):
            exec(compile(script, f"<{name} worker>", "exec"),
                 {"__name__": f"{name}_worker"})
        stdout = buf.getvalue()
    payload = next(line for line in stdout.splitlines()
                   if line.startswith("RESULT_JSON="))
    return json.loads(payload[len("RESULT_JSON="):])


def emit(rows: List[Dict], name: str) -> None:
    """Print CSV rows (``name,us_per_call,derived``) and save JSON."""
    os.makedirs(RESULTS_DIR, exist_ok=True)
    with open(os.path.join(RESULTS_DIR, f"{name}.json"), "w") as f:
        json.dump(rows, f, indent=1, default=str)
    for r in rows:
        us = r.get("us_per_call", "")
        derived = r.get("derived", "")
        print(f"{r['name']},{us},{derived}", flush=True)


def env_provenance() -> dict:
    """What ran these numbers: versions, backend, devices, XLA flags."""
    env = {"python": sys.version.split()[0],
           "platform": sys.platform,
           "xla_flags": os.environ.get("XLA_FLAGS", ""),
           "jax_platforms": os.environ.get("JAX_PLATFORMS", "")}
    try:
        import jax
        import jaxlib
        env["jax"] = jax.__version__
        env["jaxlib"] = jaxlib.__version__
        env["backend"] = jax.default_backend()
        env["device_count"] = jax.device_count()
    except Exception as e:  # pragma: no cover - jax is a baked-in dep
        env["jax"] = f"unavailable: {type(e).__name__}"
    try:
        sys.path.insert(0, os.path.join(REPO_ROOT, "src"))
        from repro.observability import METRICS_SCHEMA_VERSION
        env["metrics_schema_version"] = METRICS_SCHEMA_VERSION
    except Exception:  # pragma: no cover
        pass
    return env


def bench_trajectory(root: str = REPO_ROOT) -> List[Dict]:
    """Validate the repo-root ``BENCH_*.json`` artifacts and list them.

    Each benchmark module leaves its headline artifact at the repo root;
    this collects them into one trajectory list (embedded in
    ``summary.json`` as the cross-run provenance record), checking every
    file parses, is a dict with a ``benchmark`` name, and does not claim
    a metrics schema newer than this tree understands. A malformed
    artifact is reported in the list (``valid: false``) rather than
    silently skipped."""
    try:
        sys.path.insert(0, os.path.join(REPO_ROOT, "src"))
        from repro.observability import METRICS_SCHEMA_VERSION
    except Exception:  # pragma: no cover
        METRICS_SCHEMA_VERSION = None
    out = []
    for fname in sorted(os.listdir(root)):
        if not (fname.startswith("BENCH_") and fname.endswith(".json")):
            continue
        path = os.path.join(root, fname)
        entry = {"file": fname, "valid": True, "problems": []}
        try:
            with open(path) as f:
                doc = json.load(f)
        except (OSError, ValueError) as e:
            entry["valid"] = False
            entry["problems"].append(f"unreadable: {e}")
            out.append(entry)
            continue
        if not isinstance(doc, dict):
            entry["valid"] = False
            entry["problems"].append("not a JSON object")
            out.append(entry)
            continue
        entry["benchmark"] = doc.get("benchmark")
        if not entry["benchmark"]:
            entry["valid"] = False
            entry["problems"].append("missing 'benchmark' name")
        ver = doc.get("metrics_schema_version")
        entry["metrics_schema_version"] = ver
        if ver is not None and METRICS_SCHEMA_VERSION is not None \
                and ver > METRICS_SCHEMA_VERSION:
            entry["valid"] = False
            entry["problems"].append(
                f"claims metrics schema {ver} > understood "
                f"{METRICS_SCHEMA_VERSION}")
        entry["mtime_unix"] = round(os.path.getmtime(path), 1)
        out.append(entry)
    return out


def build_clustered_taskgraph(n_particles=4096, seed=0, *, base_side=6,
                              threshold=48, rate=2e-9):
    """Clustered-IC task graph over the §3.1-refined (split) cell set.

    Costs are seconds (``rate`` s/interaction — the measured-cost
    calibration of §3.2, ≈CPU pair-interaction throughput). Returns
    (task graph, n_leaves, per-leaf occupancy).
    """
    from repro.core import TaskGraph
    from repro.sph import clustered_ic
    from repro.sph.adaptive import refined_cell_graph
    import numpy as np

    ic = clustered_ic(n_particles, seed=seed)
    node_w, edges, leaves = refined_cell_graph(
        ic["pos"], ic["box"], base_side, threshold=threshold, max_levels=5)
    n_ngb = 48.0
    g = TaskGraph()
    occ = np.array([l.occupancy for l in leaves], dtype=np.int64)

    def self_cost(o):
        return rate * min(0.5 * o * o, n_ngb * o)

    def pair_cost(a, b):
        return rate * min(a * b, n_ngb * min(a, b))

    sort = [g.add_task("sort", resources=(c,), writes=(c,),
                       cost=max(rate * 2 * occ[c], 1e-9))
            for c in range(len(leaves))]
    ghost = [g.add_task("ghost", resources=(c,), writes=(c,),
                        cost=max(rate * occ[c], 1e-9))
             for c in range(len(leaves))]
    kick = [g.add_task("kick", resources=(c,), writes=(c,),
                       cost=max(rate * occ[c], 1e-9))
            for c in range(len(leaves))]
    for c in range(len(leaves)):
        d = g.add_task("density_self", resources=(c,), writes=(c,),
                       cost=max(self_cost(occ[c]), 1e-9))
        f = g.add_task("force_self", resources=(c,), writes=(c,),
                       cost=max(self_cost(occ[c]), 1e-9))
        g.add_dependency(d, sort[c])
        g.add_dependency(ghost[c], d)
        g.add_dependency(f, ghost[c])
        g.add_dependency(kick[c], f)
    for (a, b), _w in edges.items():
        d = g.add_task("density_pair", resources=(a, b), writes=(a, b),
                       cost=max(pair_cost(occ[a], occ[b]), 1e-9))
        f = g.add_task("force_pair", resources=(a, b), writes=(a, b),
                       cost=max(pair_cost(occ[a], occ[b]), 1e-9))
        for c in (a, b):
            g.add_dependency(d, sort[c])
            g.add_dependency(ghost[c], d)
            g.add_dependency(f, ghost[c])
            g.add_dependency(kick[c], f)
    return g, len(leaves), occ


if __name__ == "__main__":
    print(json.dumps({"_env": env_provenance(),
                      "_bench_trajectory": bench_trajectory()}, indent=1))
    raise SystemExit(
        1 if any(not e["valid"] for e in bench_trajectory()) else 0)
