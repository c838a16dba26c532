# One function per paper table/figure. Prints ``name,us_per_call,derived``
# CSV rows; JSON copies land in benchmarks/results/, and a run index with
# per-module status/timing in benchmarks/results/summary.json.
#
# Modules whose optional dependencies or device requirements are absent
# (e.g. not enough addressable devices for a mesh, a kernel backend the
# container lacks) are *skipped*, not failed: a partial benchmark run on a
# laptop still produces every row it can.
from __future__ import annotations

import importlib
import json
import os
import sys
import time
import traceback

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "results")

# exception texts that mean "environment can't run this" rather than "the
# benchmark is broken" — matched case-insensitively
_SKIP_MARKERS = (
    "addressable devices",
    "host_platform_device_count",
    "requires jaxlib",
    "unavailable backend",
    "not supported on this platform",
)


def _classify(exc: BaseException) -> str:
    if isinstance(exc, ModuleNotFoundError):
        # a missing *external* module is the environment's fault; a repo
        # module failing to resolve is a bug and must fail the run
        missing = exc.name or ""
        return ("error" if missing.startswith(("repro", "benchmarks"))
                else "skipped")
    if isinstance(exc, NotImplementedError):
        return "skipped"
    text = str(exc).lower()
    if any(marker in text for marker in _SKIP_MARKERS):
        return "skipped"
    return "error"


def main() -> None:
    print("name,us_per_call,derived")
    # module names, imported lazily inside the try below: a missing
    # optional dependency at *import* time must classify as a skip of
    # that one module, not crash the whole run before any rows print
    modules = [
        ("strong_scaling (Figs 5/6/8)", "strong_scaling"),
        ("intranode_scaling (Fig 7)", "intranode_scaling"),
        ("comm_stats (§5 messages)", "comm_stats"),
        ("partition_quality (Fig 4)", "partition_quality"),
        ("baseline_compare (§5 GADGET-2)", "baseline_compare"),
        ("kernels_bench", "kernels_bench"),
        ("halo_transport (host vs collective vs fused wire)",
         "halo_transport"),
        ("observability (task plots)", "observability_bench"),
        ("fleet_throughput (batched serving)", "fleet_throughput"),
    ]
    summary = {}
    failures = []
    for label, modname in modules:
        t0 = time.time()
        try:
            mod = importlib.import_module(f".{modname}", __package__)
            mod.run()
        except Exception as e:
            status = _classify(e)
            summary[label] = {
                "status": status, "seconds": round(time.time() - t0, 1),
                "reason": f"{type(e).__name__}: {e}"}
            if status == "error":
                failures.append((label, e))
                print(f"{label},ERROR,{type(e).__name__}: {e}",
                      file=sys.stderr)
                traceback.print_exc()
            else:
                print(f"{label},SKIP,{type(e).__name__}: {e}",
                      file=sys.stderr)
        else:
            summary[label] = {"status": "ok",
                              "seconds": round(time.time() - t0, 1)}
            print(f"# {label} done in {time.time() - t0:.1f}s",
                  file=sys.stderr)
    # factored into benchmarks/common.py so they are standalone-runnable
    # (``python -m benchmarks.common``) and testable without a full run
    from .common import bench_trajectory, env_provenance
    summary["_env"] = env_provenance()
    summary["_bench_trajectory"] = bench_trajectory()
    bad = [e["file"] for e in summary["_bench_trajectory"]
           if not e["valid"]]
    if bad:
        print(f"# invalid BENCH artifacts: {bad}", file=sys.stderr)
    os.makedirs(RESULTS_DIR, exist_ok=True)
    with open(os.path.join(RESULTS_DIR, "summary.json"), "w") as f:
        json.dump(summary, f, indent=1)
    if failures:
        raise SystemExit(f"{len(failures)} benchmark modules failed")


if __name__ == "__main__":
    main()
