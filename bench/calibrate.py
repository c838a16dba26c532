#!/usr/bin/env python3
"""Readings that the correctness limits are set from, for one cell.

    python3 bench/calibrate.py --workload sedov3d_n60.cycles \\
        --seeds 101 102 103 [--control]

For each seed, in one process: build the cell's initial condition, run the
window's episode once through the production entry, and compare its answer
with the plain reference, as a run of ``run.py`` does after its window.
With ``--control`` the reference computed in the next lower precision
(bfloat16) is compared in the program's place as well. One JSON line per
seed: the program's numbers, the control's, the neighbour-limiter wake and
deepening events of the episode, and its seconds.
"""

import argparse
import gc
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)

    from harness.cell import load_cell, load_module
    from harness.runner import (Episode, enable_compile_cache,
                                production_spec, read_answer)
    import jax
    from repro.observability.device_metrics import COUNT_INDEX
    from repro.sph import build_simulation

    cell = load_cell(args.workload)
    if jax.devices()[0].platform != "tpu":
        print("calibrate: needs a TPU", file=sys.stderr)
        return 2
    enable_compile_cache()
    cfg = cell.config
    ref = load_module("reference", cfg["reference"])
    scenario = load_module("scenarios", cfg["scenario"])
    for seed in args.seeds:
        ic = scenario.make(cfg, seed)
        sim = build_simulation(production_spec(cfg, cell.traffic,
                                               observe=False), ic)
        sim.engine.device_metrics_enabled = True
        t = time.perf_counter()
        stats = Episode(sim, cell.traffic["episode_cycles"]).run()
        episode_s = time.perf_counter() - t
        counts = sim.engine.device_metrics_last[0]
        events = {k: int(counts[:, COUNT_INDEX[k]].sum())
                  for k in ("wake_events", "deepen_events")}
        answer = read_answer(sim)
        del sim
        gc.collect()
        t = time.perf_counter()
        line = {"seed": seed, "program": ref.numbers(ic, answer, cfg, seed),
                "reference_s": time.perf_counter() - t,
                "episode_s": episode_s, **events,
                "updates": sum(s["updates"] for s in stats),
                "substeps": [s["substeps"] for s in stats],
                "force_substeps": [s["force_substeps"] for s in stats],
                "aborts": sum(s["aborts"] for s in stats),
                "bin0": int((answer["bins"] == 0).sum())}
        if args.control:
            line["control"] = ref.control_numbers(ic, answer, cfg,
                                                     seed)
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
