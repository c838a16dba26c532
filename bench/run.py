#!/usr/bin/env python3
"""Chip benchmark of the production SPH path, one cell per run.

    python3 bench/run.py --workload sedov3d_n60.cycles --seed 7 \\
        --seconds 30 --trace 0

Run from the root of a checkout, on a machine that holds the chips the cell
asks for. ``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1``
its per-layer metrics from a profiler trace of the window. The last line of
standard output is one JSON object: ``correct``, ``attempted`` (cycles in
the window), ``failed`` (of those, cycles whose device segment aborted to
the host ladder), ``metrics``, ``device``, with ``--trace 1`` also
``breakdown``, and last ``checks``: each number compared with the plain
reference beside its limit, which are also the last lines of standard
error. Anywhere but on a TPU with enough chips it exits non-zero and prints
no result.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from harness.cell import load_cell
    cell = load_cell(args.workload)
    try:
        import repro.sph  # noqa: F401  the system under test
    except ImportError as e:
        log(f"bench: the program is not in this checkout: {e}")
        return 3
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        log(f"bench: {args.workload} needs {cell.chips} TPU chip(s); JAX "
            f"found {len(devices)} {devices[0].platform} device(s)")
        return 2

    from harness.runner import enable_compile_cache, run_cell
    log(f"compile cache: {enable_compile_cache()}")
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      t_start=T_START, log=log)
    for name, c in result["checks"].items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
