"""Strong-scaling experiment (paper Figs. 5/6/8 at laptop scale).

Sweeps rank counts over the clustered task graph and prints the speed-up
and parallel-efficiency columns for async (SWIFT) vs bulk-synchronous
execution — CSV ready for plotting.

Run:  PYTHONPATH=src python examples/sph_strong_scaling.py [n_particles]
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def main():
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 8000
    from benchmarks.strong_scaling import run
    rows = run(n_particles=n,
               ranks_list=(1, 2, 4, 8, 16, 32, 64, 128))
    print("\nranks,mode,makespan_us,efficiency")
    for r in rows:
        parts = r["name"].split("/")
        eff = r["derived"].split("=")[1]
        print(f"{parts[2][5:]},{parts[1]},{r['us_per_call']},{eff}")


if __name__ == "__main__":
    main()
