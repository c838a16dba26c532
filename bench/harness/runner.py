"""One run of one cell: set-up, the measured window, the check, the metrics.

The window drives the production entry, ``build_simulation(spec, ic)`` and
back-to-back ``sim.step()`` calls, on a fixed episode: the first
``episode_cycles`` cycles from the built state. Set-up runs the episode once,
which compiles every program it uses; each pass of the window restores the
engine to the built state, as the engine's own abort path does, and runs the
episode again. So every pass does the same work whatever the program's
speed, which each pass's stats confirm, and nothing compiles inside the
window. The answer of the window's
last pass is compared with the plain reference once the window has closed.
"""

from __future__ import annotations

import gc
import shutil
import time
from typing import Callable, Dict, List

import numpy as np

from harness.cell import ROOT, Cell, load_module
from harness.clock import CompileClock
from harness import trace as tracing

CACHE_DIR = ROOT / ".bench_cache" / "jax"
TRACE_DIR = ROOT / ".bench_traces"


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    every program in it, so that only a checkout's first run compiles."""
    import jax
    CACHE_DIR.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return str(CACHE_DIR)


def production_spec(cfg: Dict, traffic: Dict, *, observe: bool):
    """The production path: time-bin × distributed, collective transport,
    device-resident state, the device schedule."""
    from repro.sph import SimulationSpec, SPHConfig
    ph = cfg["physics"]
    return SimulationSpec(
        scenario=cfg["scenario"], scenario_params={"n_side": cfg["n_side"]},
        physics=SPHConfig(kernel=ph["kernel"], alpha_visc=ph["alpha_visc"],
                          gamma=ph["gamma"], cfl=ph["cfl"]),
        dt_max=cfg["dt_max"], max_depth=cfg["max_depth"],
        ranks=cfg["ranks"], segment_cycles=traffic["segment_cycles"],
        observe={"metrics": False,
                 "device_metrics": False} if observe else False,
        **cfg["path"])


class Episode:
    """Runs the first cycles from the built state, as often as asked.

    ``restore`` puts back what a cycle changes in the engine: its state, as
    the engine's abort path restores it (``engine.state = stash``), here a
    fresh copy on the device, so that no cache keyed on the state's arrays
    hits in the window where a simulation that runs on would miss; the
    cell layout, particle order and pair list that the closing rebin
    rebuilds, with the capacity it may grow; and the engine's counters.
    Caches the engine fills on first use and keys itself (programs, the
    rank plan, bucket sizes) keep what set-up's pass filled in, as they
    would in a simulation that runs on. ``run_cell`` holds every pass to
    set-up's stats, so a pass that did other work ends the run.
    """

    LAYOUT = ("cells", "perm", "pairs", "_ci", "_cj", "_shift")
    COUNTERS = ("particle_updates", "global_equiv_updates", "substeps",
                "segments", "segment_aborts", "halo_exported_slots",
                "halo_full_slots", "cycle_index")

    def __init__(self, sim, cycles: int):
        self.sim, self.cycles = sim, cycles
        eng = sim.engine
        self._state = eng.state
        self._saved = {k: getattr(eng, k) for k in self.LAYOUT + self.COUNTERS}
        self._capacity = eng.spec.capacity

    def restore(self) -> None:
        import jax
        import jax.numpy as jnp
        eng = self.sim.engine
        eng.state = jax.tree_util.tree_map(jnp.copy, self._state)
        for k, v in self._saved.items():
            setattr(eng, k, v)
        if eng.spec.capacity != self._capacity:
            # the rebin grows the frozen grid spec's capacity this way
            object.__setattr__(eng.spec, "capacity", self._capacity)

    def run(self) -> List[Dict]:
        """The episode's per-cycle stats, each with the segment aborts of
        its cycle under ``aborts``; returns when the state is ready."""
        import jax
        eng = self.sim.engine
        out = []
        for _ in range(self.cycles):
            before = eng.segment_aborts
            with jax.profiler.TraceAnnotation(tracing.STEP):
                stats = self.sim.step()
            stats["aborts"] = eng.segment_aborts - before
            out.append(stats)
        jax.block_until_ready(eng.state.cells.pos)
        return out


# The work a cycle did, by its own stats: every pass of the window has to
# read as set-up's pass did.
WORK = ("t", "dt_max", "depth", "substeps", "force_substeps", "updates",
        "pair_tasks", "aborts")


def work_of(stats: List[Dict]) -> List[tuple]:
    return [tuple(s.get(k) for k in WORK) for s in stats]


def read_answer(sim) -> Dict[str, np.ndarray]:
    """The engine's state in the initial condition's particle order."""
    eng = sim.engine
    st = eng.state
    perm = np.asarray(eng.perm)
    valid = perm >= 0
    idx = perm[valid]

    def flat(a):
        a = np.asarray(a)
        out = np.empty((eng.n,) + a.shape[2:], a.dtype)
        out[idx] = a[valid]
        return out

    cells = st.cells
    return {"pos": flat(cells.pos), "vel": flat(cells.vel), "u": flat(cells.u),
            "accel": flat(st.accel), "dudt": flat(st.dudt),
            "rho": flat(st.rho), "bins": flat(st.bins),
            "time": float(np.asarray(st.time))}


def peak_bytes(devices) -> int:
    """Peak device memory of the fullest device. On a TPU program
    temporaries count in ``peak_bytes_reserved``, not ``peak_bytes_in_use``."""
    peaks = []
    for d in devices:
        s = d.memory_stats() or {}
        peaks.append(int(s.get("peak_bytes_reserved",
                               s.get("peak_bytes_in_use", 0))))
    return max(peaks)


def _spans(sim) -> list:
    obs = getattr(sim, "observer", None)
    return [] if obs is None else obs.tracer.spans


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             t_start: float, log: Callable[[str], None]) -> Dict:
    """One run; returns the result object the harness prints last."""
    import jax
    from repro.sph import build_simulation

    devices = jax.devices()[:cell.chips]
    clock = CompileClock()
    cfg, traffic = cell.config, cell.traffic
    ic = load_module("scenarios", cfg["scenario"]).make(cfg, seed)
    sim = build_simulation(production_spec(cfg, traffic, observe=trace), ic)
    episode = Episode(sim, traffic["episode_cycles"])
    work = work_of(episode.run())
    log(f"set-up compiles: {clock.compiles} ({clock.seconds:.3f} s), "
        f"persistent-cache hits: {clock.cache_hits}")

    if trace:
        import jax.profiler as prof
        shutil.rmtree(TRACE_DIR / cell.name, ignore_errors=True)
        # The Python tracer slowed the host control plane by a seventh to
        # two fifths (PERF.md), so the host events are the runtime's own,
        # the harness's spans and the program's spans.
        options = prof.ProfileOptions()
        options.python_tracer_level = 0
        prof.start_trace(str(TRACE_DIR / cell.name),
                         profiler_options=options)
    compiles0, spans0 = clock.compiles, len(_spans(sim))
    stats: List[Dict] = []
    walls: List[float] = []
    setup_s = time.perf_counter() - t_start
    with jax.profiler.TraceAnnotation(tracing.WINDOW):
        t0 = time.perf_counter()
        while True:
            t = time.perf_counter()
            episode.restore()
            done = episode.run()
            walls.append(time.perf_counter() - t)
            if work_of(done) != work:
                raise RuntimeError(
                    f"pass {len(walls)} of the window did other work than "
                    f"set-up's pass: {work_of(done)} against {work}")
            stats += done
            if time.perf_counter() - t0 >= seconds:
                break
        window_s = time.perf_counter() - t0
    if trace:
        prof.stop_trace()
    window_compiles = clock.compiles - compiles0
    spans = [(s.name, s.t0, s.t1) for s in _spans(sim)[spans0:]]
    memory_peak = peak_bytes(devices)
    answer = read_answer(sim)
    del sim, episode
    gc.collect()
    log(f"window: {len(stats)} cycles in {window_s:.3f} s; "
        f"window compiles: {window_compiles}")
    log(f"episode walls: min {min(walls):.4f} s, median "
        f"{float(np.median(walls)):.4f} s, max {max(walls):.4f} s")

    run = {"seconds": window_s, "cycles": len(stats), "cycle_stats": stats,
           "setup_s": setup_s, "memory_peak_bytes": memory_peak,
           "spans": spans, "chips": cell.chips, "trace": None}
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": memory_peak}
    result = {"correct": False, "attempted": len(stats),
              "failed": sum(s["aborts"] for s in stats), "metrics": {},
              "device": device}
    if trace:
        t = time.perf_counter()
        summary = tracing.summarize(
            tracing.find_xplane(str(TRACE_DIR / cell.name)), cell.chips)
        summary.add_host_spans(spans, t0)
        log(f"trace reduced in {time.perf_counter() - t:.1f} s")
        run["trace"] = summary
        if summary.devices:
            device.update(busy_s=summary.busy_s(), window_s=summary.window_s)
        result["breakdown"] = {"device_ops": summary.top_ops(),
                               "idle_gaps": summary.idle_gaps()}
    for m in cell.metrics(trace):
        value = load_module("metrics", m["name"]).read(run)
        if value is not None:
            result["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}

    t = time.perf_counter()
    numbers = load_module("reference", cfg["reference"]).numbers(
        ic, answer, cfg, seed)
    log(f"reference in {time.perf_counter() - t:.1f} s")
    checks = {k: {"value": v, "limit": cfg["limits"][k]}
              for k, v in numbers.items()}
    result["correct"] = all(c["value"] <= c["limit"] for c in checks.values())
    result["checks"] = checks
    return result
