"""The correctness check: sound runs pass it, the control and each fault
the cells can have fail it. On the CPU at n_side 8; the readings at each
cell's own size come from ``calibrate.py`` on the chip (PERF.md)."""

import json
import shutil
import subprocess
import sys
import time
from pathlib import Path

import jax
import numpy as np
import pytest

from harness.cell import Cell, load_module
from harness.runner import (Episode, production_spec, read_answer, run_cell,
                            work_of)

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
N_SIDE = 8
SEEDS = (3, 2 ** 31 + 11)
CONFIGS = ("sedov3d_n60", "sedov3d_n32_r4")


def small_cell(config, n_side=N_SIDE):
    """The cell of ``config`` under ``cycles_k1`` at ``n_side``, made from
    the files, so that a configuration is checked before ``BENCHMARK.json``
    lists a cell of it."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cfg = json.loads((BENCH / "configs" / f"{config}.json").read_text())
    # The blast's CFL step goes as n_side**-2.5 (E0 shared by N_inject
    # particles of mass 1/n_side**3, h as 1/n_side): scaling the span with
    # it keeps the configuration's ladder at the smaller size.
    cfg["dt_max"] *= (cfg["n_side"] / n_side) ** 2.5
    cfg["n_side"] = n_side
    traffic = json.loads((BENCH / "traffic" / "cycles_k1.json").read_text())
    return Cell(f"{config}.cycles", cfg["ranks"], cfg, traffic,
                bench["end_to_end"], bench["per_layer"])


def answer_of(cell, seed):
    from repro.sph import build_simulation
    cfg = cell.config
    ic = load_module("scenarios", cfg["scenario"]).make(cfg, seed)
    sim = build_simulation(production_spec(cfg, cell.traffic, observe=False),
                           ic)
    Episode(sim, cell.traffic["episode_cycles"]).run()
    return ic, read_answer(sim)


def failed(numbers, limits):
    return sorted(k for k, v in numbers.items() if not v <= limits[k])


@pytest.mark.parametrize("config", CONFIGS)
def test_program_passes_and_control_fails(config):
    cell = small_cell(config)
    cfg = cell.config
    ref = load_module("reference", cfg["reference"])
    for seed in SEEDS:
        ic, answer = answer_of(cell, seed)
        assert failed(ref.numbers(ic, answer, cfg, seed), cfg["limits"]) == []
        assert failed(ref.control_numbers(ic, answer, cfg, seed), cfg["limits"])


def _run(config, n_side=N_SIDE):
    return run_cell(small_cell(config, n_side), 5, 0.5, False,
                    t_start=time.perf_counter(), log=lambda m: None)


@pytest.fixture
def engine_class():
    from repro.sph.dist_timebins import DistTimeBinSimulation
    return DistTimeBinSimulation


def test_sound_run_is_correct():
    result = _run("sedov3d_n60")
    assert result["correct"] is True
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert list(result)[-1] == "checks"
    assert set(result["metrics"]) == {"cycle_s", "updates_per_s", "setup_s"}


def test_state_left_unchanged_is_caught(monkeypatch, engine_class):
    original = engine_class.run_cycle

    def unchanged(self):
        before = self.state
        stats = original(self)
        self.state = before
        return stats
    monkeypatch.setattr(engine_class, "run_cycle", unchanged)
    result = _run("sedov3d_n60")
    assert result["correct"] is False
    assert result["checks"]["time_err"]["value"] == pytest.approx(1.0)


def test_half_the_particles_left_out_is_caught(monkeypatch, engine_class):
    segment, gather = engine_class._run_segment, engine_class._gather_resident

    def keep_before(self):
        self._before = self.state
        return segment(self)

    def half_gathered(self, plan, res):
        gather(self, plan, res)
        half = self.state.bins.shape[0] // 2
        mix = lambda new, old: new.at[half:].set(old[half:])
        st, old = self.state, self._before
        self.state = st._replace(
            cells=jax.tree_util.tree_map(mix, st.cells, old.cells),
            **{k: mix(getattr(st, k), getattr(old, k))
               for k in ("accel", "dudt", "rho", "omega", "bins",
                         "t_start")})
    monkeypatch.setattr(engine_class, "_run_segment", keep_before)
    monkeypatch.setattr(engine_class, "_gather_resident", half_gathered)
    assert _run("sedov3d_n60")["correct"] is False


def test_halo_left_out_is_caught(monkeypatch, engine_class):
    # The rows a rank holds of its neighbours' cells reach it when the
    # state is placed on the mesh each cycle. Zeroing the in-program
    # exchange instead (its valid tables) leaves the answer bitwise
    # unchanged at n_side 16 and 32: each rank recomputes what it ships.
    scatter = engine_class._scatter_resident

    def no_halo(self, plan):
        res = scatter(self, plan)
        mask = res["mask"]
        res.update({"mask": jax.device_put(mask.at[:, plan.K:].set(0.0),
                                           mask.sharding)})
        return res
    monkeypatch.setattr(engine_class, "_scatter_resident", no_halo)
    result = _run("sedov3d_n32_r4", n_side=16)
    assert result["correct"] is False
    assert result["checks"]["rho_err"]["value"] > \
        result["checks"]["rho_err"]["limit"]


def test_altered_answer_is_caught(monkeypatch, engine_class):
    gather = engine_class._gather_resident

    def altered(self, plan, res):
        gather(self, plan, res)
        i = int(np.argmax(np.asarray(self.state.cells.mask).ravel()))
        rho = self.state.rho.reshape(-1)
        self.state = self.state._replace(
            rho=rho.at[i].multiply(1.01).reshape(self.state.rho.shape))
    monkeypatch.setattr(engine_class, "_gather_resident", altered)
    result = _run("sedov3d_n60")
    assert result["correct"] is False
    assert result["checks"]["rho_err"]["value"] == pytest.approx(1e-2,
                                                                 rel=0.01)


def test_skipped_drift_is_caught(monkeypatch, engine_class):
    segment, gather = engine_class._run_segment, engine_class._gather_resident

    def keep_before(self):
        self._before = self.state
        return segment(self)

    def not_drifted(self, plan, res):
        gather(self, plan, res)
        cells = self.state.cells._replace(pos=self._before.cells.pos)
        self.state = self.state._replace(cells=cells)
    monkeypatch.setattr(engine_class, "_run_segment", keep_before)
    monkeypatch.setattr(engine_class, "_gather_resident", not_drifted)
    result = _run("sedov3d_n60")
    assert result["correct"] is False
    assert result["checks"]["drift_err"]["value"] == pytest.approx(1.0,
                                                                   abs=0.05)


def test_episode_repeats_its_work():
    cell = small_cell("sedov3d_n60")
    from repro.sph import build_simulation
    cfg = cell.config
    ic = load_module("scenarios", cfg["scenario"]).make(cfg, 9)
    sim = build_simulation(production_spec(cfg, cell.traffic, observe=False),
                           ic)
    episode = Episode(sim, 2)
    first = episode.run()
    assert first[0]["depth"] == cfg["max_depth"]
    assert first[0]["force_substeps"] < first[0]["substeps"]
    episode.restore()
    assert work_of(episode.run()) == work_of(first)
    # without the restore the next pass runs later cycles
    assert work_of(episode.run()) != work_of(first)


def test_pass_doing_other_work_ends_the_run(monkeypatch):
    monkeypatch.setattr(Episode, "restore", lambda self: None)
    with pytest.raises(RuntimeError, match="other work"):
        _run("sedov3d_n60")


def _cli(cwd, *args):
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "sedov3d_n60.cycles",
         "--seed", "1", "--seconds", "1", "--trace", "0", *args],
        cwd=cwd, capture_output=True, text=True, timeout=300)


def test_refuses_without_a_tpu():
    p = _cli(ROOT)
    assert p.returncode != 0 and p.stdout == ""


def test_refuses_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _cli(tmp_path)
    assert p.returncode != 0 and p.stdout == ""


def test_benchmark_names_a_file_for_each_entry():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for c in bench["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert (BENCH / "scenarios" / f"{cfg['scenario']}.py").is_file()
        assert (BENCH / "reference" / f"{cfg['reference']}.py").is_file()
    for w in bench["workloads"]:
        assert (BENCH / "traffic" / f"{w['traffic']}.json").is_file()
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file()
