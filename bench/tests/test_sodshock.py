"""The Sod shock tube's check and its pair counter, on the CPU at n_side 14
(6,174 particles, 12 × 3 × 3 cells); the readings at the configuration's
own size come from ``calibrate.py`` on the chip (PERF.md)."""

import json
import time
from pathlib import Path

import numpy as np
import pytest

from harness.cell import Cell, load_module
from harness.runner import Episode, production_spec, read_answer, run_cell

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
CONFIG = "sodshock3d_n28"
N_SIDE = 14
SEEDS = (3, 2 ** 31 + 11)


def small_cell(n_side=N_SIDE, **changes):
    """The tube's cell under ``cycles_k1`` at ``n_side``, made from the
    files. The dense half's CFL step goes as h, that is as 1/n_side:
    scaling the span with it keeps the configuration's ladder (dense half
    in bin 2, sparse half in bin 1) at the smaller size."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cfg = json.loads((BENCH / "configs" / f"{CONFIG}.json").read_text())
    cfg["dt_max"] *= cfg["n_side"] / n_side
    cfg["n_side"] = n_side
    cfg.update(changes)
    traffic = json.loads((BENCH / "traffic" / "cycles_k1.json").read_text())
    return Cell(f"{CONFIG}.cycles", cfg["ranks"], cfg, traffic,
                bench["end_to_end"], bench["per_layer"])


def episode_of(cell, seed):
    from repro.sph import build_simulation
    cfg = cell.config
    ic = load_module("scenarios", cfg["scenario"]).make(cfg, seed)
    sim = build_simulation(production_spec(cfg, cell.traffic, observe=False),
                           ic)
    stats = Episode(sim, cell.traffic["episode_cycles"]).run()
    return ic, stats, read_answer(sim)


def failed(numbers, limits):
    return sorted(k for k, v in numbers.items() if not v <= limits[k])


def test_program_passes_and_control_fails():
    cell = small_cell()
    cfg = cell.config
    ref = load_module("reference", cfg["reference"])
    for seed in SEEDS:
        ic, stats, answer = episode_of(cell, seed)
        hist = stats[0]["bin_hist"]
        assert hist[1] == N_SIDE ** 3 // 4 and hist[2] == 2 * N_SIDE ** 3
        assert set(ref.numbers(ic, answer, cfg, seed)) == set(cfg["limits"])
        assert failed(ref.numbers(ic, answer, cfg, seed), cfg["limits"]) == []
        assert failed(ref.control_numbers(ic, answer, cfg, seed),
                      cfg["limits"])


def test_state_left_unchanged_is_caught(monkeypatch):
    from repro.sph.dist_timebins import DistTimeBinSimulation
    original = DistTimeBinSimulation.run_cycle

    def unchanged(self):
        before = self.state
        stats = original(self)
        self.state = before
        return stats
    monkeypatch.setattr(DistTimeBinSimulation, "run_cycle", unchanged)
    result = run_cell(small_cell(), 5, 0.5, False,
                      t_start=time.perf_counter(), log=lambda m: None)
    assert result["correct"] is False
    assert result["checks"]["time_err"]["value"] == pytest.approx(1.0)


def _interior_kick_skipped(kick):
    def skipped(state, active, dv, du, *args, **kw):
        return kick(state, active, 0.0 * dv, 0.0 * du, *args, **kw)
    return skipped


def _u_left_unkicked(kick):
    def unkicked(state, *args, **kw):
        out, n = kick(state, *args, **kw)
        return out._replace(cells=out.cells._replace(u=state.cells.u)), n
    return unkicked


@pytest.mark.parametrize("fault", [_interior_kick_skipped, _u_left_unkicked],
                         ids=["interior_kick_skipped", "u_left_unkicked"])
def test_wrong_interior_kick_is_caught(monkeypatch, fault):
    # every trip but the closing one kicks through ``_apply_force_kick``;
    # the forces, densities and drift are computed from the answer's own
    # state and stay within their limits, so kick_err alone must catch it
    from repro.sph import collectives
    monkeypatch.setattr(collectives, "_apply_force_kick",
                        fault(collectives._apply_force_kick))
    result = run_cell(small_cell(), 5, 0.5, False,
                      t_start=time.perf_counter(), log=lambda m: None)
    assert result["correct"] is False
    assert failed({k: c["value"] for k, c in result["checks"].items()},
                  small_cell().config["limits"]) == ["kick_err"]


read_hits = load_module("metrics", "pair_hit_frac").read


def test_pair_hit_frac_gives_nothing_without_the_counter():
    stats = [{"substeps": 16, "pair_table_slots": 2048,
              "pair_slots": 5 * 2048}] * 2
    assert read_hits({"cycle_stats": stats}) is None
    assert read_hits({"cycle_stats": []}) is None
    counted = {"substeps": 16, "pair_table_slots": 2048,
               "pair_slots": 5 * 2048, "pair_hits": 2048 * 64, "capacity": 8}
    assert read_hits({"cycle_stats": stats + [counted]}) == 0.5


def _neighbour_entries(pos, h, box):
    """Particle–neighbour entries with r < h_i (W(r, h_i) > 0), the
    particle itself included, by minimum image over all pairs."""
    count = 0
    for start in range(0, len(pos), 512):
        d = pos[start:start + 512, None, :] - pos[None, :, :]
        d -= box * np.round(d / box)
        r = np.sqrt(np.sum(d * d, axis=-1))
        count += int(np.sum(r < h[start:start + 512, None]))
    return count


def test_pair_hits_count_the_neighbour_entries():
    # the closing trip's density pass sums every particle's neighbours at
    # the closing positions; it alone is counted, on the sixteen-trip
    # ladder as on a one-trip cycle (max_depth 0)
    for depth in (4, 0):
        cell = small_cell(max_depth=depth)
        ic, stats, answer = episode_of(cell, 7)
        assert stats[0]["substeps"] == 2 ** depth
        counted = _neighbour_entries(answer["pos"].astype(np.float64),
                                     ic["h"].astype(np.float64),
                                     np.asarray(ic["box"]))
        assert stats[0]["pair_hits"] == counted
    (s,) = stats
    assert s["pair_slots"] == s["pair_table_slots"] and s["capacity"] == 176
    frac = read_hits({"cycle_stats": stats})
    assert frac == counted / (2 * s["pair_table_slots"] * 176 ** 2)
    assert 0.0 < frac < 0.01
