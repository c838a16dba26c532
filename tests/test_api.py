"""SimulationSpec front-end: registry, quadrants, distributed time-bin
parity and activity-aware halo volumes."""

import warnings

import numpy as np
import pytest
import jax.numpy as jnp

from repro.core import (CostModel, bin_occupancy_imbalance, insert_comm_tasks,
                        rank_bin_occupancy, TaskGraph)
from repro.sph import (SCENARIOS, SimulationProtocol, SimulationSpec, SPHConfig,
                       build_simulation, kelvin_helmholtz_ic, make_ic,
                       register_scenario, sedov_ic, sodshock_ic)
from repro.sph.dist_timebins import build_rank_plan, halo_export_schedule


# ------------------------------------------------------------------ the spec
def test_spec_validation():
    with pytest.raises(ValueError, match="integrator"):
        SimulationSpec(integrator="leapfrog")
    with pytest.raises(ValueError, match="backend"):
        SimulationSpec(backend="mpi")
    with pytest.raises(ValueError, match="scenario"):
        SimulationSpec(scenario="warp-core-breach")
    with pytest.raises(ValueError, match="halo"):
        SimulationSpec(halo="pigeon")


def test_spec_frozen_and_with():
    spec = SimulationSpec(scenario="sedov", integrator="timebin")
    with pytest.raises(Exception):
        spec.integrator = "global"
    spec2 = spec.with_(backend="distributed", ranks=4)
    assert spec2.scenario == "sedov" and spec2.ranks == 4
    assert spec.backend == "local"          # original untouched


def test_scenario_registry():
    assert {"uniform", "clustered", "sedov",
            "kelvin_helmholtz"} <= set(SCENARIOS)
    ic = make_ic("uniform", n_side=4)
    assert set(ic) >= {"pos", "vel", "mass", "u", "h", "box"}
    with pytest.raises(KeyError, match="unknown scenario"):
        make_ic("nope")

    @register_scenario("test_two_particles")
    def _two(**kw):
        return {"pos": np.zeros((2, 3), np.float32),
                "vel": np.zeros((2, 3), np.float32),
                "mass": np.ones(2, np.float32),
                "u": np.ones(2, np.float32),
                "h": np.full(2, 0.3, np.float32), "box": 1.0}

    try:
        assert "test_two_particles" in SCENARIOS
        assert len(make_ic("test_two_particles")["pos"]) == 2
    finally:
        del SCENARIOS["test_two_particles"]


def test_kelvin_helmholtz_ic_structure():
    ic = kelvin_helmholtz_ic(8, v_shear=0.5, perturb=0.05, seed=0)
    z = ic["pos"][:, 2] / ic["box"]
    vx = ic["vel"][:, 0]
    inner = (np.abs(z - 0.5) < 0.15)
    outer = (np.abs(z - 0.5) > 0.35)
    assert vx[inner].mean() > 0.4            # central slab streams +x
    assert vx[outer].mean() < -0.4           # outer gas streams -x
    assert np.abs(ic["vel"][:, 2]).max() > 0  # seeded perturbation
    assert np.abs(ic["vel"][:, 2]).max() < 0.5 * 0.5  # but subdominant
    # uniform density: one equal-mass lattice
    assert np.allclose(ic["mass"], ic["mass"][0])


def test_sodshock_ic_structure():
    ic = make_ic("sodshock", n_side=14, seed=1)
    assert ic["box"] == (2.0, 0.5, 0.5)
    n = len(ic["pos"])
    assert n == 2 * 14 ** 3 + 2 * 7 ** 3
    assert np.all(ic["pos"] >= 0) and np.all(ic["pos"] < ic["box"])
    left = np.arange(n) < 2 * 14 ** 3
    # the published states on two lattices of one particle mass
    np.testing.assert_allclose(ic["mass"], ic["mass"][0])
    np.testing.assert_allclose(ic["mass"].sum(), 0.5 * (1.0 + 0.125) / 2,
                               rtol=1e-6)
    for side, rho, press in ((left, 1.0, 1.0), (~left, 0.125, 0.1)):
        np.testing.assert_allclose(ic["u"][side], press / (rho * 2 / 3),
                                   rtol=1e-6)
    np.testing.assert_allclose(ic["h"][~left], 2 * ic["h"][left][0])
    assert np.all(ic["vel"] == 0)
    assert np.mean(ic["pos"][left, 0] < 1.0) > 0.99
    with pytest.raises(ValueError, match="even n_side"):
        sodshock_ic(7)


# ------------------------------------------------------------- the quadrants
def test_all_four_quadrants_run():
    """Every {integrator} × {backend} combination builds and advances
    through the one front-end (the acceptance criterion)."""
    base = SimulationSpec(scenario="uniform",
                          scenario_params={"n_side": 5, "seed": 0},
                          physics=SPHConfig(alpha_visc=0.8),
                          dt=0.004, dt_max=0.004, ranks=1)
    for integrator in ("global", "timebin"):
        for backend in ("local", "distributed"):
            spec = base.with_(integrator=integrator, backend=backend)
            sim = build_simulation(spec)
            assert isinstance(sim, SimulationProtocol)
            log = sim.run(0.008)
            assert sim.time == pytest.approx(0.008, rel=1e-5)
            assert len(log["t"]) >= 1
            e, p = sim.diagnostics()
            assert np.isfinite(e) and np.isfinite(p).all()


def test_legacy_constructors_warn_but_work():
    from repro.sph import Simulation, TimeBinSimulation, uniform_ic
    ic = uniform_ic(4, seed=0)
    args = (ic["pos"], ic["vel"], ic["mass"], ic["u"], ic["h"])
    with pytest.warns(DeprecationWarning):
        Simulation(*args, box=ic["box"])
    with pytest.warns(DeprecationWarning):
        TimeBinSimulation(*args, box=ic["box"])
    # the API path must not warn
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        build_simulation(SimulationSpec(
            scenario="uniform", scenario_params={"n_side": 4}))


def test_legacy_constructors_match_spec_built_states():
    """Deprecation-shim regression: the legacy constructors keep warning
    AND still produce states bitwise-equal to the spec-built engines."""
    from repro.sph import Simulation, TimeBinSimulation, uniform_ic
    ic = uniform_ic(4, seed=0)
    args = (ic["pos"], ic["vel"], ic["mass"], ic["u"], ic["h"])
    params = {"n_side": 4, "seed": 0}

    with pytest.warns(DeprecationWarning):
        legacy = Simulation(*args, box=ic["box"])
    built = build_simulation(SimulationSpec(
        scenario="uniform", scenario_params=params)).engine
    for name in ("pos", "vel", "mass", "u", "h", "mask"):
        np.testing.assert_array_equal(
            np.asarray(getattr(legacy.state.cells, name)),
            np.asarray(getattr(built.state.cells, name)), err_msg=name)
    for name in ("accel", "dudt", "rho"):
        np.testing.assert_array_equal(
            np.asarray(getattr(legacy.state, name)),
            np.asarray(getattr(built.state, name)), err_msg=name)

    with pytest.warns(DeprecationWarning):
        legacy_tb = TimeBinSimulation(*args, box=ic["box"], dt_max=0.004)
    built_tb = build_simulation(SimulationSpec(
        scenario="uniform", scenario_params=params,
        integrator="timebin", dt_max=0.004)).engine
    legacy_tb.run_cycle()
    built_tb.run_cycle()
    for name in ("pos", "vel", "u"):
        np.testing.assert_array_equal(
            np.asarray(getattr(legacy_tb.state.cells, name)),
            np.asarray(getattr(built_tb.state.cells, name)), err_msg=name)
    for name in ("accel", "dudt", "rho", "omega", "bins", "t_start"):
        np.testing.assert_array_equal(
            np.asarray(getattr(legacy_tb.state, name)),
            np.asarray(getattr(built_tb.state, name)), err_msg=name)
    assert float(legacy_tb.state.time) == float(built_tb.state.time)


# ------------------------------------------- distributed time-bin: host plan
def _toy_plan(nranks=2):
    # 4 cells in a chain, alternate ownership: every cell is a cut cell
    # except with nranks=1
    assignment = np.arange(4) % nranks
    ci = np.array([0, 1, 2, 0, 1, 2, 3])
    cj = np.array([1, 2, 3, 0, 1, 2, 3])
    return build_rank_plan(assignment, ci, cj, nranks=nranks)


def test_rank_plan_structure():
    plan = _toy_plan(2)
    assert plan.nranks == 2
    assert sorted(np.concatenate(plan.owned).tolist()) == [0, 1, 2, 3]
    # chain 0-1-2-3 with alternating ranks: cells 0..3 all sit on the cut
    assert set(plan.cut) == {0, 1, 2, 3}
    for c, (owner, orow, imps) in plan.cut.items():
        assert owner == plan.assignment[c]
        assert all(r != owner for r, _ in imps)
        assert all(row >= plan.K for _, row in imps)    # halo rows
    # single rank: no cut, trivially empty halo
    p1 = _toy_plan(1)
    assert p1.cut == {} and p1.H == 0


def test_halo_export_schedule_activity_beats_full():
    """The static accounting: with bins concentrated in few cells, the
    activity-aware export volume over a cycle is far below full-boundary."""
    plan = _toy_plan(2)
    depth = 4
    cell_bins = np.array([depth, 0, 0, 0])       # one deep cell
    sched = halo_export_schedule(cell_bins, plan, depth)
    active, full = sched["active"].sum(), sched["full"].sum()
    assert 0 < active < full
    # uniform deep bins: no advantage (every sub-step ships everything)
    sched_u = halo_export_schedule(np.full(4, depth), plan, depth)
    assert sched_u["active"].sum() == sched_u["full"].sum()


def test_rank_bin_occupancy_and_imbalance():
    assignment = np.array([0, 0, 1, 1])
    obb = np.array([[4, 0], [4, 0],          # rank 0: all slow (bin 0)
                    [0, 4], [0, 4]])         # rank 1: all fast (bin 1)
    per_rank = rank_bin_occupancy(assignment, obb)
    assert per_rank.tolist() == [[8, 0], [0, 8]]
    # rank 1 does 2x the mean time-averaged work -> imbalance 4/3
    imb = bin_occupancy_imbalance(assignment, obb)
    assert imb == pytest.approx((8.0) / ((8 * 0.5 + 8) / 2))
    balanced = bin_occupancy_imbalance(np.array([0, 1, 0, 1]), obb)
    assert balanced == pytest.approx(1.0)


def test_comm_tasks_weighted_by_activation_frequency():
    """send/recv costs and bytes scale with the resource's activation
    frequency (the activity-aware halo at the task-graph layer)."""
    def graph():
        g = TaskGraph()
        s = g.add_task("produce", resources=(0,), writes=(0,), cost=1, rank=0)
        c = g.add_task("consume", resources=(0,), cost=1, rank=1)
        g.add_dependency(c, s)
        return g

    g_full = graph()
    full = insert_comm_tasks(g_full, {0: 0}, {0: 1000.0},
                             phases={"produce": "p0", "consume": "p1"})
    g_rare = graph()
    rare = insert_comm_tasks(g_rare, {0: 0}, {0: 1000.0},
                             phases={"produce": "p0", "consume": "p1"},
                             resource_freq={0: 0.125})
    assert rare.total_bytes == pytest.approx(full.total_bytes / 8)
    send_cost = {t.kind: t.cost for t in g_rare.tasks.values()}["send"]
    send_cost_full = {t.kind: t.cost for t in g_full.tasks.values()}["send"]
    assert send_cost == pytest.approx(send_cost_full / 8)


def test_timebin_units_send_recv_activation_frequency():
    cm = CostModel(rates={})
    # cell active every sub-step: full message cost
    assert cm.timebin_units("send", [0, 0, 8], max_bin=2) == \
        pytest.approx(cm.units("send", 8))
    # cell active 1/4 of sub-steps: the whole buffer ships 1/4 as often
    assert cm.timebin_units("send", [8, 0, 0], max_bin=2) == \
        pytest.approx(cm.units("send", 8) / 4)
    # empty cell never ships
    assert cm.timebin_units("recv", [0, 0, 0], max_bin=2) == 0.0


# ------------------------------------- distributed time-bin: engine parity
def _parity_engines(nranks, n_side=5, max_depth=3):
    from repro.sph import TimeBinSimulation
    ic = sedov_ic(n_side, e0=1.0, seed=0)
    cfg = SPHConfig(alpha_visc=1.0, cfl=0.15)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        single = TimeBinSimulation(
            ic["pos"], ic["vel"], ic["mass"], ic["u"], ic["h"],
            box=ic["box"], cfg=cfg, dt_max=0.02, max_depth=max_depth)
    spec = SimulationSpec(
        scenario="sedov",
        scenario_params={"n_side": n_side, "e0": 1.0, "seed": 0},
        physics=cfg, integrator="timebin", backend="distributed",
        ranks=nranks, dt_max=0.02, max_depth=max_depth)
    dist = build_simulation(spec)
    return single, dist


def _assert_states_equal(single, dist):
    for name in ("pos", "vel", "u", "h"):
        a = np.asarray(getattr(single.state.cells, name))
        b = np.asarray(getattr(dist.engine.state.cells, name))
        np.testing.assert_array_equal(a, b, err_msg=name)
    np.testing.assert_array_equal(np.asarray(single.state.bins),
                                  np.asarray(dist.engine.state.bins))
    assert float(single.state.time) == float(dist.engine.state.time)


@pytest.mark.slow
def test_distributed_timebin_one_rank_bitwise_parity():
    """Satellite acceptance: SimulationSpec(integrator="timebin",
    backend="distributed") on one rank matches the single-host
    TimeBinSimulation trajectory bit-for-bit over ≥2 full cycles."""
    single, dist = _parity_engines(nranks=1)
    for _ in range(2):
        s1 = single.run_cycle()
        s2 = dist.step()
        assert s1["depth"] == s2["depth"]
        assert s1["substeps"] == s2["substeps"]
    _assert_states_equal(single, dist)
    assert dist.engine.halo_full_slots == 0      # one rank: no cut


@pytest.mark.slow
def test_distributed_timebin_multirank_matches_and_saves_volume():
    """Three ranks: identical physics (owned sums are complete through the
    halos) and, on a blast with real bin contrast, activity-aware halos
    ship measurably less than the full boundary."""
    single, dist = _parity_engines(nranks=3, n_side=6, max_depth=4)
    for _ in range(2):
        single.run_cycle()
        dist.step()
    _assert_states_equal(single, dist)

    # fine-grained Sedov: background cells idle through deep sub-steps
    spec = SimulationSpec(
        scenario="sedov",
        scenario_params={"n_side": 8, "e0": 1.0, "seed": 0,
                         "n_target": 16.0, "r_inject": 0.06},
        physics=SPHConfig(alpha_visc=1.0, cfl=0.15, n_target=16.0),
        integrator="timebin", backend="distributed", ranks=4, max_depth=6)
    sim = build_simulation(spec)
    stats = sim.step()
    assert stats["halo_full_slots"] > 0
    assert stats["halo_exported_slots"] < 0.7 * stats["halo_full_slots"]
