"""Cross-quadrant conformance harness: every execution path pinned to one
reference.

With four {global, timebin} × {local, distributed} quadrants, two wires
(``transport="host" | "collective"``), two residencies (``residency="host" |
"device"``) and repartitioning rank counts, "the same physics" is a claim
that needs a matrix, not a pair of spot checks. The contract asserted here:

* **time-bin family — bitwise.** Every timebin execution path (local;
  distributed × {host, collective} × {host-resident, device-resident}; 1
  and 4 ranks) reproduces the single-host :class:`TimeBinSimulation`
  trajectory bit-for-bit over ≥2 full cycles, on Sedov and
  Kelvin–Helmholtz. This is the engine-family contract every transport /
  residency lowering must preserve (exchanges are pure row copies; the
  device cycle scan re-assembles split pair work in original pair order).
* **global family — determinism + physics.** ``global × distributed``
  accumulates pair sums in per-device plan order (a *different* but fixed
  summation order from the local engine's global pair list), so bitwise
  equality with the local engine is not part of its contract; it is pinned
  by (a) run-twice bitwise determinism and (b) trajectory agreement with
  the local engine to float32 tolerances plus conservation checks.
* **transfer discipline.** The device-resident cycle scan moves zero bytes
  across the host boundary inside a segment (measured by the engine's
  :class:`TransferProbe`, not inferred), compiles at most one program per
  shape signature, and re-runs bitwise-identically. A segment that trips a
  flag replays on the host-resident ladder, still bitwise.

4-rank cases need 4 addressable devices and run in the CI job with
``XLA_FLAGS=--xla_force_host_platform_device_count=4``; on a single real
device they skip (the 1-rank matrix plus ``tests/test_transport.py``'s
subprocess parity still run everywhere).
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.sph import SimulationSpec, SPHConfig, build_simulation

requires4 = pytest.mark.skipif(
    len(jax.devices()) < 4,
    reason="needs 4 devices (XLA_FLAGS=--xla_force_host_platform_"
           "device_count=4)")

NCYCLES = 2

SCENARIOS = {
    # n_side=6 / max_depth=4 yields interior force sub-steps (a real
    # ladder), so the matrix pins the live exchange paths, not just the
    # cycle-closing boundary
    "sedov": dict(scenario="sedov",
                  scenario_params={"n_side": 6, "e0": 1.0, "seed": 0},
                  physics=SPHConfig(alpha_visc=1.0, cfl=0.15),
                  dt_max=0.02, max_depth=4),
    "kelvin_helmholtz": dict(
        scenario="kelvin_helmholtz",
        scenario_params={"n_side": 5, "v_shear": 0.5, "seed": 0},
        physics=SPHConfig(alpha_visc=1.0, cfl=0.2),
        dt_max=0.01, max_depth=3),
}

# the timebin × distributed execution paths: (transport, residency)
TIMEBIN_PATHS = [("host", "host"), ("collective", "host"),
                 ("collective", "device")]


def _timebin_spec(scenario: str, **overrides) -> SimulationSpec:
    kw = dict(SCENARIOS[scenario])
    kw.update(integrator="timebin", backend="local")
    kw.update(overrides)
    return SimulationSpec(**kw)


def _snapshot(engine) -> dict:
    out = {name: np.asarray(getattr(engine.state.cells, name))
           for name in ("pos", "vel", "u", "h", "mass", "mask")}
    for name in ("accel", "dudt", "rho", "omega", "bins", "t_start"):
        out[name] = np.asarray(getattr(engine.state, name))
    out["time"] = np.float64(engine.state.time)
    return out


def _trajectory(sim, ncycles: int = NCYCLES) -> list:
    snaps = []
    for _ in range(ncycles):
        sim.step()
        snaps.append(_snapshot(sim.engine))
    return snaps


def _assert_bitwise(got: list, want: list, label: str):
    assert len(got) == len(want)
    for cyc, (a, b) in enumerate(zip(got, want)):
        for name in b:
            np.testing.assert_array_equal(
                a[name], b[name], err_msg=f"{label}: cycle {cyc}: {name}")


_REFS: dict = {}


def _reference_run(scenario: str, ncycles: int = NCYCLES) -> tuple:
    """Single-host timebin reference (snapshots, per-cycle stats), cached.

    Longer trajectories are cached separately and reuse nothing — cheap,
    and it keeps every cached snapshot list immutable."""
    key = (scenario, ncycles)
    if key not in _REFS:
        sim = build_simulation(_timebin_spec(scenario))
        snaps, stats = [], []
        for _ in range(ncycles):
            stats.append(sim.step())
            snaps.append(_snapshot(sim.engine))
        _REFS[key] = (snaps, stats)
    return _REFS[key]


def _reference(scenario: str, ncycles: int = NCYCLES) -> list:
    return _reference_run(scenario, ncycles)[0]


# ------------------------------------------------- timebin family (bitwise)
@pytest.mark.slow
@pytest.mark.parametrize("scenario", sorted(SCENARIOS))
@pytest.mark.parametrize("transport,residency", TIMEBIN_PATHS)
def test_timebin_conformance_one_rank(scenario, transport, residency):
    spec = _timebin_spec(scenario, backend="distributed", ranks=1,
                         transport=transport, residency=residency)
    got = _trajectory(build_simulation(spec))
    _assert_bitwise(got, _reference(scenario),
                    f"{scenario}/1rank/{transport}/{residency}")


@requires4
@pytest.mark.slow
@pytest.mark.parametrize("scenario", sorted(SCENARIOS))
@pytest.mark.parametrize("transport,residency", TIMEBIN_PATHS)
def test_timebin_conformance_four_ranks(scenario, transport, residency):
    spec = _timebin_spec(scenario, backend="distributed", ranks=4,
                         transport=transport, residency=residency)
    got = _trajectory(build_simulation(spec))
    _assert_bitwise(got, _reference(scenario),
                    f"{scenario}/4rank/{transport}/{residency}")


def test_residency_requires_collective_transport():
    with pytest.raises(ValueError, match="residency"):
        SimulationSpec(residency="cloud")
    with pytest.raises(ValueError, match="collective"):
        SimulationSpec(transport="host", residency="device")
    from repro.sph.dist_timebins import DistTimeBinSimulation
    from repro.sph import uniform_ic
    ic = uniform_ic(3, seed=0)
    args = (ic["pos"], ic["vel"], ic["mass"], ic["u"], ic["h"])
    with pytest.raises(ValueError, match="collective"):
        DistTimeBinSimulation(*args, box=ic["box"], transport="host",
                              residency="device")
    with pytest.raises(ValueError, match="use_pallas"):
        DistTimeBinSimulation(*args, box=ic["box"], transport="collective",
                              residency="device",
                              cfg=SPHConfig(use_pallas=True))


def test_schedule_follows_residency():
    """The residency decides the schedule: host-resident state runs the
    host ladder, device-resident state the cycle scan. A spec that names
    the derived schedule is the same spec, and the same program, as one
    that leaves it out; the host-scheduled fused ladder is gone."""
    base = _timebin_spec("sedov", backend="distributed", ranks=1,
                         transport="collective")
    assert base.schedule == "host"
    dev = base.with_(residency="device")
    assert dev.schedule == "device"
    named = base.with_(residency="device", schedule="device")
    assert named == dev
    assert named.program_signature() == dev.program_signature()
    assert named.signature_key() == dev.signature_key()
    assert dev.with_(residency="host").schedule == "host"
    with pytest.raises(ValueError, match="fused sub-step ladder is gone"):
        base.with_(residency="device", schedule="host")
    with pytest.raises(ValueError, match="requires residency='device'"):
        base.with_(schedule="device")
    with pytest.raises(ValueError, match="schedule must be"):
        base.with_(schedule="cloud")
    with pytest.raises(ValueError, match="segment_cycles > 1"):
        base.with_(segment_cycles=2)
    from repro.sph.dist_timebins import DistTimeBinSimulation
    from repro.sph import uniform_ic
    ic = uniform_ic(3, seed=0)
    with pytest.raises(ValueError, match="fused sub-step ladder is gone"):
        DistTimeBinSimulation(ic["pos"], ic["vel"], ic["mass"], ic["u"],
                              ic["h"], box=ic["box"],
                              transport="collective", residency="device",
                              schedule="host")


# ------------------------------------------ global family (determinism + φ)
@pytest.mark.slow
@pytest.mark.parametrize("integrator,backend", [
    ("global", "local"), ("timebin", "local"),
    ("global", "distributed"), ("timebin", "distributed")])
def test_quadrant_run_twice_bitwise_deterministic(integrator, backend):
    """Same spec, two builds: bitwise-identical trajectories. The property
    the ``-p no:randomly`` CI guard protects — nothing in any engine may
    depend on interpreter state, dict order or global RNG."""
    kw = dict(SCENARIOS["sedov"])
    kw.update(integrator=integrator, backend=backend, dt=0.004)
    if backend == "distributed":
        kw.update(ranks=1)
    spec = SimulationSpec(**kw)
    a = build_simulation(spec)
    b = build_simulation(spec)
    for _ in range(2):
        a.step()
        b.step()
    ea, pa = a.diagnostics()
    eb, pb = b.diagnostics()
    assert ea == eb
    np.testing.assert_array_equal(pa, pb)
    # a.state is TimeBinState/SPHState (with .cells) or the sharded
    # ParticleCells of the global-distributed engine — compare either way
    ca = a.state.cells if hasattr(a.state, "cells") else a.state
    cb = b.state.cells if hasattr(b.state, "cells") else b.state
    for name in ("pos", "vel", "u"):
        np.testing.assert_array_equal(
            np.asarray(getattr(ca, name)),
            np.asarray(getattr(cb, name)), err_msg=name)


@pytest.mark.slow
def test_global_distributed_tracks_local_reference():
    """global × distributed pins to the local engine within float32
    accumulation-order tolerances (its pair sums fold in per-device plan
    order — same terms, different order, so bitwise equality is out of
    contract by design; see module docstring)."""
    kw = dict(SCENARIOS["sedov"])
    # rebin_every high: the distributed engine never re-bins, so the local
    # reference must not either or the per-cell layouts drift apart
    kw.update(integrator="global", dt=0.004, rebin_every=100)
    local = build_simulation(SimulationSpec(**kw))
    dist = build_simulation(SimulationSpec(**kw, backend="distributed",
                                           ranks=1))
    for _ in range(3):
        local.step()
        dist.step()
    e_l, p_l = local.diagnostics()
    e_d, p_d = dist.diagnostics()
    assert e_d == pytest.approx(e_l, rel=1e-5)
    np.testing.assert_allclose(p_d, p_l, atol=1e-5)
    g = dist.engine.gather_cells()
    for name in ("pos", "u"):
        np.testing.assert_allclose(
            np.asarray(getattr(g, name)),
            np.asarray(getattr(local.engine.state.cells, name)),
            rtol=2e-5, atol=2e-6, err_msg=name)


# ------------------------------------------------- transfer-count regression
def _assert_resident_discipline(sim, cycles: int):
    """The cycle scan's contract: inside a segment NOTHING moves between
    host and device — only the boundary scatter, table upload, stats pull
    and gather — each program compiles once, and ``cycles`` more cycles
    reuse every program."""
    eng = sim.engine
    stats = eng.transfers.stats()
    assert stats["intra_state_bytes"] == 0, stats
    assert dict(eng.transfers.intra_bytes) == {}
    # boundary traffic exists: the scatter/gather really went through the
    # probe (guards against the ledger silently going stale)
    for f in ("pos", "vel", "u", "bins"):
        assert stats["boundary_bytes"].get(f, 0) > 0, f
    assert stats["boundary_events"]["segment_tables"] > 0
    assert stats["boundary_events"]["segment_stats"] == eng.segments
    # ≤ 1 compile per (program, shape signature)
    for name, c in eng.probe.counts().items():
        if name.startswith("program:"):
            assert c == 1, (name, c)
    assert any(k[0] == "cycle_scan" for k in eng.program_keys)
    assert any(k[0] == "segment_plan" for k in eng.program_keys) \
        == (eng.segment_cycles > 1)
    builds = eng._transport.programs.builds
    compiles = eng.probe.total_compiles()
    for _ in range(cycles):                     # stable bins: full reuse
        sim.step()
    assert eng._transport.programs.builds == builds
    assert eng.probe.total_compiles() == compiles
    assert eng.transfers.stats()["intra_state_bytes"] == 0


def _run_resident(ranks: int):
    spec = _timebin_spec("sedov", backend="distributed", ranks=ranks,
                         transport="collective", residency="device")
    sim = build_simulation(spec)
    interior = 0
    for _ in range(2):
        interior += sim.step()["force_substeps"] - 1
    assert interior > 0         # the scenario must exercise a real ladder
    return sim


@pytest.mark.slow
def test_fused_resident_transfer_discipline_one_rank():
    sim = _run_resident(ranks=1)
    assert sim.engine.segments == 2 and sim.engine.segment_aborts == 0
    _assert_resident_discipline(sim, cycles=1)


def _hot_sedov_spec(ranks: int) -> SimulationSpec:
    """A Sedov configuration whose blast provably deepens bins mid-cycle
    (e0=30 with a loose CFL: the central particles' demand tightens
    inside cycle 1). max_depth=3 keeps the ladder — and the CPU's fully
    unrolled cycle scan — short."""
    return SimulationSpec(
        scenario="sedov", scenario_params={"n_side": 6, "e0": 30.0,
                                           "seed": 0},
        physics=SPHConfig(alpha_visc=1.0, cfl=0.3),
        dt_max=0.01, max_depth=3, integrator="timebin",
        backend="distributed", ranks=ranks,
        transport="collective", residency="device")


# ------------------------------------------------ device-scheduled segments
NCYC_SEG = 4


def _device_sched_spec(scenario: str, K: int, ranks: int = 4,
                       **over) -> SimulationSpec:
    return _timebin_spec(scenario, backend="distributed", ranks=ranks,
                         transport="collective", residency="device",
                         schedule="device", segment_cycles=K, **over)


@requires4
@pytest.mark.slow
@pytest.mark.parametrize("scenario", sorted(SCENARIOS))
@pytest.mark.parametrize("K", [1, 4])
def test_device_schedule_conformance(scenario, K):
    """Device-scheduled K-cycle segments are bitwise the host-scheduled
    ladder: state at every segment boundary, per-cycle stats everywhere.
    For K>1 the engine state is *defined* only at segment boundaries, so
    mid-segment cycles compare stats alone."""
    refs, ref_stats = _reference_run(scenario, NCYC_SEG)
    sim = build_simulation(_device_sched_spec(scenario, K))
    eng = sim.engine
    for c in range(NCYC_SEG):
        s = sim.step()
        assert s["schedule"] == "device" and s["segment_cycles"] == K
        r = ref_stats[c]
        for k in ("updates", "substeps", "depth", "force_substeps"):
            assert s.get(k) == r.get(k), (c, k, s.get(k), r.get(k))
        np.testing.assert_array_equal(s["bin_hist"], r["bin_hist"],
                                      err_msg=f"K={K} cycle {c}: bin_hist")
        assert s["t"] == float(refs[c]["time"])
        if (c + 1) % K == 0:
            snap = _snapshot(eng)
            for name in refs[c]:
                np.testing.assert_array_equal(
                    snap[name], refs[c][name],
                    err_msg=f"{scenario} K={K} cycle {c}: {name}")
    if scenario == "kelvin_helmholtz" and K == 4:
        # the shear flow crosses a cell boundary inside the segment —
        # the device plan cannot rebin, so the crossing sentinel MUST
        # abort and the host replay the cycles: still bitwise and still
        # per-cycle stats parity (both asserted above)
        assert eng.segment_aborts >= 1
    else:
        assert eng.segments == NCYC_SEG // K
        assert eng.segment_aborts == 0


@requires4
@pytest.mark.slow
@pytest.mark.parametrize("K", [1, 4])
def test_device_schedule_zero_intra_bytes_and_compile_discipline(K):
    """Four ranks, one- and four-cycle segments: a segment moves NOTHING
    between host and device except the boundary table upload and the
    boundary stats pull — no per-cycle flags, no bins mirrors, no schedule
    tables — over a real cut, and each (signature, bucket, K) compiles its
    programs exactly once, with full reuse on the next segments."""
    sim = build_simulation(_device_sched_spec("sedov", K))
    for _ in range(NCYC_SEG):
        sim.step()
    eng = sim.engine
    assert eng.segments == NCYC_SEG // K and eng.segment_aborts == 0
    assert eng.halo_exported_slots > 0          # a real cut was exchanged
    _assert_resident_discipline(sim, cycles=NCYC_SEG)


@requires4
@pytest.mark.slow
def test_device_schedule_mid_segment_deepening():
    """Bins that deepen in the middle of a compiled segment are handled
    entirely on device — no sentinel trip, no host fallback — and the
    boundary state stays bitwise. A one-cycle run of the same
    configuration proves the deepening event is really there: its device
    metrics row counts the deepened particles."""
    from repro.observability import device_metrics as dm
    one = build_simulation(_hot_sedov_spec(ranks=4).with_(
        observe={"trace": False, "device_metrics": True}))
    one.step()
    counts, _ = one.engine.device_metrics_last
    assert counts[:, dm.COUNT_INDEX["deepen_events"]].sum() > 0
    hot = dict(scenario="sedov",
               scenario_params={"n_side": 6, "e0": 30.0, "seed": 0},
               physics=SPHConfig(alpha_visc=1.0, cfl=0.3),
               dt_max=0.01, max_depth=3, integrator="timebin")
    ref = build_simulation(SimulationSpec(**hot, backend="local"))
    refs = _trajectory(ref, NCYC_SEG)
    sim = build_simulation(SimulationSpec(
        **hot, backend="distributed", ranks=4, transport="collective",
        residency="device", schedule="device", segment_cycles=4))
    for _ in range(NCYC_SEG):
        sim.step()
    eng = sim.engine
    snap = _snapshot(eng)
    for name in refs[-1]:
        np.testing.assert_array_equal(snap[name], refs[-1][name],
                                      err_msg=f"deepening: {name}")
    assert eng.segment_aborts == 0              # absorbed inside the scan
    assert dict(eng.transfers.intra_bytes) == {}


@pytest.mark.slow
@pytest.mark.parametrize("ranks", [1, pytest.param(4, marks=requires4)])
def test_device_schedule_nan_sentinel_trip_and_resume(ranks, tmp_path):
    """A NaN minted on device trips the in-program sentinel; the one-cycle
    segment aborts to the host-resident ladder and replays bitwise — NaNs
    propagate identically to the reference, and the observer's health
    record shows the trip."""
    ref = build_simulation(_timebin_spec("sedov"))
    ref.step()
    _poison_vel(ref.engine)
    refs = []
    with np.errstate(invalid="ignore"):
        for _ in range(2):
            ref.step()
            refs.append(_snapshot(ref.engine))
    sim = build_simulation(_device_sched_spec(
        "sedov", 1, ranks=ranks,
        observe={"device_metrics": True, "flight_dir": str(tmp_path)}))
    assert "replayed" not in sim.step()
    rec0 = sim.observer.records[-1]
    assert rec0["health"] is not None and rec0["health"]["tripped"] is False
    _poison_vel(sim.engine)
    got = []
    with np.errstate(invalid="ignore"):
        for _ in range(2):
            assert sim.step()["replayed"] is True
            got.append(_snapshot(sim.engine))
    eng = sim.engine
    assert eng.segment_aborts == 2
    rec = sim.observer.records[-1]
    assert rec["health"]["tripped"] is True
    assert rec["health"]["flags"].get("flag_nan", 0) > 0
    for c, (a, b) in enumerate(zip(got, refs)):
        for name in b:
            np.testing.assert_array_equal(
                a[name], b[name], err_msg=f"nan-resume cycle {c}: {name}")


@pytest.mark.slow
def test_device_segment_abort_replays_on_host_ladder():
    """A four-cycle segment whose particles cross cells (Sedov on a 5³
    grid: the blast moves the central particles out of their cells by
    cycle 4) trips the plan program's crossing flag — no health sentinel
    is involved — and its cycles replay on the host-resident ladder, each
    marked ``replayed`` and each bitwise the single-rank reference."""
    hot = dict(scenario="sedov",
               scenario_params={"n_side": 10, "e0": 1.0, "seed": 0,
                                "n_target": 32.0},
               physics=SPHConfig(alpha_visc=1.0, cfl=0.15, n_target=32.0),
               max_depth=2, integrator="timebin")
    refs = _trajectory(build_simulation(SimulationSpec(**hot)), NCYC_SEG)
    sim = build_simulation(SimulationSpec(
        **hot, backend="distributed", ranks=1, transport="collective",
        residency="device", segment_cycles=NCYC_SEG,
        observe={"trace": False, "device_metrics": True}))
    eng = sim.engine
    got = []
    epilogue = eng._cycle_epilogue

    def snap_after(ctx, body):      # the state at each replayed cycle's end
        stats = epilogue(ctx, body)
        got.append(_snapshot(eng))
        return stats

    eng._cycle_epilogue = snap_after
    stats = [sim.step() for _ in range(NCYC_SEG)]
    assert eng.segments == 1 and eng.segment_aborts == 1
    assert not any(r["health"]["tripped"] for r in sim.observer.records)
    assert all(s["replayed"] is True and s["segment_cycles"] == NCYC_SEG
               and "pair_slots" not in s for s in stats)
    _assert_bitwise(got, refs, "crossing replay")


def _poison_vel(eng) -> None:
    """NaN one real particle's velocity component, in place."""
    cells = eng.state.cells
    vel = np.asarray(cells.vel).copy()
    c, p = np.argwhere(np.asarray(cells.mask) > 0)[0]
    vel[c, p, 0] = np.nan
    eng.state = eng.state._replace(
        cells=cells._replace(vel=jnp.asarray(vel)))


# --------------------------------------------------- device-metrics carry
def _quadrant_state(sim) -> dict:
    """Physics-visible state for any quadrant (plain-vs-instrumented)."""
    eng = sim.engine
    if hasattr(eng, "dcells"):                      # global × distributed
        g = eng.gather_cells()
        return {n: np.asarray(getattr(g, n))
                for n in ("pos", "vel", "u", "h", "mass", "mask")}
    if hasattr(eng.state, "bins"):                  # timebin family
        return _snapshot(eng)
    out = {n: np.asarray(getattr(eng.state.cells, n))  # global × local
           for n in ("pos", "vel", "u", "h", "mass", "mask")}
    out["rho"] = np.asarray(eng.state.rho)
    return out


@pytest.mark.slow
@pytest.mark.parametrize("integrator,backend", [
    ("global", "local"), ("timebin", "local"),
    ("global", "distributed"), ("timebin", "distributed")])
def test_device_metrics_carry_bitwise_all_quadrants(integrator, backend):
    """Enabling the telemetry carry changes no number in any quadrant, and
    every quadrant reports a populated per-rank work row."""
    kw = dict(SCENARIOS["sedov"])
    kw.update(integrator=integrator, backend=backend, dt=0.004)
    if backend == "distributed":
        kw.update(ranks=1, transport="collective")
        if integrator == "timebin":
            kw.update(residency="device")
    plain = build_simulation(SimulationSpec(**kw))
    inst = build_simulation(SimulationSpec(**kw, observe=True))
    snaps_p, snaps_i = [], []
    for _ in range(NCYCLES):
        plain.step()
        inst.step()
        snaps_p.append(_quadrant_state(plain))
        snaps_i.append(_quadrant_state(inst))
    _assert_bitwise(snaps_i, snaps_p, f"dmetrics/{integrator}/{backend}")
    eng = inst.engine
    assert eng.device_metrics_enabled
    assert plain.engine.device_metrics_last is None
    counts, values = eng.device_metrics_last
    assert counts.shape[0] == 1 and values.shape[0] == 1
    assert eng.device_metrics_pulls == NCYCLES
    rec = inst.observer.records[-1]
    work = rec["device_metrics"]["per_rank_work"]
    assert len(work) == 1 and work[0] > 0
    assert rec["health"]["tripped"] is False


@pytest.mark.slow
def test_device_metrics_carry_mints_no_extra_programs():
    """The cycle scan with the telemetry output IS the program: turning
    the carry off (device_metrics=False) compiles nothing different and
    produces the bitwise-same trajectory — the row is always computed and
    always rides in the segment's one stats pull; the flag only gates the
    once-per-cycle adoption of it."""
    base = _timebin_spec("sedov", backend="distributed", ranks=1,
                         transport="collective", residency="device")
    off = build_simulation(base.with_(
        observe={"device_metrics": False}))
    on = build_simulation(base.with_(observe=True))
    got_off = _trajectory(off)
    got_on = _trajectory(on)
    _assert_bitwise(got_on, got_off, "dmetrics-on-vs-off")
    assert on.engine.probe.total_compiles() \
        == off.engine.probe.total_compiles()
    assert on.engine.probe.counts() == off.engine.probe.counts()
    # one ledgered stats pull per cycle on both engines, and the same
    # bytes: only the instrumented engine adopts the row it carries
    assert on.engine.transfers.stats() == off.engine.transfers.stats()
    assert on.engine.transfers.stats()["boundary_events"]["segment_stats"] \
        == NCYCLES
    assert on.engine.device_metrics_pulls == NCYCLES
    assert off.engine.device_metrics_pulls == 0
    assert off.engine.device_metrics_last is None


@requires4
@pytest.mark.slow
def test_device_metrics_four_rank_fused_rows():
    """4-rank cycle scan: per-rank per-phase work comes from inside the
    program, covers owned rows only (ranks sum to the global particle
    count, not 4× it), and still costs one ledgered pull per cycle."""
    spec = _timebin_spec("sedov", backend="distributed", ranks=4,
                         transport="collective", residency="device",
                         observe=True)
    sim = build_simulation(spec)
    got = _trajectory(sim)
    _assert_bitwise(got, _reference("sedov"), "dmetrics/4rank/fused")
    eng = sim.engine
    counts, values = eng.device_metrics_last
    assert counts.shape[0] == 4 and values.shape[0] == 4
    rec = sim.observer.records[-1]
    dmx = rec["device_metrics"]
    assert len(dmx["per_rank_work"]) == 4
    assert all(w > 0 for w in dmx["per_rank_work"])
    assert rec["device_imbalance"] >= 1.0
    # owned-rows-only: summed drift-active particles over ranks equals the
    # alive count exactly (halo mirrors are not double-counted)
    from repro.observability import COUNT_COLUMNS
    drift = counts[:, COUNT_COLUMNS.index("drift_active")]
    subs = counts[:, COUNT_COLUMNS.index("substeps")]
    nreal = int((np.asarray(_reference("sedov")[-1]["mask"]) > 0).sum())
    assert (subs == subs[0]).all() and subs[0] == 2 * NCYCLES \
        or (subs > 0).all()          # every rank ran every sub-step
    assert (drift > 0).all()
    assert drift.sum() == subs[0] * nreal
    assert eng.transfers.stats()["boundary_events"]["segment_stats"] \
        == NCYCLES
    # the scan's one aggregate sample feeds per-phase work to the model
    assert {"density", "force"} <= set(rec["cost_ratios"])


@requires4
@pytest.mark.slow
def test_per_cell_attribution_sums_to_phase_units_four_rank():
    """4-rank cycle scan: the per-cell work vectors (schema v3) are exact —
    per-rank owned-row sums equal the in-program value columns for
    density/force/exchange and the drift-active count, with halo rows
    folded onto owners (no double-counting)."""
    from repro.observability import CELL_COLUMNS
    from repro.observability import device_metrics as dm
    spec = _timebin_spec("sedov", backend="distributed", ranks=4,
                         transport="collective", residency="device",
                         observe=True)
    sim = build_simulation(spec)
    _trajectory(sim)
    eng = sim.engine
    cw = eng.device_cell_work_last
    assert cw is not None and list(cw["columns"]) == list(CELL_COLUMNS)
    cells = np.asarray(cw["cells"], np.float64)
    per_rank = np.asarray(cw["per_rank"], np.float64)
    assert per_rank.shape[0] == 4
    counts, values = (np.asarray(a) for a in eng.device_metrics_last)
    cix = {k: i for i, k in enumerate(CELL_COLUMNS)}
    # per-rank exactness, kind by kind: the scatter targets only owned
    # rows, so each rank's fold reproduces its own value column
    for kind in ("density", "force", "exchange"):
        want = values[:, dm.VALUE_INDEX[f"{kind}_units"]]
        got = per_rank[:, cix[kind]]
        np.testing.assert_allclose(got, want, rtol=1e-6, err_msg=kind)
    np.testing.assert_allclose(
        per_rank[:, cix["drift"]],
        counts[:, dm.COUNT_INDEX["drift_active"]], rtol=1e-6)
    # folding halo rows onto owner cells conserves every column globally
    np.testing.assert_allclose(cells.sum(axis=0), per_rank.sum(axis=0),
                               rtol=1e-6)
    assert (cells >= 0).all()
    # the v3 record carries the compact block and the advisor ran
    rec = sim.observer.records[-1]
    assert rec["cell_work"] is not None
    assert rec["cell_work"]["ncells"] == cells.shape[0]
    assert rec["advisor"] is not None
    assert rec["advisor"]["advised_imbalance"] \
        <= rec["advisor"]["current_imbalance"] + 1e-9
