"""SPH physics: kernels, oracle agreement, conservation laws."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.sph import (SPHConfig, Simulation, uniform_ic, clustered_ic,
                       get_kernel)
from repro.sph.smoothing import dw_dh, w_cubic, w_wendland_c2
from repro.sph.cellgrid import bin_particles, build_pair_list, choose_grid
from repro.sph.engine import compute_accelerations, init_state, step
from repro.sph.ref_nsquared import nsq_density, nsq_forces


@pytest.mark.parametrize("name", ["cubic", "wendland_c2"])
def test_kernel_normalisation(name):
    """∫ W(r,h) 4πr² dr = 1 (3-D normalisation) by quadrature."""
    w_fn, _ = get_kernel(name)
    h = 0.7
    r = np.linspace(1e-6, h, 20001)
    w = np.asarray(w_fn(jnp.asarray(r), h))
    integral = np.trapezoid(w * 4 * np.pi * r ** 2, r)
    assert abs(integral - 1.0) < 1e-3


@pytest.mark.parametrize("name", ["cubic", "wendland_c2"])
def test_kernel_gradient_matches_autodiff(name):
    w_fn, dwdr_fn = get_kernel(name)
    rs = jnp.linspace(0.05, 0.95, 19)
    h = 1.0
    auto = jax.vmap(jax.grad(lambda r: w_fn(r, h)))(rs)
    manual = dwdr_fn(rs, h)
    np.testing.assert_allclose(np.asarray(auto), np.asarray(manual),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("name", ["cubic"])
def test_dwdh_matches_autodiff(name):
    w_fn, _ = get_kernel(name)
    rs = jnp.linspace(0.05, 0.95, 10)
    auto = jax.vmap(jax.grad(lambda h, r: w_fn(r, h)),
                    in_axes=(None, 0))(1.0, rs)
    manual = dw_dh(rs, 1.0, name)
    np.testing.assert_allclose(np.asarray(auto), np.asarray(manual),
                               rtol=1e-4, atol=1e-5)


def _setup(n_side=8, seed=0, vel_scale=0.1):
    ic = uniform_ic(n_side, seed=seed)
    rng = np.random.default_rng(seed + 1)
    ic["vel"] = (ic["vel"]
                 + vel_scale * rng.standard_normal(ic["vel"].shape)
                 ).astype(np.float32)
    return ic


def test_cell_engine_matches_nsquared_oracle():
    ic = _setup()
    pos, vel, mass, u, h, box = (ic[k] for k in
                                 ("pos", "vel", "mass", "u", "h", "box"))
    rho_ref, drho_ref, nngb_ref = nsq_density(pos, mass, h, box)
    omega_ref = 1.0 + (h / (3 * rho_ref)) * drho_ref
    dv_ref, du_ref = nsq_forces(pos, vel, mass, u, h, rho_ref, omega_ref,
                                box, alpha_visc=0.8)

    spec = choose_grid(box, float(h.max()), len(pos))
    cells, perm = bin_particles(spec, pos, vel, mass, u, h)
    pairs = build_pair_list(spec)
    dv, du, rho, nngb = compute_accelerations(
        cells, pairs, SPHConfig(alpha_visc=0.8))

    valid = perm >= 0
    idx = perm[valid]

    def flat(a):
        out = np.zeros((len(pos),) + a.shape[2:], np.float32)
        out[idx] = np.asarray(a)[valid]
        return out

    np.testing.assert_allclose(flat(rho), np.asarray(rho_ref), rtol=2e-4)
    np.testing.assert_allclose(flat(nngb), np.asarray(nngb_ref), atol=0)
    np.testing.assert_allclose(flat(dv), np.asarray(dv_ref),
                               rtol=2e-3, atol=2e-3 * float(
                                   jnp.abs(dv_ref).max()))
    np.testing.assert_allclose(flat(du), np.asarray(du_ref),
                               rtol=2e-3, atol=2e-3 * float(
                                   jnp.abs(du_ref).max()))


def test_momentum_conserved():
    ic = _setup(vel_scale=0.2)
    sim = Simulation(ic["pos"], ic["vel"], ic["mass"], ic["u"], ic["h"],
                     box=ic["box"], cfg=SPHConfig(alpha_visc=0.8),
                     rebin_every=3)
    _, p0 = sim.diagnostics()
    sim.run(8, dt=0.004)
    _, p1 = sim.diagnostics()
    assert np.abs(p1 - p0).max() < 1e-6


def test_energy_drift_small_and_converging():
    drifts = []
    for dt, nsteps in ((0.02, 5), (0.01, 10)):
        ic = _setup(vel_scale=0.2)
        sim = Simulation(ic["pos"], ic["vel"], ic["mass"], ic["u"],
                         ic["h"], box=ic["box"],
                         cfg=SPHConfig(alpha_visc=0.0), rebin_every=100)
        e0, _ = sim.diagnostics()
        sim.run(nsteps, dt=dt)
        e1, _ = sim.diagnostics()
        drifts.append(abs(e1 - e0) / abs(e0))
    assert drifts[0] < 0.01             # <1% over the run
    assert drifts[1] < drifts[0]        # converges with dt


def test_viscosity_dissipates_kinetic_into_internal():
    ic = _setup(vel_scale=0.5)
    sim = Simulation(ic["pos"], ic["vel"], ic["mass"], ic["u"], ic["h"],
                     box=ic["box"], cfg=SPHConfig(alpha_visc=1.0),
                     rebin_every=100)
    c = sim.state.cells
    m = np.asarray(c.mass * c.mask)
    ke0 = 0.5 * np.sum(m * np.sum(np.asarray(c.vel) ** 2, -1))
    ie0 = np.sum(m * np.asarray(c.u))
    sim.run(10, dt=0.005)
    c = sim.state.cells
    m = np.asarray(c.mass * c.mask)
    ke1 = 0.5 * np.sum(m * np.sum(np.asarray(c.vel) ** 2, -1))
    ie1 = np.sum(m * np.asarray(c.u))
    assert ie1 > ie0                    # heating
    assert ke1 < ke0                    # damping


def test_clustered_ic_has_dynamic_range():
    ic = clustered_ic(3000, seed=1)
    ratio = ic["h"].max() / ic["h"].min()
    assert ratio > 4.0                  # orders-of-magnitude density contrast


def test_sedov_ic_energy_and_dt_spread():
    """The blast IC injects exactly e0 and opens a ≥3-decade CFL dt spread
    — the dynamic range the time-bin hierarchy exists for."""
    from repro.sph import sedov_ic
    from repro.sph.physics import cfl_timestep_block
    import jax.numpy as jnp

    e0 = 1.0
    ic = sedov_ic(8, e0=e0, u_background=1e-6, seed=0)
    base = uniform_ic(8, temperature=1e-6, jitter=0.02, seed=0)
    injected = float(np.sum(ic["mass"] * (ic["u"] - base["u"])))
    assert injected == pytest.approx(e0, rel=1e-4)
    # per-particle CFL spread ≥ 3 decades (hot centre vs cold background)
    dt = np.asarray(cfl_timestep_block(
        jnp.asarray(ic["h"]), jnp.asarray(ic["u"]),
        jnp.asarray(ic["vel"]), jnp.ones(len(ic["u"]))))
    assert dt.max() / dt.min() > 1e3


def test_cfl_timestep_block_masks_and_scales():
    from repro.sph.physics import cfl_timestep_block, sound_speed
    import jax.numpy as jnp

    h = jnp.asarray([0.1, 0.2, 0.1])
    u = jnp.asarray([1.0, 1.0, 4.0])
    vel = jnp.zeros((3, 3))
    mask = jnp.asarray([1.0, 1.0, 0.0])
    dt = np.asarray(cfl_timestep_block(h, u, vel, mask, cfl=0.25))
    cs = np.asarray(sound_speed(jnp.ones(3), u))
    np.testing.assert_allclose(dt[0], 0.25 * 0.1 / cs[0], rtol=1e-6)
    np.testing.assert_allclose(dt[1], 2 * dt[0], rtol=1e-6)   # ∝ h
    assert np.isinf(dt[2])                                    # padded slot


def test_sodshock_build_matches_nsquared_oracle():
    """The shock tube's two densities in its 2 × 0.5 × 0.5 box, built by
    ``build_simulation``: the cell engine's initial density and forces
    against the O(N²) oracle's per-axis minimum images."""
    from repro.sph import SimulationSpec, build_simulation, sodshock_ic
    params = {"n_side": 14, "seed": 4}
    sim = build_simulation(SimulationSpec(
        scenario="sodshock", scenario_params=params,
        physics=SPHConfig(alpha_visc=0.8)))
    ic = sodshock_ic(**params)
    pos, vel, mass, u, h = (jnp.asarray(ic[k])
                            for k in ("pos", "vel", "mass", "u", "h"))

    @jax.jit
    def oracle(pos, vel, mass, u, h):
        rho, drho, nngb = nsq_density(pos, mass, h, ic["box"])
        omega = 1.0 + (h / (3 * rho)) * drho
        dv, du = nsq_forces(pos, vel, mass, u, h, rho, omega, ic["box"],
                            alpha_visc=0.8)
        return rho, nngb, dv, du

    rho_ref, nngb_ref, dv_ref, du_ref = (np.asarray(a) for a in
                                         oracle(pos, vel, mass, u, h))
    eng = sim.engine
    perm = eng.perm
    valid = perm >= 0

    def flat(a):
        out = np.zeros((len(ic["pos"]),) + a.shape[2:], np.float32)
        out[perm[valid]] = np.asarray(a)[valid]
        return out

    st = eng.state
    assert eng.spec.dims == (12, 3, 3)
    np.testing.assert_allclose(flat(st.rho), rho_ref, rtol=2e-4)
    # away from both interfaces (x = 1 and the periodic x = 0 = 2) each
    # half holds its own state
    x = ic["pos"][:, 0]
    for centre, rho0 in ((0.5, 1.0), (1.5, 0.125)):
        inside = np.abs(x - centre) < 0.2
        np.testing.assert_allclose(rho_ref[inside].mean(), rho0, rtol=0.05)
    assert nngb_ref.min() > 30
    np.testing.assert_allclose(flat(st.accel), dv_ref, rtol=2e-3,
                               atol=2e-3 * np.abs(dv_ref).max())
    np.testing.assert_allclose(flat(st.dudt), du_ref, rtol=2e-3,
                               atol=2e-3 * np.abs(du_ref).max())
