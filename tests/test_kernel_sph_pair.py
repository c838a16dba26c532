"""Pallas sph_pair kernels vs pure-jnp oracle: shape/dtype sweeps."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.kernels.sph_pair.kernel import (density_pair_pallas,
                                           force_pair_pallas)
from repro.kernels.sph_pair.ref import density_pair_ref, force_pair_ref
from repro.sph import SPHConfig, uniform_ic
from repro.sph.cellgrid import (PairList, bin_particles, build_pair_list,
                                choose_grid)
from repro.sph.engine import _density_pass, _force_pass
from repro.sph.physics import ghost_update


def make_pair_inputs(P, C, seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    def arr(*s):
        return jnp.asarray(rng.random(s).astype(np.float32), dtype=dtype)
    pos_i = arr(P, C, 3)
    pos_j = arr(P, C, 3) + 0.1
    h = 0.3 + 0.2 * rng.random((P, C)).astype(np.float32)
    h_i = jnp.asarray(h, dtype)
    h_j = jnp.asarray(np.roll(h, 1, 0), dtype)
    m = jnp.asarray((rng.random((P, C)) + 0.5).astype(np.float32), dtype)
    mask_i = jnp.asarray((rng.random((P, C)) > 0.2).astype(np.float32), dtype)
    mask_j = jnp.asarray((rng.random((P, C)) > 0.2).astype(np.float32), dtype)
    return pos_i, h_i, m, mask_i, pos_j, h_j, m, mask_j


@pytest.mark.parametrize("P,C", [(1, 8), (3, 16), (7, 24), (2, 64)])
@pytest.mark.parametrize("kernel", ["cubic", "wendland_c2"])
def test_density_kernel_matches_ref(P, C, kernel):
    args = make_pair_inputs(P, C, seed=P * 131 + C)
    got = density_pair_pallas(*args, kernel=kernel, interpret=True)
    want = density_pair_ref(*args, kernel=kernel)
    names = ["rho_i", "drho_i", "nngb_i", "rho_j", "drho_j", "nngb_j"]
    for n, g, w in zip(names, got, want):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), rtol=2e-5,
            atol=2e-5 * max(float(jnp.abs(w).max()), 1.0), err_msg=n)


def _force_inputs(P, C, seed):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.random(s).astype(np.float32))
    pos_i, pos_j = f(P, C, 3), f(P, C, 3) + 0.05
    vel_i, vel_j = f(P, C, 3) - 0.5, f(P, C, 3) - 0.5
    h_i = 0.3 + 0.2 * f(P, C)
    h_j = 0.3 + 0.2 * f(P, C)
    rho_i, rho_j = 1.0 + f(P, C), 1.0 + f(P, C)
    P_i, P_j = 0.5 + f(P, C), 0.5 + f(P, C)
    om_i, om_j = 0.9 + 0.2 * f(P, C), 0.9 + 0.2 * f(P, C)
    cs_i, cs_j = 1.0 + f(P, C), 1.0 + f(P, C)
    m_i, m_j = 0.5 + f(P, C), 0.5 + f(P, C)
    mask_i = (f(P, C) > 0.2).astype(jnp.float32)
    mask_j = (f(P, C) > 0.2).astype(jnp.float32)
    return (pos_i, vel_i, h_i, P_i, rho_i, om_i, cs_i, m_i, mask_i,
            pos_j, vel_j, h_j, P_j, rho_j, om_j, cs_j, m_j, mask_j)


@pytest.mark.parametrize("P,C", [(2, 8), (4, 16), (3, 32)])
@pytest.mark.parametrize("alpha", [0.0, 0.8])
def test_force_kernel_matches_ref(P, C, alpha):
    args = _force_inputs(P, C, seed=P * 7 + C)
    got = force_pair_pallas(*args, kernel="cubic", alpha_visc=alpha,
                            interpret=True)
    want = force_pair_ref(*args, kernel="cubic", alpha_visc=alpha)
    mask_i = np.asarray(args[8]) > 0
    mask_j = np.asarray(args[17]) > 0
    names = ["dv_i", "du_i", "dv_j", "du_j"]
    masks = [mask_i, mask_i, mask_j, mask_j]
    for n, g, w, mk in zip(names, got, want, masks):
        g, w = np.asarray(g), np.asarray(w)
        if g.ndim == 3:
            mk = mk[..., None]
        scale = max(np.abs(w[np.broadcast_to(mk, w.shape)]).max(), 1.0)
        np.testing.assert_allclose(
            np.where(mk, g, 0), np.where(mk, w, 0),
            rtol=5e-5, atol=5e-5 * scale, err_msg=n)


def test_kernel_symmetric_pair_momentum():
    """Σ m_i dv_i + Σ m_j dv_j = 0 for a symmetric pair (paper: exploiting
    the pairwise symmetry keeps Newton's third law exact).

    The sums are accumulated in float64 so the assertion measures the
    *kernel outputs'* antisymmetry (whose floor is the f32 rounding of each
    dv entry), not the test reduction's own f32 summation noise.
    """
    args = _force_inputs(2, 16, seed=9)
    dv_i, du_i, dv_j, du_j = force_pair_pallas(*args, kernel="cubic",
                                               alpha_visc=0.8,
                                               interpret=True)
    m_i, mask_i = args[7], args[8]
    m_j, mask_j = args[16], args[17]
    w_i = np.asarray(m_i * mask_i, dtype=np.float64)
    w_j = np.asarray(m_j * mask_j, dtype=np.float64)
    p_i = (w_i[..., None] * np.asarray(dv_i, dtype=np.float64)).sum((0, 1))
    p_j = (w_j[..., None] * np.asarray(dv_j, dtype=np.float64)).sum((0, 1))
    np.testing.assert_allclose(p_i + p_j, 0.0, atol=1e-4)


def test_pallas_matches_vmap_on_padded_masked_pair_list():
    """The time-bin engine's level-restricted pair lists are padded to
    power-of-two lengths with ``pair_mask`` zeroing the padding; the Pallas
    wave execution must agree with the vmapped reference under that
    masking (over real particle slots — the kernel additionally zeroes
    padded receiver slots that the engine masks afterwards)."""
    ic = uniform_ic(6, seed=0)
    rng = np.random.default_rng(3)
    ic["vel"] = (0.1 * rng.standard_normal(ic["vel"].shape)).astype(
        np.float32)
    spec = choose_grid(ic["box"], float(ic["h"].max()), len(ic["pos"]))
    cells, _ = bin_particles(spec, ic["pos"], ic["vel"], ic["mass"],
                             ic["u"], ic["h"])
    pairs = build_pair_list(spec)

    # level-restricted subset: pairs touching the first half of the cells,
    # padded to the next power of two (exactly _pair_subset's layout)
    ci = np.asarray(pairs.ci)
    cj = np.asarray(pairs.cj)
    active = np.zeros(spec.ncells, bool)
    active[: spec.ncells // 2] = True
    idx = np.nonzero(active[ci] | active[cj])[0]
    npad = 1
    while npad < len(idx):
        npad *= 2
    idxp = np.concatenate([idx, np.zeros(npad - len(idx), idx.dtype)])
    pmask = np.zeros(npad, np.float32)
    pmask[: len(idx)] = 1.0
    sub = PairList(ci=jnp.asarray(ci[idxp]), cj=jnp.asarray(cj[idxp]),
                   shift=jnp.asarray(np.asarray(pairs.shift)[idxp]))
    pm = jnp.asarray(pmask)

    # consistent thermodynamics from the full pair list (what inactive
    # neighbours expose in the time-bin engine), then both force paths
    # over the masked sublist
    cfg_ref = SPHConfig(alpha_visc=0.8, use_pallas=False)
    rho_full, drho_full, _ = _density_pass(cells, pairs, cfg_ref)
    rho_full = jnp.where(cells.mask > 0, rho_full, 1.0)
    drho_full = jnp.where(cells.mask > 0, drho_full, 0.0)
    press, omega, cs = ghost_update(rho_full, drho_full, cells.u, cells.h)
    press = jnp.where(cells.mask > 0, press, 0.0)

    m = np.asarray(cells.mask)
    got = {}
    for use_pallas in (False, True):
        cfg = SPHConfig(alpha_visc=0.8, use_pallas=use_pallas,
                        pallas_interpret=True)
        rho, drho, nngb = _density_pass(cells, sub, cfg, pair_mask=pm)
        dv, du = _force_pass(cells, sub, rho_full, press, omega, cs, cfg,
                             pair_mask=pm)
        got[use_pallas] = {
            "rho": np.asarray(rho) * m, "drho": np.asarray(drho) * m,
            "nngb": np.asarray(nngb) * m,
            "dv": np.asarray(dv) * m[..., None], "du": np.asarray(du) * m}
    for name in got[False]:
        a, b = got[False][name], got[True][name]
        scale = max(np.abs(a).max(), 1.0)
        np.testing.assert_allclose(a, b, atol=5e-5 * scale, rtol=5e-5,
                                   err_msg=name)
    # masking is real: padded entries contribute nothing
    sub1 = PairList(ci=sub.ci[: len(idx)], cj=sub.cj[: len(idx)],
                    shift=sub.shift[: len(idx)])
    rho_nopad, _, _ = _density_pass(cells, sub1, cfg_ref)
    np.testing.assert_allclose(got[False]["rho"], np.asarray(rho_nopad) * m,
                               atol=1e-6)
