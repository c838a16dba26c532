"""SPH pair physics: density (eq. 2), forces (eq. 3), energy (eq. 4).

All functions here operate on *blocks* of particles — a receiver block
``i`` of shape (Ci, …) and a source block ``j`` of shape (Cj, …) — and are
the numerical payload of SWIFT's ``density_pair`` / ``force_pair`` tasks.
The engine vmaps them over the cell-pair list; ``kernels/sph_pair`` provides
the Pallas TPU version with these as the oracle.

Distances are formed from coordinate differences, exact f32 element-wise
arithmetic on every backend. Periodic wrapping is handled *before* the
kernel by shifting the source block by the cell-pair's periodic image offset
(provided by the cell grid), so no per-element modulo is needed inside the
hot loop — a TPU-friendly restructuring of the usual min-image convention.

The optional Monaghan artificial viscosity (standard in SWIFT) is symmetric,
so momentum and total energy remain conserved.
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from .smoothing import get_kernel

GAMMA = 5.0 / 3.0      # adiabatic index (monatomic ideal gas)
EPS = 1e-12


def eos_pressure(rho, u, gamma: float = GAMMA):
    """P = (γ−1)·ρ·u."""
    return (gamma - 1.0) * rho * u


def sound_speed(rho, u, gamma: float = GAMMA):
    """c = sqrt(γ·P/ρ) = sqrt(γ(γ−1)u)."""
    return jnp.sqrt(jnp.maximum(gamma * (gamma - 1.0) * u, 0.0))


def pairwise_r2(pos_i, pos_j):
    """(Ci, Cj) squared distances."""
    # from differences: the |xi|²+|xj|²−2·xi·xj dot form compiles to a TPU
    # convolution at default (bf16-pass) precision and cancels for close pairs
    dx = pos_i[:, None, :] - pos_j[None, :, :]
    return jnp.sum(dx * dx, axis=-1)


class DensityResult(NamedTuple):
    rho: jax.Array        # (Ci,) Σ m_j W(r, h_i)
    drho_dh: jax.Array    # (Ci,) Σ m_j ∂W/∂h(r, h_i)
    nngb: jax.Array       # (Ci,) weighted neighbour count (for h iteration)


def density_block(pos_i, h_i, pos_j, m_j, mask_j, *,
                  kernel: str = "cubic") -> DensityResult:
    """Density contributions of source block j onto receiver block i (eq. 2).

    Includes the self term when the blocks alias (W(0, h) is finite).
    ``mask_j`` zeroes padded slots.
    """
    w_fn, dwdr_fn = get_kernel(kernel)
    r2 = pairwise_r2(pos_i, pos_j)
    r = jnp.sqrt(r2 + EPS)
    h = h_i[:, None]
    w = w_fn(r, h)
    mw = m_j[None, :] * mask_j[None, :] * w
    rho = jnp.sum(mw, axis=1)
    dwdh = -(3.0 * w + r * dwdr_fn(r, h)) / h
    drho_dh = jnp.sum(m_j[None, :] * mask_j[None, :] * dwdh, axis=1)
    nngb = jnp.sum((w > 0.0) * mask_j[None, :], axis=1)
    return DensityResult(rho, drho_dh, nngb)


class ForceResult(NamedTuple):
    dv: jax.Array      # (Ci, 3) acceleration contribution
    du: jax.Array      # (Ci,)  du/dt contribution


def force_block(pos_i, vel_i, h_i, P_i, rho_i, omega_i, cs_i,
                pos_j, vel_j, h_j, P_j, rho_j, omega_j, cs_j,
                m_j, mask_j, *, kernel: str = "cubic",
                alpha_visc: float = 0.0) -> ForceResult:
    """Force and energy contributions of block j onto block i (eqs. 3, 4).

    The pair predicate is r < max(h_i, h_j) for the momentum equation and
    r < h_i for the energy equation, exactly as in the paper.
    """
    _w_fn, dwdr_fn = get_kernel(kernel)
    dx = pos_i[:, None, :] - pos_j[None, :, :]       # (Ci, Cj, 3)
    r2 = jnp.sum(dx * dx, axis=-1)
    r = jnp.sqrt(r2 + EPS)
    rhat = dx / r[:, :, None]

    hi = h_i[:, None]
    hj = h_j[None, :]
    dwi = dwdr_fn(r, hi)                              # ∇W(r, h_i) magnitude
    dwj = dwdr_fn(r, hj)                              # ∇W(r, h_j) magnitude

    # pressure term of eq. (3)
    ai = (P_i / (omega_i * rho_i ** 2))[:, None]      # (Ci, 1)
    aj = (P_j / (omega_j * rho_j ** 2))[None, :]      # (1, Cj)
    fmag = ai * dwi + aj * dwj                        # (Ci, Cj)

    valid = mask_j[None, :] * (r < jnp.maximum(hi, hj)) * (r2 > EPS)

    # artificial viscosity (Monaghan 1992), symmetric in (i, j)
    du_visc = jnp.zeros(pos_i.shape[0], dtype=pos_i.dtype)
    if alpha_visc > 0.0:
        dvel = vel_i[:, None, :] - vel_j[None, :, :]
        vdotr = jnp.sum(dvel * dx, axis=-1)
        hbar = 0.5 * (hi + hj)
        rhobar = 0.5 * (rho_i[:, None] + rho_j[None, :])
        csbar = 0.5 * (cs_i[:, None] + cs_j[None, :])
        mu = hbar * vdotr / (r2 + 0.01 * hbar * hbar)
        mu = jnp.where(vdotr < 0.0, mu, 0.0)
        beta = 2.0 * alpha_visc
        piij = (-alpha_visc * csbar * mu + beta * mu * mu) / rhobar
        dwbar = 0.5 * (dwi + dwj)
        fmag = fmag + piij * dwbar
        # viscous heating: ½ Σ m_j Π_ij v_ij·∇W̄ (symmetric split)
        mvisc = m_j[None, :] * valid
        du_visc = 0.5 * jnp.sum(
            mvisc * piij * dwbar * (vdotr / r), axis=1)

    mj = m_j[None, :] * valid
    fmag = jnp.where(valid > 0, fmag, 0.0)   # padded slots may hold non-finite
    dv = -jnp.sum((mj * fmag)[:, :, None] * rhat, axis=1)   # (Ci, 3)

    # eq. (4): du_i/dt = P_i/(Ω_i ρ_i²) Σ_j m_j (v_i − v_j)·∇W(r, h_i)
    dvel = vel_i[:, None, :] - vel_j[None, :, :]
    vdotrhat = jnp.sum(dvel * rhat, axis=-1)
    valid_u = mask_j[None, :] * (r < hi) * (r2 > EPS)
    du = (P_i / (omega_i * rho_i ** 2)) * jnp.sum(
        m_j[None, :] * valid_u * vdotrhat * dwi, axis=1)
    return ForceResult(dv, du + du_visc)


def cfl_timestep_block(h, u, vel, mask, *, gamma: float = GAMMA,
                       cfl: float = 0.25):
    """Per-particle CFL time-step: dt_i = C_CFL · h_i / (c_i + |v_i|).

    This is the quantity the time-bin hierarchy quantises into power-of-two
    bins (``timebins.assign_bins``): the dynamic range of dt_i across a
    clustered simulation reaches ~10^4, which is exactly why integrating
    everything at min_i dt_i wastes the machine. Padded slots get +inf so
    reductions and bin assignment ignore them.
    """
    cs = sound_speed(jnp.ones_like(u), u, gamma)   # c = sqrt(γ(γ−1)u)
    speed = jnp.linalg.norm(vel, axis=-1) + cs
    dt = cfl * h / jnp.maximum(speed, EPS)
    return jnp.where(mask > 0, dt, jnp.inf)


def ghost_update(rho, drho_dh, u, h, *, gamma: float = GAMMA
                 ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """The 'ghost' task (triangle in Fig. 1): close the density loop.

    Computes pressure, Ω correction (Ω = 1 + h/(3ρ)·∂ρ/∂h) and sound speed
    once every density contribution for a cell has been accumulated.
    """
    rho_safe = jnp.maximum(rho, EPS)
    omega = 1.0 + (h / (3.0 * rho_safe)) * drho_dh
    omega = jnp.where(jnp.abs(omega) < 1e-4, 1.0, omega)   # guard degenerate
    press = eos_pressure(rho_safe, u, gamma)
    cs = sound_speed(rho_safe, u, gamma)
    return press, omega, cs


def smoothing_length_update(h, rho, m, nngb, *, n_target: float = 48.0,
                            eta: float = 0.5, h_min: float = 1e-6,
                            h_max: float | None = None):
    """One fixed-point update of h towards ~constant neighbour number.

    SWIFT iterates h_i so each particle keeps ≈ n_target neighbours; a single
    damped fixed-point step per time-step tracks the compressible flow
    (smoothing lengths span orders of magnitude across the clustered IC).
    """
    ratio = (n_target / jnp.maximum(nngb, 1.0)) ** (1.0 / 3.0)
    h_new = h * (1.0 - eta + eta * ratio)
    if h_max is not None:
        h_new = jnp.minimum(h_new, h_max)
    return jnp.maximum(h_new, h_min)
