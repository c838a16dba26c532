"""O(N²) brute-force SPH oracle (and bulk-synchronous baseline stand-in).

Direct evaluation of eqs. (2)–(4) over all particle pairs with periodic
minimum-image distances, per axis of the box. This is the ground truth the
cell/task engine and the Pallas kernels are validated against, and doubles
as the "traditional code" baseline in ``benchmarks/baseline_compare.py``
(GADGET-2 fills that role in the paper; an O(N²)-masked dense evaluation is
its honest stand-in at test scale).
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from .cellgrid import box_lengths, per_axis
from .physics import EPS, GAMMA, eos_pressure, sound_speed
from .smoothing import get_kernel


def _min_image(dx, box):
    box = per_axis(box_lengths(box))
    return dx - box * jnp.round(dx / box)


def nsq_density(pos, mass, h, box, *, kernel: str = "cubic"):
    """rho, drho_dh, nngb for all particles, O(N²)."""
    w_fn, dwdr_fn = get_kernel(kernel)
    dx = _min_image(pos[:, None, :] - pos[None, :, :], box)
    r = jnp.sqrt(jnp.sum(dx * dx, axis=-1) + EPS)
    hi = h[:, None]
    w = w_fn(r, hi)
    rho = jnp.sum(mass[None, :] * w, axis=1)
    dwdh = -(3.0 * w + r * dwdr_fn(r, hi)) / hi
    drho_dh = jnp.sum(mass[None, :] * dwdh, axis=1)
    nngb = jnp.sum((w > 0.0), axis=1).astype(pos.dtype)
    return rho, drho_dh, nngb


def nsq_forces(pos, vel, mass, u, h, rho, omega, box, *,
               kernel: str = "cubic", alpha_visc: float = 0.0,
               gamma: float = GAMMA):
    """dv/dt and du/dt for all particles, O(N²) (eqs. 3, 4)."""
    _w_fn, dwdr_fn = get_kernel(kernel)
    press = eos_pressure(rho, u, gamma)
    cs = sound_speed(rho, u, gamma)
    dx = _min_image(pos[:, None, :] - pos[None, :, :], box)
    r2 = jnp.sum(dx * dx, axis=-1)
    r = jnp.sqrt(r2 + EPS)
    rhat = dx / r[:, :, None]
    hi, hj = h[:, None], h[None, :]
    dwi = dwdr_fn(r, hi)
    dwj = dwdr_fn(r, hj)
    ai = (press / (omega * rho ** 2))[:, None]
    aj = (press / (omega * rho ** 2))[None, :]
    fmag = ai * dwi + aj * dwj

    valid = (r < jnp.maximum(hi, hj)) & (r2 > EPS)

    du_visc = jnp.zeros_like(rho)
    if alpha_visc > 0.0:
        dvel = vel[:, None, :] - vel[None, :, :]
        vdotr = jnp.sum(dvel * dx, axis=-1)
        hbar = 0.5 * (hi + hj)
        rhobar = 0.5 * (rho[:, None] + rho[None, :])
        csbar = 0.5 * (cs[:, None] + cs[None, :])
        mu = hbar * vdotr / (r2 + 0.01 * hbar * hbar)
        mu = jnp.where(vdotr < 0.0, mu, 0.0)
        piij = (-alpha_visc * csbar * mu + 2.0 * alpha_visc * mu * mu) / rhobar
        dwbar = 0.5 * (dwi + dwj)
        fmag = fmag + piij * dwbar
        du_visc = 0.5 * jnp.sum(
            jnp.where(valid, mass[None, :] * piij * dwbar * (vdotr / r), 0.0),
            axis=1)

    fmag = jnp.where(valid, fmag, 0.0)
    mj = mass[None, :] * valid
    dv = -jnp.sum((mj * fmag)[:, :, None] * rhat, axis=1)

    dvel = vel[:, None, :] - vel[None, :, :]
    vdotrhat = jnp.sum(dvel * rhat, axis=-1)
    valid_u = (r < hi) & (r2 > EPS)
    du = (press / (omega * rho ** 2)) * jnp.sum(
        jnp.where(valid_u, mass[None, :] * vdotrhat * dwi, 0.0), axis=1)
    return dv, du + du_visc
