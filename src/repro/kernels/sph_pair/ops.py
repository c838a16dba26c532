"""Jit'd wrappers: gather pair blocks → Pallas kernel → scatter-accumulate.

The gather/scatter around the kernel is the wave execution of the task
graph: one ``density_pairs`` call executes *every* density task of the wave
as a single batched Pallas launch (DESIGN.md §2 C1).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from .kernel import density_pair_pallas, force_pair_pallas


def _gather(cells, pairs):
    gi = lambda a: a[pairs.ci]
    gj = lambda a: a[pairs.cj]
    pos_i = gi(cells.pos)
    pos_j = gj(cells.pos) + pairs.shift[:, None, :]
    return gi, gj, pos_i, pos_j


def density_pairs(cells, pairs, *, kernel: str = "cubic",
                  interpret: bool = False, pair_mask=None):
    """All density_pair/density_self tasks → (rho, drho_dh, nngb).

    ``pair_mask`` (npairs,) zeroes masked pair tasks' contributions (padding
    used by the time-bin engine's fixed-shape level pair lists).
    """
    gi, gj, pos_i, pos_j = _gather(cells, pairs)
    rho_i, drho_i, nn_i, rho_j, drho_j, nn_j = density_pair_pallas(
        pos_i, gi(cells.h), gi(cells.mass), gi(cells.mask),
        pos_j, gj(cells.h), gj(cells.mass), gj(cells.mask),
        kernel=kernel, interpret=interpret)

    ncells, cap = cells.mass.shape
    notself = (pairs.ci != pairs.cj).astype(cells.pos.dtype)[:, None]
    live = jnp.ones_like(notself) if pair_mask is None else pair_mask[:, None]

    def scatter(a_ij, a_ji):
        out = jnp.zeros((ncells, cap), cells.pos.dtype)
        out = out.at[pairs.ci].add(a_ij * live)
        out = out.at[pairs.cj].add(a_ji * notself * live)
        return out

    return (scatter(rho_i, rho_j), scatter(drho_i, drho_j),
            scatter(nn_i, nn_j))


def force_pairs(cells, pairs, rho, press, omega, cs, *,
                kernel: str = "cubic", alpha_visc: float = 0.0,
                interpret: bool = False, pair_mask=None):
    """All force_pair/force_self tasks → (dv, du)."""
    gi, gj, pos_i, pos_j = _gather(cells, pairs)
    dv_i, du_i, dv_j, du_j = force_pair_pallas(
        pos_i, gi(cells.vel), gi(cells.h), gi(press), gi(rho), gi(omega),
        gi(cs), gi(cells.mass), gi(cells.mask),
        pos_j, gj(cells.vel), gj(cells.h), gj(press), gj(rho), gj(omega),
        gj(cs), gj(cells.mass), gj(cells.mask),
        kernel=kernel, alpha_visc=alpha_visc, interpret=interpret)

    ncells, cap = cells.mass.shape
    notself = (pairs.ci != pairs.cj).astype(cells.pos.dtype)
    live = jnp.ones_like(notself) if pair_mask is None else pair_mask

    dv = jnp.zeros((ncells, cap, 3), cells.pos.dtype)
    dv = dv.at[pairs.ci].add(dv_i * live[:, None, None])
    dv = dv.at[pairs.cj].add(dv_j * (notself * live)[:, None, None])
    du = jnp.zeros((ncells, cap), cells.pos.dtype)
    du = du.at[pairs.ci].add(du_i * live[:, None])
    du = du.at[pairs.cj].add(du_j * (notself * live)[:, None])
    return dv, du
