"""Pallas TPU kernels for SPH cell-pair interactions.

One grid program = a block of ``PAIRS_PER_PROGRAM`` SWIFT pair tasks
(``density_pair`` / ``force_pair``). TPU-native design decisions:

* The (C × C) interaction matrix is the unit of work, laid out with the
  i-side particle on sublanes and the j-side particle on lanes. Distances
  are formed from coordinate differences, three (C, C) planes, so they are
  exact f32 element-wise arithmetic with no matmul precision to choose.
* **Symmetry exploited** — both directions of the pair are produced in one
  program (row-reductions → i-side, column-reductions → j-side), reusing the
  distance matrix. The vmapped reference evaluates each direction separately;
  the kernel does the paper's "exploit symmetries in the particle
  interactions" optimisation.
* Periodic image shifts are applied by the host wrapper (ops.py), so the
  kernel body is branch-free Euclidean geometry.

Layout: the wrappers pass per-particle scalars as (P, C) and vectors as
coordinate planes (P, 3, C), and pad P to a multiple of
``PAIRS_PER_PROGRAM``, so every block's last two dimensions are either
(8, C) or the whole (3, C) — the shapes Mosaic tiles. Inside a program each
pair's i-side fields are turned from (1, C) rows into (C, 1) columns.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ...sph.smoothing import get_kernel

EPS = 1e-12


def _two_sum(a, b):
    """Error-free f32 addition: returns (fl(a+b), rounding error)."""
    s = a + b
    bb = s - a
    err = (a - (s - bb)) + (b - bb)
    return s, err


def _two_prod(a, b):
    """Error-free f32 product via Dekker splitting (no FMA needed)."""
    p = a * b
    split = 4097.0          # 2**12 + 1 for float32 (24-bit significand)
    ca = split * a
    a_hi = ca - (ca - a)
    a_lo = a - a_hi
    cb = split * b
    b_hi = cb - (cb - b)
    b_lo = b - b_hi
    err = ((a_hi * b_hi - p) + a_hi * b_lo + a_lo * b_hi) + a_lo * b_lo
    return p, err


def _df_sum_rows(hi, lo):
    """Σ over axis 0 of the double-float matrix hi + lo, rounded once.

    A compensated (two-sum) accumulation in a fixed order: first over
    aligned 8-row slabs, then down the 8 rows of the slab sum, so the result
    is the same on every backend.
    """
    slab = 8 if hi.shape[0] % 8 == 0 else 1

    def acc(s_hi, s_lo, x_hi, x_lo):
        s, e = _two_sum(s_hi, x_hi)
        e = e + (s_lo + x_lo)
        t = s + e                       # renormalise the pair
        return t, e - (t - s)

    s_hi, s_lo = hi[:slab], lo[:slab]
    for k in range(slab, hi.shape[0], slab):
        s_hi, s_lo = acc(s_hi, s_lo, hi[k:k + slab], lo[k:k + slab])
    r_hi, r_lo = s_hi[0:1], s_lo[0:1]
    for k in range(1, slab):
        r_hi, r_lo = acc(r_hi, r_lo, s_hi[k:k + 1], s_lo[k:k + 1])
    return r_hi + r_lo


def _df_weighted_contract(w, g, rhat, axis):
    """Σ_axis w·g·r̂ in double-float precision, as a (1, C) row per
    coordinate of the (C, C) planes ``rhat``.

    ``w`` broadcasts against the (C, C) matrix ``g``. Both directions of a
    pair task contract the *same* g and r̂ matrices, so computing the
    products and the reduction in double-float makes the pair's momentum
    exchange antisymmetric to the final-rounding floor — Newton's third law
    holds to ~1 ulp of each dv entry instead of drifting with the length of
    the f32 product/reduction chain. ``axis=1`` (the i-side) contracts the
    transposed planes down their rows.
    """
    p1, e1 = _two_prod(jnp.broadcast_to(w, g.shape), g)
    out = []
    for rk in rhat:
        p2, e2 = _two_prod(p1, rk)
        lo = e2 + e1 * rk
        if axis == 1:
            p2, lo = p2.T, lo.T
        out.append(_df_sum_rows(p2, lo))
    return out


PAIRS_PER_PROGRAM = 8       # pair tasks per grid program: the (8, C) block


def _col(row):
    """(1, C) row → (C, 1) column."""
    return row.reshape(row.shape[1], 1)


def _rows(ref, b):
    """The three (1, C) coordinate rows of pair ``b`` of a (TB, 3, C)
    block."""
    return [ref[b, k:k + 1, :] for k in range(3)]


def _geometry(xi, xj):
    """(C, C) displacement planes, r² and r from (1, C) coordinate rows:
    exact f32 differences, so close pairs far from the origin keep their
    digits."""
    dx = [_col(a) - b for a, b in zip(xi, xj)]
    r2 = dx[0] * dx[0] + dx[1] * dx[1] + dx[2] * dx[2]
    return dx, r2, jnp.sqrt(r2 + EPS)


def _flag(cond):
    return jnp.where(cond, 1.0, 0.0)


# ------------------------------------------------------------------ density
def _density_kernel(h_i_ref, m_i_ref, mask_i_ref, h_j_ref, m_j_ref,
                    mask_j_ref, pos_i_ref, pos_j_ref,
                    rho_i_ref, drho_i_ref, nngb_i_ref,
                    rho_j_ref, drho_j_ref, nngb_j_ref,
                    *, kernel: str):
    w_fn, dwdr_fn = get_kernel(kernel)

    def pair(b, carry):
        row = lambda ref: ref[pl.ds(b, 1), :]        # noqa: E731
        _dx, _r2, r = _geometry(_rows(pos_i_ref, b), _rows(pos_j_ref, b))
        hi = _col(row(h_i_ref))                      # (C, 1)
        hj = row(h_j_ref)                            # (1, C)

        # i <- j (rows reduce over j)
        wi = w_fn(r, hi)
        mj = row(m_j_ref) * row(mask_j_ref)
        dwdh_i = -(3.0 * wi + r * dwdr_fn(r, hi)) / hi
        for out, val in ((rho_i_ref, mj * wi), (drho_i_ref, mj * dwdh_i),
                         (nngb_i_ref, _flag(wi > 0.0) * row(mask_j_ref))):
            out[pl.ds(b, 1), :] = jnp.sum(val, axis=1, keepdims=True).T

        # j <- i (columns reduce over i) — same r matrix, h_j kernel
        wj = w_fn(r, hj)
        mi = _col(row(m_i_ref) * row(mask_i_ref))
        dwdh_j = -(3.0 * wj + r * dwdr_fn(r, hj)) / hj
        for out, val in ((rho_j_ref, mi * wj), (drho_j_ref, mi * dwdh_j),
                         (nngb_j_ref, _flag(wj > 0.0) * _col(row(mask_i_ref)))):
            out[pl.ds(b, 1), :] = jnp.sum(val, axis=0, keepdims=True)
        return carry

    jax.lax.fori_loop(0, PAIRS_PER_PROGRAM, pair, 0)


def _blocked(fn, scalars, planes, n_scalar_out, n_plane_out, interpret):
    """Run the kernel ``fn`` over pair blocks: pad P to the block, lay
    vectors out as coordinate planes, and undo both on the outputs. ``fn``
    takes the scalar refs, then the plane refs, then the scalar and the
    plane output refs."""
    P, C = scalars[0].shape
    tb = PAIRS_PER_PROGRAM
    pad = -P % tb
    if pad:
        edge = lambda a: jnp.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1),
                                 mode="edge")                # noqa: E731
        scalars = [edge(a) for a in scalars]
        planes = [edge(a) for a in planes]
    planes = [jnp.swapaxes(a, 1, 2) for a in planes]          # (P, 3, C)
    Pp = P + pad
    f32 = scalars[0].dtype
    sca = pl.BlockSpec((tb, C), lambda p: (p, 0))
    vec = pl.BlockSpec((tb, 3, C), lambda p: (p, 0, 0))
    outs = pl.pallas_call(
        fn,
        grid=(Pp // tb,),
        in_specs=[sca] * len(scalars) + [vec] * len(planes),
        out_specs=[sca] * n_scalar_out + [vec] * n_plane_out,
        out_shape=([jax.ShapeDtypeStruct((Pp, C), f32)] * n_scalar_out
                   + [jax.ShapeDtypeStruct((Pp, 3, C), f32)] * n_plane_out),
        interpret=interpret,
    )(*scalars, *planes)
    return ([o[:P] for o in outs[:n_scalar_out]]
            + [jnp.swapaxes(o[:P], 1, 2) for o in outs[n_scalar_out:]])


def density_pair_pallas(pos_i, h_i, m_i, mask_i, pos_j, h_j, m_j, mask_j,
                        *, kernel: str = "cubic", interpret: bool = False):
    """Batched cell-pair density, both directions per program.

    Shapes: pos (P, C, 3); h/m/mask (P, C). Returns six (P, C) arrays:
    (rho_i, drho_i, nngb_i, rho_j, drho_j, nngb_j). ``interpret=True`` runs
    the kernel in the Pallas interpreter (CPU); otherwise Mosaic compiles it.
    """
    return tuple(_blocked(functools.partial(_density_kernel, kernel=kernel),
                          [h_i, m_i, mask_i, h_j, m_j, mask_j],
                          [pos_i, pos_j], 6, 0, interpret))


# -------------------------------------------------------------------- force
def _force_kernel(h_i_ref, P_i_ref, rho_i_ref, om_i_ref, cs_i_ref,
                  m_i_ref, mask_i_ref,
                  h_j_ref, P_j_ref, rho_j_ref, om_j_ref, cs_j_ref,
                  m_j_ref, mask_j_ref,
                  pos_i_ref, vel_i_ref, pos_j_ref, vel_j_ref,
                  du_i_ref, du_j_ref, dv_i_ref, dv_j_ref,
                  *, kernel: str, alpha_visc: float):
    _w_fn, dwdr_fn = get_kernel(kernel)

    def pair(b, carry):
        row = lambda ref: ref[pl.ds(b, 1), :]        # noqa: E731
        dx, r2, r = _geometry(_rows(pos_i_ref, b), _rows(pos_j_ref, b))
        rhat = [d / r for d in dx]
        hi = _col(row(h_i_ref))
        hj = row(h_j_ref)
        m_i, m_j = _col(row(m_i_ref)), row(m_j_ref)
        k_i, k_j = _col(row(mask_i_ref)), row(mask_j_ref)
        rho_i, rho_j = _col(row(rho_i_ref)), row(rho_j_ref)
        cs_i, cs_j = _col(row(cs_i_ref)), row(cs_j_ref)
        coef_i = _col(row(P_i_ref) / (row(om_i_ref) * row(rho_i_ref) ** 2))
        coef_j = row(P_j_ref) / (row(om_j_ref) * row(rho_j_ref) ** 2)

        dwi = dwdr_fn(r, hi)
        dwj = dwdr_fn(r, hj)
        fmag = coef_i * dwi + coef_j * dwj

        valid = k_i * k_j * _flag(r < jnp.maximum(hi, hj)) * _flag(r2 > EPS)

        dvel = [_col(a) - c for a, c in zip(_rows(vel_i_ref, b),
                                             _rows(vel_j_ref, b))]
        vdotrhat = dvel[0] * rhat[0] + dvel[1] * rhat[1] + dvel[2] * rhat[2]

        du_visc_i = du_visc_j = 0.0
        if alpha_visc > 0.0:
            # match physics.force_block's rounding path exactly (vdotr from
            # dx, not vdotrhat*r) so the fused kernel keeps Newton's third
            # law to the same ulp as the two-sided reference
            vdotr = dvel[0] * dx[0] + dvel[1] * dx[1] + dvel[2] * dx[2]
            hbar = 0.5 * (hi + hj)
            rhobar = 0.5 * (rho_i + rho_j)
            csbar = 0.5 * (cs_i + cs_j)
            mu = hbar * vdotr / (r2 + 0.01 * hbar * hbar)
            mu = jnp.where(vdotr < 0.0, mu, 0.0)
            beta = 2.0 * alpha_visc
            piij = (-alpha_visc * csbar * mu + beta * mu * mu) / rhobar
            dwbar = 0.5 * (dwi + dwj)
            fmag = fmag + piij * dwbar
            heat = valid * piij * dwbar * (vdotr / r)
            du_visc_i = 0.5 * jnp.sum(m_j * heat, axis=1, keepdims=True).T
            du_visc_j = 0.5 * jnp.sum(m_i * heat, axis=0, keepdims=True)

        # Both directions contract the *same* (C, C) interaction matrix
        # against the same r̂, in double-float, so the pair's momentum
        # exchange is antisymmetric to the output-rounding floor (Newton's
        # third law).
        g = jnp.where(valid > 0, fmag, 0.0) * valid
        dv_i = _df_weighted_contract(m_j, g, rhat, axis=1)
        dv_j = _df_weighted_contract(m_i, g, rhat, axis=0)
        for k in range(3):
            dv_i_ref[b, k:k + 1, :] = -dv_i[k]
            dv_j_ref[b, k:k + 1, :] = dv_j[k]

        # energy eq. (4): per-side cutoff r < h_side
        valid_ui = k_j * _flag(r < hi) * _flag(r2 > EPS)
        du_i_ref[pl.ds(b, 1), :] = (coef_i.T * jnp.sum(
            m_j * valid_ui * vdotrhat * dwi, axis=1, keepdims=True).T
            + du_visc_i)
        valid_uj = k_i * _flag(r < hj) * _flag(r2 > EPS)
        # v_ji·r̂_ji = (−dvel)·(−r̂) = vdotrhat
        du_j_ref[pl.ds(b, 1), :] = (coef_j * jnp.sum(
            m_i * valid_uj * vdotrhat * dwj, axis=0, keepdims=True)
            + du_visc_j)
        return carry

    jax.lax.fori_loop(0, PAIRS_PER_PROGRAM, pair, 0)


def force_pair_pallas(pos_i, vel_i, h_i, press_i, rho_i, om_i, cs_i, m_i,
                      mask_i, pos_j, vel_j, h_j, press_j, rho_j, om_j, cs_j,
                      m_j, mask_j, *, kernel: str = "cubic",
                      alpha_visc: float = 0.0, interpret: bool = False):
    """Batched cell-pair forces, both directions per program.

    Returns (dv_i, du_i, dv_j, du_j): (P,C,3), (P,C), (P,C,3), (P,C).
    ``interpret=True`` runs the kernel in the Pallas interpreter (CPU).
    """
    fn = functools.partial(_force_kernel, kernel=kernel,
                           alpha_visc=alpha_visc)
    du_i, du_j, dv_i, dv_j = _blocked(
        fn, [h_i, press_i, rho_i, om_i, cs_i, m_i, mask_i,
             h_j, press_j, rho_j, om_j, cs_j, m_j, mask_j],
        [pos_i, vel_i, pos_j, vel_j], 2, 2, interpret)
    return dv_i, du_i, dv_j, du_j
