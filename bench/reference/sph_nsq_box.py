"""Plain O(N²) reference of one time-bin cycle's answer in a box whose
three edges may differ, in blocks of rows.

``sph_nsq.py`` for a periodic box of three lengths and an initial condition
of several smoothing lengths, such as a shock tube's two lattices. It is a
copy of that file with these changes: minimum images per axis; the span
bounded as below; ``kick_err`` holds the change of v and u over the cycle
of every sampled particle, whatever its bin, where that file's holds the
half step of the particles still in bin 0 (every particle of such a tube
sits in a deeper bin); and the sums under
``jax.default_matmul_precision("highest")``, so no multiply-sum becomes a
default-precision matrix product on a TPU.

Independent of the program: it imports nothing of it and reads only the
benchmark's initial condition and the answer under test. The physics is the
paper's eqs. (2)–(4) as the program states them: the M4 cubic spline with
support radius h, Ω from ∂ρ/∂h, P = (γ−1)ρu, Monaghan viscosity, periodic
minimum images, all pairs with r < max(h_i, h_j) for momentum and r < h_i
for density and energy.

What a cycle answers, and what is compared (``numbers``):

* ``time_err``: the cycle's span, min(dt_max, 2**max_depth · dt_min) in
  float32, where dt_min is the smallest CFL step. Each particle's step is
  cfl·h_i over the largest c + |v| near it, so cfl·min(h) / max(c + |v|)
  bounds every step from below; with one h it is the smallest step, and
  where 2**max_depth times it reaches dt_max the span is dt_max whatever
  the neighbourhoods. Any other case depends on which speeds meet which h,
  and the reference refuses it.
* ``rho_err``: the closing density of every particle at its final position,
  worst relative gap.
* ``acc_err``, ``dudt_err``: the closing forces, worst gap over the
  largest reference magnitude, on ``SAMPLE_ROWS`` particles: all that left
  bin 0 and a sample of the rest drawn from the seed (all particles of a
  smaller cell). The closing kick has already
  been applied to the answer's v and u, so the state the forces saw is
  recovered by undoing it: v − c·a, u − c·du/dt with c = ½·span·2**−bin.
  That holds for every particle whose last step ran from a bin boundary,
  as all do unless the neighbour limiter woke them mid-step.
* ``kick_err``: the change of the sampled particles' v and u over the
  cycle, v1 − v0 and u1 − u0 with v1, u1 after the closing kick, against
  the reference's own integration of the cycle from the initial condition:
  kick-drift-kick with every particle on the ladder's finest step,
  span / 2**max_depth, the forces of each step taken at the drifted
  positions with the half-step v and u, as the program takes them; the
  worst gap over the largest reference change, of v or of u, whichever is
  worse. Nothing of it comes from the answer, so a kick skipped, scaled or
  left off u on any trip shows here. (The trapezoid of the forces at the
  cycle's two ends is not enough: at the tube's interfaces the forces turn
  within the span, and it misses u's change by a tenth.)
* ``drift_err``: the displacement over the cycle of the sampled particles,
  minimum image, worst gap over the largest reference displacement. A
  particle still in bin 0 drifted span·v½ with v½ = v0 + ½·span·a0 from the
  reference's initial forces. One in a deeper bin took many steps;
  its displacement is the integral of its velocity, which the end-corrected
  trapezoid span·(v0 + v1)/2 − span²·(a1 − a0)/12 gives to fourth order in
  the span, with v1 its closing velocity and a0, a1 the reference's forces
  at the cycle's ends. The reference's displacement is taken as positions
  of the given precision would hold it: rounded onto x0 and back.

``dtype`` is float32 for the reference; the control runs it in bfloat16.
"""

from __future__ import annotations

import math
from typing import Dict

import numpy as np
import jax
import jax.numpy as jnp

EPS = 1e-12
CUBIC_NORM = 8.0 / math.pi
BLOCK_ROWS = 128
SAMPLE_ROWS = 32768


def _w(r, h):
    q = r / h
    w = jnp.where(q <= 0.5, 1.0 - 6.0 * q * q + 6.0 * q * q * q,
                  2.0 * (1.0 - q) ** 3)
    return jnp.where(q < 1.0, CUBIC_NORM / (h * h * h) * w, 0.0)


def _dwdr(r, h):
    q = r / h
    d = jnp.where(q <= 0.5, -12.0 * q + 18.0 * q * q, -6.0 * (1.0 - q) ** 2)
    return jnp.where(q < 1.0, CUBIC_NORM / h ** 4 * d, 0.0)


def _blocks(rows: np.ndarray) -> np.ndarray:
    """``rows`` in blocks of ``BLOCK_ROWS``, the last padded with its end."""
    nb = -(-len(rows) // BLOCK_ROWS)
    pad = np.full(nb * BLOCK_ROWS - len(rows), rows[-1])
    return np.concatenate([rows, pad]).reshape(nb, BLOCK_ROWS).astype(
        np.int32)


def _map_rows(fn, rows, *cols):
    """``fn(block, *cols)`` over the blocks of ``rows``; one result row per
    entry of ``rows``."""
    n = rows.shape[0] * rows.shape[1]
    out = jax.lax.map(lambda block: fn(block, *cols), rows)
    return jax.tree_util.tree_map(lambda a: a.reshape((n,) + a.shape[2:]),
                                  out)


def _separation(pos, rows, box):
    """Per-axis minimum-image separations (B, N) of ``rows`` from all;
    ``box`` holds the three edge lengths."""
    d = [pos[rows, k][:, None] - pos[None, :, k] for k in range(3)]
    return [x - box[k] * jnp.round(x / box[k]) for k, x in enumerate(d)]


@jax.jit
def _density(pos, mass, h, box):
    """ρ and Ω of every particle."""
    def block(rows, pos, mass, h):
        dx = _separation(pos, rows, box)
        r = jnp.sqrt(dx[0] ** 2 + dx[1] ** 2 + dx[2] ** 2 + EPS)
        hi = h[rows][:, None]
        w = _w(r, hi)
        rho = jnp.sum(mass[None, :] * w, axis=1)
        dwdh = -(3.0 * w + r * _dwdr(r, hi)) / hi
        return rho, jnp.sum(mass[None, :] * dwdh, axis=1)
    n = pos.shape[0]
    rho, drho_dh = _map_rows(block, jnp.asarray(_blocks(np.arange(n))),
                             pos, mass, h)
    rho = jnp.maximum(rho[:n], EPS)
    drho_dh = drho_dh[:n]
    omega = 1.0 + h / (3.0 * rho) * drho_dh
    return rho, jnp.where(jnp.abs(omega) < 1e-4, 1.0, omega)


@jax.jit
def _forces(rows, pos, vel, mass, u, h, rho, omega, box, alpha, gamma):
    """dv/dt and du/dt of the particles ``rows`` (blocks of indices)."""
    press = (gamma - 1.0) * rho * u
    cs = jnp.sqrt(jnp.maximum(gamma * (gamma - 1.0) * u, 0.0))
    coef = press / (omega * rho ** 2)

    def block(rows, pos, vel, mass, h, rho, cs, coef):
        dx = _separation(pos, rows, box)
        dv = [vel[rows, k][:, None] - vel[None, :, k] for k in range(3)]
        r2 = dx[0] ** 2 + dx[1] ** 2 + dx[2] ** 2
        r = jnp.sqrt(r2 + EPS)
        hi, hj = h[rows][:, None], h[None, :]
        dwi, dwj = _dwdr(r, hi), _dwdr(r, hj)
        fmag = coef[rows][:, None] * dwi + coef[None, :] * dwj
        valid = (r < jnp.maximum(hi, hj)) & (r2 > EPS)
        vdotr = dv[0] * dx[0] + dv[1] * dx[1] + dv[2] * dx[2]
        hbar = 0.5 * (hi + hj)
        mu = jnp.where(vdotr < 0.0, hbar * vdotr / (r2 + 0.01 * hbar * hbar),
                       0.0)
        rhobar = 0.5 * (rho[rows][:, None] + rho[None, :])
        csbar = 0.5 * (cs[rows][:, None] + cs[None, :])
        piij = (-alpha * csbar * mu + 2.0 * alpha * mu * mu) / rhobar
        dwbar = 0.5 * (dwi + dwj)
        mj = jnp.where(valid, mass[None, :], 0.0)
        f = mj * jnp.where(valid, fmag + piij * dwbar, 0.0) / r
        acc = jnp.stack([-jnp.sum(f * dx[k], axis=1) for k in range(3)], -1)
        du_visc = 0.5 * jnp.sum(mj * piij * dwbar * vdotr / r, axis=1)
        valid_u = (r < hi) & (r2 > EPS)
        du = coef[rows] * jnp.sum(
            jnp.where(valid_u, mass[None, :] * dwi * vdotr / r, 0.0), axis=1)
        return acc, du + du_visc
    return _map_rows(block, rows, pos, vel, mass, h, rho, cs, coef)


def _integrate(pos, vel, u, mass, h, box, alpha, gamma, span: float,
               depth: int):
    """v and u of every particle at the cycle's end by kick-drift-kick over
    2**depth equal steps of ``span``, each force pass at the drifted
    positions with the half-step v and u (module docstring)."""
    n = pos.shape[0]
    rows = jnp.asarray(_blocks(np.arange(n)))

    def forces(pos, vel, u):
        rho, omega = _density(pos, mass, h, box)
        acc, dudt = _forces(rows, pos, vel, mass, u, h, rho, omega, box,
                            alpha, gamma)
        return acc[:n], dudt[:n]

    dt = jnp.asarray(span / 2 ** depth, pos.dtype)
    acc, dudt = forces(pos, vel, u)
    for _ in range(2 ** depth):
        vel, u = vel + 0.5 * dt * acc, u + 0.5 * dt * dudt
        pos = jnp.mod(pos + dt * vel, box)
        acc, dudt = forces(pos, vel, u)
        vel, u = vel + 0.5 * dt * acc, u + 0.5 * dt * dudt
    return vel, u


def box_of(ic: Dict) -> np.ndarray:
    """The box's three edge lengths (one length is a cube)."""
    return np.broadcast_to(np.asarray(ic["box"], np.float64), (3,))


def cycle_span(ic: Dict, cfg: Dict) -> float:
    """The span the program's plan gives a cycle from this initial
    condition (module docstring)."""
    h = np.asarray(ic["h"], np.float32)
    gamma = np.float32(cfg["physics"]["gamma"])
    u = np.asarray(ic["u"], np.float32)
    cs = np.sqrt(np.maximum(gamma * (gamma - np.float32(1.0)) * u,
                            np.float32(0.0)))
    speed = cs + np.sqrt(np.sum(np.asarray(ic["vel"], np.float32) ** 2, -1))
    dt_low = np.float32(cfg["physics"]["cfl"]) * np.min(h) / np.max(speed)
    capped = np.float32(dt_low) * np.float32(2.0 ** cfg["max_depth"])
    dt_max = np.float32(cfg["dt_max"])
    if np.ptp(h) != 0.0 and capped < dt_max:
        raise ValueError("with several smoothing lengths the span reference "
                         "holds only where 2**max_depth times the smallest "
                         "CFL step reaches dt_max")
    return float(min(dt_max, capped))


def _physics(cfg: Dict, dtype):
    ph = cfg["physics"]
    if ph["kernel"] != "cubic":
        raise ValueError(f"reference has the cubic kernel only, not "
                         f"{ph['kernel']!r}")
    return (jnp.asarray(ph["alpha_visc"], dtype),
            jnp.asarray(ph["gamma"], dtype))


def _f64(a) -> np.ndarray:
    return np.asarray(np.asarray(a, np.float32), np.float64)


def sample_rows(bins: np.ndarray, seed: int, size: int = SAMPLE_ROWS
                ) -> np.ndarray:
    """The particles whose forces are compared: every one outside bin 0
    (the ones the ladder worked hardest), then particles of bin 0 drawn
    from the seed, ``size`` in all, or every particle where there are
    fewer. A fixed count keeps one compiled reference for every seed."""
    n = len(bins)
    if n <= size:
        return np.arange(n)
    rng = np.random.default_rng(seed)
    deep = np.flatnonzero(bins > 0)
    if len(deep) >= size:
        return np.sort(rng.choice(deep, size, replace=False))
    rest = rng.choice(np.flatnonzero(bins == 0), size - len(deep),
                      replace=False)
    return np.sort(np.concatenate([deep, rest]))


def pre_kick(answer: Dict, span: float) -> Dict[str, np.ndarray]:
    """The answer's closing-boundary v and u before its closing kick."""
    close = 0.5 * span * np.exp2(-_f64(answer["bins"]))
    return {"vel": _f64(answer["vel"]) - close[:, None] * _f64(answer["accel"]),
            "u": _f64(answer["u"]) - close * _f64(answer["dudt"])}


def expected(ic: Dict, answer: Dict, cfg: Dict, rows: np.ndarray,
             dtype=jnp.float32) -> Dict[str, np.ndarray]:
    """What the cycle should have produced, computed in ``dtype``: its span,
    the closing density of every particle and the closing forces of
    ``rows`` at the answer's final positions (from the state before the
    closing kick), and their change of v and u and their displacement over
    the cycle."""
    put = lambda a: jnp.asarray(np.asarray(a, np.float32), dtype)
    alpha, gamma = _physics(cfg, dtype)
    box = jnp.asarray(box_of(ic), dtype)
    mass, h = put(ic["mass"]), put(ic["h"])
    blocks = jnp.asarray(_blocks(rows))
    span = cycle_span(ic, cfg)
    pre = pre_kick(answer, span)
    pos = put(answer["pos"])
    pos0 = put(ic["pos"])
    with jax.default_matmul_precision("highest"):
        rho, omega = _density(pos, mass, h, box)
        acc, dudt = _forces(blocks, pos, put(pre["vel"]), mass, put(pre["u"]),
                            h, rho, omega, box, alpha, gamma)
        rho0, omega0 = _density(pos0, mass, h, box)
        acc0, _ = _forces(blocks, pos0, put(ic["vel"]), mass, put(ic["u"]),
                          h, rho0, omega0, box, alpha, gamma)
        vel1, u1 = _integrate(pos0, put(ic["vel"]), put(ic["u"]), mass, h,
                              box, alpha, gamma, span, cfg["max_depth"])
    k = len(rows)
    t = float(jnp.asarray(span, dtype))
    vel0 = _f64(ic["vel"])[rows]
    a0, a1 = _f64(acc0)[:k], _f64(acc)[:k]
    vel_half = vel0 + 0.5 * t * a0
    stay = np.asarray(answer["bins"])[rows] == 0
    moved = np.where(stay[:, None], t * vel_half,
                     0.5 * t * (vel0 + _f64(answer["vel"])[rows])
                     - t * t / 12.0 * (a1 - a0))
    x0 = pos0[rows]
    x1 = x0 + jnp.asarray(moved, dtype)
    return {"time": t, "rho": _f64(rho),
            "accel": _f64(acc)[:k], "dudt": _f64(dudt)[:k],
            "dvel": _f64(vel1)[rows] - vel0,
            "du": _f64(u1)[rows] - _f64(ic["u"])[rows],
            "moved": _min_image(_f64(x1) - _f64(x0), box_of(ic))}


def _min_image(d: np.ndarray, box: np.ndarray) -> np.ndarray:
    return d - box * np.round(d / box)


def observed(ic: Dict, answer: Dict, cfg: Dict, rows: np.ndarray
             ) -> Dict[str, np.ndarray]:
    """The same quantities read from the program's answer."""
    return {"time": float(answer["time"]), "rho": _f64(answer["rho"]),
            "accel": _f64(answer["accel"])[rows],
            "dudt": _f64(answer["dudt"])[rows],
            "dvel": _f64(answer["vel"])[rows] - _f64(ic["vel"])[rows],
            "du": _f64(answer["u"])[rows] - _f64(ic["u"])[rows],
            "moved": _min_image(_f64(answer["pos"])[rows]
                                - _f64(ic["pos"])[rows], box_of(ic))}


def compare(ref: Dict, got: Dict) -> Dict[str, float]:
    """The compared numbers: worst gaps of ``got`` from ``ref``."""
    gap = lambda a, b: np.max(np.abs(a - b))
    out = {"time_err": abs(got["time"] - ref["time"]) / ref["time"],
           "rho_err": float(np.max(np.abs(got["rho"] - ref["rho"])
                                   / ref["rho"])),
           "acc_err": float(gap(got["accel"], ref["accel"])
                            / np.max(np.abs(ref["accel"]))),
           "dudt_err": float(gap(got["dudt"], ref["dudt"])
                             / np.max(np.abs(ref["dudt"]))),
           "kick_err": float(max(gap(got[k], ref[k]) / np.max(np.abs(ref[k]))
                                 for k in ("dvel", "du")))}
    out["drift_err"] = float(gap(got["moved"], ref["moved"])
                             / np.max(np.abs(ref["moved"])))
    return out


def numbers(ic: Dict, answer: Dict, cfg: Dict, seed: int) -> Dict[str, float]:
    """The program's numbers: its answer against the float32 reference."""
    rows = sample_rows(answer["bins"], seed)
    return compare(expected(ic, answer, cfg, rows),
                   observed(ic, answer, cfg, rows))


def control_numbers(ic: Dict, answer: Dict, cfg: Dict, seed: int
                    ) -> Dict[str, float]:
    """The control's numbers: the reference in bfloat16 in the program's
    place, on the same positions, pre-kick states and rows."""
    rows = sample_rows(answer["bins"], seed)
    return compare(expected(ic, answer, cfg, rows),
                   expected(ic, answer, cfg, rows, jnp.bfloat16))
