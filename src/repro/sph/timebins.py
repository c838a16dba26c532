"""Hierarchical time-bin integration: per-particle time-steps (1807.01341).

Real simulations have a dynamic range of ~10^4 in stable time-step size;
integrating every particle at the global minimum wastes almost the whole
machine. Following Borrow et al. (arXiv:1807.01341) and SWIFT's time
integration (arXiv:2305.13380), each particle is assigned to a power-of-two
**time bin**: bin b steps with dt = dt_max / 2**b, so bin 0 carries the
longest step and deeper bins subdivide it exactly. One *cycle* spans dt_max
and consists of 2**depth sub-steps of the finest dt, where
depth = max occupied bin.

At sub-step n the **active** bins are those whose step boundary divides n:
bins b ≥ depth − tz(n) (tz = trailing zeros; n = 0 starts every bin). Active
particles get the full density → ghost → force → kick treatment; inactive
particles are *drifted* — position-only prediction at their last kicked
velocity — and contribute to their active neighbours' sums through the
drifted positions and their stored density/pressure. Kicks are synchronised
at bin boundaries: the KDK ladder of 1807.01341 Fig. 1, which reduces to the
global-dt engine's leapfrog when depth = 0.

The task-graph side lives in ``engine.build_taskgraph(cell_bins=…,
level=…)`` + ``core.scheduler`` (activation masks, active-only wave
schedules) and ``core.cost_model.timebin_units`` / ``core.decompose.
timebin_node_weights`` (cycle-averaged work for the partitioner).

Sub-step programs are jitted with level-restricted pair lists padded to
power-of-two lengths, so the number of distinct compiled programs is
O(log npairs) per cycle, not O(2**depth).
"""

from __future__ import annotations

import functools
from typing import Dict, NamedTuple, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from ..observability import device_metrics as dmetrics
from ..observability.tracer import NULL_TRACER
from .cellgrid import GridSpec, PairList, ParticleCells, bin_particles, \
    box_lengths, build_pair_list, choose_grid, unbin, wrap_positions
from .engine import SPHConfig, _density_pass, _force_pass
from .physics import cfl_timestep_block, ghost_update

MAX_DEPTH_DEFAULT = 10      # ≥3 decades of dt spread (2**10 = 1024)
_U_FLOOR = 1e-12
_DU_SAFETY = 0.25           # dt ≤ κ·u/|du/dt| — strong-shock heating limit


def particle_timesteps(cells: ParticleCells, dudt, *, gamma: float,
                       cfl: float, du_safety: float = _DU_SAFETY,
                       u_floor=0.0):
    """Per-particle dt: CFL ∧ internal-energy criterion.

    The CFL term alone is blind to a shock front heating cold gas — u can
    e-fold in far less than h/c of the *pre-shock* sound speed. The
    κ·(u + u_floor)/|du/dt| term (SWIFT carries a similar guard) keeps the
    thermal update resolved where it is dynamically significant. The
    ``u_floor`` (typically the mass-weighted mean u) anchors "significant"
    to the problem's thermal scale: without it, numerically-cold background
    gas (u ~ 0) would be pinned onto the deepest bins by noise-level
    heating and the multi-dt advantage would evaporate.
    """
    dt = cfl_timestep_block(cells.h, cells.u, cells.vel, cells.mask,
                            gamma=gamma, cfl=cfl)
    xp = jnp if isinstance(dt, jax.Array) else np
    dt_u = du_safety * (cells.u + u_floor) / xp.maximum(xp.abs(dudt), 1e-30)
    dt_u = xp.where(cells.mask > 0, dt_u, xp.inf)
    return xp.minimum(dt, dt_u)


# ------------------------------------------------------------------ bin math
# Quantisation thresholds for assign_bins: ratio > _BIN_THRESHOLDS[k-1] puts
# a particle at least in bin k. Precomputed in float64 and rounded once to
# float32 so the decision is a pure f32 comparison — numpy and XLA disagree
# in the last ulp of log2 (the original formulation), and a bin flipping
# between host- and device-computed plans breaks the bitwise-parity contract
# of the device-scheduled path. The 1e-6 slack keeps the historical
# behaviour that dt == dt_max/2**k lands exactly in bin k.
BIN_LADDER_MAX = 24
_BIN_THRESHOLDS = np.asarray(
    2.0 ** (np.arange(BIN_LADDER_MAX) + 1e-6), np.float32)


def assign_bins(dt, dt_max, max_bin):
    """Quantise per-particle time-steps onto the power-of-two ladder.

    Returns the smallest b with dt_max / 2**b ≤ dt (so the bin step never
    exceeds the CFL step), clipped to [0, max_bin]. Works on numpy and jax
    arrays (``dt_max``/``max_bin`` may be traced scalars); +inf entries
    (padded slots) land in bin 0. Implemented as a comparison ladder
    against f32 thresholds so numpy and XLA agree bit-for-bit; bins beyond
    ``BIN_LADDER_MAX`` are unreachable (max_depth is validated against it).
    """
    xp = jnp if isinstance(dt, jax.Array) else np
    ratio = dt_max / xp.maximum(dt, 1e-30)
    thr = _BIN_THRESHOLDS if xp is np else jnp.asarray(_BIN_THRESHOLDS)
    b = (ratio[..., None] > thr).sum(axis=-1).astype(xp.int32)
    return xp.minimum(b, max_bin).astype(xp.int32)


def bin_timestep(dt_max: float, bins):
    """dt of each bin: dt_max / 2**b (exact in float — power-of-two scale)."""
    xp = jnp if isinstance(bins, jax.Array) else np
    return dt_max * xp.exp2(-bins.astype(xp.float32))


def active_level(n: int, depth: int) -> int:
    """Lowest active bin at sub-step ``n`` of a 2**depth cycle.

    Bins b ≥ active_level(n, depth) start/end a step at sub-step n. n = 0
    (cycle start) activates every bin.
    """
    if n == 0:
        return 0
    tz = (n & -n).bit_length() - 1
    return max(depth - tz, 0)


def trailing_zeros_table(nsub: int) -> np.ndarray:
    """tz(n) for n = 0..nsub as an int32 table (tz(0) := 0).

    The device-scheduled cycle program derives the active level of a traced
    sub-step index n as max(depth − tz_table[n], 0) — the same integer math
    as :func:`active_level`, with the bit-twiddling hoisted into a static
    lookup table.
    """
    return np.asarray(
        [0] + [(n & -n).bit_length() - 1 for n in range(1, nsub + 1)],
        np.int32)


# ---------------------------------------------------- reproducible reductions
def tree_sum(x):
    """Sum by fixed binary fold (pad to a power of two, halve repeatedly).

    ``xp.sum`` accumulation order is backend-defined — numpy uses pairwise
    blocks, XLA whatever the reduce lowering picks — so the same f32 data
    can sum to different last ulps on host and device. Every quantity that
    must agree bitwise between a host-computed and a device-computed cycle
    plan (u_floor) goes through this fold instead, on both sides.
    """
    xp = jnp if isinstance(x, jax.Array) else np
    x = xp.ravel(x)
    n = x.shape[0]
    p = 1
    while p < max(n, 1):
        p *= 2
    if p != n:
        x = xp.concatenate([x, xp.zeros((p - n,), x.dtype)])
    while x.shape[0] > 1:
        h = x.shape[0] // 2
        x = x[:h] + x[h:]
    return x[0]


def mass_weighted_mean_u(mass_masked, u):
    """u_floor of :func:`particle_timesteps`: Σ m·u / Σ m via tree_sum.

    Shared by the host planners and the device plan program so the floor —
    and therefore every deepening decision downstream of it — is bitwise
    identical regardless of where the plan was computed.
    """
    xp = jnp if isinstance(u, jax.Array) else np
    num = tree_sum(mass_masked * u)
    den = xp.maximum(tree_sum(mass_masked), 1e-30)
    return num / den


def speed_norm(vel):
    """|v| with a pinned evaluation order: sqrt((v0² + v1²) + v2²) in f32.

    np.linalg.norm's reduction strategy is not contractually ordered;
    spelling the three-term sum out keeps host- and device-computed signal
    speeds bit-identical.
    """
    xp = jnp if isinstance(vel, jax.Array) else np
    v0, v1, v2 = vel[..., 0], vel[..., 1], vel[..., 2]
    return xp.sqrt((v0 * v0 + v1 * v1) + v2 * v2)


def cell_max_bins(bins: np.ndarray, mask: np.ndarray) -> np.ndarray:
    """Deepest occupied bin per cell, −1 for empty cells: (ncells,)."""
    b = np.where(np.asarray(mask) > 0, np.asarray(bins), -1)
    return b.max(axis=1).astype(np.int64)


def cell_bin_histogram(bins: np.ndarray, mask: np.ndarray,
                       nbins: int) -> np.ndarray:
    """(ncells, nbins) occupancy histogram over time bins."""
    bins = np.asarray(bins)
    mask = np.asarray(mask) > 0
    ncells = bins.shape[0]
    out = np.zeros((ncells, nbins), dtype=np.int64)
    for c in range(ncells):
        bc = bins[c][mask[c]]
        if len(bc):
            out[c] = np.bincount(np.clip(bc, 0, nbins - 1), minlength=nbins)
    return out


def limit_neighbour_bins(bins: np.ndarray, mask: np.ndarray,
                         ci: np.ndarray, cj: np.ndarray, *,
                         delta: int = 2, max_bin: int,
                         max_iter: int = 256) -> np.ndarray:
    """Neighbour time-step limiter (Saitoh–Makino, at cell granularity).

    A particle on a long step sitting next to one on a very short step is
    the classic block-time-step failure mode: a shock arrives and dumps an
    enormous acceleration into a particle that then coasts on it for its
    whole long step. SWIFT limits neighbouring time bins to differ by at
    most ``delta``; here the constraint is applied per cell pair — every
    particle's bin is floored at (deepest bin among its own and neighbouring
    cells) − delta — and iterated to the fixpoint so the constraint
    propagates outwards from deep-bin regions.
    """
    mask = np.asarray(mask) > 0
    bins = np.asarray(bins)
    deep = np.where(mask, bins, -10 ** 6).max(axis=1)
    ci = np.asarray(ci)
    cj = np.asarray(cj)
    for _ in range(max_iter):
        nb = deep.copy()
        np.maximum.at(nb, ci, deep[cj])
        np.maximum.at(nb, cj, deep[ci])
        new_deep = np.maximum(deep, nb - delta)
        if (new_deep == deep).all():
            break
        deep = new_deep
    nb = deep.copy()
    np.maximum.at(nb, ci, deep[cj])
    np.maximum.at(nb, cj, deep[ci])
    floor = np.clip(nb - delta, 0, max_bin)
    out = np.maximum(bins, floor[:, None])
    return np.where(mask, out, bins).astype(np.int32)


# -------------------------------------------------------------------- state
# the state layout, as field-name tuples: the single source of truth for
# every code path that scatters/gathers/stacks TimeBinState field-by-field
# (dist_timebins' resident buffers, collectives' fused-program outputs)
STATE_CELL_FIELDS = ("pos", "vel", "mass", "u", "h", "mask")
STATE_AUX_FIELDS = ("accel", "dudt", "rho", "omega", "bins", "t_start")


class TimeBinState(NamedTuple):
    """Multi-dt engine state: the global-dt state plus per-particle bins and
    the stored thermodynamics inactive particles expose to their active
    neighbours (rho, omega at their last active update). ``t_start`` is
    each particle's current step-start time: closing kicks are computed as
    (t − t_start) − dt_bin/2, which stays consistent even when a particle
    is *woken* mid-step by the neighbour limiter and restarts off the
    global bin alignment."""
    cells: ParticleCells
    accel: jax.Array       # (ncells, C, 3)
    dudt: jax.Array        # (ncells, C)
    rho: jax.Array         # (ncells, C)
    omega: jax.Array       # (ncells, C)
    bins: jax.Array        # (ncells, C) int32
    t_start: jax.Array     # (ncells, C)
    time: jax.Array        # scalar


# ------------------------------------------------------------- jitted steps
def _active_accelerations(cells: ParticleCells, pairs: PairList, pair_mask,
                          active, rho_prev, omega_prev, cfg: SPHConfig):
    """density → ghost → force over a level-restricted pair list.

    The pair list covers every pair touching an active cell, so *active*
    particles receive complete sums; inactive particles in those cells get
    partial sums which are discarded in favour of their stored rho/omega
    (their pressure and sound speed are re-derived from stored rho and
    current u — the position-only prediction of 1807.01341).
    """
    mask = cells.mask
    rho_new, drho_dh, nngb = _density_pass(cells, pairs, cfg,
                                           pair_mask=pair_mask)
    rho_new = jnp.where(mask > 0, rho_new, 1.0)
    drho_dh = jnp.where(mask > 0, drho_dh, 0.0)
    rho = jnp.where(active > 0, rho_new, rho_prev)
    press, omega_new, cs = ghost_update(rho, drho_dh, cells.u, cells.h,
                                        gamma=cfg.gamma)
    omega = jnp.where(active > 0, omega_new, omega_prev)
    press = jnp.where(mask > 0, press, 0.0)
    dv, du = _force_pass(cells, pairs, rho, press, omega, cs, cfg,
                         pair_mask=pair_mask)
    mask3 = mask[..., None]
    return dv * mask3, du * mask, rho, omega


def timebin_init(cells: ParticleCells, pairs: PairList,
                 cfg: SPHConfig) -> TimeBinState:
    """Full (every-particle) force evaluation → synchronised initial state."""
    ones = cells.mask
    dv, du, rho, omega = _active_accelerations(
        cells, pairs, None, ones, jnp.ones_like(cells.u),
        jnp.ones_like(cells.u), cfg)
    return TimeBinState(cells=cells, accel=dv, dudt=du, rho=rho, omega=omega,
                        bins=jnp.zeros(cells.mass.shape, jnp.int32),
                        t_start=jnp.zeros(cells.mass.shape, cells.pos.dtype),
                        time=jnp.zeros((), cells.pos.dtype))


def _kick(cells: ParticleCells, accel, dudt, active, half_dt
          ) -> ParticleCells:
    """Half-kick of the active particles (their own bin's dt)."""
    active3 = active[..., None]
    v = cells.vel + half_dt[..., None] * accel * active3
    u = jnp.where(active > 0,
                  jnp.maximum(cells.u + half_dt * dudt, _U_FLOOR), cells.u)
    return cells._replace(vel=v, u=u)


def _cycle_start(state: TimeBinState, dt_max, *, cfg: SPHConfig
                 ) -> TimeBinState:
    """Opening half-kick: every bin starts its first step at n = 0."""
    active = state.cells.mask
    half_dt = 0.5 * bin_timestep(dt_max, state.bins)
    cells = _kick(state.cells, state.accel, state.dudt, active, half_dt)
    t_start = jnp.full_like(state.t_start, state.time)
    return state._replace(cells=cells, t_start=t_start)


def _drift(state: TimeBinState, dt_min, *, box) -> TimeBinState:
    """Drift *all* particles: position-only prediction for inactive ones."""
    cells = state.cells
    pos = wrap_positions(
        cells.pos + dt_min * cells.vel * cells.mask[..., None], box)
    return state._replace(cells=cells._replace(pos=pos),
                          time=state.time + dt_min)


def _substep_density_phase(state: TimeBinState, pairs: PairList, pair_mask,
                           active, *, cfg: SPHConfig, hits: bool = False):
    """Density half of a bin-boundary update (the paper's first comm phase).

    Computes fresh rho/omega for the ``active`` particles (stored values are
    kept elsewhere) and derives press/cs for *every* particle — inactive
    neighbours expose their stored rho through the equation of state. The
    distributed engine inserts the rho/press halo exchange between this
    phase and :func:`_substep_force_phase`; the single-host engine composes
    them back-to-back inside one jitted program.

    With ``hits`` a fifth value is returned: the particle–neighbour entries
    with W(r, h_i) > 0 that the pass summed into the real particles' sums
    (int32, ``engine._density_pass``).
    """
    cells = state.cells
    mask = cells.mask
    sums = _density_pass(cells, pairs, cfg, pair_mask=pair_mask, hits=hits)
    rho_new, drho_dh = sums[0], sums[1]
    rho_new = jnp.where(mask > 0, rho_new, 1.0)
    drho_dh = jnp.where(mask > 0, drho_dh, 0.0)
    rho = jnp.where(active > 0, rho_new, state.rho)
    press, omega_new, cs = ghost_update(rho, drho_dh, cells.u, cells.h,
                                        gamma=cfg.gamma)
    omega = jnp.where(active > 0, omega_new, state.omega)
    press = jnp.where(mask > 0, press, 0.0)
    if hits:
        return rho, omega, press, cs, sums[3]
    return rho, omega, press, cs


def _apply_force_kick(state: TimeBinState, active, dv, du, rho, omega,
                      wake_floor, dt_max, depth, u_floor, *, cfg: SPHConfig
                      ) -> Tuple[TimeBinState, jax.Array]:
    """Close/deepen/re-open the active bins given raw force-pass sums.

    The elementwise tail of a bin-boundary update, split from the pair pass
    so the distributed fused programs can compute the pair sums with the
    halo exchange interleaved (``sph/collectives.py``) and still share this
    exact update; :func:`_substep_force_phase` composes the two unchanged.
    """
    cells = state.cells
    mask = cells.mask
    mask3 = mask[..., None]
    dv, du = dv * mask3, du * mask
    accel = jnp.where(active[..., None] > 0, dv, state.accel)
    dudt = jnp.where(active > 0, du, state.dudt)
    # close the ending step: v is at t_start + dt_bin/2, bring it to `t`
    elapsed = state.time - state.t_start
    close = elapsed - 0.5 * bin_timestep(dt_max, state.bins)
    cells = _kick(cells, accel, dudt, active, close)
    # deepen where the new CFL/heating step (or the wake floor) demands it
    dt_need = particle_timesteps(cells, dudt, gamma=cfg.gamma, cfl=cfg.cfl,
                                 u_floor=u_floor)
    b_need = jnp.maximum(assign_bins(dt_need, dt_max, depth),
                         jnp.clip(wake_floor, 0, depth)[:, None])
    bins = jnp.where(active > 0, jnp.maximum(state.bins, b_need), state.bins)
    # open the next step
    half_new = 0.5 * bin_timestep(dt_max, bins)
    cells = _kick(cells, accel, dudt, active, half_new)
    t_start = jnp.where(active > 0, state.time, state.t_start)
    nact = jnp.sum(active).astype(jnp.int32)
    return state._replace(cells=cells, accel=accel, dudt=dudt, rho=rho,
                          omega=omega, bins=bins, t_start=t_start), nact


def _substep_force_phase(state: TimeBinState, pairs: PairList, pair_mask,
                         active, rho, omega, press, cs, wake_floor, dt_max,
                         depth, u_floor, *, cfg: SPHConfig
                         ) -> Tuple[TimeBinState, jax.Array]:
    """Force + kick half of a bin-boundary update (second comm phase)."""
    dv, du = _force_pass(state.cells, pairs, rho, press, omega, cs, cfg,
                         pair_mask=pair_mask)
    return _apply_force_kick(state, active, dv, du, rho, omega, wake_floor,
                             dt_max, depth, u_floor, cfg=cfg)


def substep_active_mask(state: TimeBinState, level, wake_floor) -> jax.Array:
    """Particles ending a step now: regular bin boundary (bins ≥ level) or
    woken by the neighbour limiter (their cell's wake_floor — deepest
    neighbourhood bin − delta — now exceeds their bin: a shock has arrived
    and coasting to the end of their long step would be unstable)."""
    at_boundary = state.bins >= level
    woken = state.bins < wake_floor[:, None]
    return ((at_boundary | woken)
            & (state.cells.mask > 0)).astype(state.cells.pos.dtype)


def _force_substep(state: TimeBinState, pairs: PairList, pair_mask, level,
                   wake_floor, dt_max, depth, u_floor, *, cfg: SPHConfig
                   ) -> Tuple[TimeBinState, jax.Array]:
    """Bin-boundary update at an interior sub-step.

    Two particle sets end a step here: bins ≥ level (their regular
    boundary) and particles *woken* by the neighbour limiter (see
    :func:`substep_active_mask`). Both are closed with a kick of
    (t − t_start) − dt_bin/2, which equals the regular half-kick for
    aligned particles and un-kicks the woken ones back to the current
    time. The closing particles may then *deepen* (their own new CFL /
    heating step, or the wake floor), and immediately open the next step
    with a first half-kick. Shallower bins wait for the cycle end.

    Composition of the density and force phases; the distributed time-bin
    engine runs the same two phases with an activity-aware halo exchange
    in between (``sph/dist_timebins.py``).
    """
    active = substep_active_mask(state, level, wake_floor)
    rho, omega, press, cs = _substep_density_phase(
        state, pairs, pair_mask, active, cfg=cfg)
    return _substep_force_phase(state, pairs, pair_mask, active, rho, omega,
                                press, cs, wake_floor, dt_max, depth,
                                u_floor, cfg=cfg)


def _apply_final_kick(state: TimeBinState, dv, du, rho, omega, dt_max,
                      *, cfg: SPHConfig) -> TimeBinState:
    """Closing kick of the cycle-ending boundary, given raw force sums."""
    cells = state.cells
    active = cells.mask
    mask3 = cells.mask[..., None]
    dv, du = dv * mask3, du * cells.mask
    elapsed = state.time - state.t_start
    close = elapsed - 0.5 * bin_timestep(dt_max, state.bins)
    cells = _kick(cells, dv, du, active, close)
    return state._replace(cells=cells, accel=dv, dudt=du, rho=rho,
                          omega=omega,
                          t_start=jnp.broadcast_to(state.time,
                                                   state.t_start.shape))


def _final_force_phase(state: TimeBinState, pairs: PairList, pair_mask,
                       rho, omega, press, cs, dt_max, *, cfg: SPHConfig
                       ) -> TimeBinState:
    """Force + closing kick of the cycle-ending boundary."""
    dv, du = _force_pass(state.cells, pairs, rho, press, omega, cs, cfg,
                         pair_mask=pair_mask)
    return _apply_final_kick(state, dv, du, rho, omega, dt_max, cfg=cfg)


def _force_final(state: TimeBinState, pairs: PairList, pair_mask, dt_max,
                 *, cfg: SPHConfig) -> TimeBinState:
    """Cycle-closing boundary: every bin ends; no step is opened."""
    active = state.cells.mask
    rho, omega, press, cs = _substep_density_phase(
        state, pairs, pair_mask, active, cfg=cfg)
    return _final_force_phase(state, pairs, pair_mask, rho, omega, press,
                              cs, dt_max, cfg=cfg)


@functools.lru_cache(maxsize=None)
def shared_timebin_programs(box, cfg: SPHConfig) -> Dict[str, object]:
    """The five jitted ladder programs per (box, physics config), shared by
    every :class:`TimeBinSimulation` instance (same rationale as
    ``engine.shared_step_program``: a fleet of same-signature requests must
    compile the ladder once, not once per request)."""
    return {
        "init": jax.jit(functools.partial(timebin_init, cfg=cfg)),
        "start": jax.jit(functools.partial(_cycle_start, cfg=cfg)),
        "drift": jax.jit(functools.partial(_drift, box=box)),
        "sub": jax.jit(functools.partial(_force_substep, cfg=cfg)),
        "final": jax.jit(functools.partial(_force_final, cfg=cfg)),
    }


# ------------------------------------------------------------------- driver
class TimeBinSimulation:
    """Host driver of the sub-step hierarchy (multi-dt ``Simulation``).

    Per cycle: quantise per-particle CFL steps into bins, pick
    depth = deepest occupied bin (bounded by ``max_depth``), run the KDK
    ladder over 2**depth sub-steps activating only due bins, then
    re-synchronise, re-bin particles into cells and re-assign bins. The
    level-restricted pair lists (all pairs touching an active cell) are
    padded to power-of-two lengths so jit programs are reused across
    sub-steps and cycles.
    """

    tracer = NULL_TRACER        # rebound when observe=True

    def __init__(self, pos, vel, mass, u, h, *, box,
                 cfg: SPHConfig = SPHConfig(),
                 dt_max: Optional[float] = None,
                 max_depth: int = MAX_DEPTH_DEFAULT,
                 bin_delta: int = 2,
                 depth_headroom: int = 2,
                 capacity_margin: float = 3.0,
                 rebin_each_cycle: bool = True):
        if type(self) is TimeBinSimulation:
            import warnings
            warnings.warn(
                "constructing repro.sph.TimeBinSimulation directly is "
                "deprecated; use repro.sph.build_simulation("
                "SimulationSpec(...)) (integrator='timebin', "
                "backend='local')", DeprecationWarning, stacklevel=2)
        self.box = box_lengths(box)
        self.cfg = cfg
        self.n = len(pos)
        self.dt_max = dt_max
        if int(max_depth) > BIN_LADDER_MAX:
            raise ValueError(
                f"max_depth {max_depth} exceeds the assign_bins comparison "
                f"ladder ({BIN_LADDER_MAX} levels)")
        self.max_depth = int(max_depth)
        self.bin_delta = int(bin_delta)
        self.depth_headroom = int(depth_headroom)
        self.rebin_each_cycle = rebin_each_cycle
        h_max = float(np.max(h))
        self.spec = choose_grid(self.box, h_max, self.n,
                                capacity_margin=capacity_margin)
        # the pair list depends on the grid's dims and box alone, which no
        # rebin changes: it is built once, and this counts the builds
        self.pair_list_builds = 0
        self._pairs_key = None
        self._rebin(np.asarray(pos), np.asarray(vel), np.asarray(mass),
                    np.asarray(u), np.asarray(h))
        progs = shared_timebin_programs(self.box, cfg)
        self._jit_init = progs["init"]
        self._jit_start = progs["start"]
        self._jit_drift = progs["drift"]
        self._jit_sub = progs["sub"]
        self._jit_final = progs["final"]
        # Cycle planning uses the signal-velocity CFL (see _signal_speeds);
        # the κ·u/|du/dt| heating guard applies only in mid-cycle deepening
        # (where it catches a shock front arriving at cold gas) — applying
        # it at planning time pins numerically-noisy cold background onto
        # deep bins and erases the multi-dt advantage.
        self.state = self._jit_init(self.cells, self.pairs)
        # counters for the speed-up accounting
        self.particle_updates = 0       # force evaluations actually received
        self.global_equiv_updates = 0   # what global-dt would have performed
        self.substeps = 0
        self.cycle_index = 0
        # device-metrics carry (single rank): rows built from the host
        # scalars the ladder already pulls (nact, nlive) — no extra sync
        self.device_metrics_enabled = False
        self.device_metrics_last: Optional[Tuple[np.ndarray,
                                                 np.ndarray]] = None
        self.device_metrics_pulls = 0
        # per-cell work attribution of the last cycle (device-metrics v2
        # contract shared with the distributed engines) or None
        self.device_cell_work_last: Optional[Dict] = None

    # ------------------------------------------------------------- plumbing
    def _rebin(self, pos, vel, mass, u, h):
        tr = self.tracer
        with tr.span("rebin.bin_particles"):
            self.cells, self.perm = bin_particles(self.spec, pos, vel, mass,
                                                  u, h)
            if self.cells.mass.shape[1] != self.spec.capacity:
                object.__setattr__(self.spec, "capacity",
                                   self.cells.mass.shape[1])
        with tr.span("rebin.pair_list"):
            # keyed on the grid, not the spec: a rebin grows the spec's
            # capacity in place, so its hash is not stable
            key = (self.spec.dims, self.spec.box)
            if key != self._pairs_key:
                self.pairs = build_pair_list(self.spec)
                self._ci = np.asarray(self.pairs.ci)
                self._cj = np.asarray(self.pairs.cj)
                self._shift = np.asarray(self.pairs.shift)
                self._pairs_key = key
                self.pair_list_builds += 1

    def _flatten_aux(self, arr, fill) -> np.ndarray:
        valid = self.perm >= 0
        idx = self.perm[valid]
        a = np.asarray(arr)
        out = np.full((self.n,) + a.shape[2:], fill, dtype=a.dtype)
        out[idx] = a[valid]
        return out

    def _rebin_state(self):
        """Re-bin particles into cells, carrying the full multi-dt state
        (no extra force pass: accel/rho/omega/bins ride along). Its phases
        are the ``rebin.*`` spans: pull and flatten, bin, pair list,
        upload."""
        tr = self.tracer
        st = self.state
        with tr.span("rebin.unbin"):
            flat = unbin(st.cells, self.perm, self.n)
            aux = {
                "accel": self._flatten_aux(st.accel, 0.0),
                "dudt": self._flatten_aux(st.dudt, 0.0),
                "rho": self._flatten_aux(st.rho, 1.0),
                "omega": self._flatten_aux(st.omega, 1.0),
                "bins": self._flatten_aux(st.bins, 0),
                "t_start": self._flatten_aux(st.t_start, 0.0),
            }
        self._rebin(flat["pos"], flat["vel"], flat["mass"], flat["u"],
                    flat["h"])
        with tr.span("rebin.upload"):
            valid = self.perm >= 0
            idx = self.perm[valid]

            def take(a, fill):
                out = np.full(self.perm.shape + a.shape[1:], fill,
                              dtype=a.dtype)
                out[valid] = a[idx]
                return out

            self.state = TimeBinState(
                cells=self.cells,
                accel=jnp.asarray(take(aux["accel"], 0.0)),
                dudt=jnp.asarray(take(aux["dudt"], 0.0)),
                rho=jnp.asarray(take(aux["rho"], 1.0)),
                omega=jnp.asarray(take(aux["omega"], 1.0)),
                bins=jnp.asarray(take(aux["bins"], 0)),
                t_start=jnp.asarray(take(aux["t_start"], 0.0)),
                time=st.time)

    def _pair_subset(self, active_cells: np.ndarray
                     ) -> Tuple[PairList, jax.Array, int]:
        """Pairs touching an active cell, padded to a power-of-two length."""
        sel = active_cells[self._ci] | active_cells[self._cj]
        idx = np.nonzero(sel)[0]
        nlive = len(idx)
        npad = 1
        while npad < max(nlive, 1):
            npad *= 2
        pad = np.zeros(npad - nlive, dtype=idx.dtype)
        idxp = np.concatenate([idx, pad])
        pmask = np.zeros(npad, np.float32)
        pmask[:nlive] = 1.0
        sub = PairList(ci=jnp.asarray(self._ci[idxp]),
                       cj=jnp.asarray(self._cj[idxp]),
                       shift=jnp.asarray(self._shift[idxp]))
        return sub, jnp.asarray(pmask), nlive

    def _wake_floor(self, bins_h: np.ndarray, mask_host: np.ndarray
                    ) -> np.ndarray:
        """Per-cell wake threshold: deepest bin in the 27-stencil − delta."""
        deep = np.where(mask_host > 0, bins_h, -10 ** 6).max(axis=1)
        nb = deep.copy()
        np.maximum.at(nb, self._ci, deep[self._cj])
        np.maximum.at(nb, self._cj, deep[self._ci])
        return np.maximum(nb - self.bin_delta, 0).astype(np.int32)

    # -------------------------------------------------------------- cycling
    def _signal_speeds(self, cells) -> np.ndarray:
        """Neighbourhood-max signal speed per cell (SWIFT's v_sig CFL).

        A cold particle at a hot interface has its force history driven by
        the *neighbour's* sound crossing, not its own — its dt must see
        max_j(c_j + |v_j|) over the interaction stencil, or the two sides
        of every interface pair integrate the shared force with mismatched
        quadratures and momentum leaks. Far from any contrast the stencil
        max equals the local value and long steps survive.
        """
        from .physics import sound_speed
        v = np.asarray(speed_norm(np.asarray(cells.vel)))
        cs = np.asarray(sound_speed(jnp.ones_like(cells.u), cells.u,
                                    self.cfg.gamma))
        speed = np.where(np.asarray(cells.mask) > 0, cs + v, 0.0)
        s_cell = speed.max(axis=1)
        s_nb = s_cell.copy()
        np.maximum.at(s_nb, self._ci, s_cell[self._cj])
        np.maximum.at(s_nb, self._cj, s_cell[self._ci])
        return s_nb

    def _plan_cycle(self) -> Tuple[float, int]:
        """Assign bins from the signal-velocity CFL field; returns
        (dt_max_cycle, depth)."""
        cells = self.state.cells
        s_nb = self._signal_speeds(cells)
        h = np.asarray(cells.h)
        dts = self.cfg.cfl * h / np.maximum(s_nb[:, None], 1e-12)
        mask = np.asarray(cells.mask) > 0
        dts = np.where(mask, dts, np.inf)
        live = dts[mask]
        dt_min_req = float(live.min())
        dt_max_c = self.dt_max if self.dt_max is not None else float(
            live.max())
        # never let the ladder exceed max_depth: shorten the cycle instead
        # of clamping fast particles onto too-long steps. The min is taken
        # in f32 so a device-computed plan (which has no f64 scalars) lands
        # on the same dt_max_c bit pattern.
        dt_max_c = float(min(np.float32(dt_max_c),
                             np.float32(dt_min_req)
                             * np.float32(2.0 ** self.max_depth)))
        bins = assign_bins(dts, dt_max_c, self.max_depth)
        bins = np.where(mask, bins, 0).astype(np.int32)
        bins = limit_neighbour_bins(bins, mask, self._ci, self._cj,
                                    delta=self.bin_delta,
                                    max_bin=self.max_depth)
        bins = np.where(mask, bins, 0).astype(np.int32)
        occupied = int(bins[mask].max()) if mask.any() else 0
        # headroom below the occupied bins: mid-cycle deepening (a shock
        # collapsing some particle's dt) has somewhere to go; empty finest
        # levels cost nothing thanks to lazy drift accumulation
        depth = min(occupied + self.depth_headroom, self.max_depth)
        self.state = self.state._replace(bins=jnp.asarray(bins))
        return dt_max_c, depth

    def run_cycle(self) -> Dict[str, float]:
        """One dt_max cycle of the KDK ladder; returns cycle stats."""
        tr = self.tracer
        if tr.enabled:
            tr.ctx["cycle"] = self.cycle_index
            tr.ctx.pop("substep", None)
        with tr.timed("cycle") as cyc:
            stats = self._run_cycle_body(tr)
        if tr.enabled:
            tr.ctx.pop("substep", None)
        self.cycle_index += 1
        stats["wall"] = cyc.elapsed
        return stats

    def _run_cycle_body(self, tr) -> Dict[str, float]:
        with tr.span("plan"):
            dt_max_c, depth = self._plan_cycle()
        nsub = 1 << depth
        dt_min = dt_max_c / nsub
        nreal = int(np.asarray(self.state.cells.mask).sum())
        bins_host = np.asarray(self.state.bins)
        mask_host = np.asarray(self.state.cells.mask)
        m_h = np.asarray(self.state.cells.mass * self.state.cells.mask)
        u_floor = float(mass_weighted_mean_u(
            m_h, np.asarray(self.state.cells.u)))
        hist = np.bincount(bins_host[mask_host > 0],
                           minlength=depth + 1)

        with tr.span("start", units=nreal):
            state = self._jit_start(self.state, jnp.float32(dt_max_c))
            if tr.enabled:
                tr.fence(state.cells.pos)
        updates = 0
        pair_tasks = 0
        force_substeps = 0
        drifted_to = 0          # sub-steps of drift applied so far
        # host caches — bins only change at force sub-steps (deepening)
        bins_h = np.asarray(state.bins)
        wake_floor = self._wake_floor(bins_h, mask_host)
        dm_on = self.device_metrics_enabled
        met_counts, met_values = dmetrics.zero_rows(1)
        mVI = dmetrics.VALUE_INDEX
        cellw = cellw_rank = None
        if dm_on:
            # per-cell attribution: single-rank flavour of the distributed
            # owned-endpoint rule — every pair charges its ci cell, drift
            # is the alive count per cell, exchange is zero (no halo)
            cellw, cellw_rank = dmetrics.zero_cell_work(self.spec.ncells, 1)
            cDI = dmetrics.CELL_INDEX
            alive_cell = (mask_host > 0).sum(axis=1).astype(np.float64)

            def attribute_cells(pair_idx):
                np.add.at(cellw[:, cDI["density"]], self._ci[pair_idx], 1.0)
                np.add.at(cellw[:, cDI["force"]], self._ci[pair_idx], 1.0)
                cellw[:, cDI["drift"]] += alive_cell
                cellw_rank[0, cDI["density"]] += len(pair_idx)
                cellw_rank[0, cDI["force"]] += len(pair_idx)
                cellw_rank[0, cDI["drift"]] += nreal
        for n in range(1, nsub):
            level = active_level(n, depth)
            active_p = ((bins_h >= level)
                        | (bins_h < wake_floor[:, None])) & (mask_host > 0)
            if not active_p.any():
                continue            # headroom level with nothing due
            if tr.enabled:
                tr.ctx["substep"] = n
            # lazily apply the accumulated drift up to time t0 + n·dt_min
            with tr.span("drift", units=nreal):
                state = self._jit_drift(
                    state, jnp.float32((n - drifted_to) * dt_min))
                if tr.enabled:
                    tr.fence(state.cells.pos)
            drifted_to = n
            sub, pmask, nlive = self._pair_subset(active_p.any(axis=1))
            sub_attrs = {}
            if tr.enabled:
                sub_attrs = dict(level=level, units=nlive, pairs=nlive,
                                 active_frac=float(active_p.sum())
                                 / max(nreal, 1))
            with tr.span("substep", **sub_attrs):
                state, nact = self._jit_sub(state, sub, pmask,
                                            jnp.int32(level),
                                            jnp.asarray(wake_floor),
                                            jnp.float32(dt_max_c),
                                            jnp.int32(depth),
                                            jnp.float32(u_floor))
                if tr.enabled:
                    tr.fence(state.cells.pos)
            updates += int(nact)
            pair_tasks += nlive
            force_substeps += 1
            # bins only change at force sub-steps (deepening / wake-up):
            # recompute the wake floors only when they actually did
            bins_new = np.asarray(state.bins)
            deepened = 0
            if not np.array_equal(bins_new, bins_h):
                deepened = int((bins_new != bins_h).sum())
                bins_h = bins_new
                wake_floor = self._wake_floor(bins_h, mask_host)
            if dm_on:
                met_counts[0] += dmetrics.host_row(
                    substeps=1, drift_active=nreal,
                    density_active=int(nact), force_active=int(nact),
                    pair_int=nlive, deepen_events=deepened,
                    wake_events=int(((bins_h < wake_floor[:, None])
                                     & (mask_host > 0)).sum()))[0]
                met_values[0, mVI["density_units"]] += nlive
                met_values[0, mVI["force_units"]] += nlive
                met_values[0, mVI["kick_units"]] += int(nact)
                acells = active_p.any(axis=1)
                attribute_cells(np.nonzero(acells[self._ci]
                                           | acells[self._cj])[0])
        if tr.enabled:
            tr.ctx["substep"] = nsub
        with tr.span("drift", units=nreal):
            state = self._jit_drift(
                state, jnp.float32((nsub - drifted_to) * dt_min))
            if tr.enabled:
                tr.fence(state.cells.pos)
        with tr.span("final", units=len(self._ci), pairs=len(self._ci),
                     active_frac=1.0):
            state = self._jit_final(state, self.pairs,
                                    jnp.ones(len(self._ci), jnp.float32),
                                    jnp.float32(dt_max_c))
            jax.block_until_ready(state.cells.pos)
        updates += nreal
        pair_tasks += len(self._ci)
        if dm_on:
            met_counts[0] += dmetrics.host_row(
                substeps=1, drift_active=nreal, density_active=nreal,
                force_active=nreal, pair_int=len(self._ci))[0]
            met_values[0, mVI["density_units"]] += len(self._ci)
            met_values[0, mVI["force_units"]] += len(self._ci)
            met_values[0, mVI["kick_units"]] += nreal
            attribute_cells(np.arange(len(self._ci)))
            c = state.cells
            dmetrics.state_health(np.asarray(c.mask), np.asarray(c.vel),
                                  np.asarray(c.u), np.asarray(state.rho),
                                  np.asarray(c.mass), met_counts,
                                  met_values, rank=0)
            self.device_metrics_last = (met_counts, met_values)
            self.device_metrics_pulls += 1
            self.device_cell_work_last = {
                "columns": list(dmetrics.CELL_COLUMNS),
                "cells": cellw, "per_rank": cellw_rank}
        else:
            self.device_metrics_last = None
            self.device_cell_work_last = None
        self.state = state
        if self.rebin_each_cycle:
            with tr.span("rebin", units=nreal):
                self._rebin_state()
        self.particle_updates += updates
        self.global_equiv_updates += nsub * nreal
        self.substeps += nsub
        return {
            "t": float(self.state.time),
            "dt_max": dt_max_c,
            "depth": depth,
            "substeps": nsub,
            "force_substeps": force_substeps + 1,   # interior + final
            "bin_hist": hist,
            "updates": updates,
            "global_equiv_updates": nsub * nreal,
            "pair_tasks": pair_tasks,
            "global_equiv_pair_tasks": nsub * len(self._ci),
        }

    def run(self, ncycles: int) -> Dict[str, list]:
        log: Dict[str, list] = {"t": [], "wall": [], "E": [], "px": [],
                                "depth": [], "updates": []}
        for _ in range(ncycles):
            stats = self.run_cycle()
            e, p = self.diagnostics()
            log["t"].append(stats["t"])
            log["wall"].append(stats["wall"])
            log["E"].append(e)
            log["px"].append(p[0])
            log["depth"].append(stats["depth"])
            log["updates"].append(stats["updates"])
        return log

    def diagnostics(self) -> Tuple[float, np.ndarray]:
        """(total energy, total momentum) over real particles."""
        c = self.state.cells
        m = np.asarray(c.mass * c.mask)
        v = np.asarray(c.vel)
        u = np.asarray(c.u)
        ke = 0.5 * np.sum(m * np.sum(v * v, axis=-1))
        ie = np.sum(m * u)
        mom = np.sum(m[..., None] * v, axis=(0, 1))
        return float(ke + ie), mom
