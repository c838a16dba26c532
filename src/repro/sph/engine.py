"""Task-based SPH engine (single host): the paper's Fig. 1 pipeline in JAX.

The computation is modelled as a :class:`~repro.core.TaskGraph` — sort /
density / ghost / force / kick tasks over cells and cell pairs with the
paper's dependency structure — and *compiled* into a static wave program
(DESIGN.md §2 C1): each wave lowers to one batched op over every task of the
wave's kind. The numerical payloads are ``physics.density_block`` /
``physics.force_block`` vmapped over the cell-pair list, or the Pallas TPU
kernels in ``repro.kernels.sph_pair`` when ``use_pallas=True``.

Host-side re-binning between jitted steps plays the role of SWIFT's particle
exchange ("particles were exchanged whenever they strayed too far beyond
their cells").
"""

from __future__ import annotations

import functools
import warnings
from dataclasses import dataclass
from typing import Dict, NamedTuple, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from ..core import CostModel, TaskGraph
from ..observability.tracer import NULL_TRACER
from .cellgrid import GridSpec, PairList, ParticleCells, bin_particles, \
    box_lengths, build_pair_list, choose_grid, unbin, wrap_positions
from .physics import GAMMA, DensityResult, ForceResult, cfl_timestep_block, \
    density_block, force_block, ghost_update, smoothing_length_update
from .smoothing import get_kernel


class SPHState(NamedTuple):
    cells: ParticleCells
    accel: jax.Array       # (ncells, C, 3)
    dudt: jax.Array        # (ncells, C)
    rho: jax.Array         # (ncells, C)
    time: jax.Array        # scalar


@dataclass(frozen=True)
class SPHConfig:
    kernel: str = "cubic"
    alpha_visc: float = 0.8
    gamma: float = GAMMA
    n_target: float = 48.0
    adapt_h: bool = False          # keep h fixed unless asked (conservation tests)
    cfl: float = 0.25
    use_pallas: bool = False
    pallas_interpret: bool = False  # run the Pallas kernels interpreted (CPU)


# --------------------------------------------------------------- wave passes
def _density_pass(cells: ParticleCells, pairs: PairList, cfg: SPHConfig,
                  pair_mask: Optional[jax.Array] = None, *,
                  hits: bool = False):
    """All density_self/density_pair tasks as two batched ops.

    ``pair_mask`` (npairs,) zeroes the contributions of masked pair tasks —
    used by the time-bin engine, which pads level-restricted pair lists to
    fixed power-of-two lengths so sub-steps reuse compiled programs.

    With ``hits`` a fourth value is returned: the particle–neighbour entries
    with W(r, h_i) > 0 summed into real particles' densities (int32),
    reduced straight from the pair blocks' neighbour counts, beside the
    sums and without a scatter of its own.
    """
    if cfg.use_pallas:
        from ..kernels.sph_pair import ops as pair_ops
        out = pair_ops.density_pairs(cells, pairs, kernel=cfg.kernel,
                                     interpret=cfg.pallas_interpret,
                                     pair_mask=pair_mask)
        if hits:
            nngb = jnp.where(cells.mask > 0, out[2], 0.0)
            return (*out, jnp.sum(nngb.astype(jnp.int32)))
        return out

    pos_i = cells.pos[pairs.ci]                        # (P, C, 3)
    pos_j = cells.pos[pairs.cj] + pairs.shift[:, None, :]
    h_i, h_j = cells.h[pairs.ci], cells.h[pairs.cj]
    m_i, m_j = cells.mass[pairs.ci], cells.mass[pairs.cj]
    k_i, k_j = cells.mask[pairs.ci], cells.mask[pairs.cj]

    dens = functools.partial(density_block, kernel=cfg.kernel)
    dij = jax.vmap(dens)(pos_i, h_i, pos_j, m_j, k_j)      # i <- j
    dji = jax.vmap(dens)(pos_j, h_j, pos_i, m_i, k_i)      # j <- i

    ncells, cap = cells.mass.shape
    notself = (pairs.ci != pairs.cj).astype(cells.pos.dtype)[:, None]
    live = jnp.ones_like(notself) if pair_mask is None else pair_mask[:, None]

    def scatter(field_ij, field_ji):
        out = jnp.zeros((ncells, cap), cells.pos.dtype)
        out = out.at[pairs.ci].add(field_ij * live)
        out = out.at[pairs.cj].add(field_ji * notself * live)
        return out

    rho = scatter(dij.rho, dji.rho)
    drho_dh = scatter(dij.drho_dh, dji.drho_dh)
    nngb = scatter(dij.nngb, dji.nngb)
    if hits:
        count = lambda n, k, w: jnp.sum(  # noqa: E731
            jnp.sum(n * k * w, axis=-1).astype(jnp.int32))
        return rho, drho_dh, nngb, (count(dij.nngb, k_i, live)
                                    + count(dji.nngb, k_j, notself * live))
    return rho, drho_dh, nngb


def _force_pass(cells: ParticleCells, pairs: PairList, rho, press, omega, cs,
                cfg: SPHConfig, pair_mask: Optional[jax.Array] = None):
    """All force_self/force_pair tasks as two batched ops."""
    if cfg.use_pallas:
        from ..kernels.sph_pair import ops as pair_ops
        return pair_ops.force_pairs(cells, pairs, rho, press, omega, cs,
                                    kernel=cfg.kernel,
                                    alpha_visc=cfg.alpha_visc,
                                    interpret=cfg.pallas_interpret,
                                    pair_mask=pair_mask)

    gi = lambda a: a[pairs.ci]
    gj = lambda a: a[pairs.cj]
    pos_i, pos_j = gi(cells.pos), gj(cells.pos) + pairs.shift[:, None, :]

    force = functools.partial(force_block, kernel=cfg.kernel,
                              alpha_visc=cfg.alpha_visc)
    fij = jax.vmap(force)(
        pos_i, gi(cells.vel), gi(cells.h), gi(press), gi(rho), gi(omega),
        gi(cs),
        pos_j, gj(cells.vel), gj(cells.h), gj(press), gj(rho), gj(omega),
        gj(cs), gj(cells.mass), gj(cells.mask))
    fji = jax.vmap(force)(
        pos_j, gj(cells.vel), gj(cells.h), gj(press), gj(rho), gj(omega),
        gj(cs),
        pos_i, gi(cells.vel), gi(cells.h), gi(press), gi(rho), gi(omega),
        gi(cs), gi(cells.mass), gi(cells.mask))

    ncells, cap = cells.mass.shape
    notself = (pairs.ci != pairs.cj).astype(cells.pos.dtype)
    live = jnp.ones_like(notself) if pair_mask is None else pair_mask

    dv = jnp.zeros((ncells, cap, 3), cells.pos.dtype)
    dv = dv.at[pairs.ci].add(fij.dv * live[:, None, None])
    dv = dv.at[pairs.cj].add(fji.dv * (notself * live)[:, None, None])
    du = jnp.zeros((ncells, cap), cells.pos.dtype)
    du = du.at[pairs.ci].add(fij.du * live[:, None])
    du = du.at[pairs.cj].add(fji.du * (notself * live)[:, None])
    return dv, du


def compute_accelerations(cells: ParticleCells, pairs: PairList,
                          cfg: SPHConfig):
    """density → ghost → force (the Fig. 1 dependency chain)."""
    rho, drho_dh, nngb = _density_pass(cells, pairs, cfg)
    # padded slots: keep safe values so downstream divisions stay finite
    rho = jnp.where(cells.mask > 0, rho, 1.0)
    drho_dh = jnp.where(cells.mask > 0, drho_dh, 0.0)
    press, omega, cs = ghost_update(rho, drho_dh, cells.u, cells.h,
                                    gamma=cfg.gamma)
    press = jnp.where(cells.mask > 0, press, 0.0)
    dv, du = _force_pass(cells, pairs, rho, press, omega, cs, cfg)
    mask3 = cells.mask[..., None]
    return dv * mask3, du * cells.mask, rho, nngb


def init_state(cells: ParticleCells, pairs: PairList,
               cfg: SPHConfig) -> SPHState:
    dv, du, rho, _ = compute_accelerations(cells, pairs, cfg)
    return SPHState(cells=cells, accel=dv, dudt=du, rho=rho,
                    time=jnp.zeros((), cells.pos.dtype))


def step(state: SPHState, pairs: PairList, dt, box,
         cfg: SPHConfig) -> SPHState:
    """One KDK leapfrog step (kick and drift are SWIFT's integrator tasks)."""
    cells = state.cells
    mask3 = cells.mask[..., None]
    # K: half kick with stored accelerations
    v_half = cells.vel + 0.5 * dt * state.accel
    u_half = jnp.maximum(cells.u + 0.5 * dt * state.dudt, 1e-12)
    # D: drift
    pos = wrap_positions(cells.pos + dt * v_half * mask3, box)
    cells = cells._replace(pos=pos, vel=v_half, u=u_half)
    # re-evaluate forces at the new positions
    dv, du, rho, nngb = compute_accelerations(cells, pairs, cfg)
    # K: second half kick
    v_new = cells.vel + 0.5 * dt * dv
    u_new = jnp.maximum(u_half + 0.5 * dt * du, 1e-12)
    h_new = cells.h
    if cfg.adapt_h:
        h_new = smoothing_length_update(cells.h, rho, cells.mass, nngb,
                                        n_target=cfg.n_target)
        h_new = jnp.where(cells.mask > 0, h_new, cells.h)
    cells = cells._replace(vel=v_new, u=u_new, h=h_new)
    return SPHState(cells=cells, accel=dv, dudt=du, rho=rho,
                    time=state.time + dt)


def cfl_timestep_particles(state: SPHState, cfg: SPHConfig) -> jax.Array:
    """Per-particle CFL dt (ncells, C); +inf on padded slots.

    The time-bin hierarchy quantises this field into power-of-two bins;
    the global-dt engine takes its minimum.
    """
    cells = state.cells
    return cfl_timestep_block(cells.h, cells.u, cells.vel, cells.mask,
                              gamma=cfg.gamma, cfl=cfg.cfl)


def cfl_timestep(state: SPHState, cfg: SPHConfig) -> jax.Array:
    """dt = C_CFL · min_i h_i / (c_i + |v_i|)."""
    return jnp.min(cfl_timestep_particles(state, cfg))


@functools.lru_cache(maxsize=None)
def shared_step_program(box, cfg: SPHConfig):
    """One jitted step program per (box, physics config), shared by every
    :class:`Simulation` instance. A per-instance ``jax.jit(partial(...))``
    gives each engine its own jit cache, so a fleet of same-signature
    requests would recompile the identical program once per request; the
    memo makes N engines of one signature cost one compile."""
    return jax.jit(functools.partial(step, box=box, cfg=cfg))


# -------------------------------------------------------------- task graph
def build_taskgraph(spec: GridSpec, pairs: PairList,
                    occupancy: np.ndarray,
                    cost_model: Optional[CostModel] = None, *,
                    cell_bins: Optional[np.ndarray] = None,
                    level: Optional[int] = None,
                    occupancy_by_bin: Optional[np.ndarray] = None,
                    time_average: bool = False) -> TaskGraph:
    """SWIFT's Fig. 1 task hierarchy for the current grid.

    Per cell: sort → … → ghost → … → kick; per pair (and per self-cell):
    density and force tasks with the dependencies of eqs. (2)–(4). Costs are
    the cost model's asymptotic estimates over the *actual* occupancies —
    the graph the domain decomposition partitions.

    Time-bin extensions (see ``timebins.py``):

    * ``cell_bins`` (ncells,) — each cell's deepest occupied time bin
      (−1 for empty cells). With ``level`` set, every task gets an
      *activation mask*: a per-cell task is active iff its cell holds a
      particle in a bin ≥ level; a pair task is active iff either cell
      does (an inactive neighbour still contributes to an active cell's
      sums, so the pair must run). ``wave_schedule(..., active_only=True)``
      then compiles a program over only the due work.
    * ``time_average`` with ``occupancy_by_bin`` (ncells, nbins) — task
      costs become cycle-averaged active work (bin b pays on a fraction
      2**(b−d) of sub-steps), so ``decompose_cells`` balances what
      actually runs rather than where particles merely sit.
    """
    cm = cost_model or CostModel(rates={})
    g = TaskGraph()
    nc = spec.ncells
    occ = np.asarray(occupancy, dtype=np.int64)
    if time_average and occupancy_by_bin is None:
        raise ValueError("time_average=True requires occupancy_by_bin")
    bins_arr = None
    if cell_bins is not None:
        bins_arr = np.asarray(cell_bins, dtype=np.int64)
    obb = None
    max_bin = 0
    if occupancy_by_bin is not None:
        obb = np.asarray(occupancy_by_bin, dtype=np.int64)
        max_bin = obb.shape[1] - 1
    elif bins_arr is not None:
        max_bin = int(bins_arr.max()) if bins_arr.size else 0

    def cell_active(c: int) -> bool:
        if bins_arr is None or level is None:
            return True
        return bool(bins_arr[c] >= level)

    def cell_cost(kind: str, c: int) -> float:
        if time_average:
            return cm.timebin_units(kind, obb[c], max_bin=max_bin)
        return cm.units(kind, max(int(occ[c]), 1))

    def inter_cost(kind: str, a: int, b: Optional[int] = None) -> float:
        if time_average:
            return cm.timebin_units(kind, obb[a],
                                    obb[b] if b is not None else None,
                                    max_bin=max_bin)
        if b is None:
            return cm.units(kind, int(occ[a]))
        return cm.units(kind, int(occ[a]), int(occ[b]))

    sort = [g.add_task("sort", resources=(c,), writes=(c,),
                       cost=cell_cost("sort", c), active=cell_active(c))
            for c in range(nc)]
    ghost = [g.add_task("ghost", resources=(c,), writes=(c,),
                        cost=cell_cost("ghost", c), active=cell_active(c))
             for c in range(nc)]
    kick = [g.add_task("kick", resources=(c,), writes=(c,),
                       cost=cell_cost("kick", c), active=cell_active(c))
            for c in range(nc)]
    ci = np.asarray(pairs.ci)
    cj = np.asarray(pairs.cj)
    for a, b in zip(ci, cj):
        a, b = int(a), int(b)
        if a == b:
            act = cell_active(a)
            d = g.add_task("density_self", resources=(a,), writes=(a,),
                           cost=inter_cost("density_self", a), active=act)
            f = g.add_task("force_self", resources=(a,), writes=(a,),
                           cost=inter_cost("force_self", a), active=act)
            res = (a,)
        else:
            act = cell_active(a) or cell_active(b)
            d = g.add_task("density_pair", resources=(a, b), writes=(a, b),
                           cost=inter_cost("density_pair", a, b), active=act)
            f = g.add_task("force_pair", resources=(a, b), writes=(a, b),
                           cost=inter_cost("force_pair", a, b), active=act)
            res = (a, b)
        for c in res:
            g.add_dependency(d, sort[c])     # density after sort
            g.add_dependency(ghost[c], d)    # ghost after every density
            g.add_dependency(f, ghost[c])    # force after ghost
            g.add_dependency(kick[c], f)     # kick after every force
    return g


# ------------------------------------------------------------------ driver
class Simulation:
    """Host-side driver: binning, jitted stepping, re-binning, diagnostics.

    .. deprecated:: constructing this directly is the legacy path; it is
       now the global×local *engine* behind ``repro.sph.build_simulation(
       SimulationSpec(integrator="global", backend="local"))``.
    """

    def __init__(self, pos, vel, mass, u, h, *, box,
                 cfg: SPHConfig = SPHConfig(),
                 capacity_margin: float = 3.0,
                 rebin_every: int = 1):
        if type(self) is Simulation:
            warnings.warn(
                "constructing repro.sph.Simulation directly is deprecated; "
                "use repro.sph.build_simulation(SimulationSpec(...)) "
                "(integrator='global', backend='local')",
                DeprecationWarning, stacklevel=2)
        self.box = box_lengths(box)
        self.cfg = cfg
        self.n = len(pos)
        self.rebin_every = rebin_every
        h_max = float(np.max(h))
        self.spec = choose_grid(self.box, h_max, self.n,
                                capacity_margin=capacity_margin)
        self._rebin(np.asarray(pos), np.asarray(vel), np.asarray(mass),
                    np.asarray(u), np.asarray(h))
        self._jit_step = shared_step_program(self.box, self.cfg)
        self.state = init_state(self.cells, self.pairs, self.cfg)
        self._steps_since_rebin = 0
        self.tracer = NULL_TRACER      # rebound when observe=True
        # device-metrics carry (single rank), filled by the api adapter
        self.device_metrics_enabled = False
        self.device_metrics_last = None
        self.device_metrics_pulls = 0
        self.device_cell_work_last = None

    def _rebin(self, pos, vel, mass, u, h):
        self.cells, self.perm = bin_particles(self.spec, pos, vel, mass, u, h)
        if self.cells.mass.shape[1] != self.spec.capacity:
            # capacity grew: record it so pair list block shapes stay valid
            object.__setattr__(self.spec, "capacity",
                               self.cells.mass.shape[1])
        self.pairs = build_pair_list(self.spec)

    def run(self, nsteps: int, dt: Optional[float] = None) -> Dict[str, list]:
        log: Dict[str, list] = {"t": [], "wall": [], "E": [], "px": []}
        for _ in range(nsteps):
            dt_step = dt if dt is not None else float(
                cfl_timestep(self.state, self.cfg))
            with self.tracer.timed("engine_step",
                                   pairs=int(self.pairs.ci.shape[0])) as sp:
                self.state = self._jit_step(self.state, self.pairs,
                                            jnp.asarray(
                                                dt_step,
                                                self.cells.pos.dtype))
                jax.block_until_ready(self.state.cells.pos)
            wall = sp.elapsed
            self._steps_since_rebin += 1
            if self._steps_since_rebin >= self.rebin_every:
                flat = unbin(self.state.cells, self.perm, self.n)
                self._rebin(flat["pos"], flat["vel"], flat["mass"],
                            flat["u"], flat["h"])
                accel0 = init_state(self.cells, self.pairs, self.cfg)
                self.state = accel0._replace(time=self.state.time)
                self._steps_since_rebin = 0
            log["t"].append(float(self.state.time))
            log["wall"].append(wall)
            e, p = self.diagnostics()
            log["E"].append(e)
            log["px"].append(p[0])
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
