"""Distributed SPH engine: graph-partitioned cells + asynchronous halos.

The full SWIFT §3.2+§3.3 pipeline on a JAX device mesh:

1. The cell graph (task costs projected onto cells) is partitioned by the
   multilevel partitioner — *work*, not data, is balanced (C2).
2. Each device owns its cells; pair tasks spanning a cut are **duplicated on
   both sides** (the paper's Fig. 2 green tasks), each side accumulating
   only its local receivers.
3. Remote cell data arrives via a halo exchange, lowered two ways (C3):

   * ``halo="allgather"`` — every device contributes its *boundary* export
     buffer to one `lax.all_gather`; the bulk-synchronous-ish baseline
     (still boundary-only, so far cheaper than gathering all data).
   * ``halo="ring"`` — R rounds of `lax.ppermute`; each round every device
     forwards a window and picks out the cells it needs as they stream by.
     Communication is split into many small point-to-point messages spread
     across the step — the TPU-native image of SWIFT's "insane number of
     small messages", and XLA can overlap rounds with interior compute
     since interior pair tasks have no data dependency on the halo.

Communication happens twice per step, exactly as the paper: positions
before the density loop, densities (ρ, P, Ω, c_s, v) before the force loop.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Dict, List, NamedTuple, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ..core import CostModel, decompose_cells
from .cellgrid import GridSpec, PairList, ParticleCells
from .engine import SPHConfig, build_taskgraph
from .physics import density_block, force_block, ghost_update


# ------------------------------------------------------------------- plan
@dataclass
class DistPlan:
    """Host-side (numpy) distribution plan for one decomposition."""
    ndev: int
    K: int                     # owned cell slots per device
    B: int                     # export buffer slots per device
    Bi: int                    # import buffer slots per device
    Pmax: int                  # pair entries per device
    assignment: np.ndarray     # (ncells,) -> device
    storage: np.ndarray        # (ncells,) -> owned slot on owner device
    # per-device arrays (leading dim ndev):
    export_slots: np.ndarray   # (ndev, B) local slot to export (0 pad)
    export_valid: np.ndarray   # (ndev, B) 1/0
    import_flat: np.ndarray    # (ndev, Bi) src_dev * B + src_slot (0 pad)
    import_valid: np.ndarray   # (ndev, Bi)
    pair_recv: np.ndarray      # (ndev, Pmax) receiver local slot
    pair_src: np.ndarray       # (ndev, Pmax) source ext slot (< K local, >= K halo)
    pair_shift: np.ndarray     # (ndev, Pmax, 3)
    pair_w: np.ndarray         # (ndev, Pmax) 1/0 validity
    ring_rounds: int = 0       # max ring distance (for halo="ring")
    ring_pick: Optional[np.ndarray] = None  # (ndev, R, Bi) slot in window or -1


def build_dist_plan(ncells: int, pairs: PairList, assignment: np.ndarray,
                    ndev: int) -> DistPlan:
    assignment = np.asarray(assignment, dtype=np.int64)
    ci = np.asarray(pairs.ci, dtype=np.int64)
    cj = np.asarray(pairs.cj, dtype=np.int64)
    shift = np.asarray(pairs.shift, dtype=np.float32)

    # owned slots, in cell order
    storage = np.zeros(ncells, dtype=np.int64)
    counts = np.zeros(ndev, dtype=np.int64)
    for c in range(ncells):
        d = assignment[c]
        storage[c] = counts[d]
        counts[d] += 1
    K = int(counts.max())

    imports: List[Dict[int, int]] = [dict() for _ in range(ndev)]  # cell->idx
    exports: List[Dict[int, int]] = [dict() for _ in range(ndev)]
    entries: List[List[Tuple[int, int, np.ndarray]]] = [[] for _ in range(ndev)]

    def halo_index(dev: int, cell: int) -> int:
        if cell not in imports[dev]:
            imports[dev][cell] = len(imports[dev])
        src = int(assignment[cell])
        if cell not in exports[src]:
            exports[src][cell] = len(exports[src])
        return imports[dev][cell]

    for a, b, s in zip(ci, cj, shift):
        a, b = int(a), int(b)
        da, db = int(assignment[a]), int(assignment[b])
        if a == b:
            entries[da].append((storage[a], storage[a], s))
            continue
        if da == db:
            entries[da].append((storage[a], storage[b], s))
            entries[da].append((storage[b], storage[a], -s))
        else:
            ha = halo_index(da, b)   # device da imports cell b
            hb = halo_index(db, a)   # device db imports cell a
            entries[da].append((storage[a], -1 - ha, s))      # mark halo
            entries[db].append((storage[b], -1 - hb, -s))

    B = max((len(e) for e in exports), default=0)
    B = max(B, 1)
    Bi = max((len(i) for i in imports), default=0)
    Bi = max(Bi, 1)
    Pmax = max((len(e) for e in entries), default=1)
    Pmax = max(Pmax, 1)

    export_slots = np.zeros((ndev, B), dtype=np.int32)
    export_valid = np.zeros((ndev, B), dtype=np.float32)
    for d in range(ndev):
        for cell, idx in exports[d].items():
            export_slots[d, idx] = storage[cell]
            export_valid[d, idx] = 1.0

    import_flat = np.zeros((ndev, Bi), dtype=np.int32)
    import_valid = np.zeros((ndev, Bi), dtype=np.float32)
    import_src_dev = np.zeros((ndev, Bi), dtype=np.int32)
    for d in range(ndev):
        for cell, idx in imports[d].items():
            src = int(assignment[cell])
            slot = exports[src][cell]
            import_flat[d, idx] = src * B + slot
            import_src_dev[d, idx] = src
            import_valid[d, idx] = 1.0

    pair_recv = np.zeros((ndev, Pmax), dtype=np.int32)
    pair_src = np.zeros((ndev, Pmax), dtype=np.int32)
    pair_shift = np.zeros((ndev, Pmax, 3), dtype=np.float32)
    pair_w = np.zeros((ndev, Pmax), dtype=np.float32)
    for d in range(ndev):
        for p, (r, s_idx, s) in enumerate(entries[d]):
            pair_recv[d, p] = r
            pair_src[d, p] = (K + (-1 - s_idx)) if s_idx < 0 else s_idx
            pair_shift[d, p] = s
            pair_w[d, p] = 1.0

    # ring schedule: round r delivers the window of device (d - r) mod ndev
    R = 0
    for d in range(ndev):
        for idx in range(Bi):
            if import_valid[d, idx] > 0:
                dist = (d - int(import_src_dev[d, idx])) % ndev
                R = max(R, dist)
    ring_pick = np.full((ndev, max(R, 1), Bi), -1, dtype=np.int32)
    for d in range(ndev):
        for idx in range(Bi):
            if import_valid[d, idx] > 0:
                src = int(import_src_dev[d, idx])
                dist = (d - src) % ndev
                if dist >= 1:
                    slot = import_flat[d, idx] - src * B
                    ring_pick[d, dist - 1, idx] = slot

    return DistPlan(ndev=ndev, K=K, B=B, Bi=Bi, Pmax=Pmax,
                    assignment=assignment, storage=storage,
                    export_slots=export_slots, export_valid=export_valid,
                    import_flat=import_flat, import_valid=import_valid,
                    pair_recv=pair_recv, pair_src=pair_src,
                    pair_shift=pair_shift, pair_w=pair_w,
                    ring_rounds=R, ring_pick=ring_pick)


def scatter_to_devices(cells: ParticleCells, plan: DistPlan) -> ParticleCells:
    """(ncells, C, …) → (ndev*K, C, …) storage layout (host-side)."""
    ncells, cap = cells.mass.shape

    def place(a):
        a = np.asarray(a)
        out = np.zeros((plan.ndev * plan.K,) + a.shape[1:], a.dtype)
        dst = plan.assignment * plan.K + plan.storage
        out[dst] = a
        return jnp.asarray(out)

    return ParticleCells(pos=place(cells.pos), vel=place(cells.vel),
                         mass=place(cells.mass), u=place(cells.u),
                         h=place(cells.h), mask=place(cells.mask))


def gather_from_devices(cells: ParticleCells, plan: DistPlan,
                        ncells: int) -> ParticleCells:
    src = plan.assignment * plan.K + plan.storage

    def take(a):
        return jnp.asarray(np.asarray(a)[src])

    return ParticleCells(pos=take(cells.pos), vel=take(cells.vel),
                         mass=take(cells.mass), u=take(cells.u),
                         h=take(cells.h), mask=take(cells.mask))


# --------------------------------------------------------------- device code
def _exchange(fields: Tuple[jax.Array, ...], export_slots, export_valid,
              import_flat, import_valid, *, axis: str, halo: str,
              ring_pick=None, ring_rounds: int = 0):
    """Halo exchange of per-cell fields. Local shapes: (K, C, …) each.

    Returns halo buffers (Bi, C, …) for each field.
    """
    exports = []
    for f in fields:
        e = f[export_slots]                           # (B, C, …)
        ev = export_valid.reshape((-1,) + (1,) * (e.ndim - 1))
        exports.append(e * ev)

    if halo == "allgather":
        halos = []
        for e in exports:
            g = jax.lax.all_gather(e, axis)           # (D, B, C, …)
            flat = g.reshape((-1,) + g.shape[2:])     # (D*B, C, …)
            h = flat[import_flat]                     # (Bi, C, …)
            iv = import_valid.reshape((-1,) + (1,) * (h.ndim - 1))
            halos.append(h * iv)
        return tuple(halos)

    if halo == "ring":
        from ..distributed.mesh_utils import ring_perm
        ndev = jax.lax.axis_size(axis)
        perm = ring_perm(ndev)
        halos = [jnp.zeros((import_flat.shape[0],) + e.shape[1:], e.dtype)
                 for e in exports]
        windows = list(exports)
        for r in range(ring_rounds):
            windows = [jax.lax.ppermute(w, axis, perm) for w in windows]
            pick = ring_pick[r]                       # (Bi,) slot or -1
            take = jnp.maximum(pick, 0)
            sel = (pick >= 0)
            for i, w in enumerate(windows):
                got = w[take]                         # (Bi, C, …)
                selb = sel.reshape((-1,) + (1,) * (got.ndim - 1))
                halos[i] = jnp.where(selb, got, halos[i])
        iv = import_valid
        return tuple(h * iv.reshape((-1,) + (1,) * (h.ndim - 1))
                     for h in halos)

    raise ValueError(f"unknown halo scheme {halo!r}")


def _pair_density(local: ParticleCells, halo_pos, halo_h, halo_m, halo_mask,
                  pair_recv, pair_src, pair_shift, pair_w, cfg: SPHConfig):
    pos_e = jnp.concatenate([local.pos, halo_pos], axis=0)
    h_e = jnp.concatenate([local.h, halo_h], axis=0)
    m_e = jnp.concatenate([local.mass, halo_m], axis=0)
    k_e = jnp.concatenate([local.mask, halo_mask], axis=0)

    pos_i = local.pos[pair_recv]
    h_i = local.h[pair_recv]
    pos_j = pos_e[pair_src] + pair_shift[:, None, :]
    dens = functools.partial(density_block, kernel=cfg.kernel)
    res = jax.vmap(dens)(pos_i, h_i, pos_j, m_e[pair_src], k_e[pair_src])

    K, cap = local.mass.shape
    w = pair_w[:, None]

    def scat(x):
        return jnp.zeros((K, cap), x.dtype).at[pair_recv].add(x * w)

    return scat(res.rho), scat(res.drho_dh), scat(res.nngb)


def _pair_force(local: ParticleCells, rho, press, omega, cs,
                halo, pair_recv, pair_src, pair_shift, pair_w,
                cfg: SPHConfig):
    (h_pos, h_vel, h_h, h_m, h_mask, h_rho, h_press, h_om, h_cs) = halo

    def ext(a, hb):
        return jnp.concatenate([a, hb], axis=0)

    pos_e = ext(local.pos, h_pos)
    vel_e = ext(local.vel, h_vel)
    h_e = ext(local.h, h_h)
    m_e = ext(local.mass, h_m)
    k_e = ext(local.mask, h_mask)
    rho_e = ext(rho, h_rho)
    P_e = ext(press, h_press)
    om_e = ext(omega, h_om)
    cs_e = ext(cs, h_cs)

    gi = lambda a: a[pair_recv]
    gj = lambda a: a[pair_src]
    force = functools.partial(force_block, kernel=cfg.kernel,
                              alpha_visc=cfg.alpha_visc)
    res = jax.vmap(force)(
        gi(local.pos), gi(local.vel), gi(local.h), gi(press), gi(rho),
        gi(omega), gi(cs),
        gj(pos_e) + pair_shift[:, None, :], gj(vel_e), gj(h_e), gj(P_e),
        gj(rho_e), gj(om_e), gj(cs_e), gj(m_e), gj(k_e))

    K, cap = local.mass.shape
    dv = jnp.zeros((K, cap, 3), local.pos.dtype)
    dv = dv.at[pair_recv].add(res.dv * pair_w[:, None, None])
    du = jnp.zeros((K, cap), local.pos.dtype)
    du = du.at[pair_recv].add(res.du * pair_w[:, None])
    return dv, du


def _safe_halo_fields(h_rho, h_om):
    """Halo padding slots must stay division-safe."""
    h_rho = jnp.where(h_rho <= 0, 1.0, h_rho)
    h_om = jnp.where(jnp.abs(h_om) < 1e-4, 1.0, h_om)
    return h_rho, h_om


def make_dist_step(mesh: Mesh, plan: DistPlan, cfg: SPHConfig, box: float,
                   *, axis: str = "data", halo: str = "allgather"):
    """Build the jitted distributed KDK step (and force initialiser).

    All per-device plan arrays ride along as sharded operands; the body is
    pure local compute + the two halo exchanges.
    """

    def local_forces(local: ParticleCells, ex_slots, ex_valid, im_flat,
                     im_valid, p_recv, p_src, p_shift, p_w, ring_pick):
        exch = functools.partial(
            _exchange, export_slots=ex_slots, export_valid=ex_valid,
            import_flat=im_flat, import_valid=im_valid, axis=axis,
            halo=halo, ring_pick=ring_pick, ring_rounds=plan.ring_rounds)

        # ---- phase 1: ship positions, run density (paper: 1st comm)
        h_pos, h_h, h_m, h_mask = exch((local.pos, local.h, local.mass,
                                        local.mask))
        rho, drho_dh, nngb = _pair_density(
            local, h_pos, h_h, h_m, h_mask, p_recv, p_src, p_shift, p_w, cfg)
        rho = jnp.where(local.mask > 0, rho, 1.0)
        drho_dh = jnp.where(local.mask > 0, drho_dh, 0.0)
        press, omega, cs = ghost_update(rho, drho_dh, local.u, local.h,
                                        gamma=cfg.gamma)
        press = jnp.where(local.mask > 0, press, 0.0)

        # ---- phase 2: ship densities, run forces (paper: 2nd comm)
        h_vel, h_rho, h_press, h_om, h_cs = exch(
            (local.vel, rho, press, omega, cs))
        h_rho, h_om = _safe_halo_fields(h_rho, h_om)
        halo_bufs = (h_pos, h_vel, h_h, h_m, h_mask, h_rho, h_press, h_om,
                     h_cs)
        dv, du = _pair_force(local, rho, press, omega, cs, halo_bufs,
                             p_recv, p_src, p_shift, p_w, cfg)
        mask3 = local.mask[..., None]
        return dv * mask3, du * local.mask, rho

    def step_local(cells: ParticleCells, accel, dudt, dt,
                   ex_slots, ex_valid, im_flat, im_valid,
                   p_recv, p_src, p_shift, p_w, ring_pick):
        mask3 = cells.mask[..., None]
        v_half = cells.vel + 0.5 * dt * accel
        u_half = jnp.maximum(cells.u + 0.5 * dt * dudt, 1e-12)
        pos = jnp.mod(cells.pos + dt * v_half * mask3, box)
        cells = cells._replace(pos=pos, vel=v_half, u=u_half)
        dv, du, rho = local_forces(cells, ex_slots, ex_valid, im_flat,
                                   im_valid, p_recv, p_src, p_shift, p_w,
                                   ring_pick)
        v_new = cells.vel + 0.5 * dt * dv
        u_new = jnp.maximum(u_half + 0.5 * dt * du, 1e-12)
        cells = cells._replace(vel=v_new, u=u_new)
        return cells, dv, du, rho

    dspec = P(axis)          # shard leading device dim
    cell_specs = ParticleCells(pos=dspec, vel=dspec, mass=dspec, u=dspec,
                               h=dspec, mask=dspec)
    plan_specs = (dspec,) * 5 + (dspec,)     # plan arrays + ring_pick

    step_m = shard_map(
        step_local, mesh=mesh,
        in_specs=(cell_specs, dspec, dspec, P(),
                  dspec, dspec, dspec, dspec, dspec, dspec, dspec, dspec,
                  dspec),
        out_specs=(cell_specs, dspec, dspec, dspec),
    )
    init_m = shard_map(
        local_forces, mesh=mesh,
        in_specs=(cell_specs, dspec, dspec, dspec, dspec, dspec, dspec,
                  dspec, dspec, dspec),
        out_specs=(dspec, dspec, dspec),
    )

    plan_args = (jnp.asarray(plan.export_slots.reshape(-1, plan.B)),
                 jnp.asarray(plan.export_valid),
                 jnp.asarray(plan.import_flat),
                 jnp.asarray(plan.import_valid),
                 jnp.asarray(plan.pair_recv),
                 jnp.asarray(plan.pair_src),
                 jnp.asarray(plan.pair_shift.reshape(plan.ndev * plan.Pmax, 3)
                             ).reshape(plan.ndev, plan.Pmax, 3),
                 jnp.asarray(plan.pair_w),
                 jnp.asarray(plan.ring_pick))

    # shard_map expects the leading dim == ndev for P(axis)-sharded args;
    # reshape per-device tables to (ndev * X, …) so slicing is even
    def flatten_dev(a):
        a = jnp.asarray(a)
        return a.reshape((a.shape[0] * a.shape[1],) + a.shape[2:])

    flat_plan = tuple(flatten_dev(a) for a in plan_args)

    def jit_step(cells, accel, dudt, dt):
        return step_m(cells, accel, dudt, dt, *flat_plan)

    def jit_init(cells):
        return init_m(cells, *flat_plan)

    return jax.jit(jit_step), jax.jit(jit_init)


# ------------------------------------------------------------------ driver
class DistSimulation:
    """Multi-device SPH driver with graph-partitioned domain decomposition."""

    def __init__(self, cells: ParticleCells, pairs: PairList,
                 spec: GridSpec, mesh: Mesh, *, cfg: SPHConfig = SPHConfig(),
                 axis: str = "data", halo: str = "allgather",
                 cost_model: Optional[CostModel] = None, seed: int = 0):
        if type(self) is DistSimulation:
            import warnings
            warnings.warn(
                "constructing DistSimulation directly is deprecated; use "
                "repro.sph.build_simulation(SimulationSpec(...)) "
                "(integrator='global', backend='distributed')",
                DeprecationWarning, stacklevel=2)
        self.spec = spec
        self.cfg = cfg
        self.mesh = mesh
        self.axis = axis
        self.halo = halo
        ndev = mesh.shape[axis]
        occupancy = np.asarray(cells.mask.sum(axis=1))
        tg = build_taskgraph(spec, pairs, occupancy, cost_model)
        self.decomp = decompose_cells(tg, spec.ncells, ndev, seed=seed)
        self.plan = build_dist_plan(spec.ncells, pairs,
                                    self.decomp.assignment, ndev)
        self.dcells = scatter_to_devices(cells, self.plan)
        self._step, self._init = make_dist_step(mesh, self.plan, cfg,
                                                spec.box, axis=axis,
                                                halo=halo)
        with mesh:
            self.accel, self.dudt, self.rho = self._init(self.dcells)
        # device-metrics carry (per rank), filled by the api adapter
        self.device_metrics_enabled = False
        self.device_metrics_last = None
        self.device_metrics_pulls = 0
        self.device_cell_work_last = None

    def step(self, dt: float):
        with self.mesh:
            self.dcells, self.accel, self.dudt, self.rho = self._step(
                self.dcells, self.accel, self.dudt,
                jnp.asarray(dt, self.dcells.pos.dtype))

    def gather_cells(self) -> ParticleCells:
        return gather_from_devices(self.dcells, self.plan, self.spec.ncells)
