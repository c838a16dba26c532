"""Distributed hierarchical time-bin integration with activity-aware halos.

The missing quadrant of the {global-dt, time-bin} × {local, distributed}
matrix: per-particle power-of-two time-steps (``timebins.py``) over a
graph-partitioned cell decomposition (``core.decompose``), where halo
exchanges are **activity-aware** — at each sub-step only the cut cells with
bins active at that sub-step contribute to the export buffer. An inactive
boundary cell's replica stays valid on the importing rank because drift is
elementwise: the importer drifts its halo copies with exactly the owner's
arithmetic, so data only has to ship when a kick actually changes it.
This is the time-axis extension of SWIFT's halo protocol (§3.3): the
communication volume per sub-step tracks the *active* fraction of the cut,
not its size — on a Sedov blast the quiescent background's boundary cells
ship (almost) nothing between cycle synchronisation points.

Structure of one force sub-step on each rank (two comm phases, exactly as
the paper's step — positions are already local via replica drift):

1. density phase (``timebins._substep_density_phase``) over the rank's
   activity-restricted pair list → fresh rho/omega/press/cs for active
   particles;
2. **exchange 1**: owners ship (rho, omega, press, cs) of *active* cut
   cells — the importer's locally-computed values for those rows are
   partial sums and are overwritten;
3. force phase (``timebins._substep_force_phase``) → kick + bin deepening;
4. **exchange 2**: owners ship the kicked state (vel, u, bins, t_start,
   accel, dudt) of active cut cells so replicas stay current.

Cut pair tasks are duplicated on both ranks (the paper's Fig. 2 green
tasks): every rank's pair list covers all pairs touching its owned cells,
so owned active particles always receive complete interaction sums.

The wire is a pluggable **transport** (``transport="host" | "collective"``):
``HostTransport`` copies rows through numpy between the ranks' jitted phase
programs, while ``CollectiveTransport`` (``sph/collectives.py``) compiles
the same copies into one shard_map program — ``lax.ppermute`` rounds over
the comm planner's export edge schedule (``core.comm_planner.
ppermute_rounds``) with an ``all_gather`` fallback — over power-of-two-
bucketed export buffers, so the exchange program is compiled once and
reused for every sub-step regardless of how many cut-cell rows are active.
Both transports are pure row copies and therefore bit-for-bit identical
(asserted in ``tests/test_transport.py``). The density/force sub-step
programs are shared across ranks: every rank's pair subset is padded to one
common power-of-two bucket, so one compiled program per (phase, bucket)
serves the whole mesh; the :class:`~repro.distributed.transport.
CompileProbe` (``self.probe``) counts the real XLA compiles. With
``nranks=1`` the engine reduces to the single-host ladder bit-for-bit
(asserted in ``tests/test_api.py``).

The residency picks one of two lowerings of the cycle, both pinned bitwise
to the single-rank :class:`~repro.sph.timebins.TimeBinSimulation`, the
oracle (``tests/test_conformance.py``):

* ``residency="host"`` — the **host-resident ladder**
  (:meth:`DistTimeBinSimulation._cycle_substeps_host`): per-rank states,
  the host loop walks the 2**depth sub-steps and dispatches the phase
  programs and exchanges above. It is the multi-rank reference that runs
  on one CPU device.
* ``residency="device"`` — the **device cycle scan**, the production path
  (:meth:`DistTimeBinSimulation._run_segment`): one stacked sharded buffer
  per field stays on the mesh, and each cycle of a K-cycle segment is one
  compiled ``lax.scan`` (``sph/collectives.build_cycle_scan_program``) that
  derives its sub-step schedule from the device-resident bins. A segment
  that trips a health, crossing or capacity flag is restored and replayed
  on the host-resident ladder over the same collective transport.

Repartitioning uses per-rank **bin occupancy**: the decomposition is
retriggered when the time-averaged active work per rank
(``core.decompose.timebin_node_weights``) drifts out of balance, and the
new partition is computed from the cycle-averaged task costs
(``CostModel.timebin_units``), weighting send/recv by activation frequency.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from jax.sharding import NamedSharding, PartitionSpec as P

from ..core import CostModel, decompose_cells
from ..core.decompose import timebin_node_weights
from ..distributed.transport import (CompileProbe, RESIDENCIES,
                                     ResidentBuffers, ShipSlots, TRANSPORTS,
                                     TransferProbe, make_transport, next_pow2,
                                     pack_allgather, pack_rounds, schedule_of)
from ..observability import device_metrics as dmetrics
from .cellgrid import PairList, ParticleCells
from .engine import SPHConfig, build_taskgraph
from .timebins import (STATE_AUX_FIELDS, STATE_CELL_FIELDS,
                       TimeBinSimulation, TimeBinState, _final_force_phase,
                       _substep_density_phase, _substep_force_phase,
                       active_level, cell_bin_histogram,
                       mass_weighted_mean_u, substep_active_mask)

_PAD_H = 1e-6          # padded-slot smoothing length (division-safe)

# scalars shipped per particle slot in each exchange (for byte accounting):
# exchange 1: rho, omega, press, cs; exchange 2: vel(3), u, bins, t_start,
# accel(3), dudt
_EX1_FIELDS = 4
_EX2_FIELDS = 10


# ------------------------------------------------------------------ rank plan
@dataclass
class RankPlan:
    """Host-side plan of one decomposition: who owns what, who imports what.

    Extended row layout per rank: rows [0, K) hold owned cells (global cell
    order), rows [K, K+H) hold halo replicas; both padded uniformly so every
    rank shares one compiled program per pair-bucket size.
    """
    nranks: int
    K: int                              # owned rows per rank (padded max)
    H: int                              # halo rows per rank (padded max)
    assignment: np.ndarray              # (ncells,) -> rank
    owned: List[np.ndarray]             # per rank: global cell ids, in order
    halo: List[np.ndarray]              # per rank: imported global cell ids
    ext_row: np.ndarray                 # (nranks, ncells) cell -> ext row (-1)
    # cut cells: cell -> (owner rank, owner ext row, [(imp rank, imp row)])
    cut: Dict[int, Tuple[int, int, List[Tuple[int, int]]]] = \
        field(default_factory=dict)
    # per-rank global-pair membership and ext-index maps
    touch: List[np.ndarray] = field(default_factory=list)   # (npairs,) bool
    ci_ext: List[np.ndarray] = field(default_factory=list)  # (npairs,) int32
    cj_ext: List[np.ndarray] = field(default_factory=list)  # (npairs,) int32

    @property
    def cut_slots(self) -> int:
        """Total (cell, importer) slots across the cut = full-boundary
        export volume of one exchange."""
        return sum(len(imps) for _, _, imps in self.cut.values())

    def export_edges(self) -> List[Tuple[int, int]]:
        """Directed rank-to-rank edges of the cut (the comm planner's
        export edge list — input to ``ppermute_rounds``)."""
        edges = {(o, ir) for _, (o, _, imps) in self.cut.items()
                 for (ir, _) in imps}
        return sorted(edges)

    def ship_slots(self, cells_due: List[int]) -> ShipSlots:
        """This sub-step's exchange: owner row → importer rows per edge."""
        slots = ShipSlots()
        for c in cells_due:
            o, orow, imps = self.cut[c]
            for (ir, irow) in imps:
                slots.add(o, ir, orow, irow)
        return slots


def build_rank_plan(assignment: np.ndarray, ci: np.ndarray, cj: np.ndarray,
                    nranks: Optional[int] = None) -> RankPlan:
    """Ownership + halo-import plan over the global cell-pair list."""
    assignment = np.asarray(assignment, dtype=np.int64)
    ncells = len(assignment)
    if nranks is None:
        nranks = int(assignment.max()) + 1 if ncells else 1
    owned = [np.nonzero(assignment == r)[0] for r in range(nranks)]
    K = max((len(o) for o in owned), default=1)
    K = max(K, 1)

    imports: List[Dict[int, int]] = [dict() for _ in range(nranks)]
    for a, b in zip(np.asarray(ci), np.asarray(cj)):
        a, b = int(a), int(b)
        ra, rb = int(assignment[a]), int(assignment[b])
        if ra == rb:
            continue
        if b not in imports[ra]:
            imports[ra][b] = len(imports[ra])
        if a not in imports[rb]:
            imports[rb][a] = len(imports[rb])
    H = max((len(i) for i in imports), default=0)

    halo = []
    ext_row = np.full((nranks, ncells), -1, dtype=np.int64)
    for r in range(nranks):
        for slot, c in enumerate(owned[r]):
            ext_row[r, c] = slot
        hl = np.empty(len(imports[r]), dtype=np.int64)
        for c, idx in imports[r].items():
            hl[idx] = c
            ext_row[r, c] = K + idx
        halo.append(hl)

    cut: Dict[int, Tuple[int, int, List[Tuple[int, int]]]] = {}
    for r in range(nranks):
        for c, idx in imports[r].items():
            o = int(assignment[c])
            if c not in cut:
                cut[c] = (o, int(ext_row[o, c]), [])
            cut[c][2].append((r, K + idx))

    plan = RankPlan(nranks=nranks, K=K, H=H, assignment=assignment,
                    owned=owned, halo=halo, ext_row=ext_row, cut=cut)
    ci_np = np.asarray(ci, dtype=np.int64)
    cj_np = np.asarray(cj, dtype=np.int64)
    for r in range(nranks):
        touch = (assignment[ci_np] == r) | (assignment[cj_np] == r)
        cie = np.where(touch, ext_row[r, ci_np], 0).astype(np.int32)
        cje = np.where(touch, ext_row[r, cj_np], 0).astype(np.int32)
        plan.touch.append(touch)
        plan.ci_ext.append(cie)
        plan.cj_ext.append(cje)
    return plan


def halo_export_schedule(cell_bins: np.ndarray, plan: RankPlan, depth: int
                         ) -> Dict[str, np.ndarray]:
    """Static per-sub-step export volumes over one 2**depth cycle.

    ``cell_bins`` is each cell's deepest occupied bin (−1 empty). A cut cell
    ships to each of its importers when active (bin ≥ level of the
    sub-step); the full-boundary baseline ships every cut cell at every
    force sub-step. Pure host arithmetic — the fast check that
    activity-aware halos beat the baseline, without running the engine.
    """
    nsub = 1 << depth
    active_slots = np.zeros(nsub, dtype=np.int64)
    full_slots = np.zeros(nsub, dtype=np.int64)
    bins = np.asarray(cell_bins)
    for n in range(1, nsub + 1):
        level = 0 if n == nsub else active_level(n, depth)
        any_active = bool((bins >= level).any())
        if not any_active:
            continue
        full = plan.cut_slots
        act = sum(len(imps) for c, (_, _, imps) in plan.cut.items()
                  if bins[c] >= level)
        active_slots[n - 1] = act
        full_slots[n - 1] = full
    return {"active": active_slots, "full": full_slots}


# ------------------------------------------------------------------- driver
class DistTimeBinSimulation(TimeBinSimulation):
    """Rank-partitioned multi-dt driver (the distributed ``timebin`` engine).

    Inherits the cycle planner, bin math and host bookkeeping from
    :class:`TimeBinSimulation`; overrides the sub-step ladder to run on
    per-rank extended (owned ⊕ halo) states with the two activity-aware
    exchanges described in the module docstring. Export volumes are
    accumulated in ``halo_exported_slots`` / ``halo_full_slots``;
    ``halo_log`` holds the *latest cycle's* per-sub-step breakdown (reset
    each cycle so long runs stay bounded).
    """

    def __init__(self, pos, vel, mass, u, h, *, box,
                 cfg: SPHConfig = SPHConfig(),
                 nranks: int = 1,
                 activity_aware: bool = True,
                 repartition_threshold: float = 1.5,
                 cost_model: Optional[CostModel] = None,
                 seed: int = 0,
                 transport: str = "host",
                 transport_mode: str = "auto",
                 residency: str = "host",
                 schedule: Optional[str] = None,
                 segment_cycles: int = 1,
                 **kw):
        if transport not in TRANSPORTS:
            raise ValueError(f"transport must be one of {TRANSPORTS}, "
                             f"got {transport!r}")
        if residency not in RESIDENCIES:
            raise ValueError(f"residency must be one of {RESIDENCIES}, "
                             f"got {residency!r}")
        if residency == "device":
            if transport != "collective":
                raise ValueError(
                    "residency='device' fuses the exchange into the "
                    "cycle scan and therefore requires "
                    "transport='collective' (the host wire has no device "
                    "mesh to keep the state resident on)")
            if cfg.use_pallas:
                raise ValueError(
                    "residency='device' compiles the vmap pair phases "
                    "into the cycle scan; use_pallas=True is not "
                    "supported on this path yet")
        schedule = schedule_of(residency, schedule)
        if int(segment_cycles) < 1:
            raise ValueError("segment_cycles must be >= 1")
        if int(segment_cycles) > 1 and schedule != "device":
            raise ValueError(
                "segment_cycles > 1 fuses consecutive cycles into one "
                "device segment and requires schedule='device'")
        self.residency = residency
        self.schedule = schedule
        self.segment_cycles = int(segment_cycles)
        self.nranks = int(nranks)
        self.activity_aware = bool(activity_aware)
        self.repartition_threshold = float(repartition_threshold)
        self._cost_model = cost_model or CostModel(rates={})
        self._seed = seed
        self.transport_kind = transport
        super().__init__(pos, vel, mass, u, h, box=box, cfg=cfg, **kw)
        # the compile-count probe: every jitted program of this engine is
        # registered, so tests can assert the bucket discipline bounds
        # recompiles (one per (program, bucket), none per sub-step)
        self.probe = CompileProbe()
        self.probe.register("drift", self._jit_drift)
        self.probe.register("cycle_start", self._jit_start)
        self._jit_sub_density = self.probe.register("density", jax.jit(
            functools.partial(self._sub_density, cfg=cfg)))
        self._jit_sub_force = self.probe.register("force", jax.jit(
            functools.partial(_substep_force_phase, cfg=cfg)))
        self._jit_final_density = self.probe.register("final_density",
            jax.jit(functools.partial(self._final_density, cfg=cfg)))
        self._jit_final_force = self.probe.register("final_force", jax.jit(
            functools.partial(_final_force_phase, cfg=cfg)))
        self.program_keys: set = set()      # (program, level, bucket) seen
        self._transport = make_transport(transport, nranks=self.nranks,
                                         probe=self.probe,
                                         mode=transport_mode)
        self._plan_cache: Optional[RankPlan] = None
        self._plan_cache_key: Optional[bytes] = None
        self._assignment = self._initial_assignment()
        self.repartitions = 0
        self.halo_exported_slots = 0
        self.halo_full_slots = 0
        self.halo_log: List[Dict[str, float]] = []
        # residency="device": host↔device traffic ledger
        self.transfers = TransferProbe()
        # device telemetry: the cycle scan always *computes* the
        # per-rank metrics row (see observability/device_metrics.py —
        # that's what keeps the instrumented program the only program);
        # this flag gates the once-per-cycle host pull + observer merge
        self.device_metrics_enabled = False
        self.device_metrics_last: Optional[Tuple[np.ndarray,
                                                 np.ndarray]] = None
        self.device_metrics_pulls = 0
        # per-cell attribution of the last pulled cycle (device-metrics
        # v2): {"columns", "cells" (ncells, C) float64, "per_rank"
        # (nranks, C)} or None — the TaskCostLedger / repartition-advisor
        # contract. Rides in the same once-per-cycle metrics transfer.
        self.device_cell_work_last: Optional[Dict] = None
        # schedule="device": whole K-cycle segments run as compiled
        # programs; run_cycle() pops one cycle's stats per call from this
        # queue. A segment aborts back to the host-resident ladder
        # (bitwise-recoverably) when a health sentinel or capacity/crossing
        # flag trips.
        self._segment_queue: List[Dict] = []
        self.segments = 0
        self.segment_aborts = 0

    # ------------------------------------------------------- jitted phases
    @staticmethod
    def _sub_density(state, pairs, pair_mask, level, wake_floor, *, cfg):
        active = substep_active_mask(state, level, wake_floor)
        rho, omega, press, cs = _substep_density_phase(
            state, pairs, pair_mask, active, cfg=cfg)
        return active, rho, omega, press, cs

    @staticmethod
    def _final_density(state, pairs, pair_mask, *, cfg):
        active = state.cells.mask
        return _substep_density_phase(state, pairs, pair_mask, active,
                                      cfg=cfg)

    # ---------------------------------------------------------- partitioning
    def _initial_assignment(self) -> np.ndarray:
        if self.nranks <= 1:
            return np.zeros(self.spec.ncells, dtype=np.int64)
        occ = np.asarray(self.state.cells.mask).sum(axis=1).astype(np.int64)
        g = build_taskgraph(self.spec, self.pairs, occ, self._cost_model)
        dec = decompose_cells(g, self.spec.ncells, self.nranks,
                              seed=self._seed)
        return np.asarray(dec.assignment, dtype=np.int64)

    def _maybe_repartition(self, bins_h: np.ndarray, mask_h: np.ndarray,
                           depth: int) -> None:
        """Per-rank bin-occupancy repartition trigger.

        The quantity balanced is the *time-averaged active work* per rank
        (``timebin_node_weights``): deep-bin (short-step) cells cost their
        rank every sub-step, shallow ones almost never. When the max/mean
        ratio exceeds the threshold, re-decompose with cycle-averaged task
        costs (``CostModel.timebin_units`` — send/recv weighted by
        activation frequency).
        """
        if self.nranks <= 1:
            return
        obb = cell_bin_histogram(bins_h, mask_h, depth + 1)
        w = timebin_node_weights(obb)
        rank_w = np.zeros(self.nranks)
        np.add.at(rank_w, self._assignment, w)
        mean = rank_w.mean()
        if mean <= 0 or rank_w.max() / mean <= self.repartition_threshold:
            return
        occ = (mask_h > 0).sum(axis=1).astype(np.int64)
        deep = (obb.shape[1] - 1 - np.argmax(obb[:, ::-1] > 0, axis=1))
        cb = np.where(obb.sum(axis=1) > 0, deep, -1)
        g = build_taskgraph(self.spec, self.pairs, occ, self._cost_model,
                            cell_bins=cb, occupancy_by_bin=obb,
                            time_average=True)
        dec = decompose_cells(g, self.spec.ncells, self.nranks,
                              seed=self._seed, occupancy_by_bin=obb)
        self._assignment = np.asarray(dec.assignment, dtype=np.int64)
        self.repartitions += 1

    # ------------------------------------------------------ scatter / gather
    def _scatter_state(self, plan: RankPlan) -> List[TimeBinState]:
        """Global mirror → per-rank extended TimeBinStates."""
        st = self.state
        fills = self._FILLS     # shared with _scatter_resident: the two
        states = []             # residencies must pad rows identically
        for r in range(plan.nranks):
            idx = np.concatenate([plan.owned[r], plan.halo[r]]).astype(int)
            split = len(plan.owned[r])
            nrows = plan.K + plan.H

            def ext(a, fill):
                a = np.asarray(a)
                out = np.full((nrows,) + a.shape[1:], fill, dtype=a.dtype)
                out[:split] = a[plan.owned[r]]
                out[plan.K:plan.K + len(plan.halo[r])] = a[plan.halo[r]]
                return jnp.asarray(out)

            cells = ParticleCells(
                pos=ext(st.cells.pos, fills["pos"]),
                vel=ext(st.cells.vel, fills["vel"]),
                mass=ext(st.cells.mass, fills["mass"]),
                u=ext(st.cells.u, fills["u"]),
                h=ext(st.cells.h, fills["h"]),
                mask=ext(st.cells.mask, fills["mask"]))
            states.append(TimeBinState(
                cells=cells,
                accel=ext(st.accel, fills["accel"]),
                dudt=ext(st.dudt, fills["dudt"]),
                rho=ext(st.rho, fills["rho"]),
                omega=ext(st.omega, fills["omega"]),
                bins=ext(st.bins, fills["bins"]),
                t_start=ext(st.t_start, fills["t_start"]),
                time=st.time))
        return states

    def _gather_state(self, plan: RankPlan, states: List[TimeBinState]
                      ) -> None:
        """Per-rank owned rows → global mirror (halo replicas discarded)."""
        st = self.state
        out = {name: np.asarray(getattr(st, name)).copy()
               for name in ("accel", "dudt", "rho", "omega", "bins",
                            "t_start")}
        cells_out = {name: np.asarray(getattr(st.cells, name)).copy()
                     for name in ("pos", "vel", "mass", "u", "h", "mask")}
        for r in range(plan.nranks):
            own = plan.owned[r]
            if not len(own):
                continue
            sr = states[r]
            for name in out:
                out[name][own] = np.asarray(getattr(sr, name))[:len(own)]
            for name in cells_out:
                cells_out[name][own] = np.asarray(
                    getattr(sr.cells, name))[:len(own)]
        self.state = TimeBinState(
            cells=ParticleCells(**{k: jnp.asarray(v)
                                   for k, v in cells_out.items()}),
            time=states[0].time,
            **{k: jnp.asarray(v) for k, v in out.items()})

    # ------------------------------------------------------------ rank plan
    def _get_plan(self) -> RankPlan:
        """The cycle's rank plan; cached per assignment (the pair list is
        static, so the plan only changes when the partition does)."""
        key = self._assignment.tobytes()
        if self._plan_cache is None or self._plan_cache_key != key:
            self._plan_cache = build_rank_plan(
                np.asarray(self._assignment), self._ci, self._cj,
                nranks=self.nranks)
            self._plan_cache_key = key
            self._transport.prepare(self._plan_cache.export_edges())
        return self._plan_cache

    # --------------------------------------------------------- pair subsets
    def _select_rank_pairs(self, plan: RankPlan,
                           active_cells: Optional[np.ndarray]
                           ) -> Tuple[List[np.ndarray], int]:
        """Per-rank live pair indices, in global pair order.

        The one selection rule (rank's touch set, optionally restricted to
        pairs touching an active cell) that both the host phase programs
        (:meth:`_rank_pair_subsets`) and the cycle scan's tables
        (:meth:`_segment_tables`) build from — the bitwise-parity contract
        between the two residencies depends on it never forking.
        """
        idxs = []
        nmax = 1
        for r in range(plan.nranks):
            sel = plan.touch[r]
            if active_cells is not None:
                sel = sel & (active_cells[self._ci] | active_cells[self._cj])
            idx = np.nonzero(sel)[0]
            idxs.append(idx)
            nmax = max(nmax, len(idx))
        return idxs, nmax

    def _rank_pair_subsets(self, plan: RankPlan,
                           active_cells: Optional[np.ndarray]
                           ) -> Tuple[List[Tuple[PairList, jax.Array, int]],
                                      int]:
        """All ranks' pair subsets, padded to one **shared** power-of-two
        bucket (the max across ranks), so a single compiled phase program
        per (phase, bucket) serves every rank. Padded entries duplicate
        pair 0 with a zero mask and contribute exact +0.0 to every sum
        (the mask property test in ``tests/test_transport.py``)."""
        idxs, nmax = self._select_rank_pairs(plan, active_cells)
        npad = next_pow2(nmax)
        out = []
        for r in range(plan.nranks):
            idx = idxs[r]
            nlive = len(idx)
            idxp = np.concatenate(
                [idx, np.zeros(npad - nlive, dtype=idx.dtype)])
            pmask = np.zeros(npad, np.float32)
            pmask[:nlive] = 1.0
            sub = PairList(ci=jnp.asarray(plan.ci_ext[r][idxp]),
                           cj=jnp.asarray(plan.cj_ext[r][idxp]),
                           shift=jnp.asarray(self._shift[idxp]))
            out.append((sub, jnp.asarray(pmask), nlive))
        return out, npad

    # ------------------------------------------------------------ exchanges
    def _exchange_set(self, plan: RankPlan, active_cells: np.ndarray
                      ) -> List[int]:
        """Cut cells due for shipping this sub-step."""
        if not self.activity_aware:
            return list(plan.cut.keys())
        return [c for c in plan.cut if active_cells[c]]

    def transport_stats(self) -> Dict[str, object]:
        """Wire-level accounting of the active transport + compile probe."""
        out = dict(self._transport.stats())
        out["compiles"] = self.probe.counts()
        out["program_keys"] = len(self.program_keys)
        out["residency"] = self.residency
        out["transfers"] = self.transfers.stats()
        return out

    # -------------------------------------------------------------- cycling
    def run_cycle(self) -> Dict[str, float]:
        tr = self.tracer
        if tr.enabled:
            tr.ctx["cycle"] = self.cycle_index
            tr.ctx.pop("substep", None)
        if self.schedule == "device":
            # device-scheduled: whole K-cycle segments run as compiled
            # programs; each run_cycle() call pops one cycle's stats
            if not self._segment_queue:
                with tr.timed("cycle") as seg:
                    self._segment_queue = self._run_segment()
                per_cycle_wall = seg.elapsed / max(len(self._segment_queue),
                                                   1)
                for s in self._segment_queue:
                    s["wall"] = per_cycle_wall
            stats = self._segment_queue.pop(0)
            if "_met" in stats:
                # the row travelled in the segment_stats boundary pull —
                # adopting it here is free (no extra transfer entry)
                self.device_metrics_last = stats.pop("_met")
                self.device_metrics_pulls += 1
                self.device_cell_work_last = stats.pop("_cellw", None)
            self.cycle_index += 1
            return stats
        with tr.timed("cycle") as cyc:
            ctx = self._cycle_prologue()
            body = self._cycle_substeps_host(ctx)
            stats = self._cycle_epilogue(ctx, body)
        if tr.enabled:
            tr.ctx.pop("substep", None)
        self.cycle_index += 1
        stats["wall"] = cyc.elapsed
        return stats

    def _cycle_prologue(self) -> Dict[str, object]:
        """Plan the cycle and open it on the global mirror (host side)."""
        # planning runs once on the host for everyone — one task on every
        # rank's row, like SWIFT's tree-build. No fence: the opening kick
        # is only dispatched here; the host ladder waits on it
        with self.tracer.span("plan", ranks=range(self.nranks),
                              collective=1) as sp:
            dt_max_c, depth = self._plan_cycle()
            nsub = 1 << depth
            nreal = int(np.asarray(self.state.cells.mask).sum())
            sp.set(units=nreal)
            bins_host = np.asarray(self.state.bins)
            mask_host = np.asarray(self.state.cells.mask)
            m_h = np.asarray(self.state.cells.mass * self.state.cells.mask)
            # fixed-shape tree fold (timebins.mass_weighted_mean_u): the
            # same reduction order the device plan program reproduces, so
            # the host- and device-derived schedules agree bit for bit
            u_floor = float(mass_weighted_mean_u(
                m_h, np.asarray(self.state.cells.u)))
            hist = np.bincount(bins_host[mask_host > 0], minlength=depth + 1)
            # opening half-kick on the global mirror, then scatter to ranks
            self.state = self._jit_start(self.state, jnp.float32(dt_max_c))
            plan = self._get_plan()
        return {"dt_max_c": dt_max_c, "depth": depth, "nsub": nsub,
                "dt_min": dt_max_c / nsub, "nreal": nreal,
                "bins_host": bins_host, "mask_host": mask_host,
                "u_floor": u_floor, "hist": hist, "plan": plan}

    def _cycle_epilogue(self, ctx: Dict[str, object],
                        body: Dict[str, int]) -> Dict[str, float]:
        """Close the cycle: repartition check, re-bin, counters, stats."""
        tr = self.tracer
        nsub, nreal = ctx["nsub"], ctx["nreal"]
        self._maybe_repartition(np.asarray(self.state.bins),
                                np.asarray(self.state.cells.mask),
                                ctx["depth"])
        if self.rebin_each_cycle:
            with tr.span("rebin", units=nreal):
                self._rebin_state()
        self.particle_updates += body["updates"]
        self.global_equiv_updates += nsub * nreal
        self.substeps += nsub
        self.halo_exported_slots += body["cycle_exported"]
        self.halo_full_slots += body["cycle_full"]
        return {
            "t": float(self.state.time),
            "dt_max": ctx["dt_max_c"],
            "depth": ctx["depth"],
            "substeps": nsub,
            "force_substeps": body["force_substeps"] + 1,
            "bin_hist": ctx["hist"],
            "updates": body["updates"],
            "global_equiv_updates": nsub * nreal,
            "pair_tasks": body["pair_tasks"],
            "global_equiv_pair_tasks": nsub * len(self._ci),
            "halo_exported_slots": body["cycle_exported"],
            "halo_full_slots": body["cycle_full"],
            "nranks": ctx["plan"].nranks,
            "residency": self.residency,
        }

    # ------------------------------------------------- device-metrics pull
    def _metrics_pull(self, counts, values, cells=None,
                      plan: Optional[RankPlan] = None) -> None:
        """Adopt one cycle's accumulated telemetry row: pull it to host —
        ONE ledgered boundary transfer per cycle (the acceptance bound
        ``benchmarks/observability_bench.py`` reports) — and expose it as
        ``device_metrics_last`` for the observer's end-of-cycle merge.
        The per-cell work buffer (``cells``, stacked device rows) rides in
        the same transfer and is folded onto global cells via the plan's
        row maps into ``device_cell_work_last``. Must run inside
        ``run_cycle`` so the transfer ledger the observer copies verbatim
        already contains this pull."""
        counts_h = np.asarray(counts)
        values_h = np.asarray(values)
        nbytes = counts_h.nbytes + values_h.nbytes
        if cells is not None and plan is not None:
            cells_h = np.asarray(cells)
            nbytes += cells_h.nbytes
            self.device_cell_work_last = dmetrics.fold_cell_rows(
                cells_h, plan.owned, plan.halo, self.spec.ncells, plan.K)
        self.transfers.record("metrics", nbytes, boundary=True)
        self.device_metrics_pulls += 1
        self.device_metrics_last = (counts_h, values_h)

    def _mirror_metrics_finish(self, plan: RankPlan, counts: np.ndarray,
                               values: np.ndarray) -> None:
        """Host-residency tail of the telemetry row: sentinel flags and
        per-rank state fingerprints from the gathered global mirror
        (whose rows the host path round-trips anyway)."""
        st = self.state
        mask = np.asarray(st.cells.mask)
        vel = np.asarray(st.cells.vel)
        u = np.asarray(st.cells.u)
        rho = np.asarray(st.rho)
        mass = np.asarray(st.cells.mass)
        for r in range(plan.nranks):
            own = plan.owned[r]
            if not len(own):
                continue
            dmetrics.state_health(mask[own], vel[own], u[own], rho[own],
                                  mass[own], counts, values, rank=r)

    def _cycle_substeps_host(self, ctx: Dict[str, object]) -> Dict[str, int]:
        """The host-orchestrated ladder: per-rank phase programs with the
        transport's exchanges (host or collective wire) in between."""
        plan: RankPlan = ctx["plan"]
        depth, nsub = ctx["depth"], ctx["nsub"]
        dt_max_c, dt_min = ctx["dt_max_c"], ctx["dt_min"]
        mask_host, u_floor = ctx["mask_host"], ctx["u_floor"]
        nreal = ctx["nreal"]
        tr = self.tracer
        with tr.span("scatter", ranks=range(plan.nranks), collective=1):
            # the prologue's opening kick lands here in the task plot
            tr.fence(self.state.cells.pos)
            states = self._scatter_state(plan)

        updates = 0
        pair_tasks = 0
        force_substeps = 0
        drifted_to = 0
        cycle_exported = 0
        cycle_full = 0
        self.halo_log = []          # latest cycle only (bounded memory)
        bins_h = ctx["bins_host"].copy()
        wake_floor = self._wake_floor(bins_h, mask_host)
        dm_on = self.device_metrics_enabled
        met_counts, met_values = dmetrics.zero_rows(plan.nranks)
        mCI, mVI = dmetrics.COUNT_INDEX, dmetrics.VALUE_INDEX
        alive_per_rank = [int((mask_host[plan.owned[r]] > 0).sum())
                          if len(plan.owned[r]) else 0
                          for r in range(plan.nranks)]
        # per-cell attribution (device-metrics v2): same owned-endpoint
        # rule as the device scatter, accumulated host-side from the pair
        # selections the ladder already computes. The per-rank exchange
        # column here is receiver-side truth (the value column's
        # ``nship // nranks`` split stays approximate on this path).
        cDI = dmetrics.CELL_INDEX
        cellw = cellw_rank = None
        if dm_on:
            cellw, cellw_rank = dmetrics.zero_cell_work(
                self.spec.ncells, plan.nranks)
            alive_cell = (mask_host > 0).sum(axis=1).astype(np.float64)

        def attribute_cells(idxs_r, ship_cells, nexch):
            for r in range(plan.nranks):
                gi = self._ci[idxs_r[r]]
                gj = self._cj[idxs_r[r]]
                tgt = np.where(self._assignment[gi] == r, gi, gj)
                np.add.at(cellw[:, cDI["density"]], tgt, 1.0)
                np.add.at(cellw[:, cDI["force"]], tgt, 1.0)
                cellw_rank[r, cDI["density"]] += len(tgt)
                cellw_rank[r, cDI["force"]] += len(tgt)
                own = plan.owned[r]
                if len(own):
                    cellw[own, cDI["drift"]] += alive_cell[own]
                cellw_rank[r, cDI["drift"]] += alive_per_rank[r]
            for c in ship_cells:
                _, _, imps = plan.cut[c]
                cellw[c, cDI["exchange"]] += nexch * len(imps)
                for (ir, _) in imps:
                    cellw_rank[ir, cDI["exchange"]] += nexch

        # per-cycle host caches: the extended wake floors are rebuilt only
        # when the wake floor itself changes (a wake-up or deepening), not
        # every sub-step
        wake_ext_cache: Dict[int, jax.Array] = {}

        def wake_ext(r):
            if r not in wake_ext_cache:
                wf = np.zeros(plan.K + plan.H, np.int32)
                wf[:len(plan.owned[r])] = wake_floor[plan.owned[r]]
                wf[plan.K:plan.K + len(plan.halo[r])] = \
                    wake_floor[plan.halo[r]]
                wake_ext_cache[r] = jnp.asarray(wf)
            return wake_ext_cache[r]

        for n in range(1, nsub):
            level = active_level(n, depth)
            active_p = ((bins_h >= level)
                        | (bins_h < wake_floor[:, None])) & (mask_host > 0)
            if not active_p.any():
                continue
            active_cells = active_p.any(axis=1)
            ship = self._exchange_set(plan, active_cells)
            slots = plan.ship_slots(ship) if ship else None
            nship = slots.total if slots else 0
            cycle_exported += nship
            cycle_full += plan.cut_slots
            self.halo_log.append({
                "substep": self.substeps + n, "level": level,
                "exported_slots": nship, "full_slots": plan.cut_slots})

            dt_d = jnp.float32((n - drifted_to) * dt_min)
            drifted_to = n
            if tr.enabled:
                tr.ctx["substep"] = n
                active_frac = float(active_p.sum()) / max(nreal, 1)
            subs, pair_bucket = self._rank_pair_subsets(plan, active_cells)
            self.program_keys.add(("density", level, pair_bucket))
            self.program_keys.add(("force", level, pair_bucket))
            phase1 = []
            for r in range(plan.nranks):
                with tr.span("drift", rank=r):
                    states[r] = self._jit_drift(states[r], dt_d)
                    if tr.enabled:
                        tr.fence(states[r].cells.pos)
                sub, pmask, nlive = subs[r]
                d_attrs = {}
                if tr.enabled:
                    d_attrs = dict(level=level, units=nlive, pairs=nlive,
                                   bucket=pair_bucket,
                                   active_frac=active_frac)
                with tr.span("density", rank=r, **d_attrs):
                    act, rho, om, pr, cs = self._jit_sub_density(
                        states[r], sub, pmask, jnp.int32(level), wake_ext(r))
                    if tr.enabled:
                        tr.fence(rho)
                phase1.append([sub, pmask, nlive, act, rho, om, pr, cs])
            # exchange 1: owner's fresh rho/omega/press/cs -> replicas
            if slots:
                fields = [[phase1[r][4 + f] for r in range(plan.nranks)]
                          for f in range(4)]
                fields = self._transport.exchange(slots, fields,
                                                  label="exchange1")
                for r in range(plan.nranks):
                    phase1[r][4:] = [fields[f][r] for f in range(4)]
            for r in range(plan.nranks):
                sub, pmask, nlive, act, rho, om, pr, cs = phase1[r]
                f_attrs = {}
                if tr.enabled:
                    f_attrs = dict(level=level, units=nlive, pairs=nlive,
                                   bucket=pair_bucket,
                                   active_frac=active_frac)
                with tr.span("force", rank=r, **f_attrs):
                    states[r], _ = self._jit_sub_force(
                        states[r], sub, pmask, act, rho, om, pr, cs,
                        wake_ext(r), jnp.float32(dt_max_c), jnp.int32(depth),
                        jnp.float32(u_floor))
                    if tr.enabled:
                        tr.fence(states[r].cells.vel)
            # exchange 2: kicked state of shipped cells -> replicas
            if slots:
                fields = [[getattr(states[r].cells, nm)
                           for r in range(plan.nranks)]
                          for nm in ("vel", "u")]
                fields += [[getattr(states[r], nm)
                            for r in range(plan.nranks)]
                           for nm in ("bins", "t_start", "accel", "dudt")]
                vel, uu, bb, ts, ac, dd = self._transport.exchange(
                    slots, fields, label="exchange2")
                for r in range(plan.nranks):
                    states[r] = states[r]._replace(
                        cells=states[r].cells._replace(
                            vel=vel[r], u=uu[r]),
                        bins=bb[r], t_start=ts[r], accel=ac[r], dudt=dd[r])
            # refresh the global bins mirror (deepening): only ranks whose
            # owned cells were active can have deepened; everyone else's
            # mirror rows are untouched — avoids re-materialising every
            # rank's bins array on every sub-step
            floor_dirty = False
            for r in range(plan.nranks):
                own = plan.owned[r]
                if not len(own) or not active_cells[own].any():
                    continue
                new_bins = np.asarray(states[r].bins)[:len(own)]
                if not np.array_equal(bins_h[own], new_bins):
                    if dm_on:
                        met_counts[r, mCI["deepen_events"]] += int(
                            (bins_h[own] != new_bins).sum())
                    bins_h[own] = new_bins
                    floor_dirty = True
            if floor_dirty:
                new_floor = self._wake_floor(bins_h, mask_host)
                if not np.array_equal(new_floor, wake_floor):
                    wake_floor = new_floor
                    wake_ext_cache.clear()     # invalidate on wake-up
            updates += int(active_p.sum())
            pair_tasks += int((active_cells[self._ci]
                               | active_cells[self._cj]).sum())
            force_substeps += 1
            if dm_on:
                sslots = nship // plan.nranks
                sbytes = sslots * mask_host.shape[1] * 4 \
                    * (_EX1_FIELDS + _EX2_FIELDS)
                for r in range(plan.nranks):
                    own = plan.owned[r]
                    act_r = int(active_p[own].sum()) if len(own) else 0
                    nlive = subs[r][2]
                    met_counts[r] += np.asarray(dmetrics.host_row(
                        substeps=1, drift_active=alive_per_rank[r],
                        density_active=act_r, force_active=act_r,
                        pair_int=nlive, exch_slots=2 * sslots,
                        exch_bytes=sbytes,
                        wake_events=int((bins_h[own]
                                         < wake_floor[own, None]).sum())
                        if len(own) else 0)[0])
                    met_values[r, mVI["density_units"]] += nlive
                    met_values[r, mVI["force_units"]] += nlive
                    met_values[r, mVI["exchange_units"]] += sslots
                    met_values[r, mVI["kick_units"]] += act_r
                attribute_cells(self._select_rank_pairs(plan,
                                                        active_cells)[0],
                                ship, 2.0)

        # final sync sub-step: everyone active, full pair lists, full cut
        dt_d = jnp.float32((nsub - drifted_to) * dt_min)
        if tr.enabled:
            tr.ctx["substep"] = nsub
        subs, pair_bucket = self._rank_pair_subsets(plan, None)
        self.program_keys.add(("final_density", 0, pair_bucket))
        self.program_keys.add(("final_force", 0, pair_bucket))
        phase1 = []
        for r in range(plan.nranks):
            with tr.span("drift", rank=r):
                states[r] = self._jit_drift(states[r], dt_d)
                if tr.enabled:
                    tr.fence(states[r].cells.pos)
            sub, pmask, nlive = subs[r]
            with tr.span("density", rank=r, units=nlive, pairs=nlive,
                         bucket=pair_bucket, active_frac=1.0):
                rho, om, pr, cs = self._jit_final_density(states[r], sub,
                                                          pmask)
                if tr.enabled:
                    tr.fence(rho)
            phase1.append([sub, pmask, nlive, rho, om, pr, cs])
        if plan.cut:
            ship = list(plan.cut.keys())
            slots = plan.ship_slots(ship)
            cycle_exported += slots.total
            cycle_full += plan.cut_slots
            fields = [[phase1[r][3 + f] for r in range(plan.nranks)]
                      for f in range(4)]
            fields = self._transport.exchange(slots, fields, stream="final",
                                              label="exchange_final")
            for r in range(plan.nranks):
                phase1[r][3:] = [fields[f][r] for f in range(4)]
        for r in range(plan.nranks):
            sub, pmask, nlive, rho, om, pr, cs = phase1[r]
            with tr.span("force", rank=r, units=nlive, pairs=nlive,
                         bucket=pair_bucket, active_frac=1.0):
                states[r] = self._jit_final_force(
                    states[r], sub, pmask, rho, om, pr, cs,
                    jnp.float32(dt_max_c))
                if tr.enabled:
                    tr.fence(states[r].cells.vel)
        jax.block_until_ready(states[-1].cells.pos)
        updates += nreal
        pair_tasks += len(self._ci)
        if dm_on:
            fslots = plan.cut_slots // plan.nranks if plan.cut else 0
            fbytes = fslots * mask_host.shape[1] * 4 * _EX1_FIELDS
            for r in range(plan.nranks):
                nlive = subs[r][2]
                met_counts[r] += np.asarray(dmetrics.host_row(
                    substeps=1, drift_active=alive_per_rank[r],
                    density_active=alive_per_rank[r],
                    force_active=alive_per_rank[r],
                    pair_int=nlive, exch_slots=fslots,
                    exch_bytes=fbytes)[0])
                met_values[r, mVI["density_units"]] += nlive
                met_values[r, mVI["force_units"]] += nlive
                met_values[r, mVI["exchange_units"]] += fslots
                met_values[r, mVI["kick_units"]] += alive_per_rank[r]
            attribute_cells(self._select_rank_pairs(plan, None)[0],
                            list(plan.cut) if plan.cut else [], 1.0)

        with tr.span("gather", ranks=range(plan.nranks), collective=1):
            self._gather_state(plan, states)
        if dm_on:
            self._mirror_metrics_finish(plan, met_counts, met_values)
            self.device_cell_work_last = {
                "columns": list(dmetrics.CELL_COLUMNS),
                "cells": cellw, "per_rank": cellw_rank}
            self._metrics_pull(met_counts, met_values)
        else:
            self.device_metrics_last = None
            self.device_cell_work_last = None
        return {"updates": updates, "pair_tasks": pair_tasks,
                "force_substeps": force_substeps,
                "cycle_exported": cycle_exported,
                "cycle_full": cycle_full}

    # ------------------------------------------------- device-resident cycle
    _CELL_FIELDS = STATE_CELL_FIELDS
    _AUX_FIELDS = STATE_AUX_FIELDS
    _FILLS = {"pos": 0.0, "vel": 0.0, "mass": 0.0, "u": 0.0, "h": _PAD_H,
              "mask": 0.0, "accel": 0.0, "dudt": 0.0, "rho": 1.0,
              "omega": 1.0, "bins": 0, "t_start": 0.0}

    def _mesh_sharding(self) -> NamedSharding:
        t = self._transport
        return NamedSharding(t.mesh, P(t.axis))

    def _scatter_resident(self, plan: RankPlan) -> ResidentBuffers:
        """Global mirror → one stacked (nranks, K+H, …) sharded buffer per
        field, placed on the transport mesh for the whole cycle."""
        st = self.state
        sh = self._mesh_sharding()
        place = lambda a: jax.device_put(jnp.asarray(a), sh)
        nrows = plan.K + plan.H
        res = ResidentBuffers(self.transfers)

        def ext_stacked(a, fill):
            a = np.asarray(a)
            out = np.full((plan.nranks, nrows) + a.shape[1:], fill,
                          dtype=a.dtype)
            for r in range(plan.nranks):
                own, hal = plan.owned[r], plan.halo[r]
                out[r, :len(own)] = a[own]
                out[r, plan.K:plan.K + len(hal)] = a[hal]
            return out

        for name in self._CELL_FIELDS:
            res.put(name, ext_stacked(getattr(st.cells, name),
                                      self._FILLS[name]), place)
        for name in self._AUX_FIELDS:
            res.put(name, ext_stacked(getattr(st, name),
                                      self._FILLS[name]), place)
        time_h = np.full((plan.nranks,), float(st.time),
                         dtype=np.asarray(st.cells.pos).dtype)
        res.put("time", time_h, place)
        return res

    def _gather_resident(self, plan: RankPlan, res: ResidentBuffers) -> None:
        """Stacked owned rows → global mirror (halo replicas discarded)."""
        st = self.state
        out = {name: np.asarray(getattr(st, name)).copy()
               for name in self._AUX_FIELDS}
        cells_out = {name: np.asarray(getattr(st.cells, name)).copy()
                     for name in self._CELL_FIELDS}
        # only owned rows come home — halo replicas are discarded anyway,
        # so pulling them would pad the boundary ledger for nothing
        pulled = {name: res.pull(name, index=np.s_[:, :plan.K])
                  for name in self._CELL_FIELDS + self._AUX_FIELDS}
        for r in range(plan.nranks):
            own = plan.owned[r]
            if not len(own):
                continue
            for name in out:
                out[name][own] = pulled[name][r, :len(own)]
            for name in cells_out:
                cells_out[name][own] = pulled[name][r, :len(own)]
        time_h = res.pull("time")
        self.state = TimeBinState(
            cells=ParticleCells(**{k: jnp.asarray(v)
                                   for k, v in cells_out.items()}),
            time=jnp.asarray(time_h[0]),
            **{k: jnp.asarray(v) for k, v in out.items()})

    # ---------------------------------------------- device-scheduled segments
    def _segment_tables(self, plan: RankPlan
                        ) -> Tuple[Dict[str, jax.Array],
                                   Dict[str, jax.Array], Tuple]:
        """Static control tables of one device-scheduled segment.

        These are activity-*independent*: the full touch-pair set per rank (compacted in ascending global pair
        order — the same subsequence every per-level host table is a
        restriction of, so masked scatters fold identical contribution
        sequences), the full-cut exchange tables, and the schedule-deriving
        side tables (per-rank pair ownership for global pair counting, row
        cell ids for the crossing sentinel, the global row gather for
        u_floor). One upload per segment, ledgered as a *boundary*
        transfer: the scanned path has zero intra-segment entries by
        construction.
        """
        t = self._transport
        nranks, nrows = plan.nranks, plan.K + plan.H
        idxs, nmax = self._select_rank_pairs(plan, None)
        splits = []
        imax, cmax = 1, 1
        for r in range(nranks):
            idx = idxs[r]
            halo_pair = ((plan.ci_ext[r][idx] >= plan.K)
                         | (plan.cj_ext[r][idx] >= plan.K))
            splits.append(halo_pair)
            imax = max(imax, int((~halo_pair).sum()))
            cmax = max(cmax, int(halo_pair.sum()))
        # static demand (the full touch set) -> plain next_pow2 buckets;
        # the signature only moves when the partition does
        B, Bi, Bc = next_pow2(nmax), next_pow2(imax), next_pow2(cmax)

        ci = np.zeros((nranks, B), np.int32)
        cj = np.zeros((nranks, B), np.int32)
        shift = np.zeros((nranks, B, 3), self._shift.dtype)
        pmask = np.zeros((nranks, B), np.float32)
        own_pair = np.zeros((nranks, B), np.float32)
        int_pos = np.zeros((nranks, Bi), np.int32)
        int_valid = np.zeros((nranks, Bi), np.float32)
        cut_pos = np.zeros((nranks, Bc), np.int32)
        cut_valid = np.zeros((nranks, Bc), np.float32)
        rowcell = np.full((nranks, nrows), -1, np.int32)
        for r in range(nranks):
            idx, halo_pair = idxs[r], splits[r]
            nlive = len(idx)
            idxp = np.concatenate(
                [idx, np.zeros(B - nlive, dtype=idx.dtype)])
            ci[r] = plan.ci_ext[r][idxp]
            cj[r] = plan.cj_ext[r][idxp]
            shift[r] = self._shift[idxp]
            pmask[r, :nlive] = 1.0
            # a pair is counted by the rank owning its ci cell — a
            # partition of the global pair list, so the psum of live own
            # pairs equals the host's global live-pair count
            own_pair[r, :nlive] = (
                self._assignment[self._ci[idx]] == r).astype(np.float32)
            ipos = np.nonzero(~halo_pair)[0]
            cpos = np.nonzero(halo_pair)[0]
            int_pos[r, :len(ipos)] = ipos
            int_valid[r, :len(ipos)] = 1.0
            cut_pos[r, :len(cpos)] = cpos
            cut_valid[r, :len(cpos)] = 1.0
            own, hal = plan.owned[r], plan.halo[r]
            rowcell[r, :len(own)] = own
            rowcell[r, plan.K:plan.K + len(hal)] = hal

        tables = {"ci": ci, "cj": cj, "shift": shift, "pmask": pmask,
                  "own_pair": own_pair, "int_pos": int_pos,
                  "int_valid": int_valid, "cut_pos": cut_pos,
                  "cut_valid": cut_valid, "rowcell": rowcell}
        slots = plan.ship_slots(list(plan.cut)) if plan.cut else ShipSlots()
        if t.mode == "ppermute":
            Be = next_pow2(max(slots.max_edge_slots, 1))
            pack, unpack, valid = pack_rounds(t.rounds, slots, nranks, Be)
            tables.update(e_pack=pack, e_unpack=unpack, e_valid=valid)
            exch_sig = ("ppermute", Be, t._perms_sig)
        else:
            Bo = next_pow2(max(slots.max_rank_exports(nranks), 1))
            Bn = next_pow2(max(slots.max_rank_imports(nranks), 1))
            pack, usrc, urows, valid = pack_allgather(slots, nranks, Bo, Bn)
            tables.update(e_pack=pack, e_usrc=usrc, e_urows=urows,
                          e_valid=valid)
            exch_sig = ("allgather", Bo, Bn)
        # global cell c lives at flattened all_gather row
        # owner_rank * K + owner_row (the plan program's u_floor gather)
        gidx = np.zeros(self.spec.ncells, np.int32)
        for r in range(nranks):
            own = plan.owned[r]
            if len(own):
                gidx[own] = r * plan.K + np.arange(len(own), dtype=np.int32)
        consts = {"gather_idx": gidx}
        self.transfers.record(
            "segment_tables",
            sum(a.nbytes for a in tables.values()) + gidx.nbytes,
            boundary=True)
        sh = self._mesh_sharding()
        tables = {k: jax.device_put(jnp.asarray(v), sh)
                  for k, v in tables.items()}
        consts = {k: jnp.asarray(v) for k, v in consts.items()}
        sig = (nranks, nrows, plan.K, B, Bi, Bc, exch_sig)
        return tables, consts, sig

    def _cycle_scan_program(self, sig: Tuple, nsub_static: int):
        from .collectives import build_cycle_scan_program
        t = self._transport
        nrows, K = sig[1], sig[2]
        key = ("cycle_scan", nsub_static, self.activity_aware) + sig \
            + (t.mode,)
        return t.programs.get(key, lambda: build_cycle_scan_program(
            t.mesh, t.axis, mode=t.mode, rounds=t.rounds, nrows=nrows, K=K,
            cfg=self.cfg, box=self.box, nsub_static=nsub_static,
            bin_delta=self.bin_delta,
            activity_aware=self.activity_aware))

    def _plan_program(self, sig: Tuple, nsub_static: int):
        from .collectives import build_plan_program
        t = self._transport
        nrows, K = sig[1], sig[2]
        key = ("segment_plan", nsub_static, self.dt_max) + sig + (t.mode,)
        return t.programs.get(key, lambda: build_plan_program(
            t.mesh, t.axis, mode=t.mode, rounds=t.rounds, nrows=nrows, K=K,
            cfg=self.cfg, box=self.box,
            dims=self.spec.dims, max_depth=self.max_depth,
            bin_delta=self.bin_delta, depth_headroom=self.depth_headroom,
            nsub_static=nsub_static, dt_max_static=self.dt_max))

    def _place_scalars(self, vals: Dict[str, np.ndarray]
                       ) -> Dict[str, jax.Array]:
        sh = self._mesh_sharding()
        self.transfers.record(
            "segment_tables",
            sum(np.asarray(v).nbytes for v in vals.values()), boundary=True)
        return {k: jax.device_put(jnp.asarray(v), sh)
                for k, v in vals.items()}

    def _run_segment(self) -> List[Dict]:
        """Run one device-scheduled segment of ``segment_cycles`` cycles.

        Cycle 1 is planned by the host prologue (it also sizes the static
        scan ladder); each further cycle is planned *on device* by the
        plan program, its scalars flowing device-to-device. Between the
        initial scatter and the final gather the host moves zero state or
        schedule bytes — one boundary upload of the static tables, one
        boundary pull of the per-cycle counters/flags at the end
        (``TransferProbe`` shows no intra-segment entries at all). If a
        health sentinel (NaN/Inf/neg-rho), a cell crossing or a
        capacity-overflow flag tripped, the pre-segment state is restored
        and the segment replays on the host-resident ladder —
        bitwise-recoverable by the residency conformance contract.
        """
        tr = self.tracer
        ranks = range(self.nranks)
        K_cycles = self.segment_cycles
        stash = self.state
        # host phases tile the cycle, each one task on every rank's row;
        # none fences: ``wait`` is the one place the host blocks on the
        # device, at the boundary pull it needs anyway
        ctx = self._cycle_prologue()
        plan: RankPlan = ctx["plan"]
        nsub_static = ctx["nsub"]
        with tr.span("scatter", ranks=ranks, collective=1):
            res = self._scatter_resident(plan)
        with tr.span("tables", ranks=ranks, collective=1):
            tables, consts, sig = self._segment_tables(plan)
            cyc_prog = self._cycle_scan_program(sig, nsub_static)
            self.program_keys.add(("cycle_scan", ctx["depth"], sig[3]))
            plan_prog = self._plan_program(sig, nsub_static) \
                if K_cycles > 1 else None
            if plan_prog is not None:
                self.program_keys.add(("segment_plan", ctx["depth"], sig[3]))
            scalars = self._place_scalars({
                "dt_max": np.full(plan.nranks, ctx["dt_max_c"], np.float32),
                "depth": np.full(plan.nranks, ctx["depth"], np.int32),
                "nsub": np.full(plan.nranks, ctx["nsub"], np.int32),
                "u_floor": np.full(plan.nranks, ctx["u_floor"],
                                   np.float32)})
        with tr.span("launch", ranks=ranks, collective=1):
            per_cnt, per_met, per_scal, per_flags = self._launch_segment(
                res, tables, consts, scalars, cyc_prog, plan_prog)
        # ---- ONE boundary pull: every cycle's counters, metrics rows,
        # device-planned scalars and sentinel flags
        with tr.span("wait", ranks=ranks, collective=1):
            pulled_cnt = [{k: np.asarray(v) for k, v in c.items()}
                          for c in per_cnt]
            pulled_met = [(np.asarray(m["counts"]), np.asarray(m["values"]),
                           np.asarray(m["cells"])) for m in per_met]
            pulled_scal = [{k: np.asarray(v) for k, v in s.items()}
                           for s in per_scal]
            pulled_flags = [{k: np.asarray(v) for k, v in f.items()}
                            for f in per_flags]
            nbytes = sum(a.nbytes for grp in pulled_cnt
                         for a in grp.values())
            nbytes += sum(c.nbytes + v.nbytes + w.nbytes
                          for c, v, w in pulled_met)
            nbytes += sum(a.nbytes for grp in pulled_scal
                          for a in grp.values())
            nbytes += sum(a.nbytes for grp in pulled_flags
                          for a in grp.values())
            self.transfers.record("segment_stats", nbytes, boundary=True)
            self.segments += 1

            mci = dmetrics.COUNT_INDEX
            sentinels = sum(
                int(c[:, mci["flag_nan"]].sum() + c[:, mci["flag_inf"]].sum()
                    + c[:, mci["flag_neg_rho"]].sum())
                for c, _, _ in pulled_met)
            crossed = sum(int(f["crossed"][0]) for f in pulled_flags)
            over = sum(int(f["capacity"][0]) for f in pulled_flags)
        if sentinels or crossed or over:
            # sentinel trip: discard the segment (the flagged program's
            # interior state is garbage by contract), restore the
            # pre-segment state and replay on the host-resident ladder —
            # bitwise identical to the reference ladder, NaNs included
            self.segment_aborts += 1
            self.state = stash
            return self._replay_on_host_ladder(K_cycles)

        with tr.span("gather", ranks=ranks, collective=1):
            self._gather_resident(plan, res)
            # the segment's device buffers are released here: left to the
            # frame's teardown, outside every phase, they cost ~14 ms a
            # cycle on four chips
            del res, tables, consts, scalars, per_cnt, per_met, per_scal, \
                per_flags
        with tr.span("repartition", ranks=ranks, collective=1):
            depth_last = int(pulled_scal[-1]["depth"][0])
            self._maybe_repartition(np.asarray(self.state.bins),
                                    np.asarray(self.state.cells.mask),
                                    depth_last)
        if self.rebin_each_cycle:
            with tr.span("rebin", units=ctx["nreal"]):
                self._rebin_state()
        with tr.span("stats", ranks=ranks, collective=1):
            return self._segment_stats(ctx, plan, sig[3], pulled_cnt,
                                       pulled_met, pulled_scal, pulled_flags)

    def _launch_segment(self, res: ResidentBuffers, tables, consts, scalars,
                        cyc_prog, plan_prog) -> Tuple[List, List, List, List]:
        """Dispatch the segment's programs, device to device: each cycle's
        counters, metrics rows, scalars and flags, still on the device."""
        names = self._CELL_FIELDS + self._AUX_FIELDS + ("time",)
        per_cnt, per_met, per_scal, per_flags = [], [], [scalars], []
        for j in range(self.segment_cycles):
            if j > 0:
                state_in = {nm: res[nm] for nm in names}
                upd, scalars, flags = plan_prog(state_in, tables, consts)
                res.update(upd)
                per_scal.append(scalars)
                per_flags.append(flags)
            state_in = {nm: res[nm] for nm in names}
            out_state, cnt, met = cyc_prog(state_in, tables, scalars)
            res.update(out_state)
            per_cnt.append(cnt)
            per_met.append(met)
        return per_cnt, per_met, per_scal, per_flags

    def _segment_stats(self, ctx: Dict[str, object], plan: RankPlan,
                       npairs: int, pulled_cnt: List[Dict],
                       pulled_met: List[Tuple], pulled_scal: List[Dict],
                       pulled_flags: List[Dict]) -> List[Dict]:
        """Per-cycle stats of a finished segment from its boundary pull,
        and the engine's counters advanced by them. ``npairs`` is the
        padded length of a rank's pair table: ``pair_slots``, the slots the
        cycle's pair passes ran over, is at most ``substeps`` times it.
        Each slot is a (``capacity`` × ``capacity``) block per direction;
        ``pair_hits`` entries of the closing trip's table had
        W(r, h_i) > 0 in its density pass."""
        K_cycles = self.segment_cycles
        nreal = ctx["nreal"]
        cut_slots = plan.cut_slots
        self.halo_log = []      # per-sub-step log is host-side only
        dm_on = self.device_metrics_enabled
        stats_list: List[Dict] = []
        for j in range(K_cycles):
            cnt, scal = pulled_cnt[j], pulled_scal[j]
            dt_max_j = float(scal["dt_max"][0])
            depth_j = int(scal["depth"][0])
            nsub_j = int(scal["nsub"][0])
            updates_j = int(cnt["updates"].sum())
            pair_j = int(cnt["pair_tasks"].sum())
            fs_j = int(cnt["force_substeps"][0])
            exported_j = int(cnt["exported"].sum())
            full_j = int(cnt["live_trips"][0]) * cut_slots
            self.particle_updates += updates_j
            self.global_equiv_updates += nsub_j * nreal
            self.substeps += nsub_j
            self.halo_exported_slots += exported_j
            self.halo_full_slots += full_j
            if j == 0:
                hist_j = ctx["hist"]
            else:
                hist_j = pulled_flags[j - 1]["hist"][0, :depth_j + 1]
            stats = {
                "t": float(cnt["t_end"][0]),
                "dt_max": dt_max_j,
                "depth": depth_j,
                "substeps": nsub_j,
                "force_substeps": fs_j + 1,
                "bin_hist": np.asarray(hist_j),
                "updates": updates_j,
                "global_equiv_updates": nsub_j * nreal,
                "pair_tasks": pair_j,
                "global_equiv_pair_tasks": nsub_j * len(self._ci),
                "halo_exported_slots": exported_j,
                "halo_full_slots": full_j,
                "pair_slots": int(cnt["pair_slots"][0]),
                "pair_table_slots": npairs,
                "pair_hits": int(cnt["pair_hits"][0]),
                "capacity": int(ctx["mask_host"].shape[1]),
                "compact_trips": int(cnt["compact_trips"][0]),
                "skipped_trips": int(cnt["skipped_trips"][0]),
                "nranks": plan.nranks,
                "residency": self.residency,
                "schedule": "device",
                "segment_cycles": K_cycles,
            }
            if dm_on:
                stats["_met"] = pulled_met[j][:2]
                stats["_cellw"] = dmetrics.fold_cell_rows(
                    pulled_met[j][2], plan.owned, plan.halo,
                    self.spec.ncells, plan.K)
            stats_list.append(stats)
        if not dm_on:
            self.device_metrics_last = None
            self.device_cell_work_last = None
        return stats_list

    def _replay_on_host_ladder(self, K_cycles: int) -> List[Dict]:
        """Abort path: re-run the segment's cycles on the host-resident
        per-rank ladder over the engine's collective transport, the path
        the conformance tests pin bitwise to the single-rank
        :class:`TimeBinSimulation` reference."""
        out = []
        for _ in range(K_cycles):
            ctx = self._cycle_prologue()
            body = self._cycle_substeps_host(ctx)
            stats = self._cycle_epilogue(ctx, body)
            stats["schedule"] = "device"
            stats["segment_cycles"] = K_cycles
            stats["replayed"] = True
            out.append(stats)
        return out
