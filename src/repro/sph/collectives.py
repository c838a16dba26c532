"""Device-collective transport: shard_map/ppermute halo exchange programs.

The wire lowering of the distributed time-bin engine's two per-sub-step
exchanges (``sph/dist_timebins.py``). Where :class:`~repro.distributed.
transport.HostTransport` copies rows through numpy, this module compiles the
same copies into one XLA program over a rank mesh:

* every rank packs the rows it owes its neighbours into a
  **power-of-two-bucketed export buffer** (mask-padded, so the program's
  shapes — and therefore its compilation — are independent of how many
  cut-cell rows are active at this sub-step);
* the buffers move either through ``lax.ppermute`` rounds — the
  neighbour-to-neighbour schedule derived from the comm planner's export
  edge list (``core.comm_planner.ppermute_rounds``) — or through one
  ``lax.all_gather`` (the fallback when the edge colouring needs more
  rounds than a gather is worth);
* each rank scatters the received slots into its halo replica rows;
  invalid (padding) slots are routed to a scratch row that is sliced off, so
  padded slots provably leave the state untouched.

Exchanges are pure row copies — the collective transport is bit-for-bit
identical to the host transport by construction, which the parity tests in
``tests/test_transport.py`` assert on 1 and 4 (emulated) devices.

Compiled programs are cached by their static signature (bucket, rounds,
field shapes) in a :class:`~repro.distributed.transport.ProgramCache`, and
every build is registered with the engine's :class:`~repro.distributed.
transport.CompileProbe` — the bucket hysteresis guarantees the cache stays
small across sub-steps and cycles.

**Fused sub-step programs** (:func:`build_fused_substep_program`): the
device-resident lowering goes further and compiles a *whole force sub-step*
— drift, density phase, exchange 1, force phase, kick and exchange 2 — into
one shard_map program over the stacked per-rank extended states, so the
state never leaves the mesh between cycle boundaries. The force pair pass
is split into **interior** pairs (both rows owned — their inputs cannot be
touched by exchange 1, so their per-pair math is scheduled against the
exchange rounds instead of behind them) and **cut** pairs (one row is a
halo replica — they wait for the exchanged densities); the two subsets'
contributions are re-assembled *in original pair-list order* and applied in
a single scatter, which keeps the fused program bit-for-bit identical to
the unsplit host-wire phases (:func:`_split_force_pass`).
"""

from __future__ import annotations

import functools
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from ..core.comm_planner import ppermute_rounds
from ..distributed.mesh_utils import ranks_mesh
from ..distributed.transport import (BucketPolicy, CompileProbe, ProgramCache,
                                     ShipSlots, Transport, pack_allgather,
                                     pack_rounds)
from ..observability import device_metrics as dmetrics
from .cellgrid import (PairList, ParticleCells, box_lengths, per_axis,
                       wrap_positions)
from .physics import force_block, sound_speed
from .timebins import (STATE_AUX_FIELDS, STATE_CELL_FIELDS, TimeBinState,
                       _apply_final_kick, _apply_force_kick, _cycle_start,
                       _drift, _substep_density_phase, assign_bins,
                       mass_weighted_mean_u, speed_norm,
                       substep_active_mask, trailing_zeros_table)


# ------------------------------------------------------- in-block row copies
def _permute_copy(loc, pack, unpack, valid, perms, axis: str, nrows: int):
    """ppermute-rounds copy of one field inside a shard_map block.

    ``loc`` (nrows, …) is this rank's field; ``pack``/``unpack``/``valid``
    are its (R, bucket) index tables. Padding slots land on a scratch row
    that is sliced off, so invalid slots provably never touch the state.
    """
    scratch = jnp.zeros((1,) + loc.shape[1:], loc.dtype)
    loc = jnp.concatenate([loc, scratch], axis=0)
    for t in range(len(perms)):
        buf = loc[pack[t]]                               # (bucket, …)
        got = jax.lax.ppermute(buf, axis, perms[t])
        keep = valid[t] > 0
        safe = jnp.where(keep, unpack[t], nrows)
        loc = loc.at[safe].set(got)
    return loc[:nrows]


def _allgather_copy(loc, pack, unpack_src, unpack_rows, valid, axis: str,
                    nrows: int):
    """all-gather fallback copy of one field inside a shard_map block."""
    scratch = jnp.zeros((1,) + loc.shape[1:], loc.dtype)
    loc = jnp.concatenate([loc, scratch], axis=0)
    buf = loc[pack]                                      # (bucket_out, …)
    g = jax.lax.all_gather(buf, axis)                    # (nranks, Bo, …)
    flat = g.reshape((-1,) + g.shape[2:])
    got = flat[unpack_src]                               # (bucket_in, …)
    keep = valid > 0
    safe = jnp.where(keep, unpack_rows, nrows)
    loc = loc.at[safe].set(got)
    return loc[:nrows]


def _named(body, name: str):
    """Name a program's body: ``jax.jit`` calls the program's module
    ``jit_<name>``, the prefix a device trace puts on every operation it
    ran, so a trace says which program each operation belongs to."""
    body.__name__ = body.__qualname__ = name
    return body


def build_permute_program(mesh, axis: str,
                          rounds: Sequence[Sequence[Tuple[int, int]]],
                          nrows: int, bucket: int, nfields: int):
    """Compile one ppermute-rounds exchange over ``nfields`` stacked fields.

    Inputs (global shapes): ``pack``/``unpack`` (nranks, R, bucket) int32,
    ``valid`` (nranks, R, bucket) float, then each field
    (nranks, nrows, …). Returns the fields with every valid received slot
    written into its destination row; everything else bit-identical.
    """
    perms = [list(rnd) for rnd in rounds]

    def body(pack, unpack, valid, *fields):
        return tuple(
            _permute_copy(f[0], pack[0], unpack[0], valid[0], perms, axis,
                          nrows)[None]
            for f in fields)

    fn = shard_map(_named(body, "halo_permute"), mesh=mesh,
                   in_specs=(P(axis),) * (3 + nfields),
                   out_specs=(P(axis),) * nfields)
    return jax.jit(fn)


def build_allgather_program(mesh, axis: str, nrows: int, bucket_out: int,
                            bucket_in: int, nfields: int):
    """Compile the all-gather fallback exchange.

    Inputs: ``pack`` (nranks, bucket_out) int32, ``unpack_src``/
    ``unpack_rows`` (nranks, bucket_in) int32, ``valid`` (nranks,
    bucket_in) float, then the stacked fields.
    """

    def body(pack, unpack_src, unpack_rows, valid, *fields):
        return tuple(
            _allgather_copy(f[0], pack[0], unpack_src[0], unpack_rows[0],
                            valid[0], axis, nrows)[None]
            for f in fields)

    fn = shard_map(_named(body, "halo_allgather"), mesh=mesh,
                   in_specs=(P(axis),) * (4 + nfields),
                   out_specs=(P(axis),) * nfields)
    return jax.jit(fn)


# ------------------------------------------------- interior/cut force split
def _split_force_pass(cells: ParticleCells, pairs: PairList, pair_mask,
                      pre, post, int_pos, int_valid, cut_pos, cut_valid,
                      *, cfg):
    """``engine._force_pass`` with the interior/cut work split.

    ``pre``/``post`` are (rho, press, omega, cs) before/after exchange 1.
    ``int_pos``/``cut_pos`` partition the live pair positions of ``pairs``
    into interior pairs (both rows owned) and cut pairs (one row a halo
    replica), each padded to its own bucket with ``*_valid`` zeros.

    Interior pairs read only owned rows, which exchange 1 never writes, so
    their per-pair contributions are computed from the *pre*-exchange
    fields — with no data dependency on the wire, XLA is free to schedule
    them against the exchange rounds. Cut pairs wait for the exchanged
    densities. Both subsets are then scattered back into **original
    pair-list position** (padding routed to a scratch slot) and applied in
    the same two accumulation ops as ``_force_pass``, so every row folds
    the same contributions in the same order — bit-for-bit identical to
    the unsplit pass over the ``post`` fields.
    """
    B = pairs.ci.shape[0]
    force = functools.partial(force_block, kernel=cfg.kernel,
                              alpha_visc=cfg.alpha_visc)

    def subset(fieldset, pos):
        rho, press, omega, cs = fieldset
        p = jnp.clip(pos, 0, max(B - 1, 0))
        ci, cj = pairs.ci[p], pairs.cj[p]
        shift = pairs.shift[p]
        gi = lambda a: a[ci]
        gj = lambda a: a[cj]
        pos_i = gi(cells.pos)
        pos_j = gj(cells.pos) + shift[:, None, :]
        fij = jax.vmap(force)(
            pos_i, gi(cells.vel), gi(cells.h), gi(press), gi(rho),
            gi(omega), gi(cs),
            pos_j, gj(cells.vel), gj(cells.h), gj(press), gj(rho),
            gj(omega), gj(cs), gj(cells.mass), gj(cells.mask))
        fji = jax.vmap(force)(
            pos_j, gj(cells.vel), gj(cells.h), gj(press), gj(rho),
            gj(omega), gj(cs),
            pos_i, gi(cells.vel), gi(cells.h), gi(press), gi(rho),
            gi(omega), gi(cs), gi(cells.mass), gi(cells.mask))
        return fij, fji

    fij_int, fji_int = subset(pre, int_pos)
    fij_cut, fji_cut = subset(post, cut_pos)

    safe_int = jnp.where(int_valid > 0, int_pos, B)
    safe_cut = jnp.where(cut_valid > 0, cut_pos, B)

    def assemble(int_vals, cut_vals):
        full = jnp.zeros((B + 1,) + int_vals.shape[1:], int_vals.dtype)
        full = full.at[safe_int].set(int_vals)
        full = full.at[safe_cut].set(cut_vals)
        return full[:B]

    dv_ij = assemble(fij_int.dv, fij_cut.dv)
    du_ij = assemble(fij_int.du, fij_cut.du)
    dv_ji = assemble(fji_int.dv, fji_cut.dv)
    du_ji = assemble(fji_int.du, fji_cut.du)

    ncells, cap = cells.mass.shape
    notself = (pairs.ci != pairs.cj).astype(cells.pos.dtype)
    live = jnp.ones_like(notself) if pair_mask is None else pair_mask
    dv = jnp.zeros((ncells, cap, 3), cells.pos.dtype)
    dv = dv.at[pairs.ci].add(dv_ij * live[:, None, None])
    dv = dv.at[pairs.cj].add(dv_ji * (notself * live)[:, None, None])
    du = jnp.zeros((ncells, cap), cells.pos.dtype)
    du = du.at[pairs.ci].add(du_ij * live[:, None])
    du = du.at[pairs.cj].add(du_ji * (notself * live)[:, None])
    return dv, du


# --------------------------------------------------- fused sub-step programs
def build_fused_substep_program(mesh, axis: str, *, mode: str,
                                rounds: Sequence[Sequence[Tuple[int, int]]],
                                nrows: int, K: int, cfg, box,
                                final: bool = False):
    """Compile one whole force sub-step as a single shard_map program.

    The device-resident engine's unit of work: drift → density phase →
    exchange 1 (rho, omega, press, cs) → split force pass → kick/deepen →
    exchange 2 (vel, u, bins, t_start, accel, dudt), all over the stacked
    per-rank extended states, which stay on the mesh. With ``final=True``
    the program is the cycle-closing boundary instead: every particle
    active, closing kick only, no exchange 2.

    Inputs are three pytrees — ``state`` (stacked per-rank field dict,
    sharded over ``axis`` and donated so buffers are reused in place),
    ``tables`` (pair lists, interior/cut split positions, wake floors and
    exchange index tables for this sub-step) and ``scalars`` (replicated
    dt/level/…). Returns the updated state dict, a per-rank ``changed``
    flag (1 iff any owned row's bin deepened — the only signal the host
    needs mid-cycle: it triggers a bins-mirror refresh; the dynamical
    state never leaves the device until the cycle gather), and a per-rank
    :mod:`~repro.observability.device_metrics` row — the in-program
    telemetry counters. The row is an **unconditional** third output:
    its reductions only add consumers to values the physics already
    computes (never producers), so instrumented and uninstrumented runs
    share this one compiled program per signature (zero extra compiles)
    and the state output is bitwise unchanged — both conformance-pinned.
    """
    perms = [list(rnd) for rnd in rounds]

    def xchg(tables, fields):
        if mode == "ppermute":
            return [_permute_copy(f, tables["e_pack"], tables["e_unpack"],
                                  tables["e_valid"], perms, axis, nrows)
                    for f in fields]
        return [_allgather_copy(f, tables["e_pack"], tables["e_usrc"],
                                tables["e_urows"], tables["e_valid"],
                                axis, nrows) for f in fields]

    def body(state, tables, scalars):
        blk = {k: v[0] for k, v in state.items()}
        tbl = {k: v[0] for k, v in tables.items()}
        st = TimeBinState(
            cells=ParticleCells(pos=blk["pos"], vel=blk["vel"],
                                mass=blk["mass"], u=blk["u"], h=blk["h"],
                                mask=blk["mask"]),
            accel=blk["accel"], dudt=blk["dudt"], rho=blk["rho"],
            omega=blk["omega"], bins=blk["bins"], t_start=blk["t_start"],
            time=blk["time"])
        st = _drift(st, scalars["dt_drift"], box=box)
        pairs = PairList(ci=tbl["ci"], cj=tbl["cj"], shift=tbl["shift"])
        pmask = tbl["pmask"]

        if final:
            active = st.cells.mask
        else:
            active = substep_active_mask(st, scalars["level"], tbl["wake"])
        rho, om, pr, cs = _substep_density_phase(st, pairs, pmask, active,
                                                 cfg=cfg)
        rho2, om2, pr2, cs2 = xchg(tbl, [rho, om, pr, cs])
        dv, du = _split_force_pass(
            st.cells, pairs, pmask, (rho, pr, om, cs),
            (rho2, pr2, om2, cs2), tbl["int_pos"], tbl["int_valid"],
            tbl["cut_pos"], tbl["cut_valid"], cfg=cfg)
        if final:
            st = _apply_final_kick(st, dv, du, rho2, om2,
                                   scalars["dt_max"], cfg=cfg)
            changed = jnp.zeros((1,), jnp.int32)
            kicked = jnp.sum((active > 0) & (st.cells.mask > 0))
            deepened = jnp.zeros((), jnp.int32)
            woken = jnp.zeros((), jnp.int32)
            nexch = 1
        else:
            st, kicked = _apply_force_kick(st, active, dv, du, rho2, om2,
                                           tbl["wake"], scalars["dt_max"],
                                           scalars["depth"],
                                           scalars["u_floor"], cfg=cfg)
            vel, uu, bb, ts, ac, dd = xchg(
                tbl, [st.cells.vel, st.cells.u, st.bins, st.t_start,
                      st.accel, st.dudt])
            deepened = jnp.sum(bb[:K] != blk["bins"][:K]
                               ).astype(jnp.int32)
            changed = (deepened > 0).astype(jnp.int32)[None]
            woken = jnp.sum(tbl["wake"] > scalars["level"]
                            ).astype(jnp.int32)
            st = st._replace(cells=st.cells._replace(vel=vel, u=uu),
                             bins=bb, t_start=ts, accel=ac, dudt=dd)
            nexch = 2
        # per-slot wire bytes are static: exchange 1 ships 4 (cap,)
        # fields; exchange 2 ships vel/accel (cap, 3) + u/bins/t_start/
        # dudt (cap,)
        cap = int(st.cells.mass.shape[1])
        slot_bytes = 4 * cap * 4
        if nexch == 2:
            slot_bytes += 10 * cap * 4
        nslots = jnp.sum(tbl["e_valid"] > 0).astype(jnp.int32)
        # telemetry covers the K *owned* rows only — halo mirrors belong
        # to their owner's row, so per-rank work and the summed energy
        # fingerprint match the host-path (no-halo) semantics exactly
        met_counts, met_values = dmetrics.measure_substep(
            mask=st.cells.mask[:K], active=active[:K],
            vel=st.cells.vel[:K], u=st.cells.u[:K],
            mass=st.cells.mass[:K], rho=st.rho[:K],
            live_pairs=jnp.sum(pmask),
            pair_int=jnp.sum(tbl["int_valid"] > 0),
            pair_cut=jnp.sum(tbl["cut_valid"] > 0),
            exch_slots=nslots * nexch, exch_bytes=nslots * slot_bytes,
            deepened=deepened, woken=woken, kicked=kicked)
        # per-cell attribution rides in the same unconditional output
        # pytree (new dict key, same out_specs): owned-row sums equal the
        # drift/density/force columns above, all-row exchange sums equal
        # exchange_units — the identities the 4-rank acceptance pins
        met_cells = dmetrics.measure_cells(
            nrows=nrows, K=K, mask=st.cells.mask[:K], pmask=pmask,
            ci=tbl["ci"], cj=tbl["cj"],
            exch_rows=(tbl["e_unpack"] if mode == "ppermute"
                       else tbl["e_urows"]),
            exch_valid=tbl["e_valid"], nexch=nexch)
        met = {"counts": met_counts[None], "values": met_values[None],
               "cells": met_cells[None]}
        out = {k: getattr(st.cells, k) for k in STATE_CELL_FIELDS}
        out.update({k: getattr(st, k) for k in STATE_AUX_FIELDS})
        out["time"] = st.time
        return {k: v[None] for k, v in out.items()}, changed, met

    fn = shard_map(_named(body, "fused_final" if final else "fused_substep"),
                   mesh=mesh, in_specs=(P(axis), P(axis), P()),
                   out_specs=(P(axis), P(axis), P(axis)))
    return jax.jit(fn, donate_argnums=(0,))


# ------------------------------------------------ device-scheduled segments
# neutral element for integer scatter-max over possibly-empty stencils
# (same value the host planners use in timebins.limit_neighbour_bins)
_NEG_INF_BIN = -10 ** 6
_SCAN_UNROLL = False
# A sparse trip runs its pair passes over a compacted bucket of 1/32 of the
# padded pair table; a table whose bucket would hold fewer than
# _COMPACT_MIN slots runs every live trip over the whole table.
_COMPACT_SHIFT = 5
_COMPACT_MIN = 64
# the cycle scan's per-trip branches, chosen rank-uniformly
SKIP, COMPACT, FULL = 0, 1, 2


def compact_bucket(npairs: int) -> int:
    """Slots of the compacted pair bucket of a padded table of ``npairs``
    pairs (a power of two): 0 where the table is too small for one."""
    slots = npairs >> _COMPACT_SHIFT
    return slots if slots >= _COMPACT_MIN else 0


def _compact_pairs(pairs: PairList, pm, kind, nslots: int, nint: int,
                   ncut: int):
    """The live pairs of ``pm`` packed into ``nslots`` slots, in original
    pair-list order, with their interior/cut split.

    ``kind`` (npairs,) is 1 at the interior positions, 2 at the cut ones.
    Returns the compacted :class:`PairList`, its mask (padding slots 0, so
    they add exact ±0.0) and interior/cut positions into it, each padded to
    ``nint``/``ncut`` slots — the operands :func:`_split_force_pass` takes
    for the whole table. Every row then folds the same contributions in the
    same order as over the whole table, less the exact zeros. The caller
    guarantees at most ``nslots`` live pairs.
    """
    live = pm > 0
    (pos,) = jnp.nonzero(live, size=nslots, fill_value=0)
    filled = jnp.arange(nslots) < jnp.sum(live)
    pm_c = jnp.where(filled, pm[pos], 0.0)
    kind_c = jnp.where(filled, kind[pos], 0)

    def split(k, size):
        sel = kind_c == k
        (at,) = jnp.nonzero(sel, size=size, fill_value=0)
        valid = (jnp.arange(size) < jnp.sum(sel)).astype(pm.dtype)
        return at.astype(jnp.int32), valid

    pairs_c = PairList(ci=pairs.ci[pos], cj=pairs.cj[pos],
                       shift=pairs.shift[pos])
    return (pairs_c, pm_c) + split(1, nint) + split(2, ncut)


def build_cycle_scan_program(mesh, axis: str, *, mode: str,
                             rounds: Sequence[Sequence[Tuple[int, int]]],
                             nrows: int, K: int, cfg, box,
                             nsub_static: int, bin_delta: int,
                             activity_aware: bool = True):
    """Compile one WHOLE cycle — every sub-step — as a single lax.scan.

    The device-scheduled lowering (``schedule="device"``): where
    :func:`build_fused_substep_program` compiles one sub-step and leaves the
    ladder bookkeeping (active levels, pair subsets, ship sets, wake floors)
    to a host loop, this program derives the entire schedule *inside* the
    compiled program from the device-resident ``bins`` array, so the host
    dispatches one call per cycle and reads nothing back until the segment
    boundary.

    Per scan trip n = 1..``nsub_static`` (the static ladder length;
    ``scalars["nsub"]`` may select a shorter dynamic ladder, later trips are
    dead):

    * the active level is ``max(depth − tz[n], 0)`` via a static
      trailing-zeros table;
    * the wake floor is computed from the live bins by pair scatter-max
      before the first trip and after each live one (the host recomputes it
      only on deepen events; these recomputes reach the same fixpoint
      values) and exchanged to halo rows over the full cut, so replica
      activity masks agree with their owners;
    * the pair subset is the *static full-touch table* gated by a dynamic
      mask — a pair is live iff it touches an active cell, exactly the host
      selection rule — and exchange validity is the static full-cut table
      gated by receiver-row activity (activity-aware shipping);
    * each trip takes one of three branches, chosen from values every rank
      shares (the ``psum`` of the owned active counts, the ``pmax`` of the
      ranks' live-pair counts, ``n == nsub``), since the exchanges sit
      inside them:

      - ``SKIP``: no particle is active anywhere and the trip is not the
        last — no pair-table work at all (no pair pass, no exchange, no
        kick, no wake floor, no telemetry row); the state keeps its carry,
        matching the host loop's ``continue`` (lazy drift included — the
        drift span accumulates in a ``drifted_to`` carry);
      - ``COMPACT``: an interior trip whose live pairs fit
        :func:`compact_bucket` on every rank — the pair passes run over the
        live pairs packed in original order (:func:`_compact_pairs`);
      - ``FULL``: the last trip (n == nsub, the cycle-closing kick) or an
        interior trip with more live pairs — the pair passes run over the
        whole table, masked.

    Padded and dead pair slots contribute exact ±0.0 through the same masked
    scatters as the host-scheduled fused path, and a compacted list keeps
    the live pairs' order, so all three branches fold the same sums — the
    bitwise contract is ``assert_array_equal`` (±0.0 and NaN compare
    equal), identical to the existing residency conformance pin.

    On the CPU the scan is **fully unrolled** (``unroll=nsub_static``):
    XLA:CPU's while-loop lowering of a rolled scan changes the
    force-reduction codegen by ~1 ulp versus the straight-line per-sub-step
    programs, which would break the bitwise contract. Unrolling recovers
    the exact straight-line HLO. On any other platform the scan stays
    rolled: an unrolled trip carries two pair-pass bodies (~45 MB of v5e
    code at n_side 60), and sixteen of them doubled the time a v5e takes to
    load the cached program. ``_SCAN_UNROLL`` is a debug hook that swaps in
    a literal Python loop over trips to separate scan-lowering effects from
    body bugs.

    Outputs: the updated state dict (donated buffers), a per-rank counter
    dict (owned active updates, owned live pair tasks, live interior trips,
    exported slots, live trips, pair slots the pair passes ran over, the
    particle–neighbour entries with W(r, h_i) > 0 the closing trip's
    density pass summed over the rank's rows, compacted and skipped trips,
    end-of-cycle time) and the cycle's accumulated device-metrics row —
    counters and health sentinels (NaN/Inf/neg-rho flags) included, so the
    segment driver's one boundary pull sees everything.
    """
    perms = [list(rnd) for rnd in rounds]
    unroll = nsub_static if mesh.devices.flat[0].platform == "cpu" else 1
    tz_np = trailing_zeros_table(nsub_static)
    v_acc = np.asarray(dmetrics._V_ACCUM)
    v_sum = jnp.asarray(v_acc == "sum")
    v_last = jnp.asarray(v_acc == "last")
    v_max = jnp.asarray(v_acc == "max")

    def xchg(tbl, fields, valid):
        if mode == "ppermute":
            return [_permute_copy(f, tbl["e_pack"], tbl["e_unpack"], valid,
                                  perms, axis, nrows) for f in fields]
        return [_allgather_copy(f, tbl["e_pack"], tbl["e_usrc"],
                                tbl["e_urows"], valid, axis, nrows)
                for f in fields]

    def recv_valid(tbl, row_act, is_final):
        """Receiver-side slot validity: full cut on the final trip, active
        rows only in between (the packed send side always ships the whole
        static bucket — validity decides what lands)."""
        full = tbl["e_valid"]
        if not activity_aware:
            return full
        rows = tbl["e_unpack"] if mode == "ppermute" else tbl["e_urows"]
        return jnp.where(is_final, full, full * row_act[rows])

    def fold_values(acc, row, live):
        """Live-gated fold of one metrics value row per ``_V_ACCUM``
        (dmetrics.combine is unconditional — a skipped trip's zero row
        must not leak into last/max/min columns)."""
        upd_sum = acc + jnp.where(live, row, 0.0)
        upd_last = jnp.where(live, row, acc)
        upd_max = jnp.maximum(acc, jnp.where(live, row, -jnp.inf))
        upd_min = jnp.minimum(acc, jnp.where(live, row, jnp.inf))
        return jnp.where(v_sum, upd_sum,
                         jnp.where(v_last, upd_last,
                                   jnp.where(v_max, upd_max, upd_min)))

    def body(state, tables, scalars):
        blk = {k: v[0] for k, v in state.items()}
        tbl = {k: v[0] for k, v in tables.items()}
        dt_max = scalars["dt_max"][0]
        depth = scalars["depth"][0]
        nsub_dyn = scalars["nsub"][0]
        u_floor = scalars["u_floor"][0]
        # dt_min = dt_max / 2**depth: exact power-of-two scaling, so the
        # traced product k·dt_min below is the correctly-rounded f32 of the
        # host's f64 computation (nsub is a power of two)
        dt_min = dt_max * jnp.exp2(-depth.astype(jnp.float32))
        tz = jnp.asarray(tz_np)
        ci, cj, pmask = tbl["ci"], tbl["cj"], tbl["pmask"]
        pairs = PairList(ci=ci, cj=cj, shift=tbl["shift"])
        cap = int(blk["mass"].shape[1])
        fdt = blk["pos"].dtype

        st0 = TimeBinState(
            cells=ParticleCells(pos=blk["pos"], vel=blk["vel"],
                                mass=blk["mass"], u=blk["u"], h=blk["h"],
                                mask=blk["mask"]),
            accel=blk["accel"], dudt=blk["dudt"], rho=blk["rho"],
            omega=blk["omega"], bins=blk["bins"], t_start=blk["t_start"],
            time=blk["time"])
        B = int(ci.shape[0])
        S = compact_bucket(B)
        full_split = (tbl["int_pos"], tbl["int_valid"], tbl["cut_pos"],
                      tbl["cut_valid"])
        if S:
            kind = jnp.zeros((B,), jnp.int32)
            kind = kind.at[tbl["int_pos"]].max(
                jnp.where(tbl["int_valid"] > 0, 1, 0))
            kind = kind.at[tbl["cut_pos"]].max(
                jnp.where(tbl["cut_valid"] > 0, 2, 0))
            S_int = min(S, int(tbl["int_pos"].shape[0]))
            S_cut = min(S, int(tbl["cut_pos"].shape[0]))
        branch_slots = jnp.asarray([0, S, B], jnp.int32)
        cnt0 = {k: jnp.zeros((), jnp.int32)
                for k in ("updates", "pair_tasks", "force_substeps",
                          "exported", "live_trips", "pair_slots",
                          "pair_hits", "compact_trips", "skipped_trips")}
        met_c0 = jnp.zeros((len(dmetrics.COUNT_COLUMNS),), jnp.int32)
        met_v0 = jnp.zeros((len(dmetrics.VALUE_COLUMNS),), jnp.float32)
        met_v0 = met_v0.at[dmetrics.VALUE_INDEX["min_rho"]].set(jnp.inf)
        met_w0 = jnp.zeros((nrows, dmetrics.N_CELL_COLS), jnp.float32)

        mask = st0.cells.mask
        maskb = mask > 0

        def wake_floor(bins):
            """Host ``_wake_floor`` from the live bins, exchanged to halo
            rows over the full cut (owned rows' stencils are complete —
            every pair touching an owned cell is in the touch table). The
            host recomputes it only on deepen events; a skipped trip leaves
            the bins, so the floor is recomputed after each live trip and
            carried — the same fixpoint values."""
            deep = jnp.max(jnp.where(maskb, bins, _NEG_INF_BIN), axis=1)
            nb = deep
            nb = nb.at[ci].max(jnp.where(pmask > 0, deep[cj], _NEG_INF_BIN))
            nb = nb.at[cj].max(jnp.where(pmask > 0, deep[ci], _NEG_INF_BIN))
            wake_own = jnp.maximum(nb - bin_delta, 0).astype(jnp.int32)
            (wake,) = xchg(tbl, [wake_own], tbl["e_valid"])
            return wake

        def trip(carry, n):
            st, drifted_to, wake, cnt, met_c, met_v, met_w = carry
            level = jnp.maximum(depth - tz[n], 0)
            is_final = n == nsub_dyn
            # ---- activity (host substep_active_mask / final mask)
            sub_act = ((st.bins >= level) | (st.bins < wake[:, None])
                       ) & maskb
            active = jnp.where(is_final, mask, sub_act.astype(fdt))
            row_act = jnp.any(sub_act, axis=1).astype(fdt)
            glob_act = jax.lax.psum(jnp.sum(sub_act[:K]).astype(jnp.int32),
                                    axis)
            live = ((glob_act > 0) | is_final) & (n <= nsub_dyn)

            def run():
                # lazy drift of everything since the last live trip
                kdt = (n - drifted_to).astype(jnp.float32) * dt_min
                std = _drift(st, kdt, box=box)
                # the static tables gated by this trip's activity
                pm = jnp.where(is_final, pmask,
                               pmask * jnp.maximum(row_act[ci], row_act[cj]))
                ev = recv_valid(tbl, row_act, is_final)

                def update(pairs_, pm_, split, closing):
                    """Density + exchange 1 + split force (as the fused
                    path) over one pair list, the kick, then exchange 2;
                    with ``closing`` (the full branch) also the density
                    pass's W > 0 entries, a sibling reduction of its sums
                    that every full-table trip computes and the cycle's
                    last trip alone keeps."""
                    rho, om, pr, cs, *hits = _substep_density_phase(
                        std, pairs_, pm_, active, cfg=cfg, hits=closing)
                    hits = (jnp.where(is_final, hits[0], 0) if closing
                            else jnp.zeros((), jnp.int32))
                    rho2, om2, pr2, cs2 = xchg(tbl, [rho, om, pr, cs], ev)
                    dv, du = _split_force_pass(
                        std.cells, pairs_, pm_, (rho, pr, om, cs),
                        (rho2, pr2, om2, cs2), *split, cfg=cfg)

                    def interior_kick():
                        return _apply_force_kick(
                            std, sub_act.astype(fdt), dv, du, rho2, om2,
                            wake, dt_max, depth, u_floor, cfg=cfg)

                    def final_kick():
                        stL = _apply_final_kick(std, dv, du, rho2, om2,
                                                dt_max, cfg=cfg)
                        return stL, jnp.sum((active > 0) & maskb
                                            ).astype(jnp.int32)

                    stK, kicked = (jax.lax.cond(is_final, final_kick,
                                                interior_kick)
                                   if closing else interior_kick())
                    # exchange 2: kicked state -> replicas. Unlike the host
                    # ladder this also runs on the final trip (full
                    # validity), so halo replicas enter the next cycle of a
                    # K>1 segment current; owned rows are untouched by
                    # construction.
                    vel, uu, bb, ts, ac, dd = xchg(
                        tbl, [stK.cells.vel, stK.cells.u, stK.bins,
                              stK.t_start, stK.accel, stK.dudt], ev)
                    return stK._replace(
                        cells=stK.cells._replace(vel=vel, u=uu), bins=bb,
                        t_start=ts, accel=ac, dudt=dd), kicked, hits

                def full():
                    return update(pairs, pm, full_split, closing=True)

                def compact():
                    pairs_c, pm_c, *split_c = _compact_pairs(
                        pairs, pm, kind, S, S_int, S_cut)
                    return update(pairs_c, pm_c, split_c, closing=False)

                # every rank takes the same branch: the exchanges sit inside
                if S:
                    nlive = jax.lax.pmax(jnp.sum(pm > 0).astype(jnp.int32),
                                         axis)
                    branch = jnp.where(~is_final & (nlive <= S), COMPACT,
                                       FULL)
                    stN, kicked, n_hits = jax.lax.cond(branch == COMPACT,
                                                       compact, full)
                else:
                    branch = jnp.int32(FULL)
                    stN, kicked, n_hits = full()
                # counters (owned partial sums; the driver psums on host)
                n_upd = jnp.where(is_final, jnp.sum(maskb[:K]),
                                  jnp.sum(sub_act[:K])).astype(jnp.int32)
                n_pair = jnp.sum((pm > 0) & (tbl["own_pair"] > 0)
                                 ).astype(jnp.int32)
                n_slots = jnp.sum(ev > 0).astype(jnp.int32)
                # telemetry row (mirrors build_fused_substep_program)
                deepened = jnp.where(is_final, 0,
                                     jnp.sum(stN.bins[:K] != st.bins[:K])
                                     ).astype(jnp.int32)
                woken = jnp.where(is_final, 0, jnp.sum(wake > level)
                                  ).astype(jnp.int32)
                nexch = jnp.where(is_final, 1, 2)
                slot_bytes = jnp.where(is_final, 4 * cap * 4,
                                       (4 + 10) * cap * 4)
                mrow_c, mrow_v = dmetrics.measure_substep(
                    mask=stN.cells.mask[:K], active=active[:K],
                    vel=stN.cells.vel[:K], u=stN.cells.u[:K],
                    mass=stN.cells.mass[:K], rho=stN.rho[:K],
                    live_pairs=jnp.sum(pm),
                    pair_int=jnp.sum(jnp.where(tbl["int_valid"] > 0,
                                               pm[tbl["int_pos"]], 0.0)
                                     ).astype(jnp.int32),
                    pair_cut=jnp.sum(jnp.where(tbl["cut_valid"] > 0,
                                               pm[tbl["cut_pos"]], 0.0)
                                     ).astype(jnp.int32),
                    exch_slots=n_slots * nexch,
                    exch_bytes=n_slots * slot_bytes,
                    deepened=deepened, woken=woken, kicked=kicked)
                mrow_w = dmetrics.measure_cells(
                    nrows=nrows, K=K, mask=stN.cells.mask[:K], pmask=pm,
                    ci=ci, cj=cj,
                    exch_rows=(tbl["e_unpack"] if mode == "ppermute"
                               else tbl["e_urows"]),
                    exch_valid=ev, nexch=nexch)
                return (stN, wake_floor(stN.bins), branch,
                        (n_upd, n_pair, n_slots, n_hits),
                        (mrow_c, mrow_v, mrow_w))

            def skip():
                # no pair pass, no exchange, no kick: every state carry,
                # the wake floor included, stays bit-identical
                zero = jnp.zeros((), jnp.int32)
                return (st, wake, jnp.int32(SKIP), (zero, zero, zero, zero),
                        (jnp.zeros_like(met_c), jnp.zeros_like(met_v),
                         jnp.zeros_like(met_w)))

            stN, wake_new, branch, (n_upd, n_pair, n_slots, n_hits), \
                (mrow_c, mrow_v, mrow_w) = jax.lax.cond(live, run, skip)
            cnt_new = {
                "updates": cnt["updates"] + n_upd,
                "pair_tasks": cnt["pair_tasks"] + n_pair,
                "force_substeps": cnt["force_substeps"]
                + (live & ~is_final).astype(jnp.int32),
                "exported": cnt["exported"] + n_slots,
                "live_trips": cnt["live_trips"] + live.astype(jnp.int32),
                "pair_slots": cnt["pair_slots"] + branch_slots[branch],
                "pair_hits": cnt["pair_hits"] + n_hits,
                "compact_trips": cnt["compact_trips"]
                + (branch == COMPACT).astype(jnp.int32),
                "skipped_trips": cnt["skipped_trips"]
                + ((branch == SKIP) & (n <= nsub_dyn)).astype(jnp.int32),
            }
            met_c_new = met_c + mrow_c
            met_v_new = fold_values(met_v, mrow_v, live)
            met_w_new = met_w + mrow_w
            drifted_new = jnp.where(live, n, drifted_to)
            return (stN, drifted_new, wake_new, cnt_new, met_c_new,
                    met_v_new, met_w_new), None

        xs = jnp.arange(1, nsub_static + 1, dtype=jnp.int32)
        carry0 = (st0, jnp.int32(0), wake_floor(st0.bins), cnt0, met_c0,
                  met_v0, met_w0)
        if _SCAN_UNROLL:        # debug hook: straight-line trips
            carry = carry0
            for n in range(1, nsub_static + 1):
                carry, _ = trip(carry, jnp.int32(n))
            stE, _, _, cnt, met_c, met_v, met_w = carry
        else:
            (stE, _, _, cnt, met_c, met_v, met_w), _ = jax.lax.scan(
                trip, carry0, xs, unroll=unroll)
        out = {k: getattr(stE.cells, k) for k in STATE_CELL_FIELDS}
        out.update({k: getattr(stE, k) for k in STATE_AUX_FIELDS})
        out["time"] = stE.time
        cnt_out = {k: v[None] for k, v in cnt.items()}
        cnt_out["t_end"] = stE.time[None]
        met = {"counts": met_c[None], "values": met_v[None],
               "cells": met_w[None]}
        return ({k: v[None] for k, v in out.items()}, cnt_out, met)

    fn = shard_map(_named(body, "cycle_scan"), mesh=mesh,
                   in_specs=(P(axis), P(axis), P(axis)),
                   out_specs=(P(axis), P(axis), P(axis)), check_vma=False)
    return jax.jit(fn, donate_argnums=(0,))


def build_plan_program(mesh, axis: str, *, mode: str,
                       rounds: Sequence[Sequence[Tuple[int, int]]],
                       nrows: int, K: int, cfg, box,
                       dims: Tuple[int, int, int], max_depth: int,
                       bin_delta: int,
                       depth_headroom: int, nsub_static: int,
                       dt_max_static: Optional[float] = None):
    """Compile the between-cycles prologue of a K>1 device segment.

    Everything ``TimeBinSimulation._plan_cycle`` + the distributed
    prologue do on the host — signal-velocity CFL field, bin assignment,
    neighbour-limiter fixpoint, cycle depth, u_floor, opening half-kick —
    expressed over the resident extended blocks, plus the two segment
    sentinels the scanned path needs:

    * ``crossed``: any owned particle's cell id (identical f32 op sequence
      as ``cellgrid.bin_particles``) differs from its resident row's cell —
      the host epilogue's re-bin would have changed the layout, so the
      segment must abort and replay host-scheduled;
    * ``capacity``: the new cycle wants more sub-steps than the compiled
      scan's static ladder (deepening beyond headroom) — same abort.

    Bitwise notes: every reduction is either order-free (min/max/compare)
    or the pinned tree fold (u_floor via all_gather + a static global
    row-gather), and the scalar chain reproduces the host's f32 rounding
    (verified by the conformance rows). The limiter runs as a
    ``while_loop`` Jacobi iteration with a full-cut exchange and a psum'd
    convergence test per sweep — the same monotone fixpoint the host
    reaches. Not donated: only four fields come back, the rest of the
    resident buffers stay live.
    """
    perms = [list(rnd) for rnd in rounds]
    box = box_lengths(box)
    cell_size = per_axis(tuple(L / n for L, n in zip(box, dims)))
    _, ny, nz = dims

    def xchg_full(tbl, fields):
        if mode == "ppermute":
            return [_permute_copy(f, tbl["e_pack"], tbl["e_unpack"],
                                  tbl["e_valid"], perms, axis, nrows)
                    for f in fields]
        return [_allgather_copy(f, tbl["e_pack"], tbl["e_usrc"],
                                tbl["e_urows"], tbl["e_valid"], axis,
                                nrows) for f in fields]

    def body(state, tables, consts):
        blk = {k: v[0] for k, v in state.items()}
        tbl = {k: v[0] for k, v in tables.items()}
        gidx = consts["gather_idx"]
        pos, vel, mass = blk["pos"], blk["vel"], blk["mass"]
        u, h, mask = blk["u"], blk["h"], blk["mask"]
        maskb = mask > 0
        cap = int(mass.shape[1])
        ci, cj, pmask = tbl["ci"], tbl["cj"], tbl["pmask"]

        # ---- crossing sentinel (cellgrid.bin_particles' id math)
        posw = wrap_positions(pos, box)
        idx3 = jnp.floor(posw / cell_size).astype(jnp.int32)
        idx3 = jnp.clip(idx3, 0, jnp.asarray(dims, jnp.int32) - 1)
        cellid = (idx3[..., 0] * ny + idx3[..., 1]) * nz + idx3[..., 2]
        crossed = jax.lax.psum(
            jnp.sum((cellid[:K] != tbl["rowcell"][:K, None]) & maskb[:K]
                    ).astype(jnp.int32), axis)

        # ---- signal-velocity CFL field (timebins._signal_speeds)
        cs = sound_speed(jnp.ones_like(u), u, cfg.gamma)
        v = speed_norm(vel)
        speed = jnp.where(maskb, cs + v, 0.0)
        s_cell = jnp.max(speed, axis=1)
        s_nb = s_cell
        s_nb = s_nb.at[ci].max(jnp.where(pmask > 0, s_cell[cj], 0.0))
        s_nb = s_nb.at[cj].max(jnp.where(pmask > 0, s_cell[ci], 0.0))
        dts = cfg.cfl * h / jnp.maximum(s_nb[:, None], 1e-12)
        dts = jnp.where(maskb, dts, jnp.inf)
        dt_min_req = jax.lax.pmin(jnp.min(dts[:K]), axis)
        if dt_max_static is not None:
            dt_max_c0 = jnp.float32(dt_max_static)
        else:
            dt_max_c0 = jax.lax.pmax(
                jnp.max(jnp.where(maskb[:K], dts[:K], -jnp.inf)), axis)
        dt_max_c = jnp.minimum(jnp.float32(dt_max_c0),
                               jnp.float32(dt_min_req)
                               * jnp.float32(2.0 ** max_depth))

        # ---- bin assignment + neighbour limiter fixpoint
        bins0 = assign_bins(dts, dt_max_c, max_depth)
        bins0 = jnp.where(maskb, bins0, 0).astype(jnp.int32)
        deep0 = jnp.max(jnp.where(maskb, bins0, _NEG_INF_BIN), axis=1)
        # halo rows' locally-computed deep/bins are incomplete (their
        # stencil is only complete on their owner); exchange before and
        # inside every sweep so halos always mirror owners
        (deep0,) = xchg_full(tbl, [deep0])

        def lim_cond(sv):
            i, _, ch = sv
            return (i < 256) & (ch > 0)

        def lim_step(sv):
            i, deep, _ = sv
            nb = deep
            nb = nb.at[ci].max(jnp.where(pmask > 0, deep[cj],
                                         _NEG_INF_BIN))
            nb = nb.at[cj].max(jnp.where(pmask > 0, deep[ci],
                                         _NEG_INF_BIN))
            new = jnp.maximum(deep, nb - bin_delta)
            (newx,) = xchg_full(tbl, [new])
            ch = jax.lax.psum(jnp.sum((newx[:K] != deep[:K])
                                      ).astype(jnp.int32), axis)
            return (i + 1, newx, ch)

        _, deep, _ = jax.lax.while_loop(
            lim_cond, lim_step, (jnp.int32(0), deep0, jnp.int32(1)))
        nb = deep
        nb = nb.at[ci].max(jnp.where(pmask > 0, deep[cj], _NEG_INF_BIN))
        nb = nb.at[cj].max(jnp.where(pmask > 0, deep[ci], _NEG_INF_BIN))
        floor = jnp.clip(nb - bin_delta, 0, max_depth)
        bins1 = jnp.where(maskb, jnp.maximum(bins0, floor[:, None]), bins0)
        bins1 = jnp.where(maskb, bins1, 0).astype(jnp.int32)
        (bins,) = xchg_full(tbl, [bins1])

        occ = jnp.maximum(jax.lax.pmax(
            jnp.max(jnp.where(maskb[:K], bins[:K], _NEG_INF_BIN)), axis), 0)
        depth = jnp.minimum(occ + depth_headroom, max_depth
                            ).astype(jnp.int32)
        nsub = jnp.left_shift(jnp.int32(1), depth)
        over = (nsub > nsub_static).astype(jnp.int32)
        # owned-bin histogram, psum'd: the host-side cycle stats' bin_hist
        # without pulling the bins array
        levels = jnp.arange(max_depth + 1, dtype=jnp.int32)
        hist = jax.lax.psum(
            jnp.sum((bins[:K][..., None] == levels) & maskb[:K][..., None],
                    axis=(0, 1)).astype(jnp.int32), axis)

        # ---- u_floor: pinned tree fold over the global (ncells, cap)
        # reconstruction (all_gather + static row gather), bitwise equal
        # to the host prologue's mass_weighted_mean_u
        mm = (mass * mask)[:K]
        gm = jax.lax.all_gather(mm, axis).reshape(-1, cap)[gidx]
        gu = jax.lax.all_gather(u[:K], axis).reshape(-1, cap)[gidx]
        u_floor = mass_weighted_mean_u(gm, gu)

        # ---- opening half-kick with the new bins (timebins._cycle_start)
        st = TimeBinState(
            cells=ParticleCells(pos=pos, vel=vel, mass=mass, u=u, h=h,
                                mask=mask),
            accel=blk["accel"], dudt=blk["dudt"], rho=blk["rho"],
            omega=blk["omega"], bins=bins, t_start=blk["t_start"],
            time=blk["time"])
        st2 = _cycle_start(st, dt_max_c, cfg=cfg)

        upd = {"bins": bins[None], "vel": st2.cells.vel[None],
               "u": st2.cells.u[None], "t_start": st2.t_start[None]}
        scal = {"dt_max": dt_max_c[None], "depth": depth[None],
                "nsub": nsub[None], "u_floor": jnp.float32(u_floor)[None]}
        flags = {"crossed": crossed[None], "capacity": over[None],
                 "hist": hist[None]}
        return upd, scal, flags

    fn = shard_map(_named(body, "segment_plan"), mesh=mesh,
                   in_specs=(P(axis), P(axis), P()),
                   out_specs=(P(axis), P(axis), P(axis)), check_vma=False)
    return jax.jit(fn)


class CollectiveTransport(Transport):
    """shard_map/ppermute lowering of the halo exchange.

    Holds the rank mesh, the round schedule of the current decomposition,
    the bucket policy and the compiled-program cache. ``prepare(edges)`` is
    called whenever the decomposition (and hence the export edge list)
    changes; ``exchange`` runs one compiled collective step.
    """

    kind = "collective"

    def __init__(self, *, nranks: int, probe: Optional[CompileProbe] = None,
                 mode: str = "auto", axis: str = "ranks",
                 min_bucket: int = 8, shrink_patience: int = 4):
        if mode not in ("auto", "ppermute", "allgather"):
            raise ValueError(f"mode must be auto|ppermute|allgather, "
                             f"got {mode!r}")
        self.nranks = int(nranks)
        self.axis = axis
        self.mesh = ranks_mesh(self.nranks, axis=axis)
        self.mode_requested = mode
        self.buckets = BucketPolicy(min_bucket=min_bucket,
                                    shrink_patience=shrink_patience)
        self.programs = ProgramCache(probe)
        self.rounds: List[List[Tuple[int, int]]] = []
        self._perms_sig: Tuple = ()
        self._edges: Optional[Tuple[Tuple[int, int], ...]] = None
        self.exchanges = 0
        self.shipped_rows = 0
        self.host_bytes = 0

    # ------------------------------------------------------------- planning
    def prepare(self, edges: Sequence[Tuple[int, int]]) -> None:
        edges_t = tuple(sorted({(int(s), int(d)) for s, d in edges}))
        if edges_t == self._edges:
            return
        self._edges = edges_t
        self.rounds = ppermute_rounds(edges_t, self.nranks)
        self._perms_sig = tuple(tuple(rnd) for rnd in self.rounds)

    @property
    def mode(self) -> str:
        if self.mode_requested != "auto":
            return self.mode_requested
        # neighbour-to-neighbour rounds beat a gather while the edge
        # colouring stays within the ring bound; degenerate cuts (more
        # rounds than ranks) fall back to one all_gather
        return "ppermute" if len(self.rounds) < self.nranks else "allgather"

    # ------------------------------------------------------------- exchange
    def exchange(self, slots: ShipSlots, fields: List[List],
                 stream: str = "substep",
                 label: Optional[str] = None) -> List[List]:
        if self._edges is None:
            raise RuntimeError("CollectiveTransport.exchange before "
                               "prepare(edges)")
        nranks = self.nranks
        # the outs_h materialisation below is the sync point: when the span
        # closes the whole collective (pack + wire + scatter) has
        # completed, so it covers the one program as a task on every
        # rank's row
        with self.tracer.span(label or "exchange", ranks=range(nranks),
                              stream=stream, mode=self.mode,
                              units=slots.total, kind="collective",
                              collective=1) as sp:
            nrows = int(np.shape(fields[0][0])[0])
            meta = tuple((tuple(np.shape(f[0])[1:]),
                          np.dtype(jnp.asarray(f[0]).dtype).name)
                         for f in fields)
            stacked = [jnp.stack([jnp.asarray(fr) for fr in f])
                       for f in fields]
            if self.mode == "ppermute":
                B = self.buckets.fit(("edge", stream), slots.max_edge_slots)
                pack, unpack, valid = pack_rounds(self.rounds, slots, nranks,
                                                  B)
                key = ("ppermute", nranks, nrows, B, self._perms_sig, meta)
                prog = self.programs.get(key, lambda: build_permute_program(
                    self.mesh, self.axis, self.rounds, nrows, B,
                    len(fields)))
                outs = prog(jnp.asarray(pack), jnp.asarray(unpack),
                            jnp.asarray(valid), *stacked)
                sp.set(bucket=B)
            else:
                Bo = self.buckets.fit(("ag_out", stream),
                                      slots.max_rank_exports(nranks))
                Bi = self.buckets.fit(("ag_in", stream),
                                      slots.max_rank_imports(nranks))
                pack, usrc, urows, valid = pack_allgather(slots, nranks, Bo,
                                                          Bi)
                key = ("allgather", nranks, nrows, Bo, Bi, meta)
                prog = self.programs.get(key, lambda: build_allgather_program(
                    self.mesh, self.axis, nrows, Bo, Bi, len(fields)))
                outs = prog(jnp.asarray(pack), jnp.asarray(usrc),
                            jnp.asarray(urows), jnp.asarray(valid), *stacked)
                sp.set(bucket=max(Bo, Bi))
            self.exchanges += 1
            self.shipped_rows += slots.total
            # normalise placement: slicing a mesh-sharded output yields
            # arrays committed to individual devices, which would make every
            # downstream phase program recompile per device. Round-tripping
            # through host memory (what the host transport does anyway)
            # keeps the phase programs' compile count identical across
            # transports. This round trip — device→host→device of every
            # full field — is exactly the residual overhead the fused
            # device-resident path (residency="device") removes;
            # host_bytes measures it.
            outs_h = [np.asarray(out) for out in outs]
            self.host_bytes += 2 * sum(o.nbytes for o in outs_h)
        return [[jnp.asarray(o[r]) for r in range(nranks)] for o in outs_h]

    def stats(self) -> Dict[str, object]:
        return {"kind": self.kind, "mode": self.mode,
                "rounds": len(self.rounds), "exchanges": self.exchanges,
                "shipped_rows": self.shipped_rows,
                "host_bytes": self.host_bytes,
                "programs": self.programs.builds,
                "bucket_events": list(self.buckets.events)}
