"""Device-side telemetry rows: in-program counters for compiled programs.

PR-5's tracer times phase *programs* from the host, fencing at every
phase boundary. That goes blind exactly where the engines are headed:
once a whole cycle is one compiled shard_map scan (and a fleet batch one
``jit(vmap(step))``), the host sees a single opaque span.
Following SWIFT's rule that every task reports its own cost from inside
the runtime (arXiv:1606.02738 §4) — and the in-kernel per-bin counter
idiom of task-based runtimes — this module defines a ``DeviceMetrics``
carry: two fixed-shape buffers **computed inside the compiled program**,

* ``counts`` — int32 ``(N_COUNTS,)`` per rank: sub-step executions,
  per-phase active-particle counts, live interior/cut pair counts,
  exchange slots and bytes, deepening/wake events, and health sentinel
  trips (NaN / Inf / non-positive density);
* ``values`` — float32 ``(N_VALUES,)`` per rank: per-phase accumulated
  work units (the asymptotic units the cost model runs on) plus a
  compact state fingerprint (total energy, |momentum|, max speed,
  min density) for the flight recorder.

The carry is **always present**: instrumented and uninstrumented runs
execute the *same* compiled program (the metrics row is an unconditional
extra output whose reductions only read values the physics already
computes), so enabling device metrics adds **zero compiles** per shape
signature and is bitwise invisible to the state — both pinned in
``tests/test_observability.py`` / ``tests/test_conformance.py``.
Accumulation across sub-steps happens on device (inside the cycle
scan's carry); the accumulated row is pulled **once per cycle** and
ledgered
through the engine's :class:`~repro.distributed.transport.TransferProbe`.

Nothing here imports jax at module scope (package rule: the CLI must be
able to set ``XLA_FLAGS`` before jax loads); in-program helpers import
``jax.numpy`` lazily.
"""

from __future__ import annotations

import math
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

DEVICE_METRICS_VERSION = 2

COUNT_COLUMNS: Tuple[str, ...] = (
    "substeps",         # sub-step program executions folded into this row
    "drift_active",     # particles drifted (alive mask count)
    "density_active",   # particles active in the density phase
    "force_active",     # particles kicked in the force phase
    "pair_int",         # live interior pair blocks
    "pair_cut",         # live cut (halo-crossing) pair blocks
    "exch_slots",       # halo slots shipped across both exchanges
    "exch_bytes",       # bytes moved through the exchanges
    "deepen_events",    # owned rows whose time bin deepened mid-cycle
    "wake_events",      # cells woken above the current ladder level
    "flag_nan",         # sub-steps on which any state value went NaN
    "flag_inf",         # ... or infinite
    "flag_neg_rho",     # ... or produced a non-positive density
)
VALUE_COLUMNS: Tuple[str, ...] = (
    "density_units",    # live pair blocks worked in the density phase
    "force_units",      # live pair blocks worked in the force phase
    "exchange_units",   # shipped halo slots (send/recv work units)
    "kick_units",       # particles integrated by the kick
    "energy_total",     # fingerprint: sum m·(u + v²/2) over alive rows
    "momentum_abs",     # fingerprint: |Σ m·v|
    "max_speed",        # fingerprint: max |v| over alive rows
    "min_rho",          # fingerprint: min density over alive rows
)
N_COUNTS = len(COUNT_COLUMNS)
N_VALUES = len(VALUE_COLUMNS)

# Per-cell work vectors (device-metrics version 2). One float32 row per
# *extended* cell row (owned rows first, halo replicas after), integer
# valued — work units stay far below 2**24 per cycle so float32 adds are
# exact. Drift/density/force land on owned rows; exchange is counted
# receiver-side on the halo rows it unpacks into (folded back onto the
# owner cell on the host, so no slot is ever double-counted).
CELL_COLUMNS: Tuple[str, ...] = (
    "drift",      # alive particles drifted in this cell's rows
    "density",    # live pair blocks attributed to this cell (density)
    "force",      # live pair blocks attributed to this cell (force)
    "exchange",   # halo slots unpacked for this cell (recv-side units)
)
N_CELL_COLS = len(CELL_COLUMNS)
CELL_INDEX = {name: i for i, name in enumerate(CELL_COLUMNS)}

# how each value column folds across sub-steps within one cycle
_V_ACCUM: Tuple[str, ...] = ("sum", "sum", "sum", "sum",
                             "last", "last", "max", "min")
_FLAG_COLUMNS = ("flag_nan", "flag_inf", "flag_neg_rho")
COUNT_INDEX = {name: i for i, name in enumerate(COUNT_COLUMNS)}
VALUE_INDEX = {name: i for i, name in enumerate(VALUE_COLUMNS)}
_CI = COUNT_INDEX
_VI = VALUE_INDEX


def zero_rows(nranks: int = 1):
    """Host-side zero accumulator: ``(counts, values)`` numpy buffers of
    shape ``(nranks, N_COUNTS)`` / ``(nranks, N_VALUES)``."""
    counts = np.zeros((nranks, N_COUNTS), np.int64)
    values = np.zeros((nranks, N_VALUES), np.float64)
    values[..., _VI["min_rho"]] = np.inf
    return counts, values


# --------------------------------------------------------------- in-program
def measure_substep(*, mask, active, vel, u, mass, rho,
                    live_pairs, pair_int, pair_cut,
                    exch_slots, exch_bytes, deepened, woken, kicked):
    """Build one per-rank metrics row *inside* a compiled program.

    Every argument is a jax value already flowing through the fused
    sub-step body (masks, post-kick state fields, live pair/slot counts)
    — the reductions here add consumers to the existing dataflow but
    never feed back into it, which is what keeps the carry bitwise
    invisible to the physics. Returns ``(counts int32[N_COUNTS],
    values float32[N_VALUES])``.
    """
    import jax.numpy as jnp

    alive = mask > 0
    f32 = jnp.float32
    nan_hit = (jnp.any(jnp.isnan(vel) & alive[..., None])
               | jnp.any(jnp.isnan(u) & alive)
               | jnp.any(jnp.isnan(rho) & alive))
    inf_hit = (jnp.any(jnp.isinf(vel) & alive[..., None])
               | jnp.any(jnp.isinf(u) & alive)
               | jnp.any(jnp.isinf(rho) & alive))
    neg_rho = jnp.any((rho <= 0) & alive & (active > 0))

    counts = jnp.stack([
        jnp.ones((), jnp.int32),
        jnp.sum(alive).astype(jnp.int32),
        jnp.sum((active > 0) & alive).astype(jnp.int32),
        jnp.asarray(kicked, jnp.int32).reshape(()),
        jnp.asarray(pair_int, jnp.int32).reshape(()),
        jnp.asarray(pair_cut, jnp.int32).reshape(()),
        jnp.asarray(exch_slots, jnp.int32).reshape(()),
        jnp.asarray(exch_bytes, jnp.int32).reshape(()),
        jnp.asarray(deepened, jnp.int32).reshape(()),
        jnp.asarray(woken, jnp.int32).reshape(()),
        nan_hit.astype(jnp.int32),
        inf_hit.astype(jnp.int32),
        neg_rho.astype(jnp.int32),
    ])

    m = jnp.where(alive, mass, 0.0)
    speed = jnp.sqrt(jnp.sum(vel * vel, axis=-1))
    energy = jnp.sum(m * (u + 0.5 * speed * speed))
    mom = jnp.sqrt(jnp.sum(jnp.sum(m[..., None] * vel,
                                   axis=tuple(range(vel.ndim - 1))) ** 2))
    values = jnp.stack([
        jnp.asarray(live_pairs, f32).reshape(()),
        jnp.asarray(pair_int + pair_cut, f32).reshape(()),
        jnp.asarray(exch_slots, f32).reshape(()),
        jnp.asarray(kicked, f32).reshape(()),
        energy.astype(f32),
        mom.astype(f32),
        jnp.max(jnp.where(alive, speed, 0.0)).astype(f32),
        jnp.min(jnp.where(alive, rho, jnp.inf)).astype(f32),
    ])
    return counts, values


def measure_cells(*, nrows: int, K: int, mask, pmask, ci, cj,
                  exch_rows=None, exch_valid=None, nexch=1):
    """Per-cell work vector of one sub-step, *inside* a compiled program.

    Returns a float32 ``(nrows, N_CELL_COLS)`` buffer over this rank's
    extended rows. Attribution rules (the identities the tests pin):

    * drift — alive-particle count per owned row (rows ``[0, K)``); the
      owned-row sum equals the ``drift_active`` count column.
    * density/force — each live pair block is charged to its *owned*
      endpoint (``ci`` when ``ci < K``, else ``cj``; the pair tables
      guarantee at least one endpoint is owned). The owned-row sums
      equal the ``density_units``/``force_units`` value columns.
    * exchange — ``nexch`` units per valid slot, charged receiver-side
      at the row the slot unpacks into. The all-row sum equals the
      ``exchange_units`` value column; the host fold maps halo rows
      back onto owner cells.

    Like :func:`measure_substep`, every input already flows through the
    fused body, so the scatters only add consumers — never producers —
    to the physics dataflow (bitwise invisible, zero extra compiles).
    Row ``nrows`` is a scratch row: invalid entries scatter there and
    are sliced away.
    """
    import jax.numpy as jnp

    f32 = jnp.float32
    cw = jnp.zeros((nrows + 1, N_CELL_COLS), f32)

    alive = jnp.sum((mask > 0).astype(f32), axis=-1)
    cw = cw.at[:K, CELL_INDEX["drift"]].set(alive[:K])

    pm = jnp.asarray(pmask, f32).reshape(-1)
    ci = jnp.asarray(ci).reshape(-1)
    cj = jnp.asarray(cj).reshape(-1)
    owner = jnp.where(ci < K, ci, cj)
    tgt = jnp.where(pm > 0, owner, nrows)
    cw = cw.at[tgt, CELL_INDEX["density"]].add(pm)
    cw = cw.at[tgt, CELL_INDEX["force"]].add(pm)

    if exch_rows is not None:
        ev = jnp.asarray(exch_valid, f32).reshape(-1)
        rows = jnp.asarray(exch_rows).reshape(-1)
        et = jnp.where(ev > 0, rows, nrows)
        cw = cw.at[et, CELL_INDEX["exchange"]].add(
            ev * jnp.asarray(nexch, f32))
    return cw[:nrows]


def zero_cell_work(ncells: int, nranks: int = 1):
    """Host-side zero accumulator for per-cell attribution: a global
    ``(ncells, N_CELL_COLS)`` float64 buffer plus a per-rank
    ``(nranks, N_CELL_COLS)`` totals buffer."""
    return (np.zeros((ncells, N_CELL_COLS), np.float64),
            np.zeros((nranks, N_CELL_COLS), np.float64))


def fold_cell_rows(cell_rows, owned: Sequence[np.ndarray],
                   halo: Sequence[np.ndarray], ncells: int,
                   K: int) -> Dict[str, object]:
    """Fold pulled per-rank extended-row buffers onto global cells.

    ``cell_rows`` is the stacked ``(nranks, nrows, N_CELL_COLS)`` device
    output; ``owned[r]``/``halo[r]`` map rank ``r``'s rows to global cell
    ids (owned rows from 0, halo rows from the shared owned-slot count
    ``K``). Halo rows only ever carry exchange units, which fold onto
    the *owner* cell's global entry — each shipped slot is counted
    exactly once. Returns the engine's ``device_cell_work_last``
    contract dict.
    """
    rows = np.asarray(cell_rows, np.float64)
    nranks = rows.shape[0]
    cells = np.zeros((ncells, N_CELL_COLS), np.float64)
    per_rank = np.zeros((nranks, N_CELL_COLS), np.float64)
    for r in range(nranks):
        own = np.asarray(owned[r], np.int64)
        hal = np.asarray(halo[r], np.int64) if r < len(halo) else \
            np.zeros(0, np.int64)
        np.add.at(cells, own, rows[r, :len(own)])
        if len(hal):
            np.add.at(cells, hal, rows[r, K:K + len(hal)])
        per_rank[r] = rows[r].sum(axis=0)
    return {"columns": list(CELL_COLUMNS), "cells": cells,
            "per_rank": per_rank}


def cell_work_record(cell_work: Optional[Dict[str, object]]) \
        -> Optional[Dict[str, object]]:
    """Compact per-record shape for metrics schema v3: columns, per-rank
    totals and global totals (the full per-cell vector stays on the
    engine — JSONL records would balloon at ncells scale)."""
    if not cell_work:
        return None
    per_rank = np.asarray(cell_work["per_rank"], np.float64)
    cells = np.asarray(cell_work["cells"], np.float64)
    return {
        "columns": list(cell_work["columns"]),
        "per_rank": [[float(x) for x in row] for row in per_rank.tolist()],
        "totals": [float(x) for x in cells.sum(axis=0).tolist()],
        "ncells": int(cells.shape[0]),
    }


def host_row(**named) -> Tuple[np.ndarray, np.ndarray]:
    """Build one 1-D ``(counts, values)`` row from host-side python
    scalars (the host-transport and local-ladder paths, which already
    hold these numbers). Unnamed columns default to zero (``min_rho``
    to +inf)."""
    counts = np.zeros(N_COUNTS, np.int64)
    values = np.zeros(N_VALUES, np.float64)
    values[_VI["min_rho"]] = np.inf
    for k, v in named.items():
        if k in _CI:
            counts[_CI[k]] = int(v)
        elif k in _VI:
            values[_VI[k]] = float(v)
        else:
            raise KeyError(f"unknown device-metrics column {k!r}")
    return counts, values


def state_health(mask, vel, u, rho, mass, counts, values, rank: int = 0,
                 active=None) -> None:
    """Fill one rank's sentinel flags + fingerprint columns in place from
    host-visible (numpy) state arrays — the host-residency paths'
    equivalent of the in-program reductions in :func:`measure_substep`.
    ``mask``/``vel``/``u``/``rho``/``mass`` are that rank's rows."""
    alive = np.asarray(mask) > 0
    vel = np.asarray(vel)
    u = np.asarray(u)
    rho = np.asarray(rho)
    mass = np.asarray(mass)
    counts[rank, _CI["flag_nan"]] += int(
        np.isnan(vel[alive]).any() or np.isnan(u[alive]).any()
        or np.isnan(rho[alive]).any())
    counts[rank, _CI["flag_inf"]] += int(
        np.isinf(vel[alive]).any() or np.isinf(u[alive]).any()
        or np.isinf(rho[alive]).any())
    neg = alive & (rho <= 0)
    if active is not None:
        neg &= np.asarray(active) > 0
    counts[rank, _CI["flag_neg_rho"]] += int(neg.any())
    m = np.where(alive, mass, 0.0)
    speed = np.sqrt((vel * vel).sum(axis=-1))
    values[rank, _VI["energy_total"]] = float(
        (m * (u + 0.5 * speed * speed)).sum())
    values[rank, _VI["momentum_abs"]] = float(np.sqrt(
        ((m[..., None] * vel).sum(axis=tuple(range(vel.ndim - 1)))
         ** 2).sum()))
    values[rank, _VI["max_speed"]] = float(speed[alive].max()) \
        if alive.any() else 0.0
    values[rank, _VI["min_rho"]] = float(rho[alive].min()) \
        if alive.any() else np.inf


# ------------------------------------------------------------- host summary
def _clean(x: float) -> Optional[float]:
    return None if (x is None or not math.isfinite(x)) else float(x)


def summarize(counts, values) -> Dict[str, object]:
    """Host-side digest of a pulled ``(nranks, N)`` metrics row pair.

    The per-record shape exported under ``device_metrics`` in schema-v2
    metrics records: raw per-rank columns plus the derived per-rank work
    (density+force units), the work imbalance (max/mean — SWIFT's
    figure of merit), and the sentinel flags.
    """
    c = np.atleast_2d(np.asarray(counts))
    v = np.atleast_2d(np.asarray(values))
    per_rank_work = (v[:, _VI["density_units"]]
                     + v[:, _VI["force_units"]]).astype(float)
    mean = float(per_rank_work.mean()) if per_rank_work.size else 0.0
    imb = float(per_rank_work.max() / mean) if mean > 0 else None
    flags = {name: int(c[:, _CI[name]].sum()) for name in _FLAG_COLUMNS}
    return {
        "version": DEVICE_METRICS_VERSION,
        "count_columns": list(COUNT_COLUMNS),
        "value_columns": list(VALUE_COLUMNS),
        "counts": c.astype(int).tolist(),
        "values": [[_clean(x) for x in row] for row in v.tolist()],
        "per_rank_work": per_rank_work.tolist(),
        "imbalance": imb,
        "flags": flags,
        "tripped": any(flags.values()),
    }


def fingerprint(values) -> List[Dict[str, Optional[float]]]:
    """Per-rank compact state fingerprint from a pulled values row."""
    v = np.atleast_2d(np.asarray(values))
    keys = ("energy_total", "momentum_abs", "max_speed", "min_rho")
    return [{k: _clean(row[_VI[k]]) for k in keys} for row in v.tolist()]


def phase_units(summary: Dict[str, object]) -> Dict[str, float]:
    """Total per-phase work units from a ``summarize()`` dict — what the
    observer feeds into ``CostModel.observe`` for fully fused runs."""
    vals = np.asarray(summary["values"], dtype=object)
    cols = list(summary["value_columns"])

    def col(name: str) -> float:
        i = cols.index(name)
        return float(sum(0.0 if x is None else float(x)
                         for x in vals[:, i]))

    return {"density": col("density_units"), "force": col("force_units"),
            "exchange": col("exchange_units"), "kick": col("kick_units")}
