"""Cell-grid decomposition of the simulation volume (paper §3.1).

    "the domain is first decomposed into a grid of cells of edge length
    larger or equal to the largest particle radius […] if two particles are
    close enough to interact, they are either in the same cell or they span
    a pair of neighbouring cells."

TPU adaptation (DESIGN.md §8.3): cells are *padded* to a fixed capacity — a
multiple of the TPU sublane/lane tile — so every ``density_pair`` /
``force_pair`` task is a dense (C × C) block computation. Host-side binning
(numpy) builds the padded layout; the jitted step never reshapes.

The half-stencil pair list realises SWIFT's symmetric pair tasks: each
unordered neighbouring cell pair appears exactly once, with the periodic
image shift carried alongside so the kernel can work with plain Euclidean
distances (see physics.py docstring).

The periodic box has an edge length per axis (``box_lengths``), and the grid
a cell count per axis: a 2 × 0.5 × 0.5 shock tube is 4·n × n × n cells. A
cubic box builds exactly the arrays of a one-length box.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, NamedTuple, Optional, Sequence, Tuple, Union

import numpy as np
import jax
import jax.numpy as jnp


class ParticleCells(NamedTuple):
    """Padded per-cell particle arrays (leading dims: ncells, capacity)."""
    pos: jax.Array     # (ncells, C, 3)
    vel: jax.Array     # (ncells, C, 3)
    mass: jax.Array    # (ncells, C)    0 for padded slots
    u: jax.Array       # (ncells, C)    internal energy
    h: jax.Array       # (ncells, C)    smoothing length
    mask: jax.Array    # (ncells, C)    1.0 real, 0.0 padded


class PairList(NamedTuple):
    """Half-stencil cell pairs. ``shift`` is the periodic image offset to be
    *added to cell j's positions* when interacting with cell i."""
    ci: jax.Array      # (npairs,) int32
    cj: jax.Array      # (npairs,) int32
    shift: jax.Array   # (npairs, 3) float


Box = Tuple[float, float, float]


def box_lengths(box: Union[float, Sequence[float]]) -> Box:
    """The periodic box's edge lengths along x, y, z, from one length (a
    cube) or three; a tuple, so program caches can key on it."""
    lengths = np.ravel(np.asarray(box, np.float64))
    if lengths.size == 1:
        lengths = np.repeat(lengths, 3)
    if lengths.size != 3 or not np.all(lengths > 0):
        raise ValueError(f"a box is one positive length or three, got "
                         f"{box!r}")
    return tuple(float(x) for x in lengths)


def per_axis(values: Sequence[float]) -> np.ndarray:
    """``values`` as a float32 (3,) array that broadcasts per axis against
    (…, 3) positions; for a cube every element computes the bits the one
    length did."""
    return np.asarray(values, np.float32)


def wrap_positions(pos, box):
    """Traced positions (…, 3) wrapped into the periodic box."""
    return jnp.mod(pos, per_axis(box_lengths(box)))


@dataclass(frozen=True)
class GridSpec:
    box: Box
    dims: Tuple[int, int, int]     # cells along x, y, z
    capacity: int

    @property
    def ncells(self) -> int:
        return int(np.prod(self.dims))

    @property
    def cell_size(self) -> Box:
        return tuple(L / n for L, n in zip(self.box, self.dims))


def choose_grid(box, h_max: float, num_particles: int, *,
                capacity_margin: float = 2.5,
                min_capacity: int = 8) -> GridSpec:
    """Pick cells per axis so every cell edge is ≥ h_max, and a padded
    capacity sized for the mean occupancy with head-room (clustered ICs are
    rebalanced by the recursive split in SWIFT; here extra-dense cells
    simply raise capacity)."""
    box = box_lengths(box)
    dims = tuple(max(int(np.floor(L / max(h_max, 1e-12))), 1) for L in box)
    ncells = int(np.prod(dims))
    mean_occ = num_particles / ncells
    cap = int(np.ceil(mean_occ * capacity_margin))
    cap = max(cap, min_capacity)
    # round capacity up to a multiple of 8 (TPU sublane)
    cap = ((cap + 7) // 8) * 8
    return GridSpec(box=box, dims=dims, capacity=cap)


def bin_particles(spec: GridSpec, pos: np.ndarray, vel: np.ndarray,
                  mass: np.ndarray, u: np.ndarray, h: np.ndarray,
                  *, grow: bool = True) -> Tuple[ParticleCells, np.ndarray]:
    """Host-side binning into the padded cell layout.

    Returns (cells, perm) where ``perm[c, k]`` is the original particle index
    in cell c slot k (−1 for padding) — used to scatter state back out.
    Raises if a cell overflows and ``grow`` is False; otherwise capacity is
    grown to fit (keeps physics exact for pathological clustering).
    """
    # lengths in the positions' own dtype, as in the device program's
    # crossing check; clipped and flattened in place (this runs every cycle)
    posw = np.mod(pos, np.asarray(spec.box, pos.dtype))
    idx3 = np.floor(posw / np.asarray(spec.cell_size, pos.dtype)
                    ).astype(np.int64)
    np.minimum(idx3, np.asarray(spec.dims) - 1, out=idx3)
    np.maximum(idx3, 0, out=idx3)
    _, ny, nz = spec.dims
    flat = idx3[:, 0] * (ny * nz)
    flat += idx3[:, 1] * nz
    flat += idx3[:, 2]
    counts = np.bincount(flat, minlength=spec.ncells)
    cap = spec.capacity
    if counts.max() > cap:
        if not grow:
            raise ValueError(
                f"cell overflow: max occupancy {counts.max()} > capacity {cap}")
        cap = int(((counts.max() + 7) // 8) * 8)
    perm = np.full((spec.ncells, cap), -1, dtype=np.int64)
    slot = np.zeros(spec.ncells, dtype=np.int64)
    order = np.argsort(flat, kind="stable")
    for p in order:
        c = flat[p]
        perm[c, slot[c]] = p
        slot[c] += 1

    def take(arr, fill):
        out = np.full((spec.ncells, cap) + arr.shape[1:], fill,
                      dtype=np.float32)
        valid = perm >= 0
        out[valid] = arr[perm[valid]]
        return out

    cells = ParticleCells(
        pos=jnp.asarray(take(posw.astype(np.float32), 0.0)),
        vel=jnp.asarray(take(vel.astype(np.float32), 0.0)),
        mass=jnp.asarray(take(mass.astype(np.float32)[:, None], 0.0)[..., 0]),
        u=jnp.asarray(take(u.astype(np.float32)[:, None], 0.0)[..., 0]),
        h=jnp.asarray(take(h.astype(np.float32)[:, None], 1e-6)[..., 0]),
        mask=jnp.asarray((perm >= 0).astype(np.float32)),
    )
    return cells, perm


def unbin(cells: ParticleCells, perm: np.ndarray, n: int) -> Dict[str, np.ndarray]:
    """Scatter padded cell arrays back to flat particle arrays."""
    valid = perm >= 0
    idx = perm[valid]
    out = {}
    for name in ("pos", "vel", "mass", "u", "h"):
        arr = np.asarray(getattr(cells, name))
        flat = arr[valid]
        shaped = np.zeros((n,) + arr.shape[2:], dtype=arr.dtype)
        shaped[idx] = flat
        out[name] = shaped
    return out


_HALF_STENCIL = [(dz, dy, dx)
                 for dz in (-1, 0, 1)
                 for dy in (-1, 0, 1)
                 for dx in (-1, 0, 1)][14:]   # lexicographic upper half (13)


def build_pair_list(spec: GridSpec, *, include_self: bool = True) -> PairList:
    """Half-stencil periodic cell-pair list with image shifts."""
    nx, ny, nz = spec.dims
    Lx, Ly, Lz = spec.box
    # only an axis of one cell can wrap a neighbour onto its own cell;
    # tested once here, not per entry (this loop runs every rebin)
    tiny = min(spec.dims) <= 2
    ci_l, cj_l, sh_l = [], [], []

    def flat(i, j, k):
        return (i * ny + j) * nz + k

    for i in range(nx):
        for j in range(ny):
            for k in range(nz):
                c = flat(i, j, k)
                if include_self:
                    ci_l.append(c)
                    cj_l.append(c)
                    sh_l.append((0.0, 0.0, 0.0))
                for (dz, dy, dx) in _HALF_STENCIL:
                    ii, jj, kk = i + dz, j + dy, k + dx
                    # periodic wrap + record the image shift of cell j
                    # relative to cell i (added to x_j to undo the wrap)
                    sz = -Lx if ii >= nx else (Lx if ii < 0 else 0.0)
                    sy = -Ly if jj >= ny else (Ly if jj < 0 else 0.0)
                    sx = -Lz if kk >= nz else (Lz if kk < 0 else 0.0)
                    n2 = flat(ii % nx, jj % ny, kk % nz)
                    if tiny and n2 == c:
                        continue
                    ci_l.append(c)
                    cj_l.append(n2)
                    # the unwrapped j position is pos_j + shift
                    sh_l.append((-sz, -sy, -sx))
    return PairList(ci=jnp.asarray(np.array(ci_l, dtype=np.int32)),
                    cj=jnp.asarray(np.array(cj_l, dtype=np.int32)),
                    shift=jnp.asarray(np.array(sh_l, dtype=np.float32)))
