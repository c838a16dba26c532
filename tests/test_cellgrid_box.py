"""The cell grid of a periodic box with three edge lengths.

A 2 × 0.5 × 0.5 shock-tube box is gridded with a cell count per axis; its
half-stencil pair list must hold every neighbour pair once, with the image
shift that gives the minimum-image separation. A cubic box must build
exactly the grid, layout and pair list of the one-length code it replaced,
kept here as the reference.
"""

import numpy as np
import pytest

from repro.sph.cellgrid import (bin_particles, box_lengths, build_pair_list,
                                choose_grid, per_axis, wrap_positions)
from repro.sph.ic import sodshock_ic, uniform_ic


def _cubic_grid(box, h_max, n, margin):
    """The one-length ``choose_grid``: (cells per side, capacity)."""
    ns = max(int(np.floor(box / max(h_max, 1e-12))), 1)
    cap = max(int(np.ceil(n / ns ** 3 * margin)), 8)
    return ns, ((cap + 7) // 8) * 8


def _cubic_cell_ids(pos, box, ns):
    posw = np.mod(pos, box)
    idx3 = np.clip(np.floor(posw / (box / ns)).astype(np.int64), 0, ns - 1)
    return posw, (idx3[:, 0] * ns + idx3[:, 1]) * ns + idx3[:, 2]


def _cubic_pairs(ns, box):
    """The one-length ``build_pair_list``, as lists."""
    stencil = [(a, b, c) for a in (-1, 0, 1) for b in (-1, 0, 1)
               for c in (-1, 0, 1)][14:]
    flat = lambda i, j, k: (i * ns + j) * ns + k      # noqa: E731
    ci, cj, sh = [], [], []
    for i in range(ns):
        for j in range(ns):
            for k in range(ns):
                c = flat(i, j, k)
                ci.append(c)
                cj.append(c)
                sh.append((0.0, 0.0, 0.0))
                for dz, dy, dx in stencil:
                    ii, jj, kk = i + dz, j + dy, k + dx
                    sz = -box if ii >= ns else (box if ii < 0 else 0.0)
                    sy = -box if jj >= ns else (box if jj < 0 else 0.0)
                    sx = -box if kk >= ns else (box if kk < 0 else 0.0)
                    n2 = flat(ii % ns, jj % ns, kk % ns)
                    if ns <= 2 and n2 == c:
                        continue
                    ci.append(c)
                    cj.append(n2)
                    sh.append((-sz, -sy, -sx))
    return (np.array(ci, np.int32), np.array(cj, np.int32),
            np.array(sh, np.float32))


@pytest.mark.parametrize("n_side,box", [(3, 1.0), (6, 0.7), (9, 1.0),
                                        (12, 2.5)])
@pytest.mark.parametrize("as_three", [False, True])
def test_cubic_box_builds_the_one_length_grid(n_side, box, as_three):
    ic = uniform_ic(n_side, box=box, seed=n_side)
    given = (box,) * 3 if as_three else box
    h_max = float(ic["h"].max())
    spec = choose_grid(given, h_max, len(ic["pos"]), capacity_margin=3.0)
    ns, cap = _cubic_grid(box, h_max, len(ic["pos"]), 3.0)
    assert spec.box == (box,) * 3 and spec.dims == (ns,) * 3
    assert spec.capacity == cap and spec.ncells == ns ** 3
    assert spec.cell_size == (box / ns,) * 3

    cells, perm = bin_particles(spec, ic["pos"], ic["vel"], ic["mass"],
                                ic["u"], ic["h"])
    posw, flat = _cubic_cell_ids(ic["pos"], box, ns)
    want = np.full_like(perm, -1)
    for c in range(ns ** 3):
        mine = np.flatnonzero(flat == c)
        want[c, :len(mine)] = mine
    np.testing.assert_array_equal(perm, want)
    valid = perm >= 0
    np.testing.assert_array_equal(np.asarray(cells.pos)[valid],
                                  posw.astype(np.float32)[perm[valid]])

    pairs = build_pair_list(spec)
    for got, ref in zip((pairs.ci, pairs.cj, pairs.shift),
                        _cubic_pairs(ns, box)):
        got = np.asarray(got)
        assert got.dtype == ref.dtype
        np.testing.assert_array_equal(got, ref)
        # ±0.0 shifts too: the same bits, not only equal values
        assert got.tobytes() == ref.tobytes()


def test_box_lengths():
    assert box_lengths(0.5) == (0.5, 0.5, 0.5)
    assert box_lengths([2, 0.5, 0.5]) == (2.0, 0.5, 0.5)
    assert box_lengths(np.float32(1.0)) == (1.0, 1.0, 1.0)
    for bad in ((1.0, 2.0), (1.0, -1.0, 1.0), 0.0):
        with pytest.raises(ValueError, match="box"):
            box_lengths(bad)


@pytest.mark.parametrize("box,ns", [(1.0, 60), (0.7, 7), (2.5, 13)])
def test_cubic_box_wraps_and_bins_as_one_length_on_device(box, ns):
    """The traced drift wrap, the crossing check's cell index and the
    minimum image over per-axis lengths give a cube the bits of one
    length."""
    import jax
    import jax.numpy as jnp
    rng = np.random.default_rng(ns)
    pos = jnp.asarray(rng.uniform(-0.3 * box, 1.3 * box, (4096, 3)),
                      jnp.float32)
    lengths, cells = per_axis((box,) * 3), per_axis((box / ns,) * 3)
    pairs = [
        (lambda p: jnp.mod(p, box), lambda p: wrap_positions(p, box)),
        (lambda p: jnp.floor(jnp.mod(p, box) / (box / ns)),
         lambda p: jnp.floor(wrap_positions(p, box) / cells)),
        (lambda p: p - box * jnp.round(p / box),
         lambda p: p - lengths * jnp.round(p / lengths)),
    ]
    for one, three in pairs:
        assert (np.asarray(jax.jit(one)(pos)).tobytes()
                == np.asarray(jax.jit(three)(pos)).tobytes())


def test_long_box_pair_list_holds_every_neighbour_once():
    """Brute force on the tube at n_side 14: every pair of particles closer
    than max(h_i, h_j) by minimum image is reached through exactly one
    entry of the pair list, whose shift gives that separation."""
    ic = sodshock_ic(14, seed=5)
    pos, h = ic["pos"].astype(np.float64), ic["h"].astype(np.float64)
    box = np.asarray(ic["box"])
    spec = choose_grid(ic["box"], float(h.max()), len(pos),
                       capacity_margin=3.0)
    assert spec.dims == (12, 3, 3) and spec.box == (2.0, 0.5, 0.5)
    cells, perm = bin_particles(spec, ic["pos"], ic["vel"], ic["mass"],
                                ic["u"], ic["h"])
    cell_of = np.empty(len(pos), np.int64)
    cell_of[perm[perm >= 0]] = np.nonzero(perm >= 0)[0]
    pairs = build_pair_list(spec)
    ci, cj = np.asarray(pairs.ci), np.asarray(pairs.cj)
    shift = np.asarray(pairs.shift, np.float64)
    assert len(ci) == spec.ncells * 14
    entry = np.full((spec.ncells, spec.ncells), -1)
    entry[ci, cj] = np.arange(len(ci))
    # each unordered cell pair once (three or more cells on every axis)
    assert len({(min(a, b), max(a, b)) for a, b in zip(ci, cj)}) == len(ci)

    posw = np.empty_like(pos)
    posw[perm[perm >= 0]] = np.asarray(cells.pos, np.float64)[perm >= 0]
    checked = 0
    for start in range(0, len(pos), 256):
        d = pos[start:start + 256, None, :] - pos[None, :, :]
        d -= box * np.round(d / box)
        r = np.sqrt(np.sum(d * d, axis=-1))
        reach = np.maximum(h[start:start + 256, None], h[None, :])
        a, j = np.nonzero(r < reach)
        i = start + a
        fwd = entry[cell_of[i], cell_of[j]]
        back = np.where(cell_of[i] == cell_of[j], -1,
                        entry[cell_of[j], cell_of[i]])
        # exactly one entry holds the pair: (cell i, cell j) with the
        # separation x_i - (x_j + shift), or its mirror with the sign turned
        assert np.all((fwd >= 0) != (back >= 0))
        sep = np.where((fwd >= 0)[:, None],
                       posw[i] - posw[j] - shift[fwd],
                       posw[i] - posw[j] + shift[back])
        np.testing.assert_allclose(sep, d[a, j], atol=1e-6)
        checked += len(i)
    assert checked > 48 * len(pos)
