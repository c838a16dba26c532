"""The host rebin's cell layout: binning and the pair list.

``bin_particles`` fills the padded cell slots with one scatter; the
per-particle loop it replaced is kept here as the oracle, and every case
must give the same ``perm`` and the same six cell arrays, bit for bit.

The pair list depends on the grid's dims and box alone, so a simulation
builds it once, when the grid is chosen, and every rebin reuses it — while
the ``rebin.pair_list`` span still opens on every rebin.
"""

import json

import numpy as np
import pytest

from repro.sph import build_simulation
from repro.sph.cellgrid import (bin_particles, build_pair_list, choose_grid)
from repro.sph.ic import sodshock_ic, uniform_ic

from test_observability import REBIN_PARTS, _device_schedule_spec


def _loop_bin(spec, pos, vel, mass, u, h, *, grow=True):
    """The per-particle slot loop: (perm, cell arrays) as numpy."""
    posw = np.mod(pos, np.asarray(spec.box, pos.dtype))
    idx3 = np.floor(posw / np.asarray(spec.cell_size, pos.dtype)
                    ).astype(np.int64)
    idx3 = np.clip(idx3, 0, np.asarray(spec.dims) - 1)
    _, ny, nz = spec.dims
    flat = (idx3[:, 0] * ny + idx3[:, 1]) * nz + idx3[:, 2]
    counts = np.bincount(flat, minlength=spec.ncells)
    cap = spec.capacity
    if counts.max() > cap:
        if not grow:
            raise ValueError("cell overflow")
        cap = int(((counts.max() + 7) // 8) * 8)
    perm = np.full((spec.ncells, cap), -1, dtype=np.int64)
    slot = np.zeros(spec.ncells, dtype=np.int64)
    for p in np.argsort(flat, kind="stable"):
        c = flat[p]
        perm[c, slot[c]] = p
        slot[c] += 1

    def take(arr, fill):
        out = np.full((spec.ncells, cap) + arr.shape[1:], fill,
                      dtype=np.float32)
        valid = perm >= 0
        out[valid] = arr[perm[valid]]
        return out

    cells = dict(
        pos=take(posw.astype(np.float32), 0.0),
        vel=take(vel.astype(np.float32), 0.0),
        mass=take(mass.astype(np.float32)[:, None], 0.0)[..., 0],
        u=take(u.astype(np.float32)[:, None], 0.0)[..., 0],
        h=take(h.astype(np.float32)[:, None], 1e-6)[..., 0],
        mask=(perm >= 0).astype(np.float32))
    return perm, cells


def _cube():
    return uniform_ic(8, box=1.0, seed=3), 1.0, 3.0


def _sod():
    ic = sodshock_ic(4, seed=5)
    return ic, ic["box"], 3.0


def _clustered():
    """A uniform lattice plus a tight clump that overflows its cell."""
    ic = uniform_ic(6, box=1.0, seed=7)
    rng = np.random.default_rng(7)
    clump = 0.41 + 0.02 * rng.random((40, 3))
    n = len(clump)
    ic = {k: np.concatenate([v, (clump if k == "pos" else v[:n])])
          for k, v in ic.items() if k in ("pos", "vel", "mass", "u", "h")}
    return ic, 1.0, 1.5


def _half_empty():
    """Particles in the lower half of x only: half the cells are empty."""
    ic = uniform_ic(8, box=1.0, seed=11)
    keep = ic["pos"][:, 0] < 0.5
    return ({k: ic[k][keep] for k in ("pos", "vel", "mass", "u", "h")},
            1.0, 3.0)


def _spec(ic, box, margin):
    return choose_grid(box, float(ic["h"].max()), len(ic["pos"]),
                       capacity_margin=margin)


@pytest.mark.parametrize("make", [_cube, _sod, _clustered, _half_empty],
                         ids=["jittered_cube", "sod_box", "clustered_grow",
                              "empty_cells"])
def test_scatter_fill_matches_the_slot_loop(make):
    ic, box, margin = make()
    spec = _spec(ic, box, margin)
    args = (ic["pos"], ic["vel"], ic["mass"], ic["u"], ic["h"])
    cells, perm = bin_particles(spec, *args)
    want_perm, want = _loop_bin(spec, *args)
    assert perm.dtype == want_perm.dtype
    np.testing.assert_array_equal(perm, want_perm)
    for name, arr in want.items():
        got = np.asarray(getattr(cells, name))
        assert got.dtype == arr.dtype and got.shape == arr.shape, name
        assert got.tobytes() == arr.tobytes(), name
    if make is _clustered:
        assert perm.shape[1] > spec.capacity       # the capacity grew
    if make is _half_empty:
        assert not (perm >= 0).any(axis=1).all()   # some cells are empty


def test_overflow_without_grow_raises():
    ic, box, margin = _clustered()
    spec = _spec(ic, box, margin)
    args = (ic["pos"], ic["vel"], ic["mass"], ic["u"], ic["h"])
    with pytest.raises(ValueError, match="cell overflow"):
        _loop_bin(spec, *args, grow=False)
    with pytest.raises(ValueError, match="cell overflow"):
        bin_particles(spec, *args, grow=False)


# ------------------------------------------------ the pair list, once a grid
def _pair_list_report(sim) -> dict:
    """How often the engine built its pair list, whether its host views
    equal a fresh build for its grid, and each cycle's rebin parts."""
    eng = sim.engine
    fresh = build_pair_list(eng.spec)
    spans = sim.observer.tracer.spans
    cycles = [(s.t0, s.t1) for s in spans if s.name == "cycle"]
    parts = [sorted(s.name for s in spans
                    if s.name.startswith("rebin.") and c0 <= s.t0
                    and s.t1 <= c1)
             for c0, c1 in cycles]
    return dict(
        builds=eng.pair_list_builds,
        same=[bool(np.array_equal(getattr(eng, k),
                                  np.asarray(getattr(fresh, f))))
              for k, f in (("_ci", "ci"), ("_cj", "cj"),
                           ("_shift", "shift"))],
        parts=parts)


_FOUR_RANK_REPORT = """
import json, os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path[:0] = [{src!r}, {tests!r}]
import jax
jax.config.update("jax_default_matmul_precision", "float32")
from repro.sph import build_simulation
from test_observability import _device_schedule_spec
from test_rebin_host import _pair_list_report
sim = build_simulation(_device_schedule_spec(4))
for _ in range(3):
    sim.step()
print(json.dumps(_pair_list_report(sim)))
"""


def _report(ranks: int) -> dict:
    if ranks == 1:
        sim = build_simulation(_device_schedule_spec(1))
        for _ in range(3):
            sim.step()
        return _pair_list_report(sim)
    import os
    import subprocess
    import sys
    here = os.path.dirname(os.path.abspath(__file__))
    script = _FOUR_RANK_REPORT.format(src=os.path.join(here, "..", "src"),
                                      tests=here)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.splitlines()[-1])


@pytest.mark.parametrize("ranks", [1, 4])
def test_pair_list_is_built_once(ranks):
    """Three device-scheduled cycles build the pair list once (at set-up),
    its host views stay those of a fresh build, and every cycle's rebin
    still opens all four of its parts."""
    got = _report(ranks)
    assert got["builds"] == 1
    assert got["same"] == [True, True, True]
    assert len(got["parts"]) == 3
    for parts in got["parts"]:
        assert parts == sorted(REBIN_PARTS)
