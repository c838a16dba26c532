"""Compile the production path's programs for a described TPU v5e.

Nothing runs: the TPU compiler, installed with jaxlib, compiles for a chip
that is described and not attached, and raises what the chip's compiler
would raise — Mosaic block shapes, programs that do not fit, missing
collectives. The topology is described inside a fixture, never at import,
so each pytest worker that imports this file collects the same tests and
only the one that runs them loads the TPU library.
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.kernels.sph_pair.kernel import (density_pair_pallas,
                                           force_pair_pallas)
from repro.sph import SPHConfig
from repro.sph.cellgrid import PairList, ParticleCells
from repro.sph.collectives import build_cycle_scan_program
from repro.sph.engine import _density_pass

C = 32                  # cell capacity of the kernels at their real width
NPAIRS = 1024


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def test_density_pallas_compiles_for_v5e(one_chip):
    vec = _sds((NPAIRS, C, 3), jnp.float32, one_chip)
    sca = _sds((NPAIRS, C), jnp.float32, one_chip)
    txt = _compiled_text(
        lambda *a: density_pair_pallas(*a, interpret=False),
        vec, sca, sca, sca, vec, sca, sca, sca)
    assert "tpu_custom_call" in txt


def test_force_pallas_compiles_for_v5e(one_chip):
    vec = _sds((NPAIRS, C, 3), jnp.float32, one_chip)
    sca = _sds((NPAIRS, C), jnp.float32, one_chip)
    args = [vec, vec] + [sca] * 7 + [vec, vec] + [sca] * 7
    txt = _compiled_text(
        lambda *a: force_pair_pallas(*a, alpha_visc=0.8, interpret=False),
        *args)
    assert "tpu_custom_call" in txt


def test_density_pass_keeps_f32_distances(one_chip):
    """No distance runs through a default-precision (bf16-pass) matmul."""
    ncells, npairs, cap = 343, 4802, 40          # the n_side=16 grid
    f32 = jnp.float32
    cells = ParticleCells(
        pos=_sds((ncells, cap, 3), f32, one_chip),
        vel=_sds((ncells, cap, 3), f32, one_chip),
        mass=_sds((ncells, cap), f32, one_chip),
        u=_sds((ncells, cap), f32, one_chip),
        h=_sds((ncells, cap), f32, one_chip),
        mask=_sds((ncells, cap), f32, one_chip))
    pairs = PairList(ci=_sds((npairs,), jnp.int32, one_chip),
                     cj=_sds((npairs,), jnp.int32, one_chip),
                     shift=_sds((npairs, 3), f32, one_chip))
    cfg = SPHConfig()
    # as the program runs: without conftest's global precision pin
    with jax.default_matmul_precision("default"):
        txt = _compiled_text(lambda c, p: _density_pass(c, p, cfg),
                             cells, pairs)
    for line in txt.splitlines():
        if " dot(" in line or "convolution(" in line:
            assert "operand_precision={highest,highest}" in line, line


def _scan_args(mesh, nranks, nrows, K, B, Bi, Bc, R, Be):
    """Argument shapes of one cycle scan at the n_side=16 grid (capacity
    40), split over ``nranks`` ranks."""
    sh = NamedSharding(mesh, P("ranks"))
    f32, i32 = jnp.float32, jnp.int32
    cap = 40
    rows = lambda *tail: (nranks, nrows) + tail            # noqa: E731
    state = {"pos": rows(cap, 3), "vel": rows(cap, 3), "mass": rows(cap),
             "u": rows(cap), "h": rows(cap), "mask": rows(cap),
             "accel": rows(cap, 3), "dudt": rows(cap), "rho": rows(cap),
             "omega": rows(cap), "t_start": rows(cap)}
    state = {k: _sds(v, f32, sh) for k, v in state.items()}
    state["bins"] = _sds(rows(cap), i32, sh)
    state["time"] = _sds((nranks,), f32, sh)
    tables = {"ci": ((nranks, B), i32), "cj": ((nranks, B), i32),
              "shift": ((nranks, B, 3), f32), "pmask": ((nranks, B), f32),
              "int_pos": ((nranks, Bi), i32),
              "int_valid": ((nranks, Bi), f32),
              "cut_pos": ((nranks, Bc), i32),
              "cut_valid": ((nranks, Bc), f32),
              "e_pack": ((nranks, R, Be), i32),
              "e_unpack": ((nranks, R, Be), i32),
              "e_valid": ((nranks, R, Be), f32),
              "own_pair": ((nranks, B), f32),
              "rowcell": ((nranks, nrows), i32)}
    tables = {k: _sds(s, d, sh) for k, (s, d) in tables.items()}
    scalars = {k: _sds((nranks,), d, sh) for k, d in (
        ("dt_max", f32), ("depth", i32), ("nsub", i32), ("u_floor", f32))}
    return state, tables, scalars


def _scan_shapes(nranks):
    if nranks == 1:
        return [], dict(nrows=343, K=343, B=8192, Bi=8192, Bc=1, R=0, Be=1)
    ring = [[(r, (r + s) % nranks) for r in range(nranks)]
            for s in (1, nranks - 1)]
    return ring, dict(nrows=256, K=96, B=4096, Bi=2048, Bc=4096, R=2, Be=64)


@pytest.mark.parametrize("nranks", [1, 4])
def test_cycle_scan_compiles_rolled_for_v5e(topo, nranks):
    """The cycle scan compiles for the chip with its trips rolled into one
    loop: each trip's skip, compact and full branches appear once, not
    once per trip of the 16."""
    mesh = Mesh(np.array(topo.devices[:nranks]), ("ranks",))
    rounds, shapes = _scan_shapes(nranks)
    prog = build_cycle_scan_program(
        mesh, "ranks", mode="ppermute", rounds=rounds,
        nrows=shapes["nrows"], K=shapes["K"],
        cfg=SPHConfig(alpha_visc=1.0, cfl=0.15), box=1.0, nsub_static=16,
        bin_delta=2)
    txt = prog.lower(*_scan_args(mesh, nranks, **shapes)).compile().as_text()
    assert " while(" in txt
    assert txt.count(" conditional(") == 3
