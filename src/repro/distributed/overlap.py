"""Chunked, overlapped collectives — SWIFT's C3 mapped to TP matmuls.

SWIFT sends many small messages and acts on data as it arrives instead of
one bulk exchange. The TPU-native incarnation: decompose a TP collective
into P−1 ``ppermute`` rounds where every round's chunk feeds its slice of
the matmul immediately:

* ``allgather_matmul``  — computes ``allgather(x, axis) @ w_local`` as a
  ring: each round multiplies the chunk currently held while the next chunk
  is in flight. XLA's latency-hiding scheduler overlaps the ppermute with
  the per-round matmul because they are independent ops in the round body.
* ``matmul_reducescatter`` — computes ``reduce_scatter(x @ w, axis)`` the
  dual way: partial products are accumulated into a chunk that rides the
  ring.

These are the beyond-paper §Perf variants; the baseline path relies on
XLA's own all-gather/reduce-scatter insertion. Equivalence against the
plain collective is tested in ``tests/test_overlap.py``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from .mesh_utils import ring_perm


def allgather_matmul_local(x_local, w_local, *, axis: str):
    """Local body: x_local (m, k_shard) — gathered dim is k? No: x is sharded
    on its leading (row) dim; result = concat of all rows @ w_local.

    x_local (m_shard, k), w_local (k, n) → out (m_shard * P, n) is what a
    plain allgather-then-matmul gives; here each round contributes the rows
    owned by a different shard, written into its slice of the output.
    """
    n_dev = jax.lax.axis_size(axis)
    idx = jax.lax.axis_index(axis)
    m = x_local.shape[0]
    out = jnp.zeros((m * n_dev, w_local.shape[1]), x_local.dtype)
    chunk = x_local
    perm = ring_perm(n_dev)
    for r in range(n_dev):
        # after r forward hops of the i→i+1 ring, we hold idx−r's rows
        src = (idx - r) % n_dev
        part = chunk @ w_local            # (m, n) — overlaps next ppermute
        out = jax.lax.dynamic_update_slice(out, part, (src * m, 0))
        if r != n_dev - 1:
            chunk = jax.lax.ppermute(chunk, axis, perm)
    return out


def matmul_reducescatter_local(x_local, w_local, *, axis: str):
    """Local body: full-row x_local (m, k), w_local (k, n); the result rows
    are reduce-scattered over ``axis``: out (m // P, n).

    Round r computes the partial destined for the neighbour r hops away and
    adds it to the accumulator riding the ring — the classic reduce-scatter
    matmul fusion.
    """
    n_dev = jax.lax.axis_size(axis)
    idx = jax.lax.axis_index(axis)
    m = x_local.shape[0]
    assert m % n_dev == 0, "row dim must divide the axis"
    ms = m // n_dev
    perm = ring_perm(n_dev)
    acc = None
    for r in range(n_dev - 1, -1, -1):
        dst = (idx + r) % n_dev
        part = jax.lax.dynamic_slice(x_local, (dst * ms, 0),
                                     (ms, x_local.shape[1])) @ w_local
        acc = part if acc is None else acc + part
        if r != 0:
            acc = jax.lax.ppermute(acc, axis, perm)
    return acc


def allgather_matmul(x, w, mesh: Mesh, *, axis: str = "model"):
    """x sharded (axis, None); w sharded (None, axis) replicated rows.
    Returns full (M, n_shard-concat) product — jit-able from outside."""
    fn = shard_map(
        functools.partial(allgather_matmul_local, axis=axis),
        mesh=mesh,
        in_specs=(P(axis, None), P(None, axis)),
        out_specs=P(None, axis))
    return fn(x, w)


def matmul_reducescatter(x, w, mesh: Mesh, *, axis: str = "model"):
    """x replicated rows, sharded cols (None, axis); w sharded (axis, None).
    Returns (M/P-sharded rows, n) = reduce_scatter(x @ w)."""
    fn = shard_map(
        functools.partial(matmul_reducescatter_local, axis=axis),
        mesh=mesh,
        in_specs=(P(None, axis), P(axis, None)),
        out_specs=P(axis, None))
    return fn(x, w)
