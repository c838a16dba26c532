"""Sequence-parallel halo exchange for windowed attention (C3, LM side).

When activations are sharded along the sequence axis, a sliding-window
attention layer only needs ``window`` trailing keys from the previous shard
— a 1-hop halo, not an all-gather. ``swa_halo_exchange`` ships exactly that
window via one ``ppermute`` (SWIFT: send the boundary cells only), and
``sp_local_attention`` runs the windowed attention entirely shard-locally.

Used by the gemma3 §Perf hillclimb (local layers with sequence-parallel
activations) and tested against full attention in
``tests/test_halo_attention.py``.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from .mesh_utils import ring_perm


def swa_halo_exchange(kv_local, *, axis: str, window: int):
    """kv_local (B, S_shard, …): returns the previous shard's trailing
    ``window`` positions (zeros for shard 0)."""
    n = jax.lax.axis_size(axis)
    idx = jax.lax.axis_index(axis)
    tail = kv_local[:, -window:]
    halo = jax.lax.ppermute(tail, axis, ring_perm(n))   # from shard idx-1
    halo = jnp.where(idx == 0, jnp.zeros_like(halo), halo)
    return halo


def _window_attn_local(q, k, v, halo_k, halo_v, *, axis: str, window: int,
                       scale: float):
    """Shard-local causal sliding-window attention.

    q/k/v (B, S_shard, H, hd); halo_* (B, window, H, hd) from the previous
    shard. Positions are globalised with the shard offset so the band mask
    is exact across the seam.
    """
    B, Ss, H, hd = q.shape
    idx = jax.lax.axis_index(axis)
    off = idx * Ss
    k_ext = jnp.concatenate([halo_k, k], axis=1)
    v_ext = jnp.concatenate([halo_v, v], axis=1)
    qpos = off + jnp.arange(Ss)
    kpos = off - window + jnp.arange(Ss + window)
    ok = (kpos[None, :] <= qpos[:, None]) \
        & (kpos[None, :] > qpos[:, None] - window) \
        & (kpos[None, :] >= 0)
    mask = jnp.where(ok, 0.0, -1e30).astype(jnp.float32)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k_ext).astype(jnp.float32)
    scores = scores * scale + mask[None, None]
    p = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v_ext)


def sp_local_attention(q, k, v, mesh: Mesh, *, axis: str = "model",
                       window: int):
    """Sequence-parallel sliding-window attention.

    q/k/v (B, S, H, hd) sharded (None, axis, None, None). One ppermute of
    ``window`` keys replaces the S-length all-gather a naive lowering emits:
    halo bytes / allgather bytes = window / S.
    """
    scale = 1.0 / math.sqrt(q.shape[-1])

    def body(q_l, k_l, v_l):
        hk = swa_halo_exchange(k_l, axis=axis, window=window)
        hv = swa_halo_exchange(v_l, axis=axis, window=window)
        return _window_attn_local(q_l, k_l, v_l, hk, hv, axis=axis,
                                  window=window, scale=scale)

    fn = shard_map(body, mesh=mesh,
                   in_specs=(P(None, axis, None, None),) * 3,
                   out_specs=P(None, axis, None, None))
    return fn(q, k, v)


def full_window_attention_ref(q, k, v, *, window: int):
    """Oracle: unsharded causal banded attention."""
    B, S, H, hd = q.shape
    scale = 1.0 / math.sqrt(hd)
    pos = jnp.arange(S)
    ok = (pos[None, :] <= pos[:, None]) & (pos[None, :] > pos[:, None] - window)
    mask = jnp.where(ok, 0.0, -1e30).astype(jnp.float32)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
    scores = scores * scale + mask[None, None]
    p = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)
