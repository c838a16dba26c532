"""Sharding/config variants for §Perf hillclimbing.

Each variant transforms (cfg, rules) before the cell is lowered. The dry-run
records results per variant, so baseline vs optimized stay separately
visible in EXPERIMENTS.md (paper-faithful floor vs beyond-paper gains).
"""

from __future__ import annotations

import dataclasses

import jax
from jax.sharding import PartitionSpec as P

from ..distributed.mesh_utils import batch_pref, valid_spec
from ..distributed.sharding_rules import ShardingRules


def apply(name: str, cfg, rules: ShardingRules) -> ShardingRules:
    if name not in VARIANTS:
        raise ValueError(f"unknown variant {name!r}; have {list(VARIANTS)}")
    return VARIANTS[name](cfg, rules)


def _seq_shard(cfg, rules: ShardingRules) -> ShardingRules:
    """Sequence-parallel activations: residual stream sharded over 'model'
    between blocks (Megatron-SP). Cuts layer-boundary residual memory and
    turns the TP all-reduce into reduce-scatter + all-gather pairs."""
    base_constrain = rules.constrain

    def constrain(x, kind=None):
        if x.ndim == 3 and x.shape[1] % rules.mesh.shape["model"] == 0:
            bp = batch_pref(rules.mesh)
            spec = valid_spec(x.shape, [bp, ["model"], []], rules.mesh)
            return jax.lax.with_sharding_constraint(x, spec)
        return base_constrain(x, kind)

    new = dataclasses.replace(rules)
    new.constrain = constrain
    new.cfg = cfg
    return new


def _scan_group(n):
    def f(cfg, rules: ShardingRules) -> ShardingRules:
        rules.cfg = dataclasses.replace(cfg, scan_group=n)
        return rules
    return f


def _ssm_chunk(n):
    def f(cfg, rules: ShardingRules) -> ShardingRules:
        rules.cfg = dataclasses.replace(cfg, ssm_chunk=n)
        return rules
    return f


def _ssm_bf16(cfg, rules: ShardingRules) -> ShardingRules:
    rules.cfg = dataclasses.replace(cfg, ssm_bf16=True)
    return rules


def _seq_shard_no_block_remat(cfg, rules: ShardingRules) -> ShardingRules:
    rules = _seq_shard(cfg, rules)
    rules.cfg = dataclasses.replace(rules.cfg, block_remat=False)
    return rules


def _seq_nbr_g2(cfg, rules: ShardingRules) -> ShardingRules:
    rules = _seq_shard_no_block_remat(cfg, rules)
    rules.cfg = dataclasses.replace(rules.cfg, scan_group=2)
    return rules


def _no_block_remat(cfg, rules: ShardingRules) -> ShardingRules:
    """Drop the per-block remat level (keep group-level sqrt remat):
    executed flops 10/6 → 8/6 of MODEL — viable once banded attention has
    freed the S×S activation memory."""
    rules.cfg = dataclasses.replace(cfg, block_remat=False)
    return rules


def _moe_group(n):
    def f(cfg, rules: ShardingRules) -> ShardingRules:
        rules.cfg = dataclasses.replace(cfg)
        object.__setattr__(rules.cfg, "_moe_group", n)   # read by moe()
        return rules
    return f


def _identity(cfg, rules):
    rules.cfg = cfg
    return rules


def _moe_ep(cfg, rules: ShardingRules) -> ShardingRules:
    """Expert parallelism: experts sharded over the model axis (requires an
    EP-compatible mesh, see ``mesh_override``) — routing becomes all-to-all,
    expert FFNs run collective-free."""
    rules.moe_ep = True
    rules.cfg = cfg
    return rules


VARIANTS = {
    "baseline": _identity,
    "seq_shard": _seq_shard,
    "scan_group8": _scan_group(8),
    "scan_group2": _scan_group(2),
    "ssm_chunk64": _ssm_chunk(64),
    "ssm_chunk32": _ssm_chunk(32),
    "ssm_chunk256": _ssm_chunk(256),
    "ep8": _moe_ep,
    "no_block_remat": _no_block_remat,
    "ssm_bf16": _ssm_bf16,
    "seq_nbr": _seq_shard_no_block_remat,
    "seq_nbr_g2": _seq_nbr_g2,
}

# variants that need a different production mesh factorisation (same chip
# count): ep8 reshapes a pod to (data=32, model=8) so 8 experts divide the
# model axis
MESH_OVERRIDES = {
    "ep8": {False: ((32, 8), ("data", "model")),
            True: ((2, 32, 8), ("pod", "data", "model"))},
}


def mesh_override(name: str, multi_pod: bool):
    """Return a Mesh for variants that refactor the pod, else None."""
    if name not in MESH_OVERRIDES:
        return None
    import math
    import numpy as np
    import jax
    from jax.sharding import Mesh
    shape, axes = MESH_OVERRIDES[name][multi_pod]
    n = math.prod(shape)
    dev = np.asarray(jax.devices()[:n]).reshape(shape)
    return Mesh(dev, axes)
