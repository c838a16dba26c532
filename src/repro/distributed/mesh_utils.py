"""Mesh helpers and divisibility-safe PartitionSpec construction."""

from __future__ import annotations

import math
from typing import Iterable, List, Optional, Sequence, Tuple, Union

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

AxisPref = Sequence[Union[str, Tuple[str, ...]]]


def ranks_mesh(nranks: int, *, axis: str = "ranks") -> Mesh:
    """1-D mesh over the first ``nranks`` devices (the transport mesh).

    Raises with the emulation hint when the process has too few devices —
    on CPU the collective transports are exercised with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``.
    """
    devices = jax.devices()
    if nranks > len(devices):
        raise ValueError(
            f"collective transport needs {nranks} addressable devices, "
            f"have {len(devices)}; set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={nranks} before "
            f"importing jax, or use transport='host'")
    return Mesh(np.array(devices[:nranks]), (axis,))


def ring_perm(n: int, offset: int = 1) -> List[Tuple[int, int]]:
    """The ring permutation (i → i + offset mod n) for ``lax.ppermute``."""
    return [(i, (i + offset) % n) for i in range(n)]


def valid_spec(shape: Sequence[int], prefs: Sequence[AxisPref],
               mesh: Mesh) -> P:
    """Build a PartitionSpec, taking each dim's first *valid* axis choice.

    A choice is valid if the dim size is divisible by the (product) axis size
    and no axis is reused. Composite choices like ``("data", "model")`` shard
    one dim over both axes. Invalid choices degrade to replication — the
    rules never produce an unlowerable sharding.
    """
    out: List[Optional[Union[str, Tuple[str, ...]]]] = []
    used: set = set()
    for dim, pref in zip(shape, prefs):
        chosen = None
        for cand in pref:
            axes = (cand,) if isinstance(cand, str) else tuple(cand)
            if any(a in used for a in axes):
                continue
            if any(a not in mesh.shape for a in axes):
                continue
            size = math.prod(mesh.shape[a] for a in axes)
            if dim > 0 and dim % size == 0 and size > 1:
                chosen = axes[0] if len(axes) == 1 else tuple(axes)
                used.update(axes)
                break
        out.append(chosen)
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def named(mesh: Mesh, spec: P) -> NamedSharding:
    return NamedSharding(mesh, spec)


def data_axes(mesh: Mesh) -> Tuple[str, ...]:
    """Axes carrying the batch: ("pod", "data") when a pod axis exists."""
    return tuple(a for a in ("pod", "data") if a in mesh.shape)


def batch_pref(mesh: Mesh) -> AxisPref:
    """Preference list for batch dims: pod+data together, then data alone."""
    da = data_axes(mesh)
    prefs: List[Union[str, Tuple[str, ...]]] = []
    if len(da) > 1:
        prefs.append(tuple(da))
    prefs.extend(da[::-1] if len(da) > 1 else da)
    return prefs
