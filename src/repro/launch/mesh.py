"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state. Single pod: (data=16, model=16) = 256 chips.
Multi-pod: (pod=2, data=16, model=16) = 512 chips — the ``pod`` axis is the
slow (DCN) dimension; batch shards over (pod, data).
"""

from __future__ import annotations

import math

import jax
from jax.sharding import Mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = math.prod(shape)
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for mesh {shape}, have {len(devices)} — "
            f"set XLA_FLAGS=--xla_force_host_platform_device_count={n} "
            f"before importing jax (dryrun.py does this)")
    import numpy as np
    dev_array = np.asarray(devices[:n]).reshape(shape)
    return Mesh(dev_array, tuple(axes))


def make_host_mesh(shape=(1, 1), axes=("data", "model")) -> Mesh:
    """Small mesh over whatever devices exist (tests / examples)."""
    n = math.prod(shape)
    import numpy as np
    dev_array = np.asarray(jax.devices()[:n]).reshape(shape)
    return Mesh(dev_array, tuple(axes))
