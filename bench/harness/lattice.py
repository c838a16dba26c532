"""The jittered lattice every configuration starts from, made from the seed.

The benchmark makes its own inputs and hands them to ``build_simulation``,
so the reference never reads an array the program made. The lattice of
equal-mass particles stands in for SWIFT's glass files; ``--seed`` moves
the jitter only, so every seed runs the same particle count and grid.
"""

from __future__ import annotations

from typing import Dict

import numpy as np


def jittered_lattice(n_side: int, *, seed: int, box: float, rho: float,
                     u: float, jitter: float, n_target: float
                     ) -> Dict[str, np.ndarray]:
    """Gas of density ``rho`` at rest on an ``n_side``³ lattice jittered by
    ``jitter`` of the spacing; ``h`` holds ``n_target`` neighbours inside
    its support."""
    rng = np.random.default_rng(seed)
    g = (np.arange(n_side) + 0.5) / n_side
    pos = np.stack(np.meshgrid(g, g, g, indexing="ij"), -1).reshape(-1, 3)
    pos = (pos + jitter * rng.standard_normal(pos.shape) / n_side) % 1.0
    pos *= box
    n = len(pos)
    spacing = box / n_side
    h = np.full(n, spacing * (3.0 * n_target / (4.0 * np.pi)) ** (1 / 3))
    return {
        "pos": pos.astype(np.float32),
        "vel": np.zeros((n, 3), np.float32),
        "mass": np.full(n, rho * box ** 3 / n, np.float32),
        "u": np.full(n, u, np.float32),
        "h": h.astype(np.float32),
        "box": box,
    }
