"""Sod shock tube initial condition, from the configuration and seed, as
SWIFT's ``examples/HydroTests/SodShock_3D/makeIC.py`` lays it out.

The periodic box ``box`` (2 × 0.5 × 0.5) holds the left state on
x ∈ [0, box_x/2) and the right state on the other half, both at rest. Each
half is a lattice jittered by ``jitter`` of its spacing, standing in for
the two glass files SWIFT tiles along x: the left one ``n_side`` particles
across the short edges, the right one at twice the spacing, so that
particles of one mass hold the 8 : 1 density ratio. ``h`` holds
``n_target`` neighbours of each half's own spacing. ``--seed`` moves the
jitter only.
"""

from __future__ import annotations

from typing import Dict

import numpy as np


def _half(n: int, box: np.ndarray, x0: float):
    """Lattice points of the half that starts at x = ``x0``, ``n`` across
    each short edge, and their spacing."""
    spacing = box[1] / n
    counts = (int(round(box[0] / 2 / spacing)), n,
              int(round(box[2] / spacing)))
    if not np.allclose(np.multiply(counts, spacing), (box[0] / 2, box[1],
                                                       box[2])):
        raise ValueError(f"a lattice {n} across does not tile half of the "
                         f"box {box.tolist()}")
    axes = [(np.arange(c) + 0.5) * spacing for c in counts]
    pos = np.stack(np.meshgrid(*axes, indexing="ij"), -1).reshape(-1, 3)
    pos[:, 0] += x0
    return pos, spacing


def make(cfg: Dict, seed: int) -> Dict[str, np.ndarray]:
    """Left state (``rho_l``, ``P_l``), right state (``rho_r``, ``P_r``)."""
    n_side = int(cfg["n_side"])
    if n_side % 2:
        raise ValueError("the sparse lattice is n_side / 2 across: n_side "
                         "must be even")
    box = np.asarray(cfg["box"], np.float64)
    gamma = cfg["physics"]["gamma"]
    rng = np.random.default_rng(seed)
    parts = []
    for n, x0, rho, press in ((n_side, 0.0, cfg["rho_l"], cfg["P_l"]),
                              (n_side // 2, box[0] / 2, cfg["rho_r"],
                               cfg["P_r"])):
        pos, spacing = _half(n, box, x0)
        pos = np.mod(pos + cfg["jitter"] * spacing
                     * rng.standard_normal(pos.shape), box)
        count = len(pos)
        parts.append({
            "pos": pos,
            "mass": np.full(count, rho * box[0] / 2 * box[1] * box[2]
                            / count),
            "u": np.full(count, press / ((gamma - 1.0) * rho)),
            "h": np.full(count, spacing * (3.0 * cfg["n_target"]
                                           / (4.0 * np.pi)) ** (1 / 3)),
        })
    ic = {k: np.concatenate([p[k] for p in parts]).astype(np.float32)
          for k in parts[0]}
    ic["vel"] = np.zeros_like(ic["pos"])
    ic["box"] = tuple(float(x) for x in box)
    return ic
