"""Sedov–Taylor blast initial condition, from the configuration and seed,
as SWIFT's ``examples/HydroTests/SedovBlast_3D/makeIC.py`` makes it."""

from __future__ import annotations

from typing import Dict

import numpy as np

from harness.lattice import jittered_lattice


def make(cfg: Dict, seed: int) -> Dict[str, np.ndarray]:
    """Uniform gas of density ``rho0`` at pressure ``P0``, and the energy
    ``E0`` given in equal shares to the ``N_inject`` particles nearest the
    centre of the box."""
    box, n_side = float(cfg["box"]), int(cfg["n_side"])
    gamma = cfg["physics"]["gamma"]
    rho0 = cfg["rho0"]
    ic = jittered_lattice(n_side, seed=seed, box=box, rho=rho0,
                          u=cfg["P0"] / (rho0 * (gamma - 1.0)),
                          jitter=cfg["jitter"], n_target=cfg["n_target"])
    d = ic["pos"] - np.full(3, box / 2.0, np.float32)
    d -= box * np.round(d / box)
    nearest = np.argsort(np.linalg.norm(d, axis=1),
                         kind="stable")[:cfg["N_inject"]]
    ic["u"][nearest] = cfg["E0"] / (cfg["N_inject"] * ic["mass"][0])
    return ic
