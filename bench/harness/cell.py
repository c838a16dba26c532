"""A cell as ``BENCHMARK.json`` names it, and the files it is made of.

Everything that belongs to one configuration, traffic mix, scenario,
reference or metric sits in a file of its own, found by name:

* ``configs/<config>.json``       sizes, physics, limits (the file that
                                   ``BENCHMARK.json`` gives for the config)
* ``traffic/<traffic>.json``      how the window drives the program
* ``scenarios/<scenario>.py``     ``make(cfg, seed)`` → initial condition
* ``reference/<reference>.py``    ``numbers(ic, answer, cfg)`` and
                                   ``control_numbers(ic, answer, cfg)``
* ``metrics/<metric>.py``         ``read(run)`` → number or None
"""

from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from types import ModuleType
from typing import Dict, List

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


@dataclass
class Cell:
    name: str
    chips: int
    config: Dict
    traffic: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]

    def metrics(self, trace: bool) -> List[Dict]:
        """The metrics this cell reports in a run with or without trace."""
        return [m for m in (self.per_layer if trace else self.end_to_end)
                if self.name in m.get("workloads", [self.name])]


def load_module(kind: str, name: str) -> ModuleType:
    path = BENCH / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} named {name!r} ({path})")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(workload: str) -> Cell:
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; have {sorted(cells)}")
    w = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = json.loads((ROOT / conf["file"]).read_text())
    traffic = json.loads((BENCH / "traffic" / f"{w['traffic']}.json")
                         .read_text())
    return Cell(workload, int(w["chips"]), config, traffic,
                bench["end_to_end"], bench["per_layer"])
