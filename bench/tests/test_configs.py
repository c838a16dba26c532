"""The file ``BENCHMARK.json`` gives for each configuration holds what the
checks in ``test_check.py`` run, which load ``configs/<name>.json``: the two
differ at most in where their values come from."""

import json
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
PROVENANCE = ("source", "limits_from")
CONFIGS = json.loads((ROOT / "BENCHMARK.json").read_text())["configs"]


@pytest.mark.parametrize("entry", CONFIGS, ids=[c["name"] for c in CONFIGS])
def test_run_file_is_the_checked_configuration(entry):
    run = json.loads((ROOT / entry["file"]).read_text())
    checked = json.loads((BENCH / "configs" / f"{entry['name']}.json")
                         .read_text())
    assert run["name"] == entry["name"] and run["source"] == entry["source"]
    assert ({k: v for k, v in run.items() if k not in PROVENANCE}
            == {k: v for k, v in checked.items() if k not in PROVENANCE})


def test_configurations_name_distinct_sources():
    sources = [(c["source"], tuple(c["reduced"])) for c in CONFIGS]
    assert len(set(sources)) == len(sources)
