"""Benchmark-harness helpers: the repo-root BENCH artifacts stay valid.

The benchmark modules each leave a headline ``BENCH_*.json`` at the repo
root; ``benchmarks/common.py`` (standalone-runnable, factored out of the
full ``benchmarks.run`` sweep) validates them into the trajectory block
of ``summary.json``. These tests pin that contract without running any
benchmark: the two checked-in artifacts must parse, name their
benchmark, and never claim a metrics schema newer than this tree.
"""

import json
import os
import sys

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, ROOT)

from benchmarks.common import bench_trajectory, env_provenance

EXPECTED_ARTIFACTS = ("BENCH_fleet.json", "BENCH_observability.json")


def test_bench_trajectory_nonempty_and_valid():
    traj = bench_trajectory()
    assert traj, "no BENCH_*.json artifacts found at the repo root"
    by_file = {e["file"]: e for e in traj}
    for fname in EXPECTED_ARTIFACTS:
        assert fname in by_file, f"missing artifact {fname}"
        entry = by_file[fname]
        assert entry["valid"], f"{fname}: {entry['problems']}"
        assert entry["benchmark"]
        assert entry["problems"] == []


def test_bench_trajectory_flags_malformed_artifact(tmp_path):
    (tmp_path / "BENCH_broken.json").write_text("{not json")
    (tmp_path / "BENCH_list.json").write_text("[1, 2]")
    (tmp_path / "BENCH_anon.json").write_text("{}")
    (tmp_path / "BENCH_future.json").write_text(
        json.dumps({"benchmark": "x", "metrics_schema_version": 999}))
    traj = {e["file"]: e for e in bench_trajectory(str(tmp_path))}
    assert len(traj) == 4
    assert not traj["BENCH_broken.json"]["valid"]
    assert not traj["BENCH_list.json"]["valid"]
    assert not traj["BENCH_anon.json"]["valid"]
    assert not traj["BENCH_future.json"]["valid"]
    assert any("newer" in p or "schema" in p
               for p in traj["BENCH_future.json"]["problems"])


def test_env_provenance_reports_toolchain():
    env = env_provenance()
    assert env["python"] and env["platform"]
    assert "jax" in env
    assert env.get("metrics_schema_version", 0) >= 3


def test_common_is_standalone_runnable():
    import subprocess
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "common.py")],
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    doc = json.loads(proc.stdout)
    assert doc["_bench_trajectory"]
    assert all(e["valid"] for e in doc["_bench_trajectory"])
