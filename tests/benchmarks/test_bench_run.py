"""run.py without a chip."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("cell", [c["name"] for c in MANIFEST["workloads"]])
def test_run_py_refuses_the_cpu(cell, tmp_path):
    """No TPU: another exit code than 0, and no result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", HOME=str(tmp_path))
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, str(ROOT / "benchmarks" / "run.py"), "--workload",
         cell, "--seed", str(2**31 + 7), "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "metrics" not in p.stdout and '"correct"' not in p.stdout
    assert "TPU" in p.stderr


def test_run_py_names_no_unknown_cell(tmp_path):
    p = subprocess.run(
        [sys.executable, str(ROOT / "benchmarks" / "run.py"), "--workload",
         "no-such-cell", "--seed", "1", "--seconds", "1"],
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and "metrics" not in p.stdout


def test_a_later_cell_reports_a_metric_without_editing_its_file():
    """`BENCHMARK.json` alone says which cells report a per-layer metric:
    a cell that no file under layer_metrics/ has heard of reads it."""
    from types import SimpleNamespace

    from benchmarks import run as harness

    manifest = {"per_layer": [
        {"name": "step_ms.train", "unit": "ms", "moves": "train_tokens_per_s",
         "workloads": ["gpt3-2.7b.steady", "some.later_cell"]},
        {"name": "input_wait_ms.train", "unit": "ms",
         "moves": "train_tokens_per_s", "workloads": ["gpt3-2.7b.steady"]}]}
    ctx = SimpleNamespace(cell={"name": "some.later_cell"})
    data = {"hist": {"oobleck_engine_step_seconds": {"sum": 2.4, "count": 2},
                     "oobleck_input_wait_seconds": {"sum": 1.0, "count": 2}}}
    got = harness.read_layer_metrics(ctx, manifest, {"train_tokens_per_s"},
                                     data)
    assert got == {"step_ms.train": {"value": pytest.approx(1200.0),
                                     "unit": "ms"}}
    manifest["per_layer"][0]["name"] = "no_such_metric"
    with pytest.raises(SystemExit):
        harness.read_layer_metrics(ctx, manifest, {"train_tokens_per_s"}, data)
