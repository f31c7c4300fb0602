"""PR 59's two per-layer metrics, `gdn_fwd_ms` and `gdn_bwd_ms`: data files
over the accepted reader `kernel_call_ms` (as `ssd_fwd_ms` is), MEMBERSHIP
of both in the manifest (never a list's end or its whole), what the reader
gives on a trace that holds the gated delta rule's two kernels
(`ops/gdn.py`) and that it gives nothing, and does not raise, on a trace
without them: the parent's, whose rule was plain XLA."""

import importlib
import json
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
CELL = "qwen3-next-80b-a3b.steady"
METRICS = {"gdn_fwd_ms": "%gdn_fwd.", "gdn_bwd_ms": "%gdn_bwd."}


def _spec(metric):
    return json.loads((ROOT / "benchmarks" / "layer_metrics"
                       / f"{metric}.json").read_text())


@pytest.mark.parametrize("metric", sorted(METRICS))
def test_the_metric_is_a_data_file_over_the_accepted_reader(metric):
    spec = _spec(metric)
    assert spec["reader"] == "kernel_call_ms"
    assert spec["args"] == {"match": METRICS[metric]}
    (entry,) = [m for m in MANIFEST["per_layer"] if m["name"] == metric]
    assert (entry["layer"], entry["moves"]) == ("kernels",
                                                "train_tokens_per_s")
    assert CELL in entry["workloads"]
    for key in ("name", "layer", "moves", "unit", "better", "source"):
        assert spec[key] == entry[key], key
    assert (entry["unit"], entry["better"], entry["source"]) == (
        "ms", "lower", "device_trace")


def test_the_cell_reports_the_end_to_end_metric_they_move():
    (rate,) = [m for m in MANIFEST["end_to_end"]
               if m["name"] == "train_tokens_per_s"]
    assert CELL in rate["workloads"]
    # The two stand next to each other and in order, beside the rule's
    # other metrics, which keep naming the cell.
    names = [m["name"] for m in MANIFEST["per_layer"]]
    assert names.index("gdn_bwd_ms") == names.index("gdn_fwd_ms") + 1
    for metric in ("gdn_rule_ms", "gdn_inverse_ms", "gdn_mixer_ms"):
        (entry,) = [m for m in MANIFEST["per_layer"] if m["name"] == metric]
        assert CELL in entry["workloads"]


def test_this_pr_brings_data_and_no_code_under_the_benchmark_s_paths():
    for metric in METRICS:
        assert (ROOT / "benchmarks" / "layer_metrics"
                / f"{metric}.json").exists()
        assert not (ROOT / "benchmarks" / "readers"
                    / f"{metric}.py").exists()
    assert not (ROOT / "benchmarks" / "flops_gdn.py").exists()


@pytest.mark.parametrize("metric", sorted(METRICS))
def test_the_reader_reads_a_call_and_nothing_where_no_kernel_ran(metric):
    spec = _spec(metric)
    reader = importlib.import_module(f"benchmarks.readers.{spec['reader']}")
    # 29 steps of 8 microbatches over 3 Gated DeltaNet layers: 696 calls of
    # each kernel, at made-up times a call; a routed kernel and the scan's
    # kernel of another model beside them.
    trace = {"time_by_name": {
        "%gdn_fwd.3 = (bf16[1,4096,4096], f32[1,64,16,128,256]) custom-call":
            [696 * 0.8e-3, 696],
        "%gdn_bwd.4 = (bf16[1,4096,2048], bf16[1,4096,2048]) custom-call":
            [464 * 2.0e-3, 464],
        "%gdn_bwd.5 = (bf16[1,4096,2048], bf16[1,4096,2048]) custom-call":
            [232 * 2.3e-3, 232],
        "%ssd_fwd.2 = bf16[1,4096,4096] custom-call": [1.0, 960],
        "%moe_gmm.3 = bf16[43008,512] custom-call": [9.0, 1800]}}
    got = reader.read({"trace": trace}, **spec["args"])
    assert got == pytest.approx({"gdn_fwd_ms": 0.8,
                                 "gdn_bwd_ms": 2.1}[metric])
    # The parent's trace: the rule as XLA fusions and three `while`s, no
    # such kernel. Nothing to read, nothing raised.
    parent = {"time_by_name": {
        "%while.194 = (s32[], f32[1,16,2,128,128]) while": [0.3776, 29],
        "%fusion.729 = f32[1,64,16,2,64,64] fusion": [0.296, 696]}}
    assert reader.read({"trace": parent}, **spec["args"]) is None
    assert reader.read({"trace": {"time_by_name": {}}}, **spec["args"]) is None
    assert reader.read({}, **spec["args"]) is None
