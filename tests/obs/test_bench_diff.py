"""bench.py: no accelerator, no number; host-side microbenches as CPU
children that cannot take the headline down; and --diff, whose direction
must follow the lower-is-better key classification and which reports only
changes beyond the noise threshold."""

import json
import subprocess
import sys
import types
from pathlib import Path

import pytest

import bench

REPO = Path(__file__).resolve().parents[2]


def test_no_accelerator_means_no_number():
    """JAX held to the CPU: bench.py exits non-zero and prints nothing on
    stdout — no replayed record, no CPU stand-in under a throughput name."""
    proc = subprocess.run([sys.executable, str(REPO / "bench.py")],
                          capture_output=True, text=True, timeout=120,
                          cwd=REPO)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "found none" in proc.stderr


def test_cpu_bench_child_env_and_result(monkeypatch):
    """A microbench child is a CPU world: off the chip, persistent compile
    cache off, out of this process's metrics sink, rig-sized virtual
    devices, and without the ambient knobs its bench sets for itself."""
    monkeypatch.setenv("OOBLECK_POLICY", "reroute")
    monkeypatch.setenv("OOBLECK_METRICS_DIR", "/somewhere")
    code = ("import json, os; print('noise'); print(json.dumps("
            "{k: os.environ.get(k) for k in ('JAX_PLATFORMS', "
            "'JAX_ENABLE_COMPILATION_CACHE', 'OOBLECK_METRICS_DIR', "
            "'OOBLECK_POLICY', 'XLA_FLAGS')}))")
    got = bench._run_cpu_bench(["-c", code], 60, devices=4,
                               scrub=("OOBLECK_POLICY",))
    assert got["JAX_PLATFORMS"] == "cpu"
    assert got["JAX_ENABLE_COMPILATION_CACHE"] == "false"
    assert got["OOBLECK_METRICS_DIR"] == ""
    assert got["OOBLECK_POLICY"] is None
    assert got["XLA_FLAGS"].endswith(
        "--xla_force_host_platform_device_count=4")


@pytest.mark.parametrize("code,timeout_s,needle", [
    ("import sys; print('boom', file=sys.stderr); sys.exit(3)", 60,
     "exit 3: boom"),
    ("print('not json')", 60, "unparseable output"),
    ("import time; time.sleep(30)", 1, "no result within 1s"),
])
def test_cpu_bench_failure_is_an_error_section(code, timeout_s, needle):
    got = bench._run_cpu_bench(["-c", code], timeout_s)
    assert set(got) == {"error"} and needle in got["error"]


def test_every_cpu_bench_names_a_module_that_exists():
    for key, (argv, timeout_s, devices, scrub) in bench._CPU_BENCHES.items():
        target = argv[1] if argv[0] == "-m" else argv[0]
        path = (REPO / (target.replace(".", "/") + ".py")
                if argv[0] == "-m" else Path(target))
        assert path.is_file(), (key, path)
        assert timeout_s > 0 and all(k.startswith("OOBLECK_") for k in scrub)


def test_peak_flops_is_a_keyed_table():
    from oobleck_tpu.parallel.train import peak_flops

    assert peak_flops("TPU v5 lite") == 197e12
    # not a substring match, and no default: an unknown kind is an error
    for kind in ("TPU v5 lite pod", "tpu v5 lite", "TPU v9", "cpu"):
        with pytest.raises(KeyError, match="no peak FLOP/s known"):
            peak_flops(kind)


@pytest.mark.parametrize("platform,stats,want", [
    ("cpu", None, 1),                      # the test backend's assumed 16 GiB
    ("tpu", {"bytes_limit": 8 * 2**30}, 2),  # asked, not assumed
    ("tpu", {}, RuntimeError),
    ("tpu", None, RuntimeError),
])
def test_compute_min_hosts_asks_the_tpu_for_its_memory(platform, stats, want):
    from oobleck_tpu.execution.engine import OobleckEngine

    dev = types.SimpleNamespace(platform=platform,
                                memory_stats=lambda: stats)
    # 6 * 2 GiB of params + 1 GiB of activations = 13 GiB
    eng = types.SimpleNamespace(
        profiles=[types.SimpleNamespace(mem_params=2 * 2**30,
                                        mem_activation=2**30)],
        devices=[dev], chips_per_host=1)
    if isinstance(want, int):
        assert OobleckEngine.compute_min_hosts(eng) == want
    else:
        with pytest.raises(want, match="reports no memory limit"):
            OobleckEngine.compute_min_hosts(eng)


def test_regression_direction_higher_is_better():
    old = {"value": 100.0}
    new = {"value": 80.0}
    lines, regressions = bench.bench_diff(old, new)
    assert regressions == ["value"]
    assert any("REGRESSION" in line for line in lines)
    # and the improvement direction is not a regression
    _, regressions = bench.bench_diff(new, old)
    assert regressions == []


def test_regression_direction_lower_is_better():
    old = {"serve": {"ttft_p50_ms": 10.0}}
    new = {"serve": {"ttft_p50_ms": 20.0}}
    _, regressions = bench.bench_diff(old, new)
    assert regressions == ["serve.ttft_p50_ms"]
    _, regressions = bench.bench_diff(new, old)
    assert regressions == []  # latency halved = improvement


def test_throughput_keys_are_higher_is_better():
    # "_s" must only match as a unit suffix: as a substring it swallows
    # "_sec"/"_speedup" and inverts the headline throughput metrics.
    for key in ("tokens_per_sec", "pipeline.mpmd_tokens_per_sec_per_chip",
                "degrade.reroute_speedup", "degrade.retention",
                "serve.tokens_per_second"):
        assert not bench._lower_is_better(key), key
    for key in ("serve.ttft_p50_ms", "step_s", "recovery.total_s",
                "pipeline.bubble_fraction", "latency"):
        assert bench._lower_is_better(key), key
    old = {"pipeline": {"tokens_per_sec": 100.0}}
    new = {"pipeline": {"tokens_per_sec": 150.0}}
    lines, regressions = bench.bench_diff(old, new)
    assert regressions == []  # 1.5x throughput is an improvement
    assert any("improved" in line for line in lines)
    _, regressions = bench.bench_diff(new, old)
    assert regressions == ["pipeline.tokens_per_sec"]


def test_noise_below_threshold_is_silent():
    old = {"value": 100.0}
    new = {"value": 100.0 * (1 - bench.DIFF_THRESHOLD / 2)}
    lines, regressions = bench.bench_diff(old, new)
    assert lines == [] and regressions == []


def test_new_and_gone_keys_reported_without_regression():
    old = {"value": 1.0, "pipeline": {"bubble": 0.1}}
    new = {"value": 1.0, "degrade": {"retention": 0.9}}
    lines, regressions = bench.bench_diff(old, new)
    assert regressions == []
    assert any("(new)" in line and "retention" in line for line in lines)
    assert any("(gone)" in line and "bubble" in line for line in lines)


