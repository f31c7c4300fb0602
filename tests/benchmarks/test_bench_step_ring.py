"""The readers of the telemetry ring's per-step fields, on rings made by
hand: the three excesses add up to the slowest step less the median step,
and a program or a run without the fields gives nothing to read."""

import json
from pathlib import Path

import pytest

from benchmarks.readers import step_ring
from oobleck_tpu.obs import telemetry

LAYER_METRICS = Path(__file__).resolve().parents[2] / "benchmarks" / "layer_metrics"
STEPS = 5
GB = 1 << 30


def _data(ring, steps=STEPS):
    return {"step_ring": ring,
            "cell": {"name": "x.steady",
                     "traffic": {"global_batch": 8, "microbatch_size": 2}},
            "train": {"microbatches_run": 4 * steps}}


def _sample(step, step_s, dispatch, readback, between_s=0.001,
            in_use=15 * GB, limit=16 * GB):
    phases = [0.0] * len(telemetry.PHASES)
    phases[telemetry.PHASES.index("engine.staging")] = 0.002
    phases[telemetry.PHASES.index("pipeline.dispatch")] = dispatch
    phases[telemetry.PHASES.index("engine.loss_readback")] = readback
    return (step, step_s, 0.0, 0.0, 0.0, 0.0, 0, between_s, tuple(phases),
            in_use, limit, GB)


def _ring(slow_part):
    """Two warm-up steps, then five of 1.000 s (dispatch 0.100, readback
    0.890, the rest 0.010), the fourth of them 0.250 s longer in one part."""
    ring = [_sample(1, 30.0, 29.0, 0.5), _sample(2, 1.0, 0.1, 0.89)]
    for k in range(STEPS):
        extra = 0.25 if k == 3 else 0.0
        ring.append(_sample(
            3 + k, 1.0 + extra,
            0.1 + (extra if slow_part == "dispatch" else 0.0),
            0.89 + (extra if slow_part == "readback" else 0.0),
            between_s=0.001 * (k + 1), in_use=(15 - k) * GB))
    return ring


def _read(metric, data):
    spec = json.loads((LAYER_METRICS / f"{metric}.json").read_text())
    assert spec["reader"] == "step_ring"
    return step_ring.read(data, **spec["args"])


@pytest.mark.parametrize("slow_part", ["dispatch", "readback", "rest"])
def test_excesses_name_the_part_and_sum_exactly(slow_part):
    data = _data(_ring(slow_part))
    excess = {p: _read(f"step_excess_ms.{p}", data)
              for p in ("dispatch", "readback", "rest")}
    for part, value in excess.items():
        assert value == pytest.approx(250.0 if part == slow_part else 0.0,
                                      abs=1e-9)
    assert sum(excess.values()) == pytest.approx((1.25 - 1.0) * 1e3, abs=1e-9)


def test_dispatch_and_readback_trading_places_read_zero():
    # Every step 1.000 s; the host sits now in the dispatch, now in the
    # readback. The slowest step (all equal: the first) has no excess.
    ring = [_sample(k, 1.0, d, 0.99 - d) for k, d in
            enumerate([0.1, 0.8, 0.1, 0.8, 0.1])]
    data = _data(ring)
    assert _read("step_excess_ms.dispatch", data) == pytest.approx(0.0)
    assert _read("step_excess_ms.readback", data) == pytest.approx(0.0)
    assert _read("step_excess_ms.rest", data) == pytest.approx(0.0)


def test_between_slow_steps_and_headroom():
    data = _data(_ring("dispatch"))
    # The window's five samples and not the warm-up's two.
    assert _read("between_steps_ms.train", data) == pytest.approx(3.0)
    assert _read("slow_steps.train", data) == 0.0      # 1.25 x is not over
    ring = _ring("dispatch")
    ring[5] = _sample(6, 1.2501, 0.35, 0.89)
    ring[2] = _sample(3, 4.0, 3.1, 0.89)
    assert _read("slow_steps.train", _data(ring)) == 2.0
    assert _read("hbm_headroom_min_pct.train", data) == pytest.approx(
        100.0 / 16)
    no_memory = [s[:telemetry.HBM_IN_USE] + (None, None, None)
                 for s in _ring("rest")]
    assert _read("hbm_headroom_min_pct.train", _data(no_memory)) is None
    assert _read("slow_steps.train", _data(no_memory)) == 0.0


@pytest.mark.parametrize("metric", [
    "step_excess_ms.dispatch", "step_excess_ms.readback",
    "step_excess_ms.rest", "between_steps_ms.train", "slow_steps.train",
    "hbm_headroom_min_pct.train"])
def test_nothing_to_read(metric, monkeypatch):
    assert _read(metric, {}) is None
    # A ring of the old shape (the parent's program under these files).
    old = [s[:7] for s in _ring("rest")]
    assert _read(metric, _data(old)) is None
    # Fewer samples than the window had steps.
    assert _read(metric, _data(_ring("rest")[-3:])) is None
    # A program without the fields at all.
    whole = _data(_ring("rest"))
    monkeypatch.delattr(telemetry, "PHASES")
    assert _read(metric, whole) is None


def test_reads_the_process_ring_in_the_order_the_steps_ran():
    ring = telemetry.reset(capacity=16, window=8)
    try:
        for s in _ring("readback"):
            ring.record_step(s[0], s[1], between_s=s[7], phases=s[8],
                             hbm=s[9:])
        data = _data(None)
        assert step_ring.window_samples(data)[0] == _ring("readback")[-STEPS:]
        assert _read("step_excess_ms.readback", data) == pytest.approx(250.0)
    finally:
        telemetry.reset()
