"""Overlap-everything engine paths, split out of test_engine.py like the
reconfig module: deferred loss readback parity, the zero-host-sync steady
state (the async-dispatch acceptance hook), and failure recovery under the
interleaved schedule."""

import time

import numpy as np
import pytest

from oobleck_tpu.execution import engine as engine_mod
from oobleck_tpu.execution.dataloader import DeviceStager
from oobleck_tpu.utils import metrics

from tests.execution.test_engine import cache_env, make_engine  # noqa: F401


def _trained(devices8, steps, **exec_overrides):
    engine = make_engine(num_hosts=4, steps=steps, devices=devices8)
    for k, v in exec_overrides.items():
        setattr(engine.args.execution, k, v)
    engine.initialize_distributed()
    engine.instantiate_pipelines(engine.args.job.global_num_microbatch)
    return engine


def test_deferred_loss_readback_matches_per_step(cache_env, devices8):
    """loss_readback_every > 1 must report the SAME loss values at the SAME
    steps as per-step readback — deferral moves the host sync off the
    critical path, it must not change the math or drop steps. steps=4 with
    every=3 exercises both the periodic drain (step 3) and the end-of-train
    finally-drain (step 4)."""

    def run(every):
        engine = _trained(devices8, steps=4, loss_readback_every=every)
        engine.train()
        return engine.loss_history

    base = run(1)
    deferred = run(3)
    assert [s for s, _ in base] == [1, 2, 3, 4]
    assert [s for s, _ in deferred] == [s for s, _ in base]
    np.testing.assert_allclose(
        [v for _, v in deferred], [v for _, v in base], rtol=1e-6)


def test_steady_state_zero_host_syncs(cache_env, devices8, monkeypatch):
    """The acceptance criterion for async dispatch: with input prefetch on
    and deferred loss readback, steady-state steps perform ZERO
    host-blocking readbacks, counted at the engine's single float() funnel
    (engine.host_sync_counter). The deferred losses must still resolve to
    finite values afterwards — the syncs moved, they didn't vanish."""
    monkeypatch.setenv("OOBLECK_PREFETCH", "1")
    engine = _trained(devices8, steps=100, loss_readback_every=100)
    assert any(isinstance(dl, DeviceStager) for dl in engine.dataloaders)

    pending = [engine._train_step()]  # warmup: compiles, first staging
    before = engine_mod.host_sync_counter.count
    for _ in range(3):
        pending.append(engine._train_step())
    after = engine_mod.host_sync_counter.count
    assert after == before, (
        f"steady-state steps performed {after - before} host sync(s)")

    assert all(isinstance(p, engine_mod.DeferredLoss) for p in pending)
    vals = [p.resolve() for p in pending]
    assert all(np.isfinite(v) for v in vals)
    assert engine_mod.host_sync_counter.count > after


def test_input_wait_metric_observed_with_prefetch(cache_env, devices8,
                                                  monkeypatch):
    """With a DeviceStager fronting the loaders, each step observes the
    time spent waiting on staged input (oobleck_input_wait_seconds) — the
    gauge that makes 'prefetch keeps the device fed' measurable."""
    monkeypatch.setenv("OOBLECK_PREFETCH", "1")
    engine = _trained(devices8, steps=3)

    def observed():
        return sum(s["count"] for s in engine._m_input_wait.series())

    counted = observed()
    engine._train_step()
    assert observed() > counted


def test_reconfigure_under_interleaved_schedule(cache_env, devices8):
    """Fail a host mid-run under pipeline_schedule=interleaved: every
    re-instantiated pipeline must carry exactly the virtual-stage degree
    _effective_virtual_stages predicts for its new (stages, microbatches) —
    either the configured one, or a clean 1f1b fallback WITH a
    flight-recorder event — and training keeps converging."""
    engine = _trained(devices8, steps=10,
                      pipeline_schedule="interleaved", virtual_stages=2)

    def check_consistency():
        fell_back = 0
        for pipe in engine.pipelines:
            want = engine._effective_virtual_stages(
                pipe.num_stages, pipe.num_microbatches, pipe.pipeline_id,
                record=False)
            assert pipe.virtual_stages == want, (
                f"pipeline {pipe.pipeline_id}: virtual_stages "
                f"{pipe.virtual_stages} != predicted {want}")
            if pipe.num_stages > 1 and want == 1:
                fell_back += 1
        return fell_back

    check_consistency()
    loss_before = [engine._train_step() for _ in range(2)][-1]

    # By time, not by count: the recorder is a bounded ring, and in a
    # process that has recorded its capacity already the count stands still.
    since = time.time()
    engine.reconfigure("10.0.0.2")
    assert "10.0.0.2" not in engine.host_ips

    fell_back = check_consistency()
    if fell_back:
        new = [e for e in metrics.flight_recorder().events()
               if e["t"] >= since]
        assert any(e["event"] == "interleave_fallback" for e in new), (
            "1f1b fallback happened without a flight-recorder event")

    losses = [engine._train_step() for _ in range(3)]
    assert all(np.isfinite(l) for l in losses)
    assert losses[-1] < loss_before


def test_sync_op_timing_splits_comm_from_compute(devices8):
    """The calibration mode's comm/compute split (the overlap measurement
    hook): with sync_op_timing on, cross-stage transfers are recorded as
    'cf'/'cb' entries in last_op_times, and stage-busy time — the bubble
    gauge's numerator — covers ONLY the compute kinds, so hidden comm can
    never masquerade as pipeline utilization."""
    from oobleck_tpu.execution.pipeline import PipelineInstance
    from oobleck_tpu.models import build_model
    from tests.execution.test_pipeline_mpmd import (
        MB, NUM_MB, SEQ, make_template)

    model = build_model("gpt2-tiny")  # 6 pipeline layers
    template = make_template([(0, 3), (3, 6)], [1, 1])
    rng = np.random.default_rng(0)
    batch = rng.integers(0, model.config.vocab_size,
                         size=(NUM_MB, MB, SEQ), dtype=np.int32)
    pipe = PipelineInstance(
        pipeline_id=0, template=template, ranks=[0, 1], model=model,
        devices=devices8[:2], num_microbatches=NUM_MB,
        total_num_microbatches=NUM_MB, microbatch_size=MB, seq_len=SEQ)
    pipe.sync_op_timing = True
    for _ in range(2):  # first step compiles; second gives clean timings
        pipe.train_step(batch)

    kinds = {k for (_, _, k) in pipe.last_op_times}
    assert {"f", "b", "cf", "cb"} <= kinds
    # every comm record carries real measured time
    for (_, _, k), (t, n) in pipe.last_op_times.items():
        if k in ("cf", "cb"):
            assert t > 0.0 and n > 0
    # and none of it leaks into the stage-busy (bubble) accounting
    for stage, busy in pipe.last_stage_busy_s.items():
        compute = sum(t for (s, _, k), (t, _) in pipe.last_op_times.items()
                      if s == stage and k in ("f", "b"))
        assert busy == pytest.approx(compute), "comm leaked into stage-busy"
