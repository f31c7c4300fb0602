"""Engine integration on the virtual 8-device CPU mesh, mirroring the
reference's engine tests (/root/reference/tests/execution/test_engine.py:
451-1065): planning + instantiation, heterogeneous training with DP sync,
and evaluation, all against ONE shared trained_engine fixture. The
engine-per-test paths live in test_engine_reconfig.py (failure/recovery)
and test_engine_families.py (model-family breadth) so each module fits the
per-call test budget."""

import os
import types

import numpy as np
import pytest

import jax

from oobleck_tpu.config import (
    DistributedArguments,
    JobArguments,
    ModelArguments,
    OobleckArguments,
)
from oobleck_tpu.execution.engine import OobleckEngine


@pytest.fixture(scope="session")
def cache_env(tmp_path_factory):
    """Session-scoped profile cache: deterministic planner inputs shared by
    every engine module, so gpt2-tiny is profiled once per run instead of
    once per module (profiling times every layer's fwd+bwd — minutes of
    redundant wall time across the split modules otherwise)."""
    tmp = tmp_path_factory.mktemp("profiles")
    old = os.environ.get("OOBLECK_TPU_CACHE")
    os.environ["OOBLECK_TPU_CACHE"] = str(tmp)
    yield
    if old is None:
        os.environ.pop("OOBLECK_TPU_CACHE", None)
    else:
        os.environ["OOBLECK_TPU_CACHE"] = old


def make_engine(num_hosts=4, steps=3, devices=None, microbatch=2, global_mb=16,
                model_name="gpt2-tiny", agent_ip=None):
    args = OobleckArguments(
        dist=DistributedArguments(
            node_ips=[f"10.0.0.{i}" for i in range(num_hosts)]
        ),
        job=JobArguments(
            microbatch_size=microbatch,
            global_microbatch_size=global_mb,
            steps=steps,
            learning_rate=1e-3,
            warmup_steps=2,
        ),
        model=ModelArguments(model_name=model_name, dataset_path="synthetic"),
    )
    devices = devices or jax.devices()[:8]
    return OobleckEngine(args, agent_ip=agent_ip, devices=devices)


@pytest.fixture(scope="module")
def trained_engine(cache_env, devices8):
    """Engine through full startup + a few steps (expensive; shared)."""
    engine = make_engine(num_hosts=4, steps=3, devices=devices8)
    engine.initialize_distributed()
    engine.instantiate_pipelines(engine.args.job.global_num_microbatch)
    return engine


def test_startup_plan(trained_engine):
    e = trained_engine
    assert e.chips_per_host == 2
    assert [t.num_hosts for t in e.templates][0] >= 1
    assert e.plan is not None
    assert sum(p.template.num_hosts for p in e.pipelines) == 4
    # all chips covered exactly once
    ranks = sorted(r for p in e.pipelines for r in p.ranks)
    assert ranks == list(range(8))


def test_train_steps_decrease_loss(trained_engine):
    e = trained_engine
    losses = [e._train_step() for _ in range(3)]
    assert all(np.isfinite(l) for l in losses)
    assert losses[-1] < losses[0]


def _np_leaves(tree):
    return [np.asarray(l, np.float32) for l in jax.tree.leaves(tree)]


def test_dp_sync_consistency(trained_engine):
    """Layer-granularity DP sync, end to end (reference engine.py:363-412):
    run the pipeline passes explicitly, hand-compute each shared layer's
    gradient sum from the captured per-pipeline local grads, and assert
    (a) do_allreduce returns exactly that sum to EVERY owner, (b) the local
    grads genuinely differ across owners (different microbatches — so a
    no-op do_allreduce cannot pass), and (c) after the optimizer step every
    owner holds identical, *changed* params. Self-contained: no dependence
    on params being init-identical or on fixture ordering (round-3 weak #2)."""
    e = trained_engine
    if len(e.pipelines) < 2:
        pytest.skip("plan chose a single pipeline")
    for pipe, dl in zip(e.pipelines, e.dataloaders):
        pipe.train_step(dl.next_batch())
    owners = e.dp_engine.owners
    shared = [li for li, ow in owners.items() if len(ow) > 1]
    assert shared, "no layer shared across pipelines in this plan"

    local = {li: [_np_leaves(p.grads[li]) for p in owners[li]]
             for li in shared}
    pre_params = {li: _np_leaves(owners[li][0].params[li]) for li in shared}

    synced = e.dp_engine.do_allreduce()

    for li in shared:
        want = [np.sum(ls, axis=0)
                for ls in zip(*local[li])]
        # Different pipelines consumed different microbatches, so the sum
        # must differ from any single owner's contribution; this is what
        # makes a no-op (return-local-grads) do_allreduce fail here.
        assert any(
            not np.allclose(w, l, rtol=1e-5, atol=1e-7)
            for w, l in zip(want, local[li][0])
        ), f"layer {li}: summed grads indistinguishable from local grads"
        for p in owners[li]:
            got = _np_leaves(synced[p.pipeline_id][li])
            for g, w in zip(got, want):
                np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-6)

    for pipe in e.pipelines:
        # As the engine's step does; the dict that comes back is the one
        # that went in, with every layer's new state.
        state = e.opt_states[pipe.pipeline_id]
        e.opt_states[pipe.pipeline_id] = pipe.apply_updates(
            e.optimizer, state, synced[pipe.pipeline_id],
        )
        assert e.opt_states[pipe.pipeline_id] is state
    for li in shared:
        ps = owners[li]
        ref = _np_leaves(ps[0].params[li])
        assert any(
            not np.allclose(r, old, rtol=1e-6, atol=1e-8)
            for r, old in zip(ref, pre_params[li])
        ), f"layer {li}: params did not change after the optimizer step"
        for other in ps[1:]:
            for x, y in zip(ref, _np_leaves(other.params[li])):
                np.testing.assert_allclose(x, y, rtol=1e-5, atol=1e-6)


def test_min_hosts_bound(cache_env, devices8):
    engine = make_engine(num_hosts=4, devices=devices8)
    engine.chips_per_host = 2
    assert engine.compute_min_hosts() >= 1


@pytest.mark.parametrize("platform,stats,want", [
    ("cpu", None, 1),                      # the test backend's assumed 16 GiB
    ("tpu", {"bytes_limit": 8 * 2**30}, 2),  # asked, not assumed
    ("tpu", {}, RuntimeError),
    ("tpu", None, RuntimeError),
])
def test_compute_min_hosts_asks_the_tpu_for_its_memory(platform, stats, want):
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


def test_peak_flops_is_a_keyed_table():
    """The engine's MFU gauge divides by this: not a substring match, and
    no default. An unknown device kind is an error."""
    from oobleck_tpu.parallel.train import peak_flops

    assert peak_flops("TPU v5 lite") == 197e12
    for kind in ("TPU v5 lite pod", "tpu v5 lite", "TPU v9", "cpu"):
        with pytest.raises(KeyError, match="no peak FLOP/s known"):
            peak_flops(kind)


def test_evaluate(trained_engine):
    # Held-out reserve exists BY DEFAULT (eval_fraction nonzero).
    assert trained_engine._eval_reserve() > 0
    loss = trained_engine.evaluate(num_batches=2)
    assert np.isfinite(loss) and 0 < loss < 20
    trained_engine.args.execution.eval_fraction = 0.1
    assert trained_engine._eval_reserve() == int(
        len(trained_engine.dataset) * 0.1
    )
    trained_engine.args.execution.eval_fraction = 0.02


def test_empty_validation_split_counts_as_absent(trained_engine, monkeypatch):
    """A validation split that tokenizes to zero sequences must count as
    absent at probe time, so the held-out tail reserve is sized nonzero and
    evaluate() never scores training data (nor divides by zero)."""
    import oobleck_tpu.execution.dataset as ds_mod
    from oobleck_tpu.execution.engine import _UNSET

    monkeypatch.setattr(ds_mod, "has_validation_split", lambda *a, **k: True)
    monkeypatch.setattr(ds_mod, "build_eval_dataset", lambda *a, **k: [])
    trained_engine._has_val_split = None
    trained_engine._eval_ds_cache = _UNSET
    try:
        assert trained_engine._has_validation_split() is False
        assert trained_engine._eval_reserve() > 0
        assert trained_engine.eval_dataset is None
        loss = trained_engine.evaluate(num_batches=2)
        assert np.isfinite(loss)
    finally:
        trained_engine._has_val_split = None
        trained_engine._eval_ds_cache = _UNSET


def test_dp_allreduce_batched_transfers_and_exactness(trained_engine):
    """The batched DP allreduce (a) moves one buffer per stage pair instead
    of one per layer-leaf, and (b) computes exactly the per-layer sums the
    reference semantics require (engine.py:363-412). Also prints a step-time
    comparison vs an unbatched reference implementation."""
    import time as _time

    e = trained_engine
    if len(e.pipelines) < 2:
        pytest.skip("plan chose a single pipeline")
    for pipe, dl in zip(e.pipelines, e.dataloaders):
        pipe.train_step(dl.next_batch())

    t0 = _time.perf_counter()
    synced = e.dp_engine.do_allreduce()
    batched_s = _time.perf_counter() - t0
    shared = [li for li, ow in e.dp_engine.owners.items() if len(ow) > 1]
    assert shared
    # Transfer count: at most ONE batched device_put per phase (the whole
    # transfer set is handed to the runtime at once), vs the 2-per-shared-
    # layer floor the unbatched implementation paid.
    assert 0 < e.dp_engine.last_transfer_count <= 2

    # Unbatched reference: per-layer device_put + add (the round-2 code).
    t0 = _time.perf_counter()
    expected: dict[int, dict[int, object]] = {}
    for li in shared:
        owners = e.dp_engine.owners[li]
        anchor = owners[0]
        target = anchor.stages[anchor.stage_of_layer(li)].param_shardings[li]
        total = anchor.grads[li]
        for other in owners[1:]:
            moved = jax.device_put(other.grads[li], target)
            total = jax.tree.map(lambda a, b: a + b, total, moved)
        expected[li] = total
    unbatched_s = _time.perf_counter() - t0
    print(f"\ndp_allreduce batched={batched_s * 1e3:.1f}ms "
          f"unbatched={unbatched_s * 1e3:.1f}ms "
          f"device_put calls={e.dp_engine.last_transfer_count} "
          f"(vs >= {2 * len(shared)} unbatched per-layer)")

    for li in shared:
        anchor_id = e.dp_engine.owners[li][0].pipeline_id
        got = jax.tree.leaves(synced[anchor_id][li])
        want = jax.tree.leaves(expected[li])
        for g, w in zip(got, want):
            np.testing.assert_allclose(np.asarray(g, np.float32),
                                       np.asarray(w, np.float32),
                                       rtol=1e-5, atol=1e-7)
