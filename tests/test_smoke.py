"""Fast smoke tier (<5 min on the 8-device CPU mesh).

Round-3 shipped with the core MPMD training path broken because the full
suite exceeds a round's test budget (round-3 review, weak #5). This module is the
must-stay-green gate: it walks planning -> heterogeneous instantiation ->
multi-pipeline _train_step (DP allreduce included) -> reconfigure -> resumed
training on one shared tiny engine, plus one fused-path step.

Run before EVERY snapshot:  python -m pytest tests/test_smoke.py -q
(also selectable as:        python -m pytest -m smoke -q)
"""

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

pytestmark = pytest.mark.smoke


@pytest.fixture(scope="module")
def cache_env(tmp_path_factory):
    import os

    tmp = tmp_path_factory.mktemp("profiles")
    old = os.environ.get("OOBLECK_TPU_CACHE")
    os.environ["OOBLECK_TPU_CACHE"] = str(tmp)
    yield
    if old is None:
        os.environ.pop("OOBLECK_TPU_CACHE", None)
    else:
        os.environ["OOBLECK_TPU_CACHE"] = old


def test_smoke_mpmd_train_allreduce_reconfigure(cache_env):
    """The exact path that broke at round-3 HEAD, end to end."""
    devices = jax.devices()[:4]
    args = OobleckArguments(
        dist=DistributedArguments(
            node_ips=[f"10.0.0.{i}" for i in range(4)]
        ),
        job=JobArguments(
            microbatch_size=1,
            global_microbatch_size=8,
            steps=4,
            learning_rate=1e-3,
            warmup_steps=1,
        ),
        model=ModelArguments(model_name="gpt2-tiny", dataset_path="synthetic"),
    )
    engine = OobleckEngine(args, devices=devices)
    engine.initialize_distributed()
    engine.instantiate_pipelines(args.job.global_num_microbatch)
    assert len(engine.pipelines) >= 2, "smoke config must exercise DP sync"

    losses = [engine._train_step() for _ in range(2)]
    assert all(np.isfinite(l) for l in losses)
    # The DP allreduce actually ran (round-3 regression raised NameError here).
    shared = [li for li, ow in engine.dp_engine.owners.items() if len(ow) > 1]
    assert shared and engine.dp_engine.last_transfer_count > 0

    engine.reconfigure("10.0.0.1")
    assert len(engine.recovery_times) == 1
    loss = engine._train_step()
    assert np.isfinite(loss)
    ranks = sorted(r for p in engine.pipelines for r in p.ranks)
    assert len(ranks) == len(set(ranks))


def test_smoke_fused_step(cache_env):
    """One fused SPMD train step on an 8-chip mesh."""
    devices = jax.devices()[:8]
    from oobleck_tpu.config import ExecutionArguments

    args = OobleckArguments(
        dist=DistributedArguments(node_ips=["10.0.0.0"]),
        job=JobArguments(
            microbatch_size=4,
            global_microbatch_size=8,
            steps=2,
            learning_rate=1e-3,
            warmup_steps=1,
        ),
        model=ModelArguments(model_name="gpt2-tiny", dataset_path="synthetic"),
        execution=ExecutionArguments(engine_path="fused", num_stages=2),
    )
    engine = OobleckEngine(args, devices=devices)
    engine.initialize_distributed()
    engine.instantiate_pipelines(args.job.global_num_microbatch)
    loss = engine._train_step()
    assert np.isfinite(loss)
