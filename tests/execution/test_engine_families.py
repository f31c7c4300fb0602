"""Model-family breadth through the MPMD engine, split out of
test_engine.py for wall-time budgeting (each family compiles its own
engine; this module is the long pole of the non-multiprocess suite)."""

import numpy as np
import pytest

from tests.execution.test_engine import cache_env, make_engine  # noqa: F401


@pytest.mark.parametrize("model_name", [
    "bert-tiny", "vit-tiny", "resnet-tiny", "clip-tiny",
    # Decoder LMs beyond gpt2 (RoPE/GQA and ALiBi position schemes) ride the
    # slow tier: gpt2-tiny already covers the decoder objective in tier 1.
    pytest.param("llama-tiny", marks=pytest.mark.slow),
    pytest.param("bloom-tiny", marks=pytest.mark.slow),
    # T5 (the one mid-pipeline batch_layers bridge) and swin (shifted
    # windows) are the two slowest compiles of the family sweep (t5-tiny
    # alone ~97 s — a third of the tier-1 overrun); bert/vit keep the
    # encoder and image objectives in tier 1, so these two ride the slow
    # tier with the other heavy families.
    pytest.param("t5-tiny", marks=pytest.mark.slow),
    pytest.param("swin-micro", marks=pytest.mark.slow),
])
def test_engine_drives_every_family(cache_env, devices8, model_name):
    """The MPMD engine is objective-agnostic (reference pipeline.py:169-216):
    MLM encoders, encoder-decoders (incl. T5's mid-pipeline batch_layers
    bridge), image classifiers (attention AND conv pipelines), and the CLIP
    dual-encoder train through the same plan -> instantiate -> train path as
    gpt2 — the round-2 gap where PipelineInstance required gpt-only
    param_specs (round-review finding, missing #1)."""
    engine = make_engine(num_hosts=2, steps=5, devices=devices8[:4],
                         microbatch=2, global_mb=8, model_name=model_name)
    engine.initialize_distributed()
    engine.instantiate_pipelines(engine.args.job.global_num_microbatch)
    losses = [engine._train_step() for _ in range(5)]
    assert all(np.isfinite(l) for l in losses), losses
    assert min(losses[2:]) < losses[0], losses
    # The generic path must also pass evaluation (forward-only program).
    assert np.isfinite(engine.evaluate(num_batches=1))


def test_clip_trains_on_real_paired_dataset(cache_env, devices8, tmp_path):
    """CLIP trains on a REAL (locally cached) paired image/caption dataset
    through the full plan -> instantiate -> train path — not synthetic
    pairs (round-4 missing #3; reference image pipeline semantics,
    dataset.py:88-148)."""
    from oobleck_tpu.config import (
        DistributedArguments,
        JobArguments,
        ModelArguments,
        OobleckArguments,
    )
    from oobleck_tpu.execution.dataset import HFImageTextDataset
    from oobleck_tpu.execution.engine import OobleckEngine
    from tests.execution.test_dataloader import make_imagefolder

    root = make_imagefolder(tmp_path / "pairs", n=64)
    args = OobleckArguments(
        dist=DistributedArguments(node_ips=["10.0.0.0", "10.0.0.1"]),
        job=JobArguments(microbatch_size=2, global_microbatch_size=8,
                         steps=3, learning_rate=1e-3, warmup_steps=2),
        model=ModelArguments(model_name="clip-tiny",
                             dataset_path=str(root)),
    )
    engine = OobleckEngine(args, devices=devices8[:4])
    assert isinstance(engine.dataset, HFImageTextDataset)
    engine.initialize_distributed()
    engine.instantiate_pipelines(args.job.global_num_microbatch)
    losses = [engine._train_step() for _ in range(3)]
    assert all(np.isfinite(l) for l in losses), losses
    assert np.isfinite(engine.evaluate(num_batches=1))


class _RecordingDataset:
    def __init__(self, ds):
        self.ds = ds
        self.seen: list[int] = []

    def __len__(self):
        return len(self.ds)

    def __getitem__(self, i):
        self.seen.append(i)
        return self.ds[i]


def test_eval_disjoint_and_rotating_default_config(cache_env, devices8):
    """Under the DEFAULT config, every index evaluate() reads is disjoint
    from every index training ever read, and consecutive evaluate() calls
    read different windows (rotation, not replay)."""
    engine = make_engine(num_hosts=2, steps=5, devices=devices8)
    engine.initialize_distributed()
    rec = _RecordingDataset(engine.dataset)
    engine.dataset = rec
    engine.instantiate_pipelines(engine.args.job.global_num_microbatch)
    for _ in range(3):
        engine._train_step()
    train_seen = set(rec.seen)

    rec.seen = []
    assert np.isfinite(engine.evaluate(num_batches=2))
    eval_first = set(rec.seen)
    rec.seen = []
    assert np.isfinite(engine.evaluate(num_batches=2))
    eval_second = set(rec.seen)

    assert eval_first and eval_second
    assert train_seen.isdisjoint(eval_first | eval_second)
    assert eval_first != eval_second  # windows rotate across calls
