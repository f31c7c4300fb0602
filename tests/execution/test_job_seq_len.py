"""The job's sequence length (`job.seq_len`): absent, the engine, its data
and the planner's profile are at the length they always were; set, it is
honoured by all three, refused above the model's context, and part of the
profile's cache key."""

import jax
import pytest

from oobleck_tpu.config import (
    DEFAULT_MAX_SEQ_LEN,
    DistributedArguments,
    JobArguments,
    ModelArguments,
    OobleckArguments,
    training_seq_len,
)
from oobleck_tpu.models import build_model
from oobleck_tpu.planning import profiler


@pytest.fixture
def cache_env(tmp_path, monkeypatch):
    monkeypatch.setenv("OOBLECK_TPU_CACHE", str(tmp_path))
    return tmp_path / "profiles"


def _args(seq_len=None, model="gpt2-tiny"):
    return OobleckArguments(
        dist=DistributedArguments(node_ips=["10.0.0.0"]),
        job=JobArguments(microbatch_size=2, global_microbatch_size=4,
                         steps=1, seq_len=seq_len),
        model=ModelArguments(model_name=model, dataset_path="synthetic"))


@pytest.mark.parametrize("model,context,want", [
    ("gpt3-2.7b", 2048, 1024), ("gpt2", 1024, 1024), ("gpt2-tiny", 128, 128),
    ("lfm2-24b-a2b", 128000, 1024), ("moonlight-16b-a3b", 8192, 1024),
], ids=lambda x: str(x))
def test_absent_is_the_length_there_was(model, context, want):
    """A context-2048 model trains at 1024 unless the job says otherwise:
    `gpt3-2.7b.steady`'s runner refuses any other length."""
    config = build_model(model, {}).config
    assert config.max_position_embeddings == context
    assert training_seq_len(config) == training_seq_len(config, None) == want
    assert want == min(context, DEFAULT_MAX_SEQ_LEN)
    assert JobArguments().seq_len is None


@pytest.mark.parametrize("seq_len", [1, 2048], ids=["one", "whole_context"])
def test_set_is_used_up_to_the_context(seq_len):
    config = build_model("gpt3-2.7b", {}).config
    assert training_seq_len(config, seq_len) == seq_len


@pytest.mark.parametrize("seq_len", [0, -4, 2049, 4096])
def test_outside_the_context_is_refused(seq_len):
    config = build_model("gpt3-2.7b", {}).config
    with pytest.raises(ValueError, match="context"):
        training_seq_len(config, seq_len)


def test_a_model_without_a_context_takes_the_default():
    class Config:
        pass
    assert training_seq_len(Config()) == DEFAULT_MAX_SEQ_LEN
    assert training_seq_len(Config(), 4096) == 4096


def test_engine_refuses_a_length_above_the_context(cache_env):
    from oobleck_tpu.execution.engine import OobleckEngine

    with pytest.raises(ValueError, match="context of 128"):
        OobleckEngine(_args(seq_len=256), devices=jax.devices()[:1])
    assert not cache_env.exists()                  # before any profile


@pytest.mark.parametrize("seq_len,want,tag", [
    (None, 128, "gpt2-tiny-default"), (64, 64, "gpt2-tiny-default+seq64"),
], ids=["absent", "set"])
def test_engine_dataset_and_profiler_use_it(cache_env, seq_len, want, tag):
    from oobleck_tpu.execution.engine import OobleckEngine

    seen = []
    timed = profiler.profile_execution_layers

    def spy(model, microbatch_size, seq_len=None):
        seen.append(training_seq_len(model.config, seq_len))
        return timed(model, microbatch_size, seq_len)

    profiler.profile_execution_layers = spy
    try:
        engine = OobleckEngine(_args(seq_len), devices=jax.devices()[:1])
    finally:
        profiler.profile_execution_layers = timed
    assert engine.seq_len == want and seen == [want]
    assert engine.dataset[0]["input_ids"].shape == (want,)
    assert [p.name for p in cache_env.iterdir()] == [tag]
    assert len(engine.profiles) == engine.model.num_pipeline_layers
    # The activations the planner budgets follow the length.
    width = engine.model.config.hidden_size
    assert engine.profiles[1].mem_activation == 2 * want * width * 2
    # The round trip: the field travels with the job's arguments.
    again = OobleckArguments.from_dict(engine.args.to_dict())
    assert again.job.seq_len == seq_len


def test_two_lengths_do_not_share_a_profile(cache_env):
    at = lambda n: profiler.profile(
        "gpt2-tiny", {}, model_tag=profiler.job_tag("default", n),
        microbatch_size=1, seq_len=n, chips_per_host=1, max_hosts=1)
    default, short, shorter = at(None), at(64), at(32)
    assert len({default, short, shorter}) == 3
    assert profiler.job_tag("default", None) == "default"
    assert profiler.job_tag("default", 64) == "default+seq64"
    rows = lambda path: profiler.load_profile(
        "gpt2-tiny", path.name.removeprefix("gpt2-tiny-"), 1)
    assert rows(short)[1].mem_activation == 2 * rows(shorter)[1].mem_activation
    assert rows(default)[1].mem_activation == 2 * rows(short)[1].mem_activation
    stamp = (short / "mb1.json").stat().st_mtime_ns
    assert at(64) == short                                   # a hit
    assert (short / "mb1.json").stat().st_mtime_ns == stamp
