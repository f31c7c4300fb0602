"""The copied yardstick still agrees with the program's own arithmetic."""

import pytest

from benchmarks import flops
from benchmarks.reference.gpt import RefConfig


@pytest.mark.parametrize("n,seq,layers,hidden", [
    (124_000_000, 1024, 12, 768), (577_600_000, 1024, 4, 2560),
    (1_638_000_000, 2048, 48, 1600)])
def test_flops_per_token_agrees_with_the_program(n, seq, layers, hidden):
    from oobleck_tpu.parallel.train import estimate_flops_per_token

    assert flops.train_flops_per_token(
        n, seq, num_layers=layers, hidden_size=hidden
    ) == estimate_flops_per_token(n, seq, num_layers=layers,
                                  hidden_size=hidden)


def test_peaks_agree_with_the_program_and_name_every_kind():
    from oobleck_tpu.parallel.train import PEAK_BF16_FLOPS

    assert {k: v["bf16_flops"] for k, v in flops.PEAKS.items()} \
        == PEAK_BF16_FLOPS


def test_mfu_agrees_with_the_program():
    from oobleck_tpu.parallel.train import mfu_estimate

    assert flops.mfu(26_700.0, 3.6e9, 1, "TPU v5 lite") == pytest.approx(
        mfu_estimate(26_700.0, 3.6e9, 1, 197e12))


@pytest.mark.parametrize("kind", ["cpu", "TPU v9", ""])
def test_unknown_device_kind_is_an_error(kind):
    with pytest.raises(KeyError):
        flops.peaks(kind)
    with pytest.raises(KeyError):
        flops.roofline_seconds(1.0, 1.0, kind)


def test_param_count_agrees_with_the_program():
    from oobleck_tpu.models import build_model
    from oobleck_tpu.parallel.train import count_params

    for name, args in (("gpt2-tiny", {}), ("gpt3-2.7b", {"num_layers": 4}),
                       ("gpt2-xl", {})):
        c = build_model(name, args).config
        rc = RefConfig(c.vocab_size, c.max_position_embeddings,
                       c.hidden_size, c.num_layers, c.num_heads)
        assert rc.num_params() == count_params(build_model(name, args))


def test_attention_kernel_counts_by_hand():
    # batch 1, 1 head, seq 4, head_dim 2, bf16.
    ops, nbytes = flops.causal_attention_fwd(1, 1, 4, 2)
    assert ops == 2 * (2 * 4 * 4 * 2) / 2 and nbytes == 4 * 4 * 2 * 2
    ops_b, nbytes_b = flops.causal_attention_bwd(1, 1, 4, 2)
    assert ops_b == 2 * ops and nbytes_b == 2 * nbytes
    # gpt3-2.7b's microbatch: about balanced between compute and bytes.
    t, bound = flops.roofline_seconds(
        *flops.causal_attention_fwd(4, 32, 1024, 80), "TPU v5 lite")
    assert bound == "compute" and 0.9e-4 < t < 1.3e-4
