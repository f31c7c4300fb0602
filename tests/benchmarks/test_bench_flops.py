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


# --------------------------------------------------------------------- #
# the Mamba-2 chunked scan (flops_ssd.py), at nemotron-3-nano-30b-a3b's   #
# call: [1, 4096, 64, 64] in 8 groups, a state of 128, chunks of 128      #
# --------------------------------------------------------------------- #

SCAN = (1, 4096, 64, 64, 8, 128, 128)
# Once a (sequence, group, chunk), 1 x 8 x 32 = 256 of them: Q = N = 128,
# W = 8 heads x 64 = 512.
QQN, QQW, QNW = 2 * 128 * 128 * 128, 2 * 128 * 128 * 512, 2 * 128 * 128 * 512
X = 4096 * 64 * 64           # elements of x, y, dY, dx
BC = 4096 * 8 * 128          # elements of B, C, dB, dC
STATES = 32 * 8 * 128 * 512  # the state at every chunk's start, float32
PER_POSITION = 4096 * 64     # float32 a (position, head)


@pytest.mark.parametrize("fn,ops,nbytes,ms,share_of_pr54s_call", [
    # C B^T, M x~, C H^T, B^T x~; x, B, C, dt, cum in, y and the states out.
    ("scan_fwd", 256 * (QQN + QQW + 2 * QNW),
     2 * (2 * X + 2 * BC) + 4 * STATES + 2 * 4 * PER_POSITION,
     0.18692, 0.18692 / 0.3395),
    # 3 at Q Q N, 3 at Q Q W, 5 at Q N W; x, dY, dx, B, C, dB, dC, the
    # states read, dt and cum in and two sums a position out.
    ("scan_bwd", 256 * (3 * QQN + 3 * QQW + 5 * QNW),
     2 * (3 * X + 4 * BC) + 4 * STATES + 4 * 4 * PER_POSITION,
     0.25094, 0.25094 / 0.6948),
])
def test_scan_kernel_counts_at_the_cells_shape(fn, ops, nbytes, ms,
                                               share_of_pr54s_call):
    from benchmarks import flops_ssd

    got_ops, got_bytes = getattr(flops_ssd, fn)(*SCAN)
    assert (got_ops, got_bytes) == (ops, nbytes)
    # ROADMAP W0 (m)'s operations to the digit: 13.96 and 37.6 GFLOP. Its
    # bytes (138 and 178 MB) counted B, C, dB, dC at ONE group of the 8:
    # 153.1 and 205.5 MB with all eight.
    assert got_ops == pytest.approx(
        {"scan_fwd": 13.96e9, "scan_bwd": 37.58e9}[fn], rel=1e-3)
    assert got_bytes == pytest.approx(
        {"scan_fwd": 153.09e6, "scan_bwd": 205.52e6}[fn], rel=1e-4)
    least, bound = flops.roofline_seconds(got_ops, got_bytes, "TPU v5 lite")
    assert bound == "memory" and least * 1e3 == pytest.approx(ms, rel=1e-4)
    # Against the one call PR 54 read (0.3395 / 0.6948 ms): 55 % and 36 %.
    assert share_of_pr54s_call == pytest.approx(
        {"scan_fwd": 0.5506, "scan_bwd": 0.3612}[fn], rel=1e-3)


def test_scan_counts_by_hand_at_a_small_shape():
    from benchmarks import flops_ssd

    # 2 sequences of 6 positions in chunks of 4 (2 chunks, the second
    # padded), 4 heads of 3 in 2 groups (W = 6), a state of 5, 2 bytes.
    steps, q, n, w = 2 * 2 * 2, 4, 5, 6
    ops, nbytes = flops_ssd.scan_fwd(2, 6, 4, 3, 2, 5, 4)
    assert ops == steps * 2 * q * (q * n + q * w + 2 * n * w)
    x, b, per = 2 * 6 * 4 * 3, 2 * 6 * 2 * 5, 2 * 6 * 4
    assert nbytes == 2 * (2 * x + 2 * b) + 4 * steps * n * w + 8 * per
    ops_b, nbytes_b = flops_ssd.scan_bwd(2, 6, 4, 3, 2, 5, 4)
    assert ops_b == steps * 2 * q * (3 * q * n + 3 * q * w + 5 * n * w)
    assert nbytes_b == 2 * (3 * x + 4 * b) + 4 * steps * n * w + 16 * per


# --------------------------------------------------------------------- #
# a dW product's float32 running sum (flops_moe.py, PR 42's kernel), at   #
# the five routed cells' shapes                                           #
# --------------------------------------------------------------------- #

@pytest.mark.parametrize("cell,rows,hidden,inter,held,sum_mb,dw_ms,bound", [
    # rows: the pairs a microbatch is expected to route to the held experts.
    ("lfm2-24b-a2b", 8 * 1024 * 4 / 8, 2048, 1536, 8, 201.33, 0.28167,
     "memory"),
    ("moonlight-16b-a3b", 4096 * 6 / 8, 2048, 1408, 8, 184.55, 0.25126,
     "memory"),
    ("nemotron-3-nano-30b-a3b", 4096 * 6 / 16, 2688, 1856, 8, 319.29,
     0.40690, "memory"),
    ("qwen3-next-80b-a3b", 4096 * 10 / 32, 2048, 512, 16, 134.22, 0.17188,
     "memory"),
    ("smallthinker-21b-a3b", 16384 * 6 / 8, 2560, 768, 8, 125.83, 0.25350,
     "memory"),
])
def test_a_dw_product_counts_the_float32_sum(cell, rows, hidden, inter, held,
                                             sum_mb, dw_ms, bound):
    import json
    from pathlib import Path

    from benchmarks import flops_moe, flops_moe_ungated

    config = json.loads((Path(flops.__file__).parent / "configs"
                         / f"{cell}.json").read_text())
    assert (config["hidden_size"], config["moe_intermediate_size"],
            config["num_experts_held"]) == (hidden, inter, held)
    ops, nbytes = flops_moe.grouped_product_dw(rows, hidden, inter, held)
    assert ops == 2 * rows * hidden * inter
    assert nbytes == rows * (hidden + inter) * 2 + held * hidden * inter * 8
    assert held * hidden * inter * 8 / 1e6 == pytest.approx(sum_mb, rel=1e-4)
    dw, got_bound = flops.roofline_seconds(ops, nbytes, "TPU v5 lite")
    assert got_bound == bound and dw * 1e3 == pytest.approx(dw_ms, rel=1e-4)
    one, _ = flops.roofline_seconds(
        *flops_moe.grouped_product(rows, hidden, inter, held), "TPU v5 lite")
    assert dw > one
    module, products, dws = ((flops_moe_ungated, 6, 2)
                             if config.get("mlp_hidden_act") == "relu2"
                             else (flops_moe, 9, 3))
    assert module.routed_layer_train_seconds(
        rows, hidden, inter, held, "TPU v5 lite") == pytest.approx(
        (products - dws) * one + dws * dw)
