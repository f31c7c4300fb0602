"""The Ouro family (`models/ouro.py`: blocks gone through `num_passes`
times over one set of weights, sandwich norms, an exit gate and a loss over
all exits): one pass is the plain stack; the exit distribution against the
plain reference's product of sigmoids (`benchmarks/reference/ouro.py`); the
contract's walk; the carry's one shape; the published sizes and the
benchmark's cut. The loss and every gradient leaf against the reference,
and a shared weight's gradient against the reference's untied copies, are
`tests/execution/test_looped_pipeline.py`'s (through the pipeline, at
hidden 64, 4 heads of 16, 2 blocks, 3 passes, 32 positions, a vocabulary of
256); the engine end to end with `evaluate()` reading the last exit is
`tests/benchmarks/test_bench_ouro.py`'s rehearsal."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.reference import ouro as ref
from oobleck_tpu.models import base, build_model, ouro
from oobleck_tpu.models.gpt import cross_entropy_loss

SEED = 5_000_000_029      # more than 32 signed bits hold
SEQ, BATCH = 32, 2


def ref_config(c) -> ref.RefConfig:
    return ref.RefConfig(
        vocab_size=c.data_vocab_size, hidden_size=c.hidden_size,
        num_layers=c.num_layers, num_passes=c.num_passes,
        num_heads=c.num_heads, num_kv_heads=c.num_kv_heads,
        head_dim=c.head_dim, intermediate_size=c.intermediate_size,
        rope_theta=c.rope_theta, norm_eps=c.norm_eps,
        exit_entropy_weight=c.exit_entropy_weight)


def as_list(tree):
    return [tree["embed"], *tree["blocks"], tree["head"]]


def test_one_pass_is_the_plain_stack():
    """R = 1: embed, the blocks once, the final norm, the head, the mean
    next-token cross-entropy; no gate, no entropy term, no gradient into
    the gate."""
    model = build_model("ouro-tiny", {"dtype": jnp.float32, "num_passes": 1})
    rc = ref_config(model.config)
    p = as_list(ref.init_params(SEED, rc))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (BATCH, SEQ), 0, 256)

    def plain(p):
        x = p[0]["wte"][tokens]
        for b in (0, 1):
            x = ref._block_forward(p[1 + b], x, rc, "highest")
        x = ref._norm(x, p[2]["close"]["ln_f"]["scale"], rc.norm_eps)
        return cross_entropy_loss(x @ p[3]["w"], tokens)

    with jax.default_matmul_precision("highest"):
        loss, grads = jax.jit(jax.value_and_grad(model.loss))(
            p, {"input_ids": tokens})
        want = jax.jit(plain)(p)
    assert float(loss) == pytest.approx(float(want), rel=2e-6)
    close = grads[2]["close"]
    assert not np.asarray(close["w_g"]).any() and float(close["b_g"]) == 0.0
    assert np.asarray(close["ln_f"]["scale"]).any()


@pytest.mark.parametrize("passes", [1, 2, 4])
def test_the_exit_distribution_sums_to_one_and_is_the_reference_s(passes):
    gates = 3.0 * jax.random.normal(jax.random.PRNGKey(passes),
                                    (passes, 2, 7))
    p = jnp.exp(ouro.exit_log_distribution(gates))
    np.testing.assert_allclose(np.asarray(p.sum(0)), 1.0, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(p),
                               np.asarray(ref.exit_distribution(gates)),
                               rtol=1e-5, atol=1e-7)
    # The last pass takes what is left, whatever its own gate says.
    moved = jnp.exp(ouro.exit_log_distribution(gates.at[-1].add(5.0)))
    np.testing.assert_allclose(np.asarray(moved), np.asarray(p), rtol=1e-6)


def test_the_contract_says_what_repeats():
    model = build_model("ouro-tiny", {})
    assert base.repeated(model) == (range(1, 3), 3)
    assert base.layer_walk(model) == (0, 1, 2, 1, 2, 1, 2, 3)
    # A part of the range is one visit's share: walked once.
    assert base.layer_walk(model, (0, 1)) == (0, 1)
    assert base.layer_walk(model, (1, 2)) == (1, 2, 1, 2, 1, 2)
    assert [base.passes_of(model, li) for li in range(4)] == [1, 3, 3, 1]
    assert [model.layer_name(i) for i in range(4)] == [
        "embed", "block_0", "close_1", "head"]
    # Every other family repeats nothing: walked as before.
    plain = build_model("smallthinker-tiny", {})
    assert base.repeated(plain) == (range(0), 1)
    assert base.layer_walk(plain) == tuple(range(plain.num_pipeline_layers))
    assert base.applied_param_count(plain) == sum(
        base.param_count(plain.init_layer(jax.random.PRNGKey(0), li))
        for li in range(plain.num_pipeline_layers))


def test_the_carry_has_one_shape_over_all_visits():
    from oobleck_tpu.parallel.cross_host import activation_avals

    model = build_model("ouro-tiny", {})
    avals = activation_avals(model, 1, SEQ)
    shapes = [jax.tree.map(lambda a: (a.shape, a.dtype.name), a)
              for a in avals]
    assert shapes[0] == shapes[1] == shapes[2] == {
        "h": ((1, SEQ, 64), "bfloat16"),
        "exits": ((3, 1, SEQ, 64), "bfloat16"),
        "gates": ((3, 1, SEQ), "float32")}


def test_published_sizes_and_the_cells_cut():
    """The defaults are Ouro-2.6B's; the benchmark's cut (six blocks) is
    the issue's 509,661,185 parameters and 11.0 GFLOP a token."""
    c = ouro.OuroConfig()
    assert (c.hidden_size, c.num_layers, c.num_heads, c.num_kv_heads,
            c.head_dim, c.intermediate_size, c.vocab_size, c.num_passes,
            c.rope_theta, c.norm_eps, c.max_position_embeddings) == (
        2048, 48, 16, 16, 128, 5632, 49152, 4, 1e6, 1e-6, 65536)
    model = build_model("ouro-2.6b", {"num_layers": 6})
    rng = jax.random.PRNGKey(0)
    held = [base.param_count(jax.eval_shape(
        lambda r, i=i: model.init_layer(r, i), rng))
        for i in range(model.num_pipeline_layers)]
    assert held[1] == 51_388_416 and held[6] == 51_388_416 + 4_097
    assert held[0] == held[7] == 100_663_296
    assert sum(held) == 509_661_185 == ref_config(model.config).num_params()
    applied = base.applied_param_count(model)
    assert applied == 4 * (6 * 51_380_224 + 100_663_296) == ref_config(
        model.config).applied_params()
    from oobleck_tpu.parallel.train import estimate_flops_per_token

    per_token = estimate_flops_per_token(
        applied, 4096, num_layers=4 * 6, hidden_size=2048)
    assert per_token == pytest.approx(11.02e9, rel=2e-3)
    with pytest.raises(ValueError, match="unknown model_args"):
        build_model("ouro-2.6b", {"total_ut_steps": 4})
    with pytest.raises(ValueError, match="passes"):
        build_model("ouro-2.6b", {"num_passes": 0})
