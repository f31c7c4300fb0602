"""LFM2-MoE family (`models/lfm2.py`): the program against the plain
reference (`benchmarks/reference/lfm2.py`) on seeded weights at
`lfm2-moe-tiny` sizes, in float32; the parts that are new; and the engine
driving the family unchanged."""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

from benchmarks.reference import lfm2 as ref
from oobleck_tpu.models import build_model
from oobleck_tpu.models import lfm2

SEED = 5_000_000_019      # more than 32 signed bits hold


def _pair(held, offset, **extra):
    model = build_model("lfm2-moe-tiny", {
        "dtype": jnp.float32, "remat": False, "attention_impl": "xla",
        "num_experts_held": held, "expert_offset": offset, **extra})
    c = model.config
    rc = ref.RefConfig.from_config({
        "vocab_rows_held": c.data_vocab_size, "hidden_size": c.hidden_size,
        "num_layers": c.num_layers, "num_heads": c.num_heads,
        "num_kv_heads": c.num_kv_heads,
        "intermediate_size": c.intermediate_size,
        "moe_intermediate_size": c.moe_intermediate_size,
        "num_experts": c.num_experts,
        "num_experts_per_tok": c.num_experts_per_tok,
        "num_dense_layers": c.num_dense_layers,
        "layer_types": list(c.operators), "num_experts_held": held,
        "expert_offset": offset})
    params = ref.init_params(SEED, rc)
    return model, rc, params, [params["embed"], *params["blocks"],
                               params["head"]]


SHARES = [(8, 0, {}), (4, 2, {}), (2, 6, {"vocab_rows_held": 128})]
SHARE_IDS = ["all_held", "experts_2_to_5", "two_experts_half_vocabulary"]


@pytest.mark.parametrize("held,offset,extra", SHARES, ids=SHARE_IDS)
def test_program_matches_reference(held, offset, extra):
    """Logits, loss and every gradient, whole and as a share."""
    model, rc, params, plist = _pair(held, offset, **extra)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0,
                                rc.vocab_size)
    logits, routing = model.forward(plist, tokens, return_routing=True)
    want, own = ref.forward(params, tokens, rc)
    assert logits.shape == (2, 32, model.config.padded_vocab_size)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(want),
                               atol=1e-5)
    assert float(ref.mismatch_share(routing, own)) == 0.0
    loss, grads = jax.value_and_grad(
        lambda pl: model.loss(pl, {"input_ids": tokens}))(plist)
    (ref_loss, _), ref_grads = ref.loss_and_grads(params, tokens, rc)
    assert abs(float(loss) - float(ref_loss)) < 1e-5
    ref_list = [ref_grads["embed"], *ref_grads["blocks"], ref_grads["head"]]
    worst = jax.tree.map(
        lambda a, b: float(jnp.max(jnp.abs(a - b))
                           / (jnp.max(jnp.abs(b)) + 1e-12)), grads, ref_list)
    assert max(jax.tree.leaves(worst)) < 1e-5, worst


def test_layer_list_is_heterogeneous():
    model = build_model("lfm2-24b-a2b", {})
    c = model.config
    ops = [model.operator(b) for b in range(c.num_layers)]
    assert ops.count(lfm2.CONV) == 30 and ops.count(lfm2.ATTN) == 10
    assert ops[:7] == ["conv", "conv", "full_attention", "conv", "conv",
                       "conv", "full_attention"]
    assert [model.is_routed(b) for b in range(4)] == [False, False, True, True]
    assert (c.head_dim, c.ffn_dim, c.num_experts, c.num_experts_per_tok) == (
        64, 11776, 64, 4)
    shapes = jax.eval_shape(lambda r: model.init_layer(r, 3),
                            jax.random.PRNGKey(0))
    assert shapes["ff"]["w1"].shape == (64, 2048, 1536)
    assert shapes["attn"]["wk"].shape == (2048, 8, 64)
    assert set(jax.eval_shape(lambda r: model.init_layer(r, 1),
                              jax.random.PRNGKey(0))) == {
        "ln_op", "ln_ff", "conv", "ff"}
    with pytest.raises(ValueError, match="layer_types"):
        build_model("lfm2-24b-a2b", {"num_layers": 5,
                                     "layer_types": ["conv"] * 4})
    with pytest.raises(ValueError, match="experts"):
        build_model("lfm2-moe-tiny", {"num_experts_held": 4,
                                      "expert_offset": 6})


@pytest.mark.parametrize("length", [3, 4], ids=["kernel3", "kernel4"])
def test_short_conv_is_a_causal_depthwise_convolution(length):
    ks = jax.random.split(jax.random.PRNGKey(2), 2)
    bu = jax.random.normal(ks[0], (2, 24, 16))
    taps = jax.random.normal(ks[1], (length, 16))
    want = lax.conv_general_dilated(
        bu.transpose(0, 2, 1), taps[::-1].T[:, None, :], (1,),
        [(length - 1, 0)], feature_group_count=16,
        dimension_numbers=("NCH", "OIH", "NCH"),
        precision=lax.Precision.HIGHEST).transpose(0, 2, 1)
    np.testing.assert_allclose(np.asarray(lfm2.short_conv(bu, taps)),
                               np.asarray(want), atol=1e-5)
    # Causal: a later token changes no earlier output.
    later = bu.at[:, 20].add(1.0)
    np.testing.assert_array_equal(
        np.asarray(lfm2.short_conv(later, taps)[:, :20]),
        np.asarray(lfm2.short_conv(bu, taps)[:, :20]))


def test_expert_bias_takes_no_gradient_and_no_optimizer_state():
    from oobleck_tpu.parallel.train import make_optimizer

    model, rc, params, plist = _pair(8, 0)
    assert model.frozen_param_names == ("expert_bias",)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0,
                                rc.vocab_size)
    grads = jax.grad(lambda pl: model.loss(pl, {"input_ids": tokens}))(plist)
    block = plist[2]
    assert not np.asarray(grads[2]["ff"]["expert_bias"]).any()
    assert np.asarray(grads[2]["ff"]["router"]).any()
    assert np.asarray(block["ff"]["expert_bias"]).any()      # seeded, not 0
    optimizer = make_optimizer(learning_rate=1e-2, warmup_steps=1,
                               weight_decay=0.1,
                               frozen=model.frozen_param_names)
    state = optimizer.init(block)
    plain = make_optimizer().init(block)
    assert jax.tree.structure(state) == jax.tree.structure(plain)
    sizes = lambda s: sum(x.size for x in jax.tree.leaves(s))
    assert sizes(plain) - sizes(state) == 2 * rc.num_experts   # mu and nu
    updates, state = optimizer.update(grads[2], state, block)
    assert not np.asarray(updates["ff"]["expert_bias"]).any()
    assert np.asarray(updates["ff"]["router"]).any()
    # Weight decay alone would have moved it.
    moved, _ = make_optimizer(weight_decay=0.1).update(
        grads[2], make_optimizer(weight_decay=0.1).init(block), block)
    assert np.asarray(moved["ff"]["expert_bias"]).any()


def test_routing_probe_counts_what_it_saw():
    from oobleck_tpu.utils import metrics

    model, rc, params, plist = _pair(4, 2)
    tokens = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0,
                                           rc.vocab_size))
    reg = metrics.registry()
    pairs = reg.counter("oobleck_moe_routed_pairs_total")
    probed = reg.counter("oobleck_moe_probed_tokens_total")
    before = probed.value()
    before_pairs = {b: pairs.value(layer=str(b)) for b in model.routed_blocks}
    routing = lfm2.routing_probe(model, plist, tokens)
    assert len(routing) == len(model.routed_blocks) == 3
    assert probed.value() - before == 64
    for block, chosen in zip(model.routed_blocks, routing):
        assert chosen.shape == (2, 32, 2)
        here = int(((chosen >= 2) & (chosen < 6)).sum())
        assert pairs.value(layer=str(block)) - before_pairs[block] == here
    _, own = ref.forward(params, jnp.asarray(tokens), rc)
    assert float(ref.mismatch_share([jnp.asarray(r) for r in routing],
                                    own)) == 0.0


def test_lfm2_engine_end_to_end(tmp_path):
    """The MPMD engine drives the family unchanged: the planner profiles a
    layer list whose layers differ, the generic stage path runs it, the
    bias stays where it was, and a host loss re-plans."""
    from oobleck_tpu.config import (
        DistributedArguments,
        JobArguments,
        ModelArguments,
        OobleckArguments,
    )
    from oobleck_tpu.execution.engine import OobleckEngine

    old = os.environ.get("OOBLECK_TPU_CACHE")
    os.environ["OOBLECK_TPU_CACHE"] = str(tmp_path / "profiles")
    try:
        args = OobleckArguments(
            dist=DistributedArguments(
                node_ips=[f"10.0.0.{i}" for i in range(4)]),
            job=JobArguments(microbatch_size=1, global_microbatch_size=8,
                             steps=4, learning_rate=1e-3, warmup_steps=1),
            model=ModelArguments(
                model_name="lfm2-moe-tiny", dataset_path="synthetic",
                model_args={"num_experts_held": 4, "expert_offset": 2,
                            "vocab_rows_held": 128}),
        )
        engine = OobleckEngine(args, devices=jax.devices()[:4])
        assert engine.dataset.vocab_size == 128       # the rows held
        engine.initialize_distributed()
        engine.instantiate_pipelines(args.job.global_num_microbatch)
        pipe = engine.pipelines[0]
        li = next(l for l, p in pipe.params.items()
                  if "expert_bias" in p.get("ff", {}))
        bias = np.asarray(pipe.params[li]["ff"]["expert_bias"])
        router = np.asarray(pipe.params[li]["ff"]["router"])
        losses = [engine._train_step() for _ in range(2)]
        assert all(np.isfinite(l) for l in losses)
        after = pipe.params[li]["ff"]
        np.testing.assert_array_equal(np.asarray(after["expert_bias"]), bias)
        assert np.abs(np.asarray(after["router"]) - router).max() > 0
        state = engine.opt_states[pipe.pipeline_id][li]
        assert sum(x.size == 0 for x in jax.tree.leaves(state)) == 2
        engine.reconfigure("10.0.0.2")
        assert np.isfinite(engine._train_step())
    finally:
        if old is None:
            os.environ.pop("OOBLECK_TPU_CACHE", None)
        else:
            os.environ["OOBLECK_TPU_CACHE"] = old
