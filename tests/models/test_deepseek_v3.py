"""DeepSeek-V3 family (`models/deepseek_v3.py`): the program against the
plain reference (`benchmarks/reference/deepseek_v3.py`) on seeded weights at
`moonlight-tiny` sizes, in float32; the share test; the parts that are new;
and the engine driving the family unchanged."""

import functools
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.reference import deepseek_v3 as ref
from oobleck_tpu.models import build_model, routed

SEED = 5_000_000_019      # more than 32 signed bits hold
BALANCE = (2, 32)


def ref_config(c, held, offset):
    return ref.RefConfig(
        vocab_size=c.data_vocab_size, hidden_size=c.hidden_size,
        num_layers=c.num_layers, num_heads=c.num_heads,
        kv_lora_rank=c.kv_lora_rank, qk_nope_head_dim=c.qk_nope_head_dim,
        qk_rope_head_dim=c.qk_rope_head_dim, v_head_dim=c.v_head_dim,
        intermediate_size=c.intermediate_size,
        moe_intermediate_size=c.moe_intermediate_size,
        first_k_dense_replace=c.first_k_dense_replace,
        num_experts=c.num_experts, num_experts_per_tok=c.num_experts_per_tok,
        n_shared_experts=c.n_shared_experts, num_experts_held=held,
        expert_offset=offset, routed_scaling_factor=c.routed_scaling_factor,
        norm_eps=c.norm_eps, latent_norm_eps=c.latent_norm_eps,
        rope_theta=c.rope_theta)


@functools.lru_cache(maxsize=None)
def _seeded(rc):
    """The seed's reference weights of one share, made once a module."""
    return ref.init_params(SEED, rc, BALANCE)


def _pair(held, offset, **extra):
    model = build_model("moonlight-tiny", {
        "dtype": jnp.float32, "remat": False, "attention_impl": "xla",
        "num_experts_held": held, "expert_offset": offset, **extra})
    rc = ref_config(model.config, held, offset)
    params = _seeded(rc)
    return model, rc, params, [params["embed"], *params["blocks"],
                               params["head"]]


SHARES = [(8, 0, {}, "xla"), (2, 4, {}, "pallas"),
          (1, 7, {"vocab_rows_held": 128}, "xla")]
SHARE_IDS = ["all_held", "experts_4_to_5_kernels",
             "one_expert_half_vocabulary"]


@pytest.mark.parametrize("held,offset,extra,impl", SHARES, ids=SHARE_IDS)
def test_program_matches_reference(held, offset, extra, impl):
    """Logits, loss and every gradient, whole and as a share; through the
    XLA form of latent attention and through the kernels (interpreted)."""
    model, rc, params, plist = _pair(held, offset, attention_impl=impl,
                                     **extra)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0,
                                rc.vocab_size)

    @jax.jit
    def program(plist):
        def loss(pl):
            logits, routing = model.forward(pl, tokens, return_routing=True)
            return model.loss_from_logits(logits, {"input_ids": tokens}), (
                logits, routing)
        return jax.value_and_grad(loss, has_aux=True)(plist)

    @jax.jit
    def reference(params):
        logits, _ = ref.forward(params, tokens, rc)
        return logits, ref.loss_and_grads(params, tokens, rc)

    (loss, (logits, routing)), grads = program(plist)
    want, ((ref_loss, own), ref_grads) = reference(params)
    assert logits.shape == (2, 32, model.config.padded_vocab_size)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(want),
                               atol=1e-5)
    assert float(ref.mismatch_share(routing, own)) == 0.0
    assert abs(float(loss) - float(ref_loss)) < 1e-5
    ref_list = [ref_grads["embed"], *ref_grads["blocks"], ref_grads["head"]]
    worst = jax.tree.map(
        lambda a, b: float(jnp.max(jnp.abs(a - b))
                           / (jnp.max(jnp.abs(b)) + 1e-12)), grads, ref_list)
    assert max(jax.tree.leaves(worst)) < 2e-5, worst
    # The selection bias selects and takes no gradient, here as there.
    for block in rc.routed_blocks:
        assert not np.asarray(grads[block + 1]["ff"]["expert_bias"]).any()
        assert np.asarray(params["blocks"][block]["ff"]["expert_bias"]).any()


@pytest.mark.parametrize("shares", [8, 2], ids=["eight_chips", "two_chips"])
def test_shares_add_up_to_the_uncut_layer(shares):
    """The share test: the routed parts that all the chips of an
    expert-parallel group give, plus the shared experts (which each
    computes alike) counted ONCE, add up to the uncut reference's layer."""
    _, rc, params, _ = _pair(8, 0)
    block = rc.routed_blocks[1]
    p = params["blocks"][block]["ff"]
    h = jax.random.normal(jax.random.PRNGKey(3), (2, 32, rc.hidden_size))
    whole, own = ref._routed(p, h, rc, "highest", None)
    shared = ref._swiglu(p["shared"], h, "highest")
    held = rc.num_experts // shares
    total = jnp.zeros_like(whole)
    for chip in range(shares):
        model = build_model("moonlight-tiny", {
            "dtype": jnp.float32, "remat": False, "attention_impl": "xla",
            "num_experts_held": held, "expert_offset": chip * held})
        lo, hi = chip * held, (chip + 1) * held
        p_chip = dict(p, w1=p["w1"][lo:hi], w3=p["w3"][lo:hi],
                      w2=p["w2"][lo:hi])
        part, chosen = model.feed_forward(block, p_chip, h,
                                          return_routing=True)
        # Every chip routes over ALL the experts, alike.
        np.testing.assert_array_equal(np.sort(np.asarray(chosen), -1),
                                      np.sort(np.asarray(own), -1))
        # What a chip gives: its experts' part and the shared experts.
        total = total + (part - model.dense_ff(p["shared"], h))
    np.testing.assert_allclose(np.asarray(total + shared), np.asarray(whole),
                               atol=3e-6)
    assert float(jnp.max(jnp.abs(shared))) > 1e-3       # it is not nothing


def test_published_shapes():
    model = build_model("moonlight-16b-a3b", {})
    c = model.config
    assert (c.num_layers, c.hidden_size, c.num_heads, c.head_dim, c.ffn_dim,
            c.num_experts, c.num_experts_per_tok, c.n_shared_experts,
            c.vocab_size, c.max_position_embeddings) == (
        27, 2048, 16, 192, 11264, 64, 6, 2, 163840, 8192)
    assert model.routed_blocks == tuple(range(1, 27))
    shape = lambda i: jax.eval_shape(lambda r: model.init_layer(r, i),
                                     jax.random.PRNGKey(0))
    dense, sparse = shape(1), shape(2)
    assert dense["attn"]["wq"].shape == (2048, 16, 192)
    assert dense["attn"]["wkv_a"].shape == (2048, 512 + 64)
    assert dense["attn"]["kv_norm"].shape == (512,)
    assert dense["attn"]["wkv_b"].shape == (512, 16, 128 + 128)
    assert dense["attn"]["wo"].shape == (16, 128, 2048)
    assert dense["ff"]["w1"].shape == (2048, 11264)
    assert sparse["ff"]["w1"].shape == (64, 2048, 1408)
    assert sparse["ff"]["shared"]["w2"].shape == (2816, 2048)
    assert sparse["ff"]["router"].shape == (2048, 64)
    assert sparse["ff"]["expert_bias"].shape == (64,)
    assert not model.fused_supported
    assert model.frozen_param_names == ("expert_bias",)
    count = lambda t: sum(int(np.prod(x.shape)) for x in jax.tree.leaves(t))
    assert count(dense["attn"]) == 13_763_072          # ISSUE 35's table
    assert count(sparse["ff"]["shared"]) == 17_301_504


def test_the_cut_is_the_issue_s_parameter_count():
    model = build_model("moonlight-16b-a3b", {
        "num_layers": 5, "num_experts_held": 8, "vocab_rows_held": 20480})
    total = sum(
        int(np.prod(x.shape)) for i in range(model.num_pipeline_layers)
        for x in jax.tree.leaves(jax.eval_shape(
            lambda r, i=i: model.init_layer(r, i), jax.random.PRNGKey(0))))
    rc = ref_config(model.config, 8, 0)
    assert total == rc.num_params() == 568_484_608


def test_profiler_times_each_kind_of_layer_once():
    model = build_model("moonlight-16b-a3b", {"num_layers": 5})
    names = [model.layer_name(i) for i in range(model.num_pipeline_layers)]
    assert names == ["embed", "dense_0", "routed_1", "routed_2", "routed_3",
                     "routed_4", "head"]
    # planning/profiler.py reuses a row by the name before its last "_".
    prefixes = [n.rsplit("_", 1)[0] for n in names[1:-1]]
    assert prefixes == ["dense"] + ["routed"] * 4


@pytest.mark.parametrize("bad,match", [
    ({"first_k_dense_replace": 9}, "first_k_dense_replace"),
    ({"qk_rope_head_dim": 7}, "even"),
    ({"num_experts_held": 4, "expert_offset": 6}, "experts"),
    ({"vocab_rows_held": 512}, "vocab_rows_held"),
    ({"no_such_field": 1}, "unknown"),
], ids=["dense_layers", "rotary_width", "experts", "vocabulary", "unknown"])
def test_configuration_is_checked(bad, match):
    with pytest.raises(ValueError, match=match):
        build_model("moonlight-tiny", bad)


def test_the_rotary_key_is_one_vector_a_position():
    """Changing the shared rotary key's projection column moves every
    head's output; the latent's norm has its own eps and scale."""
    model, rc, params, plist = _pair(8, 0)
    c = model.config
    h = jax.random.normal(jax.random.PRNGKey(5), (1, 16, c.hidden_size))
    p = plist[1]
    base = model.operator_out(0, p, h)
    moved = jax.tree.map(lambda x: x, p)
    moved["attn"] = dict(p["attn"], wkv_a=p["attn"]["wkv_a"].at[
        :, c.kv_lora_rank:].multiply(-1.0))
    by_head = jnp.einsum(
        "bse,hde->bhs", model.operator_out(0, moved, h) - base,
        jnp.ones_like(p["attn"]["wo"]))
    assert p["attn"]["wkv_a"].shape[1] == c.kv_lora_rank + c.qk_rope_head_dim
    assert float(jnp.abs(by_head).max()) > 0
    assert c.latent_norm_eps == 1e-6 and c.norm_eps == 1e-5


def test_routing_probe_takes_either_family_and_sets_the_gauge():
    from oobleck_tpu.models import lfm2
    from oobleck_tpu.utils import metrics

    assert lfm2.routing_probe is routed.routing_probe
    model, rc, params, plist = _pair(2, 4)
    tokens = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0,
                                           rc.vocab_size))
    reg = metrics.registry()
    pairs = reg.counter("oobleck_moe_routed_pairs_total")
    probed = reg.counter("oobleck_moe_probed_tokens_total")
    held_rows = reg.gauge("oobleck_moe_held_rows")
    before = probed.value()
    before_pairs = {b: pairs.value(layer=str(b)) for b in model.routed_blocks}
    routing = routed.routing_probe(model, plist, tokens)
    assert len(routing) == len(model.routed_blocks) == 2
    assert probed.value() - before == 64
    for block, chosen in zip(model.routed_blocks, routing):
        assert chosen.shape == (2, 32, 3)
        here = int(((chosen >= 4) & (chosen < 6)).sum())
        assert pairs.value(layer=str(block)) - before_pairs[block] == here
        assert held_rows.value(layer=str(block)) == here
    _, own = ref.forward(params, jnp.asarray(tokens), rc)
    assert float(ref.mismatch_share([jnp.asarray(r) for r in routing],
                                    own)) == 0.0
    # A second probe SETS the gauge (the counter adds).
    routed.routing_probe(model, plist, tokens[:1])
    again = int(((routing[0][:1] >= 4) & (routing[0][:1] < 6)).sum())
    assert held_rows.value(layer=str(model.routed_blocks[0])) == again


def test_engine_end_to_end_at_the_jobs_sequence_length(tmp_path):
    """The MPMD engine drives the family unchanged, at the job's own
    sequence length: the planner profiles two kinds of block, the generic
    stage path runs them; the bias stays as it was. (Two hosts and a host
    loss re-plan by layer, whatever the family: tests/models/test_lfm2.py.)"""
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
            dist=DistributedArguments(node_ips=["10.0.0.0"]),
            job=JobArguments(microbatch_size=1, global_microbatch_size=2,
                             steps=4, learning_rate=1e-3, warmup_steps=1,
                             seq_len=64),
            model=ModelArguments(
                model_name="moonlight-tiny", dataset_path="synthetic",
                model_args={"num_experts_held": 2, "expert_offset": 4,
                            "vocab_rows_held": 128}),
        )
        engine = OobleckEngine(args, devices=jax.devices()[:1])
        assert engine.dataset.vocab_size == 128       # the rows held
        assert engine.seq_len == 64
        engine.initialize_distributed()
        engine.instantiate_pipelines(args.job.global_num_microbatch)
        pipe = engine.pipelines[0]
        li = next(l for l, p in pipe.params.items()
                  if "shared" in p.get("ff", {}))
        before = jax.tree.map(np.asarray, pipe.params[li])
        losses = [engine._train_step() for _ in range(2)]
        assert all(np.isfinite(l) for l in losses)
        after = pipe.params[li]
        moved = lambda a, b: np.abs(np.asarray(a) - b).max() > 0
        assert moved(after["ff"]["shared"]["w1"], before["ff"]["shared"]["w1"])
        assert moved(after["ff"]["router"], before["ff"]["router"])
        assert moved(after["attn"]["wkv_a"], before["attn"]["wkv_a"])
        assert moved(after["attn"]["kv_norm"], before["attn"]["kv_norm"])
        assert not moved(after["ff"]["expert_bias"],
                         before["ff"]["expert_bias"])
    finally:
        if old is None:
            os.environ.pop("OOBLECK_TPU_CACHE", None)
        else:
            os.environ["OOBLECK_TPU_CACHE"] = old
