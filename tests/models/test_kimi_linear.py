"""`models/kimi_linear.py`: the Kimi-Linear family on the training path,
against the plain reference (`benchmarks/reference/kimi_linear.py`: float32,
the KDA layers walked one position after another) on seeded weights; the
layer kinds from the published lists; the parameter counts by part against
the configuration file's; the shares adding up to the uncut layer; a
two-stage pipeline cut between kinds against one stage; and the latent
layer against `models/deepseek_v3.py`'s with its rotary taken out.
"""

import functools
import json
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.reference import kimi_linear as ref
from oobleck_tpu.models import base, build_model, deepseek_v3, kimi_linear
from oobleck_tpu.models import routed

ROOT = Path(__file__).resolve().parents[2]
CONFIG = json.loads((ROOT / "benchmarks" / "configs"
                     / "kimi-linear-48b-a3b.json").read_text())
SEED = 5_000_000_023      # more than 32 signed bits hold
LAYERS = ["embed", "kda_dense_0", "kda_routed_1", "kda_routed_2",
          "mla_routed_3", "kda_routed_4", "head"]
# One block of each KIND, KDA(dense) MLA KDA: what a comparison compiles
# follows the blocks, and a second and third `kda_routed` teach it nothing.
KINDS = {"num_layers": 3, "kda_layers": (1, 3), "full_attn_layers": (2,)}
KIND_LAYERS = ["embed", "kda_dense_0", "mla_routed_1", "kda_routed_2", "head"]


@pytest.fixture(autouse=True, scope="module")
def _leave_no_series_behind():
    """The routing probe's counters live in the PROCESS-GLOBAL registry."""
    yield
    from oobleck_tpu.utils import metrics

    metrics.registry().clear()


def ref_config(c, held, offset):
    return ref.RefConfig(
        vocab_size=c.data_vocab_size, hidden_size=c.hidden_size,
        num_layers=c.num_layers, kda_layers=c.kda_layers,
        full_attn_layers=c.full_attn_layers,
        linear_num_heads=c.linear_num_heads,
        linear_head_dim=c.linear_head_dim,
        short_conv_kernel_size=c.short_conv_kernel_size,
        gate_rank=c.gate_rank, num_heads=c.num_heads,
        kv_lora_rank=c.kv_lora_rank, qk_nope_head_dim=c.qk_nope_head_dim,
        qk_rope_head_dim=c.qk_rope_head_dim, v_head_dim=c.v_head_dim,
        intermediate_size=c.intermediate_size,
        moe_intermediate_size=c.moe_intermediate_size,
        first_k_dense_replace=c.first_k_dense_replace,
        num_experts=c.num_experts, num_experts_per_tok=c.num_experts_per_tok,
        num_shared_experts=c.num_shared_experts, num_experts_held=held,
        expert_offset=offset, routed_scaling_factor=c.routed_scaling_factor,
        norm_eps=c.norm_eps, latent_norm_eps=c.latent_norm_eps,
        # 64 wide: the projections at the scale 0.02 gives them at 2304.
        initializer_range=0.15, vocab_pad_multiple=c.vocab_pad_multiple)


def _pair(held, offset, **extra):
    model = build_model("kimi-linear-tiny", {
        "dtype": jnp.float32, "remat": False, "attention_impl": "xla",
        "num_experts_held": held, "expert_offset": offset, **KINDS, **extra})
    rc = ref_config(model.config, held, offset)
    params = ref.init_params(SEED, rc, (2, 44))
    return model, rc, params, [params["embed"], *params["blocks"],
                               params["head"]]


# (held, offset, further model_args)
SHARES = [(4, 8, {"vocab_rows_held": 100, "chunk_size": 8})]
SHARE_IDS = ["experts_8_to_11_padded_vocabulary_ragged_chunks"]


@functools.lru_cache(maxsize=None)
def _both(case):
    """Program and reference on one share: (loss, logits, routing,
    gradients) of each, computed once, compared a layer a test."""
    held, offset, extra = SHARES[case]
    model, rc, params, plist = _pair(held, offset, **extra)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 44), 0,
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
        def loss(p):
            logits, own = ref.forward(p, tokens, rc)
            return ref.loss(p, tokens, rc)[0], (logits, own)
        return jax.value_and_grad(loss, has_aux=True)(params)

    (loss, (logits, routing)), grads = program(plist)
    (r_loss, (r_logits, own)), r_grads = reference(params)
    r_list = [r_grads["embed"], *r_grads["blocks"], r_grads["head"]]
    return (loss, logits, routing, grads), (r_loss, r_logits, own, r_list)


@pytest.mark.parametrize("case", range(len(SHARES)), ids=SHARE_IDS)
def test_program_matches_reference_on_logits_loss_and_routing(case):
    (loss, logits, routing, _), (r_loss, r_logits, own, _) = _both(case)
    rows = r_logits.shape[-1]               # the reference cuts the padding
    np.testing.assert_allclose(np.asarray(logits[..., :rows]),
                               np.asarray(r_logits), atol=5e-5)
    np.testing.assert_allclose(float(loss), float(r_loss), rtol=1e-6)
    assert len(routing) == len(own) == 2    # every layer but the first
    assert float(ref.mismatch_share(routing, own)) == 0.0


@pytest.mark.parametrize("layer", range(len(KIND_LAYERS)), ids=KIND_LAYERS)
@pytest.mark.parametrize("case", range(len(SHARES)), ids=SHARE_IDS)
def test_every_gradient_matches_the_references(case, layer):
    """Every leaf of every layer, the rule's own (A_log, dt_bias, the three
    convolutions' taps, the gated norm's scale, the two low-rank pairs) and
    the latent's norm included. Tolerance: 3e-4 of the leaf's largest
    entry; both sides are float32, and what differs is the ORDER of the
    sums (the program's chunks, levels and inverse against a walk of 44
    positions), which at these sizes reads 1e-6 to 1e-4 of a leaf's scale."""
    (_, _, _, grads), (_, _, _, r_grads) = _both(case)
    got, want = grads[layer], r_grads[layer]
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(want)):
        scale = max(float(jnp.max(jnp.abs(w))), 1e-3)
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), atol=3e-4 * scale,
            err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("shares", [4], ids=["four_shares_of_two"])
def test_shares_add_up_to_the_uncut_layer(shares):
    """The share test: the routed parts that all the chips of an
    expert-parallel group give, plus the shared expert (which each computes
    alike) counted ONCE, add up to the uncut reference's FF."""
    experts = 8
    args = {"num_experts": experts, "num_experts_per_tok": 3}
    _, rc, params, _ = _pair(experts, 0, **args)
    p = params["blocks"][1]["ff"]
    assert "router" in p
    h = jax.random.normal(jax.random.PRNGKey(3), (2, 32, rc.hidden_size))
    whole, own = ref._routed(p, h, rc, "highest", None)
    shared = ref._swiglu(p["shared"], h, "highest")
    held = experts // shares
    total = jnp.zeros_like(whole)
    for chip in range(shares):
        model = build_model("kimi-linear-tiny", {
            "dtype": jnp.float32, "remat": False, "attention_impl": "xla",
            "num_experts_held": held, "expert_offset": chip * held, **KINDS,
            **args})
        lo, hi = chip * held, (chip + 1) * held
        p_chip = dict(p, w1=p["w1"][lo:hi], w3=p["w3"][lo:hi],
                      w2=p["w2"][lo:hi])
        part, chosen = jax.jit(functools.partial(
            model.feed_forward, 1, return_routing=True))(p_chip, h)
        # Every chip routes over ALL the experts, alike.
        np.testing.assert_array_equal(np.sort(np.asarray(chosen), -1),
                                      np.sort(np.asarray(own), -1))
        total = total + (part - shared)
    np.testing.assert_allclose(np.asarray(total + shared), np.asarray(whole),
                               atol=3e-5)
    assert float(jnp.max(jnp.abs(shared))) > 1e-4       # it is not nothing


def test_layer_kinds_are_the_published_lists():
    """Layers numbered from 1 in the lists, blocks from 0 in the program:
    layer 4 and layer 27 latent, layer 1 KDA and dense, the rest routed."""
    model = build_model("kimi-linear-48b-a3b", {})
    c = model.config
    assert c.full_attn_layers == (4, 8, 12, 16, 20, 24, 27)
    assert len(c.kda_layers) == 20 and c.num_layers == 27
    kinds = [model.kind(b) for b in range(27)]
    assert kinds[3] == kinds[26] == kimi_linear.MLA
    assert kinds[0] == kinds[1] == kinds[25] == kimi_linear.KDA
    assert kinds.count(kimi_linear.MLA) == 7
    assert not model.is_routed(0) and all(model.is_routed(b)
                                          for b in range(1, 27))
    assert [model.layer_name(i) for i in (1, 2, 4, 27)] == [
        "kda_dense_0", "kda_routed_1", "mla_routed_3", "mla_routed_26"]
    assert model.frozen_param_names == ("expert_bias",)
    assert (c.linear_num_heads, c.linear_head_dim, c.num_heads, c.qk_head_dim,
            c.v_head_dim, c.kv_lora_rank, c.num_experts,
            c.num_experts_per_tok, c.routed_scaling_factor) == (
        32, 128, 32, 192, 128, 512, 256, 8, 2.446)


def test_profiler_times_each_kind_of_layer_once():
    model = build_model("kimi-linear-tiny", {})
    names = [model.layer_name(i) for i in range(model.num_pipeline_layers)]
    assert names == LAYERS
    # planning/profiler.py reuses a row by the name before its last "_".
    assert {n.rsplit("_", 1)[0] for n in names[1:-1]} == {
        "kda_dense", "kda_routed", "mla_routed"}
    assert model.routed_blocks == (1, 2, 3, 4)
    assert model.branches(0) == model.branches(3) == (routed.OP, routed.FF)


def test_parameter_counts_by_part_are_the_configuration_files():
    """The published model whole (49.1 B, the "48B") and the cell's cut,
    part by part, against `parameters` of the configuration file."""
    count = lambda model, i: base.param_count(jax.eval_shape(
        lambda r: model.init_layer(r, i), jax.random.PRNGKey(0)))
    table = CONFIG["parameters"]
    whole = build_model("kimi-linear-48b-a3b", {})
    layers = [count(whole, i) for i in range(whole.num_pipeline_layers)]
    routed_ff = 256 * table["expert"] + table["shared_expert"] + (
        table["router_and_bias"])
    assert layers[1] == table["kda_dense_layer"]
    assert layers[2] == table["kda_mixer"] + routed_ff + table["layer_norms"]
    assert layers[4] == table["latent_mixer"] + routed_ff + (
        table["layer_norms"])
    assert 49.0e9 < sum(layers) < 49.2e9
    cut = build_model(CONFIG["model_name"], dict(CONFIG["model_args"]))
    shapes = jax.eval_shape(lambda r: cut.init_layer(r, 2),
                            jax.random.PRNGKey(0))
    assert base.param_count(shapes[kimi_linear.KDA]) == table["kda_mixer"]
    assert base.param_count(shapes["ff"]) == table["routed_ff_8_held"]
    latent = jax.eval_shape(lambda r: cut.init_layer(r, 4),
                            jax.random.PRNGKey(0))
    assert base.param_count(latent["attn"]) == table["latent_mixer"]
    assert sum(count(cut, i) for i in range(cut.num_pipeline_layers)) == (
        table["total"])


@pytest.mark.parametrize("bad,match", [
    ({"kda_layers": (1, 2, 3), "full_attn_layers": (4,)}, "each of the 5"),
    ({"kda_layers": (1, 2, 3, 4, 5), "full_attn_layers": (4,)}, "once"),
    ({"first_k_dense_replace": 6}, "first_k_dense_replace"),
    ({"num_experts_held": 12, "expert_offset": 8}, "experts"),
    ({"no_such_field": 1}, "unknown model_args"),
])
def test_configuration_is_checked(bad, match):
    with pytest.raises(ValueError, match=match):
        build_model("kimi-linear-tiny", bad)


def test_latent_layer_is_deepseek_v3s_with_the_rotary_taken_out(monkeypatch):
    """Same weights, `rotate_half` the identity => the same output: the two
    families' latent mixers differ by the rotary alone. And WITH its rotary
    `deepseek_v3`'s differs from this one."""
    kimi = build_model("kimi-linear-tiny", {
        "dtype": jnp.float32, "attention_impl": "xla"})
    moon = build_model("moonlight-tiny", {
        "dtype": jnp.float32, "attention_impl": "xla"})
    ck, cm = kimi.config, moon.config
    assert (ck.hidden_size, ck.num_heads, ck.kv_lora_rank,
            ck.qk_nope_head_dim, ck.qk_rope_head_dim, ck.v_head_dim) == (
        cm.hidden_size, cm.num_heads, cm.kv_lora_rank, cm.qk_nope_head_dim,
        cm.qk_rope_head_dim, cm.v_head_dim)
    p = kimi.init_layer(jax.random.PRNGKey(0), 4)        # the latent block
    h = jax.random.normal(jax.random.PRNGKey(1), (2, 24, ck.hidden_size))
    mine = jax.jit(functools.partial(kimi.operator_out, 3))(p, h)
    with_rotary = jax.jit(functools.partial(moon.operator_out, 1))(p, h)
    assert float(jnp.max(jnp.abs(with_rotary - mine))) > 1e-5
    monkeypatch.setattr(deepseek_v3, "rotate_half", lambda x, theta: x)
    np.testing.assert_array_equal(
        np.asarray(jax.jit(functools.partial(moon.operator_out, 1))(p, h)),
        np.asarray(mine))


def test_q_and_k_reach_the_rule_at_unit_length_and_g_is_never_positive(
        monkeypatch):
    model = build_model("kimi-linear-tiny", {"dtype": jnp.float32})
    seen = {}

    def spy(q, k, v, g, beta, **kw):
        seen.update(q=q, k=k, g=g, beta=beta, kw=kw)
        return v

    monkeypatch.setattr(kimi_linear, "kimi_delta_rule", spy)
    p = model.init_layer(jax.random.PRNGKey(0), 1)
    u = jax.random.normal(jax.random.PRNGKey(1), (2, 20, 64))
    model.kda_operator(0, p[kimi_linear.KDA], u)
    d = model.config.linear_head_dim
    np.testing.assert_allclose(np.asarray(jnp.sum(seen["k"] ** 2, -1)), 1.0,
                               atol=1e-3)
    np.testing.assert_allclose(np.asarray(jnp.sum(seen["q"] ** 2, -1)),
                               1.0 / d, atol=1e-3)
    assert seen["g"].shape == (2, 20, 4, d) and seen["g"].dtype == jnp.float32
    assert float(jnp.max(seen["g"])) < 0.0
    assert seen["beta"].shape == (2, 20, 4)
    assert seen["kw"] == {"chunk": 16, "layer": "0"}


# --------------------------------------------------------------------- #
# a two-stage pipeline cut between kinds                                 #
# --------------------------------------------------------------------- #

MB, SEQ, NUM_MB = 1, 32, 2
CUT = 2       # [embed, kda_dense | mla_routed, kda_routed, head]


@pytest.fixture(scope="module")
def one_and_two(devices8):
    from oobleck_tpu.execution.pipeline import PipelineInstance
    from tests.execution.test_pipeline_mpmd import make_template

    model = build_model("kimi-linear-tiny", {"dtype": jnp.float32, **KINDS})
    batch = np.random.default_rng(0).integers(
        0, model.config.vocab_size, size=(NUM_MB, MB, SEQ), dtype=np.int32)

    def pipeline(splits):
        template = make_template(splits, [1] * len(splits))
        pipe = PipelineInstance(
            pipeline_id=0, template=template,
            ranks=list(range(template.num_chips)), model=model,
            devices=devices8, num_microbatches=NUM_MB,
            total_num_microbatches=NUM_MB, microbatch_size=MB, seq_len=SEQ)
        return pipe, float(pipe.train_step(batch))

    n = model.num_pipeline_layers
    return pipeline([(0, n)]), pipeline([(0, CUT), (CUT, n)])


def test_the_loss_of_two_stages_is_one_stages(one_and_two):
    (_, one), (_, two) = one_and_two
    assert np.isfinite(one) and two == pytest.approx(one, rel=1e-6)


@pytest.mark.parametrize("layer", range(len(KIND_LAYERS)), ids=KIND_LAYERS)
def test_every_gradient_of_two_stages_is_one_stages(one_and_two, layer):
    (one, _), (two, _) = one_and_two
    for a, b in zip(jax.tree.leaves(two.grads[layer]),
                    jax.tree.leaves(one.grads[layer])):
        scale = max(float(jnp.max(jnp.abs(b))), 1e-6)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-5 * scale, rtol=2e-4)
