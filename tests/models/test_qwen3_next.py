"""The Qwen3-Next family (`models/qwen3_next.py`) against its plain
reference (`benchmarks/reference/qwen3_next.py`, whose Gated DeltaNet
layers walk the recurrence one position after another where the program
runs it in chunks): logits, loss and every gradient, free-running and with
forced routing, whole and as a share; the 32 shares adding up to the uncut
layer with the gated shared expert counted once; the parameter count of
the benchmark's cut; the rotary that touches a quarter of a head; the
zero-centred norms; the engine on the generic stage path."""

import functools
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.reference import qwen3_next as ref
from oobleck_tpu.models import build_model, qwen3_next, routed

SEED = 5_000_000_023      # more than 32 signed bits hold


@pytest.fixture(autouse=True, scope="module")
def _leave_no_series_behind():
    """The routing probe's counters live in the PROCESS-GLOBAL registry and
    the routed readers take every series they find there: a later module on
    this worker must not read this one's layers."""
    yield
    from oobleck_tpu.utils import metrics

    metrics.registry().clear()


def ref_config(c, held, offset):
    return ref.RefConfig(
        vocab_size=c.data_vocab_size, hidden_size=c.hidden_size,
        num_layers=c.num_layers,
        full_attention_interval=c.full_attention_interval,
        linear_num_key_heads=c.linear_num_key_heads,
        linear_num_value_heads=c.linear_num_value_heads,
        linear_key_head_dim=c.linear_key_head_dim,
        linear_value_head_dim=c.linear_value_head_dim,
        linear_conv_kernel_dim=c.linear_conv_kernel_dim,
        num_heads=c.num_heads, num_kv_heads=c.num_kv_heads,
        head_dim=c.head_dim, partial_rotary_factor=c.partial_rotary_factor,
        rope_theta=c.rope_theta,
        moe_intermediate_size=c.moe_intermediate_size,
        shared_expert_intermediate_size=c.shared_expert_intermediate_size,
        num_experts=c.num_experts, num_experts_per_tok=c.num_experts_per_tok,
        num_experts_held=held, expert_offset=offset, norm_eps=c.norm_eps,
        vocab_pad_multiple=c.vocab_pad_multiple)


@functools.lru_cache(maxsize=None)
def _seeded(rc):
    """The seed's reference weights of one share, every norm's `w` and the
    gated norm's weight moved off their initial 0 and 1 (a test on zeros
    would not tell `1 + w` from `w`)."""
    params = ref.init_params(SEED, rc)
    keys = iter(jax.random.split(jax.random.PRNGKey(7), 64))
    return jax.tree.map(
        lambda x: x + 0.2 * jax.random.normal(next(keys), x.shape)
        if x.ndim == 1 else x, params)


def _pair(held, offset, **extra):
    model = build_model("qwen3-next-tiny", {
        "dtype": jnp.float32, "remat": False, "attention_impl": "xla",
        "num_experts_held": held, "expert_offset": offset, **extra})
    rc = ref_config(model.config, held, offset)
    params = _seeded(rc)
    return model, rc, params, [params["embed"], *params["blocks"],
                               params["head"]]


# (held, offset, further model_args, forced routing)
SHARES = [(16, 0, {}, False), (4, 8, {"chunk_size": 8}, False),
          (1, 15, {"vocab_rows_held": 100, "chunk_size": 64}, False),
          (4, 4, {}, True)]
SHARE_IDS = ["all_held", "experts_8_to_11_ragged_chunks",
             "one_expert_padded_vocabulary_one_chunk", "forced_routing"]


@functools.lru_cache(maxsize=None)
def _both(case):
    """Program and reference on one share: (loss, logits, routing,
    gradients) of each, computed once, compared a layer a test. Forced:
    both are handed choices neither would have made."""
    held, offset, extra, forced = SHARES[case]
    model, rc, params, plist = _pair(held, offset, **extra)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 44), 0,
                                rc.vocab_size)
    handed = None
    if forced:
        handed = [jax.random.randint(
            jax.random.PRNGKey(20 + b), (2, 44, rc.num_experts_per_tok), 0,
            rc.num_experts) for b in range(rc.num_layers)]

    @jax.jit
    def program(plist):
        def loss(pl):
            if not forced:
                logits, routing = model.forward(pl, tokens,
                                                return_routing=True)
            else:
                x, routing = model.embed(pl[0], tokens), handed
                for b in range(rc.num_layers):
                    x = model.apply_block(b, pl[b + 1], x,
                                          forced_experts=handed[b])
                logits = model.head(pl[-1], x)
            return model.loss_from_logits(logits, {"input_ids": tokens}), (
                logits, routing)
        return jax.value_and_grad(loss, has_aux=True)(plist)

    @jax.jit
    def reference(params):
        def loss(p):
            logits, own = ref.forward(p, tokens, rc, "highest", handed)
            return ref.loss(p, tokens, rc, "highest", handed)[0], (logits,
                                                                   own)
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
                               np.asarray(r_logits), atol=2e-5)
    np.testing.assert_allclose(float(loss), float(r_loss), rtol=1e-6)
    assert len(routing) == len(own) == 4    # every layer is routed
    if not SHARES[case][3]:
        assert float(ref.mismatch_share(routing, own)) == 0.0


LAYERS = ["embed", "gdn_0", "gdn_1", "gdn_2", "attn_3", "head"]


@pytest.mark.parametrize("layer", range(len(LAYERS)), ids=LAYERS)
@pytest.mark.parametrize("case", range(len(SHARES)), ids=SHARE_IDS)
def test_every_gradient_matches_the_references(case, layer):
    """Every leaf of every layer, the rule's own (A_log, dt_bias, conv
    taps, the gated norm), the head norms and the shared expert's gate
    included."""
    (_, _, _, grads), (_, _, _, r_grads) = _both(case)
    got, want = grads[layer], r_grads[layer]
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(want)):
        scale = max(float(jnp.max(jnp.abs(w))), 1e-3)
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), atol=3e-4 * scale,
            err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("shares", [32, 8, 2],
                         ids=["thirty_two_chips", "eight_chips", "two_chips"])
def test_shares_add_up_to_the_uncut_layer(shares):
    """The share test: the routed parts that all the chips of an
    expert-parallel group give, plus the GATED shared expert (which each
    computes alike) counted ONCE, add up to the uncut reference's FF."""
    experts = 32
    _, rc, params, _ = _pair(experts, 0, num_experts=experts,
                             num_experts_per_tok=5)
    p = params["blocks"][1]["ff"]
    h = jax.random.normal(jax.random.PRNGKey(3), (2, 32, rc.hidden_size))
    whole, own = ref._experts(p, h, rc, "highest", None)
    s = p["shared"]
    shared = jax.nn.sigmoid(h @ s["w_g"])[..., None] * ref._swiglu(
        s["w1"], s["w3"], s["w2"], h, "highest")
    held = experts // shares
    total = jnp.zeros_like(whole)
    for chip in range(shares):
        model = build_model("qwen3-next-tiny", {
            "dtype": jnp.float32, "remat": False, "attention_impl": "xla",
            "num_experts": experts, "num_experts_per_tok": 5,
            "num_experts_held": held, "expert_offset": chip * held})
        lo, hi = chip * held, (chip + 1) * held
        p_chip = dict(p, w1=p["w1"][lo:hi], w3=p["w3"][lo:hi],
                      w2=p["w2"][lo:hi])
        part, chosen = model.feed_forward(1, p_chip, h, return_routing=True)
        # Every chip routes over ALL the experts, alike.
        np.testing.assert_array_equal(np.sort(np.asarray(chosen), -1),
                                      np.sort(np.asarray(own), -1))
        # What a chip gives: its experts' part and the gated shared expert.
        total = total + (part - shared)
    np.testing.assert_allclose(np.asarray(total + shared), np.asarray(whole),
                               atol=3e-6)
    assert float(jnp.max(jnp.abs(shared))) > 1e-4       # it is not nothing


def test_published_shapes():
    model = build_model("qwen3-next-80b-a3b", {})
    c = model.config
    kinds = [model.kind(b) for b in range(c.num_layers)]
    assert c.num_layers == 48 and kinds[:8] == ["gdn"] * 3 + ["attn"] + [
        "gdn"] * 3 + ["attn"]
    assert (kinds.count("gdn"), kinds.count("attn")) == (36, 12)
    assert (c.hidden_size, c.key_dim, c.value_dim) == (2048, 2048, 4096)
    assert (c.linear_num_key_heads, c.linear_num_value_heads,
            c.linear_key_head_dim, c.linear_value_head_dim,
            c.linear_conv_kernel_dim, c.chunk_size) == (16, 32, 128, 128, 4,
                                                        64)
    assert (c.num_heads, c.num_kv_heads, c.head_dim, c.rotary_dim,
            c.rope_theta) == (16, 2, 256, 64, 1e7)
    assert (c.num_experts, c.num_experts_per_tok, c.moe_intermediate_size,
            c.shared_expert_intermediate_size, c.ffn_dim) == (
        512, 10, 512, 512, 5120)
    assert (c.routed_scaling_factor, c.norm_eps, c.vocab_size) == (
        1.0, 1e-6, 151936)
    assert model.router_score == "softmax" and not model.fused_supported
    assert all(model.is_routed(b) for b in range(c.num_layers))


# A layer of each kind at the published widths, its two norms included
# (the head's one too), the vocabulary padded to 19,072 rows: ISSUE 43's
# table, whose vocabulary counts the 18,992 rows held.
PARTS = {"gdn": 88_250_560, "attn": 81_795_584,
         "embed": 19_072 * 2048, "head": 19_072 * 2048 + 2048}


@functools.lru_cache(maxsize=None)
def _the_cut():
    model = build_model("qwen3-next-80b-a3b", {
        "num_layers": 4, "num_experts_held": 16, "vocab_rows_held": 18992})
    sizes = {}
    for i in range(model.num_pipeline_layers):
        shapes = jax.eval_shape(lambda r, i=i: model.init_layer(r, i),
                                jax.random.PRNGKey(0))
        sizes[model.layer_name(i)] = sum(
            int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    return model, sizes


@pytest.mark.parametrize("part", sorted(PARTS))
def test_the_cut_is_the_issue_s_parameter_count_by_part(part):
    _, sizes = _the_cut()
    of_kind = [v for n, v in sizes.items() if n.split("_")[0] == part]
    assert of_kind and set(of_kind) == {PARTS[part]}


def test_the_cut_is_the_issue_s_parameter_count():
    model, sizes = _the_cut()
    assert list(sizes) == ["embed", "gdn_0", "gdn_1", "gdn_2", "attn_3",
                           "head"]
    rc = ref_config(model.config, 16, 0)
    padding = 2 * (19_072 - 18_992) * 2048
    assert rc.num_params() == 424_340_544                # ISSUE 43: 424.3 M
    assert sum(sizes.values()) == rc.num_params() + padding
    gdn = rc.block_params(0)
    assert (gdn["w_qkvz"], gdn["w_ba"], gdn["conv"], gdn["scalars"],
            gdn["w_out"]) == (25_165_824, 131_072, 32_768, 192, 8_388_608)
    attn = rc.block_params(3)
    assert attn["attention"] + attn["head_norms"] == 27_263_488
    assert gdn["router"] + gdn["shared"] + gdn["ff"] == 54_528_000


def test_profiler_times_each_kind_of_layer_once():
    model = build_model("qwen3-next-tiny", {})
    names = [model.layer_name(i) for i in range(model.num_pipeline_layers)]
    assert names == LAYERS
    # planning/profiler.py reuses a row by the name before its last "_".
    assert {n.rsplit("_", 1)[0] for n in names[1:-1]} == {"gdn", "attn"}
    assert model.routed_blocks == (0, 1, 2, 3)
    assert model.branches(0) == model.branches(3) == (routed.OP, routed.FF)


@pytest.mark.parametrize("bad,match", [
    ({"linear_num_value_heads": 3}, "value heads"),
    ({"num_kv_heads": 3}, "key-value"),
    ({"head_dim": 8, "partial_rotary_factor": 0.4}, "rotary"),
    ({"num_experts_held": 4, "expert_offset": 14}, "experts"),
    ({"vocab_rows_held": 512}, "vocab_rows_held"),
    ({"no_such_field": 1}, "unknown"),
], ids=["value_heads", "kv_heads", "odd_rotary", "experts", "vocabulary",
        "unknown"])
def test_configuration_is_checked(bad, match):
    with pytest.raises(ValueError, match=match):
        build_model("qwen3-next-tiny", bad)


def test_rotary_touches_a_quarter_of_a_head():
    """64 of 256 columns at the published widths; here 4 of 16: position 0
    is untouched everywhere, the other positions in the rotary columns
    alone, and the program's rotation is the reference's."""
    model = build_model("qwen3-next-80b-a3b", {"num_layers": 4})
    assert model.config.rotary_dim == 64
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 2, 6, 256))
    got = np.asarray(model._partial_rotary(x))
    np.testing.assert_array_equal(got[..., 64:], np.asarray(x[..., 64:]))
    np.testing.assert_array_equal(got[..., 0, :], np.asarray(x[..., 0, :]))
    assert (got[..., 1:, :64] != np.asarray(x[..., 1:, :64])).mean() > 0.99
    np.testing.assert_allclose(
        got, np.asarray(ref._partial_rope(x, 64, 1e7)), atol=1e-6)
    # A rotation: it keeps every (position, head)'s length.
    np.testing.assert_allclose(np.linalg.norm(got, axis=-1),
                               np.linalg.norm(np.asarray(x), axis=-1),
                               rtol=1e-5)


def test_norms_are_zero_centred_and_start_at_the_identity_scale():
    """`N(x) = x / rms(x) * (1 + w)`, `w` initialised 0, in the block's two
    norms, the head's and the two head norms of attention; the gated norm's
    weight is plain and starts at 1."""
    model = build_model("qwen3-next-tiny", {"dtype": jnp.float32})
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 5, 64))
    w = jax.random.normal(jax.random.PRNGKey(2), (64,))
    unit = np.asarray(x) / np.sqrt(
        np.mean(np.square(np.asarray(x)), -1, keepdims=True) + 1e-6)
    np.testing.assert_allclose(np.asarray(model.norm(x, w)),
                               unit * (1 + np.asarray(w)), atol=1e-5,
                               rtol=1e-5)
    for index in (1, 4):
        p = model.init_layer(jax.random.PRNGKey(3), index)
        assert not np.asarray(p["ln_op"]["scale"]).any()
        assert not np.asarray(p["ln_ff"]["scale"]).any()
    assert not np.asarray(p["attn"]["q_norm"]).any()
    assert not np.asarray(p["attn"]["k_norm"]).any()
    head = model.init_layer(jax.random.PRNGKey(3), 5)
    assert not np.asarray(head["ln_f"]["scale"]).any()
    gdn = model.init_layer(jax.random.PRNGKey(3), 1)["gdn"]
    assert (np.asarray(gdn["norm"]) == 1.0).all()
    # The other families' norm is the plain one.
    other = build_model("lfm2-moe-tiny", {"dtype": jnp.float32})
    np.testing.assert_allclose(np.asarray(other.norm(x, w)),
                               unit * np.asarray(w), atol=1e-5, rtol=1e-5)


def test_seeded_scalars_are_what_the_configuration_assumes():
    """`A_log = log a`, a in (0, 16]; `dt_bias` 1; taps within 1 / sqrt(4):
    in the program's init and the reference's."""
    model, rc, _, _ = _pair(16, 0)
    c = model.config
    for p in (model.init_layer(jax.random.PRNGKey(4), 1)["gdn"],
              ref.init_params(SEED, rc)["blocks"][0]["gdn"]):
        a = np.exp(np.asarray(p["A_log"]))
        assert np.isfinite(np.asarray(p["A_log"])).all()
        assert a.min() > 0.0 and a.max() <= 16.0
        assert (np.asarray(p["dt_bias"]) == 1.0).all()
        assert np.abs(np.asarray(p["conv_taps"])).max() <= 0.5
        assert p["conv_taps"].shape == (4, 2 * c.key_dim + c.value_dim)
        assert p["w_qkvz"].shape == (64, 2 * c.key_dim + 2 * c.value_dim)
        assert p["w_ba"].shape == (64, 2 * c.linear_num_value_heads)


def test_q_and_k_reach_the_rule_at_unit_length():
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 3, 4, 16)) * 7.0
    got = np.asarray(qwen3_next.unit_length(x))
    np.testing.assert_allclose(np.linalg.norm(got, axis=-1), 1.0, atol=1e-5)
    np.testing.assert_allclose(got, np.asarray(ref._unit(x)), atol=1e-6)


def test_routing_probe_fills_the_counters_for_every_block():
    from oobleck_tpu.utils import metrics

    model, rc, _, plist = _pair(4, 8)
    tokens = jax.random.randint(jax.random.PRNGKey(5), (2, 32), 0,
                                rc.vocab_size)
    reg = metrics.registry()
    pairs = reg.counter("oobleck_moe_routed_pairs_total")
    softmax_calls = reg.counter("oobleck_moe_softmax_routed_calls_total")
    before = {b: pairs.value(layer=str(b)) for b in model.routed_blocks}
    calls_before = softmax_calls.value()
    routing = routed.routing_probe(model, plist, tokens)
    assert len(routing) == 4
    for block, chosen in zip(model.routed_blocks, routing):
        assert chosen.shape == (2, 32, 4)
        here = int(((chosen >= 8) & (chosen < 12)).sum())
        assert pairs.value(layer=str(block)) - before[block] == here
    # Four softmax-routed layers were built into the probe's program, and
    # the rule said how many chunks a sequence of each GDN block has.
    assert softmax_calls.value() - calls_before == 4
    chunks = reg.gauge("oobleck_gdn_chunks")
    assert {chunks.value(layer=str(b)) for b in (0, 1, 2)} == {2}


def test_engine_end_to_end_on_the_generic_stage_path(tmp_path):
    """The MPMD engine drives the family unchanged: the planner profiles
    two kinds of block, the generic stage path runs them in bfloat16 under
    remat; every trained leaf of the mixer moves."""
    from oobleck_tpu.config import (
        DistributedArguments,
        JobArguments,
        ModelArguments,
        OobleckArguments,
    )
    from oobleck_tpu.execution.engine import OobleckEngine
    from oobleck_tpu.utils import metrics

    old = os.environ.get("OOBLECK_TPU_CACHE")
    os.environ["OOBLECK_TPU_CACHE"] = str(tmp_path / "profiles")
    try:
        args = OobleckArguments(
            dist=DistributedArguments(node_ips=["10.0.0.0"]),
            job=JobArguments(microbatch_size=1, global_microbatch_size=2,
                             steps=4, learning_rate=1e-3, warmup_steps=1,
                             seq_len=40),
            model=ModelArguments(
                model_name="qwen3-next-tiny", dataset_path="synthetic",
                model_args={"num_experts_held": 4, "expert_offset": 4,
                            "vocab_rows_held": 100}),
        )
        named = metrics.registry().counter("oobleck_gdn_residuals_named_total")
        unnamed = named.value()
        engine = OobleckEngine(args, devices=jax.devices()[:1])
        assert engine.dataset.vocab_size == 100       # the rows held
        assert engine.seq_len == 40                   # no multiple of 16
        engine.initialize_distributed()
        engine.instantiate_pipelines(args.job.global_num_microbatch)
        pipe = engine.pipelines[0]
        before = jax.tree.map(np.asarray, dict(pipe.params))
        losses = [engine._train_step() for _ in range(2)]
        assert all(np.isfinite(l) for l in losses)
        # The stage programs differentiated the rule: its inverse went by
        # a name the layers' checkpoint keeps (ops/gdn.py).
        assert named.value() > unnamed
        moved = lambda a, b: np.abs(np.asarray(a) - b).max() > 0
        for name in ("w_qkvz", "w_ba", "conv_taps", "dt_bias", "A_log",
                     "norm", "w_out"):
            assert moved(pipe.params[1]["gdn"][name],
                         before[1]["gdn"][name]), name
        ff, ff0 = pipe.params[2]["ff"], before[2]["ff"]
        assert moved(ff["shared"]["w_g"], ff0["shared"]["w_g"])
        assert moved(ff["router"], ff0["router"])
        assert moved(ff["w2"], ff0["w2"])
        for name in ("wq", "wk", "q_norm", "k_norm", "wo"):
            assert moved(pipe.params[4]["attn"][name],
                         before[4]["attn"][name]), name
        assert moved(pipe.params[4]["ln_op"]["scale"],
                     before[4]["ln_op"]["scale"])
    finally:
        if old is None:
            os.environ.pop("OOBLECK_TPU_CACHE", None)
        else:
            os.environ["OOBLECK_TPU_CACHE"] = old
