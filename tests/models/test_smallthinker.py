"""The SmallThinker family (`models/smallthinker.py`) against its plain
reference (`benchmarks/reference/smallthinker.py`, whose attention builds
the band mask from positions against ALL keys and whose router is the
published top-6-then-softmax): logits, loss and every gradient,
free-running and with forced routing, whole and as a share, at a window
SHORTER than the sequence; the router reading the block's input and not the
experts'; the eight shares adding up to the uncut layer; the parameter
count of the benchmark's cut; the layouts read from the configuration; the
engine on the generic stage path."""

import functools
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.reference import smallthinker as ref
from oobleck_tpu.models import build_model, routed, smallthinker
from oobleck_tpu.ops import moe

SEED = 5_000_000_029      # more than 32 signed bits hold
SEQ = 44                  # the tiny preset's window is 24


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
        num_layers=c.num_layers, num_heads=c.num_heads,
        num_kv_heads=c.num_kv_heads, head_dim=c.head_dim,
        sliding_window_size=c.sliding_window_size,
        sliding_window_layout=c.windowed, rope_layout=c.rotary,
        rope_theta=c.rope_theta,
        moe_intermediate_size=c.moe_intermediate_size,
        num_experts=c.num_experts, num_experts_per_tok=c.num_experts_per_tok,
        num_experts_held=held, expert_offset=offset, norm_eps=c.norm_eps,
        vocab_pad_multiple=c.vocab_pad_multiple)


@functools.lru_cache(maxsize=None)
def _seeded(rc):
    """The seed's reference weights of one share, every norm's weight moved
    off its initial 1."""
    params = ref.init_params(SEED, rc)
    keys = iter(jax.random.split(jax.random.PRNGKey(7), 64))
    return jax.tree.map(
        lambda x: x + 0.2 * jax.random.normal(next(keys), x.shape)
        if x.ndim == 1 else x, params)


def _pair(held, offset, **extra):
    model = build_model("smallthinker-tiny", {
        "dtype": jnp.float32, "remat": False, "attention_impl": "xla",
        "num_experts_held": held, "expert_offset": offset, **extra})
    rc = ref_config(model.config, held, offset)
    params = _seeded(rc)
    return model, rc, params, [params["embed"], *params["blocks"],
                               params["head"]]


# (held, offset, further model_args, forced routing)
SHARES = [(4, 8, {"sliding_window_size": 7}, False),
          (1, 15, {"vocab_rows_held": 100, "sliding_window_size": 64}, False),
          (4, 4, {}, True),
          (16, 0, {"sliding_window_layout": (1, 0, 1, 0),
                  "rope_layout": (1, 1, 0, 0)}, False)]
SHARE_IDS = ["experts_8_to_11_window_7",
             "one_expert_padded_vocabulary_window_past_the_sequence",
             "forced_routing", "layouts_of_its_own"]


@functools.lru_cache(maxsize=None)
def _both(case):
    """Program and reference on one share: (loss, logits, routing,
    gradients) of each, computed once, compared a layer a test. Forced:
    both are handed choices neither would have made."""
    held, offset, extra, forced = SHARES[case]
    model, rc, params, plist = _pair(held, offset, **extra)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, SEQ), 0,
                                rc.vocab_size)
    handed = None
    if forced:
        # Six DISTINCT experts a token, as a selection is.
        handed = [jnp.argsort(jax.random.uniform(
            jax.random.PRNGKey(20 + b), (2, SEQ, rc.num_experts)), -1)[
                ..., :rc.num_experts_per_tok]
            for b in range(rc.num_layers)]

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


LAYERS = ["embed", "full_attn_0", "swa_attn_1", "swa_attn_2", "swa_attn_3",
          "head"]


@pytest.mark.parametrize("layer", range(len(LAYERS)), ids=LAYERS)
@pytest.mark.parametrize("case", range(len(SHARES)), ids=SHARE_IDS)
def test_every_gradient_matches_the_references(case, layer):
    (_, _, _, grads), (_, _, _, r_grads) = _both(case)
    got, want = grads[layer], r_grads[layer]
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(want)):
        scale = max(float(jnp.max(jnp.abs(w))), 1e-3)
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), atol=3e-4 * scale,
            err_msg=jax.tree_util.keystr(path))


def test_the_window_changes_the_result_at_this_length():
    """The comparison above would pass a program that ignored the window
    if the window did nothing at the test's length: it does something."""
    _, rc, params, _ = _pair(16, 0)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, SEQ), 0,
                                rc.vocab_size)
    assert rc.sliding_window_size < SEQ
    with_window = ref.forward(params, tokens, rc)[0]
    without = ref.forward(params, tokens, rc, ignore_window=True)[0]
    w = rc.sliding_window_size
    # Positions inside the first window see the same keys either way.
    np.testing.assert_allclose(np.asarray(with_window[:, :w]),
                               np.asarray(without[:, :w]), atol=1e-6)
    assert float(jnp.max(jnp.abs(with_window[:, w:] - without[:, w:]))) > 1e-3


def test_the_router_reads_the_attention_s_input():
    """Perturbing the attention's weights changes what the experts read
    and leaves every choice of the block as it was; perturbing the block's
    first norm, which the router reads through, moves them."""
    model, rc, _, plist = _pair(16, 0)
    assert model.router_reads == routed.OP
    x = jax.random.normal(jax.random.PRNGKey(2), (2, SEQ, rc.hidden_size))
    p = plist[2]
    noise = lambda t, k: jax.tree.map(
        lambda a: a + 0.5 * jax.random.normal(jax.random.PRNGKey(k), a.shape),
        t)
    out, chosen = model.apply_block(1, p, x, return_routing=True)
    out_a, chosen_a = model.apply_block(
        1, dict(p, attn=noise(p["attn"], 3)), x, return_routing=True)
    np.testing.assert_array_equal(np.asarray(chosen), np.asarray(chosen_a))
    assert float(jnp.max(jnp.abs(out - out_a))) > 1e-3
    _, chosen_n = model.apply_block(
        1, dict(p, ln_op=noise(p["ln_op"], 4)), x, return_routing=True)
    assert (np.sort(np.asarray(chosen), -1)
            != np.sort(np.asarray(chosen_n), -1)).any()
    # A family whose router reads the experts' input: the attention moves
    # its choices.
    other = build_model("qwen3-next-tiny", {
        "dtype": jnp.float32, "remat": False, "attention_impl": "xla"})
    assert other.router_reads == routed.FF
    q = other.init_layer(jax.random.PRNGKey(5), 4)
    _, c0 = other.apply_block(3, q, x, return_routing=True)
    _, c1 = other.apply_block(3, dict(q, attn=noise(q["attn"], 6)), x,
                              return_routing=True)
    assert (np.sort(np.asarray(c0), -1) != np.sort(np.asarray(c1), -1)).any()


@pytest.mark.parametrize("shares", [8, 4, 2],
                         ids=["eight_chips", "four_chips", "two_chips"])
def test_shares_add_up_to_the_uncut_layer(shares):
    """The share test: the parts that all the chips of an expert-parallel
    group give add up to the uncut reference's layer (no shared expert:
    nothing is counted twice), every chip routing over ALL the experts on
    the rows the ROUTER reads."""
    experts = 32
    _, rc, params, _ = _pair(experts, 0, num_experts=experts,
                             num_experts_per_tok=5)
    p = params["blocks"][1]["ff"]
    r = jax.random.normal(jax.random.PRNGKey(3), (2, 32, rc.hidden_size))
    y = jax.random.normal(jax.random.PRNGKey(4), (2, 32, rc.hidden_size))
    whole, own = ref._experts(p, r, y, rc, "highest", None)
    held = experts // shares
    total = jnp.zeros_like(whole)
    for chip in range(shares):
        model = build_model("smallthinker-tiny", {
            "dtype": jnp.float32, "remat": False, "attention_impl": "xla",
            "num_experts": experts, "num_experts_per_tok": 5,
            "num_experts_held": held, "expert_offset": chip * held})
        lo, hi = chip * held, (chip + 1) * held
        p_chip = dict(p, w1=p["w1"][lo:hi], w3=p["w3"][lo:hi],
                      w2=p["w2"][lo:hi])
        part, chosen = model.feed_forward(1, p_chip, y, router_in=r,
                                          return_routing=True)
        np.testing.assert_array_equal(np.sort(np.asarray(chosen), -1),
                                      np.sort(np.asarray(own), -1))
        total = total + part
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               atol=3e-6)
    assert float(jnp.max(jnp.abs(whole))) > 1e-4        # it is not nothing


@pytest.mark.parametrize("experts,k", [(64, 6), (16, 4), (8, 8)])
def test_softmax_over_all_renormalised_is_topk_then_softmax(experts, k):
    """`ops/moe.route(score="softmax", norm_topk_prob=True)` chooses the
    published router's six and gives its weights: the softmax is monotone,
    and a softmax over all renormalised over the chosen is the softmax
    over the chosen logits."""
    x = jax.random.normal(jax.random.PRNGKey(8), (96, 48))
    w = jax.random.normal(jax.random.PRNGKey(9), (48, experts))
    chosen, weights = moe.route(x, w, None, top_k=k, score="softmax")
    logits = jnp.dot(x, w, precision=jax.lax.Precision.HIGHEST)
    top, own = jax.lax.top_k(logits, k)
    np.testing.assert_array_equal(np.asarray(chosen), np.asarray(own))
    np.testing.assert_allclose(np.asarray(weights),
                               np.asarray(jax.nn.softmax(top, -1)),
                               rtol=2e-5, atol=1e-7)


def test_published_shapes():
    model = build_model("smallthinker-21b-a3b", {})
    c = model.config
    kinds = [model.kind(b) for b in range(c.num_layers)]
    assert c.num_layers == 52 and kinds[:8] == [
        "full_attn", "swa_attn", "swa_attn", "swa_attn"] * 2
    assert (kinds.count("full_attn"), kinds.count("swa_attn")) == (13, 39)
    assert c.windowed == c.rotary == smallthinker.published_layout(52)
    assert (c.hidden_size, c.num_heads, c.num_kv_heads, c.head_dim) == (
        2560, 28, 4, 128)
    assert (c.sliding_window_size, c.rope_theta,
            c.max_position_embeddings) == (4096, 1.5e6, 16384)
    assert (c.num_experts, c.num_experts_per_tok,
            c.moe_intermediate_size) == (64, 6, 768)
    assert (c.routed_scaling_factor, c.norm_eps, c.vocab_size,
            c.norm_topk_prob) == (1.0, 1e-6, 151936, True)
    assert (c.initializer_range, smallthinker.EMBEDDING_STD) == (0.02, 1.0)
    assert (model.router_score, model.router_reads,
            model.expert_activation) == ("softmax", routed.OP, "reglu")
    assert not model.fused_supported
    assert all(model.is_routed(b) for b in range(c.num_layers))


# A layer at the published widths, its two norms included (the head's one
# too), the vocabulary padded to 19,072 rows: ISSUE 45's table, whose
# vocabulary counts the 18,992 rows held.
PARTS = {"full": 68_326_400, "swa": 68_326_400,
         "embed": 19_072 * 2560, "head": 19_072 * 2560 + 2560}


@functools.lru_cache(maxsize=None)
def _the_cut():
    model = build_model("smallthinker-21b-a3b", {
        "num_layers": 4, "sliding_window_layout": [0, 1, 1, 1],
        "rope_layout": [0, 1, 1, 1], "num_experts_held": 8,
        "vocab_rows_held": 18992})
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
    assert list(sizes) == ["embed", "full_attn_0", "swa_attn_1",
                           "swa_attn_2", "swa_attn_3", "head"]
    rc = ref_config(model.config, 8, 0)
    padding = 2 * (19_072 - 18_992) * 2560
    assert rc.num_params() == 370_547_200                # ISSUE 45: 370.5 M
    assert sum(sizes.values()) == rc.num_params() + padding
    parts = rc.block_params()
    assert (parts["attention"], parts["router"], parts["experts"],
            parts["norms"]) == (20_971_520, 163_840, 47_185_920, 5_120)


def test_the_embedding_alone_is_drawn_at_unit_variance():
    """In the program's init and the reference's: every matrix at 0.02 (the
    residual outputs smaller), the embedding at 1, so that what a token
    brings outweighs what the attention layers average over its sequence
    and the router, which reads the stream, tells tokens apart."""
    model, rc, _, _ = _pair(16, 0)
    mine = model.init_layer(jax.random.PRNGKey(4), 0)["wte"]
    theirs = ref.init_params(SEED, rc)["embed"]["wte"]
    for table in (mine, theirs):
        assert table.shape == (256, 64)
        assert 0.95 < float(jnp.std(table)) < 1.05
    block = model.init_layer(jax.random.PRNGKey(4), 1)
    assert 0.018 < float(jnp.std(block["attn"]["wq"])) < 0.022
    assert 0.018 < float(jnp.std(block["ff"]["router"])) < 0.022
    head = model.init_layer(jax.random.PRNGKey(4), 5)
    assert 0.018 < float(jnp.std(head["w"])) < 0.022
    # The other routed families keep theirs at `initializer_range`.
    other = build_model("qwen3-next-tiny", {})
    assert float(jnp.std(other.init_layer(jax.random.PRNGKey(4), 0)["wte"])
                 ) < 0.03


def test_profiler_times_each_kind_of_layer_once():
    model = build_model("smallthinker-tiny", {})
    names = [model.layer_name(i) for i in range(model.num_pipeline_layers)]
    assert names == LAYERS
    # planning/profiler.py reuses a row by the name before its last "_".
    assert {n.rsplit("_", 1)[0] for n in names[1:-1]} == {"full_attn",
                                                          "swa_attn"}
    assert model.routed_blocks == (0, 1, 2, 3)
    assert model.branches(0) == model.branches(3) == (routed.OP, routed.FF)


@pytest.mark.parametrize("bad,match", [
    ({"num_kv_heads": 3}, "key-value"),
    ({"sliding_window_size": 0}, "window"),
    ({"sliding_window_layout": (0, 1, 1)}, "sliding_window_layout"),
    ({"rope_layout": (0, 1, 2, 1)}, "rope_layout"),
    ({"num_experts_held": 4, "expert_offset": 14}, "experts"),
    ({"vocab_rows_held": 512}, "vocab_rows_held"),
    ({"no_such_field": 1}, "unknown"),
], ids=["kv_heads", "window", "short_layout", "layout_values", "experts",
        "vocabulary", "unknown"])
def test_configuration_is_checked(bad, match):
    with pytest.raises(ValueError, match=match):
        build_model("smallthinker-tiny", bad)


def test_layouts_come_from_the_configuration_not_the_index():
    """A layer is windowed where `sliding_window_layout` says and rotary
    where `rope_layout` says, each by itself: the window reaches the
    attention call, and a layer without rotary knows no position."""
    model, _, _, plist = _pair(
        16, 0, sliding_window_layout=(1, 0, 0, 1), rope_layout=(0, 0, 1, 1))
    assert [model.kind(b) for b in range(4)] == [
        "swa_attn", "full_attn", "full_attn", "swa_attn"]
    seen = []
    real = smallthinker.causal_attention

    def spy(q, k, v, **kw):
        seen.append(kw.get("window"))
        return real(q, k, v, **kw)

    x = jax.random.normal(jax.random.PRNGKey(2), (1, SEQ, 64))
    try:
        smallthinker.causal_attention = spy
        outs = [model.operator_out(b, plist[b + 1], x) for b in range(4)]
    finally:
        smallthinker.causal_attention = real
    assert seen == [24, None, None, 24]
    # Without rotary and without a window (block 1) attention knows no
    # position: reversing nothing but the ORDER of earlier tokens leaves
    # the last position's output as it was; with rotary (block 2) it moves.
    flipped = jnp.concatenate([x[:, :-1][:, ::-1], x[:, -1:]], axis=1)
    for block, same in ((1, True), (2, False)):
        out = model.operator_out(block, plist[block + 1], flipped)
        close = np.allclose(np.asarray(out[:, -1]),
                            np.asarray(outs[block][:, -1]), atol=1e-5)
        assert close == same, block


def test_routing_probe_fills_the_counters_for_every_block():
    from oobleck_tpu.utils import metrics

    model, rc, _, plist = _pair(4, 8)
    tokens = jax.random.randint(jax.random.PRNGKey(5), (2, 32), 0,
                                rc.vocab_size)
    reg = metrics.registry()
    pairs = reg.counter("oobleck_moe_routed_pairs_total")
    names = ("oobleck_moe_softmax_routed_calls_total",
             "oobleck_moe_reglu_calls_total",
             "oobleck_moe_early_router_calls_total")
    before = {b: pairs.value(layer=str(b)) for b in model.routed_blocks}
    calls_before = [reg.counter(n).value() for n in names]
    routing = routed.routing_probe(model, plist, tokens)
    assert len(routing) == 4
    for block, chosen in zip(model.routed_blocks, routing):
        assert chosen.shape == (2, 32, 4)
        here = int(((chosen >= 8) & (chosen < 12)).sum())
        assert pairs.value(layer=str(block)) - before[block] == here
    # Four softmax-routed ReGLU layers whose router reads rows of its own
    # were built into the probe's program.
    assert [reg.counter(n).value() - b
            for n, b in zip(names, calls_before)] == [4, 4, 4]


def test_engine_end_to_end_on_the_generic_stage_path(tmp_path):
    """The MPMD engine drives the family unchanged: the planner profiles
    two kinds of block, the generic stage path runs them in bfloat16 under
    remat at a sequence LONGER than the window; every trained leaf moves,
    the router through the attention's input."""
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
                             seq_len=40),
            model=ModelArguments(
                model_name="smallthinker-tiny", dataset_path="synthetic",
                model_args={"num_experts_held": 4, "expert_offset": 4,
                            "vocab_rows_held": 100}),
        )
        engine = OobleckEngine(args, devices=jax.devices()[:1])
        assert engine.dataset.vocab_size == 100       # the rows held
        assert engine.seq_len == 40 > engine.model.config.sliding_window_size
        engine.initialize_distributed()
        engine.instantiate_pipelines(args.job.global_num_microbatch)
        pipe = engine.pipelines[0]
        before = jax.tree.map(np.asarray, dict(pipe.params))
        losses = [engine._train_step() for _ in range(2)]
        assert all(np.isfinite(l) for l in losses)
        moved = lambda a, b: np.abs(np.asarray(a) - b).max() > 0
        for layer in (1, 2):
            for name in ("wq", "wk", "wv", "wo"):
                assert moved(pipe.params[layer]["attn"][name],
                             before[layer]["attn"][name]), (layer, name)
            for name in ("router", "w1", "w3", "w2"):
                assert moved(pipe.params[layer]["ff"][name],
                             before[layer]["ff"][name]), (layer, name)
            for name in ("ln_op", "ln_ff"):
                assert moved(pipe.params[layer][name]["scale"],
                             before[layer][name]["scale"]), (layer, name)
    finally:
        if old is None:
            os.environ.pop("OOBLECK_TPU_CACHE", None)
        else:
            os.environ["OOBLECK_TPU_CACHE"] = old
