"""The Nemotron-H family (`models/nemotron_h.py`) against its plain
reference (`benchmarks/reference/nemotron_h.py`, whose Mamba-2 layers walk
the recurrence one position after another where the program runs it in
chunks): logits, loss and every gradient, whole and as a share; the share
test; the parameter count of the benchmark's cut; one branch a block
through `models/routed.py`, and the two older families' blocks lowering to
the text they lowered to before blocks could have one branch."""

import functools
import hashlib
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.reference import nemotron_h as ref
from oobleck_tpu.models import build_model, nemotron_h, routed

SEED = 5_000_000_019      # more than 32 signed bits hold
BALANCE = (2, 48)


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
        pattern=c.hybrid_override_pattern,
        mamba_num_heads=c.mamba_num_heads, mamba_head_dim=c.mamba_head_dim,
        ssm_state_size=c.ssm_state_size, n_groups=c.n_groups,
        conv_kernel=c.conv_kernel, num_heads=c.num_heads,
        num_kv_heads=c.num_kv_heads, head_dim=c.head_dim,
        moe_intermediate_size=c.moe_intermediate_size,
        shared_intermediate_size=c.moe_shared_expert_intermediate_size,
        num_experts=c.num_experts, num_experts_per_tok=c.num_experts_per_tok,
        num_experts_held=held, expert_offset=offset,
        routed_scaling_factor=c.routed_scaling_factor, norm_eps=c.norm_eps,
        time_step_min=c.time_step_min, time_step_max=c.time_step_max,
        time_step_floor=c.time_step_floor)


@functools.lru_cache(maxsize=None)
def _seeded(rc):
    """The seed's reference weights of one share, made once a module."""
    return ref.init_params(SEED, rc, BALANCE)


def _pair(held, offset, **extra):
    model = build_model("nemotron-h-tiny", {
        "dtype": jnp.float32, "remat": False, "attention_impl": "xla",
        "num_experts_held": held, "expert_offset": offset, **extra})
    rc = ref_config(model.config, held, offset)
    params = _seeded(rc)
    return model, rc, params, [params["embed"], *params["blocks"],
                               params["head"]]


SHARES = [(8, 0, {}), (2, 4, {"chunk_size": 8}),
          (1, 7, {"vocab_rows_held": 128, "chunk_size": 64})]
SHARE_IDS = ["all_held", "experts_4_to_5_ragged_chunks",
             "one_expert_half_vocabulary_one_chunk"]


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
    np.testing.assert_allclose(np.asarray(logits), np.asarray(r_logits),
                               atol=2e-5)
    np.testing.assert_allclose(float(loss), float(r_loss), rtol=1e-6)
    assert len(routing) == 2
    assert float(ref.mismatch_share(routing, own)) == 0.0


LAYERS = ["embed", "mamba_0", "routed_1", "mamba_2", "attn_3", "routed_4",
          "head"]


@pytest.mark.parametrize("layer", range(len(LAYERS)), ids=LAYERS)
@pytest.mark.parametrize("case", range(len(SHARES)), ids=SHARE_IDS)
def test_every_gradient_matches_the_references(case, layer):
    """Every leaf of every layer, the scan's own (A_log, D, dt_bias, conv
    taps and bias, the gated norm) included; the selection bias's is
    zero on both sides."""
    (_, _, _, grads), (_, _, _, r_grads) = _both(case)
    got, want = grads[layer], r_grads[layer]
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(want)):
        scale = max(float(jnp.max(jnp.abs(w))), 1e-3)
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), atol=3e-4 * scale,
            err_msg=jax.tree_util.keystr(path))
    if "ff" in got:
        assert not np.asarray(got["ff"]["expert_bias"]).any()


@pytest.mark.parametrize("shares,experts", [(16, 16), (8, 8), (2, 8)],
                         ids=["sixteen_chips", "eight_chips", "two_chips"])
def test_shares_add_up_to_the_uncut_layer(shares, experts):
    """The share test: the routed parts that all the chips of an
    expert-parallel group give, plus the shared expert (which each
    computes alike) counted ONCE, add up to the uncut reference's `E`
    layer."""
    _, rc, params, _ = _pair(experts, 0, num_experts=experts)
    block = rc.routed_blocks[1]
    p = params["blocks"][block]["ff"]
    h = jax.random.normal(jax.random.PRNGKey(3), (2, 32, rc.hidden_size))
    whole, own = ref._experts(p, h, rc, "highest", None)
    shared = ref._relu2_ff(p["shared"]["w1"], p["shared"]["w2"], h, "highest")
    held = rc.num_experts // shares
    total = jnp.zeros_like(whole)
    for chip in range(shares):
        model = build_model("nemotron-h-tiny", {
            "dtype": jnp.float32, "remat": False, "attention_impl": "xla",
            "num_experts": experts, "num_experts_held": held,
            "expert_offset": chip * held})
        lo, hi = chip * held, (chip + 1) * held
        p_chip = dict(p, w1=p["w1"][lo:hi], w2=p["w2"][lo:hi])
        part, chosen = model.feed_forward(block, p_chip, h,
                                          return_routing=True)
        # Every chip routes over ALL the experts, alike.
        np.testing.assert_array_equal(np.sort(np.asarray(chosen), -1),
                                      np.sort(np.asarray(own), -1))
        # What a chip gives: its experts' part and the shared expert.
        total = total + (part - model.dense_ff(p["shared"], h))
    np.testing.assert_allclose(np.asarray(total + shared), np.asarray(whole),
                               atol=3e-6)
    assert float(jnp.max(jnp.abs(shared))) > 1e-4       # it is not nothing


def test_published_shapes():
    c = build_model("nemotron-3-nano-30b-a3b", {}).config
    assert len(c.hybrid_override_pattern) == c.num_layers == 52
    assert [c.hybrid_override_pattern.count(k) for k in "ME*"] == [23, 23, 6]
    assert (c.hidden_size, c.mamba_inner, c.conv_dim) == (2688, 4096, 6144)
    assert (c.mamba_num_heads, c.mamba_head_dim, c.ssm_state_size,
            c.n_groups, c.conv_kernel, c.chunk_size) == (64, 64, 128, 8, 4, 128)
    assert (c.num_heads, c.num_kv_heads, c.head_dim) == (32, 2, 128)
    assert (c.num_experts, c.num_experts_per_tok, c.moe_intermediate_size,
            c.moe_shared_expert_intermediate_size, c.ffn_dim) == (
        128, 6, 1856, 3712, 1856)
    assert (c.routed_scaling_factor, c.norm_eps, c.vocab_size) == (
        2.5, 1e-5, 131072)
    # The unit the benchmark runs is a verbatim substring that repeats.
    assert c.hybrid_override_pattern.startswith("MEMEM*E" * 5)


# A layer of each kind at the published widths, its norm included (the
# head's too): ISSUE 37's table.
PARTS = {"mamba": 38_744_896, "attn": 23_399_040, "routed": 100_125_440,
         "embed": 44_040_192, "head": 44_042_880}


@functools.lru_cache(maxsize=None)
def _the_cut():
    model = build_model("nemotron-3-nano-30b-a3b", {
        "num_layers": 7, "hybrid_override_pattern": "MEMEM*E",
        "num_experts_held": 8, "vocab_rows_held": 16384})
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
    assert list(sizes) == ["embed", "mamba_0", "routed_1", "mamba_2",
                           "routed_3", "mamba_4", "attn_5", "routed_6",
                           "head"]
    rc = ref_config(model.config, 8, 0)
    assert sum(sizes.values()) == rc.num_params() == 528_093_120


def test_profiler_times_each_kind_of_layer_once():
    model = build_model("nemotron-h-tiny", {})
    names = [model.layer_name(i) for i in range(model.num_pipeline_layers)]
    assert names == LAYERS
    # planning/profiler.py reuses a row by the name before its last "_":
    # five kinds in one list (embedding, M, E, *, head).
    assert {n.rsplit("_", 1)[0] for n in names[1:-1]} == {
        "mamba", "routed", "attn"}
    assert model.routed_blocks == (1, 4)


@pytest.mark.parametrize("kind,branches,keys", [
    ("M", (routed.OP,), {"ln_op", "mamba"}),
    ("*", (routed.OP,), {"ln_op", "attn"}),
    ("E", (routed.FF,), {"ln_ff", "ff"}),
], ids=["mamba", "attention", "experts"])
def test_a_block_is_one_branch_behind_one_norm(kind, branches, keys):
    model = build_model("nemotron-h-tiny", {})
    block = model.config.hybrid_override_pattern.index(kind)
    assert model.branches(block) == branches
    p = model.init_layer(jax.random.PRNGKey(0), block + 1)
    assert set(p) == keys
    # The two older families keep both, in their order.
    for name in ("lfm2-moe-tiny", "moonlight-tiny"):
        assert build_model(name, {}).branches(1) == (routed.OP, routed.FF)


@pytest.mark.parametrize("bad,match", [
    ({"hybrid_override_pattern": "MEM*"}, "hybrid_override_pattern"),
    ({"hybrid_override_pattern": "MEMXE"}, "hybrid_override_pattern"),
    ({"n_groups": 3}, "groups"),
    ({"num_kv_heads": 3}, "key-value"),
    ({"num_experts_held": 4, "expert_offset": 6}, "experts"),
    ({"vocab_rows_held": 512}, "vocab_rows_held"),
    ({"no_such_field": 1}, "unknown"),
], ids=["pattern_length", "pattern_letters", "groups", "kv_heads", "experts",
        "vocabulary", "unknown"])
def test_configuration_is_checked(bad, match):
    with pytest.raises(ValueError, match=match):
        build_model("nemotron-h-tiny", bad)


def test_the_gated_norm_gates_first_and_norms_each_group():
    y = jax.random.normal(jax.random.PRNGKey(2), (3, 5, 32))
    scale = jax.random.normal(jax.random.PRNGKey(3), (32,))
    got = nemotron_h.grouped_rms_norm(y, scale, 4, 1e-5)
    want = np.concatenate([
        np.asarray(g) / np.sqrt(np.mean(np.square(np.asarray(g)), -1,
                                        keepdims=True) + 1e-5)
        for g in np.split(np.asarray(y), 4, axis=-1)], -1) * np.asarray(scale)
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-5)


def test_seeded_scalars_are_what_the_configuration_assumes():
    """`A_log = log a`, a in [1, 16]; `D = 1`; softplus(dt_bias) in
    [max(time_step_min, floor), time_step_max]; taps and bias within
    1 / sqrt(conv_kernel), in the program's init and the reference's."""
    model, rc, params, _ = _pair(8, 0)
    c = model.config
    for p in (model.init_layer(jax.random.PRNGKey(4), 1)["mamba"],
              params["blocks"][0]["mamba"]):
        a = np.exp(np.asarray(p["A_log"]))
        assert a.min() >= 1.0 and a.max() <= 16.0
        assert (np.asarray(p["D"]) == 1.0).all()
        step = np.asarray(jax.nn.softplus(p["dt_bias"]))
        assert step.min() >= c.time_step_min * 0.999
        assert step.max() <= c.time_step_max * 1.001
        bound = c.conv_kernel ** -0.5
        assert np.abs(np.asarray(p["conv_taps"])).max() <= bound
        assert np.abs(np.asarray(p["conv_bias"])).max() <= bound
        assert p["conv_taps"].shape == (c.conv_kernel, c.conv_dim)


def test_routing_probe_fills_the_counters_for_the_expert_blocks():
    from oobleck_tpu.utils import metrics

    model, rc, params, plist = _pair(2, 4)
    tokens = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0,
                                           rc.vocab_size))
    reg = metrics.registry()
    pairs = reg.counter("oobleck_moe_routed_pairs_total")
    held_rows = reg.gauge("oobleck_moe_held_rows")
    before = {b: pairs.value(layer=str(b)) for b in model.routed_blocks}
    routing = routed.routing_probe(model, plist, tokens)
    assert len(routing) == len(model.routed_blocks) == 2
    for block, chosen in zip(model.routed_blocks, routing):
        assert chosen.shape == (2, 32, 3)
        here = int(((chosen >= 4) & (chosen < 6)).sum())
        assert pairs.value(layer=str(block)) - before[block] == here
        assert held_rows.value(layer=str(block)) == here
    # The scan said how many chunks a sequence of each Mamba-2 block has.
    chunks = reg.gauge("oobleck_ssd_chunks")
    assert {chunks.value(layer=str(b)) for b in (0, 2)} == {2}


# (model, pipeline layer) -> sha256[:16] and length of the lowered text of
# one block's value-and-gradient at the parent of the PR that let a block
# have one branch (a609f75): a dense and a routed block of each family.
# The three blocks with attention (lfm2's layer 3, both of moonlight's)
# rotate q and k: theirs are as the PR that made the rotary embedding one
# pass left them (PR 46); lfm2's two conv blocks kept theirs through it.
# The three ROUTED blocks' are as PR 51 left them: their tile loops start
# from `lax.empty` (`ops/moe._unwritten`), off a TPU the same zero broadcast,
# and the text differs in the numbers of its private functions alone (the
# lengths stood); the two dense blocks kept theirs.
LOWERED_BEFORE = {
    ("lfm2-moe-tiny", 1): ("074f95528c7b5124", 25652),
    ("lfm2-moe-tiny", 2): ("4c4e9633889887fa", 100374),
    ("lfm2-moe-tiny", 3): ("3e8e4a7d1c7a8450", 126432),
    ("moonlight-tiny", 1): ("8a42a0d2bf47f840", 48851),
    ("moonlight-tiny", 2): ("0b036bbc7bcfdb73", 128404),
}


@pytest.mark.parametrize("name,index", sorted(LOWERED_BEFORE),
                         ids=lambda v: str(v))
def test_two_branch_blocks_lower_to_the_text_they_lowered_to_before(name,
                                                                    index):
    model = build_model(name, {})
    params = jax.eval_shape(
        lambda: model.init_layer(jax.random.PRNGKey(0), index))
    x = jax.ShapeDtypeStruct((2, 32, model.config.hidden_size),
                             model.config.dtype)

    def f(p, x):                            # the module is named after it
        return jnp.sum(
            model.apply_layer(index, p, x, None).astype(jnp.float32))

    text = jax.jit(jax.grad(f, argnums=(0, 1))).lower(params, x).as_text()
    assert (hashlib.sha256(text.encode()).hexdigest()[:16],
            len(text)) == LOWERED_BEFORE[name, index]


def test_engine_end_to_end_on_the_generic_stage_path(tmp_path):
    """The MPMD engine drives the family unchanged: the planner profiles
    three kinds of block, the generic stage path runs them; every trained
    leaf of the scan moves, the selection bias stays as it was."""
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
                model_name="nemotron-h-tiny", dataset_path="synthetic",
                model_args={"num_experts_held": 2, "expert_offset": 4,
                            "vocab_rows_held": 128}),
        )
        engine = OobleckEngine(args, devices=jax.devices()[:1])
        assert engine.dataset.vocab_size == 128       # the rows held
        assert engine.seq_len == 40                   # no multiple of 16
        engine.initialize_distributed()
        engine.instantiate_pipelines(args.job.global_num_microbatch)
        pipe = engine.pipelines[0]
        before = jax.tree.map(np.asarray, dict(pipe.params))
        losses = [engine._train_step() for _ in range(2)]
        assert all(np.isfinite(l) for l in losses)
        moved = lambda a, b: np.abs(np.asarray(a) - b).max() > 0
        for name in ("w_in", "conv_taps", "conv_bias", "dt_bias", "A_log",
                     "D", "norm", "w_out"):
            assert moved(pipe.params[1]["mamba"][name],
                         before[1]["mamba"][name]), name
        ff, ff0 = pipe.params[2]["ff"], before[2]["ff"]
        assert moved(ff["shared"]["w1"], ff0["shared"]["w1"])
        assert moved(ff["router"], ff0["router"])
        assert moved(ff["w2"], ff0["w2"])
        assert moved(pipe.params[4]["attn"]["wk"], before[4]["attn"]["wk"])
        assert not moved(ff["expert_bias"], ff0["expert_bias"])
    finally:
        if old is None:
            os.environ.pop("OOBLECK_TPU_CACHE", None)
        else:
            os.environ["OOBLECK_TPU_CACHE"] = old
