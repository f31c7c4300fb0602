"""The LFM2-MoE cell's own pieces: `flops_moe.py` by hand, its reader on
hand-made data, the reference's forced-routing and mismatch paths, and the
runner's control flow rehearsed on the CPU at `lfm2-moe-tiny` sizes (never
a number)."""

import json
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks import flops, flops_moe
from benchmarks.reference import lfm2 as ref

ROOT = Path(__file__).resolve().parents[2]
CONFIG = json.loads(
    (ROOT / "benchmarks" / "configs" / "lfm2-24b-a2b.json").read_text())

TINY = {
    "name": "tiny", "model_name": "lfm2-moe-tiny",
    "model_args": {"num_experts_held": 4, "expert_offset": 0,
                   "vocab_rows_held": 128},
    "vocab_size": 256, "vocab_rows_held": 128, "hidden_size": 64,
    "num_layers": 4, "num_heads": 4, "num_kv_heads": 2,
    "intermediate_size": 128, "moe_intermediate_size": 32, "num_experts": 8,
    "num_experts_per_tok": 2, "num_dense_layers": 1,
    "layer_types": ["conv", "conv", "full_attention", "conv"],
    "num_experts_held": 4,
    "execution": {"precision": "bfloat16", "remat": True},
}


# --------------------------------------------------------------------- #
# the configuration                                                      #
# --------------------------------------------------------------------- #

def test_configuration_keeps_every_published_number():
    """Every key of the catalog's config is in the file under its own
    name, and differs only where `reduced` says so."""
    published = {
        "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
        "intermediate_size": 11776, "max_position_embeddings": 128000,
        "model_type": "lfm2_moe", "moe_intermediate_size": 1536,
        "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32,
        "num_dense_layers": 2, "num_experts": 64, "num_experts_per_tok": 4,
        "num_hidden_layers": 40, "num_key_value_heads": 8,
        "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
        "routed_scaling_factor": 1, "use_expert_bias": True,
        "vocab_size": 65536}
    for key, value in published.items():
        if key in CONFIG["reduced"]:
            assert CONFIG["source_values"][key] == value, key
        else:
            assert CONFIG[key] == value, key
    assert CONFIG["layer_types"] == ["conv", "full_attention", "conv", "conv",
                                     "conv"]
    full = CONFIG["source_values"]["layer_types"]
    assert len(full) == 40 and full[1:6] == CONFIG["layer_types"]
    assert full.count("conv") == 30


def test_reference_and_program_agree_on_the_configuration():
    from oobleck_tpu.models import build_model

    model = build_model(CONFIG["model_name"], dict(CONFIG["model_args"]))
    c = model.config
    rc = ref.RefConfig.from_config(CONFIG)
    shapes = [jax.eval_shape(lambda r, i=i: model.init_layer(r, i),
                             jax.random.PRNGKey(0))
              for i in range(model.num_pipeline_layers)]
    assert rc.num_params() == sum(
        int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert 485e6 < rc.num_params() < 487e6            # ISSUE 29: 486.1 M
    assert tuple(c.operators) == rc.layer_types
    assert (c.data_vocab_size, c.experts_held, c.expert_offset) == (
        rc.vocab_size, rc.num_experts_held, rc.expert_offset) == (8192, 8, 0)
    for key in ("hidden_size", "num_kv_heads", "moe_intermediate_size",
                "num_experts", "num_experts_per_tok", "num_dense_layers",
                "norm_topk_prob", "use_expert_bias", "conv_L_cache",
                "norm_eps", "routed_scaling_factor"):
        assert getattr(c, key) == getattr(rc, key) == CONFIG.get(
            key, getattr(rc, key)), key
    assert c.rope_theta == rc.rope_theta == \
        CONFIG["rope_parameters"]["rope_theta"]


# --------------------------------------------------------------------- #
# flops_moe and its reader                                               #
# --------------------------------------------------------------------- #

def test_grouped_product_counts_by_hand():
    # 10 rows, widths 4 x 6, 2 experts held, 2-byte operands.
    ops, nbytes = flops_moe.grouped_product(10, 4, 6, 2)
    assert ops == 2 * 10 * 4 * 6
    assert nbytes == (10 * 4 + 10 * 6 + 2 * 4 * 6) * 2
    assert flops_moe.PRODUCTS_FORWARD + flops_moe.PRODUCTS_BACKWARD == 9
    # A dW product: the two row operands at 2 bytes, and the float32
    # running sum of the 2 matrices read and written (PR 42).
    ops_dw, nbytes_dw = flops_moe.grouped_product_dw(10, 4, 6, 2)
    assert ops_dw == ops
    assert nbytes_dw == (10 * 4 + 10 * 6) * 2 + 2 * 4 * 6 * (4 + 4)
    assert flops_moe.PRODUCTS_DW == 3


def test_routed_layer_seconds_at_the_cells_shape():
    # 4096 rows, 2048 x 1536, 8 experts: compute-bound, 25.8 GFLOP a product.
    ops, nbytes = flops_moe.grouped_product(4096, 2048, 1536, 8)
    assert ops == pytest.approx(25.77e9, rel=1e-3)
    assert nbytes == pytest.approx(79.7e6, rel=1e-3)
    assert flops.roofline_seconds(ops, nbytes, "TPU v5 lite")[1] == "compute"
    # Six of the nine by their products; a dW by the float32 sum of 8
    # matrices of 2048 x 1536 it reads and writes: 201.3 MB beside the
    # rows' 29.4, 0.2817 ms where its products are 0.1308.
    dw = (4096 * 3584 * 2 + 8 * 2048 * 1536 * 8) / 819e9
    assert dw == pytest.approx(0.2817e-3, rel=1e-3)
    assert flops_moe.routed_layer_train_seconds(
        4096, 2048, 1536, 8, "TPU v5 lite") == pytest.approx(
        6 * ops / 197e12 + 3 * dw)
    # Few rows: reading the experts' matrices bounds all nine.
    assert flops_moe.routed_layer_train_seconds(
        64, 2048, 1536, 8, "TPU v5 lite") == pytest.approx(
        (6 * (64 * 3584 + 8 * 2048 * 1536) * 2
         + 3 * (64 * 3584 * 2 + 8 * 2048 * 1536 * 8)) / 819e9)


def test_moe_roofline_reader_by_hand():
    from benchmarks.readers import moe_gmm_roofline_pct as reader
    from oobleck_tpu.utils import metrics

    args = {"match": ["%moe_gmm.", "%moe_tgmm."]}
    reg = metrics.registry()
    reg.clear()
    trace = {"time_by_name": {
        "%moe_gmm.3 = bf16[34816,1536] custom-call": [0.30, 900],
        "%moe_tgmm.1 = f32[8,2048,1536] custom-call": [0.15, 300],
        "%flash_fwd.2 = bf16[256,1024,128] custom-call": [9.0, 40]}}
    data = {"trace": trace, "device": {"kind": "TPU v5 lite"},
            "config": CONFIG,
            "train": {"microbatch_size": 8, "seq_len": 1024,
                      "microbatches_run": 25}}
    # A program that never probed: nothing to read, and no error.
    assert reader.read(data, **args) is None
    reg.counter("oobleck_moe_probed_tokens_total").inc(1024)
    for layer, pairs in (("1", 512), ("2", 256), ("3", 768), ("4", 512)):
        reg.counter("oobleck_moe_routed_pairs_total").inc(pairs, layer=layer)
    least = sum(flops_moe.routed_layer_train_seconds(
        share * 8192, 2048, 1536, 8, "TPU v5 lite")
        for share in (0.5, 0.25, 0.75, 0.5))
    assert reader.read(data, **args) == pytest.approx(
        100 * 25 * least / 0.45)
    del trace["time_by_name"]["%moe_gmm.3 = bf16[34816,1536] custom-call"]
    del trace["time_by_name"]["%moe_tgmm.1 = f32[8,2048,1536] custom-call"]
    assert reader.read(data, **args) is None
    assert reader.read({}, **args) is None
    reg.clear()


# --------------------------------------------------------------------- #
# the reference's forced routing and its mismatch count                  #
# --------------------------------------------------------------------- #

@pytest.fixture(scope="module")
def tiny():
    rc = ref.RefConfig.from_config(TINY)
    params = ref.init_params(2**31 + 5, rc)
    tokens = jax.random.randint(jax.random.PRNGKey(2), (1, 64), 0,
                                rc.vocab_size)
    return rc, params, tokens


def test_seeded_bias_balances_the_experts(tiny):
    """`init_params` leaves every routed block a bias that is not zero and
    evens the experts' loads, on token ids it was not balanced on too."""
    rc, params, _ = tiny
    fresh = jax.random.randint(jax.random.PRNGKey(9), (8, 256), 0,
                               rc.vocab_size)
    _, own = jax.jit(ref.forward, static_argnames=("c",))(params, fresh, c=rc)
    for block, chosen in zip(rc.routed_blocks, own):
        bias = np.asarray(params["blocks"][block]["ff"]["expert_bias"])
        assert bias.any() and np.abs(bias).max() < 0.3
        loads = np.bincount(np.asarray(chosen).reshape(-1),
                            minlength=rc.num_experts)
        assert loads.max() < 1.35 * loads.mean(), loads
        assert loads.min() > 0.65 * loads.mean(), loads
    # The rule itself, on scores with a built-in tilt towards expert 0.
    scores = jax.nn.sigmoid(jax.random.normal(jax.random.PRNGKey(4), (4096, 8))
                            + jnp.arange(8)[::-1] * 0.3)
    bias = ref.balanced_bias(scores, 2)
    _, chosen = jax.lax.top_k(scores + bias, 2)
    loads = np.bincount(np.asarray(chosen).reshape(-1), minlength=8)
    assert np.abs(loads - 1024).max() < 40 and bias[0] < bias[7]


def test_forcing_the_references_own_choice_changes_nothing(tiny):
    rc, params, tokens = tiny
    (loss, own), grads = ref.loss_and_grads(params, tokens, rc)
    assert len(own) == len(rc.routed_blocks) == 3
    assert own[0].shape == (1, 64, rc.num_experts_per_tok)
    (loss_f, own_f), grads_f = ref.loss_and_grads(params, tokens, rc,
                                                  "highest", own)
    assert float(loss) == float(loss_f)
    assert float(ref.mismatch_share(own, own_f)) == 0.0
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(grads_f)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    bias_grads = [g["ff"]["expert_bias"] for g in grads["blocks"]
                  if "expert_bias" in g["ff"]]
    assert bias_grads and not any(np.asarray(g).any() for g in bias_grads)


def test_forced_routing_is_used_and_mismatches_are_counted(tiny):
    rc, params, tokens = tiny
    (loss, own), _ = ref.loss_and_grads(params, tokens, rc)
    # Another selection for the first half of the first routed block.
    other = own[0].at[:, :32].set((own[0][:, :32] + 1) % rc.num_experts)
    forced = [other, *own[1:]]
    (loss_f, own_f), _ = ref.loss_and_grads(params, tokens, rc, "highest",
                                            forced)
    assert float(loss_f) != float(loss)
    # The first block's own choice does not depend on what was forced on
    # it (its input is the same); a third of the pairs, half of them moved.
    np.testing.assert_array_equal(np.asarray(own_f[0]), np.asarray(own[0]))
    share = float(ref.mismatch_share(forced, own_f))
    assert 32 / (3 * 64) <= share < 0.5
    # A permutation of a token's picks is the same SET.
    flipped = [c[..., ::-1] for c in own]
    assert float(ref.mismatch_share(flipped, own)) == 0.0


@pytest.mark.parametrize("mode", ["bfloat16", "fp8"])
def test_lower_precision_routes_some_tokens_elsewhere(tiny, mode):
    """The control's two readings at a size a test can hold: gradients of
    the lower-precision run against the float32 reference under the SAME
    routing, and how often its own routing differs."""
    rc, params, tokens = tiny
    # Jitted: eagerly the CPU has no bfloat16 x bfloat16 -> float32 product.
    run = jax.jit(ref.loss_and_grads, static_argnames=("c", "mode"))
    (_, chosen), grads_m = run(params, tokens, c=rc, mode=mode)
    (_, own), grads = run(params, tokens, c=rc, mode="highest",
                          forced=chosen)
    sq = lambda t: sum(float(jnp.sum(jnp.square(x)))
                       for x in jax.tree.leaves(t))
    err = (sq(jax.tree.map(lambda a, b: a - b, grads_m, grads))
           / sq(grads)) ** 0.5
    mismatch = float(ref.mismatch_share(chosen, own))
    assert 0 < err < 1 and 0 <= mismatch < 0.6
    if mode == "fp8":
        assert err > 0.03 and mismatch > 0.02


# --------------------------------------------------------------------- #
# the runner, rehearsed                                                  #
# --------------------------------------------------------------------- #

def test_runner_control_flow_on_the_cpu(tmp_path, monkeypatch):
    from benchmarks import run as harness
    from benchmarks.runners import train_lfm2

    monkeypatch.setenv("OOBLECK_TPU_CACHE", str(tmp_path / "profiles"))
    cell = {"name": "tiny.steady", "config": "tiny", "chips": 1,
            "kind": "train_lfm2",
            "traffic": {"seq_len": 128, "microbatch_size": 2,
                        "global_batch": 4, "warmup_steps": 1,
                        "learning_rate": 1e-3, "lr_warmup_steps": 2},
            "correct": {"grad_rel_err": 0.2}}
    ctx = harness.Context(cell, TINY, 2**31 + 11, 1.0, False,
                          {"platform": "cpu", "kind": "cpu", "count": 1})
    out = train_lfm2.run(ctx)
    assert ctx.setup_s is not None
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert [c["check"] for c in out["checks"]] == ["grad_rel_err"]
    assert out["checks"][0]["ok"], out["checks"]
    assert out["end_to_end"]["train_tokens_per_s"] > 0
    train = out["layer_data"]["train"]
    # What the flash readers multiply by: ONE attention layer of the four.
    assert (train["num_layers"], train["num_heads"], train["hidden_size"]) \
        == (1, 4, 64)
    assert train["microbatches_run"] == 2 * out["attempted"]
    # The probe the check made filled the program's counters, which the
    # roofline reader reads: pairs a token, for each of 3 routed blocks.
    from benchmarks.readers import moe_gmm_roofline_pct as reader

    shares = reader._pairs_per_token_by_layer()
    assert len(shares) == 3 and all(0 < s <= 2 for s in shares)
    assert abs(sum(shares) / 3 - 2 * 4 / 8) < 0.5     # top 2, 4 of 8 held
