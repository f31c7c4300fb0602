"""The Moonlight cell's own pieces: the configuration file against the
catalog's config and the `assumed` words, `flops_mla.py` by hand, the two
new readers on hand-made data, the reference's blocked attention, forced
routing and the shared experts, and the runner's and the control's flow
rehearsed on the CPU at `moonlight-tiny` sizes (never a number)."""

import json
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks import flops, flops_mla
from benchmarks.reference import deepseek_v3 as ref

ROOT = Path(__file__).resolve().parents[2]
CONFIG = json.loads(
    (ROOT / "benchmarks" / "configs" / "moonlight-16b-a3b.json").read_text())
CELL = json.loads((ROOT / "benchmarks" / "workloads"
                   / "moonlight-16b-a3b.steady.json").read_text())
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")

# The catalog's `config` of Moonlight-16B-A3B, as the driver drew it.
PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 11264,
    "kv_lora_rank": 512, "max_position_embeddings": 8192,
    "model_type": "deepseek_v3", "moe_intermediate_size": 1408,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 64,
    "n_shared_experts": 2, "norm_topk_prob": True,
    "num_attention_heads": 16, "num_experts_per_tok": 6,
    "num_hidden_layers": 27, "num_key_value_heads": 16,
    "num_nextn_predict_layers": 0, "q_lora_rank": None,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05,
    "rope_theta": 50000, "routed_scaling_factor": 2.446,
    "scoring_func": "sigmoid", "seq_aux": True,
    "tie_word_embeddings": False, "topk_group": 1,
    "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 163840}

TINY = {
    "name": "tiny", "model_name": "moonlight-tiny",
    "model_args": {"num_experts_held": 2, "expert_offset": 0,
                   "vocab_rows_held": 128},
    "vocab_size": 256, "vocab_rows_held": 128, "hidden_size": 64,
    "num_hidden_layers": 3, "num_attention_heads": 4, "kv_lora_rank": 32,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "intermediate_size": 128, "moe_intermediate_size": 32,
    "first_k_dense_replace": 1, "n_routed_experts": 8,
    "num_experts_per_tok": 3, "n_shared_experts": 2, "num_experts_held": 2,
    "routed_scaling_factor": 2.446, "rms_norm_eps": 1e-5,
    "rope_theta": 50000,
    "execution": {"precision": "bfloat16", "remat": True},
}
SEED = 2**31 + 5


# --------------------------------------------------------------------- #
# the configuration                                                      #
# --------------------------------------------------------------------- #

def test_configuration_keeps_every_published_number():
    """Every key of the catalog's config is in the file under its own
    name, and differs only where `reduced` says so."""
    if CATALOG.exists():
        rows = [json.loads(line) for line in CATALOG.read_text().splitlines()]
        (entry,) = [r for r in rows if r["name"] == "Moonlight-16B-A3B"]
        assert entry["config"] == PUBLISHED
        assert entry["source_url"] == CONFIG["source"]
    for key, value in PUBLISHED.items():
        if key in CONFIG["reduced"]:
            assert CONFIG["source_values"][key] == value, key
        else:
            assert CONFIG[key] == value, key
    assert CONFIG["reduced"] == ["num_layers", "num_hidden_layers",
                                 "num_experts_held", "vocab_rows_held"]
    assert CONFIG["source_values"] == {
        "num_layers": 27, "num_hidden_layers": 27, "num_experts_held": 64,
        "vocab_rows_held": 163840}
    assert (CONFIG["num_layers"], CONFIG["num_hidden_layers"],
            CONFIG["num_experts_held"], CONFIG["vocab_rows_held"]) == (
        5, 5, 64 // 8, 163840 // 8)
    assert CONFIG["head_dim"] == 128 + 64 and CONFIG["num_heads"] == 16
    assert "eight" in CONFIG["deployment"].lower()
    (entry,) = [c for c in MANIFEST["configs"] if c["name"] == CONFIG["name"]]
    assert entry["reduced"] == CONFIG["reduced"]


@pytest.mark.parametrize("key,words", [
    ("rotary_pairing", "rotary pairing — rotate-half; the family's "
     "interleaved pairing is a fixed permutation of the 64 rotary columns of "
     "`Wq` and `Wkv_a`, which seeded weights do not distinguish"),
    ("latent_norm_eps", "eps of the latent's RMSNorm — 1e-6, as the family's "
     "public modelling code builds it without the config's eps"),
    ("auxiliary_loss", "no auxiliary or sequence balance loss (`seq_aux` has "
     "no coefficient in the config)"),
    ("selection_bias", "the selection bias is not trained by the gradient "
     "and has no update rule here (`freeze_leaves`), and the seeded weights "
     "carry the bias that balances the seed's router on uniform ids, as "
     "`reference/lfm2.py::_balance` does"),
    ("normaliser_epsilon", "the weight normaliser's epsilon is `route`'s "
     "1e-6 where the family's code has 1e-20 (a relative 3e-7)"),
    ("initializer", "initialiser as `lfm2-24b-a2b`'s (normal 0.02, residual "
     "outputs 0.02 / sqrt(2 x 5))"),
], ids=lambda x: x if "_" in x and " " not in x else "words")
def test_what_the_config_is_silent_on_is_stated(key, words):
    """ISSUE 35's six items, in its words, in the file; and named in the
    reference's docstring."""
    assert words in CONFIG["assumed"][key]
    assert CONFIG["assumed"][key].startswith("(")
    number = CONFIG["assumed"][key][:3]
    assert number in ref.__doc__


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
    assert rc.num_params() == 568_484_608               # ISSUE 35: 568.5 M
    parts = [sum(rc.block_params(b).values()) for b in range(5)]
    assert [round(p / 1e6, 2) for p in parts] == [82.97] + [100.41] * 4
    assert round(rc.block_params(0)["attention"] / 1e6, 3) == 13.763
    assert (c.data_vocab_size, c.experts_held, c.expert_offset) == (
        rc.vocab_size, rc.num_experts_held, rc.expert_offset) == (20480, 8, 0)
    for key in ("hidden_size", "num_layers", "num_heads", "kv_lora_rank",
                "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
                "intermediate_size", "moe_intermediate_size",
                "first_k_dense_replace", "num_experts",
                "num_experts_per_tok", "n_shared_experts",
                "routed_scaling_factor", "norm_eps", "latent_norm_eps",
                "rope_theta", "initializer_range", "expert_bias_range"):
        assert getattr(c, key) == getattr(rc, key), key
    assert CELL["traffic"]["seq_len"] <= c.max_position_embeddings


def test_cell_is_the_traffic_the_issue_gives():
    """ISSUE 35's traffic: sequences of 4096 one a microbatch, eight a
    step, the learning rate warming up over 2000 steps (so the window
    trains at 2e-7 .. 3e-6; PERF.md section 6, PR 34). Its fallback (b)
    (16 x 2048, for a peak over 88 % of the chip's memory) was taken,
    measured and NOT kept: 8 x 4096 peaked at 91.9 %, 16 x 2048 at 95.4 %
    and ran 6 % slower; the peak follows the parameters, not the tokens
    (my chip runs, PR 35; `correct_why` and PERF.md section 6 say so)."""
    t = CELL["traffic"]
    assert t == {"seq_len": 4096, "microbatch_size": 1, "global_batch": 8,
                 "warmup_steps": 2, "learning_rate": 0.00016,
                 "lr_warmup_steps": 2000}
    assert "lr 2e-7..3e-6" in CELL["why"] and "384" in CELL["why"]
    (entry,) = [w for w in MANIFEST["workloads"] if w["name"] == CELL["name"]]
    assert entry["why"] == CELL["why"] and entry["chips"] == 1
    assert sorted(CELL["correct"]) == ["grad_rel_err",
                                       "routing_mismatch_share"]
    assert "PLACEHOLDER" not in CELL["correct_why"]
    for words in ("2000", "fallback (b)", "91.9 %", "95.4 %"):
        assert words in CELL["correct_why"], words


NEW_METRICS = ["flash_mla_fwd_roofline", "flash_mla_bwd_roofline",
               "flash_mla_fwd_calls_per_need", "moe_held_rows_drift"]
THIS_CELLS_TOO = ["moe_gmm_ms", "moe_tgmm_ms", "moe_gmm_roofline",
                  "moe_token_sum_ms", "moe_tile_fill_pct", "moe_load_skew",
                  "moe_step_rows_spread_pct"]
# Readers that would compute something WRONG on this cell, or find nothing
# to read: one width of `hidden_size // num_heads`, a dense model's 6 N,
# kernel names the latent calls do not carry. (Which further cells the
# lists above name is a later PR's to say: membership, never a list's whole.)
NOT_THIS_CELLS = ["flash_fwd_roofline", "flash_bwd_roofline",
                  "flash_fwd_calls_per_need", "flash_bwd_ms",
                  "mfu_pct.train", "flash_roofline", "ssd_fwd_ms",
                  "ssd_bwd_ms", "ssd_fwd_roofline", "ssd_bwd_roofline"]


@pytest.mark.parametrize("metric",
                         NEW_METRICS + THIS_CELLS_TOO + NOT_THIS_CELLS)
def test_which_metrics_name_the_cell(metric):
    (entry,) = [m for m in MANIFEST["per_layer"] if m["name"] == metric]
    if metric in NOT_THIS_CELLS:
        assert CELL["name"] not in entry["workloads"]
        return
    assert CELL["name"] in entry["workloads"]
    assert entry["moves"] == "train_tokens_per_s"
    assert entry["layer"] == "kernels"


# --------------------------------------------------------------------- #
# flops_mla and the two readers                                          #
# --------------------------------------------------------------------- #

def test_latent_attention_counts_at_the_cells_shape():
    s, h = 4096, 16
    ops, nbytes = flops_mla.latent_attention_fwd(1, h, s, 128, 64, 128)
    # Q K^T at 192 and P V at 128, 2 operations a multiply-add, halved.
    assert ops == (2 * s * s * 192 + 2 * s * s * 128) / 2 * h
    assert ops == s * s * (192 + 128) * h
    # q at 192 a head; k at 128 a head + 64 ONCE a position; v, o at 128.
    assert nbytes == 2 * (h * s * 192 + (h * s * 128 + s * 64)
                          + 2 * h * s * 128)
    ops_b, nbytes_b = flops_mla.latent_attention_bwd(1, h, s, 128, 64, 128)
    assert ops_b == s * s * (2 * 192 + 2 * 128) * h == 2 * ops
    assert nbytes_b == 2 * (2 * h * s * 192 + 2 * (h * s * 128 + s * 64)
                            + 4 * h * s * 128)
    # At one width and no shared key it is the plain yardstick's count.
    plain, _ = flops.causal_attention_fwd(2, 8, 512, 64)
    assert flops_mla.latent_attention_fwd(2, 8, 512, 64, 0, 64)[0] == plain
    assert flops.roofline_seconds(ops, nbytes, "TPU v5 lite")[1] == "compute"
    # ISSUE 35: 21.0 M forward operations a token in scores and values.
    assert round(ops / s / 1e6, 1) == 21.0


def test_mla_roofline_reader_by_hand():
    from benchmarks.readers import mla_roofline_pct as reader

    args = {"match": "%flash_mla_bwd_", "needed": ["latent_attention_bwd"]}
    trace = {"time_by_name": {
        "%flash_mla_bwd_dq.3 = bf16[16,4096,256] custom-call": [0.6, 500],
        "%flash_mla_bwd_dkv.1 = bf16[16,4096,256] custom-call": [0.9, 500],
        "%flash_mla_fwd.2 = bf16[16,4096,128] custom-call": [0.5, 1000],
        "%flash_bwd_dq.7 = bf16[96,4096,128] custom-call": [9.0, 200]}}
    data = {"trace": trace, "device": {"kind": "TPU v5 lite"},
            "config": CONFIG,
            "train": {"microbatch_size": 1, "seq_len": 4096,
                      "microbatches_run": 100, "num_layers": 5,
                      "num_heads": 16, "hidden_size": 2048}}
    ops, nbytes = flops_mla.latent_attention_bwd(1, 16, 4096, 128, 64, 128)
    least = flops.roofline_seconds(ops, nbytes, "TPU v5 lite")[0]
    assert reader.read(data, **args) == pytest.approx(
        100 * least * 100 * 5 / 1.5)
    assert reader.read(data, match="%flash_mla_fwd.",
                       needed=["latent_attention_fwd"]) == pytest.approx(
        100 * least / 2 * 100 * 5 / 0.5)
    # A program without the kernels (the parent), a configuration without
    # the widths (every other cell), no data: nothing to read, no error.
    assert reader.read(dict(data, trace={"time_by_name": {}}), **args) is None
    assert reader.read(dict(data, config={"hidden_size": 2048}),
                       **args) is None
    assert reader.read({}, **args) is None


@pytest.mark.parametrize("before,after,want", [
    ({"1": 400, "2": 380}, {"1": 404, "2": 361}, 5.0),
    ({"1": 400}, {"1": 400}, 0.0),
    ({"1": 400, "2": 0}, {"1": 300, "2": 9}, 25.0),
    ({}, {}, None), ({"1": 400}, {}, None),
], ids=["largest_layer", "still", "empty_layer_left_out", "nothing",
        "one_reading"])
def test_held_rows_drift_reader_by_hand(before, after, want):
    from benchmarks.readers import held_rows_drift_pct as reader

    got = reader.read({"held_rows": {"before": before, "after": after}})
    assert got == (None if want is None else pytest.approx(want))
    assert reader.read({}) is None


def test_calls_per_need_reads_the_latent_forward():
    """The accepted reader under a new `match`: no new code."""
    from benchmarks.readers import kernel_calls_per_need as reader

    spec = json.loads((ROOT / "benchmarks" / "layer_metrics"
                       / "flash_mla_fwd_calls_per_need.json").read_text())
    assert spec["reader"] == "kernel_calls_per_need"
    data = {"trace": {"time_by_name": {
        "%flash_mla_fwd.2 = bf16[16,4096,128] custom-call": [0.5, 1000],
        "%flash_fwd.2 = bf16[16,4096,128] custom-call": [0.5, 77]}},
        "train": {"microbatches_run": 100, "num_layers": 5}}
    assert reader.read(data, **spec["args"]) == 2.0


# --------------------------------------------------------------------- #
# the reference                                                          #
# --------------------------------------------------------------------- #

@pytest.fixture(scope="module")
def tiny():
    rc = ref.RefConfig.from_config(TINY)
    params = ref.init_params(SEED, rc, (2, 64))
    tokens = jax.random.randint(jax.random.PRNGKey(2), (1, 64), 0,
                                rc.vocab_size)
    return rc, params, tokens


@pytest.fixture(scope="module")
def run(tiny):
    rc = tiny[0]
    return jax.jit(lambda params, tokens, forced=None: ref.loss_and_grads(
        params, tokens, rc, "highest", forced))


def test_reference_imports_nothing_of_the_program():
    source = (ROOT / "benchmarks" / "reference" / "deepseek_v3.py").read_text()
    assert "import oobleck_tpu" not in source
    assert "from oobleck_tpu" not in source


@pytest.mark.parametrize("h_block,q_block", [(2, 16), (4, 64), (3, 10)],
                         ids=["blocks", "one_block", "no_divisor_whole"])
def test_attention_over_blocks_is_attention_whole(h_block, q_block):
    """Blocks of heads and queries change no value."""
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (4, 64, 24))
    k = jax.random.normal(ks[1], (4, 64, 24))
    v = jax.random.normal(ks[2], (4, 64, 16))
    blocked = ref.attend(q, k, v, "highest", h_block, q_block)
    live = jnp.arange(64)[None, :] <= jnp.arange(64)[:, None]
    scores = jnp.einsum("hqd,hkd->hqk", q, k, precision="highest") / 24 ** 0.5
    whole = jnp.einsum("hqk,hkd->hqd",
                       jax.nn.softmax(jnp.where(live, scores, -1e30), -1),
                       v, precision="highest")
    assert blocked.shape == (4, 64, 16)
    np.testing.assert_allclose(np.asarray(blocked), np.asarray(whole),
                               atol=1e-6)
    grad = jax.grad(lambda q: jnp.sum(
        ref.attend(q, k, v, "highest", h_block, q_block) ** 2))(q)
    assert np.isfinite(np.asarray(grad)).all() and np.asarray(grad).any()


def test_the_seeded_bias_balances_the_seeds_router(tiny):
    rc, params, _ = tiny
    for block in rc.routed_blocks:
        bias = np.asarray(params["blocks"][block]["ff"]["expert_bias"])
        assert bias.shape == (rc.num_experts,) and bias.any()
        assert np.abs(bias).max() < 0.5


def test_forcing_the_references_own_choice_changes_nothing(tiny, run):
    rc, params, tokens = tiny
    (loss, own), grads = run(params, tokens)
    assert len(own) == len(rc.routed_blocks) == 2
    assert own[0].shape == (1, 64, rc.num_experts_per_tok)
    (loss_f, own_f), grads_f = run(params, tokens, own)
    assert float(loss) == float(loss_f)
    assert float(ref.mismatch_share(own, own_f)) == 0.0
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(grads_f)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_forced_routing_is_used_and_mismatches_are_counted(tiny, run):
    rc, params, tokens = tiny
    (loss, own), _ = run(params, tokens)
    other = own[0].at[:, :32].set((own[0][:, :32] + 1) % rc.num_experts)
    forced = [other, *own[1:]]
    (loss_f, own_f), _ = run(params, tokens, forced)
    assert float(loss_f) != float(loss)
    np.testing.assert_array_equal(np.asarray(own_f[0]), np.asarray(own[0]))
    share = float(ref.mismatch_share(forced, own_f))
    # Half of the first routed block's tokens, and whatever that moved
    # in the block after it.
    assert 32 / (2 * 64) <= share < 0.6


def test_shared_experts_are_on_every_token_whatever_is_held(tiny):
    """With no expert of a token's choice held, the routed layer still
    gives the shared experts' output: one SwiGLU of 2 x the expert width."""
    rc, params, _ = tiny
    p = params["blocks"][1]["ff"]
    assert p["shared"]["w1"].shape == (rc.hidden_size,
                                       2 * rc.moe_intermediate_size)
    h = jax.random.normal(jax.random.PRNGKey(7), (1, 16, rc.hidden_size))
    elsewhere = jnp.full((1, 16, rc.num_experts_per_tok), 5)   # not 0-1
    out, _ = ref._routed(p, h, rc, "highest", elsewhere)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref._swiglu(p["shared"], h, "highest")),
        atol=1e-7)


def test_control_readings_at_a_size_a_test_can_hold():
    """`control_deepseek_v3.reference_vs_reference`, the path that sets the
    limits, rehearsed in the control's own precision."""
    from benchmarks import control_deepseek_v3

    cell = {"traffic": {"seq_len": 64}}
    row = control_deepseek_v3.reference_vs_reference(TINY, cell, SEED, "fp8")
    assert set(row) == {"loss_rel_err", "grad_rel_err",
                        "routing_mismatch_share", "grad_rel_err_free"}
    assert 0.03 < row["grad_rel_err"] < 1
    assert 0.02 < row["routing_mismatch_share"] < 0.9


# --------------------------------------------------------------------- #
# the runner, rehearsed                                                  #
# --------------------------------------------------------------------- #

def test_runner_control_flow_on_the_cpu(tmp_path, monkeypatch, capsys):
    from benchmarks import run as harness
    from benchmarks.readers import held_rows_drift_pct
    from benchmarks.runners import train_deepseek_v3

    monkeypatch.setenv("OOBLECK_TPU_CACHE", str(tmp_path / "profiles"))
    cell = {"name": "tiny.steady", "config": "tiny", "chips": 1,
            "kind": "train_deepseek_v3",
            "traffic": {"seq_len": 64, "microbatch_size": 1,
                        "global_batch": 2, "warmup_steps": 1,
                        "learning_rate": 1e-3, "lr_warmup_steps": 2000},
            "correct": {"grad_rel_err": 0.2, "routing_mismatch_share": 0.5}}
    ctx = harness.Context(cell, TINY, 2**31 + 11, 1.0, False,
                          {"platform": "cpu", "kind": "cpu", "count": 1})
    out = train_deepseek_v3.run(ctx)
    assert ctx.setup_s is not None
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert [c["check"] for c in out["checks"]] == [
        "grad_rel_err", "routing_mismatch_share"]
    assert all(c["ok"] for c in out["checks"]), out["checks"]
    assert out["end_to_end"]["train_tokens_per_s"] > 0
    # The job's own sequence length, under the model's context of 128;
    # every block is an attention layer.
    train = out["layer_data"]["train"]
    assert (train["seq_len"], train["num_layers"], train["num_heads"],
            train["hidden_size"]) == (64, 3, 4, 64)
    assert train["microbatches_run"] == 2 * out["attempted"]
    # Both readings of the gauge, one a routed block, and the drift reader.
    rows = out["layer_data"]["held_rows"]
    assert sorted(rows["before"]) == sorted(rows["after"]) == ["1", "2"]
    assert all(0 < v <= 64 * 3 for v in rows["before"].values())
    drift = held_rows_drift_pct.read(out["layer_data"])
    assert drift is not None and 0 <= drift < 50
    # The second probe ran the program the first compiled: nothing
    # compiles after the window.
    said = [json.loads(line) for line in capsys.readouterr().out.splitlines()
            if line.startswith("{")]
    (held,) = [o for o in said if o["observation"] == "held_rows"]
    assert held["probe_programs"] == 1 and held["before"] == rows["before"]
    from benchmarks.readers import moe_gmm_roofline_pct as reader

    shares = reader._pairs_per_token_by_layer()
    assert len(shares) == 2 and all(0 < s <= 3 for s in shares)


def test_example_job_is_the_cells_job():
    """examples/moonlight-16b-a3b.yaml is the one chip's job the cell
    measures: the same model arguments, sequence length and batch."""
    from oobleck_tpu.config import OobleckArguments

    args = OobleckArguments.from_yaml(
        str(ROOT / "examples" / "moonlight-16b-a3b.yaml"))
    assert args.model.model_name == CONFIG["model_name"]
    assert args.model.model_args == CONFIG["model_args"]
    t = CELL["traffic"]
    assert (args.job.seq_len, args.job.microbatch_size,
            args.job.global_microbatch_size, args.job.learning_rate,
            args.job.warmup_steps) == (
        t["seq_len"], t["microbatch_size"], t["global_batch"],
        t["learning_rate"], t["lr_warmup_steps"])
    assert args.execution.resolved_path() == "mpmd"
    assert (args.execution.precision, args.execution.remat) == (
        CONFIG["execution"]["precision"], CONFIG["execution"]["remat"])
