"""The Qwen3-Next cell's own pieces: the configuration file against the
catalog's config and the `assumed` words, the sizes against what the
program builds, MEMBERSHIP of the cell and its metrics in the manifest
(never a list's end or its whole), the accepted readers on this cell's
geometry by hand, the reference's recurrence in blocks, forced routing and
the gated shared expert, and the runner's and the control's flow rehearsed
on the CPU at `qwen3-next-tiny` sizes (never a number)."""

import json
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks import flops, flops_moe
from benchmarks.reference import qwen3_next as ref

ROOT = Path(__file__).resolve().parents[2]
NAME = "qwen3-next-80b-a3b"
CONFIG = json.loads(
    (ROOT / "benchmarks" / "configs" / f"{NAME}.json").read_text())
CELL = json.loads((ROOT / "benchmarks" / "workloads"
                   / f"{NAME}.steady.json").read_text())
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")


@pytest.fixture(autouse=True, scope="module")
def _leave_no_series_behind():
    """The routing probe's counters live in the PROCESS-GLOBAL registry and
    the routed readers take every series they find there: a later module on
    this worker must not read this one's layers."""
    yield
    from oobleck_tpu.utils import metrics

    metrics.registry().clear()


# The catalog's `config` of Qwen3-Next-80B-A3B-Instruct, as the driver drew
# it.
PUBLISHED = {
    "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5120,
    "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
    "linear_num_key_heads": 16, "linear_num_value_heads": 32,
    "linear_value_head_dim": 128, "max_position_embeddings": 262144,
    "mlp_only_layers": [], "model_type": "qwen3_next",
    "moe_intermediate_size": 512, "norm_topk_prob": True,
    "num_attention_heads": 16, "num_experts": 512,
    "num_experts_per_tok": 10, "num_hidden_layers": 48,
    "num_key_value_heads": 2, "partial_rotary_factor": 0.25,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 10000000,
    "shared_expert_intermediate_size": 512, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936}

TINY = {
    "name": "tiny", "model_name": "qwen3-next-tiny",
    "model_args": {"num_experts_held": 4, "expert_offset": 0,
                   "vocab_rows_held": 100},
    "vocab_size": 256, "vocab_rows_held": 100, "hidden_size": 64,
    "num_hidden_layers": 4, "full_attention_interval": 4,
    "linear_num_key_heads": 2, "linear_num_value_heads": 4,
    "linear_key_head_dim": 16, "linear_value_head_dim": 16,
    "linear_conv_kernel_dim": 4, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "partial_rotary_factor": 0.25,
    "rope_theta": 10000000, "moe_intermediate_size": 32,
    "shared_expert_intermediate_size": 32, "num_experts": 16,
    "num_experts_per_tok": 4, "num_experts_held": 4, "rms_norm_eps": 1e-6,
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
        (entry,) = [r for r in rows
                    if r["name"] == "Qwen3-Next-80B-A3B-Instruct"]
        assert entry["config"] == PUBLISHED
        assert entry["source_url"] == CONFIG["source"]
    for key, value in PUBLISHED.items():
        if key in CONFIG["reduced"]:
            assert CONFIG["source_values"][key] == value, key
        else:
            assert CONFIG[key] == value, key
    assert CONFIG["reduced"] == ["num_hidden_layers", "num_layers",
                                 "num_experts_held", "vocab_rows_held"]
    assert CONFIG["source_values"] == {
        "num_hidden_layers": 48, "num_layers": 48, "num_experts_held": 512,
        "vocab_rows_held": 151936}
    assert (CONFIG["num_layers"], CONFIG["num_hidden_layers"],
            CONFIG["num_experts_held"], CONFIG["vocab_rows_held"]) == (
        4, 4, 512 // 32, 151936 // 8)
    # One whole period of the published 3 : 1.
    assert CONFIG["num_layers"] == CONFIG["full_attention_interval"]
    # No width among the cuts: the router's 512 outputs and its 10 a token,
    # every head count and head size are the catalog's (the loop above).
    assert not [k for k in CONFIG["reduced"]
                if k.endswith(("_dim", "_rank")) or "size" in k]
    assert (CONFIG["num_heads"], CONFIG["num_kv_heads"]) == (16, 2)
    assert "thirty-two" in CONFIG["deployment"].lower()
    assert CONFIG["execution"] == {"precision": "bfloat16", "remat": True}
    (entry,) = [c for c in MANIFEST["configs"] if c["name"] == CONFIG["name"]]
    assert entry["reduced"] == CONFIG["reduced"]
    assert entry["source"] == CONFIG["source"]
    for words in ("424,340,544", "4 x 16 and not 8 x 8", "19,072",
                  "expert parallelism over 32"):
        assert words in CONFIG["reduced_why"], words


@pytest.mark.parametrize("key,words", [
    ("chunk", "the chunk of 64 positions"),
    ("column_order", "the fused projections' column order: [q | k | v | z] "
     "and [b | a]"),
    ("auxiliary_loss", "no auxiliary or balance loss (`router_aux_loss_coef` "
     "is in the published file and not in the catalog's)"),
    ("mtp", "no multi-token-prediction head"),
    ("initializer", "initialisers: `A_log = log a`, `a` uniform in (0, 16]; "
     "`dt_bias` 1"),
    ("weight_decay", "AdamW's weight decay covers every trained leaf"),
    ("share", "nothing stands in for the 31 absent chips"),
], ids=lambda x: x if " " not in x else "words")
def test_what_the_config_is_silent_on_is_stated(key, words):
    """ISSUE 43's items, numbered in the file as in the reference's
    docstring."""
    assert words in CONFIG["assumed"][key]
    assert CONFIG["assumed"][key].startswith("(")
    assert CONFIG["assumed"][key][:3] in ref.__doc__


def test_reference_and_program_agree_on_the_configuration():
    from oobleck_tpu.models import build_model

    model = build_model(CONFIG["model_name"], dict(CONFIG["model_args"]))
    c = model.config
    rc = ref.RefConfig.from_config(CONFIG)
    shapes = [jax.eval_shape(lambda r, i=i: model.init_layer(r, i),
                             jax.random.PRNGKey(0))
              for i in range(model.num_pipeline_layers)]
    built = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    # What the program builds: the 424.3 M of ISSUE 43's table and the
    # rows that pad the vocabulary to a multiple of 128.
    padding = 2 * (c.padded_vocab_size - c.data_vocab_size) * c.hidden_size
    assert rc.num_params() == 424_340_544 == CONFIG["parameters"]["all"]
    assert built == rc.num_params() + padding
    assert padding == CONFIG["parameters"]["vocabulary_padding"] == 327_680
    assert rc.padded_vocab_size == c.padded_vocab_size == 19_072
    table = CONFIG["parameters"]
    parts = [sum(rc.block_params(b).values()) for b in range(4)]
    assert parts == [table["gdn_layer"]] * 3 + [table["attention_layer"]]
    assert sum(parts) == table["four_layers"] == 346_547_264
    gdn, attn = rc.block_params(0), rc.block_params(3)
    assert (gdn["w_qkvz"] + gdn["w_ba"] + gdn["conv"] + gdn["scalars"]
            + gdn["w_out"]) == table["gdn_operator"] == 33_718_464
    assert (attn["attention"] + attn["head_norms"]
            == table["attention_operator"] == 27_263_488)
    assert gdn["router"] + gdn["shared"] + gdn["ff"] == table["ff"]
    assert (2 * rc.vocab_size * rc.hidden_size + rc.hidden_size
            == table["vocabulary_and_final_norm"] == 77_793_280)
    assert (c.data_vocab_size, c.experts_held, c.expert_offset) == (
        rc.vocab_size, rc.num_experts_held, rc.expert_offset) == (18992, 16, 0)
    for key in ("hidden_size", "num_layers", "full_attention_interval",
                "linear_num_key_heads", "linear_num_value_heads",
                "linear_key_head_dim", "linear_value_head_dim",
                "linear_conv_kernel_dim", "num_heads", "num_kv_heads",
                "head_dim", "partial_rotary_factor", "rope_theta",
                "moe_intermediate_size", "shared_expert_intermediate_size",
                "num_experts", "num_experts_per_tok", "norm_eps",
                "initializer_range", "vocab_pad_multiple"):
        assert getattr(c, key) == getattr(rc, key), key
    assert [model.kind(b) for b in range(4)] == [rc.kind(b) for b in range(4)]
    assert c.chunk_size == CONFIG["chunk_size"] == 64
    assert CELL["traffic"]["seq_len"] <= c.max_position_embeddings
    assert CONFIG["state_bytes_per_param"] == 16


def test_cell_is_the_traffic_the_issue_gives():
    """ISSUE 43's traffic: `moonlight-16b-a3b.steady`'s and
    `nemotron-3-nano-30b-a3b.steady`'s to the number, so the three cells
    differ in the model alone."""
    t = CELL["traffic"]
    assert t == {"seq_len": 4096, "microbatch_size": 1, "global_batch": 8,
                 "warmup_steps": 2, "learning_rate": 0.00016,
                 "lr_warmup_steps": 2000}
    for other in ("moonlight-16b-a3b", "nemotron-3-nano-30b-a3b"):
        assert t == json.loads((ROOT / "benchmarks" / "workloads"
                                / f"{other}.steady.json").read_text())[
            "traffic"]
    for words in ("lr 2e-7..3e-6", "~80 rows", "2,560", "32 x share",
                  "a 32nd"):
        assert words in CELL["why"], words
    (entry,) = [w for w in MANIFEST["workloads"] if w["name"] == CELL["name"]]
    assert entry["why"] == CELL["why"] and entry["chips"] == 1
    assert (entry["config"], entry["traffic"]) == (NAME, "steady")
    assert len(CELL["why"]) <= 200
    assert CELL["kind"] == "train_qwen3_next"
    assert sorted(CELL["correct"]) == ["grad_rel_err",
                                       "routing_mismatch_share"]
    assert "PLACEHOLDER" not in CELL["correct_why"]
    assert "2000" in CELL["correct_why"]


NEW_METRICS = ["gdn_rule_ms", "gdn_mixer_ms", "flash_d256_fwd_roofline",
               "flash_d256_bwd_roofline"]
THIS_CELLS_TOO = [
    "dispatch_stall_ms.train", "input_wait_ms.train", "step_ms.train",
    "step_ms_p50.train", "step_ms_max.train", "host_dispatch_ms.train",
    "device_ms_per_step.bwd", "device_ms_per_step.optimizer",
    "device_ms_per_step.grad_zero", "idle_ms_per_step.in_step",
    "idle_ms_per_step.between_steps", "idle_ms_per_step.in_dispatch",
    "idle_ms_per_step.in_readback", "setup_engine_build_s",
    "setup_executables_s", "step_excess_ms.dispatch",
    "step_excess_ms.readback", "step_excess_ms.rest",
    "between_steps_ms.train", "slow_steps.train",
    "hbm_headroom_min_pct.train", "moe_gmm_ms", "moe_tgmm_ms",
    "moe_gmm_roofline", "flash_fwd_calls_per_need", "flash_bwd_ms",
    "moe_held_rows_drift", "moe_token_sum_ms", "moe_tile_fill_pct",
    "moe_load_skew", "moe_step_rows_spread_pct"]
# Readers that would compute something WRONG on this cell, or find nothing
# to read: one width of `hidden_size // num_heads` = 128 (no head of this
# model's attention), a dense model's 6 N, kernels this model does not call,
# another family's scopes. (Which further metrics name the cell is a later
# PR's to say: this file holds membership and never a list's end or its
# whole.)
NOT_THIS_CELLS = ["flash_roofline", "mfu_pct.train", "flash_fwd_roofline",
                  "flash_bwd_roofline", "flash_mla_fwd_roofline",
                  "flash_mla_bwd_roofline", "flash_mla_fwd_calls_per_need",
                  "moe_gmm_ungated_roofline",
                  "flash_d128_fwd_roofline", "flash_d128_bwd_roofline",
                  "ssd_scan_ms", "mamba_mixer_ms", "ssd_fwd_ms", "ssd_bwd_ms",
                  "ssd_fwd_roofline", "ssd_bwd_roofline"]


@pytest.mark.parametrize("metric",
                         NEW_METRICS + THIS_CELLS_TOO + NOT_THIS_CELLS)
def test_which_metrics_name_the_cell(metric):
    (entry,) = [m for m in MANIFEST["per_layer"] if m["name"] == metric]
    if metric in NOT_THIS_CELLS:
        assert CELL["name"] not in entry["workloads"]
        return
    assert CELL["name"] in entry["workloads"]
    if metric in NEW_METRICS:
        assert entry["moves"] == "train_tokens_per_s"
        spec = json.loads((ROOT / "benchmarks" / "layer_metrics"
                           / f"{metric}.json").read_text())
        # An ACCEPTED reader: this PR brings no reader code.
        assert spec["reader"] in ("scope_ms_per_step",
                                  "flash_geometry_roofline_pct")


def test_the_manifest_lists_every_per_layer_metric_the_cell_reports():
    named = {m["name"] for m in MANIFEST["per_layer"]
             if CELL["name"] in m.get("workloads", [])}
    assert set(NEW_METRICS + THIS_CELLS_TOO) <= named
    assert not named & set(NOT_THIS_CELLS)
    (rate,) = [m for m in MANIFEST["end_to_end"]
               if m["name"] == "train_tokens_per_s"]
    assert CELL["name"] in rate["workloads"]
    # The accepted cells are still where they were.
    for cell in ("gpt3-2.7b.steady", "lfm2-24b-a2b.steady",
                 "moonlight-16b-a3b.steady",
                 "nemotron-3-nano-30b-a3b.steady"):
        assert cell in rate["workloads"]
        assert cell in {w["name"] for w in MANIFEST["workloads"]}


def test_this_pr_brings_data_and_a_runner_and_no_reader():
    """New under `benchmarks/`: the configuration, the cell, four metric
    files over accepted readers, the reference, the runner, the control
    and a README; no file under `readers/` and no `flops_*.py` (no kernel
    was written)."""
    bench = ROOT / "benchmarks"
    assert (bench / "runners" / "train_qwen3_next.py").exists()
    assert (bench / "reference" / "qwen3_next.py").exists()
    assert (bench / "control_qwen3_next.py").exists()
    assert (bench / "README-qwen3_next.md").exists()
    assert not list(bench.glob("flops_gdn*")) and not list(
        (bench / "readers").glob("*gdn*"))
    source = (bench / "runners" / "train_qwen3_next.py").read_text()
    for name in ("build_engine", "probe_held_rows", "step_gradients",
                 "backward_scopes"):
        assert f"def {name}" not in source and name in source, name
    for name in ("install_weights", "measure", "checks_from"):
        assert f"base.{name}" in source, name


# --------------------------------------------------------------------- #
# the accepted readers on this cell's geometry, by hand                  #
# --------------------------------------------------------------------- #

def test_flash_geometry_reader_at_sixteen_heads_of_256():
    from benchmarks.readers import flash_geometry_roofline_pct as reader

    spec = json.loads((ROOT / "benchmarks" / "layer_metrics"
                       / "flash_d256_fwd_roofline.json").read_text())
    trace = {"time_by_name": {
        "%flash_fwd.1 = bf16[16,4096,256] custom-call": [0.5, 176],
        "%flash_bwd_dq.1 = bf16[16,4096,256] custom-call": [9.0, 176]}}
    data = {"trace": trace, "device": {"kind": "TPU v5 lite"},
            "config": CONFIG,
            "train": {"microbatch_size": 1, "seq_len": 4096,
                      "microbatches_run": 176, "num_layers": 1}}
    ops, nbytes = flops.causal_attention_fwd(1, 16, 4096, 256)
    least = flops.roofline_seconds(ops, nbytes, "TPU v5 lite")[0]
    assert reader.read(data, **spec["args"]) == pytest.approx(
        100.0 * least * 176 / 0.5)
    # 16 heads of 256, not hidden_size // num_heads = 128: twice the work.
    half, _ = flops.causal_attention_fwd(1, 16, 4096, 128)
    assert ops == 2 * half
    assert 0 < reader.read(data, **spec["args"]) < 100


def test_scope_reader_tells_the_rule_from_the_mixer():
    from benchmarks.readers import scope_ms_per_step as reader

    text = """
ENTRY %main.9 () -> f32[] {
  %fusion.1 = f32[8]{0} fusion(%x), kind=kLoop, calls=%f1, metadata={op_name="jit(bwd)/jvp(gdn_mixer)/gdn/mul"}
  %while.2 = (s32[], f32[8]{0}) while(%t), condition=%c, body=%b, metadata={op_name="jit(bwd)/transpose(jvp())/checkpoint/rematted_computation/gdn_mixer/gdn/while"}
  %fusion.3 = f32[8,16]{1,0} fusion(%y), kind=kOutput, calls=%f3, metadata={op_name="jit(bwd)/transpose(jvp())/checkpoint/gdn_mixer/dot_general"}
  %fusion.4 = f32[8]{0} fusion(%z), kind=kLoop, calls=%f4, metadata={op_name="jit(bwd)/jvp(gated_attn)/mul"}
  %fusion.5 = f32[8]{0} fusion(%z), kind=kLoop, calls=%f5, metadata={op_name="jit(bwd)/jvp(mlp)/gdnx/mul"}
}
"""
    ms = 1e6
    ops = [["%fusion.1 f32[8] fusion", 0 * ms, 2 * ms, {}],
           ["%while.2 (s32[] while", 3 * ms, 4 * ms, {}],
           ["%fusion.3 f32[8,16] fusion", 8 * ms, 5 * ms, {}],
           ["%fusion.4 f32[8] fusion", 14 * ms, 7 * ms, {}],
           ["%fusion.5 f32[8] fusion", 22 * ms, 1 * ms, {}]]
    data = {"trace_detail": {"ops": ops, "host": {},
                             "modules": [["jit_bwd", 0.0, 30 * ms]]},
            "scopes": {"jit_bwd": reader.scopes_of_text(text)},
            "cell": {"traffic": {"global_batch": 8, "microbatch_size": 1}},
            "train": {"microbatches_run": 16}}                 # 2 steps
    args = lambda name: json.loads(
        (ROOT / "benchmarks" / "layer_metrics" / f"{name}.json").read_text()
    )["args"]
    assert reader.read(data, **args("gdn_rule_ms")) == pytest.approx(3.0)
    assert reader.read(data, **args("gdn_mixer_ms")) == pytest.approx(5.5)
    assert reader.read(dict(data, scopes=None), **args("gdn_rule_ms")) is None


def test_routed_roofline_reads_this_cell_s_sizes_from_the_configuration():
    """`readers/moe_gmm_roofline_pct.py` takes hidden 2048, expert width
    512 and the 16 held experts from the configuration: SwiGLU's 3 + 6
    products."""
    rows, hidden, inter, held = 1280.0, 2048, 512, 16
    assert (CONFIG["hidden_size"], CONFIG["moe_intermediate_size"],
            CONFIG["num_experts_held"]) == (hidden, inter, held)
    ops, nbytes = flops_moe.grouped_product(rows, hidden, inter, held)
    assert ops == 2 * rows * hidden * inter
    one, bound = flops.roofline_seconds(ops, nbytes, "TPU v5 lite")
    assert bound == "memory"        # 80 rows an expert: the matrices' bytes
    # A dW product: its two row operands and the float32 sum of 16 matrices
    # of 2048 x 512 read and written, 134.2 MB beside the rows' 6.6.
    dw = (rows * (hidden + inter) * 2 + held * hidden * inter * 8) / 819e9
    assert dw == pytest.approx(0.1719e-3, rel=1e-3)
    assert flops_moe.routed_layer_train_seconds(
        rows, hidden, inter, held, "TPU v5 lite") == pytest.approx(
        6 * one + 3 * dw)


# --------------------------------------------------------------------- #
# the reference                                                          #
# --------------------------------------------------------------------- #

@pytest.fixture(scope="module")
def tiny():
    rc = ref.RefConfig.from_config(TINY)
    params = ref.init_params(SEED, rc)
    tokens = jax.random.randint(jax.random.PRNGKey(4), (2, 64), 0,
                                rc.vocab_size)
    return rc, params, tokens


@pytest.fixture(scope="module")
def run(tiny):
    rc, params, tokens = tiny
    return jax.jit(lambda p: ref.loss_and_grads(p, tokens, rc))(params)


def test_reference_imports_nothing_of_the_program():
    source = (ROOT / "benchmarks" / "reference" / "qwen3_next.py").read_text()
    assert "import oobleck_tpu" not in source
    assert "from oobleck_tpu" not in source


def _rule_args(length, g=2, r=2, dk=8, dv=4, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    return (ref._unit(jax.random.normal(k[0], (1, length, g, dk))) * dk ** -0.5,
            ref._unit(jax.random.normal(k[1], (1, length, g, dk))),
            jax.random.normal(k[2], (1, length, g, r, dv)),
            -jax.nn.softplus(jax.random.normal(k[3], (1, length, g, r))),
            jax.nn.sigmoid(jax.random.normal(k[4], (1, length, g, r))))


def test_reference_walks_the_recurrence_and_chunks_nothing():
    """One position after another: a scan whose carry is the state and
    whose step reads one position; no [Q, Q] block and no inverse."""
    args = _rule_args(12)
    jaxpr = jax.make_jaxpr(lambda *a: ref.recurrence(*a, "highest"))(*args)
    (outer,) = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "scan"]
    assert outer.params["length"] == 1            # one block of 12 positions
    assert [v for v in outer.invars if v.aval.shape == (1, 2, 2, 8, 4)], (
        "the carry is the state [B, G, R, dk, dv]")
    text = str(jaxpr)
    assert "triangular_solve" not in text and "cumsum" not in text
    # The delta rule: after position t wrote at beta = 1 without decay,
    # the state answers k_t with v_t.
    q, k, v, g, beta = args
    o = ref.recurrence(k, k, v, jnp.zeros_like(g), jnp.ones_like(beta),
                       "highest")
    np.testing.assert_allclose(np.asarray(o), np.asarray(v), atol=1e-5)


@pytest.mark.parametrize("length,block", [(256, 128), (96, 32), (50, 128)],
                         ids=["two_blocks", "three_blocks", "no_multiple"])
def test_recurrence_over_blocks_is_the_recurrence_whole(monkeypatch, length,
                                                        block):
    """`SCAN_BLOCK` is for the gradient's memory and changes no value."""
    args = _rule_args(length, seed=2)
    f = lambda *a: jnp.sum(jnp.sin(ref.recurrence(*a, "highest")))
    monkeypatch.setattr(ref, "SCAN_BLOCK", block)
    blocked = jax.value_and_grad(f, argnums=(0, 1, 2, 3, 4))(*args)
    monkeypatch.setattr(ref, "SCAN_BLOCK", length)
    whole = jax.value_and_grad(f, argnums=(0, 1, 2, 3, 4))(*args)
    for got, want in zip(jax.tree.leaves(blocked), jax.tree.leaves(whole)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5, rtol=1e-5)


def test_the_program_s_chunked_rule_is_the_reference_s_recurrence():
    """What `correct` leans on, at a size a test can hold: the program's
    chunks and inverse against the reference's position-by-position walk."""
    from oobleck_tpu.ops.gdn import gated_delta_rule

    q, k, v, g, beta = _rule_args(100, seed=3)
    want = ref.recurrence(q, k, v, g, beta, "highest")
    flat = lambda t: t.reshape(*t.shape[:2], -1, *t.shape[4:])
    got = gated_delta_rule(q, k, flat(v), flat(g), flat(beta), chunk=32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(flat(want)),
                               atol=2e-5)


def test_forcing_the_references_own_choice_changes_nothing(tiny, run):
    rc, params, tokens = tiny
    (loss, own), grads = run
    assert len(own) == rc.num_layers == 4         # every layer is routed
    (forced_loss, _), forced = ref.loss_and_grads(params, tokens, rc,
                                                  "highest", own)
    assert float(forced_loss) == pytest.approx(float(loss), rel=1e-6)
    for a, b in zip(jax.tree.leaves(forced), jax.tree.leaves(grads)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)
    assert float(ref.mismatch_share(own, own)) == 0.0


def test_forced_routing_is_used_and_mismatches_are_counted(tiny, run):
    rc, params, tokens = tiny
    (loss, own), _ = run
    other = [(c + 1) % rc.num_experts for c in own]
    (moved, again), _ = ref.loss_and_grads(params, tokens, rc, "highest",
                                           other)
    assert float(moved) != float(loss)
    # What the reference WOULD choose still comes from its own scores
    # where nothing upstream changed: the first layer's.
    np.testing.assert_array_equal(np.asarray(again[0]), np.asarray(own[0]))
    assert float(ref.mismatch_share(other, own)) == 1.0


def test_the_gated_shared_expert_is_on_every_token_whatever_is_held(tiny):
    rc, params, _ = tiny
    p = params["blocks"][0]["ff"]
    h = jax.random.normal(jax.random.PRNGKey(6), (1, 8, rc.hidden_size))
    nowhere = jnp.full((1, 8, rc.num_experts_per_tok), rc.num_experts - 1)
    out, own = ref._experts(p, h, rc, "highest", nowhere)  # none held: 0-3
    s = p["shared"]
    gate = jax.nn.sigmoid(h @ s["w_g"])[..., None]
    np.testing.assert_allclose(
        np.asarray(out),
        np.asarray(gate * ref._swiglu(s["w1"], s["w3"], s["w2"], h,
                                      "highest")), atol=1e-7)
    assert 0.0 < float(gate.min()) and float(gate.max()) < 1.0
    # The router is a softmax over ALL the experts: the weights of the
    # reference's own ten sum to one, with no bias and no scaling factor.
    assert own.shape == (1, 8, rc.num_experts_per_tok)
    assert "expert_bias" not in p


def test_the_vocabulary_is_padded_as_the_program_pads_it(tiny):
    """100 rows held, 128 built; the padded logits are left out of the
    loss and their gradients are zero."""
    rc, params, tokens = tiny
    assert (rc.vocab_size, rc.padded_vocab_size) == (100, 128)
    assert params["embed"]["wte"].shape == (128, 64)
    assert params["head"]["w"].shape == (64, 128)
    logits, _ = ref.forward(params, tokens, rc)
    assert logits.shape == (2, 64, 100)
    _, grads = jax.jit(lambda p: ref.loss_and_grads(p, tokens, rc))(params)
    assert not np.asarray(grads["head"]["w"][:, 100:]).any()
    assert not np.asarray(grads["embed"]["wte"][100:]).any()
    assert np.asarray(grads["head"]["w"][:, :100]).any()


@pytest.mark.parametrize("mode,low,high", [("bfloat16", 5e-4, 0.03),
                                           ("fp8", 0.03, 1.0)])
def test_control_readings_at_a_size_a_test_can_hold(mode, low, high):
    """`control_qwen3_next.reference_vs_reference`, the path that sets the
    limits, rehearsed in the stated precision and in the control's: the
    recurrence's three products are rounded with every other contraction."""
    from benchmarks import control_qwen3_next

    cell = {"traffic": {"seq_len": 64}}
    row = control_qwen3_next.reference_vs_reference(TINY, cell, SEED, mode)
    assert set(row) == {"loss_rel_err", "grad_rel_err",
                        "routing_mismatch_share", "grad_rel_err_free"}
    assert low < row["grad_rel_err"] < high
    assert 0 <= row["routing_mismatch_share"] < 0.9


# --------------------------------------------------------------------- #
# the runner, rehearsed                                                  #
# --------------------------------------------------------------------- #

def test_runner_control_flow_on_the_cpu(tmp_path, monkeypatch, capsys):
    from benchmarks import run as harness
    from benchmarks.runners import train_qwen3_next
    from oobleck_tpu.utils import metrics

    monkeypatch.setenv("OOBLECK_TPU_CACHE", str(tmp_path / "profiles"))
    cell = {"name": "tiny.steady", "config": "tiny", "chips": 1,
            "kind": "train_qwen3_next",
            "traffic": {"seq_len": 64, "microbatch_size": 1,
                        "global_batch": 2, "warmup_steps": 1,
                        "learning_rate": 1e-3, "lr_warmup_steps": 2000},
            "correct": {"grad_rel_err": 0.2, "routing_mismatch_share": 0.5}}
    ctx = harness.Context(cell, TINY, 2**31 + 11, 1.0, False,
                          {"platform": "cpu", "kind": "cpu", "count": 1})
    built, build = [], train_qwen3_next.build_engine
    monkeypatch.setattr(train_qwen3_next, "build_engine",
                        lambda *a: built.append(build(*a)) or built[-1])
    out = train_qwen3_next.run(ctx)
    assert ctx.setup_s is not None
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert [c["check"] for c in out["checks"]] == [
        "grad_rel_err", "routing_mismatch_share"]
    assert all(c["ok"] for c in out["checks"]), out["checks"]
    assert out["end_to_end"]["train_tokens_per_s"] > 0
    assert out["layer_data"]["scopes"] is None           # no traced run
    # The job's own sequence length; ONE of the four layers is attention.
    train = out["layer_data"]["train"]
    assert (train["seq_len"], train["num_layers"], train["num_heads"],
            train["hidden_size"]) == (64, 1, 4, 64)
    assert train["microbatches_run"] == 2 * out["attempted"]
    assert train["n_params"] == ref.RefConfig.from_config(TINY).num_params()
    # Both readings of the gauge, a layer each: every layer is routed.
    rows = out["layer_data"]["held_rows"]
    assert sorted(rows["before"]) == sorted(rows["after"]) == [
        "0", "1", "2", "3"]
    assert all(0 < v <= 64 * 4 for v in rows["before"].values())
    # The second probe ran the program the first compiled: nothing
    # compiles after the window.
    said = [json.loads(line) for line in capsys.readouterr().out.splitlines()
            if line.startswith("{")]
    (held,) = [o for o in said if o["observation"] == "held_rows"]
    assert held["probe_programs"] == 1 and held["before"] == rows["before"]
    (phases,) = [o for o in said if o["observation"] == "setup_phases"]
    assert {"build_engine_s", "weights_s", "check_s", "warm_up_s"} <= set(
        phases)
    # Beside the one norm over everything: the worst of the Gated DeltaNet
    # layers' small leaves, named, printed and not limited.
    (check,) = [o for o in said if o["observation"] == "train_check"]
    assert 0 < check["gdn_leaf_rel_err_max"] < 0.3
    block, part, leaf = check["gdn_leaf_rel_err_at"].rsplit(".", 2)
    assert block in ("blocks.0", "blocks.1", "blocks.2")
    assert (part in ("ln_op", "ln_ff") and leaf == "scale") or (
        part == "gdn" and leaf in ("conv_taps", "dt_bias", "A_log", "norm"))
    # What a traced run hands the scope reader: the backward program's
    # instructions by the scope they were built under, the rule's among
    # them inside the mixer's.
    table = train_qwen3_next.backward_scopes(built[0])["jit_bwd"]
    rule = [v for v in table.values() if "/gdn/" in v]
    assert rule and all("gdn_mixer" in v for v in rule)
    assert len(rule) < sum("gdn_mixer" in v for v in table.values()) < len(
        table)
    assert any("gated_attn" in v for v in table.values())
    # The program's counters: three rules a traced program, softmax-routed
    # calls, and on the CPU no sum inside a kernel.
    reg = metrics.registry()
    assert reg.counter("oobleck_gdn_scans_total").value() >= 3
    assert reg.gauge("oobleck_gdn_chunks").value(layer="0") == 4
    assert reg.counter("oobleck_moe_softmax_routed_calls_total").value() >= 4
