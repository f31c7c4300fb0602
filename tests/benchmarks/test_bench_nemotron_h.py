"""The Nemotron cell's own pieces: the configuration file against the
catalog's config and the `assumed` words, `flops_moe_ungated.py` and the two
new readers on hand-made data, the reference's recurrence in blocks, forced
routing and the shared expert, and the runner's and the control's flow
rehearsed on the CPU at `nemotron-h-tiny` sizes (never a number)."""

import importlib
import json
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks import flops, flops_moe, flops_moe_ungated
from benchmarks.reference import nemotron_h as ref

ROOT = Path(__file__).resolve().parents[2]
NAME = "nemotron-3-nano-30b-a3b"
CONFIG = json.loads(
    (ROOT / "benchmarks" / "configs" / f"{NAME}.json").read_text())
CELL = json.loads((ROOT / "benchmarks" / "workloads"
                   / f"{NAME}.steady.json").read_text())
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"


@pytest.fixture(autouse=True, scope="module")
def _leave_no_series_behind():
    """The routing probe's counters live in the PROCESS-GLOBAL registry and
    the routed readers take every series they find there: a later module on
    this worker must not read this one's layers."""
    yield
    from oobleck_tpu.utils import metrics

    metrics.registry().clear()

# The catalog's `config` of NVIDIA-Nemotron-3-Nano-30B-A3B-BF16, as the
# driver drew it.
PUBLISHED = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
    "expand": 2, "head_dim": 128, "hidden_size": 2688,
    "hybrid_override_pattern": PATTERN, "intermediate_size": 1856,
    "layer_norm_epsilon": 1e-05, "mamba_head_dim": 64,
    "mamba_hidden_act": "silu", "mamba_num_heads": 64,
    "mamba_proj_bias": False, "max_position_embeddings": 262144,
    "mlp_bias": False, "mlp_hidden_act": "relu2",
    "model_type": "nemotron_h", "moe_intermediate_size": 1856,
    "moe_shared_expert_intermediate_size": 3712, "n_group": 1,
    "n_groups": 8, "n_routed_experts": 128, "n_shared_experts": 1,
    "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 6, "num_hidden_layers": 52,
    "num_key_value_heads": 2, "num_logits_to_keep": 1,
    "partial_rotary_factor": 1, "rescale_prenorm_residual": True,
    "residual_in_fp32": False, "rope_theta": 10000,
    "routed_scaling_factor": 2.5, "sliding_window": None,
    "ssm_state_size": 128, "tie_word_embeddings": False,
    "time_step_floor": 0.0001, "time_step_max": 0.1,
    "time_step_min": 0.001, "topk_group": 1, "use_bias": False,
    "use_conv_bias": True, "use_mamba_kernels": True, "vocab_size": 131072}

TINY = {
    "name": "tiny", "model_name": "nemotron-h-tiny",
    "model_args": {"num_experts_held": 2, "expert_offset": 0,
                   "vocab_rows_held": 128},
    "vocab_size": 256, "vocab_rows_held": 128, "hidden_size": 64,
    "num_hidden_layers": 5, "hybrid_override_pattern": "MEM*E",
    "mamba_num_heads": 4, "mamba_head_dim": 16, "ssm_state_size": 16,
    "n_groups": 2, "conv_kernel": 4, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "moe_intermediate_size": 40,
    "moe_shared_expert_intermediate_size": 80, "n_routed_experts": 8,
    "num_experts_per_tok": 3, "num_experts_held": 2,
    "routed_scaling_factor": 2.5, "norm_eps": 1e-5, "time_step_min": 0.001,
    "time_step_max": 0.1, "time_step_floor": 1e-4, "mlp_hidden_act": "relu2",
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
                    if r["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16"]
        assert entry["config"] == PUBLISHED
        assert entry["source_url"] == CONFIG["source"]
    for key, value in PUBLISHED.items():
        if key in CONFIG["reduced"]:
            assert CONFIG["source_values"][key] == value, key
        else:
            assert CONFIG[key] == value, key
    assert CONFIG["reduced"] == [
        "num_layers", "num_hidden_layers", "hybrid_override_pattern",
        "num_experts_held", "vocab_rows_held"]
    assert CONFIG["source_values"] == {
        "num_layers": 52, "num_hidden_layers": 52,
        "hybrid_override_pattern": PATTERN, "num_experts_held": 128,
        "vocab_rows_held": 131072}
    assert (CONFIG["num_layers"], CONFIG["num_hidden_layers"],
            CONFIG["hybrid_override_pattern"], CONFIG["num_experts_held"],
            CONFIG["vocab_rows_held"]) == (7, 7, "MEMEM*E", 128 // 16,
                                           131072 // 8)
    # The unit is a verbatim substring of the published pattern, the one
    # that repeats: layers 0-34 are five of it.
    assert PATTERN.startswith("MEMEM*E" * 5)
    assert CONFIG["head_dim"] == 128 and CONFIG["num_heads"] == 32
    assert "sixteen" in CONFIG["deployment"].lower()
    assert CONFIG["execution"] == {"precision": "bfloat16", "remat": True}
    (entry,) = [c for c in MANIFEST["configs"] if c["name"] == CONFIG["name"]]
    assert entry["reduced"] == CONFIG["reduced"]
    for words in ("1 layer in 7", "1 in 8.7", "667.0 M", "528,093,120"):
        assert words in CONFIG["reduced_why"], words


@pytest.mark.parametrize("key,words", [
    ("positional_term", "no rotary or other positional term in the "
     "attention layers: the family's public modelling code builds its "
     "attention without one, and the config's `rope_theta` 10000 and "
     "`partial_rotary_factor` 1 are carried in the file and unused"),
    ("time_step_limit", "is not clamped after the softplus (the family's "
     "`time_step_limit` default, 0 to infinity)"),
    ("gated_norm", "gate before norm, group size 4096 / 8"),
    ("initializer", "initialisers: `A_log = log a`, `a` uniform in [1, 16]; "
     "`D = 1`; `dt_bias` the inverse softplus of a step drawn log-uniformly "
     "in [`time_step_min` 0.001, `time_step_max` 0.1] and floored at "
     "`time_step_floor` 1e-4; conv taps and bias uniform in ± 1/√4; every "
     "other matrix as `lfm2-24b-a2b`'s (normal 0.02, outputs into the "
     "residual stream 0.02 / √(2 × layers as run))"),
    ("auxiliary_loss", "no auxiliary or balance loss"),
    ("selection_bias", "the selection bias takes no gradient and has no "
     "update rule here (`frozen_param_names`), and the seeded weights carry "
     "the bias that balances the seed's router on uniform ids, as "
     "`reference/lfm2.py::_balance` does"),
    ("normaliser_epsilon", "the weight normaliser's epsilon is `route`'s "
     "1e-6 where the family's code has 1e-20"),
    ("weight_decay", "AdamW's weight decay covers every trained leaf, "
     "`A_log`, `D`, `dt_bias` and the norms included, as the engine's "
     "optimizer does for every model (the family's recipe exempts them)"),
], ids=lambda x: x if "_" in x and " " not in x else "words")
def test_what_the_config_is_silent_on_is_stated(key, words):
    """ISSUE 37's eight items, in its words, in the file; and numbered in
    the reference's docstring."""
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
    assert rc.num_params() == sum(
        int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert rc.num_params() == 528_093_120               # ISSUE 37: 528.1 M
    parts = [sum(rc.block_params(b).values()) for b in range(7)]
    assert [round(p / 1e6, 3) for p in parts] == [
        38.745, 100.125, 38.745, 100.125, 38.745, 23.399, 100.125]
    m = rc.block_params(0)
    assert (m["w_in"], m["w_out"], m["conv"]) == (27_697_152, 11_010_048,
                                                  30_720)
    assert (c.data_vocab_size, c.experts_held, c.expert_offset) == (
        rc.vocab_size, rc.num_experts_held, rc.expert_offset) == (16384, 8, 0)
    assert c.hybrid_override_pattern == rc.pattern == "MEMEM*E"
    assert c.moe_shared_expert_intermediate_size == rc.shared_intermediate_size
    for key in ("hidden_size", "num_layers", "mamba_num_heads",
                "mamba_head_dim", "ssm_state_size", "n_groups", "conv_kernel",
                "num_heads", "num_kv_heads", "head_dim",
                "moe_intermediate_size", "num_experts", "num_experts_per_tok",
                "routed_scaling_factor", "norm_eps", "time_step_min",
                "time_step_max", "time_step_floor", "initializer_range",
                "expert_bias_range"):
        assert getattr(c, key) == getattr(rc, key), key
    assert c.chunk_size == CONFIG["chunk_size"] == 128
    assert CELL["traffic"]["seq_len"] <= c.max_position_embeddings


def test_cell_is_the_traffic_the_issue_gives():
    """ISSUE 37's traffic: `moonlight-16b-a3b.steady`'s to the number, so
    the two cells differ in the model alone."""
    t = CELL["traffic"]
    assert t == {"seq_len": 4096, "microbatch_size": 1, "global_batch": 8,
                 "warmup_steps": 2, "learning_rate": 0.00016,
                 "lr_warmup_steps": 2000}
    moonlight = json.loads((ROOT / "benchmarks" / "workloads"
                            / "moonlight-16b-a3b.steady.json").read_text())
    assert t == moonlight["traffic"]
    for words in ("lr 2e-7..3e-6", "~192 rows", "3,072", "16 x share",
                  "1 in 7 vs 8.7"):
        assert words in CELL["why"], words
    (entry,) = [w for w in MANIFEST["workloads"] if w["name"] == CELL["name"]]
    assert entry["why"] == CELL["why"] and entry["chips"] == 1
    assert len(CELL["why"]) <= 200
    assert sorted(CELL["correct"]) == ["grad_rel_err",
                                       "routing_mismatch_share"]
    assert "TO BE SET" not in CELL["correct_why"]
    assert "2000" in CELL["correct_why"]


NEW_METRICS = ["flash_d128_fwd_roofline", "flash_d128_bwd_roofline",
               "moe_gmm_ungated_roofline", "ssd_scan_ms", "mamba_mixer_ms"]
# PR 57's, over the scan's two kernels (PR 54): data files, one reader.
SSD_METRICS = ["ssd_fwd_ms", "ssd_bwd_ms", "ssd_fwd_roofline",
               "ssd_bwd_roofline"]
THIS_CELLS_TOO = [
    "dispatch_stall_ms.train", "input_wait_ms.train", "step_ms.train",
    "step_ms_p50.train", "step_ms_max.train", "host_dispatch_ms.train",
    "device_ms_per_step.bwd", "device_ms_per_step.optimizer",
    "device_ms_per_step.grad_zero", "idle_ms_per_step.in_step",
    "idle_ms_per_step.between_steps", "setup_engine_build_s",
    "setup_executables_s", "moe_gmm_ms", "moe_tgmm_ms",
    "flash_fwd_calls_per_need", "flash_bwd_ms", "moe_held_rows_drift",
    "moe_token_sum_ms", "moe_tile_fill_pct", "moe_load_skew",
    "moe_step_rows_spread_pct"] + SSD_METRICS
# Readers that would compute something WRONG on this cell: one width of
# `hidden_size // num_heads` = 84 (no head of this model), 3 + 6 expert
# products where these experts have 2 + 4, a dense model's 6 N, kernels
# this model does not call. (Which further metrics name the cell, and
# which cells the lists above name besides, is a later PR's to say: this
# file holds membership and never a list's end or its whole.)
NOT_THIS_CELLS = ["flash_roofline", "mfu_pct.train", "flash_fwd_roofline",
                  "flash_bwd_roofline", "moe_gmm_roofline",
                  "flash_mla_fwd_roofline", "flash_mla_bwd_roofline",
                  "flash_mla_fwd_calls_per_need"]


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
        assert (ROOT / "benchmarks" / "layer_metrics"
                / f"{metric}.json").exists()


def test_the_manifest_lists_every_per_layer_metric_the_cell_reports():
    named = {m["name"] for m in MANIFEST["per_layer"]
             if CELL["name"] in m.get("workloads", [])}
    assert set(NEW_METRICS + THIS_CELLS_TOO) <= named
    (rate,) = [m for m in MANIFEST["end_to_end"]
               if m["name"] == "train_tokens_per_s"]
    assert CELL["name"] in rate["workloads"]


# --------------------------------------------------------------------- #
# flops_moe_ungated and the two readers                                  #
# --------------------------------------------------------------------- #

def test_ungated_experts_count_two_and_four_products():
    assert (flops_moe_ungated.PRODUCTS_FORWARD,
            flops_moe_ungated.PRODUCTS_BACKWARD) == (2, 4)
    rows, hidden, inter, held = 1536.0, 2688, 1856, 8
    ops, nbytes = flops_moe.grouped_product(rows, hidden, inter, held)
    assert ops == 2 * rows * 2688 * 1856                 # 1856, not 1920
    assert nbytes == 2 * (rows * (2688 + 1856) + 8 * 2688 * 1856)
    one, bound = flops.roofline_seconds(ops, nbytes, "TPU v5 lite")
    assert bound == "memory"        # 192 rows an expert: the matrices' bytes
    # A dW product (2 of the 6): its two row operands and the float32 sum
    # of 8 matrices of 2688 x 1856 read and written (PR 42), 319.3 MB.
    ops_dw, nbytes_dw = flops_moe.grouped_product_dw(rows, hidden, inter, held)
    assert ops_dw == ops
    assert nbytes_dw == 2 * rows * (2688 + 1856) + 8 * (8 * 2688 * 1856)
    dw = nbytes_dw / 819e9
    assert dw == pytest.approx(0.4069e-3, rel=1e-3) and dw > ops / 197e12
    assert flops_moe_ungated.routed_layer_train_seconds(
        rows, hidden, inter, held, "TPU v5 lite") == pytest.approx(
        4 * one + 2 * dw)
    # Two thirds of what the same widths would need under SwiGLU.
    assert flops_moe.routed_layer_train_seconds(
        rows, hidden, inter, held, "TPU v5 lite") == pytest.approx(
        6 * one + 3 * dw)


def test_ungated_roofline_reader_by_hand():
    from benchmarks.readers import moe_gmm_ungated_roofline_pct as reader
    from oobleck_tpu.utils import metrics

    args = {"match": ["%moe_gmm.", "%moe_tgmm."]}
    trace = {"time_by_name": {
        "%moe_gmm.3 = bf16[4608,1856] custom-call": [0.4, 1800],
        "%moe_tgmm.1 = f32[8,2688,1856] custom-call": [0.2, 600],
        "%flash_fwd.2 = bf16[32,4096,128] custom-call": [9.0, 100]}}
    data = {"trace": trace, "device": {"kind": "TPU v5 lite"},
            "config": CONFIG,
            "train": {"microbatch_size": 1, "seq_len": 4096,
                      "microbatches_run": 100}}
    reg = metrics.registry()
    pairs = reg.counter("oobleck_moe_routed_pairs_total")
    probed = reg.counter("oobleck_moe_probed_tokens_total")
    pairs.inc(1500, layer="1")
    probed.inc(4096)
    from benchmarks.readers.moe_gmm_roofline_pct import (
        _pairs_per_token_by_layer,
    )

    shares = _pairs_per_token_by_layer()
    least = sum(flops_moe_ungated.routed_layer_train_seconds(
        s * 4096, 2688, 1856, 8, "TPU v5 lite") for s in shares)
    assert reader.read(data, **args) == pytest.approx(
        100 * least * 100 / 0.6)
    # Experts with a gate (every other cell), a trace without the kernels
    # (the parent), no data: nothing to read, no error.
    gated = dict(CONFIG, mlp_hidden_act="silu")
    assert reader.read(dict(data, config=gated), **args) is None
    assert reader.read(dict(data, config={"hidden_size": 2048}),
                       **args) is None
    assert reader.read(dict(data, trace={"time_by_name": {}}), **args) is None
    assert reader.read({}, **args) is None


def test_flash_geometry_reader_takes_heads_from_the_configuration():
    from benchmarks.readers import flash_geometry_roofline_pct as reader
    from benchmarks.readers import kernel_roofline_pct as one_width

    trace = {"time_by_name": {
        "%flash_fwd.2 = bf16[32,4096,128] custom-call": [0.05, 100],
        "%flash_bwd_dq.3 = bf16[32,4096,128] custom-call": [0.06, 100],
        "%flash_bwd_dkv.1 = bf16[32,4096,128] custom-call": [0.09, 100],
        "%moe_gmm.3 = bf16[4608,1856] custom-call": [9.0, 1800]}}
    data = {"trace": trace, "device": {"kind": "TPU v5 lite"},
            "config": CONFIG,
            "train": {"microbatch_size": 1, "seq_len": 4096,
                      "microbatches_run": 100, "num_layers": 1,
                      "num_heads": 32, "hidden_size": 2688}}
    fwd = {"match": "%flash_fwd.", "needed": ["causal_attention_fwd"]}
    bwd = {"match": "%flash_bwd_", "needed": ["causal_attention_bwd"]}
    ops, nbytes = flops.causal_attention_fwd(1, 32, 4096, 128)
    # ISSUE 37: 33.6 M forward operations a token in scores and values.
    assert round(ops / 4096 / 1e6, 1) == 33.6
    assert ops == 2 * (2.0 * 4096 * 4096 * 128) / 2 * 32
    least = flops.roofline_seconds(ops, nbytes, "TPU v5 lite")[0]
    assert reader.read(data, **fwd) == pytest.approx(100 * least * 100 / 0.05)
    ops_b, nbytes_b = flops.causal_attention_bwd(1, 32, 4096, 128)
    least_b = flops.roofline_seconds(ops_b, nbytes_b, "TPU v5 lite")[0]
    assert reader.read(data, **bwd) == pytest.approx(
        100 * least_b * 100 / 0.15)
    # The one-width reader would count a head of 2688 // 32 = 84.
    assert one_width.read(data, **fwd) == pytest.approx(
        reader.read(data, **fwd) * 84 / 128)
    # A configuration without the two keys, a trace without the kernels
    # (the parent), no data: nothing to read, no error.
    assert reader.read(dict(data, config={"hidden_size": 2688}),
                       **fwd) is None
    assert reader.read(dict(data, trace={"time_by_name": {}}), **fwd) is None
    assert reader.read({}, **fwd) is None


@pytest.mark.parametrize("metric", SSD_METRICS)
def test_the_scans_kernels_metrics_by_hand(metric):
    """PR 57's four over `%ssd_fwd.` / `%ssd_bwd.`: a call's mean time
    through the accepted `kernel_call_ms`, its share of the roofline through
    `readers/ssd_roofline_pct.py`, every width from the configuration and
    the `M` layers held from its pattern (3 of `MEMEM*E`)."""
    from benchmarks import flops_ssd

    spec = json.loads((ROOT / "benchmarks" / "layer_metrics"
                       / f"{metric}.json").read_text())
    (entry,) = [m for m in MANIFEST["per_layer"] if m["name"] == metric]
    assert (entry["layer"], entry["moves"]) == ("kernels",
                                                "train_tokens_per_s")
    reader = importlib.import_module(f"benchmarks.readers.{spec['reader']}")
    # 40 steps of 8 microbatches: 3 x 320 calls of each kernel, PR 54's
    # 0.3395 and 0.6948 ms a call; a routed kernel beside them.
    trace = {"time_by_name": {
        "%ssd_fwd.2 = bf16[1,4096,4096] custom-call": [960 * 0.3395e-3, 960],
        "%ssd_bwd.5 = bf16[1,4096,4096] custom-call": [960 * 0.6948e-3, 960],
        "%moe_gmm.3 = bf16[4608,1856] custom-call": [9.0, 1800]}}
    data = {"trace": trace, "device": {"kind": "TPU v5 lite"},
            "config": CONFIG,
            "train": {"microbatch_size": 1, "seq_len": 4096,
                      "microbatches_run": 320, "num_layers": 1}}
    got = reader.read(data, **spec.get("args", {}))
    if metric.endswith("_ms"):
        assert spec["reader"] == "kernel_call_ms"
        assert got == pytest.approx({"ssd_fwd_ms": 0.3395,
                                     "ssd_bwd_ms": 0.6948}[metric])
    else:
        assert spec["reader"] == "ssd_roofline_pct"
        fn = spec["args"]["needed"]
        ops, nbytes = getattr(flops_ssd, fn)(1, 4096, 64, 64, 8, 128, 128)
        least, bound = flops.roofline_seconds(ops, nbytes, "TPU v5 lite")
        assert bound == "memory"
        assert got == pytest.approx(
            100 * least / {"scan_fwd": 0.3395e-3, "scan_bwd": 0.6948e-3}[fn])
        assert got == pytest.approx({"scan_fwd": 55.06, "scan_bwd": 36.12}[fn],
                                    rel=1e-3)
        # A kernel called twice a layer and microbatch reads half.
        twice = {k: [2 * s, 2 * n] for k, (s, n) in
                 trace["time_by_name"].items()}
        assert reader.read(dict(data, trace={"time_by_name": twice}),
                           **spec["args"]) == pytest.approx(got / 2)
        # A configuration without the scan's widths or without an `M`
        # layer: nothing to read.
        for config in ({"hidden_size": 2688},
                       dict(CONFIG, hybrid_override_pattern="E*E")):
            assert reader.read(dict(data, config=config),
                               **spec["args"]) is None
    # A trace without the kernel (PR 54's parent), no data: nothing.
    assert reader.read(dict(data, trace={"time_by_name": {}}),
                       **spec.get("args", {})) is None
    assert reader.read({}, **spec.get("args", {})) is None


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_new_metric_files_say_what_they_count(metric):
    spec = json.loads((ROOT / "benchmarks" / "layer_metrics"
                       / f"{metric}.json").read_text())
    (entry,) = [m for m in MANIFEST["per_layer"] if m["name"] == metric]
    assert (entry["layer"], entry["unit"], entry["better"]) == (
        spec["layer"], spec["unit"], spec["better"])
    if metric.startswith("flash"):
        assert spec["reader"] == "flash_geometry_roofline_pct"
        assert "32 heads" in spec["what"] and "16 x repeat" in spec["what"]
    elif metric.startswith("moe"):
        assert spec["reader"] == "moe_gmm_ungated_roofline_pct"
        assert "2688 x 1856" in spec["what"]
    else:
        assert spec["reader"] == "scope_ms_per_step"
        assert spec["args"] == {
            "module": "jit_bwd",
            "scope": {"ssd_scan_ms": "ssd", "mamba_mixer_ms": "mamba"}[metric]}
        assert f"jax.named_scope('{spec['args']['scope']}')" in spec["what"]


HLO_TEXT = """\
HloModule jit_bwd, entry_computation_layout={()->f32[]}

%fused_computation.7 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  ROOT %multiply.3 = f32[8]{0} multiply(%p, %p), metadata={op_name="jit(bwd)/jvp(mamba)/ssd/mul" stack_frame_id=4}
}

ENTRY %main.9 () -> f32[] {
  %fusion.7 = f32[8]{0} fusion(%x), kind=kLoop, calls=%fused_computation.7, metadata={op_name="jit(bwd)/jvp(mamba)/ssd/mul" stack_frame_id=4}
  %while.2 = (s32[], f32[8]{0}) while(%t), condition=%c, body=%b, metadata={op_name="jit(bwd)/transpose(jvp(jvp()))/checkpoint/rematted_computation/mamba/ssd/while" stack_frame_id=5}
  %fusion.8 = f32[8]{0} fusion(%x), kind=kLoop, calls=%f8, metadata={op_name="jit(bwd)/transpose(jvp(jvp()))/checkpoint/mamba/ssd/while/body/add"}
  %fusion.9 = f32[8,16]{1,0} fusion(%y), kind=kOutput, calls=%f9, metadata={op_name="jit(bwd)/transpose(jvp(jvp()))/checkpoint/mamba/dot_general"}
  %fusion.10 = f32[8]{0} fusion(%z), kind=kLoop, calls=%f10, metadata={op_name="jit(bwd)/jvp(mlp)/routed_experts/mul"}
  %fusion.11 = f32[8]{0} fusion(%z), kind=kLoop, calls=%f11, metadata={op_name="jit(bwd)/jvp(ssdx)/mamba_like/mul"}
  %copy.4 = f32[8]{0} copy(%z)
  ROOT %add.1 = f32[] add(%a, %b), metadata={op_name="jit(bwd)/add"}
}
"""


def test_scopes_are_read_from_the_compiled_text():
    from benchmarks.readers import scope_ms_per_step as reader

    table = reader.scopes_of_text(HLO_TEXT)
    assert table["%fusion.7"] == "jit(bwd)/jvp(mamba)/ssd/mul"
    assert table["%multiply.3"] == table["%fusion.7"]
    assert table["%add.1"] == "jit(bwd)/add"           # a ROOT line too
    assert "%copy.4" not in table                      # says no scope
    assert len(table) == 8


def test_scope_reader_by_hand():
    """Busy time of the module's operations whose scope has the word as a
    path component, united (a `while` and its body count once), a step."""
    from benchmarks.readers import scope_ms_per_step as reader

    ms = 1e6
    ops = [  # name, start_ns, duration_ns, stats
        ["%fusion.7 f32[8] fusion", 0 * ms, 2 * ms, {}],
        ["%while.2 (s32[] while", 3 * ms, 4 * ms, {}],
        ["%fusion.8 f32[8] fusion", 4 * ms, 1 * ms, {}],      # inside it
        ["%fusion.9 f32[8,16] fusion", 8 * ms, 5 * ms, {}],   # mamba, no ssd
        ["%fusion.10 f32[8] fusion", 14 * ms, 7 * ms, {}],    # experts
        ["%fusion.11 f32[8] fusion", 22 * ms, 1 * ms, {}],    # look-alikes
        ["%copy.4 f32[8] copy", 24 * ms, 1 * ms, {}],         # no scope
        ["%fusion.7 f32[8] fusion", 40 * ms, 9 * ms, {}],     # another module
    ]
    detail = {"ops": ops, "host": {},
              "modules": [["jit_bwd", 0.0, 30 * ms],
                          ["jit_optimizer_update", 35 * ms, 20 * ms]]}
    data = {"trace_detail": detail,
            "scopes": {"jit_bwd": reader.scopes_of_text(HLO_TEXT)},
            "cell": {"traffic": {"global_batch": 8, "microbatch_size": 1}},
            "train": {"microbatches_run": 16}}                 # 2 steps
    ssd = {"module": "jit_bwd", "scope": "ssd"}
    mamba = {"module": "jit_bwd", "scope": "mamba"}
    assert reader.read(data, **ssd) == pytest.approx((2 + 4) / 2)
    assert reader.read(data, **mamba) == pytest.approx((2 + 4 + 5) / 2)
    # No table (every other cell, the parent), no such module, nothing
    # under the scope, no data: nothing to read, no error.
    assert reader.read(dict(data, scopes=None), **ssd) is None
    assert reader.read(dict(data, scopes={"jit_fwd": {}}), **ssd) is None
    assert reader.read(data, module="jit_bwd", scope="attention") is None
    assert reader.read({}, **ssd) is None


# --------------------------------------------------------------------- #
# the reference                                                          #
# --------------------------------------------------------------------- #

@pytest.fixture(scope="module")
def tiny():
    rc = ref.RefConfig.from_config(TINY)
    params = ref.init_params(SEED, rc, (2, 64))
    tokens = jax.random.randint(jax.random.PRNGKey(4), (2, 64), 0,
                                rc.vocab_size)
    return rc, params, tokens


@pytest.fixture(scope="module")
def run(tiny):
    rc, params, tokens = tiny
    return jax.jit(lambda p: ref.loss_and_grads(p, tokens, rc))(params)


def test_reference_imports_nothing_of_the_program():
    source = (ROOT / "benchmarks" / "reference" / "nemotron_h.py").read_text()
    assert "import oobleck_tpu" not in source
    assert "from oobleck_tpu" not in source


def test_reference_walks_the_recurrence_and_chunks_nothing():
    """One position after another: a scan whose carry is the state and
    whose step reads one position; no [Q, Q] block anywhere."""
    rc = ref.RefConfig.from_config(TINY)
    k = jax.random.split(jax.random.PRNGKey(0), 4)
    s, g, r, p, n = 12, 2, 2, 16, 16
    args = (jax.random.normal(k[0], (1, s, g, r, p)),
            jax.nn.softplus(jax.random.normal(k[1], (1, s, g, r))),
            -jnp.ones((g, r)), jax.random.normal(k[2], (1, s, g, n)),
            jax.random.normal(k[3], (1, s, g, n)), jnp.ones((g, r)))
    jaxpr = jax.make_jaxpr(lambda *a: ref.recurrence(*a, "highest"))(*args)
    (outer,) = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "scan"]
    assert outer.params["length"] == 1            # one block of 12 positions
    state = [v.aval.shape for v in outer.invars if v.aval.shape == (
        1, g, r, p, n)]
    assert state, "the carry is the state [B, G, R, P, N]"
    assert rc.conv_kernel == 4


@pytest.mark.parametrize("length,block", [(256, 128), (96, 32), (50, 128)],
                         ids=["two_blocks", "three_blocks", "no_multiple"])
def test_recurrence_over_blocks_is_the_recurrence_whole(monkeypatch, length,
                                                        block):
    """`SCAN_BLOCK` is for the gradient's memory and changes no value."""
    k = jax.random.split(jax.random.PRNGKey(2), 4)
    g, r, p, n = 2, 2, 4, 8
    args = (jax.random.normal(k[0], (1, length, g, r, p)),
            jax.nn.softplus(jax.random.normal(k[1], (1, length, g, r))),
            -jnp.exp(jax.random.normal(k[2], (g, r))),
            jax.random.normal(k[3], (1, length, g, n)),
            jax.random.normal(k[0], (1, length, g, n)), jnp.ones((g, r)))
    f = lambda *a: jnp.sum(jnp.sin(ref.recurrence(*a, "highest")))
    monkeypatch.setattr(ref, "SCAN_BLOCK", block)
    blocked = jax.value_and_grad(f, argnums=(0, 1, 2, 3, 4))(*args)
    monkeypatch.setattr(ref, "SCAN_BLOCK", length)
    whole = jax.value_and_grad(f, argnums=(0, 1, 2, 3, 4))(*args)
    for got, want in zip(jax.tree.leaves(blocked), jax.tree.leaves(whole)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5, rtol=1e-5)


def test_the_seeded_bias_balances_the_seeds_router(tiny):
    rc, params, tokens = tiny
    _, own = ref.forward(params, tokens, rc)
    for chosen in own:
        load = np.bincount(np.asarray(chosen).ravel(),
                           minlength=rc.num_experts)
        assert load.max() <= 1.6 * load.mean()


def test_forcing_the_references_own_choice_changes_nothing(tiny, run):
    rc, params, tokens = tiny
    (loss, own), grads = run
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
    # where nothing upstream changed: the first routed layer's.
    np.testing.assert_array_equal(np.asarray(again[0]), np.asarray(own[0]))
    assert float(ref.mismatch_share(other, own)) == 1.0


def test_the_shared_expert_is_on_every_token_whatever_is_held(tiny):
    rc, params, _ = tiny
    p = params["blocks"][rc.routed_blocks[0]]["ff"]
    h = jax.random.normal(jax.random.PRNGKey(6), (1, 8, rc.hidden_size))
    nowhere = jnp.full((1, 8, rc.num_experts_per_tok), rc.num_experts - 1)
    out, _ = ref._experts(p, h, rc, "highest", nowhere)   # none held: 0-1
    np.testing.assert_allclose(
        np.asarray(out),
        np.asarray(ref._relu2_ff(p["shared"]["w1"], p["shared"]["w2"], h,
                                 "highest")), atol=1e-7)


@pytest.mark.parametrize("mode,low,high", [("bfloat16", 5e-4, 0.03),
                                           ("fp8", 0.03, 1.0)])
def test_control_readings_at_a_size_a_test_can_hold(mode, low, high):
    """`control_nemotron_h.reference_vs_reference`, the path that sets the
    limits, rehearsed in the stated precision and in the control's: the
    recurrence's two products are rounded with every other contraction."""
    from benchmarks import control_nemotron_h

    cell = {"traffic": {"seq_len": 64}}
    row = control_nemotron_h.reference_vs_reference(TINY, cell, SEED, mode)
    assert set(row) == {"loss_rel_err", "grad_rel_err",
                        "routing_mismatch_share", "grad_rel_err_free"}
    assert low < row["grad_rel_err"] < high
    assert 0 <= row["routing_mismatch_share"] < 0.9


# --------------------------------------------------------------------- #
# the runner, rehearsed                                                  #
# --------------------------------------------------------------------- #

def test_runner_control_flow_on_the_cpu(tmp_path, monkeypatch, capsys):
    from benchmarks import run as harness
    from benchmarks.readers import held_rows_drift_pct
    from benchmarks.runners import train_nemotron_h

    monkeypatch.setenv("OOBLECK_TPU_CACHE", str(tmp_path / "profiles"))
    cell = {"name": "tiny.steady", "config": "tiny", "chips": 1,
            "kind": "train_nemotron_h",
            "traffic": {"seq_len": 64, "microbatch_size": 1,
                        "global_batch": 2, "warmup_steps": 1,
                        "learning_rate": 1e-3, "lr_warmup_steps": 2000},
            "correct": {"grad_rel_err": 0.2, "routing_mismatch_share": 0.5}}
    ctx = harness.Context(cell, TINY, 2**31 + 11, 1.0, False,
                          {"platform": "cpu", "kind": "cpu", "count": 1})
    built, build = [], train_nemotron_h.build_engine
    monkeypatch.setattr(train_nemotron_h, "build_engine",
                        lambda *a: built.append(build(*a)) or built[-1])
    out = train_nemotron_h.run(ctx)
    assert ctx.setup_s is not None
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert [c["check"] for c in out["checks"]] == [
        "grad_rel_err", "routing_mismatch_share"]
    assert all(c["ok"] for c in out["checks"]), out["checks"]
    assert out["end_to_end"]["train_tokens_per_s"] > 0
    assert out["layer_data"]["scopes"] is None           # no traced run
    # The job's own sequence length; ONE of the five layers is attention.
    train = out["layer_data"]["train"]
    assert (train["seq_len"], train["num_layers"], train["num_heads"],
            train["hidden_size"]) == (64, 1, 4, 64)
    assert train["microbatches_run"] == 2 * out["attempted"]
    assert train["n_params"] == ref.RefConfig.from_config(TINY).num_params()
    # Both readings of the gauge, one an `E` layer, and the drift reader.
    rows = out["layer_data"]["held_rows"]
    assert sorted(rows["before"]) == sorted(rows["after"]) == ["1", "4"]
    assert all(0 < v <= 64 * 3 for v in rows["before"].values())
    drift = held_rows_drift_pct.read(out["layer_data"])
    assert drift is not None and 0 <= drift < 50
    # The second probe ran the program the first compiled: nothing
    # compiles after the window.
    said = [json.loads(line) for line in capsys.readouterr().out.splitlines()
            if line.startswith("{")]
    (held,) = [o for o in said if o["observation"] == "held_rows"]
    assert held["probe_programs"] == 1 and held["before"] == rows["before"]
    (phases,) = [o for o in said if o["observation"] == "setup_phases"]
    assert {"build_engine_s", "weights_s", "check_s", "warm_up_s"} <= set(
        phases)
    # Beside the one norm over everything: the worst of the Mamba-2
    # layers' small leaves, named, printed and not limited.
    (check,) = [o for o in said if o["observation"] == "train_check"]
    assert 0 < check["mamba_leaf_rel_err_max"] < 0.2
    block, part, leaf = check["mamba_leaf_rel_err_at"].rsplit(".", 2)
    assert block in ("blocks.0", "blocks.2")
    assert (part, leaf) == ("ln_op", "scale") or (
        part == "mamba" and leaf in ("conv_taps", "conv_bias", "dt_bias",
                                     "A_log", "D", "norm"))
    # What a traced run hands the scope reader: the backward program's
    # instructions by the scope they were built under, the scan's among
    # them inside the mixer's.
    table = train_nemotron_h.backward_scopes(built[0])["jit_bwd"]
    ssd = [v for v in table.values() if "/ssd/" in v]
    assert ssd and all("mamba" in v for v in ssd)
    assert len(ssd) < sum("mamba" in v for v in table.values()) < len(table)
