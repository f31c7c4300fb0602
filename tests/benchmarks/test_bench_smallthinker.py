"""The SmallThinker cell's own pieces: the configuration file against the
catalog's config and the `assumed` words, the sizes against what the
program builds, MEMBERSHIP of the cell and its metrics in the manifest
(never a list's end or its whole), `flops_window` at hand-checked sizes, the
two new readers and the accepted ones on this cell's geometry by hand, the
reference's band mask and published router, and the runner's and the
control's flow rehearsed on the CPU at `smallthinker-tiny` sizes (never a
number)."""

import json
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks import flops, flops_moe, flops_window
from benchmarks.reference import smallthinker as ref

ROOT = Path(__file__).resolve().parents[2]
NAME = "smallthinker-21b-a3b"
CONFIG = json.loads(
    (ROOT / "benchmarks" / "configs" / f"{NAME}.json").read_text())
CELL = json.loads((ROOT / "benchmarks" / "workloads"
                   / f"{NAME}.steady.json").read_text())
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
ACCEPTED_CELLS = ("gpt3-2.7b.steady", "lfm2-24b-a2b.steady",
                  "moonlight-16b-a3b.steady",
                  "nemotron-3-nano-30b-a3b.steady",
                  "qwen3-next-80b-a3b.steady")


@pytest.fixture(autouse=True, scope="module")
def _leave_no_series_behind():
    """The routing probe's counters live in the PROCESS-GLOBAL registry and
    the routed readers take every series they find there: a later module on
    this worker must not read this one's layers."""
    yield
    from oobleck_tpu.utils import metrics

    metrics.registry().clear()


LAYOUT = [int(i % 4 != 0) for i in range(52)]
# The catalog's `config` of SmallThinker-21BA3B-Instruct, as the driver
# drew it.
PUBLISHED = {
    "head_dim": 128, "hidden_size": 2560, "max_position_embeddings": 16384,
    "model_name": "smallthinker_21b_instruct", "moe_ffn_hidden_size": 768,
    "moe_num_active_primary_experts": 6, "moe_num_primary_experts": 64,
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
    "num_attention_heads": 28, "num_hidden_layers": 52,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06, "rope_layout": LAYOUT,
    "rope_scaling": None, "rope_theta": 1500000,
    "sliding_window_layout": LAYOUT, "sliding_window_size": 4096,
    "tie_word_embeddings": False, "vocab_size": 151936}

TINY = {
    "name": "tiny", "model_name": "smallthinker-tiny",
    "model_args": {"num_experts_held": 4, "expert_offset": 0,
                   "vocab_rows_held": 100},
    "vocab_size": 256, "vocab_rows_held": 100, "hidden_size": 64,
    "num_hidden_layers": 4, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "sliding_window_size": 24,
    "sliding_window_layout": [0, 1, 1, 1], "rope_layout": [0, 1, 1, 1],
    "rope_theta": 1500000, "moe_ffn_hidden_size": 32,
    "moe_intermediate_size": 32, "moe_num_primary_experts": 16,
    "moe_num_active_primary_experts": 4, "num_experts_held": 4,
    "rms_norm_eps": 1e-6,
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
                    if r["name"] == "SmallThinker-21BA3B-Instruct"]
        assert entry["config"] == PUBLISHED
        assert entry["source_url"] == CONFIG["source"]
    for key, value in PUBLISHED.items():
        if key in CONFIG["reduced"]:
            assert CONFIG["source_values"][key] == value, key
        else:
            assert CONFIG[key] == value, key
    assert CONFIG["reduced"] == [
        "num_hidden_layers", "num_layers", "rope_layout",
        "sliding_window_layout", "num_experts_held", "vocab_rows_held"]
    assert CONFIG["source_values"] == {
        "num_hidden_layers": 52, "num_layers": 52, "rope_layout": LAYOUT,
        "sliding_window_layout": LAYOUT, "num_experts_held": 64,
        "vocab_rows_held": 151936}
    assert (CONFIG["num_layers"], CONFIG["num_hidden_layers"],
            CONFIG["num_experts_held"], CONFIG["vocab_rows_held"]) == (
        4, 4, 64 // 8, 151936 // 8)
    # One whole period of the published 1 : 3, the lists cut to it.
    assert CONFIG["sliding_window_layout"] == LAYOUT[:4] == [0, 1, 1, 1]
    assert CONFIG["rope_layout"] == LAYOUT[:4]
    assert CONFIG["model_args"]["sliding_window_layout"] == LAYOUT[:4]
    assert CONFIG["model_args"]["rope_layout"] == LAYOUT[:4]
    # No width among the cuts: the router's 64 outputs and its 6 a token,
    # every head count, the head size, the window and theta are the
    # catalog's (the loop above); the harness's names repeat them.
    assert not [k for k in CONFIG["reduced"]
                if k.endswith(("_dim", "_rank")) or "size" in k]
    assert (CONFIG["num_heads"], CONFIG["num_kv_heads"],
            CONFIG["moe_intermediate_size"], CONFIG["intermediate_size"],
            CONFIG["num_experts"], CONFIG["num_experts_per_tok"]) == (
        28, 4, 768, 768, 64, 6)
    assert "eight" in CONFIG["deployment"].lower()
    assert CONFIG["execution"] == {"precision": "bfloat16", "remat": True}
    (entry,) = [c for c in MANIFEST["configs"] if c["name"] == CONFIG["name"]]
    assert entry["reduced"] == CONFIG["reduced"]
    assert entry["source"] == CONFIG["source"]
    assert entry["file"] == f"benchmarks/configs/{NAME}.json"
    # The manifest's rule for a `why`, a `source` and a `layer`: 1 to 200
    # printable characters on one line (the driver refused 206).
    for text in (entry["why"], entry["source"]):
        assert 1 <= len(text) <= 200 and text.isprintable(), len(text)
    for words in ("370,547,200", "4 x 8 and an eighth", "19,072",
                  "expert parallelism over 8"):
        assert words in CONFIG["reduced_why"], words


@pytest.mark.parametrize("key,words", [
    ("activation", "the experts are ReGLU"),
    ("router_input", "the router reads N1(x), the attention's input"),
    ("secondary_experts", "no secondary experts"),
    ("attention", "no bias in the attention's projections and no norm over "
     "a head"),
    ("auxiliary_loss", "no auxiliary or balance loss"),
    ("initializer", "initialisers as lfm2-24b-a2b's"),
    ("initializer", "BUT the embedding, drawn at unit variance"),
    ("weight_decay", "AdamW's weight decay covers every trained leaf"),
    ("share", "nothing stands in for the 7 absent chips"),
], ids=lambda x: x if " " not in x else "words")
def test_what_the_config_is_silent_on_is_stated(key, words):
    """ISSUE 45's items, numbered in the file as in the reference's
    docstring."""
    assert words in CONFIG["assumed"][key]
    assert CONFIG["assumed"][key].startswith("(")
    assert CONFIG["assumed"][key][:3] in ref.__doc__


def test_reference_and_program_agree_on_the_configuration():
    from oobleck_tpu.models import build_model

    model = build_model(CONFIG["model_name"], dict(CONFIG["model_args"]))
    # The published `model_name` and this repo's name build the same model.
    assert model.config == build_model(
        NAME, dict(CONFIG["model_args"])).config
    c = model.config
    rc = ref.RefConfig.from_config(CONFIG)
    shapes = [jax.eval_shape(lambda r, i=i: model.init_layer(r, i),
                             jax.random.PRNGKey(0))
              for i in range(model.num_pipeline_layers)]
    built = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    # What the program builds: the 370.5 M of ISSUE 45's table and the
    # rows that pad the vocabulary to a multiple of 128.
    padding = 2 * (c.padded_vocab_size - c.data_vocab_size) * c.hidden_size
    assert rc.num_params() == 370_547_200 == CONFIG["parameters"]["all"]
    assert built == rc.num_params() + padding
    assert padding == CONFIG["parameters"]["vocabulary_padding"] == 409_600
    assert rc.padded_vocab_size == c.padded_vocab_size == 19_072
    table, parts = CONFIG["parameters"], rc.block_params()
    assert parts["attention"] == table["attention"] == 20_971_520
    assert parts["router"] + parts["experts"] == table["ff"] == 47_349_760
    assert parts["norms"] == table["layer_norms"] == 5_120
    assert sum(parts.values()) == table["layer"] == 68_326_400
    assert 4 * table["layer"] == table["four_layers"] == 273_305_600
    assert (2 * rc.vocab_size * rc.hidden_size + rc.hidden_size
            == table["vocabulary_and_final_norm"] == 97_241_600)
    assert (c.data_vocab_size, c.experts_held, c.expert_offset) == (
        rc.vocab_size, rc.num_experts_held, rc.expert_offset) == (18992, 8, 0)
    for key in ("hidden_size", "num_layers", "num_heads", "num_kv_heads",
                "head_dim", "sliding_window_size", "rope_theta",
                "moe_intermediate_size", "num_experts", "num_experts_per_tok",
                "norm_eps", "initializer_range", "vocab_pad_multiple"):
        assert getattr(c, key) == getattr(rc, key), key
    from oobleck_tpu.models import smallthinker

    assert ref.EMBEDDING_STD == smallthinker.EMBEDDING_STD == 1.0
    assert c.windowed == rc.sliding_window_layout == (0, 1, 1, 1)
    assert c.rotary == rc.rope_layout == (0, 1, 1, 1)
    assert [model.kind(b) for b in range(4)] == [rc.kind(b) for b in range(4)]
    assert CELL["traffic"]["seq_len"] == c.max_position_embeddings == 16384
    assert CELL["traffic"]["seq_len"] > c.sliding_window_size
    assert CONFIG["state_bytes_per_param"] == 16


def test_cell_is_the_traffic_the_issue_gives():
    """ISSUE 45's traffic: one sequence of 16384 a microbatch, four a step
    (the tokens a step of `lfm2-24b-a2b.steady`), the three newest cells'
    warm-up and learning rate to the number."""
    t = CELL["traffic"]
    assert t == {"seq_len": 16384, "microbatch_size": 1, "global_batch": 4,
                 "warmup_steps": 2, "learning_rate": 0.00016,
                 "lr_warmup_steps": 2000}
    other = json.loads((ROOT / "benchmarks" / "workloads"
                        / "qwen3-next-80b-a3b.steady.json").read_text())[
        "traffic"]
    for key in ("warmup_steps", "learning_rate", "lr_warmup_steps"):
        assert t[key] == other[key], key
    for words in ("4 x (1 x 16384)", "~1,536 rows", "12,288", "8 x share",
                  "an eighth", "window 4096"):
        assert words in CELL["why"], words
    (entry,) = [w for w in MANIFEST["workloads"] if w["name"] == CELL["name"]]
    assert entry["why"] == CELL["why"] and entry["chips"] == 1
    assert (entry["config"], entry["traffic"]) == (NAME, "steady")
    assert len(CELL["why"]) <= 200
    assert CELL["kind"] == "train_smallthinker"
    assert sorted(CELL["correct"]) == ["grad_rel_err",
                                       "routing_mismatch_share"]
    assert "PLACEHOLDER" not in CELL["correct_why"]
    for words in ("2000", "window_ignored", "float8"):
        assert words in CELL["correct_why"], words


NEW_METRICS = {"flash_swa_fwd_roofline": "window_roofline_pct",
               "flash_swa_bwd_roofline": "window_roofline_pct",
               "flash_swa_fwd_calls_per_need": "window_calls_per_need",
               "swa_attn_ms": "scope_ms_per_step",
               "full_attn_ms": "scope_ms_per_step"}
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
    "flash_d128_fwd_roofline", "flash_d128_bwd_roofline",
    "moe_held_rows_drift", "moe_token_sum_ms", "moe_tile_fill_pct",
    "moe_load_skew", "moe_step_rows_spread_pct"]
# Readers that would compute something WRONG on this cell, or find nothing
# to read: one width of `hidden_size // num_heads` = 91 (no head of this
# model), a dense model's 6 N, kernels this model does not call, other
# families' scopes and widths. (Which further metrics name the cell is a
# later PR's to say: this file holds membership and never a list's end or
# its whole.)
NOT_THIS_CELLS = ["flash_roofline", "mfu_pct.train", "flash_fwd_roofline",
                  "flash_bwd_roofline", "flash_mla_fwd_roofline",
                  "flash_mla_bwd_roofline", "flash_mla_fwd_calls_per_need",
                  "moe_gmm_ungated_roofline",
                  "flash_d256_fwd_roofline", "flash_d256_bwd_roofline",
                  "ssd_scan_ms", "mamba_mixer_ms", "ssd_fwd_ms", "ssd_bwd_ms",
                  "ssd_fwd_roofline", "ssd_bwd_roofline", "gdn_rule_ms",
                  "gdn_mixer_ms", "gdn_inverse_ms"]


@pytest.mark.parametrize("metric",
                         sorted(NEW_METRICS) + THIS_CELLS_TOO + NOT_THIS_CELLS)
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
        assert spec["reader"] == NEW_METRICS[metric]


def test_the_manifest_lists_every_per_layer_metric_the_cell_reports():
    named = {m["name"] for m in MANIFEST["per_layer"]
             if CELL["name"] in m.get("workloads", [])}
    assert set(NEW_METRICS) | set(THIS_CELLS_TOO) <= named
    assert not named & set(NOT_THIS_CELLS)
    (rate,) = [m for m in MANIFEST["end_to_end"]
               if m["name"] == "train_tokens_per_s"]
    assert CELL["name"] in rate["workloads"]
    # The accepted cells are still where they were, in their order; this
    # PR's five metrics stand next to each other in theirs, wherever later
    # PRs' appended entries have left them, and its configuration is there
    # by name.
    assert [w["name"] for w in MANIFEST["workloads"]][:5] == list(
        ACCEPTED_CELLS)
    assert rate["workloads"][:5] == list(ACCEPTED_CELLS)
    names = [m["name"] for m in MANIFEST["per_layer"]]
    first = names.index("flash_swa_fwd_roofline")
    assert names[first:first + 5] == [
        "flash_swa_fwd_roofline", "flash_swa_bwd_roofline",
        "flash_swa_fwd_calls_per_need", "swa_attn_ms", "full_attn_ms"]
    assert NAME in [c["name"] for c in MANIFEST["configs"]]
    assert MANIFEST["run_seconds"] == 30


def test_what_this_pr_brings_under_benchmarks():
    """New under `benchmarks/`: the configuration, the cell, five metric
    files, the reference, the runner, the control, a README, the window's
    arithmetic and its two readers; the runner's parts are the accepted
    runners'."""
    bench = ROOT / "benchmarks"
    for path in ("runners/train_smallthinker.py", "reference/smallthinker.py",
                 "control_smallthinker.py", "README-smallthinker.md",
                 "flops_window.py", "readers/window_roofline_pct.py",
                 "readers/window_calls_per_need.py"):
        assert (bench / path).exists(), path
    source = (bench / "runners" / "train_smallthinker.py").read_text()
    for name in ("build_engine", "probe_held_rows", "step_gradients",
                 "backward_scopes"):
        assert f"def {name}" not in source and name in source, name
    for name in ("install_weights", "measure", "checks_from"):
        assert f"base.{name}" in source, name
    readme = (bench / "README-smallthinker.md").read_text()
    for words in ("window_layers", "28 heads of 128", "window_ignored",
                  "git archive"):
        assert words in readme, words


# --------------------------------------------------------------------- #
# the window's arithmetic and its readers, by hand                       #
# --------------------------------------------------------------------- #

@pytest.mark.parametrize("seq,window,pairs", [
    (16384, 4096, 58_722_304),      # 16384 * 4096 - 4096 * 4095 / 2
    (8192, 4096, 25_167_872),
    (8, 3, 21),                     # 1 + 2 + 3 * 6
    (4096, 4096, 4096 * 4097 // 2),  # the causal half, its diagonal
    (1024, 4096, 1024 * 1025 // 2),  # a window the sequence never reaches
    (5, 1, 5),                      # itself alone
])
def test_band_pairs_at_hand_checked_sizes(seq, window, pairs):
    assert flops_window.band_pairs(seq, window) == pairs
    # Counted out, where a test can hold it.
    if seq <= 8192:
        i = np.arange(seq)[:, None]
        j = np.arange(seq)[None, :]
        assert int(((j <= i) & (i - j < window)).sum()) == pairs


def test_window_arithmetic_beside_the_causal_half():
    """Operations follow the band's pairs, bytes are the causal call's:
    the operands do not shrink with the window."""
    ops, nbytes = flops_window.window_attention_fwd(1, 28, 16384, 128, 4096)
    assert ops == 2 * 2 * 58_722_304 * 128 * 28
    assert nbytes == 4 * 28 * 16384 * 128 * 2
    bops, bbytes = flops_window.window_attention_bwd(1, 28, 16384, 128, 4096)
    assert (bops, bbytes) == (2 * ops, 2 * nbytes)
    full, full_bytes = flops.causal_attention_fwd(1, 28, 16384, 128)
    assert nbytes == full_bytes
    assert ops / full == pytest.approx(58_722_304 / (16384 ** 2 / 2))
    assert 0.43 < ops / full < 0.44                    # ISSUE 45: 43.7 %
    # Compute-bound on the chip, as the full layer is.
    assert flops.roofline_seconds(ops, nbytes, "TPU v5 lite")[1] == "compute"


def _trace(**by_name):
    return {"time_by_name": {
        f"%{name}.1 = bf16[28,16384,128] custom-call": list(v)
        for name, v in by_name.items()}}


def _data(trace, **train):
    return {"trace": trace, "device": {"kind": "TPU v5 lite"},
            "config": CONFIG,
            "train": {"microbatch_size": 1, "seq_len": 16384,
                      "microbatches_run": 48, **train}}


def _args(metric):
    return json.loads((ROOT / "benchmarks" / "layer_metrics"
                       / f"{metric}.json").read_text())["args"]


def test_window_roofline_reader_on_a_hand_made_trace():
    from benchmarks.readers import window_roofline_pct as reader

    trace = _trace(flash_swa_fwd=(1.5, 144), flash_swa_bwd_dq=(2.0, 144),
                   flash_swa_bwd_dkv=(3.0, 144), flash_fwd=(9.0, 48))
    data = _data(trace, num_layers=1, window_layers=3)
    ops, nbytes = flops_window.window_attention_fwd(1, 28, 16384, 128, 4096)
    least = flops.roofline_seconds(ops, nbytes, "TPU v5 lite")[0]
    fwd = reader.read(data, **_args("flash_swa_fwd_roofline"))
    assert fwd == pytest.approx(100.0 * least * 48 * 3 / 1.5)
    ops, nbytes = flops_window.window_attention_bwd(1, 28, 16384, 128, 4096)
    least = flops.roofline_seconds(ops, nbytes, "TPU v5 lite")[0]
    bwd = reader.read(data, **_args("flash_swa_bwd_roofline"))
    assert bwd == pytest.approx(100.0 * least * 48 * 3 / 5.0)
    assert 0 < fwd < 100 and 0 < bwd < 100
    # The full layer's kernel is not its to read, nor the other way round.
    assert reader.read(_data(_trace(flash_fwd=(9.0, 48)), num_layers=1,
                             window_layers=3),
                       **_args("flash_swa_fwd_roofline")) is None
    # Nothing to read and no error: a runner that names no windowed
    # layers, a configuration without a window (every accepted cell's, and
    # the parent's under this PR's files).
    assert reader.read(_data(trace, num_layers=1),
                       **_args("flash_swa_fwd_roofline")) is None
    other = json.loads((ROOT / "benchmarks" / "configs"
                        / "nemotron-3-nano-30b-a3b.json").read_text())
    assert reader.read(dict(data, config=other),
                       **_args("flash_swa_fwd_roofline")) is None
    assert reader.read({}, **_args("flash_swa_fwd_roofline")) is None


def test_window_calls_reader_divides_by_the_windowed_layers():
    from benchmarks.readers import kernel_calls_per_need, window_calls_per_need

    trace = _trace(flash_swa_fwd=(1.5, 144), flash_fwd=(9.0, 48))
    data = _data(trace, num_layers=1, window_layers=3)
    args = _args("flash_swa_fwd_calls_per_need")
    assert window_calls_per_need.read(data, **args) == 1.0
    # The accepted reader on the same match divides by the FULL layers.
    assert kernel_calls_per_need.read(data, **args) == 3.0
    assert kernel_calls_per_need.read(
        data, **_args("flash_fwd_calls_per_need")) == 1.0
    twice = _data(_trace(flash_swa_fwd=(3.0, 288)), num_layers=1,
                  window_layers=3)
    assert window_calls_per_need.read(twice, **args) == 2.0
    assert window_calls_per_need.read(_data(trace, num_layers=1),
                                      **args) is None
    assert window_calls_per_need.read({}, **args) is None


def test_flash_geometry_reader_reads_the_one_full_layer_at_28_heads():
    """`flash_d128_*_roofline`'s reader takes heads and head width from
    the CONFIGURATION: 28 heads of 128 here (the metric file's `what` names
    the first cell's 32), one full-attention layer, the causal half."""
    from benchmarks.readers import flash_geometry_roofline_pct as reader

    trace = _trace(flash_fwd=(0.9, 48), flash_swa_fwd=(1.5, 144))
    data = _data(trace, num_layers=1, window_layers=3)
    ops, nbytes = flops.causal_attention_fwd(1, 28, 16384, 128)
    least = flops.roofline_seconds(ops, nbytes, "TPU v5 lite")[0]
    got = reader.read(data, **_args("flash_d128_fwd_roofline"))
    assert got == pytest.approx(100.0 * least * 48 / 0.9)
    assert 0 < got < 100
    assert (CONFIG["num_attention_heads"], CONFIG["head_dim"]) == (28, 128)


def test_scope_reader_tells_the_two_kinds_of_attention_apart():
    from benchmarks.readers import scope_ms_per_step as reader

    text = """
ENTRY %main.9 () -> f32[] {
  %fusion.1 = f32[8]{0} fusion(%x), kind=kLoop, calls=%f1, metadata={op_name="jit(bwd)/jvp(full_attn)/mul"}
  %flash_fwd.2 = bf16[8]{0} custom-call(%t), custom_call_target="tpu_custom_call", metadata={op_name="jit(bwd)/jvp(full_attn)/flash_fwd/pallas_call"}
  %fusion.3 = f32[8,16]{1,0} fusion(%y), kind=kOutput, calls=%f3, metadata={op_name="jit(bwd)/transpose(jvp())/checkpoint/swa_attn/dot_general"}
  %flash_swa_bwd_dq.4 = bf16[8]{0} custom-call(%z), custom_call_target="tpu_custom_call", metadata={op_name="jit(bwd)/transpose(jvp(swa_attn))/flash_swa_bwd_dq/pallas_call"}
  %fusion.5 = f32[8]{0} fusion(%z), kind=kLoop, calls=%f5, metadata={op_name="jit(bwd)/jvp(mlp)/swa_attnx/mul"}
}
"""
    ms = 1e6
    ops = [["%fusion.1 f32[8] fusion", 0 * ms, 2 * ms, {}],
           ["%flash_fwd.2 bf16[8] custom-call", 3 * ms, 4 * ms, {}],
           ["%fusion.3 f32[8,16] fusion", 8 * ms, 5 * ms, {}],
           ["%flash_swa_bwd_dq.4 bf16[8] custom-call", 14 * ms, 7 * ms, {}],
           ["%fusion.5 f32[8] fusion", 22 * ms, 1 * ms, {}]]
    data = {"trace_detail": {"ops": ops, "host": {},
                             "modules": [["jit_bwd", 0.0, 30 * ms]]},
            "scopes": {"jit_bwd": reader.scopes_of_text(text)},
            "cell": {"traffic": {"global_batch": 4, "microbatch_size": 1}},
            "train": {"microbatches_run": 8}}                  # 2 steps
    assert reader.read(data, **_args("full_attn_ms")) == pytest.approx(3.0)
    assert reader.read(data, **_args("swa_attn_ms")) == pytest.approx(6.0)
    assert reader.read(dict(data, scopes=None),
                       **_args("swa_attn_ms")) is None


def test_routed_roofline_reads_this_cell_s_sizes_from_the_configuration():
    """`readers/moe_gmm_roofline_pct.py` takes hidden 2560, expert width
    768 and the 8 held experts from the configuration: a gated expert's 3
    + 6 products, 1,536 rows expected an expert."""
    rows, hidden, inter, held = 16384 * 6 / 8.0, 2560, 768, 8
    assert (CONFIG["hidden_size"], CONFIG["moe_intermediate_size"],
            CONFIG["num_experts_held"]) == (hidden, inter, held)
    assert rows / held == 1536
    ops, nbytes = flops_moe.grouped_product(rows, hidden, inter, held)
    assert ops == 2 * rows * hidden * inter
    one, bound = flops.roofline_seconds(ops, nbytes, "TPU v5 lite")
    assert bound == "compute"       # 1,536 rows an expert: the products
    # A dW product also moves the float32 sum of 8 matrices of 2560 x 768,
    # read and written (PR 42): 207.6 MB, 0.2535 ms, just over its
    # products' 0.2453.
    dw = (rows * (hidden + inter) * 2 + held * hidden * inter * 8) / 819e9
    assert dw == pytest.approx(0.2535e-3, rel=1e-3) and dw > one
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


def test_reference_imports_nothing_of_the_program():
    source = (ROOT / "benchmarks" / "reference" / "smallthinker.py").read_text()
    assert "import oobleck_tpu" not in source
    assert "from oobleck_tpu" not in source


@pytest.mark.parametrize("seq,window,kv,qb", [(64, 24, 2, 16), (50, 7, 4, 512),
                                              (64, None, 1, 32),
                                              (32, 100, 3, 8)])
def test_attention_in_blocks_is_attention_whole(seq, window, kv, qb):
    """Blocks of queries against ALL keys, a key-value head's query heads
    at a time, the band mask from the two positions: the same numbers as
    one dense softmax over keys and values repeated to the query heads."""
    q = jax.random.normal(jax.random.PRNGKey(0), (2 * kv, seq, 16))
    k, v = (jax.random.normal(jax.random.PRNGKey(i), (kv, seq, 16))
            for i in (1, 2))
    got = ref.attend(q, k, v, "highest", window, q_block=qb)
    i, j = jnp.arange(seq)[:, None], jnp.arange(seq)[None, :]
    seen = (j <= i) if window is None else (j <= i) & (i - j < window)
    scores = jnp.einsum("hqd,hkd->hqk", q, jnp.repeat(k, 2, axis=0),
                        precision=jax.lax.Precision.HIGHEST) / 4.0
    want = jnp.einsum("hqk,hkd->hqd", jax.nn.softmax(
        jnp.where(seen, scores, -jnp.inf), -1), jnp.repeat(v, 2, axis=0),
        precision=jax.lax.Precision.HIGHEST)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)


def test_the_router_is_the_published_one(tiny):
    """Top-6 of the LOGITS of what the router reads, then a softmax over
    those six: read from `r`, never from `y`."""
    rc, params, _ = tiny
    p = params["blocks"][1]["ff"]
    r = jax.random.normal(jax.random.PRNGKey(6), (1, 8, rc.hidden_size))
    y = jax.random.normal(jax.random.PRNGKey(7), (1, 8, rc.hidden_size))
    out, own = ref._experts(p, r, y, rc, "highest", None)
    logits = r @ p["router"]
    top, want = jax.lax.top_k(logits, rc.num_experts_per_tok)
    np.testing.assert_array_equal(np.asarray(own), np.asarray(want))
    _, again = ref._experts(p, r, 3.0 * y + 1.0, rc, "highest", None)
    np.testing.assert_array_equal(np.asarray(own), np.asarray(again))
    # By hand: ReGLU over the held experts 0-3, weights the softmax over
    # the chosen six... of which this chip's part is what is held.
    w = jax.nn.softmax(top, -1)
    by_hand = jnp.zeros_like(y)
    for e in range(rc.num_experts_held):
        w_e = jnp.sum(jnp.where(want == e, w, 0.0), -1, keepdims=True)
        hidden = jax.nn.relu(y @ p["w1"][e]) * (y @ p["w3"][e])
        by_hand = by_hand + w_e * (hidden @ p["w2"][e])
    np.testing.assert_allclose(np.asarray(out), np.asarray(by_hand),
                               atol=1e-6)
    nowhere = jnp.full((1, 8, rc.num_experts_per_tok), rc.num_experts - 1)
    none_held, _ = ref._experts(p, r, y, rc, "highest", nowhere)
    assert not np.asarray(none_held).any()       # no shared expert


def test_the_vocabulary_is_padded_as_the_program_pads_it(tiny):
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


@pytest.mark.parametrize("mode,low,high", [("fp8", 0.01, 1.0),
                                           ("window_ignored", 1e-3, 2.0)])
def test_control_readings_at_a_size_a_test_can_hold(mode, low, high):
    """`control_smallthinker.reference_vs_reference`, the path that sets
    the limits, rehearsed in the control's precision and
    with the window's mask left out (64 positions under a window of 24).
    (Forced routing against the reference's own choice is
    `tests/models/test_smallthinker.py`'s.)"""
    from benchmarks import control_smallthinker

    cell = {"traffic": {"seq_len": 64}}
    row = control_smallthinker.reference_vs_reference(TINY, cell, SEED, mode)
    assert set(row) == {"loss_rel_err", "grad_rel_err",
                        "routing_mismatch_share", "grad_rel_err_free"}
    assert low < row["grad_rel_err"] < high
    assert 0 <= row["routing_mismatch_share"] <= 1.0


# --------------------------------------------------------------------- #
# the runner, rehearsed                                                  #
# --------------------------------------------------------------------- #

def test_runner_control_flow_on_the_cpu(tmp_path, monkeypatch, capsys):
    from benchmarks import run as harness
    from benchmarks.runners import train_smallthinker
    from oobleck_tpu.utils import metrics

    monkeypatch.setenv("OOBLECK_TPU_CACHE", str(tmp_path / "profiles"))
    cell = {"name": "tiny.steady", "config": "tiny", "chips": 1,
            "kind": "train_smallthinker",
            "traffic": {"seq_len": 64, "microbatch_size": 1,
                        "global_batch": 2, "warmup_steps": 1,
                        "learning_rate": 1e-3, "lr_warmup_steps": 2000},
            "correct": {"grad_rel_err": 0.2, "routing_mismatch_share": 0.5}}
    ctx = harness.Context(cell, TINY, 2**31 + 11, 1.0, False,
                          {"platform": "cpu", "kind": "cpu", "count": 1})
    built, build = [], train_smallthinker.build_engine
    monkeypatch.setattr(train_smallthinker, "build_engine",
                        lambda *a: built.append(build(*a)) or built[-1])
    out = train_smallthinker.run(ctx)
    assert ctx.setup_s is not None
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert [c["check"] for c in out["checks"]] == [
        "grad_rel_err", "routing_mismatch_share"]
    assert all(c["ok"] for c in out["checks"]), out["checks"]
    assert out["end_to_end"]["train_tokens_per_s"] > 0
    assert out["layer_data"]["scopes"] is None           # no traced run
    # The job's own sequence length; ONE of the four layers is full
    # attention, three are windowed.
    train = out["layer_data"]["train"]
    assert (train["seq_len"], train["num_layers"], train["window_layers"],
            train["window"], train["num_heads"], train["hidden_size"]) == (
        64, 1, 3, 24, 4, 64)
    assert train["microbatches_run"] == 2 * out["attempted"]
    assert train["n_params"] == ref.RefConfig.from_config(TINY).num_params()
    rows = out["layer_data"]["held_rows"]
    assert sorted(rows["before"]) == sorted(rows["after"]) == [
        "0", "1", "2", "3"]
    assert all(0 < v <= 64 * 4 for v in rows["before"].values())
    said = [json.loads(line) for line in capsys.readouterr().out.splitlines()
            if line.startswith("{")]
    (held,) = [o for o in said if o["observation"] == "held_rows"]
    assert held["probe_programs"] == 1 and held["before"] == rows["before"]
    (check,) = [o for o in said if o["observation"] == "train_check"]
    # 96 rows expected a held expert at 64 x 4 pairs over 16 experts...
    # of which 4 are held: a tile each at least.
    assert check["expert_tiles"]["tile"] % 16 == 0
    assert all(n >= 4 for n in check["expert_tiles"]["by_layer"])
    assert len(check["expert_tiles"]["by_layer"]) == 4
    assert 0 < check["attention_grad_rel_err"] < 0.3
    assert 0 < check["attention_grad_norm_share"] < 1
    (phases,) = [o for o in said if o["observation"] == "setup_phases"]
    assert {"build_engine_s", "weights_s", "check_s", "warm_up_s"} <= set(
        phases)
    # What the program's registry says of this cell's mechanisms (on the
    # CPU attention is XLA's: no flash kernel, no sum inside a kernel).
    (counters,) = [o for o in said if o["observation"] == "program_counters"]
    assert counters["oobleck_moe_reglu_calls_total"]["all"] >= 4
    assert counters["oobleck_moe_early_router_calls_total"]["all"] >= 4
    assert counters["oobleck_moe_softmax_routed_calls_total"]["all"] >= 4
    # What a traced run hands the scope reader: the backward program's
    # instructions by the scope they were built under, both kinds of
    # attention among them.
    table = train_smallthinker.backward_scopes(built[0])["jit_bwd"]
    for scope in ("full_attn", "swa_attn", "mlp", "lm_head"):
        assert any(f"{scope}" in v.replace("(", "/").replace(")", "/")
                   .split("/") for v in table.values()), scope
    reg = metrics.registry()
    assert reg.counter("oobleck_moe_reglu_calls_total").value() >= 4
