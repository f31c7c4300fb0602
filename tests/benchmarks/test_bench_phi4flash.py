"""The Phi-4-mini-flash cell's own pieces: the configuration file against
the catalog's config and the `assumed` words, `flops_sscan.py` and
`flops_diff.py` against hand counts at tiny sizes, the two new readers and
the accepted ones the cell's data files name on hand-made data, the
reference (the recurrence a position at a time, blocks that change no
value, the planted faults), and the runner's and the control's flow
rehearsed on the CPU at `phi4flash-tiny` sizes (never a number)."""

import importlib
import json
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks import flops, flops_diff, flops_sscan
from benchmarks.reference import phi4flash as ref

ROOT = Path(__file__).resolve().parents[2]
NAME = "phi-4-mini-flash"
CONFIG = json.loads(
    (ROOT / "benchmarks" / "configs" / f"{NAME}.json").read_text())
CELL = json.loads((ROOT / "benchmarks" / "workloads"
                   / f"{NAME}.steady.json").read_text())
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
CELL_KINDS = ["mamba_source", "full_source", "gmu", "cross"]

# The catalog's `config` of Phi-4-mini-flash-reasoning, as the driver drew
# it.
PUBLISHED = {
    "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
    "intermediate_size": 10240, "layer_norm_eps": 1e-05,
    "max_position_embeddings": 262144, "mb_per_layer": 2,
    "model_type": "phi4flash", "num_attention_heads": 40,
    "num_hidden_layers": 32, "num_key_value_heads": 20, "resid_pdrop": 0,
    "sliding_window": 512, "tie_word_embeddings": True, "mlp_bias": False,
    "lm_head_bias": False, "vocab_size": 200064}

TINY = {
    "name": "tiny", "model_name": "phi4flash-tiny",
    "model_args": {"vocab_rows_held": 120},        # padded to 128
    "vocab_size": 256, "vocab_rows_held": 120, "hidden_size": 64,
    "num_hidden_layers": 8, "layer_kinds": ref.published_kinds(8),
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "intermediate_size": 128, "sliding_window": 8, "layer_norm_eps": 1e-5,
    "mamba_expand": 2, "mamba_d_state": 16, "mamba_chunk": 128,
    "execution": {"precision": "bfloat16", "remat": True},
}
SEED = 2**31 + 5


@pytest.fixture(autouse=True, scope="module")
def _leave_no_series_behind():
    """The program's counters live in the PROCESS-GLOBAL registry: a later
    module on this worker must not read this one's."""
    yield
    from oobleck_tpu.utils import metrics

    metrics.registry().clear()


# --------------------------------------------------------------------- #
# the configuration                                                      #
# --------------------------------------------------------------------- #

def test_configuration_keeps_every_published_number():
    """Every key of the catalog's config is in the file under its own
    name, and differs only where `reduced` says so."""
    if CATALOG.exists():
        rows = [json.loads(line) for line in CATALOG.read_text().splitlines()]
        (entry,) = [r for r in rows
                    if r["name"] == "Phi-4-mini-flash-reasoning"]
        assert entry["config"] == PUBLISHED
        assert entry["source_url"] == CONFIG["source"]
    for key, value in PUBLISHED.items():
        if key in CONFIG["reduced"]:
            assert CONFIG["source_values"][key] == value, key
        else:
            assert CONFIG[key] == value, key
    assert CONFIG["reduced"] == ["num_layers", "num_hidden_layers",
                                 "layer_kinds", "vocab_rows_held"]
    assert CONFIG["source_values"] == {
        "num_layers": 32, "num_hidden_layers": 32,
        "layer_kinds": ref.published_kinds(32), "vocab_rows_held": 200064}
    assert (CONFIG["num_layers"], CONFIG["num_hidden_layers"],
            CONFIG["layer_kinds"], CONFIG["vocab_rows_held"]) == (
        4, 4, CELL_KINDS, 200064 // 8)
    # The cut is a verbatim, contiguous run of the published list, and the
    # offset that keeps `lambda_init` the published layers' says where.
    offset = CONFIG["model_args"]["layer_offset"]
    assert offset == CONFIG["layer_offset"] == 16
    assert ref.published_kinds(32)[offset:offset + 4] == CELL_KINDS
    assert CONFIG["model_args"] == {
        "num_layers": 4, "layer_kinds": CELL_KINDS, "layer_offset": 16,
        "vocab_rows_held": 25008}
    assert (CONFIG["head_dim"], CONFIG["num_heads"]) == (2560 // 40, 40)
    assert "eight" in CONFIG["deployment"].lower()
    assert CONFIG["execution"] == {"precision": "bfloat16", "remat": True}
    assert CONFIG["state_bytes_per_param"] == 16
    (entry,) = [c for c in MANIFEST["configs"] if c["name"] == CONFIG["name"]]
    assert entry["reduced"] == CONFIG["reduced"]
    assert entry["file"] == f"benchmarks/configs/{NAME}.json"
    for words in ("24 B a parameter", "761.0 M", "641.1 M", "542,827,520",
                  "542,897,408", "564.4 M", "window-512 layers (8 of 32)",
                  "2 layers in 4", "8 in 32"):
        assert words in CONFIG["reduced_why"], words


@pytest.mark.parametrize("key,words", [
    ("mamba_sizes", "d_state 16, d_conv 4, expand 2 (d_inner 5120), "
     "dt_rank ceil(2560 / 16) = 160"),
    ("differential_attention", "attention is differential at all"),
    ("differential_attention", "query pair j reads key-value pair j // 2"),
    ("differential_attention", "RMSNorm over the pair's 128"),
    ("biases", "W_qkv (W_q of a cross layer) and W_o of attention"),
    ("positional_term", "no positional term of any kind"),
    ("initializer", "A_log = log(1..16) a channel; D = 1"),
    ("initializer", "the four lambda vectors normal 0.1"),
    ("untied_head", "THE HEAD IS UNTIED where the model ties it"),
    ("weight_decay", "weight decay covers every trained leaf"),
    ("dropout", "dropout 0"),
    ("carry", "(hidden, m, k, v), in bfloat16"),
    ("share", "exchanges nothing"),
], ids=lambda x: x if " " not in x else "words")
def test_what_the_config_is_silent_on_is_stated(key, words):
    assert words in CONFIG["assumed"][key]


def test_reference_and_program_agree_on_the_configuration():
    from oobleck_tpu.models import build_model
    from oobleck_tpu.ops import sscan

    model = build_model(CONFIG["model_name"], dict(CONFIG["model_args"]))
    c = model.config
    rc = ref.RefConfig.from_config(CONFIG)
    shapes = [jax.eval_shape(lambda r, i=i: model.init_layer(r, i),
                             jax.random.PRNGKey(0))
              for i in range(model.num_pipeline_layers)]
    padded = c.padded_vocab_size - c.data_vocab_size
    assert rc.num_params() + 2 * padded * c.hidden_size == sum(
        int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert rc.num_params() == 542_897_408             # ISSUE 60: 542.8 M
    parts = [sum(rc.block_params(b).values()) for b in range(4)]
    assert [round(p / 1e6, 1) for p in parts] == [119.9, 98.3, 104.9, 91.8]
    assert (c.data_vocab_size, c.kinds, c.layer_offset) == (
        rc.vocab_size, rc.kinds, rc.layer_offset) == (
        25008, tuple(CELL_KINDS), 16)
    for key in ("hidden_size", "num_layers", "num_heads", "num_kv_heads",
                "head_dim", "intermediate_size", "sliding_window", "d_state",
                "d_conv", "expand", "d_inner", "layer_norm_eps",
                "time_step_min", "time_step_max", "initializer_range",
                "lambda_range"):
        assert getattr(c, key) == getattr(rc, key), key
    assert [c.lambda_init(b) for b in range(4)] == [
        rc.lambda_init(b) for b in range(4)]
    assert (CONFIG["mamba_expand"], CONFIG["mamba_d_state"],
            CONFIG["mamba_d_conv"], CONFIG["mamba_dt_rank"],
            CONFIG["mamba_chunk"]) == (c.expand, c.d_state, c.d_conv,
                                       c.rank, sscan.CHUNK)
    assert c.rank == rc.dt_rank == 160
    assert CELL["traffic"]["seq_len"] <= c.max_position_embeddings


def test_cell_is_the_traffic_the_issue_gives():
    """ISSUE 60's traffic; the five routed cells' schedule."""
    assert CELL["traffic"] == {
        "seq_len": 8192, "microbatch_size": 1, "global_batch": 4,
        "warmup_steps": 2, "learning_rate": 0.00016, "lr_warmup_steps": 2000}
    (entry,) = [w for w in MANIFEST["workloads"] if w["name"] == CELL["name"]]
    assert entry["why"] == CELL["why"] and entry["chips"] == CELL["chips"] == 1
    assert len(CELL["why"]) <= 200 and "\n" not in CELL["why"]
    assert sorted(CELL["correct"]) == ["grad_rel_err"]
    assert CELL["kind"] == "train_phi4flash"
    for words in ("control_phi4flash.py", "no_lambda", "gmu_gated",
                  "sscan_leaf_rel_err_max", "fallback"):
        assert words in CELL["correct_why"], words


def test_example_job_is_the_cells_job():
    """examples/phi-4-mini-flash.yaml is the one chip's job the cell
    measures: the same model arguments, sequence length and batch."""
    from oobleck_tpu.config import OobleckArguments

    args = OobleckArguments.from_yaml(
        str(ROOT / "examples" / "phi-4-mini-flash.yaml"))
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


NEW_METRICS = ["sscan_fwd_ms", "sscan_bwd_ms", "sscan_fwd_roofline",
               "sscan_bwd_roofline", "flash_diff_fwd_roofline",
               "flash_diff_bwd_roofline", "mamba1_mixer_ms", "gmu_ms",
               "diff_attn_ms", "carry_bytes_max"]
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
    "hbm_headroom_min_pct.train"]
# Readers that would compute something WRONG on this cell, each with its
# reason. (Which further metrics name the cell, and which cells the lists
# above name besides, is a later PR's to say: this file holds membership
# and never a list's end or its whole.)
NOT_THIS_CELLS = {
    "mfu_pct.train": "a dense model's 6 N and one attention term a layer",
    "flash_roofline": "one width of hidden // heads over %flash_ kernels",
    "flash_fwd_roofline": "matches %flash_fwd., which this cell never calls",
    "flash_bwd_roofline": "matches %flash_bwd_, which this cell never calls",
    "flash_d128_fwd_roofline": "heads of 128 at one softmax a head",
    "flash_d128_bwd_roofline": "heads of 128 at one softmax a head",
    "flash_d256_fwd_roofline": "another model's width",
    "flash_d256_bwd_roofline": "another model's width",
    "flash_fwd_calls_per_need": "counts %flash_fwd. calls: none here",
    "flash_bwd_ms": "times %flash_bwd_dqkv. calls: none here",
    "flash_mla_fwd_roofline": "latent attention's kernels",
    "flash_mla_bwd_roofline": "latent attention's kernels",
    "flash_mla_fwd_calls_per_need": "latent attention's kernels",
    "flash_swa_fwd_roofline": "the cell holds no window layer",
    "flash_swa_bwd_roofline": "the cell holds no window layer",
    "flash_swa_fwd_calls_per_need": "the cell holds no window layer",
    "swa_attn_ms": "another model's scope", "full_attn_ms": "another's",
    "moe_gmm_roofline": "no routed block", "moe_gmm_ms": "no routed block",
    "moe_tgmm_ms": "no routed block", "moe_token_sum_ms": "no routed block",
    "moe_gmm_ungated_roofline": "no routed block",
    "moe_held_rows_drift": "no routed block",
    "moe_tile_fill_pct": "no routed block", "moe_load_skew": "no routed block",
    "moe_step_rows_spread_pct": "no routed block",
    "ssd_scan_ms": "Mamba-2's scope", "mamba_mixer_ms": "Mamba-2's scope",
    "ssd_fwd_ms": "Mamba-2's kernel", "ssd_bwd_ms": "Mamba-2's kernel",
    "ssd_fwd_roofline": "Mamba-2's kernel",
    "ssd_bwd_roofline": "Mamba-2's kernel",
    "gdn_rule_ms": "the delta rule's", "gdn_mixer_ms": "the delta rule's",
    "gdn_inverse_ms": "the delta rule's", "gdn_fwd_ms": "the delta rule's",
    "gdn_bwd_ms": "the delta rule's",
    "recovery_s.hostloss": "one chip, nothing is lost",
    "dp_sync_ms.train": "one pipeline, nothing to share",
    "device_ms_per_step.fwd": "one stage: the forward is folded into bwd",
    "stage_idle_pct.max": "one stage",
}


@pytest.mark.parametrize("metric", NEW_METRICS + THIS_CELLS_TOO
                         + sorted(NOT_THIS_CELLS))
def test_which_metrics_name_the_cell(metric):
    (entry,) = [m for m in MANIFEST["per_layer"] if m["name"] == metric]
    if metric in NOT_THIS_CELLS:
        assert CELL["name"] not in entry["workloads"], NOT_THIS_CELLS[metric]
        return
    assert CELL["name"] in entry["workloads"]
    assert entry["moves"] in ("train_tokens_per_s", "setup_s")
    if metric in NEW_METRICS:
        assert entry["moves"] == "train_tokens_per_s"
        spec = json.loads((ROOT / "benchmarks" / "layer_metrics"
                           / f"{metric}.json").read_text())
        assert (entry["layer"], entry["unit"], entry["better"],
                entry["source"]) == (spec["layer"], spec["unit"],
                                     spec["better"], spec["source"])
        assert len(spec["what"]) > 40


def test_the_manifest_lists_every_per_layer_metric_the_cell_reports():
    named = {m["name"] for m in MANIFEST["per_layer"]
             if CELL["name"] in m.get("workloads", [])}
    assert set(NEW_METRICS + THIS_CELLS_TOO) <= named
    assert not named & set(NOT_THIS_CELLS)
    (rate,) = [m for m in MANIFEST["end_to_end"]
               if m["name"] == "train_tokens_per_s"]
    assert CELL["name"] in rate["workloads"]


# --------------------------------------------------------------------- #
# the two counts, by hand at tiny sizes                                  #
# --------------------------------------------------------------------- #

def test_the_scans_count_by_hand():
    """2 positions, 3 channels, 2 states, one chunk, x in 2 bytes."""
    # forward: 7 a (position, channel, state), 3 a (position, channel)
    ops, nbytes = flops_sscan.scan_fwd(1, 2, 3, 2, 128)
    assert ops == 2 * 3 * 2 * 7 + 2 * 3 * 3
    # x, y at 2 and dt at 4 a (position, channel); B, C at 4 a (position,
    # state); one chunk's start [3, 2] float32; A [3, 2] and D [3].
    assert nbytes == 6 * (2 + 2 + 4) + 2 * 4 * 4 + 6 * 4 + (6 + 3) * 4
    ops, nbytes = flops_sscan.scan_bwd(1, 2, 3, 2, 128)
    assert ops == 2 * 3 * 2 * 16 + 2 * 3 * 7
    # x, dy, dx at 2, dt, d dt at 4; B, C, dB, dC; the start; A, D, dA, dD.
    assert nbytes == 6 * (3 * 2 + 2 * 4) + 4 * 4 * 4 + 6 * 4 + 2 * 9 * 4
    # Three chunks of 128 in 300 positions: three starts a (channel, state).
    more = flops_sscan.scan_fwd(1, 300, 3, 2, 128)[1]
    one = flops_sscan.scan_fwd(1, 300, 3, 2, 512)[1]
    assert more - one == 2 * 3 * 2 * 4
    # At the cell: 671 M state updates a layer and sequence (ISSUE 60), and
    # the bytes bound both kernels on the matrix peak's count.
    assert 8192 * 5120 * 16 == 671_088_640
    for fn, low, high in (("scan_fwd", 0.40, 0.50), ("scan_bwd", 0.70, 0.85)):
        ops, nbytes = getattr(flops_sscan, fn)(1, 8192, 5120, 16, 128)
        least, bound = flops.roofline_seconds(ops, nbytes, "TPU v5 lite")
        assert bound == "memory" and low < least * 1e3 < high, (fn, least)


def test_differential_attentions_count_by_hand():
    """One pair, 4 positions, d = 2: the causal half is 4^2 / 2 = 8 pairs;
    each softmax 2 d for its scores and 2 (2 d) for its values."""
    ops, nbytes = flops_diff.diff_attention_fwd(1, 1, 4, 2)
    assert ops == 2 * 8 * (2 * 2 + 2 * 4)
    # q1, q2, k1, k2 at d, v at 2 d once, a1, a2 at 2 d: 10 d a position.
    assert nbytes == (4 * 2 + 4 + 2 * 4) * 4 * 2
    ops_b, nbytes_b = flops_diff.diff_attention_bwd(1, 1, 4, 2)
    assert ops_b == 2 * ops and nbytes_b == 2 * nbytes
    # The cell: 20 pairs of 64 at 8192, ISSUE 60's 0.38 GFLOP a token over
    # two layers, forward and backward.
    f = flops_diff.diff_attention_fwd(1, 20, 8192, 64)[0]
    b = flops_diff.diff_attention_bwd(1, 20, 8192, 64)[0]
    assert round(2 * (f + b) / 8192 / 1e9, 2) == 0.38
    # What two calls of a one-softmax kernel padded to 128 lanes issue: the
    # score products at twice the width.
    padded = 2 * flops.causal_attention_fwd(1, 20, 8192, 128)[0]
    assert padded / f == pytest.approx(8 / 6)


@pytest.mark.parametrize("metric", NEW_METRICS[:6])
def test_the_kernels_metrics_by_hand(metric):
    spec = json.loads((ROOT / "benchmarks" / "layer_metrics"
                       / f"{metric}.json").read_text())
    reader = importlib.import_module(f"benchmarks.readers.{spec['reader']}")
    # 25 steps of 4 microbatches: 100 calls of each scan kernel (one
    # Mamba-1 layer), 2 layers x 2 softmaxes x 100 of each flash kernel.
    trace = {"time_by_name": {
        "%sscan_fwd.2 = bf16[1,8192,5120] custom-call": [100 * 2.0e-3, 100],
        "%sscan_bwd.5 = bf16[1,8192,5120] custom-call": [100 * 10.0e-3, 100],
        "%flash_diff_fwd.1 = bf16[20,8192,128] custom-call": [400 * 3e-3, 400],
        "%flash_diff_bwd_dqkv.3 = bf16[20,8192,128] custom-call": [
            400 * 8e-3, 400],
        "%flash_fwd.9 = bf16[32,4096,128] custom-call": [9.0, 100]}}
    data = {"trace": trace, "device": {"kind": "TPU v5 lite"},
            "config": CONFIG,
            "train": {"microbatch_size": 1, "seq_len": 8192,
                      "microbatches_run": 100}}
    got = reader.read(data, **spec["args"])
    if metric.endswith("_ms"):
        assert spec["reader"] == "kernel_call_ms"
        assert got == pytest.approx({"sscan_fwd_ms": 2.0,
                                     "sscan_bwd_ms": 10.0}[metric])
    elif metric.startswith("sscan"):
        assert spec["reader"] == "sscan_roofline_pct"
        fn = spec["args"]["needed"]
        ops, nbytes = getattr(flops_sscan, fn)(1, 8192, 5120, 16, 128)
        least = flops.roofline_seconds(ops, nbytes, "TPU v5 lite")[0]
        spent = {"scan_fwd": 2.0e-3, "scan_bwd": 10.0e-3}[fn]
        assert got == pytest.approx(100 * least / spent) and 0 < got < 100
        no_layer = dict(CONFIG, layer_kinds=["full_source", "cross"])
        assert reader.read(dict(data, config=no_layer), **spec["args"]) is None
    else:
        assert spec["reader"] == "diff_roofline_pct"
        fn = spec["args"]["needed"]
        ops, nbytes = getattr(flops_diff, fn)(1, 20, 8192, 64)
        least, bound = flops.roofline_seconds(ops, nbytes, "TPU v5 lite")
        spent = {"diff_attention_fwd": 2 * 3e-3,
                 "diff_attention_bwd": 2 * 8e-3}[fn]
        assert bound == "compute"
        assert got == pytest.approx(100 * least / spent) and 0 < got < 100
        no_layer = dict(CONFIG, layer_kinds=["mamba_source", "gmu"])
        assert reader.read(dict(data, config=no_layer), **spec["args"]) is None
    if not metric.endswith("_ms"):
        # A kernel called twice as often reads half; a configuration of
        # another family reads nothing.
        twice = {k: [2 * s, 2 * n] for k, (s, n) in
                 trace["time_by_name"].items()}
        assert reader.read(dict(data, trace={"time_by_name": twice}),
                           **spec["args"]) == pytest.approx(got / 2)
        assert reader.read(dict(data, config={"hidden_size": 2560}),
                           **spec["args"]) is None
    # A trace without the kernel (the parent), no data: nothing, no error.
    assert reader.read(dict(data, trace={"time_by_name": {}}),
                       **spec["args"]) is None
    assert reader.read({}, **spec["args"]) is None


@pytest.mark.parametrize("metric,scope", [
    ("mamba1_mixer_ms", "mamba1"), ("gmu_ms", "gmu"),
    ("diff_attn_ms", "diff_attn")])
def test_the_parts_metrics_name_the_programs_scopes(metric, scope):
    spec = json.loads((ROOT / "benchmarks" / "layer_metrics"
                       / f"{metric}.json").read_text())
    assert spec["reader"] == "scope_ms_per_step"
    assert spec["args"] == {"module": "jit_bwd", "scope": scope}
    assert f"jax.named_scope('{scope}')" in spec["what"]
    source = (ROOT / "oobleck_tpu" / "models" / "phi4flash.py").read_text()
    assert f'@jax.named_scope("{scope}")' in source


def test_the_carrys_gauge_is_read_through_the_accepted_counter_reader():
    from benchmarks.readers import counter_value
    from oobleck_tpu.utils import metrics

    spec = json.loads((ROOT / "benchmarks" / "layer_metrics"
                       / "carry_bytes_max.json").read_text())
    assert spec["reader"] == "counter_value"
    metrics.registry().gauge(spec["args"]["counter"]).set(167_772_160)
    data = {"cell": {"name": CELL["name"]}}
    assert counter_value.read(data, **spec["args"]) == 167_772_160
    # The cell's carry: 4 x 42 MB, (2560 + 5120 + 2 x 1280) numbers of 2
    # bytes a position.
    assert 8192 * (2560 + 5120 + 2 * 1280) * 2 == 167_772_160
    assert counter_value.read({}, **spec["args"]) is None


# --------------------------------------------------------------------- #
# the reference                                                          #
# --------------------------------------------------------------------- #

@pytest.fixture(scope="module")
def tiny():
    """No layer at all: the embedding, the head and the loss (the layers
    are `tests/models/test_phi4flash.py`'s to compare)."""
    rc = ref.RefConfig.from_config(dict(TINY, num_hidden_layers=0,
                                        layer_kinds=[]))
    params = ref.init_params(SEED, rc)
    tokens = jax.random.randint(jax.random.PRNGKey(4), (2, 40), 0,
                                rc.vocab_size)
    return rc, params, tokens


def test_reference_imports_nothing_of_the_program():
    source = (ROOT / "benchmarks" / "reference" / "phi4flash.py").read_text()
    assert "import oobleck_tpu" not in source
    assert "from oobleck_tpu" not in source


def test_reference_walks_the_recurrence_a_position_at_a_time():
    """A scan whose carry is the state [B, C, N] and whose step reads one
    position; nothing of [S, C, N] is an operand of anything else."""
    k = jax.random.split(jax.random.PRNGKey(0), 4)
    s, ch, n = 12, 6, 4
    args = (jax.random.normal(k[0], (1, s, ch)),
            jax.nn.softplus(jax.random.normal(k[1], (1, s, ch))),
            -jnp.ones((ch, n)), jax.random.normal(k[2], (1, s, n)),
            jax.random.normal(k[3], (1, s, n)), jnp.ones((ch,)))
    jaxpr = jax.make_jaxpr(lambda *a: ref.recurrence(*a, "highest"))(*args)
    (outer,) = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "scan"]
    assert outer.params["length"] == 1            # one block of 12 positions
    assert [v.aval.shape for v in outer.invars if v.aval.shape == (1, ch, n)]


@pytest.mark.parametrize("length,block", [(256, 128), (50, 128)],
                         ids=["two_blocks", "no_multiple"])
def test_recurrence_over_blocks_is_the_recurrence_whole(monkeypatch, length,
                                                        block):
    """`SCAN_BLOCK` is for the gradient's memory and changes no value."""
    k = jax.random.split(jax.random.PRNGKey(2), 5)
    ch, n = 6, 4
    args = (jax.random.normal(k[0], (1, length, ch)),
            jax.nn.softplus(jax.random.normal(k[1], (1, length, ch))),
            -jnp.exp(jax.random.normal(k[2], (ch, n))),
            jax.random.normal(k[3], (1, length, n)),
            jax.random.normal(k[4], (1, length, n)), jnp.ones((ch,)))
    f = lambda *a: jnp.sum(jnp.sin(ref.recurrence(*a, "highest")))
    monkeypatch.setattr(ref, "SCAN_BLOCK", block)
    blocked = jax.value_and_grad(f, argnums=(0, 1, 2, 3, 4))(*args)
    monkeypatch.setattr(ref, "SCAN_BLOCK", length)
    whole = jax.value_and_grad(f, argnums=(0, 1, 2, 3, 4))(*args)
    for got, want in zip(jax.tree.leaves(blocked), jax.tree.leaves(whole)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5, rtol=1e-5)


def test_the_loss_in_blocks_is_the_loss_whole(tiny, monkeypatch):
    rc, params, tokens = tiny
    whole = ref.loss(params, tokens, rc)
    logits = ref.forward(params, tokens, rc)[:, :-1]
    gold = jnp.take_along_axis(logits, tokens[:, 1:, None], -1)[..., 0]
    plain = jnp.mean(jax.nn.logsumexp(logits, -1) - gold)
    assert float(whole) == pytest.approx(float(plain), rel=1e-6)
    monkeypatch.setattr(ref, "LOSS_BLOCK", 8)
    assert float(ref.loss(params, tokens, rc)) == pytest.approx(
        float(plain), rel=1e-6)


def test_attention_in_blocks_is_two_full_softmaxes_under_the_mask(monkeypatch):
    k = jax.random.split(jax.random.PRNGKey(3), 3)
    q, kk = (jax.random.normal(x, (4, 16, 8)) for x in k[:2])
    v = jax.random.normal(k[2], (4, 16, 16))
    for window in (None, 5):
        t, j = jnp.arange(16)[:, None], jnp.arange(16)[None, :]
        seen = (j <= t) if window is None else (j <= t) & (t - j < window)
        scores = jnp.einsum("hqd,hkd->hqk", q, kk) * 8 ** -0.5
        want = jnp.einsum("hqk,hkd->hqd", jax.nn.softmax(
            jnp.where(seen, scores, -jnp.inf), -1), v)
        monkeypatch.setattr(ref, "H_BLOCK", 2)
        monkeypatch.setattr(ref, "Q_BLOCK", 4)
        np.testing.assert_allclose(
            np.asarray(ref.attend(q, kk, v, "highest", window)),
            np.asarray(want), atol=1e-5)


def test_an_unknown_fault_is_refused(tiny):
    rc, params, tokens = tiny
    with pytest.raises(AssertionError):
        ref.forward(params, tokens, rc, "highest", "another")


@pytest.mark.parametrize("control,low,high", [
    ("bfloat16", 5e-4, 0.03), ("fp8", 0.03, 1.0), ("no_lambda", 5e-3, 1.0),
    ("gmu_gated", 5e-3, 1.0)])
def test_control_readings_at_a_size_a_test_can_hold(control, low, high):
    """`control_phi4flash.reference_vs_reference`, the path that sets the
    limit, rehearsed on the cell's four-layer list: the stated precision,
    the control's, and the two planted faults, each of which moves the
    gradients."""
    from benchmarks import control_phi4flash

    (mode, fault), = [(m, f) for name, m, f in control_phi4flash.CONTROLS
                      if name == control]
    four = dict(TINY, num_hidden_layers=4, layer_kinds=CELL_KINDS,
                model_args={"layer_offset": 16})
    row = control_phi4flash.reference_vs_reference(
        four, {"traffic": {"seq_len": 24}}, SEED, mode, fault)
    assert set(row) == {"loss_rel_err", "grad_rel_err"}
    assert low < row["grad_rel_err"] < high
    assert [name for name, *_ in control_phi4flash.CONTROLS] == [
        "bfloat16", "fp8", "no_lambda", "gmu_gated"]


# --------------------------------------------------------------------- #
# the runner, rehearsed                                                  #
# --------------------------------------------------------------------- #

def test_runner_control_flow_on_the_cpu(tmp_path, monkeypatch, capsys):
    from benchmarks import run as harness
    from benchmarks.runners import train_phi4flash

    monkeypatch.setenv("OOBLECK_TPU_CACHE", str(tmp_path / "profiles"))
    cell = {"name": "tiny.steady", "config": "tiny", "chips": 1,
            "kind": "train_phi4flash",
            "traffic": {"seq_len": 40, "microbatch_size": 1,
                        "global_batch": 2, "warmup_steps": 1,
                        "learning_rate": 1e-3, "lr_warmup_steps": 2000},
            "correct": {"grad_rel_err": 0.2}}
    ctx = harness.Context(cell, TINY, 2**31 + 11, 1.0, False,
                          {"platform": "cpu", "kind": "cpu", "count": 1})
    built, build = [], train_phi4flash.build_engine
    monkeypatch.setattr(train_phi4flash, "build_engine",
                        lambda *a: built.append(build(*a)) or built[-1])
    out = train_phi4flash.run(ctx)
    assert ctx.setup_s is not None
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert [c["check"] for c in out["checks"]] == ["grad_rel_err"]
    assert all(c["ok"] for c in out["checks"]), out["checks"]
    assert out["end_to_end"]["train_tokens_per_s"] > 0
    assert out["layer_data"]["scopes"] is None           # no traced run
    train = out["layer_data"]["train"]
    assert (train["seq_len"], train["num_layers"], train["num_heads"],
            train["hidden_size"]) == (40, 2, 4, 64)
    assert train["microbatches_run"] == 2 * out["attempted"]
    assert train["n_params"] == ref.RefConfig.from_config(TINY).num_params()
    said = [json.loads(line) for line in capsys.readouterr().out.splitlines()
            if line.startswith("{")]
    (phases,) = [o for o in said if o["observation"] == "setup_phases"]
    assert {"build_engine_s", "weights_s", "check_s", "warm_up_s"} <= set(
        phases)
    # Beside the one norm over everything: the worst of the small leaves,
    # named, printed and not limited.
    (check,) = [o for o in said if o["observation"] == "train_check"]
    assert 0 < check["sscan_leaf_rel_err_max"] < 2.0      # 64 wide, bfloat16
    assert check["pad_grad_abs_max"] == 0.0
    block, part, leaf = check["sscan_leaf_rel_err_at"].rsplit(".", 2)
    assert part in ("mamba", "attn") and leaf in train_phi4flash.SMALL
    # The program's own counters: the chunks by layer, the carry's bytes.
    (counters,) = [o for o in said if o["observation"] == "program_counters"]
    assert counters["oobleck_pipeline_carry_bytes_max"] == {
        "all": 40 * 2 * (64 + 128 + 2 * 32)}
    assert set(counters["oobleck_sscan_chunks_total"]) == {"0", "2", "4"}
    # What a traced run hands the scope reader: the backward program's
    # instructions by the scope they were built under.
    table = train_phi4flash.backward_scopes(built[0])["jit_bwd"]
    for scope, inside in (("sscan", "mamba1"), ("cross_attn", None)):
        found = [v for v in table.values() if f"/{scope}/" in v
                 or f"({scope})" in v]
        assert found, scope
        if inside:
            assert all(inside in v for v in found)
    for scope in ("mamba1", "gmu", "diff_attn"):
        assert any(scope in v for v in table.values()), scope
    diff = [v for v in table.values() if "cross_attn" in v]
    assert all("diff_attn" in v for v in diff)
