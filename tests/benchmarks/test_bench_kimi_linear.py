"""The Kimi-Linear cell's own pieces: the configuration file against the
catalog's config and the `assumed` words, its sizes against what the program
and the reference build, the manifest's entries found by name, the
accepted readers the cell is named under on hand-made data,
the reference's planted faults, and the runner's and the control's flow
rehearsed on the CPU at `kimi-linear-tiny` sizes (never a number)."""

import json
from pathlib import Path

import pytest

import jax
import jax.numpy as jnp

from benchmarks import flops, flops_mla
from benchmarks.reference import kimi_linear as ref

ROOT = Path(__file__).resolve().parents[2]
NAME = "kimi-linear-48b-a3b"
CONFIG = json.loads(
    (ROOT / "benchmarks" / "configs" / f"{NAME}.json").read_text())
CELL = json.loads((ROOT / "benchmarks" / "workloads"
                   / f"{NAME}.steady.json").read_text())
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
PUBLISHED_MLA = [4, 8, 12, 16, 20, 24, 27]
REDUCED = ["num_hidden_layers", "num_layers", "linear_attn_config",
           "kda_layers", "full_attn_layers", "num_experts_held",
           "vocab_rows_held"]

# One block of each KIND, KDA(dense) MLA KDA: what the rehearsal compiles
# follows the blocks, and a third kind of block teaches it nothing new.
TINY = {
    "name": "tiny", "model_name": "kimi-linear-tiny",
    "model_args": {"num_layers": 3, "kda_layers": [1, 3],
                   "full_attn_layers": [2], "num_experts_held": 4,
                   "expert_offset": 4, "vocab_rows_held": 128},
    "vocab_size": 256, "vocab_rows_held": 128, "hidden_size": 64,
    "num_hidden_layers": 3,
    "linear_attn_config": {"kda_layers": [1, 3],
                           "full_attn_layers": [2], "num_heads": 4,
                           "head_dim": 16, "short_conv_kernel_size": 4},
    "full_attn_layers": [2], "gate_rank": 8, "num_attention_heads": 4,
    "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "intermediate_size": 128, "moe_intermediate_size": 32,
    "first_k_dense_replace": 1, "num_experts": 16,
    "num_experts_per_token": 4, "num_shared_experts": 1,
    "num_experts_held": 4, "expert_offset": 4,
    "routed_scaling_factor": 2.446, "rms_norm_eps": 1e-5,
    # 64 wide: 0.15 gives the projections the scale 0.02 gives them at 2304.
    "initializer_range": 0.15,
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
    name, and differs only where `reduced` says so: the depth and, inside
    `linear_attn_config`, the two layer LISTS (its widths stand)."""
    assert CATALOG.exists()
    rows = [json.loads(line) for line in CATALOG.read_text().splitlines()]
    (entry,) = [r for r in rows if r["name"] == "Kimi-Linear-48B-A3B-Instruct"]
    assert entry["source_url"] == CONFIG["source"]
    for key, value in entry["config"].items():
        if key in CONFIG["reduced"]:
            assert CONFIG["source_values"][key] == value, key
        else:
            assert CONFIG[key] == value, key
    assert CONFIG["reduced"] == REDUCED
    linear = CONFIG["linear_attn_config"]
    published = entry["config"]["linear_attn_config"]
    assert {k: v for k, v in linear.items() if not k.endswith("_layers")} == {
        k: v for k, v in published.items() if not k.endswith("_layers")} == {
        "num_heads": 32, "head_dim": 128, "short_conv_kernel_size": 4}
    assert published["full_attn_layers"] == PUBLISHED_MLA
    assert (CONFIG["num_hidden_layers"], CONFIG["num_layers"],
            linear["kda_layers"], linear["full_attn_layers"],
            CONFIG["kda_layers"], CONFIG["full_attn_layers"]) == (
        5, 5, [1, 2, 3, 5], [4], [1, 2, 3, 5], [4])
    assert CONFIG["model_args"] == {
        "num_layers": 5, "kda_layers": [1, 2, 3, 5], "full_attn_layers": [4],
        "num_experts_held": 8, "expert_offset": 0, "vocab_rows_held": 20480}
    assert CONFIG["source_values"] == {
        "num_hidden_layers": 27, "num_layers": 27,
        "linear_attn_config": published,
        "kda_layers": published["kda_layers"],
        "full_attn_layers": PUBLISHED_MLA,
        "num_experts_held": 256, "vocab_rows_held": 163840}
    # No width among the keys changed.
    for key in CONFIG["reduced"]:
        assert not key.endswith(("_dim", "_rank", "_size"))
    assert "arXiv:2510.26692" in CONFIG["paper"]
    assert "thirty-two" in CONFIG["deployment"].lower()
    assert CONFIG["execution"] == {"precision": "bfloat16", "remat": True}
    assert CONFIG["state_bytes_per_param"] == 16
    (entry,) = [c for c in MANIFEST["configs"] if c["name"] == CONFIG["name"]]
    assert entry["reduced"] == CONFIG["reduced"]
    assert entry["source"] == CONFIG["source"]
    assert entry["file"] == f"benchmarks/configs/{NAME}.json"
    for words in ("39,514,272", "29,114,880", "63,700,992", "7,077,888",
                  "590,080", "602,434,432", "9.64 GB", "KDA(dense) KDA KDA "
                  "MLA KDA", "335.8 M"):
        assert words in CONFIG["reduced_why"], words


@pytest.mark.parametrize("number,key,words", [
    (1, "gate_rank", "128 = linear_attn_config.head_dim"),
    (2, "biases", "no bias on any projection, W_gb included"),
    (3, "mixer_order", "conv -> SiLU -> l2 norm"),
    (4, "decay_init", "log U(1, 16)"),
    (5, "gated_norm", "then gates by a SIGMOID"),
    (6, "chunk", "chunk of 64"),
    (7, "latent_scores", "192^-1/2"),
    (8, "losses", "selection bias frozen"),
    (9, "sequence_length", "sequences of 4096"),
    (10, "initializer", "EMBEDDING at unit variance"),
    (11, "share", "exchanges nothing"),
], ids=lambda x: x if isinstance(x, str) and " " not in x else "")
def test_what_the_config_is_silent_on_is_stated_and_numbered(
        number, key, words):
    assert CONFIG["assumed"][key].startswith(f"({number}) ")
    assert words in CONFIG["assumed"][key]
    assert list(CONFIG["assumed"]).index(key) == number - 1


def test_reference_and_program_agree_on_the_configuration():
    """The file's sizes are what `build_model` builds and what the
    reference builds: part by part, layer by layer, and in all."""
    from oobleck_tpu.models import base, build_model

    model = build_model(CONFIG["model_name"], dict(CONFIG["model_args"]))
    c = model.config
    rc = ref.RefConfig.from_config(CONFIG)
    assert (c.hidden_size, c.num_layers, c.kda_layers, c.full_attn_layers,
            c.linear_num_heads, c.linear_head_dim, c.short_conv_kernel_size,
            c.gate_rank, c.chunk_size, c.num_heads, c.kv_lora_rank,
            c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim,
            c.intermediate_size, c.moe_intermediate_size,
            c.first_k_dense_replace, c.num_experts, c.num_experts_per_tok,
            c.num_shared_experts, c.experts_held, c.expert_offset,
            c.routed_scaling_factor, c.norm_eps, c.latent_norm_eps,
            c.data_vocab_size, c.padded_vocab_size, c.vocab_size) == (
        rc.hidden_size, rc.num_layers, rc.kda_layers, rc.full_attn_layers,
        rc.linear_num_heads, rc.linear_head_dim, rc.short_conv_kernel_size,
        rc.gate_rank, CONFIG["chunk_size"], rc.num_heads, rc.kv_lora_rank,
        rc.qk_nope_head_dim, rc.qk_rope_head_dim, rc.v_head_dim,
        rc.intermediate_size, rc.moe_intermediate_size,
        rc.first_k_dense_replace, rc.num_experts, rc.num_experts_per_tok,
        rc.num_shared_experts, rc.num_experts_held, rc.expert_offset,
        rc.routed_scaling_factor, rc.norm_eps, rc.latent_norm_eps,
        rc.vocab_size, rc.padded_vocab_size, CONFIG["vocab_size"])
    assert c.norm_topk_prob is CONFIG["moe_renormalize"] is True
    assert CONFIG["num_heads"] == CONFIG["num_attention_heads"] == c.num_heads
    assert c.max_position_embeddings == CONFIG["max_position_embeddings"] == (
        CONFIG["model_max_length"])
    count = lambda i: base.param_count(jax.eval_shape(
        lambda r: model.init_layer(r, i), jax.random.PRNGKey(0)))
    table = CONFIG["parameters"]
    layers = [count(i) for i in range(model.num_pipeline_layers)]
    assert layers == [
        table["embedding"], table["kda_dense_layer"],
        table["kda_routed_layer"], table["kda_routed_layer"],
        table["latent_routed_layer"], table["kda_routed_layer"],
        table["head"] + table["final_norm"]]
    assert sum(layers) == rc.num_params() == table["total"] == 602_434_432
    kda, latent = rc.block_params(0), rc.block_params(3)
    assert (kda["mixer"], kda["ff"], kda["norms"]) == (
        table["kda_mixer"], table["dense_ff"], table["layer_norms"])
    assert (latent["mixer"], latent["ff"], latent["shared"],
            latent["router"]) == (
        table["latent_mixer"], 8 * table["expert"], table["shared_expert"],
        table["router_and_bias"])
    assert CELL["traffic"]["seq_len"] <= c.max_position_embeddings
    # The seeded weights are the tree the program's layers hold.
    tiny = ref.RefConfig.from_config(TINY)
    seeded = jax.eval_shape(lambda: ref.init_params(SEED, tiny, (1, 16)))
    program = build_model("kimi-linear-tiny", dict(TINY["model_args"]))
    for li, tree in enumerate([seeded["embed"], *seeded["blocks"],
                               seeded["head"]]):
        own = jax.eval_shape(lambda r, i=li: program.init_layer(r, i),
                             jax.random.PRNGKey(0))
        assert jax.tree.map(lambda a: a.shape, tree) == jax.tree.map(
            lambda a: a.shape, own)


def test_cell_is_the_traffic_the_issue_gives():
    """ISSUE 67's traffic, `moonlight-16b-a3b.steady`'s to the number but
    for its fallback (a), which the chip called for and the cell's `why`
    says."""
    (sibling,) = [json.loads(p.read_text()) for p in
                  [ROOT / "benchmarks" / "workloads"
                   / "moonlight-16b-a3b.steady.json"]]
    mine = dict(CELL["traffic"])
    assert mine.pop("global_batch") in (8, 6, 4)
    theirs = dict(sibling["traffic"])
    theirs.pop("global_batch")
    assert mine == theirs
    if CELL["traffic"]["global_batch"] != 8:
        assert "fallback (a)" in CELL["why"]
    (entry,) = [w for w in MANIFEST["workloads"] if w["name"] == CELL["name"]]
    assert entry["why"] == CELL["why"] and entry["chips"] == CELL["chips"] == 1
    assert (entry["config"], entry["traffic"]) == (NAME, "steady")
    assert sorted(CELL["correct"]) == [
        "grad_rel_err", "kda_leaf_rel_err_max", "mla_grad_rel_err",
        "routing_mismatch_share"]
    assert CELL["kind"] == "train_kimi_linear"
    for words in ("control_kimi_linear.py", "scalar_decay", "rotary_on",
                  "beta_left_out", "shared_left_out", "decay_grad_cut",
                  "kda_leaf_rel_err_max", "float8-e4m3"):
        assert words in CELL["correct_why"], words


def test_every_why_is_one_line_of_at_most_200_characters():
    (config,) = [c for c in MANIFEST["configs"] if c["name"] == NAME]
    for why in (CELL["why"], config["why"], config["source"]):
        assert 0 < len(why) <= 200 and "\n" not in why and "\t" not in why


def test_example_job_is_the_cells_job():
    from oobleck_tpu.config import OobleckArguments

    args = OobleckArguments.from_yaml(
        str(ROOT / "examples" / f"{NAME}.yaml"))
    assert args.model.model_name == CONFIG["model_name"]
    assert args.model.model_args == CONFIG["model_args"]
    t = CELL["traffic"]
    assert (args.job.seq_len, args.job.microbatch_size,
            args.job.learning_rate, args.job.warmup_steps) == (
        t["seq_len"], t["microbatch_size"], t["learning_rate"],
        t["lr_warmup_steps"])
    assert args.execution.resolved_path() == "mpmd"
    assert (args.execution.precision, args.execution.remat) == (
        CONFIG["execution"]["precision"], CONFIG["execution"]["remat"])


# --------------------------------------------------------------------- #
# the manifest                                                           #
# --------------------------------------------------------------------- #

NEW_METRICS = ["kda_mixer_ms", "kda_rule_ms", "kda_inverse_ms",
               "mla_mixer_ms"]
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
    "hbm_headroom_min_pct.train",
    # Gated experts of 1024 through the grouped kernels in the four routed
    # layers (the dense layer and the shared expert are plain matmuls, in
    # neither the kernels' time nor their need): the rows come from the
    # program's own counters, the widths from the configuration.
    "moe_gmm_roofline", "moe_gmm_ms", "moe_tgmm_ms", "moe_token_sum_ms",
    "moe_tile_fill_pct", "moe_load_skew", "moe_step_rows_spread_pct",
    "moe_held_rows_drift",
    # The latent kernels of the ONE latent layer: the runner hands
    # `train.num_layers` as the count of latent layers (as every runner of
    # mixed kinds hands the layers its readers count), the heads and widths
    # come from the configuration.
    "flash_mla_fwd_roofline", "flash_mla_bwd_roofline",
    "flash_mla_fwd_calls_per_need"]
# Readers that would compute something WRONG on this cell, each with its
# reason. (Which further metrics name the cell is a later PR's to say: this
# file holds membership and never a list's end or its whole.)
NOT_THIS_CELLS = {
    "mfu_pct.train": "6 N over the parameters HELD, where a token applies "
                     "8 / 256 of the routed experts' share and no term "
                     "counts the rule's or the attention's products",
    "mfu_pct.looped": "the looped model's count",
    "flash_roofline": "one width of hidden // heads over %flash_ kernels",
    "flash_fwd_roofline": "the plain flash kernels: none in this program",
    "flash_bwd_roofline": "the plain flash kernels",
    "flash_fwd_calls_per_need": "the plain flash kernels",
    "flash_bwd_ms": "the plain flash kernels",
    "flash_d128_fwd_roofline": "the plain flash kernels",
    "flash_d128_bwd_roofline": "the plain flash kernels",
    "flash_d256_fwd_roofline": "the plain flash kernels",
    "flash_d256_bwd_roofline": "the plain flash kernels",
    "flash_swa_fwd_roofline": "no window layer",
    "flash_swa_bwd_roofline": "no window layer",
    "flash_swa_fwd_calls_per_need": "no window layer",
    "flash_diff_fwd_roofline": "differential attention's kernels",
    "flash_diff_bwd_roofline": "differential attention's kernels",
    "swa_attn_ms": "no window layer", "full_attn_ms": "smallthinker's scope",
    "diff_attn_ms": "phi-4's scope", "gmu_ms": "phi-4's scope",
    "moe_gmm_ungated_roofline": "these experts are gated",
    "gdn_rule_ms": "the scope `gdn`: this rule is built under `kda`",
    "gdn_mixer_ms": "the scope `gdn_mixer`",
    "gdn_inverse_ms": "the scope `gdn_inverse` is inside `kda_inverse` "
                      "here, and its file describes Gated DeltaNet's",
    "gdn_fwd_ms": "Gated DeltaNet's kernel",
    "gdn_bwd_ms": "Gated DeltaNet's kernel",
    "ssd_scan_ms": "Mamba-2's scope", "mamba_mixer_ms": "Mamba-2's scope",
    "ssd_fwd_ms": "Mamba-2's kernel", "ssd_bwd_ms": "Mamba-2's kernel",
    "ssd_fwd_roofline": "Mamba-2's kernel",
    "ssd_bwd_roofline": "Mamba-2's kernel",
    "sscan_fwd_ms": "Mamba-1's kernel", "sscan_bwd_ms": "Mamba-1's kernel",
    "sscan_fwd_roofline": "Mamba-1's kernel",
    "sscan_bwd_roofline": "Mamba-1's kernel",
    "mamba1_mixer_ms": "Mamba-1's scope",
    "loop_blocks_ms": "no loop", "exit_heads_ms": "no loop",
    "loop_block_visits": "no loop", "loop_scanned_passes": "no loop",
    "carry_bytes_max": "one stage: no carry crosses a cut",
    "device_ms_per_step.fwd": "one stage: no forward program of its own",
    "stage_idle_pct.max": "one stage", "dp_sync_ms.train": "one pipeline",
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
        assert set(entry) == {"name", "unit", "better", "source", "layer",
                              "moves", "workloads"}


def test_the_manifest_lists_every_per_layer_metric_the_cell_reports():
    named = {m["name"] for m in MANIFEST["per_layer"]
             if CELL["name"] in m.get("workloads", [])}
    assert set(NEW_METRICS + THIS_CELLS_TOO) <= named
    assert not named & set(NOT_THIS_CELLS)
    (rate,) = [m for m in MANIFEST["end_to_end"]
               if m["name"] == "train_tokens_per_s"]
    assert CELL["name"] in rate["workloads"]
    # The family's own entries stand next to each other, in order.
    names = [m["name"] for m in MANIFEST["per_layer"]]
    first = names.index(NEW_METRICS[0])
    assert names[first:first + len(NEW_METRICS)] == NEW_METRICS


# --------------------------------------------------------------------- #
# the readers                                                            #
# --------------------------------------------------------------------- #

def _trace(**seconds_by_name):
    return {"time_by_name": {k: (v, 1) for k, v in seconds_by_name.items()}}


def test_latent_metrics_count_the_latent_layers_the_runner_hands():
    """One latent layer of five: on the runner's `train` record (the latent
    layers' count, their 32 heads) the ACCEPTED readers need `flops_mla.py`'s
    one layer a microbatch and one call of the forward kernel."""
    from benchmarks.readers import kernel_calls_per_need, mla_roofline_pct

    kind = "TPU v5 lite"
    ops, nbytes = flops_mla.latent_attention_fwd(1, 32, 4096, 128, 64, 128)
    least = flops.roofline_seconds(ops, nbytes, kind)[0]
    rc = ref.RefConfig.from_config(CONFIG)
    data = {"trace": {"time_by_name": {
                "%flash_mla_fwd.3": (2 * 16 * least, 32),
                "%fusion.1": (9.0, 1)}},
            "train": {"microbatch_size": 1, "num_heads": rc.num_heads,
                      "seq_len": 4096, "microbatches_run": 16,
                      "num_layers": len(rc.full_attn_layers)},
            "config": CONFIG, "device": {"kind": kind}}
    assert data["train"]["num_layers"] == 1 and rc.num_layers == 5
    assert mla_roofline_pct.read(
        data, match="%flash_mla_fwd.",
        needed=["latent_attention_fwd"]) == pytest.approx(50.0)
    # 32 calls where 16 microbatches of one latent layer need 16 (the cell
    # itself reads 1.0: the layer's checkpoint keeps the kernel's O and LSE).
    assert kernel_calls_per_need.read(
        data, match="%flash_mla_fwd.") == pytest.approx(2.0)


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_layer_metric_files_name_readers_that_are_there(metric):
    import importlib

    spec = json.loads((ROOT / "benchmarks" / "layer_metrics"
                       / f"{metric}.json").read_text())
    reader = importlib.import_module(f"benchmarks.readers.{spec['reader']}")
    # On data without a trace every reader reads nothing and raises nothing.
    assert reader.read({"config": CONFIG}, **spec["args"]) is None


# --------------------------------------------------------------------- #
# the reference                                                          #
# --------------------------------------------------------------------- #

def test_an_unknown_fault_is_refused_and_the_control_lists_every_fault():
    from benchmarks import control_kimi_linear

    rc = ref.RefConfig.from_config(TINY)
    with pytest.raises(ValueError, match="fault must be one of"):
        ref.forward(None, None, rc, "highest", None, "no_such_fault")
    assert {f for *_, f in control_kimi_linear.CONTROLS} == set(ref.FAULTS)
    assert [m for _, m, f in control_kimi_linear.CONTROLS if f is None] == [
        "bfloat16", "fp8"]
    assert all(m == "highest" for _, m, f in control_kimi_linear.CONTROLS
               if f is not None)


# --------------------------------------------------------------------- #
# the runner, rehearsed                                                  #
# --------------------------------------------------------------------- #

@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    """The runner's whole flow on the CPU at tiny sizes, once: (its result,
    the engine it built, what it said, the context, the weights it made)."""
    import contextlib
    import io
    import os

    from benchmarks import run as harness
    from benchmarks.runners import train_kimi_linear

    old = os.environ.get("OOBLECK_TPU_CACHE")
    os.environ["OOBLECK_TPU_CACHE"] = str(
        tmp_path_factory.mktemp("profiles"))
    cell = {"name": "tiny.steady", "config": "tiny", "chips": 1,
            "kind": "train_kimi_linear",
            "traffic": {"seq_len": 32, "microbatch_size": 1,
                        "global_batch": 2, "warmup_steps": 1,
                        "learning_rate": 1e-3, "lr_warmup_steps": 2000},
            "correct": {"grad_rel_err": 0.1, "routing_mismatch_share": 0.3,
                        "mla_grad_rel_err": 0.15,
                        "kda_leaf_rel_err_max": 0.3}}
    ctx = harness.Context(cell, TINY, 2**31 + 11, 0.3, False,
                          {"platform": "cpu", "kind": "cpu", "count": 1})
    built, build = [], train_kimi_linear.build_engine
    made, make = [], train_kimi_linear.init_params
    train_kimi_linear.build_engine = (
        lambda *a: built.append(build(*a)) or built[-1])
    train_kimi_linear.init_params = (
        lambda *a: made.append(make(*a)) or made[-1])
    said = io.StringIO()
    try:
        with contextlib.redirect_stdout(said):
            out = train_kimi_linear.run(ctx)
    finally:
        train_kimi_linear.build_engine = build
        train_kimi_linear.init_params = make
        if old is None:
            os.environ.pop("OOBLECK_TPU_CACHE", None)
        else:
            os.environ["OOBLECK_TPU_CACHE"] = old
    lines = [json.loads(line) for line in said.getvalue().splitlines()
             if line.startswith("{")]
    return out, built[0], lines, ctx, made[0]


def test_runner_control_flow_on_the_cpu(rehearsal):
    from benchmarks.runners import train_kimi_linear

    out, engine, said, ctx, _ = rehearsal
    assert ctx.setup_s is not None
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert sorted(c["check"] for c in out["checks"]) == sorted(CELL["correct"])
    assert all(c["ok"] for c in out["checks"]), out["checks"]
    assert out["end_to_end"]["train_tokens_per_s"] > 0
    assert out["layer_data"]["scopes"] is None           # no traced run
    train = out["layer_data"]["train"]
    # `num_layers` counts the LATENT layers: what `flash_mla_*` read.
    assert (train["seq_len"], train["num_layers"], train["num_heads"],
            train["hidden_size"]) == (32, 1, 4, 64)
    assert train["microbatches_run"] == 2 * out["attempted"]
    assert train["n_params"] == ref.RefConfig.from_config(TINY).num_params()
    assert sorted(out["layer_data"]["held_rows"]["before"]) == list("12")
    (phases,) = [o for o in said if o["observation"] == "setup_phases"]
    assert {"build_engine_s", "weights_s", "check_s", "warm_up_s"} <= set(
        phases)
    (check,) = [o for o in said if o["observation"] == "train_check"]
    assert 0 < check["kda_leaf_rel_err_max"] < 0.3        # 64 wide, bfloat16
    leaf = check["kda_leaf_rel_err_at"]
    assert ".kda." in leaf or leaf.endswith(".scale")
    assert leaf.rsplit(".", 1)[1] not in train_kimi_linear.MATRICES
    # The planner timed each KIND of block once.
    names = [engine.model.layer_name(i) for i in range(5)]
    assert names == ["embed", "kda_dense_0", "mla_routed_1", "kda_routed_2",
                     "head"]
    # What a traced run hands the scope reader.
    table = train_kimi_linear.backward_scopes(engine)["jit_bwd"]
    for scope in ("kda_mixer", "kda", "kda_inverse", "mla_mixer"):
        assert any(f"/{scope}/" in v or f"({scope})" in v
                   for v in table.values()), scope
    # Nested as the data files say: the inverse inside the rule inside the
    # mixer, and nothing of the rule in the latent mixer.
    assert any("kda_mixer" in v and "/kda/" in v.split("kda_mixer", 1)[1]
               and "kda_inverse" in v.split("/kda/", 1)[1]
               for v in table.values())
    assert not any("mla_mixer" in v and "kda" in v.replace("kda_", "")
                   for v in table.values())


@pytest.mark.parametrize("fault", ref.FAULTS[1:])
def test_a_planted_fault_reads_over_the_limit_in_the_runner_s_check(
        rehearsal, monkeypatch, fault):
    """The reference with the fault in the reference's place: the program,
    which has none, then disagrees with it by what the fault moves, through
    the runner's own check and its own limit."""
    from benchmarks.runners import train as base
    from benchmarks.runners import train_kimi_linear

    _, engine, _, ctx, params = rehearsal
    base.install_weights(engine, params)     # the window trained them since
    honest = ref.loss_and_grads
    monkeypatch.setattr(
        train_kimi_linear.ref, "loss_and_grads",
        lambda p, t, c, mode, forced: honest(p, t, c, mode, forced, fault))
    numbers = train_kimi_linear.check_against_reference(ctx, engine, params,
                                                        ctx.seed)
    checks = {c["check"]: c for c in base.checks_from(
        numbers, ctx.cell["correct"])}
    assert not all(c["ok"] for c in checks.values()), numbers
    if fault == "rotary_on":
        # The latent layer alone: the norm over everything hardly moves, the
        # norm over the latent mixers does.
        assert not checks["mla_grad_rel_err"]["ok"], numbers
    if fault == "decay_grad_cut":
        # The backward alone, and of the decay alone: its leaves read 1.0
        # (no gradient at all) and nothing else moves.
        assert [k for k, c in checks.items() if not c["ok"]] == [
            "kda_leaf_rel_err_max"], numbers
        assert numbers["kda_leaf_rel_err_at"].rsplit(".", 1)[1] in (
            "A_log", "dt_bias")
