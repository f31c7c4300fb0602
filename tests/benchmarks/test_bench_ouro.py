"""The Ouro cell's own pieces: the configuration file against the
catalog's config and the `assumed` words, `flops_looped.py` against a count
made from the reference's own shapes, the new reader and the accepted ones
the cell's data files name on hand-made data, the reference (the loss in
blocks, the planted faults), and the runner's and the control's flow
rehearsed on the CPU at `ouro-tiny` sizes (never a number)."""

import json
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks import flops, flops_looped
from benchmarks.reference import ouro as ref

ROOT = Path(__file__).resolve().parents[2]
NAME = "ouro-2.6b"
CONFIG = json.loads(
    (ROOT / "benchmarks" / "configs" / f"{NAME}.json").read_text())
CELL = json.loads((ROOT / "benchmarks" / "workloads"
                   / f"{NAME}.steady.json").read_text())
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")

# The catalog's `config` of Ouro-2.6B, as the driver drew it.
PUBLISHED = {
    "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 5632, "layer_types": ["full_attention"] * 48,
    "max_position_embeddings": 65536, "max_window_layers": 48,
    "model_type": "ouro", "num_attention_heads": 16,
    "num_hidden_layers": 48, "num_key_value_heads": 16,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
    "sliding_window": None, "tie_word_embeddings": False,
    "total_ut_steps": 4, "early_exit_threshold": 1,
    "use_sliding_window": False, "vocab_size": 49152}

TINY = {
    "name": "tiny", "model_name": "ouro-tiny",
    "model_args": {"num_passes": 2},            # the preset has 3
    "vocab_size": 256, "vocab_rows_held": 256, "hidden_size": 64,
    "num_hidden_layers": 2, "total_ut_steps": 2, "num_attention_heads": 4,
    "num_key_value_heads": 4, "head_dim": 16, "intermediate_size": 128,
    "rope_theta": 1000000, "rms_norm_eps": 1e-6, "exit_entropy_weight": 0.1,
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
    name, and differs only where `reduced` says so: the depth alone."""
    if CATALOG.exists():
        rows = [json.loads(line) for line in CATALOG.read_text().splitlines()]
        (entry,) = [r for r in rows if r["name"] == "Ouro-2.6B"]
        assert entry["config"] == PUBLISHED
        assert entry["source_url"] == CONFIG["source"]
    for key, value in PUBLISHED.items():
        if key in CONFIG["reduced"]:
            assert CONFIG["source_values"][key] == value, key
        else:
            assert CONFIG[key] == value, key
    assert CONFIG["reduced"] == ["num_hidden_layers", "num_layers",
                                 "layer_types", "max_window_layers"]
    assert (CONFIG["num_hidden_layers"], CONFIG["num_layers"],
            CONFIG["layer_types"], CONFIG["max_window_layers"]) == (
        6, 6, ["full_attention"] * 6, 6)
    assert CONFIG["model_args"] == {"num_layers": 6}
    assert (CONFIG["total_ut_steps"], CONFIG["vocab_size"],
            CONFIG["vocab_rows_held"]) == (4, 49152, 49152)
    assert "arXiv:2510.25741" in CONFIG["paper"]
    assert "eight" in CONFIG["deployment"].lower()
    assert CONFIG["execution"] == {"precision": "bfloat16", "remat": True}
    assert CONFIG["state_bytes_per_param"] == 16
    (entry,) = [c for c in MANIFEST["configs"] if c["name"] == CONFIG["name"]]
    assert entry["reduced"] == CONFIG["reduced"]
    assert entry["source"] == CONFIG["source"]
    assert entry["file"] == f"benchmarks/configs/{NAME}.json"
    for words in ("509,661,185", "51,388,416", "8.15 GB", "561.0 M",
                  "the floor is four", "402.7 M of 1,635.8 M"):
        assert words in CONFIG["reduced_why"], words


@pytest.mark.parametrize("number,key,words", [
    (1, "pass_close", "closes EVERY pass"),
    (2, "sandwich_norms", "u = u + N(Attn(N(u; n1)); n2)"),
    (3, "biases", "no bias on any projection"),
    (4, "exit_gate", "reads the NORMED state"),
    (5, "loss", "beta = exit_entropy_weight = 0.1"),
    (6, "early_exit_threshold", "does nothing in training"),
    (7, "sequence_length", "sequences of 4096"),
    (8, "initializer", "w_g normal 0.02"),
    (9, "share", "exchanges nothing"),
], ids=lambda x: x if isinstance(x, str) and " " not in x else "")
def test_what_the_config_is_silent_on_is_stated_and_numbered(
        number, key, words):
    assert CONFIG["assumed"][key].startswith(f"({number}) ")
    assert words in CONFIG["assumed"][key]
    assert list(CONFIG["assumed"]).index(key) == number - 1


def test_reference_and_program_agree_on_the_configuration():
    """The file's sizes are what `build_model` builds and what the
    reference builds: layer by layer, and in all."""
    from oobleck_tpu.models import base, build_model

    model = build_model(CONFIG["model_name"], dict(CONFIG["model_args"]))
    c = model.config
    rc = ref.RefConfig.from_config(CONFIG)
    assert (c.hidden_size, c.num_layers, c.num_passes, c.num_heads,
            c.num_kv_heads, c.head_dim, c.intermediate_size, c.vocab_size,
            c.padded_vocab_size, c.rope_theta, c.norm_eps,
            c.exit_entropy_weight) == (
        rc.hidden_size, rc.num_layers, rc.num_passes, rc.num_heads,
        rc.num_kv_heads, rc.head_dim, rc.intermediate_size, rc.vocab_size,
        rc.vocab_size, rc.rope_theta, rc.norm_eps, rc.exit_entropy_weight)
    assert c.max_position_embeddings == CONFIG["max_position_embeddings"]
    held = sum(base.param_count(jax.eval_shape(
        lambda r, i=i: model.init_layer(r, i), jax.random.PRNGKey(0)))
        for i in range(model.num_pipeline_layers))
    table = CONFIG["parameters"]
    assert held == rc.num_params() == table["all"] == 509_661_185
    block = rc.block_params()
    assert (block["attention"], block["ff"], block["norms"]) == (
        table["attention"], table["ff"], table["block_norms"])
    assert table["block"] == sum(block.values()) == 51_388_416
    assert base.applied_param_count(model) == rc.applied_params() == (
        table["applied"])
    assert base.repeated(model) == (range(1, 7), 4)
    assert CELL["traffic"]["seq_len"] <= c.max_position_embeddings
    # The seeded weights are the tree the program's layers hold.
    tiny = ref.RefConfig.from_config(TINY)
    seeded = ref.init_params(SEED, tiny)
    program = build_model("ouro-tiny", dict(TINY["model_args"]))
    for li, tree in enumerate([seeded["embed"], *seeded["blocks"],
                               seeded["head"]]):
        own = jax.eval_shape(lambda r, i=li: program.init_layer(r, i),
                             jax.random.PRNGKey(0))
        assert jax.tree.map(lambda a: a.shape, tree) == jax.tree.map(
            lambda a: a.shape, own)


def test_cell_is_the_traffic_the_issue_gives():
    """ISSUE 64's traffic with its fallback (a), which the chip called for
    (19 steps a window at a global batch of 4) and the cell's `why` says;
    the five routed cells' schedule."""
    assert "global_batch 3: fallback (a)" in CELL["why"]
    assert CELL["traffic"] == {
        "seq_len": 4096, "microbatch_size": 1, "global_batch": 3,
        "warmup_steps": 2, "learning_rate": 0.0003, "lr_warmup_steps": 2000}
    (entry,) = [w for w in MANIFEST["workloads"] if w["name"] == CELL["name"]]
    assert entry["why"] == CELL["why"] and entry["chips"] == CELL["chips"] == 1
    assert (entry["config"], entry["traffic"]) == (NAME, "steady")
    assert sorted(CELL["correct"]) == ["grad_rel_err"]
    assert CELL["kind"] == "train_ouro"
    for words in ("control_ouro.py", "one_pass_short", "last_visit_grad",
                  "last_exit_only", "small_leaf_rel_err_max", "fallback"):
        assert words in CELL["correct_why"], words


def test_every_why_is_one_line_of_at_most_200_characters():
    (config,) = [c for c in MANIFEST["configs"] if c["name"] == NAME]
    for why in (CELL["why"], config["why"], config["source"]):
        assert 0 < len(why) <= 200 and "\n" not in why and "\t" not in why


def test_example_job_is_the_cells_job():
    """examples/ouro-2.6b.yaml is the one chip's job the cell measures, and
    says nothing of the loop: the model states it."""
    from oobleck_tpu.config import OobleckArguments

    args = OobleckArguments.from_yaml(
        str(ROOT / "examples" / "ouro-2.6b.yaml"))
    assert args.model.model_name == CONFIG["model_name"]
    assert args.model.model_args == CONFIG["model_args"]
    t = CELL["traffic"]
    assert (args.job.seq_len, args.job.microbatch_size,
            args.job.global_microbatch_size, args.job.learning_rate,
            args.job.warmup_steps) == (
        t["seq_len"], t["microbatch_size"], t["global_batch"],
        t["learning_rate"], t["lr_warmup_steps"])
    assert args.execution.resolved_path() == "mpmd"
    assert args.execution.resolved_virtual_stages == 1
    assert (args.execution.precision, args.execution.remat) == (
        CONFIG["execution"]["precision"], CONFIG["execution"]["remat"])


NEW_METRICS = ["mfu_pct.looped", "loop_blocks_ms", "exit_heads_ms",
               "loop_block_visits"]
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
    # 16 heads of 128 through the plain flash kernels, one call a block
    # VISIT: the runner's `train.num_layers` is passes x blocks.
    "flash_d128_fwd_roofline", "flash_d128_bwd_roofline",
    "flash_fwd_calls_per_need", "flash_bwd_ms",
    # The carry with its exit states.
    "carry_bytes_max"]
# Readers that would compute something WRONG on this cell, each with its
# reason. (Which further metrics name the cell, and which cells the lists
# above name besides, is a later PR's to say: this file holds membership
# and never a list's end or its whole.)
NOT_THIS_CELLS = {
    "mfu_pct.train": "6 N over the parameters HELD: 3.6 x low where blocks "
                     "and head are applied four times (mfu_pct.looped)",
    "flash_roofline": "one width of hidden // heads over %flash_ kernels",
    "flash_fwd_roofline": "gpt3-2.7b's geometry, hidden // heads",
    "flash_bwd_roofline": "gpt3-2.7b's geometry, hidden // heads",
    "flash_d256_fwd_roofline": "another model's width",
    "flash_d256_bwd_roofline": "another model's width",
    "flash_mla_fwd_roofline": "latent attention's kernels",
    "flash_mla_bwd_roofline": "latent attention's kernels",
    "flash_mla_fwd_calls_per_need": "latent attention's kernels",
    "flash_swa_fwd_roofline": "no window layer",
    "flash_swa_bwd_roofline": "no window layer",
    "flash_swa_fwd_calls_per_need": "no window layer",
    "flash_diff_fwd_roofline": "differential attention's kernels",
    "flash_diff_bwd_roofline": "differential attention's kernels",
    "swa_attn_ms": "no window layer",
    "full_attn_ms": "its file describes smallthinker's full layers, which "
                    "have no positional term; loop_blocks_ms has these",
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
    "sscan_fwd_ms": "Mamba-1's kernel", "sscan_bwd_ms": "Mamba-1's kernel",
    "sscan_fwd_roofline": "Mamba-1's kernel",
    "sscan_bwd_roofline": "Mamba-1's kernel",
    "mamba1_mixer_ms": "Mamba-1's scope", "gmu_ms": "another model's scope",
    "diff_attn_ms": "another model's scope",
    "gdn_rule_ms": "the delta rule's", "gdn_mixer_ms": "the delta rule's",
    "gdn_inverse_ms": "the delta rule's", "gdn_fwd_ms": "the delta rule's",
    "gdn_bwd_ms": "the delta rule's",
    "recovery_s.hostloss": "one chip, nothing is lost",
    "dp_sync_ms.train": "one pipeline, nothing to share",
    "device_ms_per_step.fwd": "one stage, its visits folded: the forward "
                              "is inside bwd",
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


# --------------------------------------------------------------------- #
# the yardstick's arithmetic                                             #
# --------------------------------------------------------------------- #

def test_the_looped_count_is_the_reference_s_own_shapes():
    """Every 2-d product of the reference, read from the shapes of its own
    seeded weights (abstractly: nothing of this size is made), counted once
    a use: 9.81 GFLOP a token in matrices and 1.21 in causal attention at
    the cell's sizes."""
    rc = ref.RefConfig.from_config(CONFIG)
    shapes = jax.eval_shape(lambda: ref.init_params(0, rc))
    size = lambda tree: sum(int(np.prod(a.shape))
                            for a in jax.tree.leaves(tree) if a.ndim >= 2)
    blocks = sum(size(b) for b in shapes["blocks"])
    applied = rc.num_passes * (blocks + size(shapes["head"]))
    assert size(shapes["embed"]) == size(shapes["head"]) == 100_663_296
    assert applied == rc.applied_params() == flops_looped.applied_params(
        passes=4, blocks=6, hidden=2048, heads=16, kv_heads=16, head_dim=128,
        intermediate=5632, vocab=49152)
    seq = CELL["traffic"]["seq_len"]
    per_token = flops_looped.from_config(CONFIG, seq)
    attention = 6.0 * 4 * 6 * 2048 * seq
    assert per_token == 6.0 * applied + attention
    assert 6.0 * applied == pytest.approx(9.81e9, rel=1e-3)
    assert attention == pytest.approx(1.21e9, rel=2e-3)
    assert per_token == CONFIG["parameters"]["flops_per_token"]["all"]
    # The attention term is `flops.py`'s, a block VISIT a layer; and where
    # nothing repeats the count is `flops.py`'s over the matrices.
    assert per_token == flops.train_flops_per_token(
        applied, seq, num_layers=24, hidden_size=2048)
    once = flops_looped.train_flops_per_token(
        passes=1, blocks=6, hidden=2048, heads=16, kv_heads=16, head_dim=128,
        intermediate=5632, vocab=49152, seq_len=seq)
    assert once == flops.train_flops_per_token(
        blocks + size(shapes["head"]), seq, num_layers=6, hidden_size=2048)
    # What 6 N over the parameters held would say.
    assert per_token / (6.0 * rc.num_params()) == pytest.approx(3.6, rel=5e-3)


def test_the_new_reader_by_hand():
    from benchmarks.readers import looped_mfu_pct, mfu_pct

    data = {"train": {"tokens_per_s": 10_000.0, "seq_len": 4096},
            "config": CONFIG, "device": {"kind": "TPU v5 lite"}}
    want = 100.0 * 10_000.0 * 11_022_630_912 / 197e12
    assert looped_mfu_pct.read(data) == pytest.approx(want)
    # Nothing to read, and no error: a configuration that states no
    # passes (every other cell's), a runner that hands no `train`.
    other = json.loads((ROOT / "benchmarks" / "configs"
                        / "smallthinker-21b-a3b.json").read_text())
    assert looped_mfu_pct.read(dict(data, config=other)) is None
    assert looped_mfu_pct.read({"config": CONFIG}) is None
    # And the accepted reader on this cell's data reads 3.3 x low.
    t = dict(data["train"], n_params=509_661_185, num_layers=24,
             hidden_size=2048)
    assert looped_mfu_pct.read(data) / mfu_pct.read(
        dict(data, train=t)) == pytest.approx(11.0226 / 4.2659, rel=1e-3)


@pytest.mark.parametrize("metric,reader,args", [
    ("loop_blocks_ms", "scope_ms_per_step",
     {"module": "jit_bwd", "scope": "loop_blocks"}),
    ("exit_heads_ms", "scope_ms_per_step",
     {"module": "jit_bwd", "scope": "exit_heads"}),
    ("loop_block_visits", "counter_value",
     {"counter": "oobleck_loop_block_visits_total"}),
    ("mfu_pct.looped", "looped_mfu_pct", None),
])
def test_the_data_files_name_accepted_readers_and_the_programs_names(
        metric, reader, args):
    spec = json.loads((ROOT / "benchmarks" / "layer_metrics"
                       / f"{metric}.json").read_text())
    assert spec["reader"] == reader and spec.get("args") == args
    source = (ROOT / "oobleck_tpu" / "models" / "ouro.py").read_text() + (
        ROOT / "oobleck_tpu" / "execution" / "pipeline.py").read_text()
    name = (args or {}).get("scope") or (args or {}).get("counter")
    if name:
        assert f'"{name}"' in source


# --------------------------------------------------------------------- #
# the reference                                                          #
# --------------------------------------------------------------------- #

def test_reference_imports_nothing_of_the_program():
    text = (ROOT / "benchmarks" / "reference" / "ouro.py").read_text()
    assert "oobleck_tpu" not in text.split('"""', 2)[2]
    assert "Precision.HIGHEST" in (
        ROOT / "benchmarks" / "reference" / "gpt.py").read_text()


@pytest.fixture(scope="module")
def tiny():
    rc = ref.RefConfig.from_config(TINY)
    tokens = jax.random.randint(jax.random.PRNGKey(2), (1, 48), 0, 256)
    return rc, ref.init_params(SEED, rc), tokens


def test_the_loss_in_blocks_is_the_loss_whole(tiny, monkeypatch):
    rc, params, tokens = tiny
    whole = jax.jit(lambda p: ref.loss(p, tokens, rc))(params)
    monkeypatch.setattr(ref, "LOSS_BLOCK", 16)
    blocked = jax.jit(lambda p: ref.loss(p, tokens, rc))(params)
    np.testing.assert_allclose(np.asarray(blocked[0]), np.asarray(whole[0]),
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(blocked[1]), np.asarray(whole[1]),
                               rtol=1e-6)
    assert whole[1].shape == (2,)


def test_an_unknown_fault_is_refused(tiny):
    rc, params, tokens = tiny
    with pytest.raises(ValueError, match="fault must be one of"):
        ref.loss(params, tokens, rc, "highest", "another")


def test_the_control_reads_over_the_stated_precision_at_a_size_a_test_can_hold():
    """`control_ouro.reference_vs_reference`, the path that sets the limit,
    rehearsed at tiny sizes on the CONTROL itself: float8 operands move the
    gradients by more than a tenth (the three planted faults go through the
    runner's own check below)."""
    from benchmarks import control_ouro

    assert [name for name, *_ in control_ouro.CONTROLS] == [
        "bfloat16", "fp8", "one_pass_short", "last_visit_grad",
        "last_exit_only"]
    assert {f for *_, f in control_ouro.CONTROLS} == set(ref.FAULTS)
    row = control_ouro.reference_vs_reference(
        dict(TINY, num_hidden_layers=1, total_ut_steps=2),
        {"traffic": {"seq_len": 16}}, SEED, "fp8", None)
    assert set(row) == {"loss_rel_err", "grad_rel_err"}
    assert 0.05 < row["grad_rel_err"] < 1.0


# --------------------------------------------------------------------- #
# the runner, rehearsed                                                  #
# --------------------------------------------------------------------- #

@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    """The runner's whole flow on the CPU at tiny sizes, once: (its result,
    the engine it built, what it said, the context)."""
    import contextlib
    import io
    import os

    from benchmarks import run as harness
    from benchmarks.runners import train_ouro

    old = os.environ.get("OOBLECK_TPU_CACHE")
    os.environ["OOBLECK_TPU_CACHE"] = str(
        tmp_path_factory.mktemp("profiles"))
    cell = {"name": "tiny.steady", "config": "tiny", "chips": 1,
            "kind": "train_ouro",
            "traffic": {"seq_len": 32, "microbatch_size": 1,
                        "global_batch": 2, "warmup_steps": 1,
                        "learning_rate": 1e-3, "lr_warmup_steps": 2000},
            "correct": {"grad_rel_err": 0.2}}
    ctx = harness.Context(cell, TINY, 2**31 + 11, 0.3, False,
                          {"platform": "cpu", "kind": "cpu", "count": 1})
    built, build = [], train_ouro.build_engine
    train_ouro.build_engine = lambda *a: built.append(build(*a)) or built[-1]
    said = io.StringIO()
    try:
        with contextlib.redirect_stdout(said):
            out = train_ouro.run(ctx)
    finally:
        train_ouro.build_engine = build
        if old is None:
            os.environ.pop("OOBLECK_TPU_CACHE", None)
        else:
            os.environ["OOBLECK_TPU_CACHE"] = old
    lines = [json.loads(line) for line in said.getvalue().splitlines()
             if line.startswith("{")]
    return out, built[0], lines, ctx


def test_runner_control_flow_on_the_cpu(rehearsal):
    from benchmarks.runners import train_ouro

    out, engine, said, ctx = rehearsal
    assert ctx.setup_s is not None
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert [c["check"] for c in out["checks"]] == ["grad_rel_err"]
    assert all(c["ok"] for c in out["checks"]), out["checks"]
    assert out["end_to_end"]["train_tokens_per_s"] > 0
    assert out["layer_data"]["scopes"] is None           # no traced run
    train = out["layer_data"]["train"]
    # The attention VISITS of a microbatch: passes x blocks.
    assert (train["seq_len"], train["num_layers"], train["num_heads"],
            train["hidden_size"]) == (32, 2 * 2, 4, 64)
    assert train["microbatches_run"] == 2 * out["attempted"]
    assert train["n_params"] == ref.RefConfig.from_config(TINY).num_params()
    (phases,) = [o for o in said if o["observation"] == "setup_phases"]
    assert {"build_engine_s", "weights_s", "check_s", "warm_up_s"} <= set(
        phases)
    # Beside the one norm over everything: the worst of the small leaves,
    # named, and each exit's own cross-entropy, printed and not limited.
    (check,) = [o for o in said if o["observation"] == "train_check"]
    assert 0 < check["small_leaf_rel_err_max"] < 2.0      # 64 wide, bfloat16
    assert check["small_leaf_rel_err_at"].rsplit(".", 1)[1] in (
        train_ouro.SMALL)
    assert len(check["exit_cross_entropy"]) == 2
    assert all(4.0 < ce < 7.0 for ce in check["exit_cross_entropy"])
    # The program's own counters: what its programs hold of the loop.
    (counters,) = [o for o in said if o["observation"] == "program_counters"]
    assert counters["oobleck_loop_block_visits_total"] == {"all": 4.0}
    assert counters["oobleck_loop_exits_total"] == {"all": 2.0}
    assert counters["oobleck_pipeline_carry_bytes_max"] == {
        "all": 32 * (2 * 64 + 2 * 2 * 64 + 2 * 4)}
    # What a traced run hands the scope reader: the backward program's
    # instructions by the scope they were built under.
    table = train_ouro.backward_scopes(engine)["jit_bwd"]
    for scope in ("loop_blocks", "exit_heads"):
        assert any(f"/{scope}/" in v or f"({scope})" in v
                   for v in table.values()), scope
    inside = [v for v in table.values() if "full_attn" in v]
    assert inside and all("loop_blocks" in v for v in inside)


def test_the_engine_drives_the_family_end_to_end(rehearsal):
    """The rehearsal's engine, after its steps: the planner's rows charge a
    block its three passes, the gauge reads ONE carry, the MFU gauge
    counts applied parameters, the one stage's program walks the loop, and
    `evaluate()` reports the last exit's accuracy."""
    from oobleck_tpu.models import base

    _, engine, _, _ = rehearsal
    carry = 32 * (2 * 64 + 2 * 2 * 64 + 2 * 4)
    assert [p.mem_activation for p in engine.profiles[:3]] == [
        carry, 2 * carry, 2 * carry]
    fpt, _, _ = engine._flops_info()
    assert fpt == 6.0 * base.applied_param_count(engine.model) + (
        6.0 * 2 * 2 * 64 * 32)
    pipe = engine.pipelines[0]
    assert pipe.virtual_stages == 1
    assert pipe.stages[0].walks == ((0, 1, 2, 1, 2, 3),)
    assert np.isfinite(engine.evaluate(num_batches=1))
    correct, count = pipe.last_eval_metrics
    assert count == 2 * 31 and 0 <= correct <= count


@pytest.mark.parametrize("fault", ref.FAULTS[1:])
def test_a_planted_fault_reads_over_the_limit_in_the_runner_s_check(
        rehearsal, monkeypatch, fault):
    """The reference with the fault in the reference's place: the
    program, which has none, then disagrees with it by what the fault
    moves, through the runner's own check and its own limit."""
    from benchmarks.reference import ouro
    from benchmarks.runners import train as base
    from benchmarks.runners import train_ouro

    _, engine, _, ctx = rehearsal
    rc = ref.RefConfig.from_config(TINY)
    params = ref.init_params(ctx.seed, rc)
    base.install_weights(engine, params)
    honest = ouro.loss_and_grads
    monkeypatch.setattr(
        train_ouro.ref, "loss_and_grads",
        lambda p, t, c, mode: honest(p, t, c, mode, fault))
    numbers = train_ouro.check_against_reference(ctx, engine, params,
                                                 ctx.seed)
    (check,) = base.checks_from(numbers, ctx.cell["correct"])
    assert not check["ok"] and check["value"] > 0.25, numbers
