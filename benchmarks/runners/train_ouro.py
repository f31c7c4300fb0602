"""kind: train_ouro -- steady training steps of the Ouro family (blocks
gone through `total_ut_steps` times over one set of weights, sandwich
norms, an exit gate and a loss over all exits) through
`OobleckEngine.train()`, checked against `reference/ouro.py`.

The run is `runners/train.py`'s (`install_weights`, `measure`,
`checks_from`); the engine is `runners/train_deepseek_v3.py`'s
(`build_engine`: the JOB states its sequence length, the corpus is token
ids uniform over the vocabulary); one step's gradients are
`runners/train_lfm2.py`'s (`step_gradients`); the table that lets a traced
run time the model's parts is `runners/train_nemotron_h.py`'s
(`backward_scopes`). No block is routed, so there is no routing probe and
`correct` is decided on `grad_rel_err` alone.

Beside the one norm over all 510 M parameters that decides `correct`, the
check says, printed and not limited: the loss's relative error; the
WORST-LEAF relative error over the leaves that norm cannot see (the gate's
`w_g` and `b_g`, the final norm, the four norm scales a block: a few
thousand numbers beside matrices of millions), `small_leaf_rel_err_max` and
the leaf that reads it; and each exit's own cross-entropy
(`exit_cross_entropy`, the reference's). After the window the program's
own counters say what its traced programs hold of the loop
(`program_counters`). `train.num_layers` is the attention VISITS a
microbatch makes (passes x blocks): what the flash kernels' readers
multiply a call's need by.
"""

from __future__ import annotations

import time

from benchmarks import traffic
from benchmarks.reference import ouro as ref
from benchmarks.runners import train as base
from benchmarks.runners.train_deepseek_v3 import build_engine
from benchmarks.runners.train_lfm2 import step_gradients
from benchmarks.runners.train_nemotron_h import backward_scopes

SMALL = ("scale", "w_g", "b_g")
COUNTERS = ("oobleck_loop_block_visits_total", "oobleck_loop_exits_total",
            "oobleck_pipeline_stage_visits_total",
            "oobleck_pipeline_carry_bytes_max", "oobleck_rotary_calls_total",
            "oobleck_flash_residuals_named_total")


def small_leaves(tree) -> dict:
    """`{"blocks.5.close.w_g": leaf, ...}`: the norm scales and the gate's
    two leaves, whose gradients no matrix's norm would show."""
    import jax

    small = {}
    for b, block in enumerate(tree["blocks"]):
        for path, leaf in jax.tree_util.tree_leaves_with_path(block):
            keys = [k.key for k in path]
            if keys[-1] in SMALL:
                small[f"blocks.{b}." + ".".join(keys)] = leaf
    return small


def check_against_reference(ctx, engine, params, seed: int) -> dict:
    """One seeded sequence, repeated to fill pipeline 0's share of a step,
    through the engine's forward and backward; beside it the reference's
    loss and gradients of that sequence, float32 at HIGHEST."""
    import jax
    import jax.numpy as jnp

    rc = ref.RefConfig.from_config(ctx.config)
    pipe = engine.pipelines[0]
    seq = traffic.token_block(seed, 1, engine.seq_len, rc.vocab_size)
    loss_eng, eng_grads, scale = step_gradients(engine, seq)
    home = next(iter(params["head"]["w"].devices()))
    eng_grads = jax.device_put(eng_grads, home)

    @jax.jit
    def compare(params, tokens, eng_grads):
        (loss, exit_ce), grads = ref.loss_and_grads(params, tokens, rc,
                                                    "highest")
        sq = lambda t: sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                           for x in jax.tree.leaves(t))
        diff = jax.tree.map(
            lambda a, b: a.astype(jnp.float32) * scale - b, eng_grads, grads)
        small = small_leaves(grads)
        by_leaf = jnp.stack([jnp.sqrt(sq(d) / sq(small[k]))
                             for k, d in small_leaves(diff).items()])
        return loss, sq(grads), sq(diff), exit_ce, by_leaf

    *scalars, exit_ce, by_leaf = compare(params, jnp.asarray(seq), eng_grads)
    loss_ref, ref_sq, diff_sq = (float(x) for x in scalars)
    by_leaf = dict(zip(small_leaves(params), (float(x) for x in by_leaf)))
    worst = max(by_leaf, key=by_leaf.get)
    pipe.grads = {}
    return {"loss_engine": loss_eng, "loss_reference": loss_ref,
            "loss_rel_err": abs(loss_eng - loss_ref) / abs(loss_ref),
            "grad_rel_err": (diff_sq / ref_sq) ** 0.5,
            "grad_norm_reference": ref_sq ** 0.5,
            "small_leaf_rel_err_max": by_leaf[worst],
            "small_leaf_rel_err_at": worst,
            "exit_cross_entropy": [float(x) for x in exit_ce]}


def program_counters() -> dict:
    """What the program's own registry says of the mechanisms this cell
    exists for, `{family: {label values: value}}`. A program without a
    family says nothing of it."""
    from oobleck_tpu.utils import metrics

    out = {}
    for metric in metrics.registry().snapshot()["metrics"]:
        if metric["name"] in COUNTERS:
            out[metric["name"]] = {
                ",".join(s["labels"].values()) or "all": s["value"]
                for s in metric["series"]}
    return out


def run(ctx) -> dict:
    import jax

    phases, last = {}, [time.monotonic()]

    def phase_ends(name: str) -> None:
        now = time.monotonic()
        phases[name + "_s"] = now - last[0]
        last[0] = now

    chips = int(ctx.cell["chips"])
    devices = jax.devices()[:chips]
    engine = build_engine(ctx, [f"10.0.0.{i}" for i in range(chips)], devices)
    rc = ref.RefConfig.from_config(ctx.config)
    ctx.say_memory("engine_built")
    phase_ends("build_engine")
    params = ref.init_params(ctx.seed, rc)
    base.install_weights(engine, params)
    ctx.say_memory("weights_installed")
    phase_ends("weights")
    numbers = check_against_reference(ctx, engine, params, ctx.seed)
    del params
    ctx.say("train_check", **numbers)
    ctx.say_memory("checked")
    phase_ends("check")
    engine.train()          # warm-up: `warmup_steps` steps, the first compiles
    ctx.say_memory("warmed_up")
    phase_ends("warm_up")
    m = base.measure(ctx, engine)
    ctx.say("setup_phases", setup_s=ctx.setup_s, **phases,
            before_runner_s=ctx.setup_s - sum(phases.values()))
    ctx.say("program_counters", **program_counters())
    scopes = backward_scopes(engine) if ctx.trace else None
    rate = m["steps"] * m["tokens_per_step"] / m["elapsed_s"] / chips
    job = ctx.cell["traffic"]
    return {
        "attempted": m["steps"], "failed": m["failed"],
        "checks": base.checks_from(numbers, ctx.cell["correct"]),
        "end_to_end": {"train_tokens_per_s": rate},
        "layer_data": {
            "hist": m["hist"], "chips": chips, "scopes": scopes,
            "train": {"tokens_per_s": rate, "seq_len": engine.seq_len,
                      "microbatch_size": job["microbatch_size"],
                      "microbatches_run": m["steps"] * (
                          job["global_batch"] // job["microbatch_size"]),
                      "n_params": rc.num_params(),
                      "num_layers": rc.num_passes * rc.num_layers,
                      "hidden_size": rc.hidden_size,
                      "num_heads": rc.num_heads}},
    }
