"""kind: train_phi4flash -- steady training steps of the Phi-4-mini-flash
family (Mamba-1, differential attention, and a cross-decoder whose Gated
Memory Unit and cross-attention read another layer's scan output and keys
and values through the layer list's carry) through `OobleckEngine.train()`,
checked against `reference/phi4flash.py`.

The run is `runners/train.py`'s (`measure`, `checks_from`, and
`install_weights` behind this file's, which pads the vocabulary to the
program's rows); the engine is `runners/train_deepseek_v3.py`'s
(`build_engine`: the JOB states its sequence length, the corpus is token
ids uniform over the vocabulary rows held); one step's gradients are
`runners/train_lfm2.py`'s (`step_gradients`); the table that lets a traced
run time the model's parts is `runners/train_nemotron_h.py`'s
(`backward_scopes`). No block is routed, so there is no routing probe and
`correct` is decided on `grad_rel_err` alone.

Beside the one norm over all 543 M parameters that decides `correct`, the
check says the WORST-LEAF relative error over the leaves that norm cannot
see (`A_log`, `D`, `dt_bias`, `w_dt`, the conv's taps and bias, the four
`lambda` vectors: a few numbers each beside matrices of millions):
`sscan_leaf_rel_err_max` and the leaf that reads it, printed and not
limited. After the window the program's own counters say which kernels and
how large a carry its traced programs hold (`program_counters`).
"""

from __future__ import annotations

import time

from benchmarks import traffic
from benchmarks.reference import phi4flash as ref
from benchmarks.runners import train as base
from benchmarks.runners.train_deepseek_v3 import build_engine
from benchmarks.runners.train_lfm2 import step_gradients
from benchmarks.runners.train_nemotron_h import backward_scopes

SMALL = ("A_log", "D", "dt_bias", "w_dt", "conv_taps", "conv_bias",
         "lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2")
COUNTERS = ("oobleck_sscan_calls_total", "oobleck_sscan_chunks_total",
            "oobleck_flash_diff_calls_total", "oobleck_flash_live_pairs",
            "oobleck_flash_residuals_named_total",
            "oobleck_pipeline_carry_bytes_max")


def small_leaves(tree) -> dict:
    """`{"blocks.0.mamba.A_log": leaf, ...}`: the leaves of `SMALL`, whose
    gradients no matrix's norm would show."""
    small = {}
    for b, block in enumerate(tree["blocks"]):
        for part in ("mamba", "attn"):
            small.update({f"blocks.{b}.{part}.{k}": v
                          for k, v in block.get(part, {}).items()
                          if k in SMALL})
    return small


def install_weights(engine, params) -> None:
    """`runners/train.py`'s, the vocabulary padded with zero rows to the
    rows the program holds (25,008 held rows are no multiple of 128: the
    program pads them to 25,088, and its loss never reads the padding)."""
    import jax.numpy as jnp

    pad = engine.model.config.padded_vocab_size - params["head"]["w"].shape[1]
    base.install_weights(engine, {
        "embed": {"wte": jnp.pad(params["embed"]["wte"], ((0, pad), (0, 0)))},
        "blocks": params["blocks"],
        "head": dict(params["head"],
                     w=jnp.pad(params["head"]["w"], ((0, 0), (0, pad))))})


def check_against_reference(ctx, engine, params, seed: int) -> dict:
    """One seeded sequence, repeated to fill pipeline 0's share of a step,
    through the engine's forward and backward; beside it the reference's
    loss and gradients of that sequence, float32 at HIGHEST. The padding
    rows' gradients (`pad_grad_abs_max`, printed: they have to be 0) are
    cut off before the comparison."""
    import jax
    import jax.numpy as jnp

    rc = ref.RefConfig.from_config(ctx.config)
    pipe = engine.pipelines[0]
    seq = traffic.token_block(seed, 1, engine.seq_len, rc.vocab_size)
    loss_eng, eng_grads, scale = step_gradients(engine, seq)
    home = next(iter(params["head"]["w"].devices()))
    eng_grads = jax.device_put(eng_grads, home)
    rows = rc.vocab_size
    wte, w = eng_grads["embed"]["wte"], eng_grads["head"]["w"]
    pad_grad = max(float(jnp.max(jnp.abs(wte[rows:]), initial=0.0)),
                   float(jnp.max(jnp.abs(w[:, rows:]), initial=0.0)))
    eng_grads = {"embed": {"wte": wte[:rows]}, "blocks": eng_grads["blocks"],
                 "head": dict(eng_grads["head"], w=w[:, :rows])}

    @jax.jit
    def compare(params, tokens, eng_grads):
        loss, grads = ref.loss_and_grads(params, tokens, rc, "highest")
        sq = lambda t: sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                           for x in jax.tree.leaves(t))
        diff = jax.tree.map(
            lambda a, b: a.astype(jnp.float32) * scale - b, eng_grads, grads)
        small = small_leaves(grads)
        by_leaf = jnp.stack([jnp.sqrt(sq(d) / sq(small[k]))
                             for k, d in small_leaves(diff).items()])
        return loss, sq(grads), sq(diff), by_leaf

    *scalars, by_leaf = compare(params, jnp.asarray(seq), eng_grads)
    loss_ref, ref_sq, diff_sq = (float(x) for x in scalars)
    by_leaf = dict(zip(small_leaves(params), (float(x) for x in by_leaf)))
    worst = max(by_leaf, key=by_leaf.get)
    pipe.grads = {}
    return {"loss_engine": loss_eng, "loss_reference": loss_ref,
            "loss_rel_err": abs(loss_eng - loss_ref) / abs(loss_ref),
            "grad_rel_err": (diff_sq / ref_sq) ** 0.5,
            "grad_norm_reference": ref_sq ** 0.5,
            "pad_grad_abs_max": pad_grad,
            "sscan_leaf_rel_err_max": by_leaf[worst],
            "sscan_leaf_rel_err_at": worst}


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
    install_weights(engine, params)
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
                      "num_layers": sum(
                          k in (ref.FULL_SOURCE, ref.CROSS) for k in rc.kinds),
                      "hidden_size": rc.hidden_size,
                      "num_heads": rc.num_heads}},
    }
