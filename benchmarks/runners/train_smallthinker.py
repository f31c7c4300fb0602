"""kind: train_smallthinker -- steady training steps of the SmallThinker
family (sliding-window attention with rotary three layers in four, one
full-attention layer without a positional term, a router that reads the
block's input before the attention, ReGLU experts) through
`OobleckEngine.train()`, checked against `reference/smallthinker.py`.

Nothing of the run is this file's own: `runners/train.py`'s
`install_weights`, `measure` and `checks_from`; `runners/train_lfm2.py`'s
`UniformCorpus` (through `build_engine`) and `step_gradients`;
`runners/train_deepseek_v3.py`'s `build_engine` (the JOB states its
sequence length) and `probe_held_rows` (the checked sequence's routing read
by the program's own `routing_probe`, once before the warm-up and once
AFTER the window has closed); `runners/train_nemotron_h.py`'s
`backward_scopes` (a traced run hands `readers/scope_ms_per_step.py` the
scope of every instruction of the `jit_bwd` the window ran). What differs:
the reference, whose attention builds the band mask from positions against
ALL keys where the program's kernels visit the band's block pairs alone,
and whose router is the published top-6-then-softmax; and no selection
bias to balance: this family's router has none, so the rows on the held
experts are what the seed's router gives (printed before the warm-up and
after the window, `held_rows`), and with them the row tiles in use
(`expert_tiles` in the check's line). Beside the one norm over all 370 M
parameters that decides `correct`, the check says the relative error over
the four layers' ATTENTION leaves alone (`W_q`, `W_k`, `W_v`, `W_o`: what
the flash kernels' gradients reach) and those leaves' share of the whole
gradient's norm: `attention_grad_rel_err`, `attention_grad_norm_share`,
printed and not limited.

`train` names TWO kinds of attention layer. `num_layers` is the count of
FULL-attention layers (one of the four) and `num_heads` their 28 heads:
the plain flash kernels' counts, times and rooflines are read against that
(`flash_fwd_calls_per_need`, `flash_dq_ms`, `flash_dkv_ms`,
`flash_d128_*_roofline`, heads and head width from the configuration).
`window_layers` is the count of WINDOWED layers (three) and `window` their
window: `readers/window_roofline_pct.py` and `window_calls_per_need.py`
read the `flash_swa_*` kernels against those.
"""

from __future__ import annotations

import time

from benchmarks import traffic
from benchmarks.reference import smallthinker as ref
from benchmarks.runners import train as base
from benchmarks.runners.train_deepseek_v3 import build_engine, probe_held_rows
from benchmarks.runners.train_lfm2 import step_gradients
from benchmarks.runners.train_nemotron_h import backward_scopes


def expert_tiles(config: dict, chosen) -> dict:
    """The row tiles `ops/moe.routed_experts` has in use for the checked
    sequence, by routed layer: each held expert's rows padded to whole
    tiles of `choose_row_tile`'s size (one at least)."""
    import numpy as np

    from oobleck_tpu.ops.moe import choose_row_tile

    held, offset = config["num_experts_held"], config.get("expert_offset", 0)
    experts = config["moe_num_primary_experts"]
    tile = choose_row_tile(chosen[0].size, experts)
    by_layer = []
    for c in chosen:
        rows = np.bincount(np.asarray(c).ravel(),
                           minlength=experts)[
            offset:offset + held]
        by_layer.append(int(np.maximum(-(-rows // tile), 1).sum()))
    return {"tile": tile, "by_layer": by_layer}


def check_against_reference(ctx, engine, params, seed: int) -> dict:
    """One seeded sequence, repeated to fill pipeline 0's share of a step,
    through the engine's forward and backward; beside it the reference's
    loss and gradients of that sequence, float32 at HIGHEST, under the
    program's expert choices. Also the held rows the probe read."""
    import jax
    import jax.numpy as jnp

    rc = ref.RefConfig.from_config(ctx.config)
    pipe = engine.pipelines[0]
    seq = traffic.token_block(seed, 1, engine.seq_len, rc.vocab_size)
    chosen, held_rows = probe_held_rows(engine, seq)
    loss_eng, eng_grads, scale = step_gradients(engine, seq)
    home = next(iter(params["head"]["w"].devices()))
    eng_grads = jax.device_put(eng_grads, home)

    @jax.jit
    def compare(params, tokens, eng_grads, chosen):
        (loss, own), grads = ref.loss_and_grads(params, tokens, rc,
                                                "highest", chosen)
        sq = lambda t: sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                           for x in jax.tree.leaves(t))
        diff = jax.tree.map(
            lambda a, b: a.astype(jnp.float32) * scale - b, eng_grads, grads)
        attention = lambda t: [block["attn"] for block in t["blocks"]]
        return (loss, sq(grads), sq(diff), ref.mismatch_share(chosen, own),
                sq(attention(grads)), sq(attention(diff)))

    loss_ref, ref_sq, diff_sq, mismatch, attn_sq, attn_diff_sq = (
        float(x) for x in compare(
            params, jnp.asarray(seq), eng_grads,
            [jnp.asarray(c) for c in chosen]))
    pipe.grads = {}
    return {"loss_engine": loss_eng, "loss_reference": loss_ref,
            "loss_rel_err": abs(loss_eng - loss_ref) / abs(loss_ref),
            "grad_rel_err": (diff_sq / ref_sq) ** 0.5,
            "grad_norm_reference": ref_sq ** 0.5,
            "routing_mismatch_share": mismatch,
            "attention_grad_rel_err": (attn_diff_sq / attn_sq) ** 0.5,
            "attention_grad_norm_share": (attn_sq / ref_sq) ** 0.5,
            "expert_tiles": expert_tiles(ctx.config, chosen),
            "held_rows": held_rows}


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
    before = numbers.pop("held_rows")
    ctx.say("train_check", **numbers)
    ctx.say_memory("checked")
    phase_ends("check")
    engine.train()          # warm-up: `warmup_steps` steps, the first compiles
    ctx.say_memory("warmed_up")
    phase_ends("warm_up")
    m = base.measure(ctx, engine)
    # The window has closed and the trace has stopped: the same sequence
    # through the probe again, on the weights the window trained.
    seq = traffic.token_block(ctx.seed, 1, engine.seq_len, rc.vocab_size)
    _, after = probe_held_rows(engine, seq)
    # 1 where the second probe ran the program the first compiled.
    probe = getattr(engine.model, "_routing_probe_fn", None)
    ctx.say("held_rows", before=before, after=after,
            probe_programs=probe._cache_size() if probe else None)
    ctx.say("setup_phases", setup_s=ctx.setup_s, **phases,
            before_runner_s=ctx.setup_s - sum(phases.values()))
    ctx.say("program_counters", **program_counters())
    scopes = backward_scopes(engine) if ctx.trace else None
    rate = m["steps"] * m["tokens_per_step"] / m["elapsed_s"] / chips
    job = ctx.cell["traffic"]
    windowed = sum(rc.sliding_window_layout)
    return {
        "attempted": m["steps"], "failed": m["failed"],
        "checks": base.checks_from(numbers, ctx.cell["correct"]),
        "end_to_end": {"train_tokens_per_s": rate},
        "layer_data": {
            "hist": m["hist"], "chips": chips,
            "held_rows": {"before": before, "after": after},
            "scopes": scopes,
            "train": {"tokens_per_s": rate, "seq_len": engine.seq_len,
                      "microbatch_size": job["microbatch_size"],
                      "microbatches_run": m["steps"] * (
                          job["global_batch"] // job["microbatch_size"]),
                      "n_params": rc.num_params(),
                      "num_layers": rc.num_layers - windowed,
                      "window_layers": windowed,
                      "window": rc.sliding_window_size,
                      "hidden_size": rc.hidden_size,
                      "num_heads": rc.num_heads}},
    }


COUNTERS = ("oobleck_flash_live_pairs", "oobleck_flash_window_calls_total",
            "oobleck_flash_residuals_named_total",
            "oobleck_moe_reglu_calls_total",
            "oobleck_moe_early_router_calls_total",
            "oobleck_moe_softmax_routed_calls_total",
            "oobleck_pipeline_grad_accumulations_total")


def program_counters() -> dict:
    """What the program's own registry says of the mechanisms this cell
    exists for, `{family: {label values: value}}`: the grid steps a head of
    each flash kernel, the windowed kernels, ReGLU calls and early-router
    calls built into traced programs, the leaves summed inside `moe_tgmm`.
    A program without a family says nothing of it."""
    from oobleck_tpu.utils import metrics

    out = {}
    for metric in metrics.registry().snapshot()["metrics"]:
        if metric["name"] in COUNTERS:
            out[metric["name"]] = {
                ",".join(s["labels"].values()) or "all": s["value"]
                for s in metric["series"]}
    return out
