"""kind: train_kimi_linear -- steady training steps of the Kimi-Linear
family (Kimi Delta Attention three layers in four, one latent-attention
layer without positions, a dense first layer, then sigmoid-routed experts
beside a shared one) through `OobleckEngine.train()`, checked against
`reference/kimi_linear.py`.

Nothing of the run is this file's own: `runners/train.py`'s
`install_weights`, `measure` and `checks_from`; `runners/train_lfm2.py`'s
`UniformCorpus` (through `build_engine`) and `step_gradients`;
`runners/train_deepseek_v3.py`'s `build_engine` (the JOB states its
sequence length) and `probe_held_rows` (the checked sequence's routing read
by the program's own `routing_probe`, once before the warm-up and once
AFTER the window has closed); `runners/train_nemotron_h.py`'s
`backward_scopes` (a traced run hands `readers/scope_ms_per_step.py` the
scope of every instruction of the `jit_bwd` the window ran). What differs:
the reference, whose KDA layers walk the recurrence one position after
another with a decay a channel where the program runs it in chunks, takes
its decayed products in levels and inverts a triangular system a chunk.

`train` names the LATENT layers alone (`num_layers` 1 of the 5 blocks run)
and their 32 heads, as every runner of mixed kinds hands the count of the
layers whose kernels the accepted readers count (`train_nemotron_h.py`): the
accepted `flash_mla_*` metrics read this cell unedited.

`correct` is decided on four numbers: the one norm over all 602 M
parameters (`grad_rel_err`), the routing (`routing_mismatch_share`), the
same norm over the LATENT mixers' 29 M alone (`mla_grad_rel_err`: one layer
in five, 1 % of the whole norm, which a fault inside it does not move), and
the WORST-LEAF relative error over the KDA layers' small leaves (`A_log`,
`dt_bias`, the three convolutions' taps, the gated norm's scale and the
layer's two norms: nothing in the whole norm, and all that a wrong backward
of the decay would move): `kda_leaf_rel_err_max`, said with the leaf that
reads it.
"""

from __future__ import annotations

import time

from benchmarks import traffic
from benchmarks.reference import kimi_linear as ref
from benchmarks.runners import train as base
from benchmarks.runners.train_deepseek_v3 import build_engine, probe_held_rows
from benchmarks.runners.train_lfm2 import step_gradients
from benchmarks.runners.train_nemotron_h import backward_scopes

MATRICES = ("w_q", "w_k", "w_v", "w_fa", "w_fb", "w_b", "w_ga", "w_gb", "w_o")


def kda_leaves(rc: ref.RefConfig, tree) -> dict:
    """`{"blocks.0.kda.A_log": leaf, ...}`: every leaf of the KDA blocks
    but the matrices: what the rule's decay path, the convolutions and the
    norms train, whose gradients no matrix's norm would show."""
    small = {}
    for b in range(rc.num_layers):
        if rc.kind(b) == ref.KDA:
            block = tree["blocks"][b]
            small[f"blocks.{b}.ln_op.scale"] = block["ln_op"]["scale"]
            small[f"blocks.{b}.ln_ff.scale"] = block["ln_ff"]["scale"]
            small.update({f"blocks.{b}.kda.{k}": v
                          for k, v in block[ref.KDA].items()
                          if k not in MATRICES})
    return small


def latent_leaves(rc: ref.RefConfig, tree) -> list:
    """The latent mixers' subtrees (`wq`, `wkv_a`, the latent's norm,
    `wkv_b`, `wo` of every latent block): 29 M of 602 M parameters, whose
    gradients are 1 % of the one norm over everything, so that a fault in
    the latent layers alone (rotary left on) does not move that norm."""
    return [tree["blocks"][b]["attn"] for b in range(rc.num_layers)
            if rc.kind(b) == ref.MLA]


def sum_of_squares(tree):
    import jax
    import jax.numpy as jnp

    return sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
               for x in jax.tree.leaves(tree))


def part_errors(rc: ref.RefConfig, diff, grads):
    """Of a tree of differences against the reference's gradients:
    (`mla_grad_rel_err`, the relative error of every leaf of `kda_leaves`,
    stacked in that order)."""
    import jax.numpy as jnp

    sq = sum_of_squares
    small = kda_leaves(rc, grads)
    by_leaf = jnp.stack([jnp.sqrt(sq(d) / sq(small[k]))
                         for k, d in kda_leaves(rc, diff).items()])
    latent = jnp.sqrt(sq(latent_leaves(rc, diff))
                      / sq(latent_leaves(rc, grads)))
    return latent, by_leaf


def worst_leaf(rc: ref.RefConfig, tree, by_leaf) -> dict:
    """`kda_leaf_rel_err_max` and the leaf that reads it."""
    named = dict(zip(kda_leaves(rc, tree), (float(x) for x in by_leaf)))
    worst = max(named, key=named.get)
    return {"kda_leaf_rel_err_max": named[worst],
            "kda_leaf_rel_err_at": worst}


def init_params(cell: dict, rc: ref.RefConfig, seed: int):
    """The seed's weights, the selection bias balanced on sequences of the
    cell's own length."""
    return ref.init_params(seed, rc, (ref.BALANCE_TOKENS[0],
                                      cell["traffic"]["seq_len"]))


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
        diff = jax.tree.map(
            lambda a, b: a.astype(jnp.float32) * scale - b, eng_grads, grads)
        return (loss, sum_of_squares(grads), sum_of_squares(diff),
                ref.mismatch_share(chosen, own),
                *part_errors(rc, diff, grads))

    *scalars, by_leaf = compare(
        params, jnp.asarray(seq), eng_grads, [jnp.asarray(c) for c in chosen])
    loss_ref, ref_sq, diff_sq, mismatch, latent = (float(x) for x in scalars)
    pipe.grads = {}
    return {"loss_engine": loss_eng, "loss_reference": loss_ref,
            "loss_rel_err": abs(loss_eng - loss_ref) / abs(loss_ref),
            "grad_rel_err": (diff_sq / ref_sq) ** 0.5,
            "grad_norm_reference": ref_sq ** 0.5,
            "routing_mismatch_share": mismatch,
            "mla_grad_rel_err": latent,
            **worst_leaf(rc, params, by_leaf),
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
    params = init_params(ctx.cell, rc, ctx.seed)
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
    scopes = backward_scopes(engine) if ctx.trace else None
    rate = m["steps"] * m["tokens_per_step"] / m["elapsed_s"] / chips
    job = ctx.cell["traffic"]
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
                      "num_layers": len(rc.full_attn_layers),
                      "hidden_size": rc.hidden_size,
                      "num_heads": rc.num_heads}},
    }
