"""kind: train_qwen3_next -- steady training steps of the Qwen3-Next family
(Gated DeltaNet mixers three layers in four, one gated softmax-attention
layer, softmax-routed experts beside a gated shared one in every layer)
through `OobleckEngine.train()`, checked against `reference/qwen3_next.py`.

Nothing of the run is this file's own: `runners/train.py`'s
`install_weights`, `measure` and `checks_from`; `runners/train_lfm2.py`'s
`UniformCorpus` (through `build_engine`) and `step_gradients`;
`runners/train_deepseek_v3.py`'s `build_engine` (the JOB states its
sequence length) and `probe_held_rows` (the checked sequence's routing read
by the program's own `routing_probe`, once before the warm-up and once
AFTER the window has closed); `runners/train_nemotron_h.py`'s
`backward_scopes` (a traced run hands `readers/scope_ms_per_step.py` the
scope of every instruction of the `jit_bwd` the window ran). What differs:
the reference, whose Gated DeltaNet layers walk the recurrence one position
after another where the program runs it in chunks and inverts a triangular
system a chunk; and no selection bias to balance: this family's router has
none, so the rows on the held experts are what the seed's router gives
(printed before the warm-up and after the window, `held_rows`).

`train` names the ATTENTION layer (one of the four) and its 16 heads: the
plain flash kernels' counts and times are read against that; their
rooflines at heads of 256 take heads and head width from the configuration
(`readers/flash_geometry_roofline_pct.py`).

Beside the one norm over all 424 M parameters that decides `correct`, the
check says the WORST-LEAF relative error over the Gated DeltaNet layers'
small leaves (`A_log`, `dt_bias`, the conv's taps, the gated norm's weight
and the layer's two norms: a few thousand numbers a layer, nothing in that
norm): `gdn_leaf_rel_err_max` and the leaf that reads it, printed and not
limited.
"""

from __future__ import annotations

import time

from benchmarks import traffic
from benchmarks.reference import qwen3_next as ref
from benchmarks.runners import train as base
from benchmarks.runners.train_deepseek_v3 import build_engine, probe_held_rows
from benchmarks.runners.train_lfm2 import step_gradients
from benchmarks.runners.train_nemotron_h import backward_scopes

PROJECTIONS = ("w_qkvz", "w_ba", "w_out")


def gdn_leaves(rc: ref.RefConfig, tree) -> dict:
    """`{"blocks.0.gdn.A_log": leaf, ...}`: every leaf of the Gated
    DeltaNet blocks but the matrices: what the rule's decay path, the conv
    and the norms train, whose gradients no matrix's norm would show."""
    small = {}
    for b in range(rc.num_layers):
        if rc.kind(b) == ref.GDN:
            block = tree["blocks"][b]
            small[f"blocks.{b}.ln_op.scale"] = block["ln_op"]["scale"]
            small[f"blocks.{b}.ln_ff.scale"] = block["ln_ff"]["scale"]
            small.update({f"blocks.{b}.gdn.{k}": v
                          for k, v in block[ref.GDN].items()
                          if k not in PROJECTIONS})
    return small


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
        small = gdn_leaves(rc, grads)
        by_leaf = jnp.stack([
            jnp.sqrt(sq(d) / sq(small[k]))
            for k, d in gdn_leaves(rc, diff).items()])
        return (loss, sq(grads), sq(diff), ref.mismatch_share(chosen, own),
                by_leaf)

    *scalars, by_leaf = compare(
        params, jnp.asarray(seq), eng_grads, [jnp.asarray(c) for c in chosen])
    loss_ref, ref_sq, diff_sq, mismatch = (float(x) for x in scalars)
    by_leaf = dict(zip(gdn_leaves(rc, params), (float(x) for x in by_leaf)))
    worst = max(by_leaf, key=by_leaf.get)
    pipe.grads = {}
    return {"loss_engine": loss_eng, "loss_reference": loss_ref,
            "loss_rel_err": abs(loss_eng - loss_ref) / abs(loss_ref),
            "grad_rel_err": (diff_sq / ref_sq) ** 0.5,
            "grad_norm_reference": ref_sq ** 0.5,
            "routing_mismatch_share": mismatch,
            "gdn_leaf_rel_err_max": by_leaf[worst],
            "gdn_leaf_rel_err_at": worst,
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
                      "num_layers": sum(rc.kind(b) == ref.ATTN
                                        for b in range(rc.num_layers)),
                      "hidden_size": rc.hidden_size,
                      "num_heads": rc.num_heads}},
    }
