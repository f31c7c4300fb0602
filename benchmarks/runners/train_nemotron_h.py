"""kind: train_nemotron_h -- steady training steps of the Nemotron-H family
(every layer ONE mixer: Mamba-2, attention, or experts without a gate
beside a shared one) through `OobleckEngine.train()`, checked against
`reference/nemotron_h.py`.

The run is `runners/train.py`'s (`install_weights`, `measure`,
`checks_from`), the corpus and the way `correct` is decided are
`runners/train_lfm2.py`'s (`UniformCorpus`, `step_gradients`; the reference
handed the PROGRAM's expert choices, read by the program's own
`routing_probe`: `grad_rel_err`, and `routing_mismatch_share` beside it),
the engine and the two probes of the checked sequence's routing, one before
the warm-up and one AFTER the window has closed, are
`runners/train_deepseek_v3.py`'s (`build_engine`: the JOB states its
sequence length; `probe_held_rows`: both readings of the program's gauge
`oobleck_moe_held_rows{layer}` go on to `readers/held_rows_drift_pct.py`).
What differs: the reference, whose Mamba-2 layers walk the recurrence one
position after another where the program runs it in chunks.

`train` names the ATTENTION layers (one of the seven) and their 32 heads,
as `train_lfm2.py` does for its model: the plain flash kernels' counts and
times are read against that; their rooflines at this model's geometry
(heads of 128 that are no `hidden_size // num_heads`) take heads and head
width from the configuration (`readers/flash_geometry_roofline_pct.py`).
Where a set-up's seconds went is said after the window (`setup_phases`).

Two things this runner adds to what it shares. (1) The check says, beside
the one norm over all 528 M parameters that decides `correct`, the
WORST-LEAF relative error over the Mamba-2 layers' own leaves (`A_log`,
`dt_bias`, `D`, the conv's taps and bias, the gated norm's scale: a few
hundred numbers a layer, nothing in that norm): `mamba_leaf_rel_err_max`
and the leaf that reads it, printed and not limited. (2) A traced run
hands `readers/scope_ms_per_step.py` the table it needs to time the
model's parts, the chunked scan first: which `jax.named_scope` each
instruction of `jit_bwd` was built under, from the text of the executable
the window ran (`backward_scopes`: after the window has closed and the
trace has stopped, so neither sees it).
"""

from __future__ import annotations

import time

from benchmarks import traffic
from benchmarks.readers.scope_ms_per_step import scopes_of_text
from benchmarks.reference import nemotron_h as ref
from benchmarks.runners import train as base
from benchmarks.runners.train_deepseek_v3 import build_engine, probe_held_rows
from benchmarks.runners.train_lfm2 import step_gradients


def init_params(cell: dict, rc: ref.RefConfig, seed: int):
    """The seed's weights, the selection bias balanced on sequences of the
    cell's own length."""
    return ref.init_params(seed, rc, (ref.BALANCE_TOKENS[0],
                                      cell["traffic"]["seq_len"]))


def mamba_leaves(rc: ref.RefConfig, tree) -> dict:
    """`{"blocks.0.mamba.A_log": leaf, ...}`: every leaf of the Mamba-2
    blocks but the two projections: what the recurrence's decay path, the
    conv and the norms train, whose gradients no matrix's norm would show."""
    small = {}
    for b, kind in enumerate(rc.pattern):
        if kind == ref.MAMBA:
            block = tree["blocks"][b]
            small[f"blocks.{b}.ln_op.scale"] = block["ln_op"]["scale"]
            small.update({f"blocks.{b}.mamba.{k}": v
                          for k, v in block["mamba"].items()
                          if k not in ("w_in", "w_out")})
    return small


def backward_scopes(engine) -> dict | None:
    """`{"jit_bwd": {"%fusion.123": op_name, ...}}` of a one-stage
    pipeline's backward program, lowered again at the avals its calls had
    (as `execution/precompile.py` does) and compiled: the persistent cache
    hands back the executable the window ran, whose text names the scope
    of every instruction. None where the pipeline is not one stage."""
    import jax

    pipe = engine.pipelines[0]
    local = [st for st in pipe.stages if st.is_local]
    if len(pipe.stages) != 1 or len(local) != 1 or len(local[0].chunks) != 1:
        return None
    (st,) = local
    avals = tuple(
        jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=a.sharding), pipe.params[li])
        for li in st.chunks[0])
    sample = pipe.model.sample_batch(pipe.microbatch_size, pipe.seq_len)
    batch = {k: jax.ShapeDtypeStruct(v.shape, v.dtype,
                                     sharding=st.batch_sharding)
             for k, v in sample.items()}
    compiled = st.bwd[0].lower(avals, avals, None, batch).compile()
    return {"jit_bwd": scopes_of_text(compiled.as_text())}


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
        small = mamba_leaves(rc, grads)
        by_leaf = jnp.stack([
            jnp.sqrt(sq(d) / sq(small[k]))
            for k, d in mamba_leaves(rc, diff).items()])
        return (loss, sq(grads), sq(diff), ref.mismatch_share(chosen, own),
                by_leaf)

    *scalars, by_leaf = compare(
        params, jnp.asarray(seq), eng_grads, [jnp.asarray(c) for c in chosen])
    loss_ref, ref_sq, diff_sq, mismatch = (float(x) for x in scalars)
    by_leaf = dict(zip(mamba_leaves(rc, params), (float(x) for x in by_leaf)))
    worst = max(by_leaf, key=by_leaf.get)
    pipe.grads = {}
    return {"loss_engine": loss_eng, "loss_reference": loss_ref,
            "loss_rel_err": abs(loss_eng - loss_ref) / abs(loss_ref),
            "grad_rel_err": (diff_sq / ref_sq) ** 0.5,
            "grad_norm_reference": ref_sq ** 0.5,
            "routing_mismatch_share": mismatch,
            "mamba_leaf_rel_err_max": by_leaf[worst],
            "mamba_leaf_rel_err_at": worst,
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
                      "num_layers": rc.pattern.count(ref.ATTN),
                      "hidden_size": rc.hidden_size,
                      "num_heads": rc.num_heads}},
    }
