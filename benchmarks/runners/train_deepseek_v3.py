"""kind: train_deepseek_v3 -- steady training steps of the DeepSeek-V3
family (latent attention, shared experts beside routed ones) through
`OobleckEngine.train()`, checked against `reference/deepseek_v3.py`.

The run is `runners/train.py`'s (`install_weights`, `measure`,
`checks_from`), the corpus and the way `correct` is decided are
`runners/train_lfm2.py`'s (`UniformCorpus`, `step_gradients`; the reference
handed the PROGRAM's expert choices, read by the program's own
`routing_probe`: `grad_rel_err`, and `routing_mismatch_share` beside it).
What differs: the reference; that the JOB states its sequence length
(`job.seq_len`, the cell's `traffic.seq_len`), where the two runners beside
this one take the engine's default and refuse any other; and that the
checked sequence's routing is probed a second time AFTER the window has
closed (outside `setup_s` and the rate; the probe's program is the one the
first call compiled), so that both readings of the program's gauge
`oobleck_moe_held_rows{layer}` go on to `readers/held_rows_drift_pct.py`.

`train` names ALL the blocks as attention layers (every block has latent
attention) at the model's hidden size and head count; the latent kernels'
reader takes the three head widths from the configuration. Where a
set-up's seconds went is said after the window (`setup_phases`).
"""

from __future__ import annotations

import time

from benchmarks import traffic
from benchmarks.reference import deepseek_v3 as ref
from benchmarks.runners import train as base
from benchmarks.runners.train_lfm2 import UniformCorpus, step_gradients

HELD_ROWS = "oobleck_moe_held_rows"


def build_engine(ctx, node_ips: list[str], devices: list):
    from oobleck_tpu.config import (
        DistributedArguments,
        ExecutionArguments,
        JobArguments,
        ModelArguments,
        OobleckArguments,
    )
    from oobleck_tpu.execution.engine import OobleckEngine

    job = ctx.cell["traffic"]
    args = OobleckArguments(
        dist=DistributedArguments(node_ips=list(node_ips)),
        job=JobArguments(
            microbatch_size=job["microbatch_size"],
            global_microbatch_size=job["global_batch"],
            steps=job["warmup_steps"],
            learning_rate=job["learning_rate"],
            warmup_steps=job["lr_warmup_steps"],
            seq_len=job["seq_len"]),
        model=ModelArguments(model_name=ctx.config["model_name"],
                             model_args=dict(ctx.config["model_args"]),
                             dataset_path="synthetic"),
        execution=ExecutionArguments(**ctx.config["execution"],
                                     **ctx.cell.get("execution", {})),
    )
    engine = OobleckEngine(args, devices=list(devices))
    if engine.seq_len != job["seq_len"]:
        raise SystemExit(
            f"the cell states seq_len {job['seq_len']}, the engine trains "
            f"at {engine.seq_len}")
    engine.dataset = UniformCorpus(ctx.config["vocab_rows_held"],
                                   engine.seq_len, ctx.seed)
    engine.initialize_distributed()
    engine.instantiate_pipelines(args.job.global_num_microbatch)
    return engine


def init_params(cell: dict, rc: ref.RefConfig, seed: int):
    """The seed's weights, the selection bias balanced on sequences of the
    cell's own length."""
    return ref.init_params(seed, rc, (ref.BALANCE_TOKENS[0],
                                      cell["traffic"]["seq_len"]))


def probe_held_rows(engine, seq) -> tuple[list, dict[str, float]]:
    """The experts every routed block of the engine's CURRENT weights
    chooses for `seq`, and what the probe set the program's gauge to: the
    (token, slot) pairs of `seq` on the experts held here, by routed
    block."""
    from oobleck_tpu.models.routed import routing_probe
    from oobleck_tpu.utils import metrics

    pipe = engine.pipelines[0]
    n = engine.model.num_pipeline_layers
    chosen = routing_probe(engine.model,
                           [pipe.params[li] for li in range(n)], seq)
    gauge = metrics.registry().gauge(HELD_ROWS)
    return chosen, {str(b): gauge.value(layer=str(b))
                    for b in engine.model.routed_blocks}


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
        return loss, sq(grads), sq(diff), ref.mismatch_share(chosen, own)

    loss_ref, ref_sq, diff_sq, mismatch = (float(x) for x in compare(
        params, jnp.asarray(seq), eng_grads, [jnp.asarray(c) for c in chosen]))
    pipe.grads = {}
    return {"loss_engine": loss_eng, "loss_reference": loss_ref,
            "loss_rel_err": abs(loss_eng - loss_ref) / abs(loss_ref),
            "grad_rel_err": (diff_sq / ref_sq) ** 0.5,
            "grad_norm_reference": ref_sq ** 0.5,
            "routing_mismatch_share": mismatch,
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
    rate = m["steps"] * m["tokens_per_step"] / m["elapsed_s"] / chips
    job = ctx.cell["traffic"]
    return {
        "attempted": m["steps"], "failed": m["failed"],
        "checks": base.checks_from(numbers, ctx.cell["correct"]),
        "end_to_end": {"train_tokens_per_s": rate},
        "layer_data": {
            "hist": m["hist"], "chips": chips,
            "held_rows": {"before": before, "after": after},
            "train": {"tokens_per_s": rate, "seq_len": engine.seq_len,
                      "microbatch_size": job["microbatch_size"],
                      "microbatches_run": m["steps"] * (
                          job["global_batch"] // job["microbatch_size"]),
                      "n_params": rc.num_params(),
                      "num_layers": rc.num_layers,
                      "hidden_size": rc.hidden_size,
                      "num_heads": rc.num_heads}},
    }
