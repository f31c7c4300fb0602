"""kind: train -- steady training steps through `OobleckEngine.train()`.

The system under test is the engine as `chip_smoke.py::_engine` builds it
(`engine_path` at its default), with the synthetic corpus going through the
real dataloader and device stager. From the benchmark come only the seed's
corpus, the seed's weights (made by `reference/gpt.py`, installed where a
restore would put them) and the clock.

Set-up: build, install weights, check against the plain reference on one
seeded microbatch (loss and every gradient), run `warmup_steps` steps (the
first compiles). Window: `engine.train()` until a drain request lands at
`--seconds`; the rate is the tokens of the steps that completed over the
time they took, first step's start to last step's end.
"""

from __future__ import annotations

import threading
import time

import numpy as np

from benchmarks import traffic
from benchmarks.reference import gpt as ref

STEP_HIST = "oobleck_engine_step_seconds"
INPUT_WAIT_HIST = "oobleck_input_wait_seconds"
DISPATCH_STALL_HIST = "oobleck_dispatch_stall_seconds"


def hist_totals(name: str) -> tuple[float, int]:
    from oobleck_tpu.utils import metrics

    series = metrics.registry().histogram(name).series()
    return (sum(s["sum"] for s in series), sum(s["count"] for s in series))


def build_engine(ctx, node_ips: list[str], devices: list):
    from oobleck_tpu.config import (
        DistributedArguments,
        ExecutionArguments,
        JobArguments,
        ModelArguments,
        OobleckArguments,
    )
    from oobleck_tpu.execution.dataset import SyntheticTextDataset
    from oobleck_tpu.execution.engine import OobleckEngine

    job = ctx.cell["traffic"]
    args = OobleckArguments(
        dist=DistributedArguments(node_ips=list(node_ips)),
        job=JobArguments(
            microbatch_size=job["microbatch_size"],
            global_microbatch_size=job["global_batch"],
            steps=job["warmup_steps"],
            learning_rate=job["learning_rate"],
            warmup_steps=job["lr_warmup_steps"]),
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
    # The corpus of this seed, through the engine's own loader and stager.
    engine.dataset = SyntheticTextDataset(
        engine.model.config.vocab_size, engine.seq_len,
        seed=ctx.seed % (1 << 31))
    engine.initialize_distributed()
    engine.instantiate_pipelines(args.job.global_num_microbatch)
    return engine


def install_weights(engine, params) -> None:
    """The seed's weights into every pipeline, on each layer's own
    sharding: where a restored checkpoint's would go. AdamW's state starts
    at zero whatever the weights, so it stays as the engine made it."""
    import jax

    n = engine.model.num_pipeline_layers
    by_layer = [params["embed"], *params["blocks"], params["head"]]
    assert len(by_layer) == n, (len(by_layer), n)
    for pipe in engine.pipelines:
        for li in list(pipe.params):
            sharding = pipe.stages[pipe.stage_of_layer(li)].param_shardings[li]
            pipe.params[li] = jax.device_put(by_layer[li], sharding)


def check_against_reference(ctx, engine, params, seed: int) -> dict:
    """One seeded sequence, repeated to fill pipeline 0's share of a step,
    through the engine's forward and backward; the plain reference's loss
    and gradients of that sequence, float32 at HIGHEST, beside them.
    Returns the two numbers `correct` is decided on."""
    import jax
    import jax.numpy as jnp

    rc = ref.RefConfig.from_config(ctx.config)
    pipe = engine.pipelines[0]
    seq = traffic.token_block(seed, 1, engine.seq_len, rc.vocab_size)
    batch = np.broadcast_to(
        seq, (pipe.num_microbatches, pipe.microbatch_size, engine.seq_len))
    loss_eng = float(pipe.train_step({"input_ids": np.ascontiguousarray(batch)}))
    # Pipeline grads are scaled by 1 / (microbatches of the whole step).
    scale = pipe.total_num_microbatches / pipe.num_microbatches
    n = engine.model.num_pipeline_layers
    g = pipe.grads
    eng_grads = {"embed": g[0], "blocks": [g[i] for i in range(1, n - 1)],
                 "head": g[n - 1]}
    # Beside the reference's weights (a no-op where one chip holds it all).
    home = next(iter(params["head"]["w"].devices()))
    eng_grads = jax.device_put(eng_grads, home)

    @jax.jit
    def compare(params, tokens, eng_grads):
        loss, grads = ref.loss_and_grads(params, tokens, rc, "highest")
        sq = lambda t: sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                           for x in jax.tree.leaves(t))
        diff = jax.tree.map(
            lambda a, b: a.astype(jnp.float32) * scale - b, eng_grads, grads)
        return loss, sq(grads), sq(diff)

    loss_ref, ref_sq, diff_sq = (float(x) for x in compare(
        params, jnp.asarray(seq), eng_grads))
    pipe.grads = {}
    return {"loss_engine": loss_eng, "loss_reference": loss_ref,
            "loss_rel_err": abs(loss_eng - loss_ref) / abs(loss_ref),
            "grad_rel_err": (diff_sq / ref_sq) ** 0.5,
            "grad_norm_reference": ref_sq ** 0.5}


def checks_from(numbers: dict, limits: dict) -> list[dict]:
    return [{"check": k, "value": numbers[k], "limit": limits[k],
             "ok": bool(np.isfinite(numbers[k]) and numbers[k] <= limits[k])}
            for k in sorted(limits)]


def measure(ctx, engine) -> dict:
    """`engine.train()` for the window; a drain request ends it at the
    first step boundary after `--seconds`."""
    job = ctx.cell["traffic"]
    tokens_per_step = job["global_batch"] * engine.seq_len
    before = {h: hist_totals(h) for h in
              (STEP_HIST, INPUT_WAIT_HIST, DISPATCH_STALL_HIST)}
    step0 = engine.step
    cpu0 = time.process_time()
    engine.args.job.steps = 1 << 30
    timer = threading.Timer(ctx.seconds, engine.request_drain)
    timer.daemon = True
    ctx.start_trace()
    ctx.window_starts()
    t0 = time.perf_counter()
    timer.start()
    try:
        engine.train()
    finally:
        timer.cancel()
    elapsed = time.perf_counter() - t0
    cpu_s = time.process_time() - cpu0
    ctx.stop_trace()
    steps = engine.step - step0
    hist = {}
    for h, (s0, c0) in before.items():
        s1, c1 = hist_totals(h)
        hist[h] = {"sum": s1 - s0, "count": c1 - c0}
    losses = [l for _, l in engine.loss_history[-steps:]] if steps else []
    # Each step's seconds, from the program's telemetry ring (step, step_s,
    # ...), and this process's CPU seconds: a window that reads slow shows
    # here whether one step stalled or all were slow, and whether the host
    # had the CPU.
    from oobleck_tpu.obs import telemetry

    step_seconds = [round(s[1], 4) for s in telemetry.telemetry().samples()
                    if s[0] > step0]
    ctx.say("train_window", steps=steps, elapsed_s=elapsed,
            tokens_per_step=tokens_per_step,
            step_seconds_sum=hist[STEP_HIST]["sum"],
            step_seconds=step_seconds, process_cpu_s=cpu_s,
            first_loss=losses[0] if losses else None,
            last_loss=losses[-1] if losses else None)
    finite = all(np.isfinite(l) for l in losses)
    return {"steps": steps, "elapsed_s": elapsed, "hist": hist,
            "tokens_per_step": tokens_per_step,
            "failed": 0 if finite else steps}


def run(ctx) -> dict:
    import jax

    chips = int(ctx.cell["chips"])
    devices = jax.devices()[:chips]
    engine = build_engine(ctx, [f"10.0.0.{i}" for i in range(chips)], devices)
    rc = ref.RefConfig.from_config(ctx.config)
    ctx.say_memory("engine_built")
    params = ref.init_params(ctx.seed, rc, stacked=False)
    install_weights(engine, params)
    ctx.say_memory("weights_installed")
    numbers = check_against_reference(ctx, engine, params, ctx.seed)
    del params
    ctx.say("train_check", **numbers)
    ctx.say_memory("checked")
    engine.train()          # warm-up: `warmup_steps` steps, the first compiles
    ctx.say_memory("warmed_up")
    m = measure(ctx, engine)
    rate = m["steps"] * m["tokens_per_step"] / m["elapsed_s"] / chips
    return {
        "attempted": m["steps"], "failed": m["failed"],
        "checks": checks_from(numbers, ctx.cell["correct"]),
        "end_to_end": {"train_tokens_per_s": rate},
        "layer_data": {
            "hist": m["hist"], "chips": chips,
            "train": {"tokens_per_s": rate, "seq_len": engine.seq_len,
                      "microbatch_size": ctx.cell["traffic"]["microbatch_size"],
                      "microbatches_run": m["steps"] * (
                          ctx.cell["traffic"]["global_batch"]
                          // ctx.cell["traffic"]["microbatch_size"]),
                      "n_params": rc.num_params(),
                      "num_layers": rc.num_layers,
                      "hidden_size": rc.hidden_size,
                      "num_heads": rc.num_heads}},
    }
