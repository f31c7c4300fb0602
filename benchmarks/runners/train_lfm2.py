"""kind: train_lfm2 -- steady training steps of a routed-expert model
through `OobleckEngine.train()`, checked against `reference/lfm2.py`.

The run is `runners/train.py`'s: the same engine path, loader and stager,
weights installed where a restore would put them, the same window, rate
and histograms (`install_weights`, `measure`, `checks_from` are its own).
What differs is the family: the corpus is token ids drawn UNIFORMLY over
the vocabulary rows this chip holds (`UniformCorpus`, below), the reference
is `reference/lfm2.py`, and the comparison that decides `correct` hands the
reference the PROGRAM's expert choices:

  * `routing_probe` (the model's own layers, jitted, on the installed
    weights) reads which experts every routed block chooses for the checked
    sequence, and fills the program's counters of routed pairs;
  * the reference computes everything else itself -- scores, weights,
    experts, loss, every gradient -- with that selection (`grad_rel_err`),
    and says what it would have selected from its own hidden state
    (`routing_mismatch_share`).

A free-running comparison cannot decide `correct`: a rounding error of a
percent on a router score swaps a token's fourth and fifth expert, and the
gradients then differ by how often that happened, in the program as in the
control (`control_lfm2.py` reads both ways; PERF.md section 2).
"""

from __future__ import annotations

import numpy as np

from benchmarks import traffic
from benchmarks.reference import lfm2 as ref
from benchmarks.runners import train as base


class UniformCorpus:
    """Sample i is a row of token ids uniform over `vocab` rows, from the
    seed and i alone (`traffic.token_block`'s generator, one stream a
    sample). The engine's own synthetic corpus is learnable, arithmetic
    progressions the model picks up within ten steps; in a routed model the
    router then moves with the loss, the rows routed to the held experts
    change through the window, and with them the step's time: 1.457 s ->
    1.579 s over 20 steps (my chip run, PR 29). On uniform ids nothing can
    be learned but the ids' frequencies, and the work stays what the seed
    made it."""

    def __init__(self, vocab: int, seq_length: int, seed: int,
                 num_samples: int = 8192):
        self.vocab, self.seq_length = vocab, seq_length
        self.seed, self.num_samples = int(seed), num_samples

    def __len__(self) -> int:
        return self.num_samples

    def __getitem__(self, idx: int) -> dict:
        if not 0 <= idx < self.num_samples:
            raise IndexError(idx)
        rng = np.random.default_rng(
            np.random.SeedSequence([self.seed, 5, int(idx)]))
        return {"input_ids": rng.integers(0, self.vocab, self.seq_length,
                                          dtype=np.int32)}


def build_engine(ctx, node_ips: list[str], devices: list):
    """`runners/train.py::build_engine`, with the seed's corpus drawn
    uniformly over the vocabulary rows held (the configuration's
    `vocab_rows_held`), not over the published vocabulary."""
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
    engine.dataset = UniformCorpus(ctx.config["vocab_rows_held"],
                                   engine.seq_len, ctx.seed)
    engine.initialize_distributed()
    engine.instantiate_pipelines(args.job.global_num_microbatch)
    return engine


def step_gradients(engine, seq):
    """One sequence, repeated to fill pipeline 0's share of a step, through
    the engine's forward and backward: (loss, gradients in the reference's
    tree, what to multiply them by). Pipeline gradients are scaled by
    1 / (microbatches of the whole step)."""
    pipe = engine.pipelines[0]
    n = engine.model.num_pipeline_layers
    batch = np.broadcast_to(
        seq, (pipe.num_microbatches, pipe.microbatch_size, engine.seq_len))
    loss = float(pipe.train_step({"input_ids": np.ascontiguousarray(batch)}))
    g = pipe.grads
    grads = {"embed": g[0], "blocks": [g[i] for i in range(1, n - 1)],
             "head": g[n - 1]}
    return loss, grads, pipe.total_num_microbatches / pipe.num_microbatches


def check_against_reference(ctx, engine, params, seed: int) -> dict:
    """One seeded sequence, repeated to fill pipeline 0's share of a step,
    through the engine's forward and backward; beside it the reference's
    loss and gradients of that sequence, float32 at HIGHEST, under the
    program's expert choices."""
    import jax
    import jax.numpy as jnp

    from oobleck_tpu.models.lfm2 import routing_probe

    rc = ref.RefConfig.from_config(ctx.config)
    pipe = engine.pipelines[0]
    n = engine.model.num_pipeline_layers
    seq = traffic.token_block(seed, 1, engine.seq_len, rc.vocab_size)
    chosen = routing_probe(engine.model,
                           [pipe.params[li] for li in range(n)], seq)
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
            "routing_mismatch_share": mismatch}


def run(ctx) -> dict:
    import jax

    chips = int(ctx.cell["chips"])
    devices = jax.devices()[:chips]
    engine = build_engine(ctx, [f"10.0.0.{i}" for i in range(chips)], devices)
    rc = ref.RefConfig.from_config(ctx.config)
    ctx.say_memory("engine_built")
    params = ref.init_params(ctx.seed, rc)
    base.install_weights(engine, params)
    ctx.say_memory("weights_installed")
    numbers = check_against_reference(ctx, engine, params, ctx.seed)
    del params
    ctx.say("train_check", **numbers)
    ctx.say_memory("checked")
    engine.train()          # warm-up: `warmup_steps` steps, the first compiles
    ctx.say_memory("warmed_up")
    m = base.measure(ctx, engine)
    rate = m["steps"] * m["tokens_per_step"] / m["elapsed_s"] / chips
    job = ctx.cell["traffic"]
    return {
        "attempted": m["steps"], "failed": m["failed"],
        "checks": base.checks_from(numbers, ctx.cell["correct"]),
        "end_to_end": {"train_tokens_per_s": rate},
        "layer_data": {
            "hist": m["hist"], "chips": chips,
            # What the flash readers multiply by: the ATTENTION layers held
            # and their heads; the routed layers' sizes the expert readers
            # take from the configuration.
            "train": {"tokens_per_s": rate, "seq_len": engine.seq_len,
                      "microbatch_size": job["microbatch_size"],
                      "microbatches_run": m["steps"] * (
                          job["global_batch"] // job["microbatch_size"]),
                      "n_params": rc.num_params(),
                      "num_layers": sum(t == ref.ATTN
                                        for t in rc.layer_types),
                      "hidden_size": rc.hidden_size,
                      "num_heads": rc.num_heads}},
    }
