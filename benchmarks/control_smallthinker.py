"""The readings that `smallthinker-21b-a3b.steady`'s limits of `correct` are
set from: `control_qwen3_next.py`'s twin for the SmallThinker family.

    python benchmarks/control_smallthinker.py \\
        --workload smallthinker-21b-a3b.steady --seeds 6 --control-seeds 3

For each control seed, the CONTROL: `reference/smallthinker.py` put in the
program's place and computed one precision below what the configuration
states (`fp8`, per-tensor scaled), and for scale the reference in the
stated precision (`bfloat16`), against the float32 reference; and a SECOND
control, `window_ignored`: the float32 reference with every layer full
causal (the window's mask left out) against the reference itself, which
has to read above the limit, so that a program which skipped the mask
could not pass. Both ways:

  forced   the float32 reference is handed the lower-precision run's
           expert choices (what `runners/train_smallthinker.py` does with the
           program's): `grad_rel_err`, and `routing_mismatch_share`, the
           (token, layer) pairs whose top-6 set the float32
           reference would have chosen differently;
  free     both choose for themselves: `grad_rel_err_free`.

For each seed, the PROGRAM the same two ways, the engine built once
(`forced` is the runner's own check; `free` re-runs the reference without
the program's choices). A limit belongs above the program's largest
reading and below the control's smallest; PERF.md section 2 records all of
them. Needs the chip the cell needs.
"""

from __future__ import annotations

import argparse
import functools
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

WINDOW_IGNORED = "window_ignored"


def reference_vs_reference(config: dict, cell: dict, seed: int,
                           mode: str) -> dict:
    import jax
    import jax.numpy as jnp

    from benchmarks import traffic
    from benchmarks.reference import smallthinker as ref

    rc = ref.RefConfig.from_config(config)
    params = ref.init_params(seed, rc)
    seq = jnp.asarray(traffic.token_block(
        seed, 1, cell["traffic"]["seq_len"], rc.vocab_size))
    sq = lambda t: sum(jnp.sum(jnp.square(x)) for x in jax.tree.leaves(t))

    @jax.jit
    def lower(params, tokens):
        if mode == WINDOW_IGNORED:
            (loss, chosen), grads = ref.loss_and_grads(
                params, tokens, rc, "highest", ignore_window=True)
        else:
            (loss, chosen), grads = ref.loss_and_grads(params, tokens, rc,
                                                       mode)
        return loss, chosen, grads

    @functools.partial(jax.jit, static_argnames="forced")
    def against(params, tokens, grads_m, chosen, forced: bool):
        """The float32 reference, handed `chosen` or left to choose,
        against `grads_m`; one set of reference gradients alive at a
        time."""
        (loss, own), grads = ref.loss_and_grads(
            params, tokens, rc, "highest", chosen if forced else None)
        diff = jax.tree.map(lambda a, b: a - b, grads_m, grads)
        return loss, sq(grads), sq(diff), ref.mismatch_share(chosen, own)

    loss_m, chosen, grads_m = lower(params, seq)
    loss, ref_sq, diff_sq, mismatch = (float(x) for x in against(
        params, seq, grads_m, chosen, forced=True))
    _, free_sq, free_diff, _ = (float(x) for x in against(
        params, seq, grads_m, chosen, forced=False))
    return {"loss_rel_err": abs(float(loss_m) - loss) / abs(loss),
            "grad_rel_err": (diff_sq / ref_sq) ** 0.5,
            "routing_mismatch_share": mismatch,
            "grad_rel_err_free": (free_diff / free_sq) ** 0.5}


def program_readings(ctx, n_seeds: int) -> list[dict]:
    """The program's own numbers on `n_seeds` seeds, the engine built once:
    the runner's check (forced), then the same gradients against the
    reference left to choose for itself (free)."""
    import jax
    import jax.numpy as jnp

    from benchmarks import traffic
    from benchmarks.reference import smallthinker as ref
    from benchmarks.runners import train, train_smallthinker as runner
    from benchmarks.runners.train_lfm2 import step_gradients

    if n_seeds <= 0:
        return []
    rc = ref.RefConfig.from_config(ctx.config)
    chips = int(ctx.cell["chips"])
    engine = runner.build_engine(
        ctx, [f"10.0.0.{i}" for i in range(chips)], jax.devices()[:chips])
    pipe = engine.pipelines[0]
    sq = lambda t: sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                       for x in jax.tree.leaves(t))

    @jax.jit
    def free(params, tokens, eng_grads, scale):
        (_, _), grads = ref.loss_and_grads(params, tokens, rc, "highest")
        diff = jax.tree.map(
            lambda a, b: a.astype(jnp.float32) * scale - b, eng_grads, grads)
        return sq(grads), sq(diff)

    out = []
    for k in range(n_seeds):
        seed = ctx.seed + 7919 * k
        params = ref.init_params(seed, rc)
        train.install_weights(engine, params)
        row = runner.check_against_reference(ctx, engine, params, seed)
        # The same step again for the free comparison (the check cleared
        # the pipeline's gradients).
        seq = traffic.token_block(seed, 1, engine.seq_len, rc.vocab_size)
        _, eng_grads, scale = step_gradients(engine, seq)
        ref_sq, diff_sq = (float(x) for x in free(
            params, jnp.asarray(seq), eng_grads, scale))
        pipe.grads = {}
        out.append(dict(row, grad_rel_err_free=(diff_sq / ref_sq) ** 0.5,
                        seed=seed))
        del params, eng_grads
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=2_500_000_011)
    ap.add_argument("--seeds", type=int, default=6)
    ap.add_argument("--control-seeds", type=int, default=3)
    ns = ap.parse_args(argv)

    from benchmarks import run as harness

    harness.set_cache_environment()
    from benchmarks.runners.train import checks_from

    cell = harness.load_json(HERE / "workloads" / f"{ns.workload}.json")
    config = harness.load_json(HERE / "configs" / f"{cell['config']}.json")
    device = harness.device_record(int(cell["chips"]))
    ctx = harness.Context(cell, config, ns.seed, 0.0, False, device)

    # The control first: it needs room for two sets of gradients, which
    # the engine's optimizer state would not leave.
    for k in range(ns.control_seeds):
        seed = ns.seed + 7919 * k
        for mode in ("bfloat16", "fp8", WINDOW_IGNORED):
            t0 = time.monotonic()
            row = reference_vs_reference(config, cell, seed, mode)
            # Held to the cell's limits by the runner's own function: the
            # float8 and window-ignored rows have to come out `ok: false`,
            # the bfloat16 rows true.
            ctx.say("control_vs_reference", mode=mode, seed=seed, **row,
                    checks=checks_from(row, cell.get("correct", {})),
                    seconds=time.monotonic() - t0)
    for row in program_readings(ctx, ns.seeds):
        ctx.say("program_vs_reference", **row)
    return 0


if __name__ == "__main__":
    sys.exit(main())
