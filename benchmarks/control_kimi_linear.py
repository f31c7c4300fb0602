"""The readings that `kimi-linear-48b-a3b.steady`'s limits of `correct` are
set from: `control_deepseek_v3.py`'s twin for the Kimi-Linear family.

    python benchmarks/control_kimi_linear.py \\
        --workload kimi-linear-48b-a3b.steady --seeds 6 --control-seeds 3 \\
        [--fault-seeds 1]

For each control seed, `reference/kimi_linear.py` put in the program's
place, against itself in float32 (each handed ITS OWN expert choices as the
program's would be: `grad_rel_err`, `routing_mismatch_share`,
`mla_grad_rel_err`, the norm over the latent mixers alone, and
`kda_leaf_rel_err_max`, the worst of the KDA layers' small leaves):

  fp8              computed one precision below what the configuration
                   states (per-tensor scaled float8-e4m3 operands of every
                   contraction, the recurrence's three included): the
                   CONTROL, which has to come out not correct;
  bfloat16         in the stated precision, for scale: correct;
  scalar_decay     float32, one decay a head (the mean over its channels);
  rotary_on        float32, rotary left on in the latent layers;
  beta_left_out    float32, every write at full strength;
  shared_left_out  float32, the routed layers without the shared expert;
  decay_grad_cut   float32, a right forward whose backward loses the
                   decay's gradient (`stop_gradient` on g):
                   the five PLANTED FAULTS, each has to come out not
                   correct.

For each seed, the PROGRAM's own numbers, the engine built once (the
runner's check). A limit is the geometric middle of the program's largest
reading and the control's smallest; the cell's `correct_why` and PERF.md
section 2 record all of them. Needs the chip the cell needs.
"""

from __future__ import annotations

import argparse
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

# (name, mode of arithmetic, planted fault)
CONTROLS = (("bfloat16", "bfloat16", None), ("fp8", "fp8", None),
            ("scalar_decay", "highest", "scalar_decay"),
            ("rotary_on", "highest", "rotary_on"),
            ("beta_left_out", "highest", "beta_left_out"),
            ("shared_left_out", "highest", "shared_left_out"),
            ("decay_grad_cut", "highest", "decay_grad_cut"))


def reference_vs_reference(config: dict, cell: dict, seed: int, mode: str,
                           fault) -> dict:
    import jax
    import jax.numpy as jnp

    from benchmarks import traffic
    from benchmarks.reference import kimi_linear as ref
    from benchmarks.runners.train_kimi_linear import (
        init_params,
        part_errors,
        sum_of_squares as sq,
        worst_leaf,
    )

    rc = ref.RefConfig.from_config(config)
    params = init_params(cell, rc, seed)
    seq = jnp.asarray(traffic.token_block(
        seed, 1, cell["traffic"]["seq_len"], rc.vocab_size))

    @jax.jit
    def lower(params, tokens):
        """The stand-in's gradients under its own choices."""
        (loss, chosen), grads = ref.loss_and_grads(params, tokens, rc, mode,
                                                   None, fault)
        return loss, grads, chosen

    @jax.jit
    def against(params, tokens, grads_m, chosen):
        """One set of reference gradients alive at a time."""
        (loss, own), grads = ref.loss_and_grads(params, tokens, rc,
                                                "highest", chosen)
        diff = jax.tree.map(lambda a, b: a - b, grads_m, grads)
        return (loss, sq(grads), sq(diff), ref.mismatch_share(chosen, own),
                *part_errors(rc, diff, grads))

    loss_m, grads_m, chosen = lower(params, seq)
    *scalars, by_leaf = against(params, seq, grads_m, chosen)
    loss, ref_sq, diff_sq, mismatch, latent = (float(x) for x in scalars)
    return {"loss_rel_err": abs(float(loss_m) - loss) / abs(loss),
            "grad_rel_err": (diff_sq / ref_sq) ** 0.5,
            "routing_mismatch_share": mismatch,
            "mla_grad_rel_err": latent,
            **worst_leaf(rc, params, by_leaf)}


def program_readings(ctx, n_seeds: int) -> list[dict]:
    """The program's own numbers on `n_seeds` seeds, the engine built
    once: the runner's check."""
    import jax

    from benchmarks.reference import kimi_linear as ref
    from benchmarks.runners import train as base
    from benchmarks.runners import train_kimi_linear as runner

    if n_seeds <= 0:
        return []
    rc = ref.RefConfig.from_config(ctx.config)
    chips = int(ctx.cell["chips"])
    engine = runner.build_engine(
        ctx, [f"10.0.0.{i}" for i in range(chips)], jax.devices()[:chips])
    out = []
    for k in range(n_seeds):
        seed = ctx.seed + 7919 * k
        params = runner.init_params(ctx.cell, rc, seed)
        base.install_weights(engine, params)
        row = runner.check_against_reference(ctx, engine, params, seed)
        row.pop("held_rows")
        out.append(dict(row, seed=seed))
        del params
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=2_500_000_011)
    ap.add_argument("--seeds", type=int, default=6)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--only", default=None,
                    help="comma-separated names of CONTROLS to run")
    ap.add_argument("--fault-seeds", type=int, default=None,
                    help="seeds for the rows other than fp8 (default: "
                         "as many as --control-seeds)")
    ns = ap.parse_args(argv)

    from benchmarks import run as harness

    harness.set_cache_environment()
    from benchmarks.runners.train import checks_from

    cell = harness.load_json(HERE / "workloads" / f"{ns.workload}.json")
    config = harness.load_json(HERE / "configs" / f"{cell['config']}.json")
    device = harness.device_record(int(cell["chips"]))
    ctx = harness.Context(cell, config, ns.seed, 0.0, False, device)

    # The controls first: each needs room for two sets of gradients, which
    # the engine's optimizer state would not leave.
    others = ns.control_seeds if ns.fault_seeds is None else ns.fault_seeds
    for k in range(ns.control_seeds):
        seed = ns.seed + 7919 * k
        for name, mode, fault in CONTROLS:
            if (name != "fp8" and k >= others) or (
                    ns.only and name not in ns.only.split(",")):
                continue
            t0 = time.monotonic()
            row = reference_vs_reference(config, cell, seed, mode, fault)
            # Held to the cell's limits by the runner's own function: only
            # the bfloat16 rows may come out `ok: true`.
            ctx.say("control_vs_reference", control=name, seed=seed, **row,
                    checks=checks_from(row, cell.get("correct", {})),
                    seconds=time.monotonic() - t0)
    for row in program_readings(ctx, ns.seeds):
        ctx.say("program_vs_reference", **row)
    return 0


if __name__ == "__main__":
    sys.exit(main())
