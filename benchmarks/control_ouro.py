"""The readings that `ouro-2.6b.steady`'s limit of `correct` is set from:
`control_phi4flash.py`'s twin for the Ouro family.

    python benchmarks/control_ouro.py \\
        --workload ouro-2.6b.steady --seeds 6 --control-seeds 3

For each control seed, `reference/ouro.py` put in the program's place,
against itself in float32:

  fp8              computed one precision below what the configuration
                   states (per-tensor scaled float8-e4m3 operands of every
                   contraction): the CONTROL, which has to come out not
                   correct;
  bfloat16         in the stated precision, for scale: correct;
  one_pass_short   float32, R - 1 passes;
  last_visit_grad  float32, a shared weight's gradient taken from its last
                   visit alone;
  last_exit_only   float32, the last exit's loss alone: the three PLANTED
                   FAULTS, each has to come out not correct.

For each seed, the PROGRAM's own numbers, the engine built once (the
runner's check). A limit belongs above the program's largest reading and
below the smallest of the other four; PERF.md section 2 records all of
them. Needs the chip the cell needs.
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
            ("one_pass_short", "highest", "one_pass_short"),
            ("last_visit_grad", "highest", "last_visit_grad"),
            ("last_exit_only", "highest", "last_exit_only"))


def reference_vs_reference(config: dict, cell: dict, seed: int, mode: str,
                           fault) -> dict:
    import jax
    import jax.numpy as jnp

    from benchmarks import traffic
    from benchmarks.reference import ouro as ref

    rc = ref.RefConfig.from_config(config)
    params = ref.init_params(seed, rc)
    seq = jnp.asarray(traffic.token_block(
        seed, 1, cell["traffic"]["seq_len"], rc.vocab_size))
    sq = lambda t: sum(jnp.sum(jnp.square(x)) for x in jax.tree.leaves(t))

    @jax.jit
    def lower(params, tokens):
        (loss, _), grads = ref.loss_and_grads(params, tokens, rc, mode, fault)
        return loss, grads

    @jax.jit
    def against(params, tokens, grads_m):
        """One set of reference gradients alive at a time."""
        (loss, _), grads = ref.loss_and_grads(params, tokens, rc, "highest")
        diff = jax.tree.map(lambda a, b: a - b, grads_m, grads)
        return loss, sq(grads), sq(diff)

    loss_m, grads_m = lower(params, seq)
    loss, ref_sq, diff_sq = (float(x) for x in against(params, seq, grads_m))
    return {"loss_rel_err": abs(float(loss_m) - loss) / abs(loss),
            "grad_rel_err": (diff_sq / ref_sq) ** 0.5}


def program_readings(ctx, n_seeds: int) -> list[dict]:
    """The program's own numbers on `n_seeds` seeds, the engine built
    once: the runner's check."""
    import jax

    from benchmarks.reference import ouro as ref
    from benchmarks.runners import train as base
    from benchmarks.runners import train_ouro as runner

    if n_seeds <= 0:
        return []
    rc = ref.RefConfig.from_config(ctx.config)
    chips = int(ctx.cell["chips"])
    engine = runner.build_engine(
        ctx, [f"10.0.0.{i}" for i in range(chips)], jax.devices()[:chips])
    out = []
    for k in range(n_seeds):
        seed = ctx.seed + 7919 * k
        params = ref.init_params(seed, rc)
        base.install_weights(engine, params)
        row = runner.check_against_reference(ctx, engine, params, seed)
        out.append(dict(row, seed=seed))
        del params
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

    # The controls first: each needs room for two sets of gradients, which
    # the engine's optimizer state would not leave.
    for k in range(ns.control_seeds):
        seed = ns.seed + 7919 * k
        for name, mode, fault in CONTROLS:
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
