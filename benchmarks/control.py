"""The readings that `correct`'s limits are set from, many seeds to a process.

    python benchmarks/control.py --workload <cell> --seeds 12 --control-seeds 3

For each seed: the PROGRAM against the plain reference (the numbers a run
of `run.py` prints as its `correct` observations), the set-up paid once;
cells of `kind: train`, the only kind there is so far.
For each control seed: the CONTROL, which is the reference itself put in
the program's place and computed one precision below what the cell's
configuration states (`fp8`, per-tensor scaled, for bfloat16), and for
scale the reference in the stated precision (`bfloat16`), both against the
float32 reference. A limit belongs above the program's largest reading and
below the control's smallest; PERF.md section 2 records both.

The same comparison at a size a test run can hold is
`tests/benchmarks/test_bench_reference.py`. Needs the chip the cell needs.
"""

from __future__ import annotations

import argparse
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))


def reference_vs_reference(config: dict, cell: dict, seed: int,
                           mode: str) -> dict:
    """The reference in `mode` against the reference at float32/HIGHEST,
    on the inputs and by the measure the training runner uses."""
    import jax
    import jax.numpy as jnp

    from benchmarks import traffic
    from benchmarks.reference import gpt as ref

    rc = ref.RefConfig.from_config(config)
    params = ref.init_params(seed, rc, stacked=False)
    seq = jnp.asarray(traffic.token_block(
        seed, 1, cell["traffic"]["seq_len"], rc.vocab_size))

    @jax.jit
    def compare(params, tokens):
        loss, grads = ref.loss_and_grads(params, tokens, rc, "highest")
        loss_m, grads_m = ref.loss_and_grads(params, tokens, rc, mode)
        sq = lambda t: sum(jnp.sum(jnp.square(x))
                           for x in jax.tree.leaves(t))
        diff = jax.tree.map(lambda a, b: a - b, grads_m, grads)
        return loss, loss_m, sq(grads), sq(diff)

    loss, loss_m, ref_sq, diff_sq = (float(x) for x in compare(params, seq))
    return {"loss_rel_err": abs(loss_m - loss) / abs(loss),
            "grad_rel_err": (diff_sq / ref_sq) ** 0.5}


def program_readings(ctx, n_seeds: int) -> list[dict]:
    """The program's own numbers on `n_seeds` seeds, the engine built once."""
    import jax

    from benchmarks.reference import gpt as ref
    from benchmarks.runners import train

    if n_seeds <= 0:
        return []
    rc = ref.RefConfig.from_config(ctx.config)
    chips = int(ctx.cell["chips"])
    engine = train.build_engine(
        ctx, [f"10.0.0.{i}" for i in range(chips)], jax.devices()[:chips])
    out = []
    for k in range(n_seeds):
        seed = ctx.seed + 7919 * k
        params = ref.init_params(seed, rc, stacked=False)
        train.install_weights(engine, params)
        out.append(dict(train.check_against_reference(
            ctx, engine, params, seed), seed=seed))
        del params
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=2_500_000_011)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ns = ap.parse_args(argv)

    from benchmarks import run as harness

    harness.set_cache_environment()
    cell = harness.load_json(HERE / "workloads" / f"{ns.workload}.json")
    config = harness.load_json(HERE / "configs" / f"{cell['config']}.json")
    device = harness.device_record(int(cell["chips"]))
    ctx = harness.Context(cell, config, ns.seed, 0.0, False, device)

    # The control first: it needs room for two sets of gradients, which
    # the engine's optimizer state would not leave.
    for k in range(ns.control_seeds):
        seed = ns.seed + 7919 * k
        for mode in ("bfloat16", "fp8"):
            ctx.say("control_vs_reference", mode=mode, seed=seed,
                    **reference_vs_reference(config, cell, seed, mode))
    for row in program_readings(ctx, ns.seeds):
        ctx.say("program_vs_reference", **row)
    return 0


if __name__ == "__main__":
    sys.exit(main())
