"""The readings that the host-loss cell's limits are set from, on ONE chip:
nothing here exists only across chips.

    python benchmarks/control_hostloss.py --workload gpt3-2.7b.hostloss \
        --seeds 5 --control-seeds 3

What is read is the job's first steps (`runners/train_hostloss.py`: each
step's loss, the first gradient as AdamW got it, each leaf's change,
against the plain reference that follows the job from its seed). For each
seed:

* `program_one_chip`: the PROGRAM on one chip and one stage, read and
  compared exactly as the cell's run reads its four chips: the same
  forward, backward and optimizer programs but for the stage cut, on more
  seeds than four chips' budget reaches. The lower readings; the cell's own
  runs on four chips print theirs.

and for each control seed the reference in the program's place:

* `fp8`: one precision below what the configuration states (operands of
  every contraction rounded to float8-e4m3, `reference/gpt.py`);
* `half_batch_left_out`: half of a step's rows never reach the optimizer,
  the mean taken over the rest;
* `exchange_left_out`: the gradient sum between the two pipelines left
  out, so a pipeline steps on its own half, each microbatch weighed by the
  whole step's count.

A state returned unchanged needs no run: every leaf's change is nought, so
`param_change_norm_gap` reads 1. Each reading goes through the cell's own
limits (`checks_from`), so a line says whether `correct` would have come
out false.
"""

from __future__ import annotations

import argparse
import gc
import sys
import time
from pathlib import Path
from types import SimpleNamespace

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

FIRST_STEPS_NUMBERS = ("step_loss_rel_err", "first_grad_rel_err",
                       "first_grad_norm_gap", "param_change_norm_gap")


def one_chip(ctx):
    """`ctx` with the cell laid on one chip and one stage."""
    return SimpleNamespace(
        cell=dict(ctx.cell, execution={"engine_path": "mpmd"}),
        config=ctx.config, seed=ctx.seed)


def say_first_steps(ctx, what: str, got: dict, want: dict, device) -> dict:
    from benchmarks.runners import train, train_hostloss

    numbers = train_hostloss.compare_first_steps(got, want, device)
    limits = {k: ctx.cell["correct"][k] for k in FIRST_STEPS_NUMBERS}
    checks = train.checks_from(numbers, limits)
    ctx.say("first_steps", reading=what, seed=ctx.seed,
            correct=all(c["ok"] for c in checks),
            failed_numbers=[c["check"] for c in checks if not c["ok"]],
            **numbers)
    return numbers


def first_steps_readings(ctx, device, program: bool, controls: bool) -> None:
    import jax

    from benchmarks.reference import gpt as ref
    from benchmarks.runners import train, train_hostloss

    rc = ref.RefConfig.from_config(ctx.config)
    seeded = lambda: ref.init_params(ctx.seed, rc, stacked=False)
    got = None
    if program:
        # The engine first: at rest and in a step it leaves the reference
        # no room.
        one = one_chip(ctx)
        engine = train.build_engine(one, ["10.0.0.0"], [device])
        train.install_weights(engine, seeded())
        got = train_hostloss.first_steps(one, engine, jax.device_get)
        train_hostloss.close(engine)
        del engine
        gc.collect()
    t0 = time.perf_counter()
    want = train_hostloss.reference_first_steps(
        ctx, device, seeded(), jax.device_get)
    moments = want["moment_norms"][0]
    ctx.say("reference_first_steps", seed=ctx.seed, losses=want["losses"],
            seconds=time.perf_counter() - t0,
            # Each pipeline layer's gradient norm AFTER the clip: under 1,
            # the layer was not clipped.
            clipped_grad_norm_by_layer=[
                (sum(n * n for k, n in moments.items()
                     if k.startswith(f"{li}[")) ** 0.5)
                / (1 - ctx.cell["traffic"]["optimizer"]["b1"])
                for li in range(rc.num_layers + 2)])
    if got is not None:
        say_first_steps(ctx, "program_one_chip", got, want, device)
        del got
    if controls:
        for what, mode, fault in (("fp8", "fp8", None),
                                  ("half_batch_left_out", "highest",
                                   "half_batch_left_out"),
                                  ("exchange_left_out", "highest",
                                   "exchange_left_out")):
            control = train_hostloss.reference_first_steps(
                ctx, device, seeded(), jax.device_get, mode, fault)
            say_first_steps(ctx, what, control, want, device)
            del control
            gc.collect()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=2_500_000_011)
    ap.add_argument("--seeds", type=int, default=5)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--no-program", action="store_true",
                    help="the reference and its controls only")
    ns = ap.parse_args(argv)

    from benchmarks import run as harness

    harness.set_cache_environment()
    cell = harness.load_json(HERE / "workloads" / f"{ns.workload}.json")
    config = harness.load_json(HERE / "configs" / f"{cell['config']}.json")
    record = harness.device_record(1)
    import jax

    from oobleck_tpu.utils.compile_cache import ensure_persistent_cache

    ensure_persistent_cache()
    device = jax.devices()[0]
    for k in range(ns.seeds):
        ctx = harness.Context(cell, config, ns.seed + 7919 * k, 0.0, False,
                              record)
        first_steps_readings(ctx, device, not ns.no_program,
                             k < ns.control_seeds)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
