"""Regenerate the recorded degrade-bench corpus fixture.

Runs a reroute on the REAL engine (two identical 2-stage pipelines, one
per host, on 4 virtual CPU devices; host 1 is lost and the survivor
absorbs its microbatches on the same topology: no re-plan, no state
movement, no recompile) and commits what a production incident leaves
behind: the flight-recorder ring (including the engine's own
``degrade_decision``), an ``incident-0.json`` built by the real
IncidentBuilder with wall-clock marks from the measured recovery, and a
``degrade-bench.json`` summary. The numbers are a CPU rig's, kept only as
the simulator's cross-validation ground truth; none is a device metric. The incident's attrs additionally freeze
the rig shape, calibrated per-op durations, and the measured step
timings — which is exactly what ``sim.slo.replay_incident`` needs to
cross-validate the simulator against this measurement.

Calibration runs with ``sync_op_timing`` ON (the pipeline's opt-in
profiling mode): default async-dispatch enqueue times pin the whole step
on whichever op happens to block, which makes the replayed makespan
linear in M and biases the projected slowdown to exactly 2.0 on this
rig. Synced timing records true per-op durations, so the projection and
the measurement describe the same pipeline. The committed projection is
computed through the SAME PipelineSpec/plan_reroute path
``replay_incident`` replays — one computation, not two models.

The script refuses to commit a noise-corrupted fixture: if the planner's
replay-projected survivor slowdown disagrees with the measurement by more
than MAX_DISAGREEMENT (the cross-validation test gates at 15%), it exits
non-zero — rerun it on a quieter machine.

Usage:  python tests/sim/make_degrade_fixture.py [out_dir]
        (default out_dir: tests/sim/data/degrade_bench)
"""

from __future__ import annotations

import json
import os
import shutil
import statistics
import sys
import time

MAX_DISAGREEMENT = 0.10
WARMUP_STEPS = 3
CALIBRATE_STEPS = 3
MEASURE_STEPS = 9

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))


_MODEL_ARGS = {"hidden_size": 128, "num_layers": 8,
               "max_position_embeddings": 64}


def _make_engine():
    import jax

    from oobleck_tpu.config import (
        DistributedArguments,
        JobArguments,
        ModelArguments,
        OobleckArguments,
    )
    from oobleck_tpu.execution.engine import OobleckEngine

    hosts = ["10.0.0.0", "10.0.0.1"]
    args = OobleckArguments(
        dist=DistributedArguments(node_ips=hosts),
        job=JobArguments(
            microbatch_size=1,
            global_microbatch_size=8,
            steps=64,
            learning_rate=1e-3,
            warmup_steps=2,
        ),
        model=ModelArguments(
            model_name="gpt2-tiny", dataset_path="synthetic",
            model_tag="degrade-bench",  # own profile cache: non-default args
            model_args=dict(_MODEL_ARGS),
        ),
    )
    args.execution.degrade_enabled = True
    args.execution.precompile_recovery_depth = 0  # mechanism cost, not warmth
    args.execution.eval_fraction = 0.0
    engine = OobleckEngine(args, devices=jax.devices()[:2 * len(hosts)])
    engine.initialize_distributed()
    engine.instantiate_pipelines(args.job.global_num_microbatch)
    return engine


def _steps(engine, n: int) -> None:
    for _ in range(n):
        engine._train_step()


def _recover_and_step(engine, lost_ip: str) -> float:
    """Failure-to-next-step latency: reconfigure + the first step after."""
    t0 = time.perf_counter()
    engine.reconfigure(lost_ip)
    engine._train_step()
    return time.perf_counter() - t0


def _median_step_s(eng, n: int) -> float:
    """Median wall-clock seconds per step over n individually timed steps:
    one scheduler hiccup in a mean would corrupt a fixture forever."""
    samples = []
    for _ in range(n):
        t0 = time.perf_counter()
        eng._train_step()
        samples.append(time.perf_counter() - t0)
    return statistics.median(samples)


def main() -> int:
    out_dir = sys.argv[1] if len(sys.argv) > 1 else os.path.join(
        os.path.dirname(__file__), "data", "degrade_bench")
    if os.path.isdir(out_dir):
        shutil.rmtree(out_dir)
    os.makedirs(out_dir)
    os.environ["OOBLECK_METRICS_DIR"] = out_dir

    from oobleck_tpu.degrade.classify import classify_failure
    from oobleck_tpu.degrade.planner import PipelineSpec, plan_reroute
    from oobleck_tpu.obs.incident import IncidentBuilder
    from oobleck_tpu.utils import metrics

    eng = _make_engine()
    assert len(eng.pipelines) == 2, [p.ranks for p in eng.pipelines]
    _steps(eng, WARMUP_STEPS)

    # Calibrate with synced per-op timing, then measure with it off so the
    # measured steps see the production dispatch path.
    for p in eng.pipelines:
        p.sync_op_timing = True
    _steps(eng, CALIBRATE_STEPS)
    pipe = eng.pipelines[0]
    calibrated = dict(pipe.last_op_times)
    for p in eng.pipelines:
        p.sync_op_timing = False
    pre_step_s = _median_step_s(eng, MEASURE_STEPS)

    # Freeze the rig + calibration BEFORE the failure mutates it.
    rig = {
        "hosts": 2,
        "chips_per_host": eng.chips_per_host,
        "hosts_per_pipeline": 1,
        "microbatches_per_pipeline": pipe.num_microbatches,
        "virtual_stages": pipe.virtual_stages,
        "lost_host": 1,
    }
    op_times = [[s, c, k, total, count]
                for (s, c, k), (total, count) in sorted(calibrated.items())]

    detect_t = time.time()
    recovery_s = _recover_and_step(eng, "10.0.0.1")
    assert len(eng.pipelines) == 1 and eng.pipelines[0].num_microbatches == 8
    reconfigure_s = eng.recovery_times[-1]
    post_step_s = _median_step_s(eng, MEASURE_STEPS)

    # Project through the replay_incident code path: calibrated specs for
    # both replicas, the real classifier, the real planner.
    stages = rig["hosts_per_pipeline"] * rig["chips_per_host"]
    specs = [PipelineSpec(num_stages=stages,
                          num_microbatches=rig["microbatches_per_pipeline"],
                          virtual_stages=rig["virtual_stages"],
                          op_times=calibrated)
             for _ in range(2)]
    ranks = [[pi * stages + i for i in range(stages)] for pi in range(2)]
    plan = plan_reroute(classify_failure(rig["lost_host"], ranks,
                                         rig["chips_per_host"]), specs)
    assert plan.feasible, plan.reason
    retention_projected = plan.throughput_retention
    measured = {
        "pre_failure_step_s": round(pre_step_s, 6),
        "post_reroute_step_s": round(post_step_s, 6),
        "recovery_to_next_step_s": round(recovery_s, 6),
        "reconfigure_s": round(reconfigure_s, 6),
        # The survivor's step cost after absorbing the dead replica's
        # microbatches vs its pre-failure share (half the serialized
        # two-replica step on this homogeneous rig).
        "survivor_slowdown_measured": round(post_step_s / (pre_step_s / 2), 6),
        "survivor_slowdown_projected": round(1.0 / retention_projected, 6),
        "throughput_retention_projected": round(retention_projected, 6),
    }

    disagreement = abs(measured["survivor_slowdown_projected"]
                       - measured["survivor_slowdown_measured"]) \
        / measured["survivor_slowdown_measured"]
    print(json.dumps({"measured": measured,
                      "projected_vs_measured": round(disagreement, 4)}))
    if disagreement > MAX_DISAGREEMENT:
        print(f"REJECT: projected/measured slowdown disagree by "
              f"{disagreement:.1%} > {MAX_DISAGREEMENT:.0%} — noisy run, "
              f"not committing a fixture the cross-val test would fail",
              file=sys.stderr)
        shutil.rmtree(out_dir)
        return 1

    inc = IncidentBuilder("10.0.0.1", cause="bench_injected",
                          rig=rig, op_times=op_times, measured=measured)
    inc.mark("detect", detect_t)
    inc.mark("apply_start", detect_t)
    inc.mark("apply_end", detect_t + reconfigure_s)
    inc.mark("first_step", detect_t + recovery_s)
    path = inc.commit(out_dir)
    flight_path = metrics.flight_recorder().dump("degrade_fixture")
    with open(os.path.join(out_dir, "degrade-bench.json"), "w") as f:
        json.dump({"rig": rig, "measured": measured}, f, indent=1,
                  sort_keys=True)
        f.write("\n")
    print(json.dumps({"incident": path, "flight": flight_path,
                      "out_dir": out_dir}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
