"""Headline benchmark: GPT-2 124M training throughput on the local chip(s).

The measurement runs in THIS process, on the accelerator JAX reports, and
the one JSON line it prints names that device: `platform`, `device_kind`
and `device_count`. With no accelerator there is nothing to measure:
bench.py raises, exits non-zero and prints no number. It replays no old
record and offers no CPU stand-in.

On the chip the Pallas flash kernel is validated against the XLA reference
first (forward and dq/dk/dv); a kernel that fails to lower or disagrees
fails the run.

Under further keys of the same line ride the repo's host-side
microbenches (serve, spec, pipeline, degrade, policy, grow, overlap, sim,
master, goodput, pool, router). Each is a CPU world of its own — a child
process on `JAX_PLATFORMS=cpu`, several with forced virtual device counts —
so it leaves the chip to this process, and none of its numbers is a device
metric: they are counts and host-clock figures of control-plane code. One
that fails or outlives its cap leaves `{"error": ...}` under its key and
the headline stands.
"""

import json
import os
import subprocess
import sys
import time

def _measure() -> dict:
    """Run the benchmark in the current process and return the result dict."""
    import jax

    from oobleck_tpu.models import build_model
    from oobleck_tpu.parallel.mesh import MeshShape, make_mesh
    from oobleck_tpu.parallel.train import build_train_step, make_optimizer
    from oobleck_tpu.utils.compile_cache import ensure_persistent_cache

    device = _require_accelerator()
    n = device["device_count"]
    ensure_persistent_cache()
    model_name = os.environ.get("BENCH_MODEL", "gpt2")
    model_args = json.loads(os.environ.get("BENCH_MODEL_ARGS", "null"))
    seq = int(os.environ.get("BENCH_SEQ", "1024"))
    batch = int(os.environ.get("BENCH_BATCH", "8"))
    steps = int(os.environ.get("BENCH_STEPS", "10"))

    model = build_model(model_name, model_args)
    # Numerical validation of the Pallas flash kernels ON DEVICE (fwd +
    # grads vs the XLA reference) — the kernels are exercised by every
    # step below, so a silent numeric bug would poison the headline
    # number. Raises on a mismatch or a kernel that will not lower.
    _validate_flash_on_device()
    mesh = make_mesh(MeshShape.infer(n))  # pure data-parallel across local chips
    init_fn, step_fn = build_train_step(
        model, mesh, num_microbatches=1, optimizer=make_optimizer()
    )
    state = init_fn(jax.random.PRNGKey(0))
    tokens = model.sample_batch(batch, seq)["input_ids"]

    for _ in range(2):  # warmup: compile + 2 steps
        state, metrics = step_fn(state, tokens)
    jax.block_until_ready(metrics.loss)

    t0 = time.perf_counter()
    for _ in range(steps):
        state, metrics = step_fn(state, tokens)
    jax.block_until_ready(metrics.loss)
    dt = time.perf_counter() - t0

    tokens_per_step = batch * seq
    tps_per_chip = tokens_per_step * steps / dt / n

    result = {
        "metric": f"tokens/sec/chip ({model_name} {seq=} {batch=})",
        "value": round(tps_per_chip, 1),
        "unit": "tokens/s/chip",
        **device,
        "flash_validated": True,
    }
    # Achieved FLOP/s and MFU next to raw tokens/s, via the SAME estimate
    # the engine's per-step MFU gauge uses (parallel/train.py) so bench and
    # /metrics can never diverge.
    from oobleck_tpu.parallel.train import estimate_flops_per_token, peak_flops
    from oobleck_tpu.utils import metrics as metrics_mod

    n_params = sum(l.size for l in jax.tree.leaves(state.params))
    cfg = model.config
    flops_per_token = estimate_flops_per_token(
        n_params, seq,
        num_layers=getattr(cfg, "num_layers", 0),
        hidden_size=getattr(cfg, "hidden_size", 0),
    )
    achieved = flops_per_token * tps_per_chip  # per chip
    result["tflops_per_chip"] = round(achieved / 1e12, 2)
    peak = peak_flops(device["device_kind"])
    result["mfu"] = round(achieved / peak, 4)
    # Publish through the real metrics plane too: with OOBLECK_METRICS_DIR
    # set, the headline numbers land in the same JSONL sink the engine and
    # recovery chain write, keeping one trajectory record.
    metrics_mod.set_role("bench")
    reg = metrics_mod.registry()
    reg.gauge("oobleck_bench_tokens_per_sec_per_chip",
              "bench.py headline throughput").set(tps_per_chip)
    reg.gauge("oobleck_bench_tflops_per_chip",
              "bench.py achieved FLOP/s per chip").set(achieved / 1e12)
    reg.gauge("oobleck_bench_mfu", "bench.py MFU").set(achieved / peak)
    metrics_mod.dump_jsonl()
    # Checkpoint-stall microbench (oobleck_tpu/ckpt/bench.py): async writer
    # vs sync baseline p50/p99 so the durability tax is tracked next to
    # throughput. Best-effort — a broken disk must not eat the headline.
    try:
        from oobleck_tpu.ckpt.bench import measure_stalls

        result["ckpt"] = measure_stalls(saves=4, mb=16)
    except Exception as exc:  # noqa: BLE001
        result["ckpt"] = {"error": f"{type(exc).__name__}: {exc}"}
    if os.environ.get("BENCH_COMPARE") == "1":
        # Opt-in: the MPMD interpreter path on the same config, so fused vs
        # interpreter can be compared on identical hardware.
        result["mpmd_tokens_per_sec_per_chip"] = round(
            _measure_mpmd(model, batch, seq, steps, n), 1)
    return result


def _measure_mpmd(model, batch: int, seq: int, steps: int, n: int) -> float:
    """Tokens/s/chip for the MPMD interpreter (single pipeline, one stage
    per chip set) on the same model/shapes as the fused headline."""
    import jax

    from oobleck_tpu.execution.engine import DataParallelEngine  # noqa: F401
    from oobleck_tpu.execution.pipeline import PipelineInstance
    from oobleck_tpu.planning.templates import PipelineTemplate, StageSpec

    nl = model.num_pipeline_layers
    tmpl = PipelineTemplate(
        stages=(StageSpec(layer_indices=tuple(range(nl)), num_chips=n,
                          forward=1.0, backward=3.0, mem_required=1 << 20),),
        iteration_time=4.0, num_layers=nl, num_hosts=1, chips_per_host=n,
    )
    pipe = PipelineInstance(
        pipeline_id=0, template=tmpl, ranks=list(range(n)), model=model,
        devices=jax.devices()[:n], num_microbatches=1,
        total_num_microbatches=1, microbatch_size=batch, seq_len=seq,
        exec_cache={},
    )
    tokens = model.sample_batch(batch, seq)["input_ids"][None]
    for _ in range(2):
        loss = pipe.train_step(tokens)
    jax.block_until_ready(loss)
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = pipe.train_step(tokens)
    jax.block_until_ready(loss)
    dt = time.perf_counter() - t0
    return batch * seq * steps / dt / n


def _validate_flash_on_device() -> None:
    """Flash kernel (fwd + dq/dk/dv) vs the XLA reference on the real chip.
    Raises on a mismatch or a lowering error: a kernel the chip refuses is
    the finding, not a flag on a line that carries on."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from oobleck_tpu.ops.attention import _xla_causal_attention
    from oobleck_tpu.ops.flash import flash_attention

    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q, k, v = (jax.random.normal(kk, (2, 4, 512, 64), jnp.bfloat16) * 0.3
               for kk in ks)
    got = jax.jit(flash_attention)(q, k, v)
    want = jax.jit(_xla_causal_attention)(q, k, v)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=2e-2, atol=2e-2,
    )
    loss_f = lambda fn: (lambda q, k, v: jnp.sum(fn(q, k, v) ** 2))
    gf = jax.jit(jax.grad(loss_f(flash_attention), argnums=(0, 1, 2)))
    gx = jax.jit(jax.grad(loss_f(_xla_causal_attention), argnums=(0, 1, 2)))
    for a, b in zip(gf(q, k, v), gx(q, k, v)):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            rtol=5e-2, atol=5e-2,
        )


def _measure_pipeline() -> dict:
    """1F1B vs interleaved 1F1B on the MPMD interpreter (gpt2-tiny scaled
    to hidden 256 / 6 blocks so block compute dominates embed/head, 2
    stages, 8 microbatches, 2 virtual CPU devices): tokens/s plus the
    schedule-replay bubble (execution/schedule.simulate_bubble — the same
    estimator behind the engine's measured
    oobleck_engine_pipeline_bubble_fraction gauge). Per-chunk durations
    come from a calibration pass with sync_op_timing (block on each
    compute inside the timed region): async-dispatch enqueue times would
    misattribute the step's whole drain to whichever op blocks. The
    acceptance bar is interleaved's measured bubble landing strictly
    below the 1F1B closed form (S-1)/(M+S-1)."""
    import jax

    from oobleck_tpu.execution.pipeline import PipelineInstance
    from oobleck_tpu.execution.schedule import (
        Op,
        bubble_fraction,
        simulate_bubble,
    )
    from oobleck_tpu.models import build_model
    from oobleck_tpu.planning.templates import PipelineTemplate, StageSpec

    S, M = 2, 8
    batch_mb, seq = 2, 128
    steps = int(os.environ.get("BENCH_PIPELINE_STEPS", "3"))
    model = build_model("gpt2-tiny", {"hidden_size": 256, "num_layers": 6,
                                      "max_position_embeddings": 256})
    nl = model.num_pipeline_layers
    split = nl // S
    stages = tuple(
        StageSpec(
            layer_indices=tuple(
                range(i * split, nl if i == S - 1 else (i + 1) * split)),
            num_chips=1, forward=1.0, backward=3.0, mem_required=1 << 20,
        )
        for i in range(S)
    )
    tmpl = PipelineTemplate(stages=stages, iteration_time=4.0, num_layers=nl,
                            num_hosts=S, chips_per_host=1)
    out: dict = {
        "num_stages": S, "num_microbatches": M,
        "bubble_1f1b_closed_form": round(bubble_fraction(S, M), 4),
    }
    tokens = model.sample_batch(batch_mb * M, seq)["input_ids"].reshape(
        M, batch_mb, seq)
    for label, v in (("1f1b", 1), ("interleaved", 2)):
        pipe = PipelineInstance(
            pipeline_id=0, template=tmpl, ranks=list(range(S)), model=model,
            devices=jax.devices()[:S], num_microbatches=M,
            total_num_microbatches=M, microbatch_size=batch_mb, seq_len=seq,
            exec_cache={}, virtual_stages=v,
        )
        for _ in range(2):  # warmup: compile both phases
            loss = pipe.train_step(tokens)
        float(loss)
        pipe.sync_op_timing = True  # calibration: true per-op durations
        durs: dict = {}
        for _ in range(2):
            loss = pipe.train_step(tokens)
            for k, (tot, cnt) in pipe.last_op_times.items():
                a, b = durs.get(k, (0.0, 0))
                durs[k] = (a + tot, b + cnt)
        float(loss)
        pipe.sync_op_timing = False  # throughput: the real async hot path
        t0 = time.perf_counter()
        for _ in range(steps):
            loss = pipe.train_step(tokens)
        float(loss)
        dt = time.perf_counter() - t0

        def dur_fn(inst, _d=durs):
            kind = "b" if inst.op is Op.BACKWARD else "f"
            tot, cnt = _d.get((inst.stage, inst.chunk, kind), (0.0, 0))
            if not cnt:  # chunk never timed: any same-kind average
                same = [tc for (s, c, k), tc in _d.items() if k == kind]
                tot, cnt = (sum(t for t, _ in same),
                            sum(c for _, c in same))
            return tot / cnt if cnt else 1.0

        out[label] = {
            "virtual_stages": v,
            "tokens_per_sec": round(batch_mb * M * seq * steps / dt, 1),
            "bubble_closed_form": round(bubble_fraction(S, M, v), 4),
            "bubble_measured": round(simulate_bubble(S, M, v, dur_fn), 4),
        }
    out["interleaved_beats_1f1b_closed_form"] = (
        out["interleaved"]["bubble_measured"] < out["bubble_1f1b_closed_form"]
    )
    return out


_POOL_KNOBS = (
    "OOBLECK_MASTER_STATE_DIR", "OOBLECK_CHAOS", "OOBLECK_POOL",
    "OOBLECK_POOL_POLICY", "OOBLECK_POOL_LEASE_TTL_S",
    "OOBLECK_POOL_MIN_TRAIN_HOSTS", "OOBLECK_POOL_SWEEP_S",
    "OOBLECK_POOL_QUEUE_HIGH", "OOBLECK_POOL_TTFT_SLO_S", "OOBLECK_POOL_HYST")

# key on the emitted line -> (python argv, wall-clock cap in seconds,
# virtual CPU devices the bench's rig needs, ambient operator knobs the
# bench sets for itself and must not inherit). Each module's docstring
# says what it measures.
_CPU_BENCHES = {
    "serve": (["-m", "oobleck_tpu.serve.bench"], 150, None, ()),
    "spec": (["-m", "oobleck_tpu.serve.spec_bench"], 150, None, ()),
    "pipeline": ([os.path.abspath(__file__), "--pipeline"], 120, 2, ()),
    "degrade": (["-m", "oobleck_tpu.degrade.bench"], 300, 4, ()),
    "policy": (["-m", "oobleck_tpu.policy.bench"], 420, 8,
               ("OOBLECK_POLICY",)),
    "grow": (["-m", "oobleck_tpu.policy.grow_bench"], 300, 8,
             ("OOBLECK_POLICY",)),
    "overlap": (["-m", "oobleck_tpu.parallel.overlap_bench"], 480, 8, ()),
    "sim": (["-m", "oobleck_tpu.sim.bench"], 120, None, ()),
    "master": (["-m", "oobleck_tpu.elastic.master_bench"], 120, None,
               ("OOBLECK_MASTER_STATE_DIR", "OOBLECK_REATTACH_WINDOW")),
    "goodput": (["-m", "oobleck_tpu.obs.goodput_bench"], 120, None,
                ("OOBLECK_STRAGGLER_RATIO", "OOBLECK_STRAGGLER_Z",
                 "OOBLECK_STRAGGLER_PERSIST", "OOBLECK_TELEMETRY")),
    "pool": (["-m", "oobleck_tpu.pool.bench"], 240, None, _POOL_KNOBS),
    "router": (["-m", "oobleck_tpu.serve.router.bench"], 300, None,
               _POOL_KNOBS + ("OOBLECK_ROUTER_PORT", "OOBLECK_ROUTER_PROBE_S",
                              "OOBLECK_ROUTER_SKEW_MAX", "OOBLECK_ROUTER_RETRY",
                              "OOBLECK_ROUTER_URL")),
}


def _run_cpu_bench(argv: list[str], timeout_s: int, devices: int | None = None,
                   scrub: tuple[str, ...] = ()) -> dict:
    """One host-side microbench in a CPU world of its own: the last line of
    its stdout parsed as JSON, or `{"error": ...}`. The child never sees
    the chip (this process holds it), the persistent compile cache (off on
    every CPU world, utils/compile_cache.py) or this process's metrics
    sink."""
    from oobleck_tpu.utils.compile_cache import CPU_WORLD_ENV

    env = {k: v for k, v in os.environ.items() if k not in scrub}
    env.update({"JAX_PLATFORMS": "cpu", "OOBLECK_METRICS_DIR": "",
                **CPU_WORLD_ENV})
    if devices:
        env["XLA_FLAGS"] = (
            env.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={devices}").strip()
    try:
        proc = subprocess.run([sys.executable, *argv], capture_output=True,
                              text=True, env=env, timeout=timeout_s)
    except subprocess.TimeoutExpired:
        return {"error": f"no result within {timeout_s}s"}
    if proc.returncode != 0:
        tail = proc.stderr.strip().splitlines()[-1:] or ["no stderr"]
        return {"error": f"exit {proc.returncode}: {tail[0][:160]}"}
    try:
        return json.loads(proc.stdout.strip().splitlines()[-1])
    except (ValueError, IndexError) as exc:
        return {"error": f"unparseable output: {exc}"}


def _metrics_sink_summary() -> dict | None:
    """Summary of the OOBLECK_METRICS_DIR JSONL sink, or None when the dir is
    unset/empty. Counters and histograms in the sink are per-process
    cumulative, so only the LAST snapshot of each file counts; recovery
    latency merges the per-process histograms before taking percentiles."""
    from oobleck_tpu.utils import metrics as metrics_mod

    d = os.environ.get(metrics_mod.ENV_METRICS_DIR)
    if not d or not os.path.isdir(d):
        return None
    snaps = metrics_mod.latest_per_file(metrics_mod.read_jsonl_dir(d))
    if not snaps:
        return None
    summary: dict = {"snapshots": len(snaps)}
    for key, name in (("tokens_per_sec", "oobleck_engine_tokens_per_sec"),
                      ("mfu", "oobleck_engine_mfu")):
        series = metrics_mod.find_series(snaps, name)
        if series:
            summary[key] = round(max(s.get("value", 0.0) for s in series), 4)
    rec = metrics_mod.merge_histogram_series(
        metrics_mod.find_series(snaps, "oobleck_recovery_latency_seconds"))
    if rec and rec.get("count"):
        summary["recovery_latency_s"] = {
            "count": int(rec["count"]),
            "p50": round(metrics_mod.histogram_percentile(rec, 0.50), 3),
            "p90": round(metrics_mod.histogram_percentile(rec, 0.90), 3),
            "p99": round(metrics_mod.histogram_percentile(rec, 0.99), 3),
        }
    return summary


def _analysis_summary() -> dict:
    """One oobleck-lint run over the tree: rule inventory plus finding
    counts, so the bench line records the static-analysis posture the
    build shipped with (and a diff catches a finding-count creep)."""
    from pathlib import Path

    from oobleck_tpu.analysis import all_rules, run_analysis

    result = run_analysis(Path(__file__).resolve().parent)
    s = result.summary()
    return {
        "rules": s["rules"],
        "rule_codes": [r.code for r in all_rules()],
        "files_scanned": s["files"],
        "findings": s["findings_new"],
        # Deliberately NOT named *findings*: a new justified suppression
        # is not a regression, and the diff keys direction off the name.
        "suppressed": s["findings_suppressed"],
        "baselined": s["findings_baselined"],
        "parse_errors": s["parse_errors"],
    }


def _emit(result: dict) -> None:
    # Fold in the JSONL metrics sink (engine gauges, recovery-latency
    # percentiles) so the perf trajectory is tracked from real counters
    # rather than ad-hoc prints. A corrupt sink must not cost the line.
    try:
        sink = _metrics_sink_summary()
        if sink:
            result["metrics_sink"] = sink
    except Exception as exc:  # noqa: BLE001 — reported on the line
        result["metrics_sink_error"] = f"{type(exc).__name__}: {exc}"
    for key, (argv, timeout_s, devices, scrub) in _CPU_BENCHES.items():
        result[key] = _run_cpu_bench(argv, timeout_s, devices, scrub)
    # Static-analysis posture (oobleck_tpu/analysis): in-process, cheap.
    # `findings` counts NEW findings — anything nonzero means the tree
    # regressed against the lint gate, so the diff treats it lower-is-
    # better (see _LOWER_BETTER).
    try:
        result["analysis"] = _analysis_summary()
    except Exception as exc:  # noqa: BLE001 — reported on the line
        result["analysis"] = {"error": f"{type(exc).__name__}: {exc}"}
    print(json.dumps(result))


# --------------------------------------------------------------------------
# --diff: round-over-round comparison of the emitted bench lines.

# Relative change below this is noise, not a finding.
DIFF_THRESHOLD = 0.05

# Key-name fragments whose metrics improve DOWNWARD (latencies, pauses,
# stalls, bubbles). Everything else is treated as higher-is-better.
# Rate/ratio fragments win over any lower-is-better match: "_s" as a bare
# substring would swallow "_sec"/"_speedup" and invert the headline
# throughput keys, so unit suffixes are matched as suffixes only.
_HIGHER_BETTER = ("per_sec", "per_second", "speedup", "retention",
                  "throughput", "goodput", "agreement", "sustained",
                  "hit_rate", "hidden_fraction", "attainment")
_LOWER_BETTER = ("latency", "seconds", "ttft", "pause", "bubble", "stall",
                 "p50", "p90", "p99", "findings", "parse_errors", "regret",
                 "bytes_per_token", "abs_diff", "overhead", "failed",
                 "dropped")
_LOWER_BETTER_SUFFIXES = ("_s", "_ms", "_us")


def _round_files() -> list[str]:
    """BENCH_r*.json next to this script, ordered by round number."""
    import glob
    import re

    here = os.path.dirname(os.path.abspath(__file__))
    out = []
    for path in glob.glob(os.path.join(here, "BENCH_r*.json")):
        m = re.match(r"BENCH_r(\d+)\.json$", os.path.basename(path))
        if m:
            out.append((int(m.group(1)), path))
    return [p for _, p in sorted(out)]


def _parsed_line(path: str) -> dict | None:
    """The emitted bench line inside one round file (the driver wraps it
    under "parsed"; accept a bare line too)."""
    try:
        with open(path) as f:
            rec = json.load(f)
    except Exception:
        return None
    if isinstance(rec, dict) and isinstance(rec.get("parsed"), dict):
        return rec["parsed"]
    if isinstance(rec, dict) and "value" in rec:
        return rec
    return None


def _numeric_leaves(d: dict, prefix: str = "") -> dict:
    """Flatten to {dotted.key: float}. A section that errored has no
    numbers, so it shows up as keys gone, never as a regression."""
    out: dict = {}
    for k, v in d.items():
        if k in ("note", "metric", "unit", "config", "device_count"):
            continue
        key = f"{prefix}{k}"
        if isinstance(v, bool):
            continue
        if isinstance(v, (int, float)):
            out[key] = float(v)
        elif isinstance(v, dict):
            out.update(_numeric_leaves(v, key + "."))
    return out


def _lower_is_better(key: str) -> bool:
    leaf = key.rsplit(".", 1)[-1]
    if any(frag in leaf for frag in _HIGHER_BETTER):
        return False
    return (leaf.endswith(_LOWER_BETTER_SUFFIXES)
            or any(frag in leaf for frag in _LOWER_BETTER))


def bench_diff(old: dict, new: dict) -> tuple[list[str], list[str]]:
    """(report_lines, regressions) comparing two emitted bench lines."""
    a, b = _numeric_leaves(old), _numeric_leaves(new)
    lines: list[str] = []
    regressions: list[str] = []
    for key in sorted({**a, **b}):
        if key not in a:
            lines.append(f"  {key}: (new) {b[key]:g}")
            continue
        if key not in b:
            lines.append(f"  {key}: {a[key]:g} -> (gone)")
            continue
        ov, nv = a[key], b[key]
        if ov == 0:
            delta = 0.0 if nv == 0 else float("inf")
        else:
            delta = (nv - ov) / abs(ov)
        if abs(delta) < DIFF_THRESHOLD:
            continue
        worse = delta > 0 if _lower_is_better(key) else delta < 0
        tag = "REGRESSION" if worse else "improved"
        lines.append(f"  {key}: {ov:g} -> {nv:g} ({delta:+.1%}) {tag}")
        if worse:
            regressions.append(key)
    return lines, regressions


def _diff_main() -> int:
    files = _round_files()
    if len(files) < 2:
        print(f"bench --diff: need two BENCH_r*.json rounds, have "
              f"{len(files)}")
        return 0
    old_path, new_path = files[-2], files[-1]
    old, new = _parsed_line(old_path), _parsed_line(new_path)
    if old is None or new is None:
        print("bench --diff: unparseable round file "
              f"({old_path if old is None else new_path})")
        return 1
    print(f"bench --diff: {os.path.basename(old_path)} -> "
          f"{os.path.basename(new_path)}")
    lines, regressions = bench_diff(old, new)
    for line in lines or ["  no changes beyond "
                          f"{DIFF_THRESHOLD:.0%} threshold"]:
        print(line)
    if regressions:
        print(f"{len(regressions)} regression(s): {', '.join(regressions)}")
        return 1
    return 0


def _require_accelerator() -> dict:
    """The device fields of the emitted line — or no line at all."""
    import jax

    devs = jax.devices()
    if devs[0].platform == "cpu":
        raise SystemExit(
            "bench.py measures an accelerator and JAX found none "
            "(platform cpu): nothing to report")
    return {"platform": devs[0].platform,
            "device_kind": devs[0].device_kind,
            "device_count": len(devs)}


def main() -> None:
    if "--diff" in sys.argv[1:]:
        raise SystemExit(_diff_main())
    if "--pipeline" in sys.argv[1:]:
        print(json.dumps(_measure_pipeline()))
        return
    _emit(_measure())


if __name__ == "__main__":
    main()
