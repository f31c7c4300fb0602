"""kind: train_hostloss -- a host is lost in the middle of the window and
training goes on.

The system under test is the engine of `runners/train.py` over four
one-chip hosts (`build_engine`), two-stage pipelines side by side, every
recovery option at its default: the policy plane chooses the arm, the
recovery precompiler has walked the plans a loss can leave. From the
benchmark come the seed's corpus and weights, the clock, and ONE public
request, `engine.request_reconfiguration(<host>)`, from a timer at
`lose_at_window_share` of the window; the engine applies it at its next
step boundary, as it applies a master's broadcast.

Set-up (all of it `setup_s`): build, install the seed's weights, check
pipeline 0's gradient (both stages' leaves) of one sequence against the
plain reference, then the job's first `warmup_steps` steps through
`engine.train()`, the call and the feed the window uses, on the object the
window gets: after the first, AdamW's first moment of every leaf is read
(its norm in both pipelines; pipeline 0's kept whole, on the chip of the
host that will be lost); after the last, each leaf's change since the seed.
Then the wait for the precompile walk (a real loss comes hours in, long
after the walk has ended; the wait stands in for the hours).
Window: `engine.train()` until a drain request lands at `--seconds`.
After the window, outside `setup_s` and the window, everything `correct` is
decided on is held against `benchmarks/reference/` (`gpt.py`,
`train_steps.py`), which shares nothing with the program:

* the rows the surviving layout would train on next against the corpus's
  rows of that step by the benchmark's own count (exact: a recovery that
  loses a batch or repeats one has moved the feed);
* the surviving pipeline's whole gradient of one sequence from the seed's
  weights, as before the window (the re-planned layout's programs);
* the reference FOLLOWS the job's first `warmup_steps` steps from the seed,
  a row at a time on the chip the loss left idle: each step's loss, the
  first gradient as AdamW got it (norm of the difference over all leaves,
  and the gap of norms by the worst leaf), each leaf's change (gap of norms
  by the worst leaf). Those steps ran through both pipelines, the stage
  hand-offs, the gradient sum between the pipelines and the optimizer.

What no reference here can follow is the optimizer's state ACROSS the loss
(thirty steps from the seed): README-hostloss.md says what stands in.

The three rates and times are `window_metrics`' arithmetic over the
telemetry ring's samples of the window's steps (`step_s`, and `between_s`:
the engine applies the loss between two steps, so the recovery lies in the
first later step's `between_s`), held to the runner's own clock around
`engine.train()` (`ring_clock_gap`); README-hostloss.md has the timeline.
"""

from __future__ import annotations

import gc
import threading
import time

import numpy as np

from benchmarks import trace_detail, trace_reduce
from benchmarks.reference import gpt as ref
from benchmarks.reference import train_steps as plain
from benchmarks.runners import train

COMPILE_COUNTER = "oobleck_compile_seconds_total"
BUILD_SPANS = ("engine.build", "engine.plan", "engine.instantiate")
# The arm that ran, by the flight-recorder event each arm leaves.
ARM_OF_EVENT = {"engine_degraded": "reroute",
                "engine_reconfigured": "reinstantiate",
                "engine_restored": "restore"}
# A traced run's profile: this long before the loss is requested, and this
# long after (the recovery and a few steps of the new layout).
TRACE_BEFORE_S, TRACE_AFTER_S = 6.0, 8.0


def layout(engine, hosts: list[str]) -> dict:
    """Each pipeline's stages as the hosts they run on, the layers of each
    stage and the microbatches the pipeline takes of a step; the hosts the
    engine still counts, and those of them that no pipeline uses."""
    host_of = lambda rank: hosts[rank // engine.chips_per_host]
    pipes, used = [], set()
    for p in engine.pipelines:
        stages = [sorted({host_of(r) for r in st.ranks}) for st in p.stages]
        used.update(h for st in stages for h in st)
        pipes.append({"stage_hosts": stages,
                      "stage_layers": [list(st.layer_ids) for st in p.stages],
                      "microbatches": p.num_microbatches})
    return {"pipelines": pipes, "hosts": list(engine.host_ips),
            "idle_hosts": [h for h in engine.host_ips if h not in used]}


def say_chip_memory(ctx, devices, hosts: list[str], stage: str) -> None:
    """Each chip's bytes in use and its peak so far, by host: which chips a
    peak belongs to (`ctx.say_memory` names the fullest one only)."""
    stats = [d.memory_stats() or {} for d in devices]
    ctx.say("chip_memory", stage=stage,
            bytes_in_use={h: m.get("bytes_in_use")
                          for h, m in zip(hosts, stats)},
            peak_bytes_in_use={h: m.get("peak_bytes_in_use")
                               for h, m in zip(hosts, stats)})


def ring_fields() -> tuple[int, int]:
    """Where a ring sample keeps `between_s` and the fullest chip's bytes
    in use at the step's end (the program's own indices)."""
    from oobleck_tpu.obs import telemetry

    return telemetry.BETWEEN_S, telemetry.HBM_IN_USE


def window_metrics(samples: list, loss_step: int, tokens_per_step: int,
                   chips_before: int, chips_after: int) -> dict:
    """The cell's three numbers from the window's step samples, in the
    order the steps ran: `(step, step_s, ..., between_s at [7], ...)`.
    `loss_step` is the number of the last step that ran on the layout the
    window started with."""
    between, _ = ring_fields()
    before = [s for s in samples if s[0] <= loss_step]
    after = [s for s in samples if s[0] > loss_step]
    out = {"steps_before": len(before), "steps_after": len(after),
           "before_loss_tokens_per_s": None, "recovery_s": None,
           "after_loss_tokens_per_s": None}
    if before:
        # First step's start to the last four-chip step's end.
        seconds = sum(s[1] for s in before) + sum(
            s[between] for s in before[1:])
        out["before_s"] = seconds
        out["before_loss_tokens_per_s"] = (
            len(before) * tokens_per_step / seconds / chips_before)
    if after:
        # Last four-chip step's end to the first later step's end: the
        # seconds in which no step completed.
        out["recovery_s"] = after[0][between] + after[0][1]
        out["recovery_between_s"] = after[0][between]
        out["recovery_first_step_s"] = after[0][1]
    rest = after[1:]
    if rest:
        seconds = sum(s[between] + s[1] for s in rest)
        out["after_s"] = seconds
        out["after_loss_tokens_per_s"] = (
            len(rest) * tokens_per_step / seconds / chips_after)
    return out


def step_numbers_failed(numbers: list[int], first: int) -> int:
    """Step numbers skipped or repeated in `numbers`, which should count up
    from `first` by one."""
    wrong = 0
    expect = first
    for n in numbers:
        if n != expect:
            wrong += 1
        expect = n + 1
    return wrong


def samples_after(ring: list, last_before) -> list:
    """The samples the ring took after `last_before` (the one it held last
    when the window started; None: an empty ring)."""
    for k in range(len(ring) - 1, -1, -1):
        if ring[k] is last_before:
            return ring[k + 1:]
    return list(ring)


# --------------------------------------------------------------------- #
# the job's first steps, on the object the window gets                   #
# --------------------------------------------------------------------- #

def first_moment(state):
    """AdamW's first moment in one layer's optimizer state (the one part
    of it with a `mu` and a `nu`)."""
    import jax

    is_adam = lambda x: hasattr(x, "mu") and hasattr(x, "nu")
    (adam,) = [x for x in jax.tree.leaves(state, is_leaf=is_adam)
               if is_adam(x)]
    return adam.mu


def first_steps(ctx, engine, hold) -> dict:
    """Drive `engine` through the job's first `warmup_steps` steps by
    `engine.train()`. After step 1: each leaf's norm of AdamW's first
    moment, which is (1 - b1) times the first gradient as AdamW got it, in
    every pipeline, and pipeline 0's moments themselves, moved by `hold`
    to where the window leaves room. After the last: each leaf's norm of
    its change since the seed's weights, in every pipeline."""
    import jax

    rc = ref.RefConfig.from_config(ctx.config)
    steps = ctx.cell["traffic"]["warmup_steps"]
    engine.args.job.steps = 1
    engine.train()
    moment_norms, held = {}, None
    for pipe in engine.pipelines:
        state = engine.opt_states[pipe.pipeline_id]
        moments = {li: first_moment(state[li]) for li in sorted(pipe.params)}
        norms = {}
        for li, mu in moments.items():
            norms.update(plain.leaf_norms(li, mu))
        moment_norms[pipe.pipeline_id] = norms
        if held is None:
            held = [hold(moments[li]) for li in sorted(moments)]
        del moments, state
    engine.args.job.steps = steps
    engine.train()
    seeded = plain.by_layer(ref.init_params(ctx.seed, rc, stacked=False))
    change_norms = {}
    for pipe in engine.pipelines:
        norms = {}
        for li in sorted(pipe.params):
            p = pipe.params[li]
            start = jax.device_put(
                seeded[li], jax.tree.map(lambda x: x.sharding, p))
            norms.update(plain.leaf_change_norms(li, p, start))
        change_norms[pipe.pipeline_id] = norms
    return {"steps": steps, "losses": dict(engine.loss_history),
            "moment_norms": moment_norms, "first_moments": held,
            "change_norms": change_norms}


def reference_first_steps(ctx, device, params, hold, mode: str = "highest",
                          fault: str | None = None) -> dict:
    """What `first_steps` reads of the program, read of the plain reference
    as it follows the job's first steps from the seed's `params` (consumed)
    on `device`. With another `mode`, or a `fault`, the reference is in the
    program's place: a control."""
    import jax

    rc = ref.RefConfig.from_config(ctx.config)
    job = ctx.cell["traffic"]
    tokens = lambda step: plain.step_tokens(ctx.seed, step, job, rc.vocab_size)
    out = {"steps": job["warmup_steps"], "losses": {}}
    with jax.default_device(device):
        for step, loss, params, m in plain.follow(
                params, tokens, out["steps"], rc, job, mode,
                job["reference_rows_per_block"], fault):
            out["losses"][step] = loss
            if step == 1:
                out["moment_norms"] = {0: plain.tree_leaf_norms(m)}
                out["first_moments"] = [hold(x) for x in plain.by_layer(m)]
        del m
        seeded = ref.init_params(ctx.seed, rc, stacked=False)
        out["change_norms"] = {
            0: plain.tree_leaf_change_norms(params, seeded)}
    return out


def compare_first_steps(got: dict, want: dict, device) -> dict:
    """The numbers `correct` holds the job's first steps to: `got` against
    `want`, the reference's (both as `first_steps` gives them; the whole
    moments are brought together on `device`)."""
    want_moments, want_changes = want["moment_norms"][0], want["change_norms"][0]
    moved = plain.moved_leaves(want_moments)
    out = {"first_grad_rel_err": plain.rel_err(
               got["first_moments"], want["first_moments"], device),
           "step_loss_rel_err": max(
               np.nan_to_num(abs(got["losses"].get(s, np.nan) - l) / abs(l),
                             nan=np.inf)
               for s, l in want["losses"].items()),
           "leaves_compared": len(want_moments), "leaves_moved": len(moved),
           "losses_program_reference": [
               [s, got["losses"].get(s), l]
               for s, l in sorted(want["losses"].items())]}
    for name, table, against, leaves in (
            ("first_grad_norm_gap", "moment_norms", want_moments, None),
            ("param_change_norm_gap", "change_norms", want_changes, moved)):
        gap, leaf, pid = max(
            plain.worst_norm_gap(norms, against, leaves) + (pid,)
            for pid, norms in got[table].items())
        out[name], out[name + "_at"] = gap, f"pipeline {pid} {leaf}"
    return out


# --------------------------------------------------------------------- #
# the window                                                             #
# --------------------------------------------------------------------- #

def build_span_seconds() -> float:
    """Seconds this process has spent so far in the engine's build spans."""
    from oobleck_tpu.obs.spans import span_recorder

    return sum(s["t1"] - s["t0"] for s in span_recorder().spans()
               if s["name"] in BUILD_SPANS)


def measure(ctx, engine) -> dict:
    """`engine.train()` for the window; one timer requests the loss, one
    the drain that ends the window at the next step boundary. A traced run
    profiles from `TRACE_BEFORE_S` before the request to `TRACE_AFTER_S`
    after it: four chips' operations over the whole window are more than
    the reduction needs."""
    from benchmarks.run import cache_counts
    from oobleck_tpu.obs import telemetry
    from oobleck_tpu.utils import metrics

    job = ctx.cell["traffic"]
    lose_at = job["lose_at_window_share"] * ctx.seconds
    step0 = engine.step
    ring = telemetry.telemetry()
    last_before = ring.last()
    engine.args.job.steps = 1 << 30
    timers = [
        threading.Timer(ctx.seconds, engine.request_drain),
        threading.Timer(lose_at, engine.request_reconfiguration,
                        args=(job["lose_host"],)),
    ]
    if ctx.trace:
        timers += [
            threading.Timer(max(lose_at - TRACE_BEFORE_S, 0.0),
                            ctx.start_trace),
            threading.Timer(lose_at + TRACE_AFTER_S, ctx.stop_trace),
        ]
    for t in timers:
        t.daemon = True
    setup_spans_s = build_span_seconds()
    ctx.window_starts()
    wall0 = time.time()
    t0 = time.perf_counter()
    for t in timers:
        t.start()
    try:
        engine.train()
    finally:
        for t in timers:
            t.cancel()
    elapsed = time.perf_counter() - t0
    for t in timers:
        t.join()            # a profile that is still being written
    ctx.stop_trace()
    at_end = cache_counts()
    return {
        "step0": step0, "steps": engine.step - step0, "elapsed_s": elapsed,
        "samples": samples_after(ring.samples(), last_before),
        "events": [e for e in metrics.flight_recorder().events()
                   if e["t"] >= wall0],
        "losses": dict(engine.loss_history),
        "setup": {"engine_build_s": setup_spans_s,
                  "executables_s": ctx.cache_at_window["compile_s"]},
        "cache": {k: at_end[k] - ctx.cache_at_window[k] for k in at_end},
    }


def say_recovery(ctx, engine, m: dict, applied: dict | None) -> None:
    """The policy's verdict with its reason and each arm's projected cost,
    the arm that ran, the engine's own recovery time, and what compiled or
    was read from the cache inside the window."""
    for e in m["events"]:
        if e["event"] == "policy_decision":
            ctx.say("policy_verdict", mechanism=e.get("mechanism"),
                    reason=e.get("reason"), costs=e.get("costs"),
                    infeasible=e.get("infeasible"))
        elif e["event"] == "degrade_decision":
            ctx.say("degrade_decision", mechanism=e.get("mechanism"),
                    reason=e.get("reason"),
                    estimated_retention=e.get("estimated_retention"),
                    measured_recovery_s=e.get("measured_recovery_s"))
    ctx.say("recovery",
            arm=ARM_OF_EVENT[applied["event"]] if applied else None,
            applied_after_step=applied.get("step") if applied else None,
            engine_recovery_times=list(engine.recovery_times),
            in_window=m["cache"])


def close(engine) -> None:
    """Stop what an engine leaves running (the precompile walk that a
    recovery re-arms for the NEXT loss, the input stagers), so that the
    next phase has the chips and the compiler to itself."""
    walk = getattr(engine, "_precompiler", None)
    if walk is not None:
        walk.cancel()
        walk.wait()
    for dl in engine.dataloaders:
        if hasattr(dl, "close"):
            dl.close()


def next_rows_out_of_place(ctx, engine) -> int:
    """Rows of the batch the engine's loaders hand out next that are not
    the corpus's rows of the step that comes next by the benchmark's own
    count, row for row in the pipelines' order (a step short of rows: all
    of them)."""
    rc = ref.RefConfig.from_config(ctx.config)
    want = plain.step_tokens(ctx.seed, engine.step + 1, ctx.cell["traffic"],
                             rc.vocab_size)
    got = np.concatenate([
        np.asarray(dl.next_batch()["input_ids"]).reshape(-1, want.shape[1])
        for dl in engine.dataloaders])
    if got.shape != want.shape:
        return len(want)
    return int((got != want).any(axis=1).sum())


def split_at_loss(detail: dict, devices: dict) -> dict:
    """What the readers get of a traced run's profile (`detail` as
    `trace_detail.from_profile` gives it, `devices` as
    `trace_reduce.load_xplane` does). The part on the first layout, cut to
    whole steps, is handed over as THE trace detail, so that a reader
    written for a steady window reads a steady two-pipeline window and no
    step of the second layout; each chip's busy seconds in that part; and
    the host spans from the loss on, for the recovery's own metrics."""
    steps = detail["host"].get("engine.step", [])
    lost = detail["host"].get("engine.reconfigure", [])
    if not lost:
        return {}
    t_loss = lost[0][0]
    whole = [s for s in steps if s[0] + s[1] <= t_loss]
    if not whole:
        return {}
    t_first = whole[0][0]
    inside = lambda start: t_first <= start < t_loss
    busy = {
        name: sum(e - s for s, e in trace_reduce.busy_intervals(
            [ev for ev in events if inside(ev[1])])) / 1e9
        for name, events in devices.items()}
    return {
        "trace_detail": {
            "ops": [o for o in detail["ops"] if inside(o[1])],
            "modules": [x for x in detail["modules"] if inside(x[1])],
            "host": {k: [s for s in v if inside(s[0])]
                     for k, v in detail["host"].items()}},
        "trace_detail_recovery": {
            "host": {k: [s for s in v if s[0] >= t_loss]
                     for k, v in detail["host"].items()}},
        "device_busy": {"window_s": (t_loss - t_first) / 1e9,
                        "busy_s": busy},
        "traced_steps_before": len(whole),
    }


def traced_view(ctx) -> dict:
    path = trace_reduce.find_xplane(str(ctx.trace_dir))
    detail = trace_detail.from_profile(path)
    if not detail:
        return {}
    return split_at_loss(detail, trace_reduce.load_xplane(path)["devices"])


def run(ctx) -> dict:
    import jax

    chips = int(ctx.cell["chips"])
    job = ctx.cell["traffic"]
    devices = jax.devices()[:chips]
    hosts = [f"10.0.0.{i}" for i in range(chips)]
    lost_chip = devices[hosts.index(job["lose_host"])]
    engine = train.build_engine(ctx, hosts, devices)
    rc = ref.RefConfig.from_config(ctx.config)
    ctx.say("layout", when="window_start", **layout(engine, hosts))
    ctx.say_memory("engine_built")
    params = ref.init_params(ctx.seed, rc, stacked=False)
    train.install_weights(engine, params)
    numbers = train.check_against_reference(ctx, engine, params, ctx.seed)
    del params
    ctx.say("train_check", when="window_start", **numbers)
    ctx.say_memory("checked")
    # The job's first steps (the first compiles). What is kept of them for
    # the reference waits on the chip of the host that will be lost: a
    # stage-0 chip has no room for it inside the window.
    got = first_steps(ctx, engine, lambda t: jax.device_put(t, lost_chip))
    ctx.say_memory("warmed_up")
    t0 = time.perf_counter()
    walk = engine.start_recovery_precompile(wait=True)
    precompile_wait_s = time.perf_counter() - t0
    ctx.say("recovery_precompile", wait_s=precompile_wait_s,
            stats=None if walk is None else dict(walk.stats))
    say_chip_memory(ctx, devices, hosts, "window_start")

    m = measure(ctx, engine)
    ctx.say_memory("window_closed")
    say_chip_memory(ctx, devices, hosts, "window_end")
    applied = next((e for e in m["events"] if e["event"] in ARM_OF_EVENT),
                   None)
    after = layout(engine, hosts)
    ctx.say("layout", when="window_end", **after)
    say_recovery(ctx, engine, m, applied)
    tokens_per_step = job["global_batch"] * job["seq_len"]
    last_step = m["step0"] + m["steps"]
    # The last step that completed on the layout the window started with.
    loss_step = applied["step"] if applied else last_step
    w = window_metrics(m["samples"], loss_step, tokens_per_step, chips,
                       len(after["hosts"]))
    between, in_use = ring_fields()
    ring_covers_s = sum(s[1] + s[between] for s in m["samples"])
    ctx.say("hostloss_window", steps=m["steps"], elapsed_s=m["elapsed_s"],
            ring_covers_s=ring_covers_s,
            loss_requested_at_s=job["lose_at_window_share"] * ctx.seconds,
            steps_as_number_seconds_between_layout=[
                [s[0], round(s[1], 4), round(s[between], 4),
                 "first" if s[0] <= loss_step else "second"]
                for s in m["samples"]],
            fullest_chip_bytes_in_use={
                "first_layout": max((s[in_use] or 0 for s in m["samples"]
                                     if s[0] <= loss_step), default=None),
                "second_layout": max((s[in_use] or 0 for s in m["samples"]
                                      if s[0] > loss_step), default=None)},
            **w)
    numbers_run = [s[0] for s in m["samples"]]
    failed = step_numbers_failed(numbers_run, m["step0"] + 1) + sum(
        1 for n in numbers_run
        if not np.isfinite(m["losses"].get(n, float("nan"))))
    view = traced_view(ctx) if ctx.trace else {}
    if view:
        # The whole steps of the profile's first part, as a reader that
        # works out a per-step number counts them.
        traced_steps = view.pop("traced_steps_before")
        view["train"] = {"microbatches_run": traced_steps * (
            job["global_batch"] // job["microbatch_size"])}
        ctx.say("traced_first_layout", steps=traced_steps,
                **view["device_busy"])

    # What `correct` is decided on, after the window and outside `setup_s`.
    numbers["ring_clock_gap"] = (
        abs(ring_covers_s - m["elapsed_s"]) / m["elapsed_s"])
    numbers["next_rows_out_of_place"] = next_rows_out_of_place(ctx, engine)
    idle = [d for d, h in zip(devices, hosts) if h in after["idle_hosts"]]
    with jax.default_device(idle[0] if idle else lost_chip):
        params = ref.init_params(ctx.seed, rc, stacked=False)
    train.install_weights(engine, params)
    again = train.check_against_reference(ctx, engine, params, ctx.seed)
    ctx.say("train_check", when="window_end", **again)
    numbers["grad_rel_err_after_loss"] = again["grad_rel_err"]
    close(engine)
    del engine
    gc.collect()
    t0 = time.perf_counter()
    hold = lambda t: jax.device_put(t, lost_chip)
    want = reference_first_steps(
        ctx, next(iter(params["head"]["w"].devices())), params, hold)
    del params
    followed = compare_first_steps(got, want, lost_chip)
    del got, want
    ctx.say("first_steps_against_reference",
            seconds=time.perf_counter() - t0, **followed)
    numbers.update(followed)

    return {
        "attempted": m["steps"], "failed": failed,
        "checks": train.checks_from(numbers, ctx.cell["correct"]),
        "end_to_end": {k: w[k] for k in (
            "before_loss_tokens_per_s", "recovery_s",
            "after_loss_tokens_per_s")},
        "layer_data": {
            "window_counters": {COMPILE_COUNTER: m["cache"]["compile_s"]},
            "setup_seconds": dict(m["setup"],
                                  precompile_wait_s=precompile_wait_s),
            **view},
    }
