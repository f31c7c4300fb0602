"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py             # one TPU chip
    python chip_smoke.py --chips 4   # one four-chip host: the cross-chip path only

One chip, in order, every phase a child process that has exited before the
next starts (a chip belongs to one process at a time; this parent stays off
JAX until its last step):

  1. kernels   the Pallas flash (fwd + dq/dk/dv), paged-decode and
               paged-verify kernels against their XLA references at gpt2
               124M widths and the serve engine's default geometry, and the
               Mamba-2 scan's two (`ssd_fwd`, `ssd_bwd`) against its
               `jax.numpy` path at nemotron-3-nano-30b-a3b's widths, each
               shown to be a compiled Mosaic kernel (`tpu_custom_call`);
  2. trainer   `python -m oobleck_tpu.elastic.master` plus
               `python -m oobleck_tpu.elastic.run --config-path <yaml>`:
               examples/gpt2.yaml (12 layers / 768 / 12 heads, seq 1024,
               microbatch 8, bf16, remat) with `steps` cut and checkpoints
               on, through master -> agent -> worker on a cold profile
               cache: profile, plan, instantiate, train, commit, exit 0;
  3. server    `python -m oobleck_tpu.serve.server` on the checkpoint root
               the trainer wrote (paged KV at its defaults, lookup
               speculation on), answering /healthz and a few
               /v1/generate requests of mixed prompt length;
  4. device    the parent asks JAX what it ran on and prints the last line.

Four chips, in ONE child that owns all four devices: the MPMD engine over
four one-chip hosts (two two-chip pipelines, DP allreduce between them, a
host lost and the plan re-instantiated on the survivors) and the fused step on a (data=2, stage=2)
mesh, each against a one-device run of the same seed, global batch and
step count, plus where the parameters actually live.

Every line printed before the last is an observation that names the device
it was taken on; none is a claim. Any phase that fails ends the run with a
non-zero exit and its name; nothing is caught and carried past. There is no
CPU branch: without a TPU, or beside nothing else of the repo, the script
fails and prints no result. The last line of stdout on success is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import time
import math
import urllib.error
import urllib.request
from pathlib import Path

# JAX-free, and the first thing to fail beside nothing else of the repo.
from oobleck_tpu.utils import metrics
from oobleck_tpu.utils.compile_cache import cache_entries

HERE = Path(__file__).resolve().parent
OUT = HERE / "chiprun_out" / "chip_smoke"
SELF = [sys.executable, str(Path(__file__).resolve())]
PLATFORM = "tpu"

# The trainer's job: examples/gpt2.yaml with these fields — which exist —
# set, and nothing else changed.
TRAIN_YAML = HERE / "examples" / "gpt2.yaml"
TRAIN_STEPS = 6
CKPT_INTERVAL = 3
VOCAB = 50257  # gpt2's; request tokens are drawn below it
# Prompt lengths of the requests (the server's default max_seq is 256), plus
# one prompt that repeats a 6-token pattern this many times.
PROMPT_LENS, PATTERN_REPEATS, MAX_TOKENS = (5, 37, 150), 12, 24
# Wall-clock caps per phase (seconds, compilation included); their sum
# stays inside the 1200 s the whole script is given.
KERNELS_CAP, TRAINER_CAP, SERVER_CAP, CROSSCHIP_CAP = 180, 540, 360, 1100

# Cross-chip leg (--chips 4): gpt2 124M at full width and depth.
CROSS_MODEL, CROSS_MODEL_ARGS = "gpt2", {}
CROSS_MICROBATCH, CROSS_GLOBAL_BATCH = 2, 16
CROSS_STEPS_BEFORE, CROSS_STEPS_AFTER = 3, 3
# The repo's "same loss trajectory on every mesh" bound
# (tests/test_train_spmd.py): relative, per step.
TRAJECTORY_RTOL = 2e-2


class PhaseFailed(Exception):
    pass


def say(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def need(ok: bool, phase: str, why: str) -> None:
    if not ok:
        raise PhaseFailed(f"{phase}: {why}")


# --------------------------------------------------------------------- #
# child phases: these own the chip                                       #
# --------------------------------------------------------------------- #

def _device_record() -> dict:
    import jax

    devs = jax.devices()
    need(devs[0].platform == PLATFORM, "device",
         f"JAX reports platform {devs[0].platform!r}, not {PLATFORM!r}")
    return {"platform": devs[0].platform, "device_kind": devs[0].device_kind,
            "device_count": len(devs)}


def _cache_counts() -> dict:
    """This process's reads of and writes to the persistent compile cache
    (JAX's own events, utils/compile_cache.py)."""
    reg = metrics.registry()
    ctr = reg.counter("oobleck_compile_cache_events_total")
    return {"cache_entries_read": int(ctr.value(event="entry_read")),
            "cache_entries_written": int(ctr.value(event="entry_written")),
            "compile_s": round(
                reg.counter("oobleck_compile_seconds_total").value(), 1)}


def _max_err(got, want) -> float:
    import numpy as np

    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    need(got.shape == want.shape, "kernels", f"{got.shape} != {want.shape}")
    need(bool(np.isfinite(got).all()), "kernels", "non-finite kernel output")
    return float(np.max(np.abs(got - want)))


def phase_kernels() -> None:
    """Each kernel on the device against its XLA reference, at the widths
    and the geometry the trainer and the server are about to use."""
    import jax
    import jax.numpy as jnp

    from oobleck_tpu.config import ServeArguments
    from oobleck_tpu.ops.attention import _xla_causal_attention
    from oobleck_tpu.ops.flash import flash_attention
    from oobleck_tpu.ops.paged_attention import (
        _paged_decode_pallas,
        _paged_decode_xla,
        _paged_verify_pallas,
        _paged_verify_xla,
    )
    from oobleck_tpu.ops.ssd import _scan_xla, ssd_scan
    from oobleck_tpu.serve.kv_blocks import pages_for
    from oobleck_tpu.utils.compile_cache import ensure_persistent_cache

    device = _device_record()
    ensure_persistent_cache()

    def check(name, kernel, reference, args, tol):
        fn = jax.jit(kernel)
        compiled = fn.lower(*args).compile()
        need("tpu_custom_call" in compiled.as_text(), "kernels",
             f"{name}: no Mosaic kernel in the executable (interpreted or "
             "reference path?)")
        got = jax.tree.leaves(jax.block_until_ready(fn(*args)))
        want = jax.tree.leaves(jax.jit(reference)(*args))
        err = max(_max_err(g, w) for g, w in zip(got, want))
        need(err <= tol, "kernels", f"{name}: max abs err {err} > {tol}")
        say("kernels", check=name, max_abs_err=err, tol=tol, **device)

    # gpt2 124M, one microbatch of examples/gpt2.yaml: [8, 12, 1024, 64].
    ks = jax.random.split(jax.random.PRNGKey(7), 8)
    q, k, v = (jax.random.normal(kk, (8, 12, 1024, 64), jnp.bfloat16) * 0.3
               for kk in ks[:3])
    check("flash_fwd", flash_attention, _xla_causal_attention, (q, k, v),
          2e-2)
    grads = lambda fn: jax.grad(
        lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32) ** 2),
        argnums=(0, 1, 2))
    check("flash_dq_dk_dv", grads(flash_attention),
          grads(_xla_causal_attention), (q, k, v), 5e-2)

    # The serve engine's defaults, derived as ServingPlane._build_engine and
    # _build_spec derive them; gpt2's MHA pools (12 heads of 64).
    a = ServeArguments()
    pages = a.kv_pages or max(2, a.slots * a.max_seq // a.page_size)
    lanes = a.lanes or max(a.slots, min(pages - 1, 8 * a.slots))
    table, t = pages_for(a.max_seq, a.page_size), a.spec_k + 1
    kp, vp = (jax.random.normal(kk, (pages, 12, a.page_size, 64),
                                jnp.bfloat16) * 0.3 for kk in ks[3:5])
    # Lanes' chains alias pages (the default pool is smaller than lanes x
    # table), which a read-only check does not mind; lengths are ragged,
    # with room for the verify rows' t - 1 extra keys.
    tables = (1 + jnp.arange(lanes * table, dtype=jnp.int32) % (pages - 1)
              ).reshape(lanes, table)
    lengths = jax.random.randint(ks[5], (lanes,), 1, a.max_seq - t,
                                 jnp.int32)
    qd = jax.random.normal(ks[6], (lanes, 12, 64), jnp.bfloat16) * 0.3
    qv = jax.random.normal(ks[7], (lanes, t, 12, 64), jnp.bfloat16) * 0.3
    check("paged_decode", _paged_decode_pallas, _paged_decode_xla,
          (qd, kp, vp, tables, lengths), 2e-2)
    check(f"paged_verify_T{t}", _paged_verify_pallas, _paged_verify_xla,
          (qv, kp, vp, tables, lengths), 2e-2)

    # The Mamba-2 scan at nemotron-3-nano-30b-a3b's widths (heads of 64 in
    # groups of 8, a state of 128, chunks of 128), 16 heads of its 64 over a
    # quarter of its sequence; the reference is the scan's own `jax.numpy`
    # path, every gradient over its own largest entry.
    ks = jax.random.split(jax.random.PRNGKey(11), 6)
    scan_args = (
        jax.random.normal(ks[0], (1, 1024, 16, 64), jnp.bfloat16),
        0.1 * jax.nn.softplus(jax.random.normal(ks[1], (1, 1024, 16))),
        -jnp.exp(jax.random.normal(ks[2], (16,))),
        jax.random.normal(ks[3], (1, 1024, 2, 128), jnp.bfloat16) * 0.3,
        jax.random.normal(ks[4], (1, 1024, 2, 128), jnp.bfloat16) * 0.3,
        jax.random.normal(ks[5], (16,)))
    scan_grads = lambda fn: lambda *args: [
        g / jnp.max(jnp.abs(g)) for g in jax.grad(
            lambda *a: jnp.sum(fn(*a).astype(jnp.float32) ** 2),
            argnums=range(6))(*args)]
    scan_xla = lambda *args: _scan_xla(*args, 128)
    check("ssd_fwd", lambda *args: ssd_scan(*args, chunk=128), scan_xla,
          scan_args, 5e-2)
    check("ssd_bwd", scan_grads(lambda *args: ssd_scan(*args, chunk=128)),
          scan_grads(scan_xla), scan_args, 2e-2)
    say("kernels", done=True, **_cache_counts(), **device)


def _engine(node_ips, devices, **execution):
    from oobleck_tpu.config import (
        DistributedArguments,
        ExecutionArguments,
        JobArguments,
        ModelArguments,
        OobleckArguments,
    )
    from oobleck_tpu.execution.engine import OobleckEngine

    args = OobleckArguments(
        dist=DistributedArguments(node_ips=list(node_ips)),
        job=JobArguments(
            microbatch_size=CROSS_MICROBATCH,
            global_microbatch_size=CROSS_GLOBAL_BATCH,
            steps=CROSS_STEPS_BEFORE + CROSS_STEPS_AFTER,
            learning_rate=1e-4, warmup_steps=2),
        model=ModelArguments(model_name=CROSS_MODEL,
                             model_args=dict(CROSS_MODEL_ARGS),
                             dataset_path="synthetic"),
        execution=ExecutionArguments(**execution),
    )
    engine = OobleckEngine(args, devices=list(devices))
    engine.initialize_distributed()
    engine.instantiate_pipelines(args.job.global_num_microbatch)
    return engine


def _leaf_device_ids(tree) -> set[int]:
    import jax

    return {d.id for leaf in jax.tree.leaves(tree)
            for d in leaf.sharding.device_set}


def _same_trajectory(name, got, want, device) -> None:
    need(all(math.isfinite(x) for x in got), "crosschip",
         f"{name}: non-finite loss in {got}")
    worst = max(abs(g - w) / abs(w) for g, w in zip(got, want))
    say("crosschip", check=f"{name}_vs_one_device", losses=got,
        one_device_losses=want, max_rel_diff=worst, rtol=TRAJECTORY_RTOL,
        loss_fell=got[-1] < got[0], **device)
    need(len(got) == len(want) and worst <= TRAJECTORY_RTOL, "crosschip",
         f"{name}: trajectory {got} leaves the one-device run {want} "
         f"(max rel diff {worst} > {TRAJECTORY_RTOL})")


def phase_crosschip() -> None:
    """What exists only across chips, and what it is compared with."""
    import gc

    import jax

    from oobleck_tpu.utils.compile_cache import ensure_persistent_cache

    device = _device_record()
    need(device["device_count"] == 4, "crosschip",
         f"needs 4 devices, JAX reports {device['device_count']}")
    ensure_persistent_cache()
    os.environ["OOBLECK_TPU_CACHE"] = str(OUT / "tpu_cache")
    devs = jax.devices()
    hosts = [f"10.0.0.{i}" for i in range(4)]
    n_steps = CROSS_STEPS_BEFORE + CROSS_STEPS_AFTER

    # -- MPMD: four one-chip hosts, lose one, go on ---------------------- #
    t0 = time.monotonic()
    # num_stages=2: two-chip pipelines, so stage-to-stage edges cross chips
    # too (left alone, the planner may give each chip a one-stage pipeline).
    eng = _engine(hosts, devs, engine_path="mpmd", num_stages=2)
    need(len(eng.pipelines) >= 2, "crosschip",
         f"plan has {len(eng.pipelines)} pipeline(s); DP sync needs >= 2")
    need(all(p.num_stages == 2 for p in eng.pipelines), "crosschip",
         "a pipeline does not span two chips")
    placed = set()
    for pipe in eng.pipelines:
        for st in pipe.stages:
            want = {eng.devices[r].id for r in st.ranks}
            for li in st.layer_ids:
                got = _leaf_device_ids(pipe.params[li])
                need(got == want, "crosschip",
                     f"pipeline {pipe.pipeline_id} layer {li} lives on "
                     f"devices {sorted(got)}, its stage's ranks are "
                     f"{sorted(want)}")
            placed |= want
    need(placed == {d.id for d in devs}, "crosschip",
         f"parameters on devices {sorted(placed)} only")
    say("crosschip", check="mpmd_placement",
        pipelines=[[list(st.ranks) for st in p.stages]
                   for p in eng.pipelines],
        param_device_ids=sorted(placed), **device)
    losses = [eng._train_step() for _ in range(CROSS_STEPS_BEFORE)]
    shared = [li for li, ow in eng.dp_engine.owners.items() if len(ow) > 1]
    dp_transfers = eng.dp_engine.last_transfer_count
    need(bool(shared) and dp_transfers > 0, "crosschip",
         "no DP allreduce ran between the pipelines")
    eng.reconfigure(hosts[1])
    need(len(eng.recovery_times) == 1, "crosschip", "reconfigure left no "
         "recovery record")
    lost = eng.devices[1].id
    after = set().union(*(_leaf_device_ids(p.params) for p in eng.pipelines))
    need(lost not in after and after, "crosschip",
         f"after losing {hosts[1]} parameters sit on {sorted(after)}")
    losses += [eng._train_step() for _ in range(CROSS_STEPS_AFTER)]
    say("crosschip", check="mpmd_reconfigure", lost_host=hosts[1],
        recovery_s=eng.recovery_times[0],
        pipelines_after=[[list(st.ranks) for st in p.stages]
                         for p in eng.pipelines],
        param_device_ids_after=sorted(after),
        dp_shared_layers=len(shared), dp_transfers_per_step=dp_transfers,
        wall_s=time.monotonic() - t0, **device)
    del eng
    gc.collect()

    ref = _engine(hosts[:1], devs[:1], engine_path="mpmd")
    ref_losses = [ref._train_step() for _ in range(n_steps)]
    del ref
    gc.collect()
    _same_trajectory("mpmd_4_hosts_one_lost", losses, ref_losses, device)

    # -- fused: one SPMD program over (data=2, stage=2) ------------------ #
    t0 = time.monotonic()
    eng = _engine(hosts[:1], devs, engine_path="fused", num_stages=2, fsdp=1)
    shape = {k: v for k, v in eng.fused.mesh.shape.items() if v > 1}
    need(shape == {"data": 2, "stage": 2}, "crosschip",
         f"fused mesh is {dict(eng.fused.mesh.shape)}")
    placed = _leaf_device_ids(eng.fused.state.params)
    need(placed == {d.id for d in devs}, "crosschip",
         f"fused parameters on devices {sorted(placed)} only")
    fused_losses = [eng._train_step() for _ in range(n_steps)]
    say("crosschip", check="fused_mesh", mesh=shape,
        param_device_ids=sorted(placed), wall_s=time.monotonic() - t0,
        **device)
    del eng
    gc.collect()
    ref = _engine(hosts[:1], devs[:1], engine_path="fused", fsdp=1)
    ref_losses = [ref._train_step() for _ in range(n_steps)]
    del ref
    _same_trajectory("fused_data2_stage2", fused_losses, ref_losses, device)
    say("crosschip", done=True, **_cache_counts(), **device)


# --------------------------------------------------------------------- #
# parent: starts the program's own processes, never touches the chip     #
# --------------------------------------------------------------------- #

def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _child_env(**extra) -> dict:
    env = dict(os.environ)
    env.update({
        "PYTHONPATH": str(HERE) + os.pathsep + env.get("PYTHONPATH", ""),
        "PYTHONUNBUFFERED": "1",
        # Profiles land inside this run's output: the profile cache is cold.
        "OOBLECK_TPU_CACHE": str(OUT / "tpu_cache"),
        **extra,
    })
    return env


def _stop(proc: subprocess.Popen, sig=signal.SIGTERM) -> None:
    """End a process this script started, and everything it started."""
    if proc.poll() is None:
        try:
            os.killpg(proc.pid, sig)
            proc.wait(timeout=20)
        except (ProcessLookupError, subprocess.TimeoutExpired):
            pass
    try:
        os.killpg(proc.pid, signal.SIGKILL)
    except ProcessLookupError:
        pass
    proc.wait()


def _wait_for_line(log: Path, needle: str, proc: subprocess.Popen,
                   deadline: float, phase: str, bad: str = "") -> str:
    while True:
        text = log.read_text(errors="replace")
        for line in text.splitlines():
            if needle in line:
                return line
        need(not bad or bad not in text, phase,
             f"{bad!r} in the log; tail:\n" + text[-3000:])
        need(proc.poll() is None, phase,
             f"process exited {proc.returncode} before {needle!r}; log tail:\n"
             + text[-3000:])
        need(time.monotonic() < deadline, phase,
             f"no {needle!r} within the phase's cap; log tail:\n"
             + text[-3000:])
        time.sleep(0.5)


def _group_runs(pgid: int, needle: str) -> bool:
    """Whether a live process of that process group has `needle` in its
    command line (the master's launcher starts the agent as its child)."""
    for d in Path("/proc").iterdir():
        if not d.name.isdigit():
            continue
        try:
            # /proc/<pid>/stat: "pid (comm) state ..."; the master never
            # reaps its launcher's children, so an exited agent stays as Z.
            state = (d / "stat").read_text().rsplit(")", 1)[1].split()[0]
            if (state != "Z" and os.getpgid(int(d.name)) == pgid
                    and needle in (d / "cmdline").read_text()):
                return True
        except (OSError, IndexError):
            continue  # gone while we looked
    return False


def _sink_values(metrics_dir: Path):
    """Reader of the JSONL metrics sink the phase's processes wrote: the
    last value of a series in each process, filtered by labels."""
    snaps = metrics.latest_per_file(metrics.read_jsonl_dir(str(metrics_dir)))

    def values(name: str, **labels) -> list[float]:
        return [s.get("value", 0.0) for s in metrics.find_series(snaps, name)
                if all(s.get("labels", {}).get(k) == v
                       for k, v in labels.items())]

    return values


def run_child_phase(name: str, cap_s: int) -> dict:
    """One of this script's own phases as a child; returns its last record
    (which names the device it ran on)."""
    entries = cache_entries()
    proc = subprocess.run(SELF + ["--phase", name], env=_child_env(),
                          stdout=subprocess.PIPE, text=True, timeout=cap_s)
    sys.stdout.write(proc.stdout)
    sys.stdout.flush()
    need(proc.returncode == 0, name, f"child exited {proc.returncode}")
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    need(last.get("done") is True, name, "child ended without its record")
    say(name, cache_entries_before=entries,
        cache_entries_after=cache_entries())
    return {k: last[k] for k in ("platform", "device_kind", "device_count")}


def phase_trainer(device: dict) -> Path:
    import re

    import yaml

    from oobleck_tpu.ckpt.restore import complete_step_dirs

    phase, t0 = "trainer", time.monotonic()
    deadline = t0 + TRAINER_CAP
    port, ckpt = _free_port(), OUT / "ckpt"
    job = yaml.safe_load(TRAIN_YAML.read_text())
    job["dist"]["master_port"] = port
    job["job"]["steps"] = TRAIN_STEPS
    job["execution"]["checkpoint_dir"] = str(ckpt)
    job["execution"]["checkpoint_interval"] = CKPT_INTERVAL
    cfg = OUT / "gpt2.yaml"
    cfg.write_text(yaml.safe_dump(job, sort_keys=False))
    metrics_dir = OUT / "metrics" / phase
    env = _child_env(OOBLECK_METRICS_DIR=str(metrics_dir))
    entries = cache_entries()

    log = OUT / "trainer.log"
    with open(log, "wb") as logf:
        master = subprocess.Popen(
            [sys.executable, "-m", "oobleck_tpu.elastic.master",
             "--port", str(port)],
            env=env, stdout=logf, stderr=subprocess.STDOUT, cwd=HERE,
            start_new_session=True)
    try:
        _wait_for_line(log, "master listening", master, deadline, phase)
        subprocess.run(
            [sys.executable, "-m", "oobleck_tpu.elastic.run",
             "--config-path", str(cfg)],
            env=env, check=True, timeout=60, cwd=HERE)
        _wait_for_line(log, "reports training complete", master, deadline,
                       phase, bad="worker process died")
        # The agent leaves by itself once its worker has exited 0.
        while _group_runs(master.pid, "oobleck_tpu.elastic.agent"):
            need(time.monotonic() < deadline, phase,
                 "the agent outlived its finished worker")
            time.sleep(0.2)
    finally:
        _stop(master)
    text = log.read_text(errors="replace")
    need("worker finished training; agent exiting" in text, phase,
         "the agent did not report a clean worker exit")
    need(f"worker on 1 x {device['device_kind']}" in text, phase,
         f"the worker did not name {device['device_kind']!r} as its device")

    losses = [float(x) for x in re.findall(
        rf"step \d+/{TRAIN_STEPS} loss ([-+.\deEinfa]+)", text)]
    need(len(losses) >= TRAIN_STEPS, phase,
         f"{len(losses)} step losses logged, {TRAIN_STEPS} asked for")
    need(all(math.isfinite(x) for x in losses), phase,
         f"non-finite loss in {losses}")
    need(losses[-1] < losses[0], phase, f"loss did not fall: {losses}")
    steps = [s for s, _ in complete_step_dirs(ckpt)]
    need(TRAIN_STEPS in steps, phase,
         f"no committed checkpoint of step {TRAIN_STEPS} under {ckpt} "
         f"(committed: {steps})")
    if "pipeline templates from the native planner" in text:
        planner = "native"
    else:
        warn = [l for l in text.splitlines()
                if "native planner unavailable" in l]
        need(bool(warn), phase, "no line says which planner made the plan")
        planner = "python (fallback): " + warn[0].split("unavailable", 1)[1]
    shape = re.search(r"model (\S+): (\d+) pipeline layers, hidden (\d+), "
                      r"seq_len (\d+)", text)
    need(shape is not None, phase, "the engine did not log its model shape")
    sink = _sink_values(metrics_dir)
    say(phase, entry="elastic.master + elastic.run --config-path",
        model=shape.group(1), pipeline_layers=int(shape.group(2)),
        hidden=int(shape.group(3)), seq_len=int(shape.group(4)),
        microbatch=job["job"]["microbatch_size"],
        global_batch=job["job"]["global_microbatch_size"],
        steps=len(losses), losses=losses, committed_steps=steps,
        planner=planner, wall_s=round(time.monotonic() - t0, 1),
        tokens_per_sec=max(sink("oobleck_engine_tokens_per_sec"),
                           default="not measured"),
        mfu=max(sink("oobleck_engine_mfu"), default="not measured"),
        cache_entries_read=int(sum(sink(
            "oobleck_compile_cache_events_total", event="entry_read"))),
        compile_s=round(sum(sink("oobleck_compile_seconds_total")), 1),
        cache_entries_before=entries, cache_entries_after=cache_entries(),
        **device)
    return ckpt


def _http(url: str, body: dict | None = None, timeout: float = 120.0):
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        url, data=data, headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, resp.read()
    except urllib.error.HTTPError as e:  # the status is the caller's check
        return e.code, e.read()


def phase_server(ckpt: Path, device: dict) -> None:
    import random
    import re

    phase, t0 = "server", time.monotonic()
    deadline = t0 + SERVER_CAP
    entries = cache_entries()
    env = _child_env(OOBLECK_CKPT_DIR=str(ckpt), OOBLECK_SERVE_SPEC="lookup",
                     OOBLECK_METRICS_DIR=str(OUT / "metrics" / phase))
    log = OUT / "server.log"
    with open(log, "wb") as logf:
        server = subprocess.Popen(
            [sys.executable, "-m", "oobleck_tpu.serve.server"],
            env=env, stdout=logf, stderr=subprocess.STDOUT, cwd=HERE,
            start_new_session=True)
    try:
        line = _wait_for_line(log, "serving on :", server, deadline, phase)
        port = int(re.search(r"serving on :(\d+)", line).group(1))
        need(device["device_kind"] in line, phase,
             f"the server did not name {device['device_kind']!r}: {line}")
        ready_s = time.monotonic() - t0
        base = f"http://127.0.0.1:{port}"
        status, raw = _http(base + "/healthz")
        health = json.loads(raw)
        need(status == 200 and health.get("ok") is True, phase,
             f"/healthz: {status} {health}")
        need(health.get("step") == TRAIN_STEPS, phase,
             f"serving step {health.get('step')}, trained {TRAIN_STEPS}")

        rng = random.Random(0)
        # Mixed prompt lengths; the third repeats a short pattern, which is
        # what prompt-lookup drafting feeds on, so the verify kernel runs.
        pattern = [rng.randrange(VOCAB) for _ in range(6)]
        prompts = [[rng.randrange(VOCAB) for _ in range(n)]
                   for n in PROMPT_LENS]
        prompts.insert(2, pattern * PATTERN_REPEATS)
        answers = []
        for i, prompt in enumerate(prompts):
            status, raw = _http(base + "/v1/generate",
                                {"tokens": prompt, "max_tokens": MAX_TOKENS})
            body = json.loads(raw)
            need(status == 200, phase, f"request {i}: {status} {body}")
            toks = body["tokens"]
            need(len(toks) == MAX_TOKENS and all(
                isinstance(t, int) and 0 <= t < VOCAB for t in toks), phase,
                f"request {i}: bad tokens {toks}")
            need(body["step"] == TRAIN_STEPS, phase,
                 f"request {i} answered from step {body['step']}")
            answers.append({"prompt_len": len(prompt), "tokens": len(toks),
                            "ttft_ms": body["ttft_ms"],
                            "latency_ms": body["latency_ms"],
                            "finish_reason": body["finish_reason"]})
        status, raw = _http(base + "/metrics")
        prom = raw.decode()

        def prom_sum(name: str, label: str = "") -> float:
            return sum(float(l.rsplit(" ", 1)[1]) for l in prom.splitlines()
                       if l.startswith(name) and label in l)

        drafted = prom_sum("oobleck_serve_spec_drafted_tokens_total")
        need(drafted > 0, phase,
             "no token was drafted: the verify kernel never ran")
    finally:
        _stop(server, signal.SIGINT)
    say(phase, entry="serve.server on the trainer's checkpoint root",
        ready_s=round(ready_s, 1), healthz=health, requests=answers,
        spec_drafted_tokens=drafted,
        spec_accepted_tokens=prom_sum(
            "oobleck_serve_spec_accepted_tokens_total"),
        cache_entries_read=int(prom_sum(
            "oobleck_compile_cache_events_total", 'event="entry_read"')),
        compile_s=round(prom_sum("oobleck_compile_seconds_total"), 1),
        cache_entries_before=entries, cache_entries_after=cache_entries(),
        wall_s=round(time.monotonic() - t0, 1), **device)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--chips", type=int, choices=[1, 4], default=1)
    p.add_argument("--phase", choices=["kernels", "crosschip"],
                   help=argparse.SUPPRESS)  # a child of this script
    a = p.parse_args(argv)
    try:
        if a.phase:
            {"kernels": phase_kernels, "crosschip": phase_crosschip}[a.phase]()
            return 0
        shutil.rmtree(OUT, ignore_errors=True)
        OUT.mkdir(parents=True)
        if a.chips == 4:
            device = run_child_phase("crosschip", CROSSCHIP_CAP)
        else:
            device = run_child_phase("kernels", KERNELS_CAP)
            ckpt = phase_trainer(device)
            phase_server(ckpt, device)
        # Every child has exited: the chip is free for this process to ask.
        mine = _device_record()
        need(mine == device and mine["device_count"] == a.chips, "device",
             f"children ran on {device}, this process sees {mine}, "
             f"--chips {a.chips}")
    except PhaseFailed as e:
        print(f"chip_smoke FAILED in {e}", file=sys.stderr)
        return 1
    finally:
        if not a.phase:  # logs and metrics stay; gigabytes of weights go
            shutil.rmtree(OUT / "ckpt", ignore_errors=True)
    print(json.dumps({"ok": True, "device": {
        "platform": mine["platform"], "kind": mine["device_kind"],
        "count": mine["device_count"]}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
