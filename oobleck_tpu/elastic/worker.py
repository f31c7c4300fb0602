"""Worker entry point: one process per TPU host.

Capability match for /root/reference/oobleck/elastic/worker.py:13-34. The
worker owns every local chip (no per-device pinning) and drives the engine:
initialize the JAX runtime -> build -> instantiate pipelines -> train.

Multi-host (OOBLECK_MULTIHOST=1): the JAX distributed runtime MUST come up
before anything touches a backend (profiling, model init), so the coordinator
chain runs here, first thing — host 0's worker picks a free port and
announces `ip:port` up its agent pipe (agent -> master -> every agent ->
every worker pipe), the TPU equivalent of the reference's rank-0 TCPStore
port chain + NCCL world init (engine.py:563-593).
"""

from __future__ import annotations

import logging
import os
import socket
import time

from oobleck_tpu.config import OobleckArguments

logger = logging.getLogger("oobleck.worker")


def coordinator_announcement(address: str, world: int) -> dict:
    """The coordinator message. `world` is the generation tag: the survivor
    set only ever shrinks, so its size uniquely identifies a reconfiguration
    round — stale announcements from an earlier (larger) world must not be
    adopted by respawned workers. Shared by the worker-side chain here and
    the embedded-engine chain (engine._initialize_multihost)."""
    return {"kind": "coordinator", "address": address, "world": world}


def coordinator_address_if_current(msg, world: int) -> str | None:
    """Address from a coordinator message iff it matches this generation
    (untagged messages are trusted — the legacy single-generation form)."""
    if not isinstance(msg, dict) or msg.get("kind") != "coordinator":
        return None
    if msg.get("world", world) != world:
        return None
    return msg["address"]


def _init_jax_distributed(pipe, agent_ip: str, args: OobleckArguments,
                          timeout_s: float = 120.0) -> None:
    """Run the coordinator chain and bring up jax.distributed.

    Called before the engine exists, so this owns the pipe exclusively:
    non-coordinator messages seen while waiting are dropped (none are
    expected before initialization completes)."""
    import jax

    node_ips = list(args.dist.node_ips)
    world = len(node_ips)
    process_id = node_ips.index(agent_ip)
    if process_id == 0:
        with socket.socket() as s:
            s.bind((agent_ip, 0))
            port = s.getsockname()[1]
        address = f"{agent_ip}:{port}"
        pipe.send(coordinator_announcement(address, world))
    else:
        deadline = time.monotonic() + timeout_s
        address = None
        while time.monotonic() < deadline:
            if pipe.poll(1.0):
                msg = pipe.recv()
                addr = coordinator_address_if_current(msg, world)
                if addr is not None:
                    address = addr
                    break
        if address is None:
            raise TimeoutError("no coordinator address from the agent")
    jax.distributed.initialize(
        coordinator_address=address,
        num_processes=len(node_ips),
        process_id=process_id,
    )
    logger.info("jax.distributed initialized: %s (process %d/%d)",
                address, process_id, len(node_ips))


def worker_main(pipe, agent_ip: str, args_dict: dict) -> None:
    # Fresh spawned process: without a handler, INFO logs (per-step loss,
    # checkpoint/restore lines — the operator's training signal) vanish.
    logging.basicConfig(
        level=logging.INFO,
        format=f"[worker {agent_ip}] %(name)s: %(message)s")
    # Stack dump on demand (`kill -USR1 <worker>`): a wedged collective or
    # a stuck compile is otherwise undebuggable in a spawned worker —
    # operators (and this repo's own hang triage) get every thread's
    # Python stack on stderr without killing training.
    import faulthandler
    import signal as _signal

    faulthandler.register(_signal.SIGUSR1, all_threads=True)
    from oobleck_tpu.utils import metrics
    from oobleck_tpu.utils.chaos import chaos

    metrics.set_role("worker")
    chaos().barrier("worker_start", ip=agent_ip)
    args = OobleckArguments.from_dict(args_dict)
    job = args.job
    # Sanity mirrored from the reference (worker.py:27-28); JobArguments also
    # enforces this at construction.
    assert job.global_microbatch_size % job.microbatch_size == 0

    if os.environ.get("OOBLECK_MULTIHOST") == "1":
        # One shared jax.distributed world for BOTH paths: the fused SPMD
        # program spans it directly; the MPMD engine runs host-local stage
        # jits inside it, with cross-host pipeline edges and the layer-
        # granularity DP allreduce riding XLA collectives over process
        # meshes (parallel/cross_host.py) — the TPU-native equivalent of
        # the reference's node-spanning NCCL pipelines + DP groups
        # (pipeline.py:582-617, engine.py:363-412).
        _init_jax_distributed(pipe, agent_ip, args)

    from oobleck_tpu.execution.engine import OobleckEngine
    from oobleck_tpu.utils.compile_cache import ensure_persistent_cache

    # This process owns the host's chips from here on: profile-on-miss
    # (inside the engine's constructor), planning and every compile.
    import jax

    local = jax.local_devices()
    logger.info("worker on %d x %s (%s), compile cache %s", len(local),
                local[0].device_kind, local[0].platform,
                ensure_persistent_cache())

    engine = OobleckEngine(args, agent_ip=agent_ip, agent_pipe=pipe)
    engine.initialize_distributed()
    engine.instantiate_pipelines(job.global_num_microbatch)
    # Warm recovery: AOT-compile the stage executables of the likely
    # post-failure plans into the persistent compilation cache on a
    # background thread (execution/precompile.py) — at failure time the
    # re-planned world deserializes instead of cold-compiling.
    # OOBLECK_PRECOMPILE_WAIT=1 blocks until warm before step 1 (tests
    # that inject a failure at a fixed step need the warmth guaranteed).
    engine.start_recovery_precompile(
        wait=os.environ.get("OOBLECK_PRECOMPILE_WAIT") == "1"
    )
    engine.train()
    # Held-out evaluation at the end of the run (the reference builds eval
    # machinery it never drives, dataset.py:39-54 / dataloader.py:101).
    # Collective in multi-host mode — every worker reaches here after its
    # train loop completes the same step count.
    final = engine.evaluate()
    logger.info("final eval loss %.4f%s", final,
                "" if engine.last_eval_metrics is None
                or "accuracy" not in engine.last_eval_metrics
                else f" accuracy {engine.last_eval_metrics['accuracy']:.4f}")
