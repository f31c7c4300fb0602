"""Zero-downtime checkpoint hot-reload for the serving plane.

The watcher polls the checkpoint root for a newer COMMITTED step
(`complete_step_dirs` — presence of the atomically-renamed MANIFEST.json
is the commit marker, so a torn write is invisible here by
construction), validates and assembles it off the decode path, stages
the weights to device, and posts the swap to the batcher, which applies
it between decode steps. In-flight requests are never dropped.

Quarantine-awareness: the watcher is a READ-ONLY consumer of a root a
live trainer owns. It never renames/quarantines dirs (that is the
trainer's startup job) — a dir that fails validation here is simply
skipped and retried never (the trainer's GC or quarantine will handle
it); dirs the trainer has already quarantined live under `quarantine/`
and are structurally invisible to the step-dir walk.

Chaos: the `serve_reload` barrier fires on every reload attempt —
`OOBLECK_CHAOS=delay_at=serve_reload:0.5` injects a slow reload (cold
storage, NFS stall) and `kill_at=serve_reload` a torn one.
"""

from __future__ import annotations

import logging
import threading

import jax
import numpy as np

from oobleck_tpu.ckpt import manifest as mf
from oobleck_tpu.ckpt import restore
from oobleck_tpu.utils import background, metrics
from oobleck_tpu.utils.chaos import chaos

logger = logging.getLogger("oobleck.serve")

CHAOS_BARRIER_RELOAD = "serve_reload"


def params_from_payload(model, payload: dict):
    """Checkpoint payload (either kind) -> fused host params tree.

    kind=layers assembles {0: embed, 1..L: block, L+1: head} through the
    fused path's own converter; kind=fused_stacked already IS the fused
    tree."""
    if payload.get("kind") == mf.KIND_FUSED_STACKED:
        return payload["params"]
    from oobleck_tpu.execution.fused import layers_to_params

    return layers_to_params(model, payload["params"])


def load_latest_params(root, model) -> tuple[int, object] | None:
    """Newest committed checkpoint -> (step, fused host params), or None.

    Read-only (`quarantine_bad=False`): shares step selection with the
    engine restore via ckpt.load_latest."""
    res = restore.load_latest(root, quarantine_bad=False)
    if res is None:
        return None
    step, payload = res
    return step, params_from_payload(model, payload)


def publish_params(root, model, params, *, step: int,
                   model_name: str | None = None,
                   model_args: dict | None = None) -> None:
    """Write a fused params tree as one committed checkpoint step (no
    optimizer state) — the minimal trainer->server handoff, used by
    tests. Training jobs publish through the engine's durable-state plane
    instead."""
    from oobleck_tpu.ckpt import DurableStatePlane
    from oobleck_tpu.execution.fused import params_to_layers

    extra: dict = {}
    if model_name:
        extra["model_name"] = model_name
    if model_args:
        extra["model_args"] = model_args
    layers = params_to_layers(model, jax.tree.map(np.asarray, params))
    plane = DurableStatePlane(root, asynchronous=False)
    try:
        plane.save(step=step, params=layers,
                   opt_state={li: [] for li in layers}, extra=extra)
    finally:
        plane.close()


class CheckpointWatcher:
    """Polls a checkpoint root and feeds newer committed steps to the
    batcher as staged weight swaps."""

    def __init__(self, root, model, engine, batcher, *,
                 poll_secs: float = 5.0, current_step: int = -1,
                 ip: str | None = None):
        self.root = root
        self.model = model
        self.engine = engine
        self.batcher = batcher
        self.poll_secs = float(poll_secs)
        self.current_step = int(current_step)
        self.ip = ip
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._run, name="oobleck-serve-reload", daemon=True)
        reg = metrics.registry()
        self.m_failures = reg.counter(
            "oobleck_serve_reload_failures_total",
            "Reload attempts that failed validation/assembly")
        self.m_step = reg.gauge(
            "oobleck_serve_weights_step", "Checkpoint step currently served")
        if self.current_step >= 0:
            self.m_step.set(self.current_step)

    def start(self) -> "CheckpointWatcher":
        self._thread.start()
        return self

    def stop(self, timeout: float = 10.0) -> None:
        self._stop.set()
        self._thread.join(timeout)

    def _run(self) -> None:
        while not self._stop.wait(self.poll_secs):
            try:
                self.poll_once()
            except Exception:  # noqa: BLE001
                # The watcher must outlive any single bad poll: serving
                # the current weights beats dying on a reload error.
                logger.exception("reload poll failed")
                self.m_failures.inc()

    def poll_once(self) -> int | None:
        """One poll: load the newest committed step newer than what we
        serve, stage it, and post the swap. Returns the new step, or None
        when there is nothing newer (or nothing valid)."""
        steps = restore.complete_step_dirs(self.root)
        if not steps or steps[0][0] <= self.current_step:
            return None
        chaos().barrier(CHAOS_BARRIER_RELOAD, ip=self.ip)
        for step, d in steps:
            if step <= self.current_step:
                break
            try:
                payload = restore.load_step_dir(d)
            except restore.CheckpointCorrupt as e:
                # Skip, never quarantine (the trainer owns the root); the
                # next-newest complete step still wins this poll.
                logger.warning("reload: %s failed validation (%s); "
                               "keeping step %d", d.name, e,
                               self.current_step)
                self.m_failures.inc()
                continue
            params = params_from_payload(self.model, payload)
            # Staging device_puts run on the watcher thread while the
            # batcher decodes — fence them (utils/background.py) so the
            # two can't interleave inside the XLA runtime.
            with background.device_work("serve_stage"):
                staged = self.engine.stage_params(params)
            self.batcher.post_swap(step, staged)
            self.current_step = step
            self.m_step.set(step)
            logger.info("reload: staged step %d for swap", step)
            return step
        return None
