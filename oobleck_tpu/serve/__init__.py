"""Elastic serving plane (L6): continuous-batching inference over the
durable-state plane's checkpoints.

The training side of this repo survives faults by reconfiguring instead
of restarting; this package extends the same posture to inference: a
server bound to a live training job's checkpoint root
(`OOBLECK_CKPT_DIR`) hot-reloads the newest committed step while
serving, without dropping in-flight requests.

    engine.py    PagedDecodeEngine — KV pool + jitted prefill/decode
                 (persistent-compile-cache routed, cache donated)
    batcher.py   ContinuousBatcher — bounded admission queue, slot
                 scheduling between decode steps, backpressure
    reload.py    CheckpointWatcher — poll committed steps, stage off the
                 decode path, swap at a decode-step barrier
    server.py    stdlib HTTP: POST /v1/generate, GET /healthz, /metrics
    router/      multi-replica front door: prefix-affine routing,
                 failover, pool-driven scale-out (own package docstring)

`ServingPlane` wires the four together over one checkpoint root; pass
`router_url=` (or set `OOBLECK_ROUTER_URL`) and the replica
self-registers with a router on start and deregisters on stop.
"""

from __future__ import annotations

import logging
import os
import threading
import time

from oobleck_tpu.config import ServeArguments
from oobleck_tpu.serve.batcher import ContinuousBatcher, GenRequest, QueueFull
from oobleck_tpu.serve.engine import PagedDecodeEngine
from oobleck_tpu.serve.kv_blocks import BlockAllocator, PagesExhausted
from oobleck_tpu.serve.reload import (
    CheckpointWatcher,
    load_latest_params,
    params_from_payload,
    publish_params,
)
from oobleck_tpu.serve.server import ServeHTTPServer

__all__ = [
    "BlockAllocator", "CheckpointWatcher", "ContinuousBatcher",
    "GenRequest", "PagedDecodeEngine", "PagesExhausted",
    "QueueFull", "ServeArguments", "ServeHTTPServer", "ServingPlane",
    "load_latest_params", "params_from_payload", "publish_params",
]

logger = logging.getLogger("oobleck.serve")


class ServingPlane:
    """One process's serving stack over one checkpoint root.

    start() blocks until a committed checkpoint exists (a server may come
    up before its training job's first save), loads it, warms the decode
    programs, and starts batcher + reload watcher + HTTP server."""

    def __init__(self, root, *, model=None, model_name: str | None = None,
                 model_args: dict | None = None,
                 args: ServeArguments | None = None,
                 wait_secs: float = 60.0, ip: str | None = None,
                 router_url: str | None = None):
        self.root = root
        self.model = model
        self.model_name = model_name
        self.model_args = model_args
        self.args = args or ServeArguments()
        self.args.apply_serve_env_overrides()
        self.wait_secs = wait_secs
        self.ip = ip
        # Multi-replica mode: a router front door to self-register with
        # (serve/router/). Explicit arg wins; env covers deployments that
        # launch replicas as plain `python -m oobleck_tpu.serve.server`.
        self.router_url = router_url \
            if router_url is not None \
            else (os.environ.get("OOBLECK_ROUTER_URL") or None)
        self.engine: PagedDecodeEngine | None = None
        self.batcher: ContinuousBatcher | None = None
        self.watcher: CheckpointWatcher | None = None
        self.server: ServeHTTPServer | None = None

    def _wait_for_checkpoint(self):
        from oobleck_tpu.ckpt import restore

        deadline = time.monotonic() + self.wait_secs
        while True:
            res = restore.load_latest(self.root, quarantine_bad=False)
            if res is not None:
                return res
            if time.monotonic() >= deadline:
                raise TimeoutError(
                    f"no committed checkpoint under {self.root} after "
                    f"{self.wait_secs}s")
            time.sleep(0.2)

    def _resolve_model(self, payload: dict):
        if self.model is not None:
            return self.model
        meta = payload.get("meta", {})
        name = self.model_name or meta.get("model_name")
        if not name:
            raise ValueError(
                "no model: pass model/model_name or checkpoint meta must "
                "carry model_name")
        margs = dict(meta.get("model_args") or {})
        margs.update(self.model_args or {})
        from oobleck_tpu.models import build_model

        return build_model(name, margs)

    def _build_engine(self, model, max_seq: int):
        """A paged pool sized to the HBM budget a dense slot cache would
        take (slots * max_seq tokens), with the decode width (`lanes`)
        freed from that budget: short requests pay no max_seq
        reservation."""
        a = self.args
        page = a.page_size
        num_pages = a.kv_pages or max(2, a.slots * max_seq // page)
        lanes = a.lanes or max(a.slots, min(num_pages - 1, 8 * a.slots))
        return PagedDecodeEngine(model, lanes=lanes, max_seq=max_seq,
                                 page_size=page, num_pages=num_pages)

    def _build_spec(self):
        """Speculative-decode controller from the serve args; None when
        speculation is off or the engine has no multi-token verify path.
        Warms the fixed-width verify program so the first drafting request
        doesn't pay a compile."""
        a = self.args
        if a.speculation == "off" \
                or not getattr(self.engine, "supports_verify", False):
            return None
        from oobleck_tpu.serve.speculative import SpecConfig, build_controller

        spec = build_controller(SpecConfig(
            mode=a.speculation, k=a.spec_k, min_accept=a.spec_min_accept,
            ngram=a.spec_ngram, probe_every=a.spec_probe_every,
            draft_root=a.spec_draft_root))
        if spec is not None:
            self.engine.warmup_verify(spec.config.k + 1)
        return spec

    def start(self) -> "ServingPlane":
        step, payload = self._wait_for_checkpoint()
        model = self._resolve_model(payload)
        max_seq = min(self.args.max_seq,
                      model.config.max_position_embeddings)
        if max_seq != self.args.max_seq:
            logger.info("clamping max_seq %d -> model max positions %d",
                        self.args.max_seq, max_seq)
        self.engine = self._build_engine(model, max_seq)
        self.engine.set_params(
            self.engine.stage_params(params_from_payload(model, payload)),
            step)
        self.engine.warmup()
        spec = self._build_spec()
        self.batcher = ContinuousBatcher(
            self.engine, max_queue=self.args.max_queue,
            default_max_tokens=self.args.max_tokens_default,
            spec=spec).start()
        self.watcher = CheckpointWatcher(
            self.root, model, self.engine, self.batcher,
            poll_secs=self.args.reload_secs, current_step=step,
            ip=self.ip).start()
        self.server = ServeHTTPServer(self.batcher,
                                      port=self.args.port).start()
        logger.info("serving plane up: step %d, %d slots, max_seq %d, "
                    "port %d", step, self.args.slots, max_seq,
                    self.server.port)
        if self.router_url:
            # Register off-thread: a replica may come up before its
            # router, and serving must not block on the handshake.
            threading.Thread(target=self._register_with_router,
                             name="oobleck-serve-register",
                             daemon=True).start()
        return self

    def _register_with_router(self, attempts: int = 30,
                              backoff_s: float = 1.0) -> None:
        from oobleck_tpu.serve.router import register_with_router
        from oobleck_tpu.serve.server import REPLICA_WIRE_V

        payload = {
            "v": REPLICA_WIRE_V,
            "host": self.ip or "127.0.0.1",
            "port": self.server.port,
            "lanes": int(getattr(self.engine, "slots", 0) or 1),
            "weights_step": self.engine.params_step,
            "page_size": int(getattr(self.engine, "page_size", 0) or 0),
        }
        for _ in range(attempts):
            ack = register_with_router(self.router_url, payload)
            if ack is not None:
                logger.info("registered with router %s as %s:%d",
                            self.router_url, payload["host"],
                            payload["port"])
                return
            time.sleep(backoff_s)
        logger.warning("could not register with router %s after %d "
                       "attempts", self.router_url, attempts)

    def stop(self) -> None:
        if self.router_url and self.server is not None:
            from oobleck_tpu.serve.router import deregister_from_router

            # Best-effort clean exit; a missed deregister just means the
            # router's prober declares us down in a couple of sweeps.
            deregister_from_router(self.router_url,
                                   self.ip or "127.0.0.1",
                                   self.server.port, timeout_s=2.0)
        if self.server is not None:
            self.server.close()
        if self.watcher is not None:
            self.watcher.stop()
        if self.batcher is not None:
            self.batcher.stop()
