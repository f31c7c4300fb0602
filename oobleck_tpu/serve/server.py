"""Stdlib HTTP front end for the serving plane.

Same dependency-free pattern as the master's /metrics endpoint
(utils/metrics.py): ThreadingHTTPServer on a daemon thread, port 0 binds
an ephemeral port (read `.port` after start).

Endpoints:
  POST /v1/generate   {"tokens": [..]} or {"prompt": ".."} (byte-level
                      stand-in tokenizer), optional "max_tokens",
                      "temperature", "deadline_ms", "eos_token".
                      -> {"tokens", "text", "finish_reason", "step",
                          "ttft_ms", "latency_ms", "trace_id"}
                      Optional "trace_id" in the body joins server-side
                      spans to the caller's trace (obs/spans).
                      429 when the admission queue is full (backpressure)
                      with an honest Retry-After header derived from the
                      measured queue drain rate, 400 on malformed input.
  GET  /healthz       {"ok", "step", "slots_active", "queue_depth"} plus
                      the router-facing replica state: "v" (wire
                      version), "weights_step", "lanes",
                      "lane_occupancy", "page_size", "retry_after_s" —
                      so the router (and humans) read replica state
                      without scraping /metrics.
  GET  /metrics       Prometheus text for this process's registry
                      (TTFT/per-token histograms, queue/slot gauges,
                      reload counters).

Chaos (`kill_replica=<port>[@<req>]`, `hang_replica=<port>:<secs>`): the
generate path checks both directives per request — a killed replica
aborts the in-flight connection with no response and stops accepting,
a hung one sleeps before answering. Both one-shot, flight-recorded.

Run standalone against a training job's checkpoint root:

    OOBLECK_CKPT_DIR=/ckpt OOBLECK_SERVE_PORT=8000 \
        python -m oobleck_tpu.serve.server
"""

from __future__ import annotations

import json
import logging
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

from oobleck_tpu.serve.batcher import GenRequest, QueueFull
from oobleck_tpu.utils import metrics
from oobleck_tpu.utils.chaos import chaos

logger = logging.getLogger("oobleck.serve")

# Replica wire version advertised in /healthz and the router-registration
# handshake. Routers accept replicas WITHOUT it (legacy wire compat) but
# can only trust the richer keys when it is present.
REPLICA_WIRE_V = 1


class _QuietThreadingHTTPServer(ThreadingHTTPServer):
    """ThreadingHTTPServer that logs handler crashes instead of printing
    tracebacks — a chaos-killed connection aborts mid-response by design
    and must not spray stderr."""

    def handle_error(self, request, client_address):  # noqa: D102
        logger.debug("serve http handler error from %s", client_address,
                     exc_info=True)


def tokens_from_body(body: dict, vocab_size: int) -> list[int]:
    """Request tokens: explicit id list, or a byte-level stand-in
    tokenization of "prompt" (this repo trains on synthetic data — a real
    deployment drops its tokenizer in here)."""
    if "tokens" in body:
        tokens = body["tokens"]
        if (not isinstance(tokens, list) or not tokens
                or not all(isinstance(t, int) and not isinstance(t, bool)
                           and 0 <= t < vocab_size for t in tokens)):
            raise ValueError(
                f"tokens must be a non-empty list of ints in [0, {vocab_size})")
        return tokens
    if "prompt" in body:
        raw = str(body["prompt"]).encode("utf-8")
        if not raw:
            raise ValueError("empty prompt")
        return [b % vocab_size for b in raw]
    raise ValueError("body needs 'tokens' or 'prompt'")


def text_from_tokens(tokens: list[int]) -> str:
    """Inverse of the byte-level stand-in (lossy for ids >= 256)."""
    return bytes(t for t in tokens if t < 256).decode("utf-8", "replace")


class ServeHTTPServer:
    """HTTP front end over a ContinuousBatcher."""

    def __init__(self, batcher, *, port: int = 0, host: str = "0.0.0.0",
                 request_timeout: float = 120.0):
        self.batcher = batcher
        self.request_timeout = request_timeout
        outer = self

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, fmt, *args):  # keep test logs quiet
                logger.debug("serve http: " + fmt, *args)

            def _reply(self, code: int, payload: dict,
                       ctype: str = "application/json",
                       headers: dict | None = None) -> None:
                body = json.dumps(payload).encode() \
                    if ctype == "application/json" else payload
                self.send_response(code)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(body)))
                for k, v in (headers or {}).items():
                    self.send_header(k, str(v))
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self):
                try:
                    path = self.path.split("?")[0]
                    if path == "/healthz":
                        self._reply(200, outer._health())
                    elif path == "/metrics":
                        text = metrics.render_prometheus(
                            [metrics.registry().snapshot()]).encode()
                        self._reply(
                            200, text,
                            "text/plain; version=0.0.4; charset=utf-8")
                    else:
                        self.send_error(404)
                except Exception:  # noqa: BLE001 — endpoint must never kill the server
                    logger.exception("serve GET failed")
                    self.send_error(500)

            def do_POST(self):
                try:
                    if self.path.split("?")[0] != "/v1/generate":
                        self.send_error(404)
                        return
                    if outer._chaos_hooks(self):
                        return  # replica died mid-request (no response)
                    length = int(self.headers.get("Content-Length") or 0)
                    try:
                        body = json.loads(self.rfile.read(length) or b"{}")
                        if not isinstance(body, dict):
                            raise ValueError("body must be a JSON object")
                        code, payload, headers = outer._generate(body)
                    except ValueError as e:
                        code, payload, headers = 400, {"error": str(e)}, None
                    self._reply(code, payload, headers=headers)
                except Exception:  # noqa: BLE001 — endpoint must never kill the server
                    logger.exception("serve POST failed")
                    self.send_error(500)

        self._server = _QuietThreadingHTTPServer((host, port), Handler)
        self._server.daemon_threads = True
        self.port = self._server.server_address[1]
        self._thread = threading.Thread(
            target=self._server.serve_forever, name="oobleck-serve-http",
            daemon=True)

    def _chaos_hooks(self, handler) -> bool:
        """Per-request replica fault injection; True when the replica just
        died (the handler must return without replying)."""
        c = chaos()
        if not c.active:
            return False
        secs = c.hang_replica_secs(self.port)
        if secs:
            time.sleep(secs)
        if c.kill_replica_now(self.port):
            # Die like a process, not like an endpoint: abort this
            # connection with no response bytes and stop accepting. The
            # shutdown runs on its own thread (shutdown() blocks until
            # the accept loop notices, and this handler thread must not
            # wait on that).
            threading.Thread(target=self.close, daemon=True).start()
            try:
                handler.connection.close()
            except OSError:
                pass
            return True
        return False

    def _health(self) -> dict:
        eng = self.batcher.engine
        lanes = getattr(eng, "slots", 0) or 0
        active = self.batcher.slots_active
        return {"ok": eng.params is not None,
                "step": eng.params_step,
                "slots_active": active,
                "queue_depth": self.batcher.queue_depth,
                # Router-facing replica state (versioned; routers fall
                # back to the legacy keys above when "v" is absent).
                "v": REPLICA_WIRE_V,
                "weights_step": eng.params_step,
                "lanes": lanes,
                "lane_occupancy": round(active / lanes, 4) if lanes else 1.0,
                "page_size": int(getattr(eng, "page_size", 0) or 0),
                "retry_after_s": self.batcher.retry_after_s()}

    def _generate(self, body: dict) -> tuple[int, dict, dict | None]:
        vocab = self.batcher.engine.model.config.vocab_size
        tokens = tokens_from_body(body, vocab)
        max_tokens = int(body.get("max_tokens",
                                  self.batcher.default_max_tokens))
        if max_tokens < 1:
            raise ValueError("max_tokens must be >= 1")
        deadline_ms = body.get("deadline_ms")
        eos = body.get("eos_token")
        if eos is not None and not isinstance(eos, int):
            raise ValueError("eos_token must be an int")
        # Client-supplied trace id (distributed tracing across the caller's
        # own spans) or a fresh one; returned in the response either way so
        # the caller can join server-side spans to its request.
        trace_id = body.get("trace_id")
        if trace_id is not None and not isinstance(trace_id, str):
            raise ValueError("trace_id must be a string")
        # Per-request speculative-decode mode; None defers to the serving
        # plane's default, and a request can only narrow (off) or pick
        # among the drafters the plane enabled.
        speculation = body.get("speculation")
        if speculation is not None and speculation not in ("off", "lookup",
                                                           "draft"):
            raise ValueError("speculation must be one of off|lookup|draft")
        req = GenRequest(
            tokens, max_tokens=max_tokens,
            temperature=float(body.get("temperature", 0.0)),
            deadline_s=(float(deadline_ms) / 1e3) if deadline_ms else None,
            eos_token=eos, trace_id=trace_id, speculation=speculation)
        try:
            self.batcher.submit(req)
        except QueueFull as e:
            # Honest backpressure: when the queue will drain is derivable
            # from how fast it HAS been draining — advertise that, not a
            # constant, so clients (and the router's spill logic) back
            # off proportionally to the actual overload.
            retry_after = self.batcher.retry_after_s()
            return 429, {"error": str(e), "retry_after_s": retry_after}, \
                {"Retry-After": retry_after}
        if not req.wait(self.request_timeout):
            return 504, {"error": "generation timed out"}, None
        if req.finish_reason in ("error", "shutdown"):
            return 500, {"error": req.finish_reason}, None
        if req.finish_reason == "too_long":
            return 400, {"error": "prompt + max_tokens exceed max_seq"}, None
        return 200, {
            "tokens": req.out_tokens,
            "text": text_from_tokens(req.out_tokens),
            "finish_reason": req.finish_reason,
            "step": req.step,
            "ttft_ms": round((req.ttft_s or 0.0) * 1e3, 3),
            "latency_ms": round((req.total_s or 0.0) * 1e3, 3),
            "trace_id": req.trace_id,
        }, None

    def start(self) -> "ServeHTTPServer":
        self._thread.start()
        logger.info("serve http listening on :%d", self.port)
        return self

    def close(self) -> None:
        self._server.shutdown()
        self._server.server_close()


def main() -> None:  # pragma: no cover - exercised via ServingPlane in tests
    import os

    from oobleck_tpu.serve import ServingPlane

    logging.basicConfig(level=logging.INFO)
    root = os.environ.get("OOBLECK_CKPT_DIR")
    if not root:
        raise SystemExit("set OOBLECK_CKPT_DIR to the checkpoint root")
    plane = ServingPlane(
        root, model_name=os.environ.get("OOBLECK_SERVE_MODEL"),
        model_args=json.loads(os.environ.get("OOBLECK_SERVE_MODEL_ARGS", "{}")))
    plane.start()
    import jax

    devs = jax.local_devices()
    print(f"serving on :{plane.server.port} from {root} "
          f"({len(devs)} x {devs[0].device_kind}, {devs[0].platform})",
          flush=True)
    try:
        threading.Event().wait()
    except KeyboardInterrupt:
        plane.stop()


if __name__ == "__main__":  # pragma: no cover
    main()
