"""HTTP front end: request/response contract over a deterministic fake
engine (generate, healthz, metrics, 4xx paths, 429 backpressure), plus
the one real-engine test here — warmup routing through the persistent
compilation cache with hit/miss accounting."""

import http.client
import json

import jax
import pytest

from oobleck_tpu.serve.batcher import ContinuousBatcher, GenRequest
from oobleck_tpu.serve.server import ServeHTTPServer, tokens_from_body
from tests.serve.test_batcher import FakeEngine


@pytest.fixture()
def served():
    b = ContinuousBatcher(FakeEngine(), idle_sleep=0.001).start()
    srv = ServeHTTPServer(b, port=0).start()
    yield srv
    srv.close()
    b.stop()


def _call(port: int, method: str, path: str, body: dict | None = None):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
    payload = json.dumps(body) if body is not None else None
    conn.request(method, path, payload,
                 {"Content-Type": "application/json"} if payload else {})
    resp = conn.getresponse()
    raw = resp.read()
    conn.close()
    try:
        return resp.status, json.loads(raw)
    except (ValueError, UnicodeDecodeError):
        return resp.status, raw


def test_generate_roundtrip(served):
    status, out = _call(served.port, "POST", "/v1/generate",
                        {"tokens": [1, 2, 3], "max_tokens": 4})
    assert status == 200, out
    assert out["tokens"] == [4, 5, 6, 7]
    assert out["finish_reason"] == "length"
    assert out["step"] == 1
    assert out["ttft_ms"] >= 0 and out["latency_ms"] >= 0
    assert isinstance(out["text"], str)


def test_generate_from_prompt_stand_in_tokenizer(served):
    status, out = _call(served.port, "POST", "/v1/generate",
                        {"prompt": "hi", "max_tokens": 2})
    assert status == 200, out
    # byte-level stand-in: "hi" -> [104 % 32, 105 % 32] -> argmax chain
    assert out["tokens"] == [(105 % 32) + 1, (105 % 32) + 2]


def test_generate_rejects_malformed(served):
    for body in ({},                                  # no tokens/prompt
                 {"tokens": []},                      # empty
                 {"tokens": "abc"},                   # not a list
                 {"tokens": [1, 99]},                 # out of vocab (32)
                 {"tokens": [1], "max_tokens": 0},    # no tokens requested
                 {"tokens": [1], "eos_token": "x"}):  # bad eos type
        status, out = _call(served.port, "POST", "/v1/generate", body)
        assert status == 400, (body, out)
        assert "error" in out
    status, _ = _call(served.port, "POST", "/nope", {"tokens": [1]})
    assert status == 404
    status, _ = _call(served.port, "GET", "/nope")
    assert status == 404


def test_generate_too_long_is_400(served):
    status, out = _call(served.port, "POST", "/v1/generate",
                        {"tokens": [1] * 12, "max_tokens": 12})  # > max_seq 16
    assert status == 400
    assert "max_seq" in out["error"]


def test_queue_full_is_429():
    b = ContinuousBatcher(FakeEngine(), max_queue=1)  # never started
    srv = ServeHTTPServer(b, port=0).start()
    try:
        b.submit(GenRequest([1], max_tokens=1))  # occupy the only slot
        status, out = _call(srv.port, "POST", "/v1/generate",
                            {"tokens": [1], "max_tokens": 1})
        assert status == 429
        assert "full" in out["error"]
    finally:
        srv.close()
        b.stop()


def test_healthz_and_metrics(served):
    status, health = _call(served.port, "GET", "/healthz")
    assert status == 200
    assert health["ok"] is True
    assert health["step"] == 1
    assert {"slots_active", "queue_depth"} <= health.keys()

    _call(served.port, "POST", "/v1/generate",
          {"tokens": [2], "max_tokens": 2})
    status, text = _call(served.port, "GET", "/metrics")
    assert status == 200
    body = text.decode() if isinstance(text, bytes) else str(text)
    for name in ("oobleck_serve_ttft_seconds", "oobleck_serve_tokens_total",
                 "oobleck_serve_requests_total", "oobleck_serve_queue_depth"):
        assert name in body, name


def test_tokens_from_body_validation():
    assert tokens_from_body({"tokens": [0, 5]}, 10) == [0, 5]
    assert tokens_from_body({"prompt": "A"}, 1000) == [65]
    for bad in ({"tokens": [True]}, {"prompt": ""}, {}):
        with pytest.raises(ValueError):
            tokens_from_body(bad, 10)


def test_warmup_compiles_every_program_with_the_cpu_cache_off():
    """Serve jits go through ensure_persistent_cache: on the CPU backend
    that switches the persistent cache off (a warm XLA:CPU entry can abort
    the process), the engine records no directory, and warmup still
    compiles every prefill bucket and the decode step up front — twice
    over for a second engine after jax.clear_caches()."""
    from oobleck_tpu.models import build_model
    from oobleck_tpu.serve.engine import DecodeEngine

    model = build_model("gpt2-tiny", {"num_layers": 1})
    params = model.init_params(jax.random.PRNGKey(0))

    eng = DecodeEngine(model, slots=1, max_seq=32)
    assert eng.compile_cache_dir is None
    assert jax.config.jax_enable_compilation_cache is False
    eng.set_params(eng.stage_params(params), 1)
    n = eng.warmup()
    assert n == len(eng.prefill_buckets) + 1

    jax.clear_caches()
    eng2 = DecodeEngine(model, slots=1, max_seq=32)
    eng2.set_params(eng2.stage_params(params), 1)
    assert eng2.warmup() == n
