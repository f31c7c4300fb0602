"""Real-socket end-to-end: three ServingPlane replicas behind one
router, hot-reload mid-traffic, a chaos-killed replica mid-traffic, and
ZERO failed idempotent requests.

The acceptance property of the router PR, verbatim: replicas
self-register over `router_url`, the router routes real generate
traffic by prefix affinity, a `kill_replica` chaos directive murders
one replica's HTTP server mid-request, and every idempotent request
still returns 200 — the in-flight one via recorded failover, later ones
via the DOWN mark. Meanwhile the training side publishes a newer
checkpoint and the fleet's weights_step follows it through /healthz
probes, requests uninterrupted.
"""

import http.client
import json
import threading
import time

import jax
import pytest

from oobleck_tpu.config import ServeArguments
from oobleck_tpu.models import build_model
from oobleck_tpu.serve import ServingPlane
from oobleck_tpu.serve.reload import publish_params
from oobleck_tpu.serve.router import RouterPlane
from oobleck_tpu.utils import chaos as chaos_mod
from oobleck_tpu.utils import metrics

MODEL = "gpt2-tiny"
MODEL_ARGS = {"num_layers": 2}
PAGE = 16


def _post(port, body, timeout=60):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    conn.request("POST", "/v1/generate", json.dumps(body),
                 {"Content-Type": "application/json"})
    resp = conn.getresponse()
    out = json.loads(resp.read())
    conn.close()
    return resp.status, out


def _get(port, path):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
    conn.request("GET", path)
    resp = conn.getresponse()
    out = json.loads(resp.read())
    conn.close()
    return resp.status, out


def _wait_routable(router, n):
    """Self-registration is async: wait until the router can route to n."""
    deadline = time.monotonic() + 30
    while len(router.registry.routable()[0]) < n:
        assert time.monotonic() < deadline, "replicas never all registered"
        time.sleep(0.05)


def test_three_replicas_one_router_kill_and_reload_mid_traffic(
        tmp_path, monkeypatch):
    monkeypatch.setenv("OOBLECK_METRICS_DIR", str(tmp_path / "obs"))
    model = build_model(MODEL, MODEL_ARGS)
    params = model.init_params(jax.random.PRNGKey(0))
    root = tmp_path / "ckpt"
    publish_params(root, model, params, step=1,
                   model_name=MODEL, model_args=MODEL_ARGS)

    router = RouterPlane(host="127.0.0.1", probe_s=0.1, seed=0).start()
    planes = [ServingPlane(
        root,
        args=ServeArguments(port=0, slots=2, max_seq=64,
                            reload_secs=0.05),
        router_url=f"127.0.0.1:{router.port}") for _ in range(3)]
    chaos_mod.reset("")
    try:
        for p in planes:
            p.start()
        _wait_routable(router, 3)
        _, health = _get(router.port, "/healthz")
        assert health["replicas"] == 3 and health["fleet_weights_step"] == 1

        # Warm a prefix so affinity has something to be affine TO, and
        # learn which replica owns it — that's the one chaos will kill.
        head = list(range(1, 2 * PAGE + 1))
        status, out = _post(router.port, {"tokens": head, "max_tokens": 4})
        assert status == 200 and out["route_reason"] == "affine"
        victim_key = out["routed_to"]
        victim_port = int(victim_key.split(":")[1])
        # Kill the affine replica on its 3rd generate request from now.
        chaos_mod.reset(f"kill_replica={victim_port}@3")

        # Concurrent idempotent clients (temperature 0) sharing the
        # warmed prefix, while the trainer publishes step 2.
        results, lock = [], threading.Lock()

        def client(i):
            status, out = _post(router.port, {
                "tokens": head + [i + 1], "max_tokens": 4,
                "temperature": 0.0})
            with lock:
                results.append((status, out))

        def trainer():
            p2 = jax.tree.map(lambda a: a * 0.999, params)
            publish_params(root, model, p2, step=2,
                           model_name=MODEL, model_args=MODEL_ARGS)

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(10)]
        threads.append(threading.Thread(target=trainer))
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)

        # ZERO failed idempotent requests: the chaos kill aborted one
        # mid-flight and refused later ones — the router absorbed all
        # of it (retry-once failover + DOWN mark).
        assert len(results) == 10
        for status, out in results:
            assert status == 200, out
            assert out["finish_reason"] == "length"
        assert any(out["route_reason"] == "failover"
                   for _, out in results)

        # The death is on the record: replica marked down, failover
        # flight-recorded with a trace id, incident committed.
        _, view = _get(router.port, "/replicas")
        by_key = {r["replica"]: r for r in view["replicas"]}
        assert by_key[victim_key]["state"] == "down"
        failovers = [e for e in metrics.flight_recorder().events()
                     if e["event"] == "router_failover"]
        assert failovers and all(e["trace_id"] for e in failovers)
        # Filter by this test's ephemeral victim port: the flight ring
        # may still hold kill_replica injections from other tests.
        kills = [e for e in metrics.flight_recorder().events()
                 if e["event"] == "chaos_injection"
                 and e.get("action") == "kill_replica"
                 and e.get("port") == victim_port]
        assert len(kills) == 1

        # Hot-reload propagates THROUGH the router's probes: surviving
        # replicas pick up step 2 and the fleet view follows.
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            _, health = _get(router.port, "/healthz")
            if health["fleet_weights_step"] == 2:
                break
            time.sleep(0.1)
        assert health["fleet_weights_step"] == 2

        # Post-kill traffic routes cleanly to the survivors.
        status, out = _post(router.port, {"tokens": head,
                                          "max_tokens": 4})
        assert status == 200 and out["routed_to"] != victim_key
    finally:
        chaos_mod.reset("")
        for p in planes:
            p.stop()
        router.stop()


@pytest.mark.parametrize("affinity", [True, False])
def test_prefix_hits_follow_the_routing(tmp_path, affinity):
    """Why affinity exists, counted on real engines: a request hits the
    prefix cache exactly when the replica it was routed to served that
    prompt head before. With affinity every repeat of a head goes back to
    the replica that holds it, so every repeat is a hit; with affinity off
    the router balances by load, and the fleet earns only the hits its
    routing happens to give."""
    model = build_model(MODEL, MODEL_ARGS)
    params = model.init_params(jax.random.PRNGKey(0))
    root = tmp_path / "ckpt"
    publish_params(root, model, params, step=1,
                   model_name=MODEL, model_args=MODEL_ARGS)
    router = RouterPlane(host="127.0.0.1", probe_s=0.1, seed=0,
                         affinity=affinity).start()
    planes = [ServingPlane(
        root,
        args=ServeArguments(port=0, slots=2, max_seq=64, reload_secs=5.0,
                            page_size=PAGE, kv_pages=64, lanes=2),
        router_url=f"127.0.0.1:{router.port}") for _ in range(2)]
    chaos_mod.reset("")
    hits = metrics.registry().counter("oobleck_serve_prefix_hits_total", "")
    heads = [[(h * 31 + j) % 199 + 1 for j in range(2 * PAGE)]
             for h in range(5)]
    rounds = 3
    try:
        for p in planes:
            p.start()
        _wait_routable(router, 2)
        hits0 = hits.value()
        served = {}                    # head index -> replicas that hold it
        expected = repeats = 0
        for r in range(rounds):
            for h, head in enumerate(heads):
                status, out = _post(router.port, {
                    "tokens": head + [r + 1], "max_tokens": 2,
                    "temperature": 0.0})
                assert status == 200, out
                assert out["route_reason"] == (
                    "affine" if affinity else "balanced")
                holders = served.setdefault(h, set())
                expected += out["routed_to"] in holders
                repeats += r > 0
                holders.add(out["routed_to"])
        assert hits.value() - hits0 == expected
        assert expected <= repeats == (rounds - 1) * len(heads)
        if affinity:
            assert all(len(holders) == 1 for holders in served.values())
            assert expected == repeats
    finally:
        chaos_mod.reset("")
        for p in planes:
            p.stop()
        router.stop()
