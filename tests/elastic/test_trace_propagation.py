"""Trace-context propagation across the elastic control plane, over real
localhost sockets: the recovery verb the master broadcasts after a failure
carries the incident's trace context as ONE extra JSON key, the agent
stamps its notified_at and relays it down the worker pipe, and every hop
stays byte-compatible with legacy peers that predate the key."""

import asyncio
import types

import pytest

from oobleck_tpu.elastic.agent import OobleckAgent
from oobleck_tpu.elastic.master import OobleckMasterDaemon
from oobleck_tpu.elastic.message import (
    RequestType,
    ResponseType,
    recv_msg,
    send_request,
)
from oobleck_tpu.obs import spans

from tests.elastic.scripted import launch_job, start_master


async def _launch_and_register(daemon, ips):
    await launch_job(daemon.port, ips)
    conns = []
    for ip in ips:
        r, w = await asyncio.open_connection("127.0.0.1", daemon.port)
        await send_request(w, RequestType.REGISTER_AGENT, {"ip": ip})
        assert (await recv_msg(r))["kind"] == ResponseType.SUCCESS.value
        conns.append((r, w))
    return conns


@pytest.mark.asyncio
async def test_recovery_verb_carries_trace_context(monkeypatch):
    """Victim socket dies -> the survivor's DEGRADE verb must carry the
    trace context (trace_id + master-side wall marks) AND keep the legacy
    shape (kind/lost_ip) untouched, so pre-trace agents parse it fine."""
    monkeypatch.delenv("OOBLECK_DEGRADE", raising=False)
    daemon, task = await start_master()
    try:
        (r1, w1), (r2, w2) = await _launch_and_register(
            daemon, ["10.0.0.1", "10.0.0.2"])
        w2.close()  # host 2 dies without a word

        msg = await recv_msg(r1, timeout=5)
        # legacy surface first: the fields a pre-trace agent reads
        assert msg["kind"] == ResponseType.DEGRADE.value
        assert msg["lost_ip"] == "10.0.0.2"
        # the one extra key, shaped for extract()
        ctx = spans.extract(msg)
        assert ctx is not None
        assert isinstance(ctx["trace_id"], str) and len(ctx["trace_id"]) == 16
        assert ctx["cause"] == "disconnect"
        assert ctx["broadcast_at"] >= ctx["detected_at"]
        # the master recorded both chain spans on that trace
        names = {s["name"]
                 for s in spans.span_recorder().for_trace(ctx["trace_id"])}
        assert {"incident.detect", "incident.broadcast"} <= names
        # /status shows the recovery entry under the same trace_id
        rec = [r for r in daemon._status()["recoveries"]
               if r.get("trace_id") == ctx["trace_id"]]
        assert rec and rec[0]["lost_ip"] == "10.0.0.2"
        w1.close()
    finally:
        task.cancel()
        await daemon.stop()


@pytest.mark.asyncio
async def test_agent_stamps_notified_and_relays_to_worker():
    """The agent hop: notified_at is stamped into the relayed context and
    the worker pipe payload carries the same trace key."""
    agent = OobleckAgent("127.0.0.1", 0, "10.0.0.1")
    agent.node_ips = ["10.0.0.1", "10.0.0.2"]
    sent = []
    agent.worker = types.SimpleNamespace(
        pipe=types.SimpleNamespace(send=sent.append))

    trace = {"trace_id": "abc123def4567890", "detected_at": 100.0,
             "broadcast_at": 100.5, "cause": "disconnect"}
    await agent.on_reconfiguration("10.0.0.2", degrade=True, trace=trace)

    (payload,) = sent
    assert payload["kind"] == "degrade" and payload["lost_ip"] == "10.0.0.2"
    relayed = spans.extract(payload)
    assert relayed["trace_id"] == trace["trace_id"]
    assert relayed["notified_at"] >= trace["broadcast_at"]
    assert trace.get("notified_at") is None  # stamped on a copy, not in place
    names = {s["name"]
             for s in spans.span_recorder().for_trace(trace["trace_id"])}
    assert "incident.notified" in names


@pytest.mark.asyncio
async def test_agent_tolerates_legacy_verb_without_trace():
    """A legacy master sends no trace context: the relay must still work,
    with no trace key invented downstream."""
    agent = OobleckAgent("127.0.0.1", 0, "10.0.0.1")
    agent.node_ips = ["10.0.0.1", "10.0.0.2"]
    sent = []
    agent.worker = types.SimpleNamespace(
        pipe=types.SimpleNamespace(send=sent.append))

    await agent.on_reconfiguration("10.0.0.2", degrade=False, trace=None)

    (payload,) = sent
    assert payload == {"kind": "reconfigure", "lost_ip": "10.0.0.2"}
    assert spans.extract(payload) is None


@pytest.mark.asyncio
async def test_incident_digest_surfaces_in_status():
    """A worker's committed incident rides its metrics push up the relay;
    the master keeps a bounded, trace_id-deduped list in /status."""
    from oobleck_tpu.elastic.master import MAX_INCIDENTS

    daemon = OobleckMasterDaemon(port=0, launcher=None)
    digest = {"trace_id": "t1", "lost_ip": "10.0.0.2",
              "cause": "chaos_kill_stage",
              "phases": {"detect_to_first_step": 1.2}, "total_s": 1.2,
              "committed_at": 123.0}
    push = {"ip": "10.0.0.1", "role": "worker",
            "snapshot": {"metrics": [], "incident": digest}}
    daemon._record_metrics_push(push)
    daemon._record_metrics_push(push)  # periodic resend: deduped
    got = daemon._status()["incidents"]
    assert len(got) == 1
    assert got[0]["trace_id"] == "t1"
    assert got[0]["total_s"] == 1.2
    # bounded: old incidents age out beyond MAX_INCIDENTS
    for i in range(MAX_INCIDENTS + 5):
        daemon._record_metrics_push(
            {"ip": "10.0.0.1", "role": "worker",
             "snapshot": {"incident": {**digest, "trace_id": f"t{i + 2}"}}})
    assert len(daemon._status()["incidents"]) == MAX_INCIDENTS
