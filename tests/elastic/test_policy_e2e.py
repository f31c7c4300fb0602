"""Policy plane through the REAL master control loop (in-process daemon,
raw-socket agents — the tier-1 idiom from test_chaos.py): every recovery
broadcast carries the scored decision, a flapping host is quarantined and
refused re-registration with hysteresis, and a spot-preemption advance
notice triggers a proactive broadcast to everyone including the victim
(whose later death is then a clean exit, not a second incident)."""

import asyncio

import pytest

from oobleck_tpu.elastic.message import (
    RequestType,
    ResponseType,
    recv_msg,
    send_request,
)
from oobleck_tpu.policy.engine import DECISION_KEY
from oobleck_tpu.utils import metrics

from tests.elastic.scripted import launch_job, start_master


async def _start_master(node_ips):
    daemon, task = await start_master()
    await launch_job(daemon.port, node_ips)
    return daemon, task


async def _register(port, ip):
    r, w = await asyncio.open_connection("127.0.0.1", port)
    await send_request(w, RequestType.REGISTER_AGENT, {"ip": ip})
    msg = await recv_msg(r)
    return r, w, msg


def _events(event):
    return [e for e in metrics.flight_recorder().events()
            if e.get("event") == event]


@pytest.mark.asyncio
async def test_flapping_host_quarantined_and_refused():
    """Churn e2e: a host that connects and dies twice in quick succession
    is quarantined by the flap detector — its third registration refused —
    while every loss broadcast to the survivor carries the full policy
    decision and lands in the /status decision log."""
    daemon, task = await _start_master(["10.0.0.1", "10.0.0.2"])
    try:
        # Pin the health tracker's clock: the two scripted flaps land
        # milliseconds apart, so the host's real-time MTBF (and with it
        # the 2x hysteresis window) would be milliseconds too — the lazy
        # lift could race the third registration. Frozen time = failures
        # in the same instant, quarantine provably still armed.
        daemon.policy.health._clock = lambda: 1000.0

        r_srv, w_srv, msg = await _register(daemon.port, "10.0.0.1")
        assert msg["kind"] == ResponseType.SUCCESS.value

        verbs = []
        for _ in range(2):  # two flap cycles: register, then vanish
            _, w_vic, msg = await _register(daemon.port, "10.0.0.2")
            assert msg["kind"] == ResponseType.SUCCESS.value
            w_vic.close()
            verb = await recv_msg(r_srv, timeout=10)
            verbs.append(verb)

        # Every broadcast carried the scored decision for that incident.
        for verb in verbs:
            decision = verb[DECISION_KEY]
            assert decision["lost_ips"] == ["10.0.0.2"]
            assert set(decision["costs"]) == {"reroute", "reinstantiate",
                                              "restore"}
            assert decision["mechanism"] in decision["costs"]
        # Second failure inside the (default) window -> quarantined.
        assert daemon.policy.is_quarantined("10.0.0.2")
        r3, w3, msg = await _register(daemon.port, "10.0.0.2")
        assert msg["kind"] == ResponseType.FAILURE.value
        assert msg["error"] == "quarantined"
        w3.close()
        assert _events("register_refused")[-1]["ip"] == "10.0.0.2"

        status = daemon._status()
        pol = status["policy"]
        assert "10.0.0.2" in pol["quarantined"]
        assert pol["hosts"]["10.0.0.2"]["failures"] == 2
        assert pol["hosts"]["10.0.0.2"]["mtbf_s"] is not None
        assert len(pol["decisions"]) >= 2
        assert all("mechanism" in d for d in pol["decisions"])
        w_srv.close()
    finally:
        task.cancel()
        await daemon.stop()


@pytest.mark.asyncio
async def test_preemption_notice_triggers_proactive_broadcast():
    """Spot-preemption advance notice: the master reacts BEFORE the corpse
    appears — proactive decision broadcast to ALL agents including the
    victim (so its agent drains the worker), the victim marked clean so
    its actual death is not a second incident."""
    daemon, task = await _start_master(["10.0.0.1", "10.0.0.2"])
    try:
        r_srv, w_srv, msg = await _register(daemon.port, "10.0.0.1")
        assert msg["kind"] == ResponseType.SUCCESS.value
        r_vic, w_vic, msg = await _register(daemon.port, "10.0.0.2")
        assert msg["kind"] == ResponseType.SUCCESS.value

        await send_request(w_vic, RequestType.PREEMPTION_NOTICE,
                           {"ip": "10.0.0.2", "deadline_s": 5.0})
        for reader in (r_srv, r_vic):  # victim gets the verb too: it drains
            verb = await recv_msg(reader, timeout=10)
            assert verb["lost_ip"] == "10.0.0.2"
            decision = verb[DECISION_KEY]
            assert decision["proactive"] is True
        assert daemon.agents["10.0.0.2"].clean_exit is True
        assert _events("preemption_notice")[-1]["deadline_s"] == 5.0

        # The host dies inside the warning window: clean exit, no second
        # broadcast to the survivor.
        w_vic.close()
        await asyncio.sleep(0.3)
        with pytest.raises((asyncio.TimeoutError, TimeoutError)):
            await recv_msg(r_srv, timeout=1.0)
        assert "10.0.0.2" not in daemon.agents
        w_srv.close()
    finally:
        task.cancel()
        await daemon.stop()
