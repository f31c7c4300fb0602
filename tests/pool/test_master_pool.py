"""Master pool wiring end-to-end over real localhost TCP: POOL_BORROW
deny/grant, the LEASE_GRANT broadcast with the proactive+inplace drain
decision, the zero-respawn yield, journal + /status visibility, the
release -> LEASE_RECLAIM grow path, cross-tenant attribution, and the
expiry sweep."""

import asyncio

import pytest

from oobleck_tpu.elastic import journal as journal_mod
from oobleck_tpu.elastic.message import (
    JOINED_KEY,
    LEASE_KEY,
    TENANT_KEY,
    ResponseType,
)
from oobleck_tpu.pool import arbiter as arbiter_mod
from oobleck_tpu.policy.engine import DECISION_KEY
from oobleck_tpu.utils import metrics

from tests.elastic.scripted import (
    ScriptedAgent,
    launch_job,
    pool_rpc,
    start_master,
)

AGENTS = ("10.9.0.1", "10.9.0.2", "10.9.0.3")


@pytest.fixture(autouse=True)
def pool_env(tmp_path, monkeypatch):
    monkeypatch.setenv(journal_mod.ENV_STATE_DIR, str(tmp_path))
    monkeypatch.setenv(arbiter_mod.ENV_POOL, "1")
    monkeypatch.setenv(arbiter_mod.ENV_LEASE_TTL, "60")
    monkeypatch.setenv(arbiter_mod.ENV_SWEEP, "0.1")
    monkeypatch.setattr(metrics, "_flight", metrics.FlightRecorder())


async def start_fleet():
    m, task = await start_master()
    await launch_job(m.port, AGENTS)
    fleet = [ScriptedAgent(ip) for ip in AGENTS]
    for a in fleet:
        await a.register(m.port)
    return m, task, fleet


async def stop_fleet(m, task, fleet):
    task.cancel()
    # Agents first: on Python 3.12 the master's wait_closed() waits for
    # every open connection, and would wait for these until the timeout.
    for a in fleet:
        a.close()
    await m.stop()


@pytest.mark.asyncio
async def test_idle_borrow_is_denied_on_the_wire():
    m, task, fleet = await start_fleet()
    try:
        msg = await pool_rpc(m.port, {TENANT_KEY: "serve-a", "chips": 1,
                                      "pressure": {"slo_debt_s": 0.0}})
        assert msg["kind"] == ResponseType.FAILURE.value
        assert "denied" in msg["error"]
        assert msg[DECISION_KEY]["mechanism"] == "deny"
        assert m.pool.leases.active() == []
    finally:
        await stop_fleet(m, task, fleet)


@pytest.mark.asyncio
async def test_borrow_grant_drain_release_cycle():
    m, task, fleet = await start_fleet()
    try:
        # Pressured borrow: the arbiter drains one training host.
        msg = await pool_rpc(m.port, {TENANT_KEY: "serve-a", "chips": 1,
                                      "pressure": {"slo_debt_s": 90.0},
                                      "slo": {"ttft_p99_s": 2.0}})
        assert msg["kind"] == ResponseType.SUCCESS.value
        lease = msg[LEASE_KEY]
        assert lease["state"] == "active"
        assert lease["tenant"] == "serve-a"
        victim_ip = lease["hosts"][0]
        assert victim_ip == AGENTS[-1]  # most recently registered yields

        # Every agent sees LEASE_GRANT carrying the proactive in-place
        # drain decision — the PROVEN preemption path, not a new one.
        for a in fleet:
            g = await a.wait_verb({ResponseType.LEASE_GRANT.value}, 5.0)
            assert g["lost_ip"] == victim_ip
            assert g[DECISION_KEY]["proactive"] and g[DECISION_KEY]["inplace"]
            assert g[LEASE_KEY]["lease_id"] == lease["lease_id"]

        # The victim's exit is expected: no failure detection, no
        # recovery broadcast, no respawn.
        victim = next(a for a in fleet if a.ip == victim_ip)
        assert m.agents[victim_ip].clean_exit
        victim.close()
        await asyncio.sleep(0.2)
        recovery = [x for a in fleet for x in a.inbox
                    if x.get("kind") in (ResponseType.RECONFIGURATION.value,
                                         ResponseType.DEGRADE.value,
                                         ResponseType.RESTORE.value)]
        assert recovery == []

        # /status pool block + journal both know the lease.
        st = m._status()["pool"]
        assert st["enabled"]
        assert len(st["leases"]["active"]) == 1
        assert {"serve-a", "default"} <= set(st["tenants"])
        assert st["decisions"][-1]["mechanism"] == "borrow_drain"
        assert lease["lease_id"] in m.journal.state["leases"]
        assert m.journal.state["jobs"]["default"] is not None

        # Release: chips flow back through the grow path to survivors.
        msg = await pool_rpc(m.port, {TENANT_KEY: "serve-a",
                                      "release": lease["lease_id"],
                                      "pressure": {"slo_debt_s": 0.0}})
        assert msg["kind"] == ResponseType.SUCCESS.value
        assert msg[LEASE_KEY]["state"] == "returned"
        assert msg[DECISION_KEY]["mechanism"] == "reclaim_grow"
        for a in fleet[:2]:
            rec = await a.wait_verb({ResponseType.LEASE_RECLAIM.value}, 5.0)
            assert rec[JOINED_KEY] == [victim_ip]
        assert lease["lease_id"] not in m.journal.state["leases"]

        # Cross-tenant attribution landed under the grant's trace id.
        cost = m.pool.tenants.incident_cost(st["decisions"][-1]["trace_id"])
        assert cost is not None and "default" in cost
        assert cost["default"]["lost_s"] > 0
    finally:
        await stop_fleet(m, task, fleet)


@pytest.mark.asyncio
async def test_expiry_sweep_reclaims_unreleased_lease():
    m, task, fleet = await start_fleet()
    try:
        msg = await pool_rpc(m.port, {TENANT_KEY: "serve-a", "chips": 1,
                                      "pressure": {"slo_debt_s": 90.0},
                                      "lease_ttl_s": 0.3})
        assert msg["kind"] == ResponseType.SUCCESS.value
        lease = msg[LEASE_KEY]
        deadline = asyncio.get_event_loop().time() + 10.0
        hit = None
        while asyncio.get_event_loop().time() < deadline:
            hits = [x for x in fleet[0].inbox
                    if x.get("kind") == ResponseType.LEASE_RECLAIM.value
                    and x[LEASE_KEY]["lease_id"] == lease["lease_id"]]
            if hits:
                hit = hits[0]
                break
            await asyncio.sleep(0.05)
        assert hit is not None, "sweep never reclaimed the expired lease"
        assert hit[LEASE_KEY]["state"] == "expired"
        assert lease["lease_id"] not in m.journal.state["leases"]
    finally:
        await stop_fleet(m, task, fleet)
