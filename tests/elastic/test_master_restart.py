"""Control-plane resilience over real localhost TCP: master hard-killed
mid-job, restarted against its journal, fleet REATTACHes without a single
respawn; hosts that died DURING the outage are recovered from the journal
alone through the normal policy chain; the epoch fence refuses stale
masters and stale verbs in both directions."""

import asyncio
import json

import pytest

from oobleck_tpu.elastic import journal as journal_mod
from oobleck_tpu.elastic import master as master_mod
from oobleck_tpu.elastic.agent import OobleckAgent
from oobleck_tpu.elastic.message import (
    EPOCH_KEY,
    PROTOCOL_VERSION,
    TENANT_KEY,
    RequestType,
    ResponseType,
    recv_msg,
    send_msg,
    send_request,
)
from oobleck_tpu.pool import arbiter as arbiter_mod
from oobleck_tpu.utils import metrics

from tests.elastic.scripted import pool_rpc
from tests.elastic.test_control_plane import (
    RecordingLauncher,
    job_args,  # noqa: F401 — fixture re-export
    launch_job,
    register_agent,
    start_master,
)

REATTACH_WINDOW = "0.3"


@pytest.fixture(autouse=True)
def _fresh_flight(monkeypatch):
    # Bounded module-global ring; fresh per test so event assertions are
    # not at the mercy of suite ordering.
    monkeypatch.setattr(metrics, "_flight", metrics.FlightRecorder())


@pytest.fixture
def state_dir(tmp_path, monkeypatch):
    monkeypatch.setenv(journal_mod.ENV_STATE_DIR, str(tmp_path))
    monkeypatch.setenv(master_mod.ENV_REATTACH_WINDOW, REATTACH_WINDOW)
    return tmp_path


def hard_kill(daemon):
    """Emulate SIGKILL on an in-process master: journaling stops NOW,
    registrations vanish without close handlers (no dying-gasp EV_DEPART,
    no failure detection), transports abort (RST, never FIN)."""
    infos = list(daemon.agents.values())
    daemon.agents.clear()
    daemon.journal = None
    for info in infos:
        info.writer.transport.abort()


async def restart_master(port):
    launcher = RecordingLauncher()
    daemon = master_mod.OobleckMasterDaemon(port=port, launcher=launcher)
    await daemon.start()
    return daemon, asyncio.create_task(daemon.serve_forever())


async def reattach(daemon, ip, last_epoch=0, buffered=None):
    r, w = await asyncio.open_connection("127.0.0.1", daemon.port)
    await send_request(w, RequestType.REATTACH,
                       {"ip": ip, "protocol": PROTOCOL_VERSION,
                        "ping_interval": 10.0, "last_epoch": last_epoch,
                        "worker_alive": True, "buffered": buffered or []})
    msg = await recv_msg(r, timeout=5)
    return r, w, msg


def flight_events(name):
    return [e for e in metrics.flight_recorder().events()
            if e["event"] == name]


@pytest.mark.asyncio
async def test_restart_full_fleet_reattaches_zero_respawns(
        job_args, state_dir):  # noqa: F811
    daemon, launcher, task = await start_master()
    port = daemon.port
    assert daemon.master_epoch == 1
    await launch_job(daemon, job_args)
    socks = [await register_agent(daemon, ip)
             for ip in job_args.dist.node_ips]

    hard_kill(daemon)
    task.cancel()
    await daemon.stop()
    for _, w, _ in socks:
        w.close()

    daemon2, task2 = await restart_master(port)
    try:
        # Replayed the journal: epoch burned, job restored, fleet expected.
        assert daemon2.master_epoch == 2
        assert daemon2.job is not None
        assert daemon2._expected_reattach == set(job_args.dist.node_ips)

        fleet = [await reattach(daemon2, ip)
                 for ip in job_args.dist.node_ips]
        for _, _, msg in fleet:
            assert msg["kind"] == ResponseType.SUCCESS.value
            assert msg[EPOCH_KEY] == 2
            assert msg["args"]["dist"]["node_ips"] == job_args.dist.node_ips

        await asyncio.wait_for(daemon2._reconcile_task, timeout=5)
        # Nothing respawned, nothing recovered: the launcher never ran and
        # no recovery verb reached the fleet.
        assert daemon2.launcher.launched == []
        for r, w, _ in fleet:
            with pytest.raises(asyncio.TimeoutError):
                await recv_msg(r, timeout=0.2)

        status = daemon2._status()["control_plane"]
        assert status["master_epoch"] == 2
        assert status["journaling"] is True
        assert status["reattached_agents"] == 3
        assert status["awaiting_reattach"] == []
        assert status["replayed_entries"] >= 4  # job + 3 registers
        assert status["open_incidents"] == 0

        assert len(flight_events("master_restart")) == 1
        assert len(flight_events("reattach")) == 3
        [rec] = flight_events("reattach_reconciled")
        assert rec["missing"] == []
        assert sorted(rec["reattached"]) == job_args.dist.node_ips
        for _, w, _ in fleet:
            w.close()
    finally:
        task2.cancel()
        await daemon2.stop()


@pytest.mark.asyncio
async def test_host_dead_during_outage_recovered_from_journal(
        job_args, state_dir, monkeypatch):  # noqa: F811
    monkeypatch.delenv("OOBLECK_DEGRADE", raising=False)
    daemon, _, task = await start_master()
    port = daemon.port
    await launch_job(daemon, job_args)
    socks = [await register_agent(daemon, ip)
             for ip in job_args.dist.node_ips]

    hard_kill(daemon)
    task.cancel()
    await daemon.stop()
    for _, w, _ in socks:
        w.close()
    # 10.0.0.3 dies while the master is down: nobody was watching. Only
    # the journal remembers the fleet ever had it.

    daemon2, task2 = await restart_master(port)
    try:
        # One survivor replays a buffered masterless-era observation.
        survivors = [
            await reattach(
                daemon2, "10.0.0.1", last_epoch=1,
                buffered=[{"kind": "failure", "ip": "10.0.0.1",
                           "cause": "worker_exit"}]),
            await reattach(daemon2, "10.0.0.2", last_epoch=1),
        ]

        msgs = [await recv_msg(r, timeout=5) for r, _, _ in survivors]
        for msg in msgs:
            assert msg["kind"] == ResponseType.DEGRADE.value
            assert msg["lost_ip"] == "10.0.0.3"
            assert msg[EPOCH_KEY] == 2

        [rec] = flight_events("reattach_reconciled")
        assert rec["missing"] == ["10.0.0.3"]
        assert flight_events("masterless_replay")[0]["ip"] == "10.0.0.1"
        # The loss went through the normal incident chain: journaled open
        # incident + forensics entry with the outage cause.
        assert daemon2.journal.state["open_incidents"]
        with daemon2._snap_lock:
            assert daemon2._recoveries[-1]["cause"] == "master_outage"
        for _, w, _ in survivors:
            w.close()
    finally:
        task2.cancel()
        await daemon2.stop()


@pytest.mark.asyncio
@pytest.mark.parametrize("history", ["fleet", "fleet_failure_lease"])
async def test_replay_reaches_the_registry_the_dead_master_held(
        job_args, state_dir, monkeypatch, history):  # noqa: F811
    """Whatever the dead master had folded into its journal (the fleet
    alone; or a lost host with its open incident and failure history, and
    a chip lease out to another tenant), the restarted one replays into
    the SAME registry, key for key, and burns a new epoch."""
    monkeypatch.delenv("OOBLECK_DEGRADE", raising=False)
    monkeypatch.setenv(arbiter_mod.ENV_POOL, "1")
    daemon, _, task = await start_master()
    port = daemon.port
    await launch_job(daemon, job_args)
    socks = [await register_agent(daemon, ip)
             for ip in job_args.dist.node_ips]
    if history == "fleet_failure_lease":
        socks[2][1].close()                 # 10.0.0.3 dies: an incident
        for r, _, _ in socks[:2]:
            assert (await recv_msg(r, timeout=5))["lost_ip"] == "10.0.0.3"
        granted = await pool_rpc(port, {
            TENANT_KEY: "serve-a", "chips": 1,
            "pressure": {"slo_debt_s": 90.0}, "lease_ttl_s": 60.0})
        assert granted["kind"] == ResponseType.SUCCESS.value
    held = json.loads(json.dumps(daemon.journal.state))
    if history == "fleet_failure_lease":
        assert held["open_incidents"] and held["leases"]
        assert held["failures"]["10.0.0.3"]

    hard_kill(daemon)
    task.cancel()
    await daemon.stop()
    for _, w, _ in socks:
        w.close()

    daemon2, task2 = await restart_master(port)
    try:
        assert daemon2.journal.state == held
        assert daemon2.master_epoch == 2
        assert daemon2.journal.replayed_entries >= 4
    finally:
        task2.cancel()
        await daemon2.stop()


@pytest.mark.asyncio
async def test_stale_master_refuses_to_drive_fleet(job_args, state_dir):  # noqa: F811
    """Fence, master side: an agent that has applied epoch 7 verbs must
    not be adopted by an epoch-2 master (resurrected from an old journal
    copy) — the handshake fails loudly instead of splitting the brain."""
    daemon, _, task = await start_master()
    await launch_job(daemon, job_args)
    try:
        _, w, msg = await reattach(daemon, "10.0.0.1", last_epoch=7)
        assert msg["kind"] == ResponseType.FAILURE.value
        assert "stale master" in msg["error"]
        [ev] = flight_events("stale_master_refused")
        assert ev["agent_epoch"] == 7
        assert ev["master_epoch"] == 1
        assert "10.0.0.1" not in daemon.agents
        w.close()
    finally:
        task.cancel()
        await daemon.stop()


def test_agent_rejects_lower_epoch_verbs():
    """Fence, agent side: verbs stamped below the highest applied epoch
    are dropped and flight-recorded; unstamped verbs (legacy masters)
    keep the pre-fence trust."""
    agent = OobleckAgent("127.0.0.1", 1, "10.0.0.1")
    assert agent._epoch_admits({"kind": "degrade", EPOCH_KEY: 3})
    assert agent._last_epoch == 3
    assert not agent._epoch_admits({"kind": "degrade", EPOCH_KEY: 2})
    [ev] = [e for e in metrics.flight_recorder().events()
            if e["event"] == "stale_epoch_rejected"]
    assert ev["epoch"] == 2 and ev["applied_epoch"] == 3
    assert agent._epoch_admits({"kind": "degrade"})  # unstamped: legacy
    assert agent._last_epoch == 3


@pytest.mark.asyncio
async def test_register_survives_half_handshake(job_args):  # noqa: F811
    """Satellite regression: a master that crashes mid-handshake can emit
    SUCCESS with no job-args payload before the socket dies. The agent
    must treat that as a retryable half-handshake, re-dial, and complete
    registration against the restarted master."""
    calls = {"n": 0}

    async def serve(reader, writer):
        await recv_msg(reader)
        calls["n"] += 1
        if calls["n"] == 1:
            await send_msg(writer, {"kind": ResponseType.SUCCESS.value})
            writer.close()  # crashed before the args frame existed
            return
        await send_msg(writer, {"kind": ResponseType.SUCCESS.value,
                                "args": job_args.to_dict()})
        # Python 3.12: wait_closed() below waits for every connection this
        # server still holds open, so the handler closes what it accepted.
        writer.close()

    server = await asyncio.start_server(serve, "127.0.0.1", 0)
    port = server.sockets[0].getsockname()[1]
    try:
        agent = OobleckAgent("127.0.0.1", port, "10.0.0.1")
        await agent.connect_to_master()
        await agent.register(attempts=3)
        assert calls["n"] == 2
        assert agent.args.dist.node_ips == job_args.dist.node_ips
    finally:
        server.close()
        await server.wait_closed()
