"""Agent unit tests over real localhost TCP, mirroring the reference's
agent coverage (/root/reference/tests/elastic/test_agent.py:15-85): register
handshake, master-message dispatch, the self-termination kill switch, the
coordinator relay chain, and the worker-death watchdog. The worker process
is faked — a Pipe plus a stub process — exactly as the reference mocks its
worker launch."""

import asyncio
import multiprocessing as mp
import threading

import pytest

from oobleck_tpu.config import OobleckArguments
from oobleck_tpu.elastic.agent import OobleckAgent, Worker
from oobleck_tpu.elastic.master import OobleckMasterDaemon
from oobleck_tpu.elastic.message import (
    RequestType,
    ResponseType,
    recv_msg,
    send_request,
)


class RecordingLauncher:
    def __init__(self):
        self.launched = []

    async def launch(self, ip, master_ip, master_port, args):
        self.launched.append(ip)


class FakeProcess:
    def __init__(self):
        self.alive = True
        self.terminated = False
        self.exitcode = None

    def is_alive(self):
        return self.alive

    def terminate(self):
        self.terminated = True
        self.alive = False


def fake_worker():
    parent, child = mp.Pipe()
    return Worker(pipe=parent, process=FakeProcess()), child


@pytest.fixture
def job_args():
    args = OobleckArguments()
    args.dist.node_ips = ["10.0.0.1", "10.0.0.2", "10.0.0.3"]
    return args


async def start_master_with_job(job_args):
    daemon = OobleckMasterDaemon(port=0, launcher=RecordingLauncher())
    await daemon.start()
    task = asyncio.create_task(daemon.serve_forever())
    r, w = await asyncio.open_connection("127.0.0.1", daemon.port)
    await send_request(w, RequestType.LAUNCH_JOB, {"args": job_args.to_dict()})
    assert (await recv_msg(r))["kind"] == ResponseType.SUCCESS.value
    w.close()
    return daemon, task


async def registered_agent(daemon, ip="10.0.0.1"):
    agent = OobleckAgent("127.0.0.1", daemon.port, ip)
    await agent.connect_to_master()
    await agent.register()
    return agent


@pytest.mark.asyncio
async def test_register_receives_job_args(job_args):
    daemon, task = await start_master_with_job(job_args)
    agent = await registered_agent(daemon)
    assert agent.args.model.model_name == job_args.model.model_name
    assert agent.node_ips == job_args.dist.node_ips
    assert "10.0.0.1" in daemon.agents
    task.cancel()


@pytest.mark.asyncio
async def test_register_without_job_raises(job_args):
    daemon = OobleckMasterDaemon(port=0, launcher=RecordingLauncher())
    await daemon.start()
    task = asyncio.create_task(daemon.serve_forever())
    agent = OobleckAgent("127.0.0.1", daemon.port, "10.0.0.1")
    await agent.connect_to_master()
    with pytest.raises(RuntimeError, match="registration failed"):
        await agent.register()
    task.cancel()


@pytest.mark.asyncio
async def test_reconfiguration_forwarded_to_worker(job_args):
    """Another host dies: the agent trims node_ips and pushes the lost ip
    down the worker pipe (reference agent.py:217-232)."""
    daemon, task = await start_master_with_job(job_args)
    agent = await registered_agent(daemon, "10.0.0.1")
    agent.worker, child = fake_worker()

    await agent.on_reconfiguration("10.0.0.2")
    assert agent.node_ips == ["10.0.0.1", "10.0.0.3"]
    assert child.poll(1)
    assert child.recv() == {"kind": "reconfigure", "lost_ip": "10.0.0.2"}
    task.cancel()


@pytest.mark.asyncio
async def test_kill_switch_terminates_self(job_args):
    """The agent whose ip is declared lost terminates itself and its
    worker — the built-in fault-injection kill switch."""
    daemon, task = await start_master_with_job(job_args)
    agent = await registered_agent(daemon, "10.0.0.2")
    agent.worker, _ = fake_worker()

    with pytest.raises(SystemExit):
        await agent.on_reconfiguration("10.0.0.2")
    assert agent.worker.process.terminated
    task.cancel()


@pytest.mark.asyncio
async def test_response_loop_dispatches_reconfiguration(job_args):
    """End-to-end over sockets: a peer agent disconnecting makes the master
    broadcast its recovery verb (DEGRADE by default — reroute first), which
    the response_loop routes to the worker pipe verb intact."""
    daemon, task = await start_master_with_job(job_args)
    agent = await registered_agent(daemon, "10.0.0.1")
    agent.worker, child = fake_worker()
    loop_task = asyncio.create_task(agent.response_loop())

    # a second agent registers then dies
    r2, w2 = await asyncio.open_connection("127.0.0.1", daemon.port)
    await send_request(w2, RequestType.REGISTER_AGENT, {"ip": "10.0.0.3"})
    assert (await recv_msg(r2))["kind"] == ResponseType.SUCCESS.value
    w2.close()

    for _ in range(100):
        if child.poll(0):
            break
        await asyncio.sleep(0.05)
    verb = child.recv()
    assert verb["kind"] == "degrade"
    assert verb["lost_ip"] == "10.0.0.3"
    # the recovery verb carries its trace context down the pipe, with the
    # agent's notified_at stamped after the master's broadcast_at
    trace = verb["trace"]
    assert trace["notified_at"] >= trace["broadcast_at"]
    assert agent.node_ips == ["10.0.0.1", "10.0.0.2"]
    loop_task.cancel()
    task.cancel()


@pytest.mark.asyncio
async def test_coordinator_relay_via_worker_pipe(job_args):
    """Worker announces the JAX coordinator -> agent forwards to master ->
    master broadcasts -> agent routes it back down the worker pipe
    (the full rank-0 port chain, reference agent.py:181-194)."""
    daemon, task = await start_master_with_job(job_args)
    agent = await registered_agent(daemon, "10.0.0.1")
    agent.worker, child = fake_worker()
    loops = [asyncio.create_task(agent.response_loop()),
             asyncio.create_task(agent.worker_port_loop())]

    child.send({"kind": "coordinator", "address": "10.0.0.1:7777"})
    for _ in range(100):
        if daemon.coordinator is not None:
            break
        await asyncio.sleep(0.05)
    assert daemon.coordinator == "10.0.0.1:7777"
    # the broadcast came back down our own worker pipe
    for _ in range(100):
        if child.poll(0):
            break
        await asyncio.sleep(0.05)
    assert child.recv() == {"kind": "coordinator", "address": "10.0.0.1:7777"}
    for l in loops:
        l.cancel()
    task.cancel()


@pytest.mark.asyncio
async def test_worker_watchdog_terminates_agent(job_args):
    """A dead worker process surfaces as host failure: the agent exits so
    the master's disconnect detection reconfigures the cluster (beyond the
    reference, which leaves worker death unhandled, agent.py:171-173)."""
    daemon, task = await start_master_with_job(job_args)
    agent = await registered_agent(daemon, "10.0.0.1")
    agent.worker, _ = fake_worker()
    agent.worker.process.alive = False
    agent.worker.process.exitcode = 1

    # Await the coroutine directly: wait_for would wrap it in a Task, and a
    # SystemExit inside a Task re-raises out of the event loop (crashing the
    # run) instead of propagating here. The conftest's outer 30 s wait_for
    # still bounds a hang.
    with pytest.raises(SystemExit):
        await agent.worker_watch_loop()
    task.cancel()


@pytest.mark.asyncio
async def test_heartbeats_flow_during_slow_bringup(job_args, monkeypatch):
    """A slow worker spawn must not starve the heartbeats, or the master's
    read deadline evicts a healthy host before its worker ever launches."""
    import oobleck_tpu.elastic.master as master_mod
    monkeypatch.setattr(master_mod, "read_deadline", lambda interval: 0.5)
    daemon, task = await start_master_with_job(job_args)
    agent = OobleckAgent("127.0.0.1", daemon.port, "10.0.0.1")
    agent.ping_interval = 0.1
    release = threading.Event()
    launched = []
    monkeypatch.setattr(
        agent, "launch_worker",
        lambda: release.wait(30) and launched.append(True))
    run_task = asyncio.create_task(agent.run())
    try:
        # The spawn blocks the bring-up for 3x the read deadline...
        await asyncio.sleep(1.5)
        # ...yet the pings kept the registration alive (and no
        # RECONFIGURATION self-terminated the run task).
        assert "10.0.0.1" in daemon.agents
        assert not run_task.done()
        assert not launched
        release.set()
        for _ in range(100):
            if launched:
                break
            await asyncio.sleep(0.05)
        assert launched
    finally:
        release.set()
        run_task.cancel()
        task.cancel()


@pytest.mark.asyncio
async def test_ping_pong_through_response_loop(job_args):
    """The ping loop's PONG responses are consumed silently by the
    response loop (heartbeat actually scheduled — reference defines but
    never schedules it, agent.py:280-288)."""
    daemon, task = await start_master_with_job(job_args)
    agent = await registered_agent(daemon, "10.0.0.1")
    agent.worker, child = fake_worker()
    loop_task = asyncio.create_task(agent.response_loop())

    async with agent._send_lock:
        await send_request(agent._writer, RequestType.PING)
    await asyncio.sleep(0.3)
    # PONG consumed without touching the worker pipe or crashing the loop
    assert not child.poll(0)
    assert not loop_task.done()
    loop_task.cancel()
    task.cancel()


_OFF_JAX_SCRIPT = '''
import asyncio, multiprocessing as mp, sys

from oobleck_tpu.config import OobleckArguments
from oobleck_tpu.elastic import agent as agent_mod
from oobleck_tpu.elastic.master import OobleckMasterDaemon
from oobleck_tpu.elastic.run import OobleckClient


class Launcher:
    async def launch(self, ip, master_ip, master_port, args):
        pass


class DoneProcess:
    """Stands in for the spawned worker: it has already trained and left."""
    pid, exitcode = 0, 0

    def __init__(self, **kw):
        self.target = kw["target"]

    def start(self):
        pass

    def is_alive(self):
        return False


class Ctx:
    Pipe = staticmethod(mp.Pipe)
    Process = DoneProcess


async def main():
    daemon = OobleckMasterDaemon(port=0, launcher=Launcher())
    await daemon.start()
    serve = asyncio.create_task(daemon.serve_forever())
    args = OobleckArguments()
    args.dist.master_ip, args.dist.master_port = "127.0.0.1", daemon.port
    args.dist.node_ips = ["10.0.0.1"]
    client = OobleckClient(args)          # the CLI's half
    await client.connect_to_master()
    await client.request_job_launch()

    agent_mod.mp.get_context = lambda method: Ctx
    agents.append(agent_mod.OobleckAgent("127.0.0.1", daemon.port, "10.0.0.1"))
    # register -> bring-up -> launch_worker -> worker exit 0 -> JOB_DONE ->
    # SystemExit(0), which leaves the event loop from inside its task.
    await agents[0].run()


agents = []
try:
    asyncio.run(main())
except SystemExit as e:
    assert e.code == 0, e.code
else:
    raise AssertionError("agent.run returned without the worker's exit")
# launch_worker took the real path up to the spawn itself
target = agents[0].worker.process.target
assert target.__module__ == "oobleck_tpu.elastic.worker", target
import jax  # the master imports it at module top; importing is harmless
from jax._src import xla_bridge

assert not xla_bridge.backends_are_initialized(), "control plane touched JAX"
assert "oobleck_tpu.planning.profiler" not in sys.modules, "agent profiled"
print("CONTROL_PLANE_OFF_JAX")
'''


def test_master_cli_and_agent_never_initialise_a_jax_backend():
    """A chip belongs to one process at a time, and that process is the
    worker. One fresh interpreter plays master, CLI and agent through
    register -> bring-up -> launch_worker -> worker exit -> JOB_DONE; at the
    end it has no JAX backend and never imported the profiler (profile-on-
    miss lives in OobleckEngine's constructor, i.e. in the worker)."""
    import subprocess
    import sys
    from pathlib import Path

    proc = subprocess.run(
        [sys.executable, "-c", _OFF_JAX_SCRIPT],
        cwd=Path(__file__).resolve().parents[2], capture_output=True,
        text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "CONTROL_PLANE_OFF_JAX" in proc.stdout
    assert not hasattr(OobleckAgent, "ensure_profile")
