"""Per-host agent: supervises this host's worker process.

Capability match for the reference agent
(/root/reference/oobleck/elastic/agent.py:27-302), with TPU process topology:
ONE worker process per host (a TPU host drives all its local chips through a
single JAX process) instead of one per GPU with CUDA_VISIBLE_DEVICES pinning
(reference agent.py:148-174).

Responsibilities:
  * register with the master over TCP, receive the job args;
  * spawn the worker with a multiprocessing Pipe for control messages.
    The agent itself never touches JAX: a chip belongs to one process at
    a time, and that process is the worker — so profile-on-miss (the
    reference's agent-side _run_profiler, agent.py:84-110) runs there, in
    OobleckEngine's constructor;
  * relay the JAX coordinator address worker -> master and master -> worker
    (the reference's rank-0 port chain, agent.py:181-194);
  * on RECONFIGURATION: remove the lost ip, push it down the worker pipe; if
    *we* are the lost host, self-terminate — the built-in fault-injection
    kill switch (reference agent.py:217-232);
  * heartbeat PING on an interval (the reference defines but never schedules
    it, agent.py:280-288 — actually scheduled here).
"""

from __future__ import annotations

import asyncio
import collections
import logging
import multiprocessing as mp
import os
import time
from dataclasses import dataclass

from oobleck_tpu.config import OobleckArguments
from oobleck_tpu.elastic.message import (
    EPOCH_KEY,
    JOINED_KEY,
    PROTOCOL_VERSION,
    TELEMETRY_KEY,
    RequestType,
    ResponseType,
    recv_msg,
    send_request,
)
from oobleck_tpu.obs import spans
from oobleck_tpu.policy.engine import DECISION_KEY
from oobleck_tpu.utils import metrics, recovery
from oobleck_tpu.utils.chaos import chaos

logger = logging.getLogger("oobleck.agent")

PING_INTERVAL = 10.0
# Multi-host: how long an unexplained worker death may wait for the
# RECONFIGURATION that explains it (a peer died mid-collective) before the
# agent gives up and terminates.
WORKER_DEATH_GRACE = 30.0
# Bounded connect/register retries with exponential backoff: a master that
# is still binding its port (agents race the launcher) or briefly
# partitioned gets retried; a genuinely absent master fails loudly in
# bounded time instead of hanging the host forever. The bound applies to
# BRING-UP only — once a job is established, losing the master flips the
# agent into masterless mode (capped-backoff redial forever, training
# uninterrupted) instead of terminating: a master outage must stall
# *detection*, never *training*.
CONNECT_ATTEMPTS = 6
REGISTER_ATTEMPTS = 4
BACKOFF_INITIAL = 0.5
BACKOFF_CAP = 10.0
# Worker-observed events buffered while masterless, replayed on REATTACH.
MASTERLESS_BUFFER = 64


def _env_float(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, "") or default)
    except ValueError:
        logger.warning("ignoring malformed %s", name)
        return default


@dataclass
class Worker:
    pipe: object  # mp.connection.Connection
    process: object  # mp.Process


class OobleckAgent:
    def __init__(self, master_ip: str, master_port: int, agent_ip: str):
        self.master_ip = master_ip
        self.master_port = master_port
        self.agent_ip = agent_ip
        self.args: OobleckArguments | None = None
        self.worker: Worker | None = None
        self.node_ips: list[str] = []
        self._reader: asyncio.StreamReader | None = None
        self._writer: asyncio.StreamWriter | None = None
        self._send_lock = asyncio.Lock()
        # Serializes worker creation between bring-up and a concurrent
        # RECONFIGURATION-driven respawn (both may run once the control
        # loops start ahead of the worker launch).
        self._worker_lock = asyncio.Lock()
        self.ping_interval = _env_float("OOBLECK_PING_INTERVAL",
                                        PING_INTERVAL)
        # Stamp of the last RECONFIGURATION we acted on, for the
        # RECOVERY_DEADLINE respawn accounting.
        self._notified_at: float | None = None
        # Latest coordinator announcement, replayed to a freshly launched
        # worker: the response loop runs during bring-up (it must — the
        # heartbeat deadline is ticking), so a broadcast can land before
        # the worker exists. The `world` tag makes replaying a stale one
        # safe (the worker rejects mismatched generations).
        self._last_coordinator: dict | None = None
        # Heartbeat RTT: stamp of the last PING sent; the PONG in the
        # response loop closes the measurement.
        self._ping_sent_at: float | None = None
        # True while a chaos flap cycle holds the master connection down:
        # the response/ping loops must ride it out instead of terminating
        # on the (intentional) connection loss.
        self._flapping = False
        # Masterless degraded mode: monotonic stamp of when the master
        # link died mid-job (None while attached). Training continues;
        # the response loop owns the redial-forever/REATTACH cycle.
        self._masterless_since: float | None = None
        # Highest master epoch this agent has applied a verb from: the
        # split-brain fence floor. 0 = no epoch seen (legacy trust).
        self._last_epoch = 0
        # Latest telemetry digest observed in a worker metrics snapshot
        # (obs/telemetry.py); epoch-stamped onto every heartbeat so the
        # master's fleet-health plane gets per-host samples for free.
        self._telemetry_digest: dict | None = None
        # Worker-observed failures / committed incidents that could not be
        # pushed while masterless; bounded, replayed on REATTACH.
        self._buffer: collections.deque = collections.deque(
            maxlen=MASTERLESS_BUFFER)
        # chaos partition_master: monotonic deadline before which redial
        # attempts are suppressed (the link is "partitioned", not down).
        self._partition_until = 0.0
        reg = metrics.registry()
        self._m_rtt = reg.gauge(
            "oobleck_agent_heartbeat_rtt_seconds",
            "Round-trip time of the last PING/PONG to the master")
        self._m_worker_alive = reg.gauge(
            "oobleck_agent_worker_alive",
            "1 while this host's worker process is alive")
        self._m_respawns = reg.counter(
            "oobleck_agent_worker_respawns_total",
            "Worker respawns triggered by reconfiguration")
        self._m_masterless = reg.gauge(
            "oobleck_agent_masterless_seconds",
            "Seconds this agent has been without a master (0 = attached)")

    # ------------------------------------------------------------------ #

    async def run(self) -> None:
        metrics.set_role("agent")
        await self.connect_to_master()
        await self.register()
        # Heartbeats must start the moment we are registered: the master's
        # read deadline (3x ping cadence) is already ticking, so the worker
        # spawn runs off-thread while the event loop keeps the control
        # plane live.
        tasks = [self._bringup(), self.response_loop(),
                 self.ping_loop(), self.worker_port_loop(),
                 self.worker_watch_loop()]
        # Churn fault injections owned by the agent (utils/chaos.py).
        flap = chaos().flap_period(self.agent_ip)
        if flap is not None:
            tasks.append(self._flap_loop(flap))
        notice = chaos().preempt_notice(self.agent_ip)
        if notice is not None:
            tasks.append(self._preemption_chaos(*notice))
        partition = chaos().partition_master_secs(self.agent_ip)
        if partition is not None:
            tasks.append(self._partition_chaos(partition))
        await asyncio.gather(*tasks)

    async def _bringup(self) -> None:
        async with self._worker_lock:
            if self.worker is None:  # a mid-bringup respawn already launched
                await asyncio.to_thread(self.launch_worker)

    async def worker_watch_loop(self) -> None:
        """Worker death must surface as a host failure: drop the master
        connection so disconnect-based detection fires (the reference treats
        worker-level failure as out of scope, agent.py:171-173 — here the
        agent exits with its worker so the cluster reconfigures).

        Exceptions: exit code 0 is training completing normally (exit
        cleanly, don't declare the host dead); and in multi-host mode a
        worker dying of a PEER's failure (collective partner gone) gets a
        grace window for the explaining RECONFIGURATION to arrive — the
        respawn replaces self.worker, clearing the pending death."""
        pending: tuple[object, float] | None = None
        while True:
            await asyncio.sleep(1.0)
            w = self.worker
            alive = w is not None and w.process.is_alive()
            self._m_worker_alive.set(1.0 if alive else 0.0)
            if w is None or alive:
                pending = None
                continue
            if w.process.exitcode == 0:
                logger.info("worker finished training; agent exiting")
                try:
                    async with self._send_lock:
                        await send_request(self._writer, RequestType.JOB_DONE)
                except (ConnectionError, OSError):
                    pass
                raise SystemExit(0)
            if self._multihost():
                grace = _env_float("OOBLECK_WORKER_DEATH_GRACE",
                                   WORKER_DEATH_GRACE)
                if pending is None or pending[0] is not w:
                    pending = (w, time.monotonic())
                    if self._masterless_since is not None:
                        # Nobody is watching: queue the observation for
                        # replay on REATTACH so the restarted master still
                        # learns about the death.
                        self._buffer.append(
                            {"kind": "failure", "ip": self.agent_ip,
                             "cause": "worker_exit"})
                    logger.warning(
                        "worker died (exit=%s); waiting %.0fs for a "
                        "reconfiguration that explains it",
                        w.process.exitcode, grace)
                    continue
                if time.monotonic() - pending[1] < grace:
                    continue
            logger.error("worker process died (exit=%s); terminating agent",
                         w.process.exitcode)
            self.terminate()

    @staticmethod
    def _multihost() -> bool:
        return os.environ.get("OOBLECK_MULTIHOST") == "1"

    # -- churn fault injections (utils/chaos.py directives) -------------- #

    async def _flap_loop(self, period: float) -> None:
        """flap_host: drop the master connection every `period` seconds and
        re-register — the repeated down/up the policy plane's quarantine
        exists for. The gap before re-dialing lets the master observe the
        disconnect as a failure (that is the point of the fault). Once the
        master quarantines this host, register() exhausts its bounded
        retries and the agent dies for real — a quarantined flapper must
        not hammer the control plane forever."""
        while True:
            await asyncio.sleep(period)
            logger.warning("chaos: flap — dropping master connection")
            metrics.flight_recorder().record(
                "chaos_injection", action="flap_drop", ip=self.agent_ip)
            self._flapping = True
            try:
                self._writer.close()
                await self._writer.wait_closed()
            except (ConnectionError, OSError):
                pass
            await asyncio.sleep(min(1.0, period / 4))
            await self.connect_to_master()
            await self.register()  # raises once quarantined -> agent exits
            self._flapping = False
            logger.warning("chaos: flap — re-registered")

    async def _partition_chaos(self, secs: float) -> None:
        """partition_master: sever this host's master link for `secs`
        seconds — the master stays up, the agent simply cannot reach it.
        The agent must ride it out in masterless mode (training
        uninterrupted) and REATTACH once the partition heals; the master
        meanwhile sees a heartbeat-deadline eviction and broadcasts the
        loss, so healing also exercises the stale-membership reconcile."""
        await asyncio.sleep(1.0)  # let registration settle first
        logger.warning("chaos: partitioned from master for %.1fs", secs)
        metrics.flight_recorder().record(
            "chaos_injection", action="partition_master", ip=self.agent_ip,
            seconds=secs)
        self._partition_until = time.monotonic() + secs
        try:
            self._writer.close()
            await self._writer.wait_closed()
        except (ConnectionError, OSError):
            pass

    async def _preemption_chaos(self, warn_s: float, delay_s: float) -> None:
        """preempt_notice: after `delay_s`, send the master a SIGTERM-style
        advance warning, then die for real `warn_s` later — whatever state
        the drain managed to flush by then is all that survives."""
        await asyncio.sleep(delay_s)
        logger.warning("chaos: preemption notice (host dies in %.1fs)",
                       warn_s)
        try:
            async with self._send_lock:
                await send_request(self._writer,
                                   RequestType.PREEMPTION_NOTICE,
                                   {"ip": self.agent_ip,
                                    "deadline_s": warn_s})
        except (ConnectionError, OSError):
            pass
        await asyncio.sleep(warn_s)
        logger.warning("chaos: preemption deadline reached; host dies now")
        metrics.flight_recorder().record(
            "chaos_injection", action="preempt_kill", ip=self.agent_ip)
        metrics.flight_recorder().dump("preemption_deadline")
        w = self.worker
        if w is not None and w.process.is_alive():
            w.process.kill()
        logging.shutdown()
        os._exit(1)

    async def connect_to_master(self, attempts: int = CONNECT_ATTEMPTS) -> None:
        """Exponential-backoff reconnect: agents race the master's listener
        at cluster bring-up (the launcher fires them before the accept loop
        necessarily exists on a remote host), and a refused connect must be
        a retry, not a dead host."""
        delay = BACKOFF_INITIAL
        for attempt in range(1, attempts + 1):
            try:
                self._reader, self._writer = await asyncio.open_connection(
                    self.master_ip, self.master_port
                )
                return
            except OSError as e:
                if attempt == attempts:
                    raise
                logger.warning(
                    "master %s:%d not reachable (%s); retry %d/%d in %.1fs",
                    self.master_ip, self.master_port, e, attempt,
                    attempts - 1, delay,
                )
                await asyncio.sleep(delay)
                delay = min(delay * 2, BACKOFF_CAP)

    async def register(self, attempts: int = REGISTER_ATTEMPTS) -> None:
        """Reference _register_agent (agent.py:70-82), with bounded retry:
        an agent that reaches the master before LAUNCH_JOB configured it
        gets FAILURE + a closed socket — reconnect and try again instead of
        dying at bring-up. Registration advertises the heartbeat cadence
        (protocol v2) so the master can derive this agent's read deadline."""
        delay = BACKOFF_INITIAL
        last: Exception | None = None
        for attempt in range(1, attempts + 1):
            try:
                async with self._send_lock:
                    await send_request(
                        self._writer, RequestType.REGISTER_AGENT,
                        {"ip": self.agent_ip,
                         "protocol": PROTOCOL_VERSION,
                         "ping_interval": self.ping_interval},
                    )
                msg = await recv_msg(self._reader)
                if msg.get("kind") == ResponseType.SUCCESS.value:
                    # A master that crashes mid-handshake can emit the
                    # SUCCESS frame without (or with a torn) job-args
                    # payload; that is a retryable half-handshake against
                    # the restarted master, not a fatal protocol error.
                    try:
                        args = OobleckArguments.from_dict(msg["args"])
                    except (KeyError, TypeError, ValueError) as e:
                        last = RuntimeError(
                            f"half-handshake: SUCCESS without usable "
                            f"job args ({e})")
                    else:
                        self.args = args
                        self.node_ips = list(self.args.dist.node_ips)
                        logger.info("registered; job model=%s",
                                    self.args.model.model_name)
                        return
                else:
                    last = RuntimeError(f"registration failed: {msg}")
            except (ConnectionError, OSError, asyncio.IncompleteReadError,
                    asyncio.TimeoutError, TimeoutError) as e:
                last = e
            if attempt == attempts:
                break
            logger.warning("registration attempt %d/%d failed (%s); "
                           "retrying in %.1fs", attempt, attempts, last, delay)
            await asyncio.sleep(delay)
            delay = min(delay * 2, BACKOFF_CAP)
            # The master closes the connection on FAILURE; re-dial. Close
            # our side first — a leaked half-dead socket lingers in a
            # master _agent_loop until its read deadline, where it would be
            # mistaken for THIS agent hanging and evicted.
            if self._writer is not None:
                self._writer.close()
                try:
                    await self._writer.wait_closed()
                except (ConnectionError, OSError):
                    pass
            await self.connect_to_master()
        raise RuntimeError(
            f"registration failed after {attempts} attempts: {last}"
        )

    # ------------------------------------------------------------------ #

    def launch_worker(self) -> None:
        """One worker per host with a control pipe (reference agent.py:148-174)."""
        from oobleck_tpu.elastic import worker as worker_mod

        ctx = mp.get_context("spawn")
        parent_pipe, child_pipe = ctx.Pipe()
        proc = ctx.Process(
            target=worker_mod.worker_main,
            args=(child_pipe, self.agent_ip, self.args.to_dict()),
            daemon=True,
        )
        proc.start()
        self.worker = Worker(pipe=parent_pipe, process=proc)
        logger.info("agent %s launched worker pid=%d", self.agent_ip, proc.pid)
        if self._last_coordinator is not None:
            # Deliver an announcement that arrived before the worker did;
            # worker-side generation tagging drops it if it is stale.
            parent_pipe.send(self._last_coordinator)

    def _stop_worker(self, timeout: float | None = None) -> None:
        """Terminate the worker, escalating to SIGKILL — a worker wedged in
        a collective with a dead peer can ignore SIGTERM.

        SIGTERM triggers the worker's checkpoint preemption hook (ckpt/
        writer.py drains any in-flight snapshot before obeying), so the
        default join timeout covers the flush grace: killing inside the
        grace window would tear the very checkpoint the hook protects."""
        if timeout is None:
            from oobleck_tpu.ckpt.writer import FLUSH_GRACE_ENV

            try:
                grace = float(os.environ.get(FLUSH_GRACE_ENV, "10"))
            except ValueError:
                grace = 10.0
            timeout = max(15.0, grace + 5.0)
        w = self.worker
        self.worker = None  # watch loop must not treat this as a death
        if w is None or not w.process.is_alive():
            return
        w.process.terminate()
        w.process.join(timeout)
        if w.process.is_alive():
            logger.warning("worker ignored SIGTERM; killing")
            w.process.kill()
            w.process.join(5.0)

    def respawn_worker(self) -> None:
        """Multi-host recovery: restart the worker against the surviving
        hosts. The fresh worker re-runs the coordinator chain (a new
        jax.distributed world of the survivors) and restores position and
        weights from the surviving live-state mirrors (checkpoint-free)
        or, failing that, the latest checkpoint."""
        t0 = time.monotonic()
        self._stop_worker()
        self.args.dist.node_ips = list(self.node_ips)
        self.launch_worker()
        elapsed = time.monotonic() - t0
        logger.info("worker respawned for %d survivors in %.1fs",
                    len(self.node_ips), elapsed)
        self._m_respawns.inc()
        metrics.flight_recorder().record("worker_respawn", ip=self.agent_ip,
                                         survivors=len(self.node_ips),
                                         elapsed_s=round(elapsed, 3))
        since_notice = (
            time.monotonic() - self._notified_at
            if self._notified_at is not None else None
        )
        recovery.mark(recovery.RESPAWN, ip=self.agent_ip,
                      survivors=len(self.node_ips),
                      elapsed=round(elapsed, 3),
                      since_notified=(round(since_notice, 3)
                                      if since_notice is not None else None))

    # ------------------------------------------------------------------ #

    async def response_loop(self) -> None:
        """Dispatch master messages (reference on_receive_response,
        agent.py:234-278)."""
        while True:
            if self._flapping:
                # A chaos flap cycle owns the connection (and its register
                # handshake reads); stay off the stream until it is back.
                await asyncio.sleep(0.1)
                continue
            try:
                msg = await recv_msg(self._reader, timeout=None)
            except (asyncio.IncompleteReadError, ConnectionError, OSError):
                if self._flapping:
                    continue
                # Masterless degraded mode: a lost master mid-job stalls
                # detection, never training. Redial forever; the worker
                # keeps stepping the whole time.
                await self._ride_out_masterless()
                continue
            kind = msg.get("kind")
            if not self._epoch_admits(msg):
                continue
            if kind == ResponseType.PONG.value:
                if self._ping_sent_at is not None:
                    rtt = time.monotonic() - self._ping_sent_at
                    self._ping_sent_at = None
                    self._m_rtt.set(rtt)
                continue
            if kind == ResponseType.RECONFIGURATION.value:
                await self.on_reconfiguration(msg["lost_ip"],
                                              trace=spans.extract(msg),
                                              decision=msg.get(DECISION_KEY))
            elif kind == ResponseType.DEGRADE.value:
                await self.on_reconfiguration(msg["lost_ip"], degrade=True,
                                              trace=spans.extract(msg),
                                              decision=msg.get(DECISION_KEY))
            elif kind == ResponseType.RESTORE.value:
                await self.on_reconfiguration(msg["lost_ip"], restore=True,
                                              trace=spans.extract(msg),
                                              decision=msg.get(DECISION_KEY))
            elif kind == ResponseType.GROW.value:
                await self.on_grow(list(msg.get(JOINED_KEY) or ()),
                                   trace=spans.extract(msg),
                                   decision=msg.get(DECISION_KEY))
            elif kind == ResponseType.LEASE_GRANT.value:
                # Pool plane: one of our hosts is leased to another
                # tenant. Same path as a proactive drain — the decision
                # rides flagged proactive+inplace, so the victim drains
                # (checkpoint flush, clean exit) and survivors reroute
                # in place, zero respawns.
                await self.on_reconfiguration(msg["lost_ip"], degrade=True,
                                              trace=spans.extract(msg),
                                              decision=msg.get(DECISION_KEY))
            elif kind == ResponseType.LEASE_RECLAIM.value:
                # Pool plane: leased chips flowing back — membership
                # extends through the same grow path a JOIN batch rides.
                await self.on_grow(list(msg.get(JOINED_KEY) or ()),
                                   trace=spans.extract(msg),
                                   decision=msg.get(DECISION_KEY))
            elif kind == ResponseType.FORWARD_COORDINATOR.value:
                payload = {"kind": "coordinator", "address": msg["address"]}
                if msg.get("world") is not None:
                    payload["world"] = msg["world"]
                self._last_coordinator = payload
                if self.worker is not None:
                    self.worker.pipe.send(payload)
            elif kind == ResponseType.SUCCESS.value and "dist_info" in msg:
                if self.worker is not None:
                    self.worker.pipe.send(
                        {"kind": "dist_info", "dist_info": msg["dist_info"]}
                    )
            elif kind == ResponseType.FAILURE.value:
                # Explicit absorb: a FAILURE reply to an in-band request
                # (e.g. a forward the master refused) is diagnostic, not
                # fatal — log it so the verb never vanishes silently.
                logger.warning("master replied FAILURE: %s",
                               msg.get("error", msg))

    def _epoch_admits(self, msg: dict) -> bool:
        """Split-brain fence: reject any verb stamped with a master epoch
        LOWER than the highest this agent has applied — a resurrected old
        master (or a delayed frame from one) must never drive the fleet.
        Unstamped messages are admitted (legacy masters predate the fence;
        untagged trust is the pre-fence behavior)."""
        epoch = msg.get(EPOCH_KEY)
        if epoch is None:
            return True
        epoch = int(epoch)
        if epoch < self._last_epoch:
            logger.error(
                "rejecting %s from stale master epoch %d (< applied %d)",
                msg.get("kind"), epoch, self._last_epoch)
            metrics.flight_recorder().record(
                "stale_epoch_rejected", ip=self.agent_ip,
                kind=msg.get("kind"), epoch=epoch,
                applied_epoch=self._last_epoch)
            return False
        self._last_epoch = epoch
        return True

    async def _ride_out_masterless(self) -> None:
        """Masterless degraded mode: the master link died mid-job. Training
        continues untouched; this coroutine owns the capped-backoff
        redial-forever cycle and returns only once a REATTACH (or legacy
        re-register fallback) lands. The bring-up CONNECT_ATTEMPTS bound
        deliberately does NOT apply here — an established job must survive
        an arbitrarily long master outage."""
        self._masterless_since = time.monotonic()
        logger.error("master connection lost mid-job; entering masterless "
                     "mode (training continues; redialing forever)")
        metrics.flight_recorder().record("masterless_enter",
                                         ip=self.agent_ip)
        delay = BACKOFF_INITIAL
        while True:
            self._m_masterless.set(
                time.monotonic() - self._masterless_since)
            wait = self._partition_until - time.monotonic()
            if wait > 0:  # chaos partition: link is severed, not down
                await asyncio.sleep(min(1.0, wait))
                continue
            try:
                self._reader, self._writer = await asyncio.open_connection(
                    self.master_ip, self.master_port)
            except OSError:
                await asyncio.sleep(delay)
                delay = min(delay * 2, BACKOFF_CAP)
                continue
            if await self._reattach():
                break
            try:
                self._writer.close()
                await self._writer.wait_closed()
            except (ConnectionError, OSError):
                pass
            await asyncio.sleep(delay)
            delay = min(delay * 2, BACKOFF_CAP)
        outage = time.monotonic() - self._masterless_since
        self._masterless_since = None
        self._m_masterless.set(0.0)
        logger.warning("reattached to master after %.1fs masterless",
                       outage)
        metrics.flight_recorder().record(
            "masterless_exit", ip=self.agent_ip,
            outage_s=round(outage, 3))

    async def _reattach(self) -> bool:
        """One REATTACH handshake against a freshly dialed master. Carries
        the worker's liveness (the master must NOT relaunch it), the
        highest applied epoch (fence baseline exchange), and the bounded
        buffer of events observed while masterless."""
        w = self.worker
        worker_alive = bool(w is not None and w.process.is_alive())
        try:
            async with self._send_lock:
                await send_request(
                    self._writer, RequestType.REATTACH,
                    {"ip": self.agent_ip,
                     "protocol": PROTOCOL_VERSION,
                     "ping_interval": self.ping_interval,
                     "last_epoch": self._last_epoch,
                     "worker_alive": worker_alive,
                     "buffered": list(self._buffer)})
            msg = await recv_msg(self._reader)
        except (ConnectionError, OSError, asyncio.IncompleteReadError,
                asyncio.TimeoutError, TimeoutError):
            return False
        if msg.get("kind") == ResponseType.SUCCESS.value:
            epoch = msg.get(EPOCH_KEY)
            if epoch is not None:
                self._last_epoch = max(self._last_epoch, int(epoch))
            self._buffer.clear()  # delivered — the master replayed them
            return True
        if "stale master" in str(msg.get("error", "")):
            # The fence cut the other way: WE have seen a newer epoch than
            # this master. Keep dialing — the current master will answer.
            logger.error("dialed a stale master (our epoch %d); retrying",
                         self._last_epoch)
            return False
        # Legacy master (predates REATTACH) answers FAILURE: fall back to
        # plain REGISTER_AGENT, which it treats as a fresh bring-up —
        # slower (worker relaunch on the next reconfiguration), never wrong.
        logger.warning("master refused REATTACH (%s); falling back to "
                       "REGISTER_AGENT", msg.get("error", msg))
        try:
            await self.connect_to_master()
            await self.register()
        except (RuntimeError, OSError):
            return False
        self._buffer.clear()
        return True

    async def on_reconfiguration(self, lost_ip: str,
                                 degrade: bool = False,
                                 restore: bool = False,
                                 trace: dict | None = None,
                                 decision: dict | None = None) -> None:
        """Reference on_receive_reconfiguration (agent.py:217-232).

        `degrade` / `restore` carry the master's verb through to the
        worker: reroute the loss into pipeline bubbles (oobleck_tpu/
        degrade) or resume from the last durable checkpoint, instead of
        the default template re-instantiation. `decision` is the policy
        plane's full verdict (oobleck_tpu/policy), forwarded down the
        worker pipe so the engine honors the same mechanism the master
        chose; a proactive decision (preemption notice) makes the VICTIM
        drain — checkpoint flush before the host dies — rather than
        self-terminate on the spot. A DEGRADE decision flagged `inplace`
        on multihost is forwarded to the live worker (survivors reroute
        at a consensus step boundary, zero respawns) instead of paying
        the ~21 s respawn.

        `trace` is the incident's propagated trace context (obs/spans);
        the agent stamps its notified_at wall time into it and forwards it
        down the worker pipe so the engine's incident report spans master,
        agent, and worker."""
        verb = ("restore" if restore
                else "degrade" if degrade else "reconfiguration")
        logger.warning("host %s lost (verb=%s)", lost_ip, verb)
        self._notified_at = time.monotonic()
        notified_wall = time.time()
        if trace is not None:
            trace = {**trace, "notified_at": notified_wall}
            spans.span_recorder().record(
                "incident.notified", notified_wall, notified_wall,
                trace_id=trace.get("trace_id"), lost_ip=lost_ip,
                ip=self.agent_ip)
        metrics.flight_recorder().record("reconfiguration_notified",
                                         lost_ip=lost_ip, ip=self.agent_ip,
                                         verb=verb)
        recovery.mark(recovery.NOTIFIED, lost_ip=lost_ip, ip=self.agent_ip)
        if lost_ip == self.agent_ip:
            w = self.worker
            if (decision and decision.get("proactive") and w is not None
                    and w.process.is_alive()):
                # Advance notice: the host is still alive — drain. The
                # worker flushes its checkpoint and exits 0; the watch
                # loop then reports JOB_DONE and the agent exits cleanly.
                logger.warning("this host is being preempted; draining "
                               "worker before death")
                payload = {"kind": "drain", "lost_ip": lost_ip}
                if trace is not None:
                    payload[spans.TRACE_KEY] = trace
                w.pipe.send(payload)
                return
            # We are declared dead: the built-in failure-injection kill switch.
            logger.warning("this host is the victim; terminating")
            self.terminate()
            return
        if lost_ip in self.node_ips:
            self.node_ips.remove(lost_ip)
        if self._multihost():
            w = self.worker
            if w is not None and w.process.exitcode == 0:
                # Our own training already completed; a peer's departure
                # (however the master classified it) changes nothing.
                logger.info("training already complete; ignoring host loss")
                return
            if (degrade and decision and decision.get("inplace")
                    and w is not None and w.process.is_alive()):
                # ROADMAP item-1 remainder: survivors apply the reroute in
                # place. The victim is still draining (proactive notice),
                # so the jax.distributed world is not yet broken — all
                # processes agree on a reroute generation and apply it at
                # the same step boundary (engine-side consensus). If the
                # engine can't, it sends `degrade_fallback` back up and we
                # respawn after all.
                payload = {"kind": "degrade", "lost_ip": lost_ip,
                           "inplace": True}
                if trace is not None:
                    payload[spans.TRACE_KEY] = trace
                payload[DECISION_KEY] = decision
                w.pipe.send(payload)
                return
            # A peer process is gone: the shared jax.distributed world is
            # broken and cannot shrink in place — restart the worker over
            # the survivors. Weights + data position come from the live
            # state mirror when configured (checkpoint-free recovery), else
            # the latest checkpoint. to_thread: _stop_worker joins for up
            # to 20s and must not stall the response/ping/relay loops
            # mid-recovery.
            async with self._worker_lock:
                await asyncio.to_thread(self.respawn_worker)
        elif self.worker is not None:
            # Single-host: the engine reconfigures in place — the
            # reference's NCCL-rebuild model (engine.py:91-180). The verb
            # survives the pipe so the engine's listener sees what the
            # master asked for.
            kind = ("restore" if restore
                    else "degrade" if degrade else "reconfigure")
            payload = {"kind": kind, "lost_ip": lost_ip}
            if trace is not None:
                payload[spans.TRACE_KEY] = trace
            if decision is not None:
                payload[DECISION_KEY] = decision
            self.worker.pipe.send(payload)

    async def on_grow(self, joined_ips: list[str],
                      trace: dict | None = None,
                      decision: dict | None = None) -> None:
        """GROW broadcast: hosts `joined_ips` arrived mid-training and the
        master's policy plane scored the absorption. Nothing terminates and
        no survivor respawns — the verb only extends membership and rides
        the worker pipe down to the engine, which applies the chosen grow
        arm at its next step boundary. The joining host receives the same
        broadcast: its membership now includes itself, and its worker (when
        one eventually launches into the grown world) sees the same
        verdict."""
        logger.warning("hosts %s joined (grow verdict=%s)", joined_ips,
                       (decision or {}).get("mechanism"))
        self._notified_at = time.monotonic()
        notified_wall = time.time()
        if trace is not None:
            trace = {**trace, "notified_at": notified_wall}
            spans.span_recorder().record(
                "incident.notified", notified_wall, notified_wall,
                trace_id=trace.get("trace_id"),
                joined_ips=",".join(joined_ips), ip=self.agent_ip)
        metrics.flight_recorder().record("grow_notified",
                                         joined_ips=joined_ips,
                                         ip=self.agent_ip)
        for ip in joined_ips:
            if ip not in self.node_ips:
                self.node_ips.append(ip)
        if self.worker is not None:
            payload: dict = {"kind": "grow", JOINED_KEY: joined_ips}
            if trace is not None:
                payload[spans.TRACE_KEY] = trace
            if decision is not None:
                payload[DECISION_KEY] = decision
            self.worker.pipe.send(payload)

    async def ping_loop(self) -> None:
        while True:
            await asyncio.sleep(self.ping_interval)
            if self._flapping:
                continue  # connection intentionally down (chaos flap)
            if self._masterless_since is not None:
                continue  # the response loop owns the redial cycle
            if chaos().heartbeat_stalled(self.agent_ip):
                # Fault injection: go silent WITHOUT closing the socket —
                # the hung-peer case only the master's heartbeat deadline
                # (never TCP disconnect) can detect.
                logger.warning("chaos: heartbeat stalled (socket held open)")
                continue
            try:
                async with self._send_lock:
                    self._ping_sent_at = time.monotonic()
                    payload: dict = {"ip": self.agent_ip}
                    if self._telemetry_digest is not None:
                        # Piggybacked fleet-health digest: legacy masters
                        # ignore the key; the epoch stamp lets a restarted
                        # master drop samples from a dead incarnation.
                        payload[TELEMETRY_KEY] = dict(
                            self._telemetry_digest,
                            epoch=self._last_epoch)
                    await send_request(self._writer, RequestType.PING,
                                       payload)
                # Piggyback this agent's registry snapshot on the heartbeat
                # cadence — one extra fire-and-forget frame per interval.
                await self._push_metrics("agent",
                                         metrics.registry().snapshot())
            except (ConnectionError, OSError):
                # The response loop observes the same dead socket and
                # enters masterless mode; keep ticking for the reattach.
                continue

    async def _push_metrics(self, role: str, snapshot: dict) -> None:
        """Ship one registry snapshot to the master (METRICS, no reply)."""
        if self._masterless_since is not None:
            # No master to push to. The only snapshot content the master
            # cannot reconstruct after the outage is the engine's committed
            # incident report — keep it in the bounded replay buffer.
            report = (snapshot or {}).get("incident")
            if isinstance(report, dict):
                self._buffer.append({"kind": "incident",
                                     "report": dict(report)})
            return
        try:
            async with self._send_lock:
                await send_request(self._writer, RequestType.METRICS,
                                   {"ip": self.agent_ip, "role": role,
                                    "snapshot": snapshot})
        except (ConnectionError, OSError):
            pass  # the response/ping loops own connection-loss handling

    async def worker_port_loop(self) -> None:
        """Poll the worker pipe for upward messages: the coordinator
        announcement (reference forward_worker_port, agent.py:181-188)."""
        while True:
            try:
                if self.worker is not None and self.worker.pipe.poll():
                    msg = self.worker.pipe.recv()
                    if msg.get("kind") == "metrics":
                        # Relay the worker's registry snapshot upward so the
                        # master's /metrics covers training-quality gauges.
                        snap = msg.get("snapshot") or {}
                        if isinstance(snap.get("telemetry"), dict):
                            # Keep only the newest digest; the ping loop
                            # stamps it onto each heartbeat.
                            self._telemetry_digest = snap["telemetry"]
                        await self._push_metrics("worker", snap)
                    elif msg.get("kind") == "degrade_fallback":
                        # The engine judged the in-place multihost reroute
                        # infeasible after all — pay for the respawn.
                        logger.warning(
                            "worker cannot apply in-place reroute (%s); "
                            "respawning", msg.get("reason"))
                        metrics.flight_recorder().record(
                            "degrade_fallback", ip=self.agent_ip,
                            reason=msg.get("reason"))
                        async with self._worker_lock:
                            await asyncio.to_thread(self.respawn_worker)
                    elif msg.get("kind") == "coordinator":
                        # Keep the `world` generation tag intact: dropping
                        # it here would make every downstream worker take
                        # the untagged-trust branch and accept stale
                        # pre-failure coordinator addresses.
                        payload = {"address": msg["address"]}
                        if msg.get("world") is not None:
                            payload["world"] = msg["world"]
                        async with self._send_lock:
                            await send_request(
                                self._writer, RequestType.FORWARD_COORDINATOR,
                                payload,
                            )
            except (EOFError, OSError):
                # Worker died with the pipe open mid-poll; the watch loop
                # owns death handling.
                await asyncio.sleep(1.0)
            await asyncio.sleep(0.05)

    def terminate(self) -> None:
        if self.worker is not None and self.worker.process.is_alive():
            self.worker.process.terminate()
        raise SystemExit(1)


def main() -> None:
    import argparse

    p = argparse.ArgumentParser()
    p.add_argument("--master-ip", required=True)
    p.add_argument("--master-port", type=int, required=True)
    p.add_argument("--agent-ip", required=True)
    a = p.parse_args()
    logging.basicConfig(level=logging.INFO)
    agent = OobleckAgent(a.master_ip, a.master_port, a.agent_ip)
    asyncio.run(agent.run())


if __name__ == "__main__":
    main()
