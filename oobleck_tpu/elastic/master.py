"""Master daemon: single-job cluster manager.

Capability match for the reference master
(/root/reference/oobleck/elastic/master.py:22-274):

  * accepts one job (LAUNCH_JOB) and launches one agent per host — over SSH
    when an ssh client is available, else as local subprocesses (the test
    harness injects a mock launcher, like the reference's mocked asyncssh,
    tests/elastic/test_master.py:46-49);
  * registers agents and serves DistributionInfo;
  * detects host failure by TCP disconnect (master.py:214-231) AND by
    heartbeat deadline — every agent read carries a deadline derived from
    the agent's advertised ping cadence (protocol v2, message.py), so a
    hung-but-connected peer (socket open, no traffic) is evicted in
    bounded time instead of stalling detection forever; either way the
    master broadcasts (RECONFIGURATION, lost_ip) to survivors
    (close_agent, master.py:192-203) and stamps the RECOVERY_DEADLINE
    detect/broadcast marks (utils/recovery.py);
  * relays the JAX coordinator address from the first agent to all agents
    (the reference's rank0-port chain, master.py:137-154);
  * answers PING (the reference defines ping but never schedules it,
    agent.py:54-61 — here the agent actually pings, see agent.py).

Max cluster size mirrors the reference's 32 (master.py:19).
"""

from __future__ import annotations

import asyncio
import logging
import os
import shutil
import subprocess
import sys
import threading
import time
from dataclasses import dataclass, field

from oobleck_tpu.config import OobleckArguments
from oobleck_tpu.elastic import journal as journal_mod
from oobleck_tpu.elastic.message import (
    DEFAULT_PING_INTERVAL,
    EPOCH_KEY,
    JOINED_KEY,
    LEASE_KEY,
    TELEMETRY_KEY,
    TENANT_KEY,
    DistributionInfo,
    RequestType,
    ResponseType,
    read_deadline,
    recv_msg,
    send_response,
)
from oobleck_tpu.obs import fleet as obs_fleet
from oobleck_tpu.obs import spans
from oobleck_tpu.obs import telemetry as obs_telemetry
from oobleck_tpu.policy import PolicyEngine
from oobleck_tpu.policy.engine import DECISION_KEY, MECH_DRAIN, \
    MECH_OBSERVE, MECH_QUARANTINE, MECH_REINSTANTIATE, MECH_REROUTE, \
    MECH_RESTORE
from oobleck_tpu.pool import arbiter as pool_arbiter
from oobleck_tpu.pool.leases import ST_EXPIRED, ST_RETURNED
from oobleck_tpu.pool.tenants import KIND_SERVE, KIND_TRAIN, TenantSpec
from oobleck_tpu.utils import metrics, recovery
from oobleck_tpu.utils.chaos import chaos

MAX_NUM_HOSTS = 32

# Near-simultaneous JOINs (a whole spot batch provisioning at once) are
# folded into ONE grow incident: the first arrival opens this window, and
# everything landing inside it rides the same policy decision + broadcast
# (mirrors the correlated-LOSS batching of _maybe_reconfigure).
ENV_JOIN_WINDOW = "OOBLECK_JOIN_WINDOW"
DEFAULT_JOIN_WINDOW_S = 0.25

# Committed incident reports pushed up from workers, kept for /status.
MAX_INCIDENTS = 16

# Post-restart reconciliation window: a restarted master waits this long
# for masterless agents to REATTACH before journal-vs-reality reconcile —
# every expected host still missing at the close becomes ONE batched loss
# incident through the normal policy chain (the grow-window mirror for
# the restart direction).
ENV_REATTACH_WINDOW = "OOBLECK_REATTACH_WINDOW"
DEFAULT_REATTACH_WINDOW_S = 10.0

logger = logging.getLogger("oobleck.master")


@dataclass
class AgentInfo:
    ip: str
    reader: asyncio.StreamReader
    writer: asyncio.StreamWriter
    clean_exit: bool = False  # JOB_DONE received: departure is not a failure
    protocol: int = 1
    ping_interval: float = DEFAULT_PING_INTERVAL
    read_deadline: float = read_deadline(DEFAULT_PING_INTERVAL)
    # monotonic stamp of the last message on this channel; /status reports
    # heartbeat ages from it.
    last_seen: float = field(default_factory=time.monotonic)


class LocalLauncher:
    """Spawn agents as local subprocesses (single-host / test deployments)."""

    def __init__(self):
        self.procs: list[subprocess.Popen] = []

    async def launch(self, ip: str, master_ip: str, master_port: int,
                     args: OobleckArguments) -> None:
        proc = subprocess.Popen(
            [sys.executable, "-m", "oobleck_tpu.elastic.agent",
             "--master-ip", master_ip, "--master-port", str(master_port),
             "--agent-ip", ip],
        )
        self.procs.append(proc)
        logger.info("launched agent for %s (pid %d)", ip, proc.pid)


class SSHLauncher:
    """Launch agents over ssh (reference run_node_agents, master.py:60-91,
    which uses asyncssh + conda; here: the system ssh client). Each agent's
    combined stdout/stderr streams to a per-host log file under
    {log_dir}/{timestamp}-{model}/{ip}.out (reference master.py:79-91) —
    DEVNULLing them would make remote worker crashes invisible."""

    def __init__(self, username: str | None, node_port: int = 22,
                 log_dir: str | None = None):
        import tempfile

        self.username = username
        self.node_port = node_port
        self.log_dir = log_dir or os.path.join(
            tempfile.gettempdir(), "oobleck_tpu", "logs"
        )
        self._job_dir: str | None = None
        self._launch_counts: dict[str, int] = {}
        if shutil.which("ssh") is None:
            raise RuntimeError("no ssh client available; use LocalLauncher")

    def start_job(self, args: OobleckArguments) -> None:
        """New per-job log directory; the master calls this at LAUNCH_JOB so
        a long-lived daemon never mixes two jobs' logs into one dir."""
        ts = time.strftime("%Y%m%d-%H%M%S")
        self._job_dir = os.path.join(
            self.log_dir, f"{ts}-{args.model.model_name}"
        )
        self._launch_counts = {}
        os.makedirs(self._job_dir, exist_ok=True)

    def _log_path(self, ip: str, args: OobleckArguments) -> str:
        if self._job_dir is None:
            self.start_job(args)
        # Per-launch suffix: repeated launches for one host (the config
        # allows num_agents_per_node in principle) must not interleave into
        # one file.
        k = self._launch_counts.get(ip, 0)
        self._launch_counts[ip] = k + 1
        name = f"{ip}.out" if k == 0 else f"{ip}-{k}.out"
        return os.path.join(self._job_dir, name)

    async def launch(self, ip: str, master_ip: str, master_port: int,
                     args: OobleckArguments) -> None:
        target = f"{self.username}@{ip}" if self.username else ip
        cmd = (
            f"{sys.executable} -m oobleck_tpu.elastic.agent "
            f"--master-ip {master_ip} --master-port {master_port} "
            f"--agent-ip {ip}"
        )
        path = self._log_path(ip, args)
        # open() can block on slow/remote filesystems (the log dir may be
        # NFS); never stall the heartbeat loop for it.
        logf = await asyncio.to_thread(open, path, "ab")
        try:
            proc = await asyncio.create_subprocess_exec(
                "ssh", "-p", str(self.node_port), target, cmd,
                stdout=logf, stderr=asyncio.subprocess.STDOUT,
            )
        finally:
            logf.close()  # the child holds its own descriptor
        logger.info("launched agent on %s (ssh pid %s, log %s)",
                    ip, proc.pid, path)


class OobleckMasterDaemon:
    def __init__(self, port: int = 0, launcher=None):
        self._requested_port = port
        self.port: int | None = None
        self.launcher = launcher
        self.job: OobleckArguments | None = None
        self.agents: dict[str, AgentInfo] = {}
        self.coordinator: str | None = None  # "ip:port" of the JAX coordinator
        self.coordinator_world: int | None = None  # its generation tag
        self._server: asyncio.Server | None = None
        self._pending_ips: list[str] = []
        # Cluster metrics aggregation: latest registry snapshot per
        # (host, role), pushed over METRICS. The threading.Lock (not an
        # asyncio one) is deliberate — the HTTP endpoint reads this map
        # from its own daemon threads.
        self._snap_lock = threading.Lock()
        self._remote_snapshots: dict[tuple[str, str], dict] = {}
        # Recovery lifecycle for /status: detect → broadcast → resolved
        # (first post-broadcast worker snapshot = the pipeline is stepping
        # again).
        self._recoveries: list[dict] = []
        # Mid-training JOINs waiting for the batching window to close; the
        # first arrival schedules the flush task, every arrival inside the
        # window rides the same grow incident.
        self._pending_joins: list[tuple[str, float | None]] = []
        self._join_flush_task: asyncio.Task | None = None
        # Incident forensics reports (obs/incident.py) committed by workers
        # and pushed up piggybacked on METRICS snapshots; bounded ring.
        self._incidents: list[dict] = []
        # Adaptive fault-tolerance policy: scores reroute / reinstantiate /
        # restore per incident from live signals (oobleck_tpu/policy).
        self.policy = PolicyEngine(
            multihost=os.environ.get("OOBLECK_MULTIHOST") == "1")
        # Fleet-health plane (obs/fleet.py): per-host telemetry rows fed
        # by heartbeat digests; a persistently slow-but-alive host raises
        # a SLOWDOWN incident through the same classify -> policy chain
        # failures use.
        self.fleet = obs_fleet.FleetTracker()
        # Shared chip-pool plane (oobleck_tpu/pool): serve<->train chip
        # borrowing through leases, arbitrated by the same cost scorer
        # the recovery planes use. Inert unless OOBLECK_POOL=1 — a
        # single-job cluster keeps its exact pre-pool behavior.
        self._train_tenant = (
            os.environ.get(pool_arbiter.ENV_POOL_TENANT, "").strip()
            or journal_mod.DEFAULT_TENANT)
        self.pool: pool_arbiter.PoolArbiter | None = None
        if pool_arbiter.pool_enabled():
            self.pool = pool_arbiter.PoolArbiter()
            self.pool.tenants.register(
                TenantSpec(name=self._train_tenant, kind=KIND_TRAIN))
        self._lease_sweep_task: asyncio.Task | None = None
        # Durable control-plane journal (OOBLECK_MASTER_STATE_DIR): the
        # master's own survival plane. None = journaling off (the pre-PR
        # in-memory-only behavior); epoch 0 means "no fence" to agents.
        self.journal: journal_mod.MasterJournal | None = None
        self.master_epoch = 0
        # Post-restart reconciliation: agents the replayed journal expects,
        # the set that actually REATTACHed, and the window-close task.
        self._expected_reattach: set[str] = set()
        self._reattached: set[str] = set()
        self._reattached_total = 0
        self._reconcile_task: asyncio.Task | None = None
        self._outage_trace_id: str | None = None
        self.metrics_port: int | None = None
        self._connections: set[asyncio.StreamWriter] = set()
        self._http: metrics.MetricsHTTPServer | None = None
        reg = metrics.registry()
        self._m_agents = reg.gauge(
            "oobleck_master_agents", "Currently registered agents")
        self._m_registrations = reg.counter(
            "oobleck_master_registrations_total", "Agent registrations")
        self._m_reconfigs = reg.counter(
            "oobleck_master_reconfigurations_total",
            "RECONFIGURATION broadcasts sent to survivors")
        self._m_pushes = reg.counter(
            "oobleck_master_metrics_pushes_total",
            "METRICS snapshots received", )
        self._m_grows = reg.counter(
            "oobleck_master_grow_broadcasts_total",
            "GROW broadcasts sent for mid-training JOIN batches")
        self._m_epoch = reg.gauge(
            "oobleck_master_epoch",
            "Monotonic master incarnation epoch (split-brain fence)")
        self._m_reattaches = reg.counter(
            "oobleck_master_reattaches_total",
            "Agents re-attached after a master restart")
        self._m_journal_lag = reg.gauge(
            "oobleck_master_journal_lag_entries",
            "Journal entries appended since the last snapshot compaction")
        self._m_slowdowns = reg.counter(
            "oobleck_master_slowdown_incidents_total",
            "SLOWDOWN incidents raised for gray-failing (alive but "
            "persistently slow) hosts")
        self._m_lease_broadcasts = reg.counter(
            "oobleck_master_lease_broadcasts_total",
            "LEASE_GRANT / LEASE_RECLAIM broadcasts (pool plane)")

    # ------------------------------------------------------------------ #

    async def start(self) -> None:
        metrics.set_role("master")
        self._open_journal()
        self._server = await asyncio.start_server(
            self._on_connected, host="0.0.0.0", port=self._requested_port
        )
        self.port = self._server.sockets[0].getsockname()[1]
        logger.info("master listening on :%d", self.port)
        self._start_metrics_endpoint()
        if self.pool is not None:
            self._lease_sweep_task = asyncio.ensure_future(
                self._lease_sweep_loop())
        if self._expected_reattach:
            # A restarted master with a replayed fleet: give masterless
            # agents one reattach window before journal-vs-reality
            # reconciliation declares the no-shows lost.
            self._reconcile_task = asyncio.ensure_future(
                self._reconcile_after_window())
        kill = chaos().kill_master_after()
        if kill is not None:
            asyncio.ensure_future(self._kill_master_chaos(kill[0]))

    @staticmethod
    async def _kill_master_chaos(after_s: float) -> None:
        """kill_master: SIGKILL this process after `after_s` — no cleanup,
        no dying gasp, exactly the outage the journal's per-entry fsync
        must survive. The flight recorder is dumped first: SIGKILL leaves
        no other trace of the injection in the postmortem artifacts."""
        import signal

        await asyncio.sleep(after_s)
        logger.warning("chaos: master SIGKILLing itself now")
        metrics.flight_recorder().dump("chaos_kill_master")
        logging.shutdown()
        os.kill(os.getpid(), signal.SIGKILL)

    def _open_journal(self) -> None:
        """Boot against the durable journal when configured: replay the
        snapshot + tail, burn a fresh epoch, rehydrate the policy plane's
        adaptive state, and — when the journal shows a job mid-flight —
        arm the reattach/reconcile machinery for the fleet it expects."""
        state_dir = journal_mod.state_dir()
        if not state_dir:
            return
        self.journal = journal_mod.MasterJournal(state_dir)
        self.journal.open()
        self.master_epoch = self.journal.epoch
        self._m_epoch.set(self.master_epoch)
        state = self.journal.state
        restart = bool(state["agents"]) or state["job"] is not None
        self.policy.restore_persisted(state)
        if state["job"] is not None:
            try:
                self.job = OobleckArguments.from_dict(state["job"])
            except Exception as e:  # noqa: BLE001 — a bad journaled job must
                logger.error("journaled job unparseable (%s); dropped", e)
                self.job = None  # not brick the restart
        if self.job is not None:
            self._expected_reattach = set(state["agents"])
        if self.pool is not None and state.get("leases"):
            # Who holds whose chips survives the master: the lease book
            # rehydrates from the replayed EV_LEASE entries and the sweep
            # resumes exactly where the dead incarnation left off.
            self.pool.leases.restore(state["leases"])
            logger.warning("pool: %d active lease(s) restored from journal",
                           len(self.pool.leases.active()))
        if restart:
            # The outage is itself an incident: one trace stitches the
            # restart → replay → reattached → reconciled phase marks (the
            # detect mark belongs to whoever killed us — SIGKILL leaves
            # no dying gasp — so the trace opens at restart).
            self._outage_trace_id = spans.new_trace_id()
            t = time.time()
            spans.span_recorder().record(
                "outage.restart", t, t, trace_id=self._outage_trace_id,
                epoch=self.master_epoch)
            spans.span_recorder().record(
                "outage.replay", t - (self.journal.last_replay_s or 0.0), t,
                trace_id=self._outage_trace_id,
                entries=self.journal.replayed_entries)
            metrics.flight_recorder().record(
                "master_restart", epoch=self.master_epoch,
                trace_id=self._outage_trace_id,
                expected_agents=sorted(self._expected_reattach),
                replayed_entries=self.journal.replayed_entries,
                replay_s=round(self.journal.last_replay_s or 0.0, 6))
            logger.warning(
                "master restarted at epoch %d: %d journal entries replayed, "
                "expecting %d agents to reattach", self.master_epoch,
                self.journal.replayed_entries, len(self._expected_reattach))

    def _journal(self, kind: str, **fields) -> None:
        if self.journal is not None:
            self.journal.append(kind, **fields)
            self._m_journal_lag.set(self.journal.entries_since_snapshot)

    def _start_metrics_endpoint(self) -> None:
        raw = os.environ.get(metrics.ENV_METRICS_PORT, "0")
        try:
            port = int(raw)
        except ValueError:
            logger.warning("malformed %s=%r ignored; using an ephemeral "
                           "port", metrics.ENV_METRICS_PORT, raw)
            port = 0
        if port < 0:  # explicit opt-out
            return
        try:
            self._http = metrics.MetricsHTTPServer(
                self._render_metrics, self._status, port=port).start()
        except OSError as e:
            logger.warning("metrics endpoint unavailable: %s", e)
            return
        self.metrics_port = self._http.port
        logger.info("metrics endpoint on :%d (/metrics, /status)",
                    self.metrics_port)

    async def serve_forever(self) -> None:
        assert self._server is not None
        # NOT `async with self._server`: its __aexit__ awaits wait_closed(),
        # which on Python 3.12 blocks until every connection handler returns —
        # agent loops are intentionally long-lived, so cancellation would hang.
        await self._server.serve_forever()

    async def stop(self) -> None:
        if self._server is not None:
            self._server.close()
            for writer in list(self._connections):
                writer.close()
            await self._server.wait_closed()
        if self._http is not None:
            self._http.close()
            self._http = None
        if self._reconcile_task is not None:
            self._reconcile_task.cancel()
            self._reconcile_task = None
        if self._lease_sweep_task is not None:
            self._lease_sweep_task.cancel()
            self._lease_sweep_task = None
        if self.journal is not None:
            self.journal.close()

    # ------------------------------------------------------------------ #
    # metrics plane (called from the HTTP server's daemon threads)

    def _render_metrics(self) -> str:
        self._m_agents.set(len(self.agents))
        snaps = [metrics.registry().snapshot()]
        labels = [{"host": "master", "role": "master"}]
        with self._snap_lock:
            remotes = dict(self._remote_snapshots)
        for (host, role), snap in sorted(remotes.items()):
            snaps.append(snap)
            labels.append({"host": host, "role": role})
        return metrics.render_prometheus(snaps, labels)

    def _status(self) -> dict:
        now = time.monotonic()
        agents = [
            {
                "ip": a.ip,
                "protocol": a.protocol,
                "ping_interval_s": a.ping_interval,
                "read_deadline_s": a.read_deadline,
                "heartbeat_age_s": round(now - a.last_seen, 3),
                "clean_exit": a.clean_exit,
            }
            for a in self.agents.values()
        ]
        with self._snap_lock:
            recoveries = [dict(r) for r in self._recoveries]
            # Full reports are heavy; /status carries the forensic digest
            # (phases + totals), the JSON file on the worker has the rest.
            incidents = [
                {k: i.get(k) for k in ("trace_id", "lost_ip", "cause",
                                       "phases", "total_s", "committed_at")}
                for i in self._incidents
            ]
            worker_snaps = {
                host: snap for (host, role), snap
                in self._remote_snapshots.items() if role == "worker"
            }
        # Current pipeline template, as reported by the workers themselves:
        # the info-gauge value is the adoption step, so the highest value
        # across all series (old plans linger in the registry) is current.
        template = None
        best = -1.0
        for snap in worker_snaps.values():
            for m in snap.get("metrics", []):
                if m["name"] == "oobleck_engine_pipeline_template_info":
                    for s in m["series"]:
                        if s.get("value", 0) >= best:
                            best = s.get("value", 0)
                            template = s.get("labels", {})
        # Newest restorable checkpoint step across the cluster (rank 0 owns
        # the commit, so max over workers is the committed truth); -1 until
        # the first durable commit, None when checkpointing is off.
        last_durable = None
        for snap in worker_snaps.values():
            for m in snap.get("metrics", []):
                if m["name"] == "oobleck_ckpt_last_durable_step":
                    for s in m["series"]:
                        v = int(s.get("value", -1))
                        if last_durable is None or v > last_durable:
                            last_durable = v
        # Fleet health: the tracker's per-host z/ratio rows plus the
        # goodput ledger view from the most-advanced worker snapshot and
        # the cluster's best MFU estimate.
        goodput = None
        best_step = -1
        for snap in worker_snaps.values():
            g = snap.get("goodput")
            if isinstance(g, dict) and snap.get("step", 0) >= best_step:
                best_step = snap.get("step", 0)
                goodput = g
        fleet_health = dict(self.fleet.snapshot())
        fleet_health["goodput"] = goodput
        fleet_health["mfu"] = self._worker_gauge_max("oobleck_engine_mfu")
        return {
            "job": self.job.model.model_name if self.job else None,
            "agents": agents,
            "coordinator": self.coordinator,
            "pipeline_template": template,
            "last_durable_step": last_durable,
            "recoveries": recoveries,
            "in_flight_recoveries": [
                r for r in recoveries if r.get("resolved_at") is None
            ],
            "incidents": incidents,
            "fleet_health": fleet_health,
            # Bounded like the incident digest: quarantine set, per-host
            # MTBF estimates, and the last MAX_DECISIONS policy decisions.
            "policy": self.policy.status(),
            "control_plane": self._control_plane_status(),
            # Always present so dashboards need no key probe; the full
            # tenant/lease/decision block only when the plane is on.
            "pool": (self.pool.status() if self.pool is not None
                     else {"enabled": False}),
        }

    def _control_plane_status(self) -> dict:
        """Bounded control-plane block: the master's own survival state —
        epoch, journal lag, replay cost, and how much of the fleet came
        back after the last restart."""
        block: dict = {
            "master_epoch": self.master_epoch,
            "journaling": self.journal is not None,
            "reattached_agents": self._reattached_total,
            "awaiting_reattach": sorted(self._expected_reattach),
        }
        if self.journal is not None:
            j = self.journal.status()
            block["journal_lag"] = j["journal_lag"]
            block["last_replay_s"] = j["last_replay_s"]
            block["replayed_entries"] = j["replayed_entries"]
            block["open_incidents"] = j["open_incidents"]
        return block

    # -- live signals for the policy scorer (worker-pushed metrics) ------ #

    def _worker_series(self, name: str):
        """All series of one metric family across worker snapshots."""
        with self._snap_lock:
            snaps = [snap for (_, role), snap
                     in self._remote_snapshots.items() if role == "worker"]
        for snap in snaps:
            for m in snap.get("metrics", []):
                if m["name"] == name:
                    yield from m["series"]

    def _worker_gauge_max(self, name: str) -> float | None:
        vals = [s.get("value", 0) for s in self._worker_series(name)]
        return max(vals) if vals else None

    def _step_seconds(self) -> float | None:
        """Mean step wall time across the cluster, or None pre-training."""
        total = count = 0.0
        for s in self._worker_series("oobleck_engine_step_seconds"):
            total += s.get("sum", 0.0)
            count += s.get("count", 0)
        return total / count if count else None

    def _staleness_steps(self) -> float | None:
        """current step - last durable checkpoint step, or None when no
        restorable checkpoint exists (restore infeasible)."""
        durable = self._worker_gauge_max("oobleck_ckpt_last_durable_step")
        if durable is None or durable < 0:
            return None
        step = self._worker_gauge_max("oobleck_engine_steps_total")
        return max(float(step) - durable, 0.0) if step is not None else 0.0

    def _projected_retention(self) -> float | None:
        """The degrade plane's replay-projected survivor throughput, as
        published by the workers (planner projection when one exists)."""
        return self._worker_gauge_max("oobleck_degrade_projected_retention")

    def decide_recovery(self, lost_ips: list[str], *,
                        proactive: bool = False):
        """Consult the policy engine with master-side live signals."""
        degrade = os.environ.get("OOBLECK_DEGRADE", "1").lower() not in (
            "0", "false", "no")
        survivors = [ip for ip in self.agents if ip not in lost_ips]
        total = len(survivors) + len(lost_ips)
        return self.policy.decide(
            lost_ips,
            degrade_enabled=degrade,
            reroute_retention=self._projected_retention(),
            survivor_frac=len(survivors) / total if total else 1.0,
            staleness_steps=self._staleness_steps(),
            step_seconds=self._step_seconds(),
            proactive=proactive,
        )

    def decide_grow(self, joined_ips: list[str], *,
                    lifetime_hints: dict[str, float] | None = None):
        """Consult the policy engine's grow direction with master-side
        live signals. `current_hosts` excludes the joiners themselves —
        they are already in self.agents by flush time, but the retention
        math needs the pre-grow fleet size."""
        current = max(len(self.agents) - len(joined_ips), 1)
        return self.policy.decide_grow(
            joined_ips,
            current_hosts=current,
            staleness_steps=self._staleness_steps(),
            step_seconds=self._step_seconds(),
            lifetime_hints=lifetime_hints,
            cause="join",
        )

    def _record_metrics_push(self, msg: dict) -> None:
        ip = msg.get("ip", "?")
        role = msg.get("role", "agent")
        snap = msg.get("snapshot") or {}
        self._m_pushes.inc(role=role)
        incident = msg.get("incident") or snap.get("incident")
        with self._snap_lock:
            self._remote_snapshots[(ip, role)] = snap
            if isinstance(incident, dict):
                # A worker committed incident-<n>.json and piggybacked the
                # report on its metrics push; keep it for /status forensics
                # (dedup by trace_id — periodic pushes may resend it).
                tid = incident.get("trace_id")
                if not any(i.get("trace_id") == tid for i in self._incidents):
                    self._incidents.append(incident)
                    del self._incidents[:-MAX_INCIDENTS]
        resolved: list[str] = []
        with self._snap_lock:
            if role == "worker":
                # A worker shipping fresh metrics after a broadcast means
                # the pipeline is stepping again: close open recoveries.
                for r in self._recoveries:
                    if (r.get("resolved_at") is None
                            and r.get("broadcast_at") is not None):
                        r["resolved_at"] = time.time()
                        if r.get("trace_id"):
                            resolved.append(r["trace_id"])
        for tid in resolved:
            self._journal(journal_mod.EV_INCIDENT_CLOSE, trace_id=tid)
        if resolved:
            # Snapshot the policy EWMAs alongside the close: the adaptive
            # state a restarted master scores its first decisions with.
            self._journal(journal_mod.EV_EWMA,
                          ewma=self.policy.ewma_snapshot())

    # ------------------------------------------------------------------ #

    async def _on_connected(self, reader: asyncio.StreamReader,
                            writer: asyncio.StreamWriter) -> None:
        # Every accepted connection is closed by the master when its
        # handler is done with it, and by stop() while it is not: since
        # Python 3.12 Server.wait_closed() waits for each of them, and a
        # half-open or still-attached peer would hold stop() forever.
        self._connections.add(writer)
        try:
            await self._serve_connection(reader, writer)
        finally:
            self._connections.discard(writer)
            writer.close()

    async def _serve_connection(self, reader: asyncio.StreamReader,
                                writer: asyncio.StreamWriter) -> None:
        try:
            # Bounded first read: a connection that registers nothing within
            # a default heartbeat deadline is dead weight (or a socket-
            # holding DoS), not a future agent.
            msg = await recv_msg(reader,
                                 timeout=read_deadline(DEFAULT_PING_INTERVAL))
        except (asyncio.TimeoutError, TimeoutError,
                asyncio.IncompleteReadError, ConnectionError):
            writer.close()
            return
        kind = msg.get("kind")
        if kind == RequestType.LAUNCH_JOB.value:
            await self._handle_launch_job(msg, reader, writer)
        elif kind == RequestType.REGISTER_AGENT.value:
            await self._handle_register_agent(msg, reader, writer)
        elif kind == RequestType.JOIN.value:
            await self._handle_join(msg, reader, writer)
        elif kind == RequestType.REATTACH.value:
            await self._handle_reattach(msg, reader, writer)
        elif kind == RequestType.POOL_BORROW.value:
            await self._handle_pool_borrow(msg, writer)
        else:
            await send_response(writer, ResponseType.FAILURE,
                                {"error": f"unexpected first message {kind}"})
            writer.close()

    async def _handle_launch_job(self, msg, reader, writer) -> None:
        """Reference request_job_handler (master.py:93-135)."""
        if self.job is not None:
            await send_response(writer, ResponseType.FAILURE,
                                {"error": "job already running"})
            return
        try:
            args = OobleckArguments.from_dict(msg["args"])
        except Exception as e:  # noqa: BLE001 — any parse failure becomes FAILURE
            await send_response(writer, ResponseType.FAILURE, {"error": str(e)})
            return
        if len(args.dist.node_ips) > MAX_NUM_HOSTS:
            await send_response(writer, ResponseType.FAILURE,
                                {"error": f"too many hosts (max {MAX_NUM_HOSTS})"})
            return
        if args.dist.num_agents_per_node != 1:
            # The registry is keyed by host IP; multiple agents per host would
            # alias each other (and a TPU host needs exactly one JAX process).
            await send_response(writer, ResponseType.FAILURE,
                                {"error": "num_agents_per_node must be 1"})
            return
        self.job = args
        self._pending_ips = list(args.dist.node_ips)
        # Tenant-keyed: N jobs replay as N jobs (journal.py EV_JOB).
        self._journal(journal_mod.EV_JOB, args=args.to_dict(),
                      tenant=self._train_tenant)
        await send_response(writer, ResponseType.SUCCESS)
        if self.launcher is not None and hasattr(self.launcher, "start_job"):
            self.launcher.start_job(args)
        if self.launcher is not None:
            for ip in args.dist.node_ips:
                for _ in range(args.dist.num_agents_per_node):
                    await self.launcher.launch(
                        ip, args.dist.master_ip, self.port, args
                    )

    async def _handle_register_agent(self, msg, reader, writer) -> None:
        """Reference register_agent_handler (master.py:156-190)."""
        ip = msg.get("ip") or writer.get_extra_info("peername")[0]
        if self.job is None:
            await send_response(writer, ResponseType.FAILURE,
                                {"error": "no job configured"})
            writer.close()
            return
        if self.policy.is_quarantined(ip):
            # Flap quarantine: a host that failed twice inside its MTBF
            # window is refused until it proves stable (hysteresis in
            # policy/health.py). The agent's bounded register backoff
            # turns the refusal into a clean exit, not a retry storm.
            logger.warning("refusing registration from quarantined host %s",
                           ip)
            metrics.flight_recorder().record("register_refused", ip=ip,
                                             reason="quarantined")
            await send_response(writer, ResponseType.FAILURE,
                                {"error": "quarantined"})
            writer.close()
            return
        interval = float(msg.get("ping_interval") or DEFAULT_PING_INTERVAL)
        info = AgentInfo(
            ip, reader, writer,
            protocol=int(msg.get("protocol") or 1),
            ping_interval=interval,
            read_deadline=read_deadline(interval),
        )
        self.agents[ip] = info
        self._m_registrations.inc()
        self._journal(journal_mod.EV_REGISTER, ip=ip,
                      tenant=self._train_tenant)
        # A re-registering host starts a fresh fleet-health life: stale
        # rows (and latched straggler flags) must not follow it in.
        self.fleet.clear(ip)
        if self.policy.health.consume_lift(ip):
            # A host whose flap quarantine lifted (hysteresis satisfied) is
            # re-registering: accepted like any other, but the handshake is
            # a REJOIN and the forensic record must say so — "this host was
            # refused, proved stable, and came back" reads very differently
            # from a first-contact register in a postmortem.
            metrics.flight_recorder().record(
                "quarantine_rejoin", ip=ip, protocol=info.protocol,
                ping_interval=info.ping_interval)
            logger.info("quarantined host %s rejoined after hysteresis "
                        "lift", ip)
        else:
            metrics.flight_recorder().record(
                "register", ip=ip, protocol=info.protocol,
                ping_interval=info.ping_interval)
        logger.info(
            "agent %s registered (protocol v%d, ping %.1fs, read deadline "
            "%.1fs)", ip, info.protocol, info.ping_interval,
            info.read_deadline,
        )
        await send_response(writer, ResponseType.SUCCESS,
                            {"args": self.job.to_dict()})
        if self.coordinator is not None:
            # Late registrant: replay the coordinator announcement it missed.
            await send_response(writer, ResponseType.FORWARD_COORDINATOR,
                                self._coordinator_payload())
        # Keep the channel open: this connection is the liveness signal.
        try:
            await self._agent_loop(info)
        finally:
            # Identity guard: an agent that re-dialed (register retry)
            # replaces its registry entry; when the OLD connection's loop
            # unwinds it must not evict the NEW live registration.
            if self.agents.get(ip) is info:
                await self._close_agent(ip)
            else:
                info.writer.close()

    async def _handle_join(self, msg, reader, writer) -> None:
        """Mid-training JOIN: a freshly provisioned host volunteering
        capacity to a running job. Distinct from initial bring-up (the
        host was never in node_ips) and from a quarantine-lifted host
        re-registering (that one replays REGISTER_AGENT and is tagged
        quarantine_rejoin). The handshake mirrors register — SUCCESS with
        job args, coordinator replay, long-lived liveness channel — but
        instead of filling a known slot it opens (or rides) a batched
        GROW incident."""
        ip = msg.get("ip") or writer.get_extra_info("peername")[0]
        if self.job is None:
            await send_response(writer, ResponseType.FAILURE,
                                {"error": "no job configured"})
            writer.close()
            return
        if self.policy.is_quarantined(ip):
            # A flapping host does not get to grow the cluster either; the
            # same hysteresis that gates re-registration gates JOIN.
            logger.warning("refusing JOIN from quarantined host %s", ip)
            metrics.flight_recorder().record("join_refused", ip=ip,
                                             reason="quarantined")
            await send_response(writer, ResponseType.FAILURE,
                                {"error": "quarantined"})
            writer.close()
            return
        if ip in self.agents or len(self.agents) >= MAX_NUM_HOSTS:
            reason = "already registered" if ip in self.agents \
                else f"cluster full (max {MAX_NUM_HOSTS})"
            metrics.flight_recorder().record("join_refused", ip=ip,
                                             reason=reason)
            await send_response(writer, ResponseType.FAILURE,
                                {"error": reason})
            writer.close()
            return
        interval = float(msg.get("ping_interval") or DEFAULT_PING_INTERVAL)
        info = AgentInfo(
            ip, reader, writer,
            protocol=int(msg.get("protocol") or 1),
            ping_interval=interval,
            read_deadline=read_deadline(interval),
        )
        self.agents[ip] = info
        self._m_registrations.inc()
        self._journal(journal_mod.EV_REGISTER, ip=ip,
                      tenant=self._train_tenant)
        self.fleet.clear(ip)
        # Expected-lifetime hint for the policy's amortization horizon: the
        # joiner may advertise one (spot instances know their own market),
        # else a chaos spot_lifetime directive supplies it for drills.
        hint: float | None = None
        raw_hint = msg.get("spot_lifetime_s")
        if raw_hint is not None:
            try:
                hint = float(raw_hint) or None
            except (TypeError, ValueError):
                hint = None
        if hint is None:
            hint = chaos().spot_lifetime(ip)
        metrics.flight_recorder().record(
            "join", ip=ip, protocol=info.protocol,
            ping_interval=info.ping_interval, spot_lifetime_s=hint)
        logger.info("host %s JOINed mid-training (protocol v%d, lifetime "
                    "hint %s)", ip, info.protocol, hint)
        await send_response(writer, ResponseType.SUCCESS,
                            {"args": self.job.to_dict()})
        if self.coordinator is not None:
            await send_response(writer, ResponseType.FORWARD_COORDINATOR,
                                self._coordinator_payload())
        self._pending_joins.append((ip, hint))
        if self._join_flush_task is None or self._join_flush_task.done():
            self._join_flush_task = asyncio.ensure_future(self._flush_joins())
        try:
            await self._agent_loop(info)
        finally:
            if self.agents.get(ip) is info:
                await self._close_agent(ip)
            else:
                info.writer.close()

    def _join_window_s(self) -> float:
        raw = os.environ.get(ENV_JOIN_WINDOW, "")
        try:
            return float(raw) if raw else DEFAULT_JOIN_WINDOW_S
        except ValueError:
            return DEFAULT_JOIN_WINDOW_S

    async def _flush_joins(self) -> None:
        """Close the batching window: every JOIN that landed inside it
        becomes ONE grow incident — one trace, one policy decision, one
        GROW broadcast (the grow-direction mirror of correlated-loss
        batching in the engine's _maybe_reconfigure)."""
        await asyncio.sleep(self._join_window_s())
        batch, self._pending_joins = self._pending_joins, []
        # Keep only joiners still registered: one that dialed in and died
        # inside the window is already handled by its own loss path.
        batch = [(ip, h) for ip, h in batch if ip in self.agents]
        if not batch:
            return
        joined = [ip for ip, _ in batch]
        hints = {ip: h for ip, h in batch if h is not None}
        trace_id = spans.new_trace_id()
        detected_at = time.time()
        with self._snap_lock:
            self._recoveries.append({
                "lost_ip": "", "joined_ips": list(joined), "cause": "join",
                "trace_id": trace_id, "detected_at": detected_at,
                "broadcast_at": None, "resolved_at": None,
            })
        spans.span_recorder().record(
            "incident.detect", detected_at, detected_at, trace_id=trace_id,
            joined_ips=",".join(joined), cause="join")
        fr = metrics.flight_recorder()
        fr.record("join_detected", joined_ips=",".join(joined),
                  trace_id=trace_id)
        fr.dump(f"join_detected:{'+'.join(joined)}")
        decision = self.decide_grow(joined, lifetime_hints=hints)
        await self._broadcast_grow(joined, decision,
                                   include=list(self.agents.values()))

    # -- shared chip pool (oobleck_tpu/pool) --------------------------- #

    async def _handle_pool_borrow(self, msg, writer) -> None:
        """POOL_BORROW: a serve replica group under traffic pressure asks
        to borrow training chips — or returns a lease it holds (the one
        verb covers both directions; the ``release`` key picks the
        reclaim path). The request is an INCIDENT: it flows through the
        arbiter's classify -> score -> broadcast chain exactly like a
        host loss, and a granted borrow reuses the proven proactive-drain
        path — the victim's worker flushes and exits cleanly (JOB_DONE,
        zero respawns) while survivors reroute in place."""
        if self.pool is None:
            await send_response(
                writer, ResponseType.FAILURE,
                {"error": "pool plane disabled "
                          f"(set {pool_arbiter.ENV_POOL}=1)"})
            writer.close()
            return
        tenant = str(msg.get(TENANT_KEY) or "serve")
        self.pool.tenants.register(TenantSpec(
            name=tenant, kind=KIND_SERVE, slo=dict(msg.get("slo") or {})))
        # Pressure is priced SERVE-SIDE (pool/pressure.py) and rides the
        # request: the master never needs serve-plane scrape access.
        pressure = msg.get("pressure") or {}
        try:
            slo_debt = max(float(pressure.get("slo_debt_s") or 0.0), 0.0)
        except (TypeError, ValueError):
            slo_debt = 0.0
        try:
            if msg.get("release"):
                await self._pool_release(msg, writer, slo_debt)
            else:
                await self._pool_grant(msg, writer, tenant, slo_debt)
        finally:
            writer.close()

    async def _pool_grant(self, msg, writer, tenant: str,
                          slo_debt: float) -> None:
        if self.job is None:
            await send_response(writer, ResponseType.FAILURE,
                                {"error": "no job configured"})
            return
        chips = max(int(msg.get("chips") or 1), 1)
        leased = self.pool.leases.leased_hosts()
        train_hosts = len([ip for ip in self.agents if ip not in leased])
        ttl: float | None = None
        if msg.get("lease_ttl_s") is not None:
            try:
                ttl = float(msg["lease_ttl_s"]) or None
            except (TypeError, ValueError):
                ttl = None
        # The live master keeps no standing spare pool — every registered
        # host is training — so drain-vs-deny is the live decision;
        # deployments with spares score them in the sim.
        decision = self.pool.decide_borrow(
            tenant, chips,
            train_hosts=train_hosts,
            spare_hosts=0,
            slo_debt_s=slo_debt,
            lease_ttl_s=ttl,
            lender=self._train_tenant,
            cause=str(msg.get("cause") or "pressure"),
        )
        if decision.mechanism != pool_arbiter.MECH_BORROW_DRAIN:
            # deny, or a forced spare arm that is infeasible live.
            await send_response(writer, ResponseType.FAILURE, {
                "error": f"borrow denied ({decision.reason})",
                DECISION_KEY: decision.as_payload()})
            return
        victims = self._pick_lease_hosts(chips)
        if len(victims) < chips:
            await send_response(writer, ResponseType.FAILURE, {
                "error": f"not enough leasable hosts "
                         f"({len(victims)}/{chips})",
                DECISION_KEY: decision.as_payload()})
            return
        ttl = ttl if ttl is not None else self.pool.lease_ttl_s
        lease = self.pool.leases.grant(
            tenant, victims, ttl, lender=self._train_tenant,
            trace_id=decision.trace_id or "")
        decision.hosts = list(victims)
        decision.lease_id = lease.lease_id
        # WAL before the fleet learns anything: a master that dies past
        # this line restarts knowing who holds whose chips.
        self._journal(journal_mod.EV_LEASE, lease_id=lease.lease_id,
                      state="active", tenant=tenant,
                      lender=self._train_tenant, hosts=list(victims),
                      expires_at=lease.expires_at)
        self._journal(journal_mod.EV_INCIDENT_OPEN,
                      trace_id=decision.trace_id,
                      lost_ip=",".join(victims), cause="pool_borrow")
        with self._snap_lock:
            self._recoveries.append({
                "lost_ip": ",".join(victims), "cause": "pool_borrow",
                "trace_id": decision.trace_id,
                "detected_at": decision.decided_at,
                "broadcast_at": None, "resolved_at": None,
            })
        fr = metrics.flight_recorder()
        fr.record("lease_granted", lease_id=lease.lease_id, tenant=tenant,
                  hosts=",".join(victims), ttl_s=ttl,
                  trace_id=decision.trace_id)
        fr.dump(f"lease_granted:{lease.lease_id}")
        # Cross-tenant attribution: the LENDER pays the projected
        # degraded-training seconds, charged under the arbiter's
        # incident trace so the incident file can total the bill.
        self.pool.tenants.attribute(
            decision.trace_id or "",
            {self._train_tenant: decision.projected_cost_s or 0.0},
            cause="pool_borrow")
        for ip in victims:
            victim = self.agents.get(ip)
            if victim is not None:
                # The drained worker's departure is a clean JOB_DONE
                # exit, not a second incident.
                victim.clean_exit = True
            await self._broadcast_lease_grant(ip, lease, decision)
            # Its telemetry row describes a training life that just
            # ended; returning via JOIN starts a fresh one.
            self.fleet.clear(ip)
        await send_response(writer, ResponseType.SUCCESS,
                            {LEASE_KEY: lease.as_record(),
                             DECISION_KEY: decision.as_payload()})

    async def _pool_release(self, msg, writer, slo_debt: float) -> None:
        """Early return: the borrower's peak passed. The arbiter still
        scores hold-vs-reclaim (a forced ``hold`` baseline extends the
        lease instead), and a reclaim flows the hosts back through the
        grow path."""
        lease_id = str(msg.get("release"))
        lease = self.pool.leases.get(lease_id)
        if lease is None:
            await send_response(writer, ResponseType.FAILURE,
                                {"error": f"unknown lease {lease_id}"})
            return
        leased = self.pool.leases.leased_hosts()
        train_hosts = len([ip for ip in self.agents if ip not in leased])
        decision = self.pool.decide_reclaim(
            lease, train_hosts=train_hosts, slo_debt_s=slo_debt,
            cause="release")
        if decision.mechanism == pool_arbiter.MECH_HOLD:
            extended = self.pool.leases.extend(lease_id,
                                               self.pool.lease_ttl_s)
            self._journal(journal_mod.EV_LEASE, lease_id=lease_id,
                          state="active", tenant=lease.tenant,
                          lender=lease.lender, hosts=list(lease.hosts),
                          expires_at=extended.expires_at)
            await send_response(writer, ResponseType.SUCCESS,
                                {LEASE_KEY: extended.as_record(),
                                 DECISION_KEY: decision.as_payload()})
            return
        ended = self.pool.leases.end(lease_id, ST_RETURNED)
        self._journal(journal_mod.EV_LEASE, lease_id=lease_id,
                      state=ST_RETURNED, tenant=ended.tenant)
        # Cross-tenant bill under ONE trace: the borrower pays whatever
        # pressure it still carries (re-exposure), the lender pays the
        # projected grow-absorption cost of taking the chips back.
        self.pool.tenants.attribute(
            decision.trace_id or "",
            {ended.tenant: slo_debt,
             ended.lender: decision.projected_cost_s or 0.0},
            cause="pool_release")
        metrics.flight_recorder().record(
            "lease_released", lease_id=lease_id, tenant=ended.tenant,
            hosts=",".join(ended.hosts), trace_id=decision.trace_id)
        await self._broadcast_lease_reclaim(ended, decision)
        await send_response(writer, ResponseType.SUCCESS,
                            {LEASE_KEY: ended.as_record(),
                             DECISION_KEY: decision.as_payload()})

    def _pick_lease_hosts(self, chips: int) -> list[str]:
        """Lease victims: most recently registered first (least pipeline
        seniority), never the coordinator host, never a host already out
        on a lease."""
        coord_ip = (self.coordinator or "").rsplit(":", 1)[0]
        leased = self.pool.leases.leased_hosts()
        return [ip for ip in reversed(list(self.agents))
                if ip not in leased and ip != coord_ip][:chips]

    async def _lease_sweep_loop(self) -> None:
        """Lease expiry is an incident, not a timer: every sweep feeds
        due leases to the arbiter, which scores hold-vs-reclaim with the
        same cost model. Pressure only ever rides POOL_BORROW requests,
        so no renewal arriving before expiry IS the off-peak signal: a
        due lease carries zero debt and its chips flow back through the
        grow path."""
        period = pool_arbiter.sweep_period_s()
        while True:
            await asyncio.sleep(period)
            for lease in self.pool.leases.due():
                leased = self.pool.leases.leased_hosts()
                train_hosts = len(
                    [ip for ip in self.agents if ip not in leased])
                decision = self.pool.decide_reclaim(
                    lease, train_hosts=train_hosts, cause="expiry")
                if decision.mechanism == pool_arbiter.MECH_HOLD:
                    # Unreachable under adaptive scoring (an expired
                    # lease makes hold infeasible) but a future forced
                    # baseline must extend, not leak the lease.
                    self.pool.leases.extend(lease.lease_id,
                                            self.pool.lease_ttl_s)
                    continue
                ended = self.pool.leases.end(lease.lease_id, ST_EXPIRED)
                if ended is None:
                    continue
                self._journal(journal_mod.EV_LEASE,
                              lease_id=ended.lease_id, state=ST_EXPIRED,
                              tenant=ended.tenant)
                self.pool.tenants.attribute(
                    decision.trace_id or "",
                    {ended.lender: decision.projected_cost_s or 0.0},
                    cause="pool_expiry")
                await self._broadcast_lease_reclaim(ended, decision)

    async def _handle_reattach(self, msg, reader, writer) -> None:
        """Post-outage re-attachment: an agent that rode out a master
        outage in masterless mode re-dials the restarted master. Its
        worker is ALIVE and mid-training — nothing is launched, nothing
        respawns; the handshake only restores the liveness channel,
        replays the agent's buffered masterless-era observations, and
        marks the host present for the reconcile window. Quarantine does
        NOT gate reattach: the host never left the job, and evicting a
        healthy running worker over pre-outage flap history would turn
        the master's own outage into a training incident."""
        ip = msg.get("ip") or writer.get_extra_info("peername")[0]
        if self.job is None:
            await send_response(writer, ResponseType.FAILURE,
                                {"error": "no job configured"})
            writer.close()
            return
        last_epoch = int(msg.get("last_epoch") or 0)
        if self.master_epoch and last_epoch > self.master_epoch:
            # The agent has applied verbs from a HIGHER epoch than ours:
            # we are the zombie (resurrected from an older journal or a
            # partitioned copy). Refuse — the fence cuts both ways.
            logger.error(
                "agent %s reports epoch %d > ours %d; this master is "
                "stale and must not drive the fleet", ip, last_epoch,
                self.master_epoch)
            metrics.flight_recorder().record(
                "stale_master_refused", ip=ip, agent_epoch=last_epoch,
                master_epoch=self.master_epoch)
            await send_response(writer, ResponseType.FAILURE,
                                {"error": "stale master epoch"})
            writer.close()
            return
        interval = float(msg.get("ping_interval") or DEFAULT_PING_INTERVAL)
        info = AgentInfo(
            ip, reader, writer,
            protocol=int(msg.get("protocol") or 1),
            ping_interval=interval,
            read_deadline=read_deadline(interval),
        )
        old = self.agents.get(ip)
        if old is not None:
            old.writer.close()  # superseded pre-outage connection
        self.agents[ip] = info
        self._m_reattaches.inc()
        self._reattached.add(ip)
        self._reattached_total += 1
        # Tenant-stamped like every registration: the reconciled fleet
        # must replay into the same tenant the job entry is keyed by.
        self._journal(journal_mod.EV_REGISTER, ip=ip,
                      tenant=self._train_tenant)
        worker_alive = bool(msg.get("worker_alive", True))
        metrics.flight_recorder().record(
            "reattach", ip=ip, last_epoch=last_epoch,
            epoch=self.master_epoch, worker_alive=worker_alive,
            buffered=len(msg.get("buffered") or ()))
        if self._outage_trace_id is not None:
            t = time.time()
            spans.span_recorder().record(
                "outage.reattached", t, t, trace_id=self._outage_trace_id,
                ip=ip, worker_alive=worker_alive)
        logger.info("agent %s reattached (last_epoch=%d, worker_alive=%s)",
                    ip, last_epoch, worker_alive)
        self._replay_buffered(ip, msg.get("buffered"))
        await send_response(
            writer, ResponseType.SUCCESS,
            {"args": self.job.to_dict(), EPOCH_KEY: self.master_epoch})
        if self.coordinator is not None:
            await send_response(writer, ResponseType.FORWARD_COORDINATOR,
                                self._coordinator_payload())
        try:
            await self._agent_loop(info)
        finally:
            if self.agents.get(ip) is info:
                await self._close_agent(ip)
            else:
                info.writer.close()

    def _replay_buffered(self, ip: str, buffered) -> None:
        """Fold an agent's masterless-era queue back into the planes that
        missed it: worker-observed failures feed the MTBF/quarantine
        estimator (and the journal), committed incident reports land in
        the /status forensics ring. Bounded — the agent's queue already
        is, but a hostile payload must not be."""
        if not isinstance(buffered, list):
            return
        for ev in buffered[:64]:
            if not isinstance(ev, dict):
                continue
            if ev.get("kind") == "failure" and ev.get("ip"):
                cause = str(ev.get("cause") or "masterless")
                self.policy.observe_failure(str(ev["ip"]), cause)
                self._journal(journal_mod.EV_FAILURE, ip=str(ev["ip"]),
                              cause=cause)
                metrics.flight_recorder().record(
                    "masterless_replay", ip=str(ev["ip"]), cause=cause,
                    via=ip)
            elif ev.get("kind") == "incident" \
                    and isinstance(ev.get("report"), dict):
                rep = ev["report"]
                tid = rep.get("trace_id")
                with self._snap_lock:
                    if not any(i.get("trace_id") == tid
                               for i in self._incidents):
                        self._incidents.append(rep)
                        del self._incidents[:-MAX_INCIDENTS]

    def _reattach_window_s(self) -> float:
        raw = os.environ.get(ENV_REATTACH_WINDOW, "")
        try:
            return float(raw) if raw else DEFAULT_REATTACH_WINDOW_S
        except ValueError:
            return DEFAULT_REATTACH_WINDOW_S

    async def _reconcile_after_window(self) -> None:
        """Close the post-restart reconciliation: journal-vs-reality.
        Every host the replayed journal expected that neither REATTACHed
        nor freshly registered inside the window died DURING the outage —
        all of them become ONE batched loss incident (one trace, one
        policy decision) through the normal recovery chain."""
        await asyncio.sleep(self._reattach_window_s())
        missing = sorted(ip for ip in self._expected_reattach
                         if ip not in self.agents)
        self._expected_reattach = set()
        fr = metrics.flight_recorder()
        fr.record("reattach_reconciled", epoch=self.master_epoch,
                  reattached=sorted(self._reattached), missing=missing)
        if self._outage_trace_id is not None:
            t = time.time()
            spans.span_recorder().record(
                "outage.reconciled", t, t, trace_id=self._outage_trace_id,
                reattached=len(self._reattached),
                missing=",".join(missing))
        if not missing:
            logger.info("reconciled after restart: all %d agents "
                        "reattached", len(self._reattached))
            return
        logger.warning("reconciled after restart: hosts %s died during "
                       "the outage", missing)
        trace_id = spans.new_trace_id()
        detected_at = time.time()
        for ip in missing:
            self.policy.observe_failure(ip, "master_outage")
            self._journal(journal_mod.EV_FAILURE, ip=ip,
                          cause="master_outage")
            self._journal(journal_mod.EV_DEPART, ip=ip)
            with self._snap_lock:
                self._recoveries.append({
                    "lost_ip": ip, "cause": "master_outage",
                    "trace_id": trace_id, "detected_at": detected_at,
                    "broadcast_at": None, "resolved_at": None,
                })
        self._journal(journal_mod.EV_INCIDENT_OPEN, trace_id=trace_id,
                      lost_ip=",".join(missing), cause="master_outage")
        spans.span_recorder().record(
            "incident.detect", detected_at, detected_at, trace_id=trace_id,
            lost_ip=",".join(missing), cause="master_outage")
        fr.record("detect", ip=",".join(missing), cause="master_outage",
                  trace_id=trace_id)
        fr.dump(f"failure_detected:{'+'.join(missing)}")
        recovery.mark(recovery.DETECT, lost_ip=",".join(missing),
                      cause="master_outage")
        # ONE policy decision for the correlated batch; the per-ip
        # broadcasts share it (agents prune membership one ip at a time).
        decision = self.decide_recovery(missing)
        for ip in missing:
            await self._broadcast_recovery(
                ip, decision, include=list(self.agents.values()))

    def _coordinator_payload(self) -> dict:
        """Coordinator relay payload; the generation tag is included only
        when the announcer supplied one (absent = legacy untagged trust)."""
        payload = {"address": self.coordinator}
        if self.coordinator_world is not None:
            payload["world"] = self.coordinator_world
        return payload

    async def _agent_loop(self, agent: AgentInfo) -> None:
        """Serve requests from one agent until it disconnects OR misses its
        heartbeat deadline (reference agent_handler, master.py:214-231 —
        which reads with timeout=None and therefore never detects a hung
        peer; here every read is bounded by the agent's own cadence)."""
        while True:
            try:
                msg = await recv_msg(agent.reader,
                                     timeout=agent.read_deadline)
            except (asyncio.TimeoutError, TimeoutError):
                if self._is_failure(agent):
                    logger.warning(
                        "agent %s sent nothing for %.1fs (ping interval "
                        "%.1fs); evicting hung peer", agent.ip,
                        agent.read_deadline, agent.ping_interval,
                    )
                    self._on_failure_detected(agent.ip, "heartbeat_deadline")
                    recovery.mark(recovery.DETECT, lost_ip=agent.ip,
                                  cause="heartbeat_deadline",
                                  deadline=agent.read_deadline)
                return
            except (asyncio.IncompleteReadError, ConnectionError):
                if self._is_failure(agent):
                    logger.warning("agent %s disconnected", agent.ip)
                    self._on_failure_detected(agent.ip, "disconnect")
                    recovery.mark(recovery.DETECT, lost_ip=agent.ip,
                                  cause="disconnect")
                return
            agent.last_seen = time.monotonic()
            kind = msg.get("kind")
            if kind == RequestType.PING.value:
                metrics.flight_recorder().record("heartbeat", ip=agent.ip)
                d = msg.get(TELEMETRY_KEY)
                if obs_telemetry.digest_ok(d):
                    # Piggybacked fleet-health digest (legacy agents send
                    # none — they simply contribute no row). The epoch
                    # stamp fences out samples describing a dead master
                    # incarnation's steps.
                    self.fleet.ingest(
                        agent.ip, d, epoch=d.get("epoch"),
                        min_epoch=self.master_epoch or None)
                    slow_ip = self.fleet.consume_straggler()
                    if slow_ip is not None:
                        await self._on_slowdown_detected(slow_ip)
                await send_response(agent.writer, ResponseType.PONG)
            elif kind == RequestType.METRICS.value:
                # Fire-and-forget: no response, never back-pressures pings.
                self._record_metrics_push(msg)
            elif kind == RequestType.GET_DIST_INFO.value:
                info = DistributionInfo(
                    agent_ips=list(self.agents.keys()),
                    world_size=len(self.agents) * (
                        self.job.dist.num_workers if self.job else 1
                    ),
                )
                await send_response(agent.writer, ResponseType.SUCCESS,
                                    {"dist_info": info.to_dict()})
            elif kind == RequestType.JOB_DONE.value:
                logger.info("agent %s reports training complete", agent.ip)
                agent.clean_exit = True
            elif kind == RequestType.PREEMPTION_NOTICE.value:
                await self._handle_preemption(agent, msg)
            elif kind == RequestType.FORWARD_COORDINATOR.value:
                # First agent's worker announces the JAX coordinator address;
                # relay to everyone (reference forward_rank0_port_handler,
                # master.py:137-154). The `world` generation tag rides along
                # so respawned workers can reject stale pre-failure
                # announcements (worker.coordinator_address_if_current).
                self.coordinator = msg["address"]
                self.coordinator_world = msg.get("world")
                for other in list(self.agents.values()):
                    await send_response(
                        other.writer, ResponseType.FORWARD_COORDINATOR,
                        self._coordinator_payload(),
                    )
            else:
                await send_response(agent.writer, ResponseType.FAILURE,
                                    {"error": f"unknown request {kind}"})

    def _is_failure(self, agent: AgentInfo) -> bool:
        """A read-loop exit counts as a host failure (DETECT mark +
        eviction warning) only when the connection still represents a live
        registration: not after JOB_DONE (completion is not a failure) and
        not when a re-registration already superseded this connection."""
        return not agent.clean_exit and self.agents.get(agent.ip) is agent

    def _on_failure_detected(self, lost_ip: str, cause: str) -> None:
        """Flight-record the detection, open a /status recovery entry, and
        dump the ring — this is the postmortem moment. Mints the incident's
        trace_id: every span and verb in this recovery, in every process,
        stitches onto it."""
        # Feed the online MTBF/flap estimator — the failure log IS the
        # policy plane's churn signal.
        self.policy.observe_failure(lost_ip, cause)
        # Its fleet-health row describes a host that no longer exists.
        self.fleet.clear(lost_ip)
        self._journal(journal_mod.EV_FAILURE, ip=lost_ip, cause=cause)
        if self.policy.is_quarantined(lost_ip):
            self._journal(journal_mod.EV_QUARANTINE, ip=lost_ip,
                          entered=True)
        trace_id = spans.new_trace_id()
        self._journal(journal_mod.EV_INCIDENT_OPEN, trace_id=trace_id,
                      lost_ip=lost_ip, cause=cause)
        with self._snap_lock:
            self._recoveries.append({
                "lost_ip": lost_ip, "cause": cause, "trace_id": trace_id,
                "detected_at": time.time(), "broadcast_at": None,
                "resolved_at": None,
            })
        t = time.time()
        spans.span_recorder().record(
            "incident.detect", t, t, trace_id=trace_id,
            lost_ip=lost_ip, cause=cause)
        fr = metrics.flight_recorder()
        fr.record("detect", ip=lost_ip, cause=cause, trace_id=trace_id)
        fr.dump(f"failure_detected:{lost_ip}")

    async def _on_slowdown_detected(self, ip: str) -> None:
        """Gray failure: the fleet tracker flagged `ip` as alive but
        persistently slow. Open a SLOWDOWN incident through the same
        classify -> policy chain real failures use — the host is NOT dead,
        so there is no observe_failure/EV_FAILURE, but the incident gets a
        trace_id, a /status recovery entry, and a scored decision. An
        active arm (drain / quarantine) reuses the preemption machinery:
        broadcast to everyone INCLUDING the victim, whose worker flushes a
        checkpoint and exits cleanly (JOB_DONE, zero respawns)."""
        self._m_slowdowns.inc()
        ratio = self.fleet.ratio(ip) or self.fleet.ratio_threshold
        trace_id = spans.new_trace_id()
        self._journal(journal_mod.EV_INCIDENT_OPEN, trace_id=trace_id,
                      lost_ip=ip, cause="slowdown")
        detected_at = time.time()
        entry = {
            "lost_ip": ip, "cause": "slowdown", "trace_id": trace_id,
            "detected_at": detected_at, "broadcast_at": None,
            "resolved_at": None, "slowdown_ratio": ratio,
        }
        with self._snap_lock:
            self._recoveries.append(entry)
        spans.span_recorder().record(
            "incident.detect", detected_at, detected_at, trace_id=trace_id,
            lost_ip=ip, cause="slowdown", ratio=ratio)
        fr = metrics.flight_recorder()
        fr.record("slowdown_detected", ip=ip, ratio=ratio,
                  trace_id=trace_id)
        fr.dump(f"slowdown_detected:{ip}")
        n = len(self.agents)
        decision = self.policy.decide_slowdown(
            ip, slowdown_ratio=ratio,
            survivor_frac=(n - 1) / n if n else 1.0)
        logger.warning(
            "slowdown incident for %s (ratio %.2f): %s (%s)", ip, ratio,
            decision.mechanism, decision.reason)
        if decision.mechanism == MECH_OBSERVE:
            # Passive arm: keep the host, keep watching. The incident
            # closes immediately — nothing was broadcast, so the usual
            # first-worker-snapshot close would never fire.
            with self._snap_lock:
                entry["mechanism"] = MECH_OBSERVE
                entry["resolved_at"] = detected_at
            self._journal(journal_mod.EV_INCIDENT_CLOSE, trace_id=trace_id)
            return
        victim = self.agents.get(ip)
        if victim is not None:
            # The drained worker's departure is a clean JOB_DONE exit,
            # not a second incident.
            victim.clean_exit = True
        await self._broadcast_recovery(ip, decision,
                                       include=list(self.agents.values()))
        # The drained host's telemetry row describes a life that just
        # ended; its next registration starts a fresh one.
        self.fleet.clear(ip)

    async def _handle_preemption(self, agent: AgentInfo, msg: dict) -> None:
        """Spot-preemption advance notice: the host will die in ~deadline_s.
        React BEFORE the corpse appears — policy decision now (proactive),
        recovery broadcast to everyone INCLUDING the victim, whose agent
        drains its worker (checkpoint flush) inside the warning window.
        The victim's later disconnect is then a clean exit, not a second
        incident."""
        ip = msg.get("ip") or agent.ip
        deadline_s = float(msg.get("deadline_s") or 0.0)
        logger.warning("preemption notice from %s: host dies in ~%.1fs",
                       ip, deadline_s)
        metrics.flight_recorder().record(
            "preemption_notice", ip=ip, deadline_s=deadline_s)
        self._on_failure_detected(ip, "preemption_notice")
        decision = self.decide_recovery([ip], proactive=True)
        victim = self.agents.get(ip)
        if victim is not None:
            # Its read-loop exit (the host dying) must not re-broadcast.
            victim.clean_exit = True
        await self._broadcast_recovery(ip, decision,
                                       include=list(self.agents.values()))

    async def _close_agent(self, ip: str) -> None:
        """Reference close_agent (master.py:192-203): drop the agent and
        broadcast the loss to survivors — unless the agent announced a clean
        JOB_DONE departure (completion is not a failure)."""
        agent = self.agents.pop(ip, None)
        if agent is not None:
            agent.writer.close()
            self._journal(journal_mod.EV_DEPART, ip=ip)
        if agent is not None and agent.clean_exit:
            if not self.agents and not (
                    self.pool is not None and self.pool.leases.active()):
                # The last agent's clean exit closes the job in the
                # journal: a later master restart must not wait for a
                # completed fleet to reattach. A lease-drained fleet is
                # NOT a completed job — chips out on loan come back.
                self._journal(journal_mod.EV_JOB_DONE,
                              tenant=self._train_tenant)
            return
        # Adaptive policy (oobleck_tpu/policy): score reroute /
        # reinstantiate / restore from live signals and broadcast the
        # cheapest feasible verb. OOBLECK_DEGRADE=0 stays a hard
        # feasibility gate on rerouting; OOBLECK_POLICY forces a fixed arm.
        decision = self.decide_recovery([ip])
        await self._broadcast_recovery(ip, decision,
                                       include=list(self.agents.values()))

    def _verb_for(self, mechanism: str) -> ResponseType:
        return {
            MECH_REROUTE: ResponseType.DEGRADE,
            MECH_REINSTANTIATE: ResponseType.RECONFIGURATION,
            MECH_RESTORE: ResponseType.RESTORE,
            # Slowdown arms ride the DEGRADE verb: survivors take the
            # in-place reroute path, the victim (included in the
            # broadcast, preemption-style) drains and exits cleanly.
            MECH_DRAIN: ResponseType.DEGRADE,
            MECH_QUARANTINE: ResponseType.DEGRADE,
        }[mechanism]

    async def _broadcast_recovery(self, ip: str, decision,
                                  include: list[AgentInfo]) -> None:
        """Broadcast the decided recovery verb for the loss of `ip` with
        the policy decision attached. The wire trace and flight recorder
        must show which recovery the master ASKED for (and why), not just
        which one the engine took."""
        verb = self._verb_for(decision.mechanism)
        # Trace context rides the verb (one extra JSON key; legacy agents
        # ignore it) carrying the incident's trace_id plus the master-side
        # wall-clock marks, so the worker's incident report can reconstruct
        # the full detect → broadcast → notified → apply chain.
        broadcast_at = time.time()
        trace_ctx: dict | None = None
        with self._snap_lock:
            for r in self._recoveries:
                if r["lost_ip"] == ip and r["broadcast_at"] is None:
                    r["broadcast_at"] = broadcast_at
                    r["mechanism"] = decision.mechanism
                    if r.get("trace_id"):
                        trace_ctx = {
                            "trace_id": r["trace_id"],
                            "detected_at": r["detected_at"],
                            "broadcast_at": broadcast_at,
                            "cause": r.get("cause"),
                        }
        payload: dict = {"lost_ip": ip, DECISION_KEY: decision.as_payload()}
        if self.master_epoch:
            # Split-brain fence: agents reject verbs below their
            # highest-applied epoch, so a zombie pre-restart master's
            # broadcasts are refused fleet-wide. Epoch 0 (journaling off)
            # stays unstamped — legacy untagged trust.
            payload[EPOCH_KEY] = self.master_epoch
        if trace_ctx is not None:
            payload[spans.TRACE_KEY] = trace_ctx
            decision.trace_id = trace_ctx["trace_id"]
            spans.span_recorder().record(
                "incident.broadcast", broadcast_at, broadcast_at,
                trace_id=trace_ctx["trace_id"], lost_ip=ip, verb=verb.value,
                mechanism=decision.mechanism, survivors=len(self.agents))
        for other in include:
            try:
                await send_response(other.writer, verb, payload)
            except ConnectionError:
                pass
        self._m_reconfigs.inc()
        fr = metrics.flight_recorder()
        fr.record("reconfiguration_broadcast", lost_ip=ip,
                  survivors=len(self.agents), verb=verb.value,
                  mechanism=decision.mechanism)
        # Second dump so the postmortem file holds the complete sequence
        # detect → broadcast (the detect-time dump races the broadcast).
        fr.dump(f"reconfiguration_broadcast:{ip}")
        recovery.mark(recovery.BROADCAST, lost_ip=ip,
                      survivors=len(self.agents))

    async def _broadcast_grow(self, joined_ips: list[str], decision,
                              include: list[AgentInfo]) -> None:
        """Broadcast the decided grow verb for a JOIN batch, policy
        decision attached. GROW always rides the one verb — the chosen arm
        (absorb_spare / grow_dp / grow_reshape) travels inside the
        decision payload, so legacy receivers that predate the verb skip
        the whole thing knowingly (absorption degrades to a no-op, never
        an outage). The empty lost_ip satisfies the shared broadcast
        machinery's core-key contract."""
        broadcast_at = time.time()
        trace_ctx: dict | None = None
        with self._snap_lock:
            for r in self._recoveries:
                if (r.get("joined_ips") == joined_ips
                        and r["broadcast_at"] is None):
                    r["broadcast_at"] = broadcast_at
                    r["mechanism"] = decision.mechanism
                    if r.get("trace_id"):
                        trace_ctx = {
                            "trace_id": r["trace_id"],
                            "detected_at": r["detected_at"],
                            "broadcast_at": broadcast_at,
                            "cause": r.get("cause"),
                        }
        payload: dict = {"lost_ip": "", DECISION_KEY: decision.as_payload()}
        payload[JOINED_KEY] = list(joined_ips)
        if self.master_epoch:
            payload[EPOCH_KEY] = self.master_epoch
        if trace_ctx is not None:
            payload[spans.TRACE_KEY] = trace_ctx
            decision.trace_id = trace_ctx["trace_id"]
            spans.span_recorder().record(
                "incident.broadcast", broadcast_at, broadcast_at,
                trace_id=trace_ctx["trace_id"],
                joined_ips=",".join(joined_ips),
                verb=ResponseType.GROW.value,
                mechanism=decision.mechanism, agents=len(self.agents))
        for other in include:
            try:
                await send_response(other.writer, ResponseType.GROW, payload)
            except ConnectionError:
                pass
        self._m_grows.inc(mechanism=decision.mechanism)
        fr = metrics.flight_recorder()
        fr.record("grow_broadcast", joined_ips=",".join(joined_ips),
                  agents=len(self.agents), mechanism=decision.mechanism)
        fr.dump(f"grow_broadcast:{'+'.join(joined_ips)}")

    async def _broadcast_lease_grant(self, ip: str, lease,
                                     decision) -> None:
        """LEASE_GRANT rides the proactive-drain DEGRADE shape: the verb
        carries the arbiter decision flagged proactive (the victim
        drains — checkpoint flush, clean exit) and inplace (survivors
        reroute at a step boundary, zero respawns), plus the lease
        record under LEASE_KEY. Legacy agents fall back to
        RECONFIGURATION semantics (message.py), so a mixed fleet still
        converges."""
        broadcast_at = time.time()
        with self._snap_lock:
            for r in self._recoveries:
                if (r.get("trace_id") == decision.trace_id
                        and r["broadcast_at"] is None):
                    r["broadcast_at"] = broadcast_at
                    r["mechanism"] = decision.mechanism
        wire_decision = dict(decision.as_payload(),
                             proactive=True, inplace=True)
        payload: dict = {"lost_ip": ip, DECISION_KEY: wire_decision}
        payload[LEASE_KEY] = lease.as_record()
        if self.master_epoch:
            payload[EPOCH_KEY] = self.master_epoch
        if decision.trace_id:
            payload[spans.TRACE_KEY] = {
                "trace_id": decision.trace_id,
                "detected_at": decision.decided_at,
                "broadcast_at": broadcast_at,
                "cause": "pool_borrow",
            }
            spans.span_recorder().record(
                "incident.broadcast", broadcast_at, broadcast_at,
                trace_id=decision.trace_id, lost_ip=ip,
                verb=ResponseType.LEASE_GRANT.value,
                mechanism=decision.mechanism, survivors=len(self.agents))
        for other in list(self.agents.values()):
            try:
                await send_response(other.writer,
                                    ResponseType.LEASE_GRANT, payload)
            except ConnectionError:
                pass
        self._m_lease_broadcasts.inc(verb=ResponseType.LEASE_GRANT.value)
        fr = metrics.flight_recorder()
        fr.record("lease_grant_broadcast", lost_ip=ip,
                  lease_id=lease.lease_id, tenant=lease.tenant,
                  mechanism=decision.mechanism)
        fr.dump(f"lease_grant_broadcast:{ip}")
        recovery.mark(recovery.BROADCAST, lost_ip=ip,
                      survivors=len(self.agents))

    async def _broadcast_lease_reclaim(self, lease, decision) -> None:
        """LEASE_RECLAIM rides the GROW shape: the returning hosts
        travel under JOINED_KEY so agents extend membership through
        on_grow, while the host processes themselves re-enter through
        the normal JOIN/grow machinery (relaunching the returned host's
        agent is the deployer's job, exactly as for any grown host). The
        empty lost_ip satisfies the shared broadcast core-key
        contract."""
        broadcast_at = time.time()
        payload: dict = {"lost_ip": "", DECISION_KEY: decision.as_payload()}
        payload[JOINED_KEY] = list(lease.hosts)
        payload[LEASE_KEY] = lease.as_record()
        if self.master_epoch:
            payload[EPOCH_KEY] = self.master_epoch
        if decision.trace_id:
            payload[spans.TRACE_KEY] = {
                "trace_id": decision.trace_id,
                "detected_at": decision.decided_at,
                "broadcast_at": broadcast_at,
                "cause": f"pool_{lease.state}",
            }
            spans.span_recorder().record(
                "incident.broadcast", broadcast_at, broadcast_at,
                trace_id=decision.trace_id,
                joined_ips=",".join(lease.hosts),
                verb=ResponseType.LEASE_RECLAIM.value,
                mechanism=decision.mechanism, agents=len(self.agents))
        for other in list(self.agents.values()):
            try:
                await send_response(other.writer,
                                    ResponseType.LEASE_RECLAIM, payload)
            except ConnectionError:
                pass
        self._m_lease_broadcasts.inc(
            verb=ResponseType.LEASE_RECLAIM.value)
        fr = metrics.flight_recorder()
        fr.record("lease_reclaim_broadcast", lease_id=lease.lease_id,
                  hosts=",".join(lease.hosts), state=lease.state,
                  mechanism=decision.mechanism)
        fr.dump(f"lease_reclaim_broadcast:{lease.lease_id}")


async def _amain(port: int, launcher: str, username: str | None,
                 node_port: int, log_dir: str | None) -> None:
    if launcher == "ssh":
        l = SSHLauncher(username, node_port=node_port, log_dir=log_dir)
    else:
        l = LocalLauncher()
    daemon = OobleckMasterDaemon(port=port, launcher=l)
    await daemon.start()
    await daemon.serve_forever()


if __name__ == "__main__":
    import argparse

    p = argparse.ArgumentParser()
    p.add_argument("--port", type=int, default=19191)
    p.add_argument("--launcher", choices=["local", "ssh"], default="local",
                   help="ssh: one agent per host over ssh, with per-host "
                        "log capture; local: subprocesses (single machine)")
    p.add_argument("--username", default=None)
    p.add_argument("--node-port", type=int, default=22)
    p.add_argument("--log-dir", default=None)
    a = p.parse_args()
    logging.basicConfig(level=logging.INFO)
    asyncio.run(_amain(a.port, a.launcher, a.username, a.node_port, a.log_dir))
