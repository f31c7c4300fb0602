"""Fault injection for the elastic control plane (env/config driven).

Recovery claims are only as good as the faults they survived, so the
failure modes the control plane defends against — lost peers, hung
sockets, slow links — are injectable on demand and exercised by tests
(the reference repo injects failures only by SIGKILLing whole agents
from the outside; a hung-but-connected peer is not reproducible that
way).

One env var, ``OOBLECK_CHAOS``, holds a comma-separated list of
directives; each directive is ``action=arg[:qual][@ip]``:

    delay_send=0.25             sleep 0.25 s before every control message
    delay_send=0.25:ping        ... only before PING messages
    drop_send=ping              drop every PING before it hits the wire
    drop_send=ping:3            drop only the 3rd PING
    stall_heartbeat=2@10.0.0.1  agent 10.0.0.1 stops pinging after its
                                2nd ping, socket left OPEN (the hung-peer
                                case TCP disconnect detection cannot see)
    kill_at=step_end:3@10.0.0.1 SIGKILL the process at the 3rd hit of the
                                named barrier, on that host only
    delay_at=serve_reload:0.5   sleep 0.5 s at every hit of the named
                                barrier (slow-I/O injection: a reload
                                crawling on cold storage, an NFS stall)
    kill_stage=1:0              stage-addressed kill: declare the host
                                owning STAGE 1 of pipeline REPLICA 0
                                lost, once, at the next step boundary —
                                the deterministic "kill one DP peer of a
                                specific stage" fault the degraded-mode
                                tests need (single-controller: the engine
                                synthesizes the host loss in place of an
                                out-of-band SIGKILL)
    flap_host=10.0.0.1:2        churn: host 10.0.0.1 flaps — its agent
                                drops the master connection every 2 s and
                                re-registers, repeatedly (the policy
                                plane's quarantine-with-hysteresis case)
    kill_hosts=10.0.0.1+10.0.0.2  correlated simultaneous failure: both
                                hosts declared lost in the SAME step
                                boundary, once (rerouting around two
                                losses at once is usually infeasible —
                                the policy plane must see them together)
    preempt_notice=5:1@10.0.0.1 spot preemption with advance warning:
                                1 s after startup host 10.0.0.1 sends a
                                SIGTERM-style notice to the master, then
                                dies for real 5 s later — the window the
                                proactive drain + checkpoint flush must
                                fit inside
    join_host=10.0.0.5          capacity arrival: host 10.0.0.5 JOINs the
                                running job at the next step boundary,
                                once. The joiner has no process yet, so
                                for THIS action the ``@`` segment is a
                                step-boundary delay, not a process
                                filter: ``join_host=10.0.0.5@3`` arrives
                                after 3 step polls (deterministic — the
                                engine polls once per step)
    join_hosts=10.0.0.5+10.0.0.6  correlated capacity arrival: both hosts
                                JOIN in the SAME step boundary, once —
                                the near-simultaneous-arrival case the
                                master's grow batching window exists for
    spot_lifetime=10.0.0.5:30   the arriving host is a spot instance
                                expected to live ~30 s: the policy plane
                                reads this (NON-consuming) as the
                                amortization horizon when scoring the
                                grow arms, and the engine arms a deferred
                                synthetic loss of that host 30 s after it
                                is admitted — arrival followed by churn
    kill_master=5               control-plane fault: the MASTER process
                                SIGKILLs itself 5 s after startup — the
                                outage the durable journal + agent
                                masterless mode exist for. With a qual,
                                ``kill_master=5:3`` advises the harness
                                to restart the master 3 s after the kill
                                (the master cannot restart itself; the
                                test harness reads the qual)
    partition_master=10.0.0.1:8 network partition: agent 10.0.0.1 loses
                                its master link for 8 s — the master
                                stays up and evicts the host on heartbeat
                                deadline, the agent rides it out
                                masterless and REATTACHes when the
                                partition heals (stale-membership
                                reconcile, not a restart)
    slow_host=10.0.0.1:2.5      gray failure: host 10.0.0.1 runs every
                                step 2.5x slower (its worker sleeps the
                                extra wall time after each step) but
                                stays alive and heartbeating — the
                                straggler the fleet-health detector must
                                flag from telemetry, since no liveness
                                signal ever fires. Like join_host, the
                                ``@`` segment is a step-boundary delay:
                                ``slow_host=10.0.0.1:2.5@3`` starts
                                slowing on the 4th step poll (a healthy
                                baseline first, then degradation)
    kill_replica=8001           serving-replica death: the replica whose
                                HTTP server listens on port 8001 dies at
                                its next /v1/generate request — the
                                in-flight connection aborts with no
                                response and the port stops accepting.
                                ``kill_replica=8001@3`` dies at its 3rd
                                request instead (deterministic mid-
                                traffic kill for router failover tests).
                                One-shot: a dead replica cannot die again
    hang_replica=8001:2         serving-replica hang: the replica on port
                                8001 sleeps 2 s before answering its next
                                request — alive-but-unresponsive, the
                                case the router's liveness probes must
                                flag without any TCP disconnect. One-shot
    traffic_wave=40:20          serve traffic wave: the open-loop load
                                generator ramps its request rate in a
                                triangle wave peaking at 40 req/s with a
                                20 s period — the injectable diurnal peak
                                that drives pool borrow/return cycles
                                without a real client fleet. Like
                                join_host, the ``@`` segment is a poll
                                delay: ``traffic_wave=40:20@3`` stays at
                                baseline for 3 polls first. NON-consuming
                                after activation; activation is
                                flight-recorded once
    spec_misdraft=0.5           speculative-decode fault: each draft
                                token the serve-plane drafter proposes
                                is replaced with a deliberately wrong
                                one with probability 0.5 — acceptance
                                collapses and the verify/rollback path
                                runs hot, but the OUTPUT must stay
                                byte-identical (greedy acceptance
                                discards the junk, rollback rewinds its
                                KV). ``spec_misdraft=0.5@3`` poisons
                                only requests from admission ordinal 3
                                on. NON-consuming after activation;
                                activation is flight-recorded once

Barriers are explicit calls (``chaos().barrier("step_end", ip=...)``)
placed at recovery-relevant points: worker start, step start/end, and
``ckpt_mid_write`` — between the checkpoint writer's shard-data rename
and its manifest write (ckpt/writer.py), the exact window where a kill
leaves a torn checkpoint the restore path must quarantine. The ``@ip``
filter selects a victim in a cluster whose processes share one
environment; directives without ``@ip`` match every process.

Inactive chaos (no env var) costs one attribute read per hook — the
layer is safe to leave compiled into production paths.
"""

from __future__ import annotations

import logging
import os
import signal
import time
from dataclasses import dataclass

logger = logging.getLogger("oobleck.chaos")

ENV_VAR = "OOBLECK_CHAOS"

_KNOWN_ACTIONS = ("delay_send", "drop_send", "stall_heartbeat", "kill_at",
                  "delay_at", "kill_stage", "flap_host", "kill_hosts",
                  "preempt_notice", "join_host", "join_hosts",
                  "spot_lifetime", "kill_master", "partition_master",
                  "slow_host", "traffic_wave", "kill_replica",
                  "hang_replica", "spec_misdraft")


@dataclass
class Rule:
    action: str           # one of _KNOWN_ACTIONS
    arg: str              # seconds / message kind / barrier name / count
    qual: str | None      # ordinal (drop/kill) or kind filter (delay)
    ip: str | None        # restrict to processes reporting this host ip

    def matches_ip(self, ip: str | None) -> bool:
        return self.ip is None or self.ip == ip

    @property
    def nth(self) -> int | None:
        return int(self.qual) if self.qual else None


def parse_spec(spec: str) -> list[Rule]:
    rules: list[Rule] = []
    for directive in spec.split(","):
        directive = directive.strip()
        if not directive:
            continue
        action, sep, payload = directive.partition("=")
        if not sep or action not in _KNOWN_ACTIONS:
            raise ValueError(
                f"bad chaos directive {directive!r}: want "
                f"action=arg[:qual][@ip] with action in {_KNOWN_ACTIONS}"
            )
        payload, _, ip = payload.partition("@")
        arg, _, qual = payload.partition(":")
        rule = Rule(action=action, arg=arg, qual=qual or None, ip=ip or None)
        # Validate eagerly: a typo'd injection spec must fail the test run
        # at parse time, not silently inject nothing.
        if action == "delay_send":
            float(rule.arg)
        elif action == "delay_at":
            float(rule.qual or 0)  # delay_at=<barrier>:<seconds>
        elif action == "stall_heartbeat":
            int(rule.arg or 0)
        elif action == "kill_stage":
            int(rule.arg)           # kill_stage=<stage>:<replica>
            int(rule.qual or 0)
        elif action == "flap_host":
            if not rule.arg:        # flap_host=<ip>:<period>
                raise ValueError(f"flap_host needs a host ip: {directive!r}")
            if float(rule.qual or 0) <= 0:
                raise ValueError(
                    f"flap_host needs a positive period: {directive!r}")
        elif action == "kill_hosts":
            if not all(p for p in rule.arg.split("+")) or not rule.arg:
                raise ValueError(
                    f"kill_hosts needs '+'-joined host ips: {directive!r}")
        elif action == "preempt_notice":
            if float(rule.arg) <= 0:  # preempt_notice=<secs>[:<delay>]@ip
                raise ValueError(
                    f"preempt_notice needs positive seconds: {directive!r}")
            float(rule.qual or 0)
            if not rule.ip:
                raise ValueError(
                    f"preempt_notice needs a victim @ip: {directive!r}")
        elif action == "join_host":
            if not rule.arg:        # join_host=<ip>[@<step-delay>]
                raise ValueError(
                    f"join_host needs a joining ip: {directive!r}")
            int(rule.ip or 0)       # @segment = step-boundary delay
        elif action == "join_hosts":
            if not rule.arg or not all(p for p in rule.arg.split("+")):
                raise ValueError(
                    f"join_hosts needs '+'-joined host ips: {directive!r}")
            int(rule.ip or 0)
        elif action == "spot_lifetime":
            if not rule.arg:        # spot_lifetime=<ip>:<secs>
                raise ValueError(
                    f"spot_lifetime needs a host ip: {directive!r}")
            if float(rule.qual or 0) <= 0:
                raise ValueError(
                    f"spot_lifetime needs positive seconds: {directive!r}")
        elif action == "kill_master":
            if float(rule.arg) <= 0:  # kill_master=<after_s>[:<restart_s>]
                raise ValueError(
                    f"kill_master needs positive seconds: {directive!r}")
            float(rule.qual or 0)
        elif action == "partition_master":
            if not rule.arg:        # partition_master=<ip>:<secs>
                raise ValueError(
                    f"partition_master needs an agent ip: {directive!r}")
            if float(rule.qual or 0) <= 0:
                raise ValueError(
                    f"partition_master needs positive seconds: {directive!r}")
        elif action == "slow_host":
            if not rule.arg:        # slow_host=<ip>:<factor>[@<step>]
                raise ValueError(
                    f"slow_host needs a victim ip: {directive!r}")
            if float(rule.qual or 0) <= 1.0:
                raise ValueError(
                    f"slow_host needs a factor > 1.0: {directive!r}")
            int(rule.ip or 0)       # @segment = step-boundary delay
        elif action == "traffic_wave":
            if float(rule.arg) <= 0:  # traffic_wave=<peak_rps>:<period_s>[@<poll>]
                raise ValueError(
                    f"traffic_wave needs a positive peak rps: {directive!r}")
            if float(rule.qual or 0) <= 0:
                raise ValueError(
                    f"traffic_wave needs a positive period: {directive!r}")
            int(rule.ip or 0)       # @segment = poll delay
        elif action == "kill_replica":
            if int(rule.arg) <= 0:  # kill_replica=<port>[@<req>]
                raise ValueError(
                    f"kill_replica needs a replica port: {directive!r}")
            if int(rule.ip or 1) < 1:  # @segment = request ordinal
                raise ValueError(
                    f"kill_replica ordinal must be >= 1: {directive!r}")
        elif action == "hang_replica":
            if int(rule.arg) <= 0:  # hang_replica=<port>:<secs>
                raise ValueError(
                    f"hang_replica needs a replica port: {directive!r}")
            if float(rule.qual or 0) <= 0:
                raise ValueError(
                    f"hang_replica needs positive seconds: {directive!r}")
        elif action == "spec_misdraft":
            rate = float(rule.arg)  # spec_misdraft=<rate>[@<req>]
            if not 0.0 < rate <= 1.0:
                raise ValueError(
                    f"spec_misdraft rate must be in (0, 1]: {directive!r}")
            if int(rule.ip or 1) < 1:  # @segment = request ordinal
                raise ValueError(
                    f"spec_misdraft ordinal must be >= 1: {directive!r}")
        elif rule.qual is not None:
            int(rule.qual)
        rules.append(rule)
    return rules


class Chaos:
    """Parsed chaos directives + per-rule event counters for one process."""

    def __init__(self, spec: str | None = None):
        if spec is None:
            spec = os.environ.get(ENV_VAR, "")
        self.rules = parse_spec(spec)
        self.active = bool(self.rules)
        self._counts: dict[int, int] = {}

    def _count(self, rule: Rule) -> int:
        i = self.rules.index(rule)
        self._counts[i] = self._counts.get(i, 0) + 1
        return self._counts[i]

    # -- control-plane message hooks (wired into message.send_msg) ------- #

    def send_delay(self, kind: str) -> float:
        """Seconds to sleep before sending a message of `kind`."""
        return sum(
            float(r.arg) for r in self.rules
            if r.action == "delay_send" and r.qual in (None, kind)
        )

    def drop_send(self, kind: str) -> bool:
        """Whether to silently drop a message of `kind` (counts events)."""
        for r in self.rules:
            if r.action == "drop_send" and r.arg == kind:
                n = self._count(r)
                if r.nth is None or n == r.nth:
                    logger.warning("chaos: dropping %s message", kind)
                    from oobleck_tpu.utils import metrics

                    metrics.flight_recorder().record(
                        "chaos_injection", action="drop_send", kind=kind,
                        hit=n)
                    return True
        return False

    # -- heartbeat stall -------------------------------------------------- #

    def heartbeat_stalled(self, ip: str | None) -> bool:
        """True once this process's heartbeat should go silent. The socket
        stays open — only the periodic traffic stops, which is exactly the
        failure mode a `timeout=None` read never detects."""
        for r in self.rules:
            if r.action == "stall_heartbeat" and r.matches_ip(ip):
                if self._count(r) > int(r.arg or 0):
                    return True
        return False

    # -- stage-addressed kill ---------------------------------------------- #

    def kill_stage_target(self) -> tuple[int, int] | None:
        """One-shot (stage, replica) of a pending stage-addressed kill,
        or None. Consuming: each kill_stage rule fires exactly once — the
        injected failure kills the host, and a dead host cannot die again.
        The caller (the engine's step loop) resolves which host owns that
        stage and synthesizes the loss."""
        for r in self.rules:
            if r.action != "kill_stage":
                continue
            i = self.rules.index(r)
            if self._counts.get(i, 0):
                continue
            self._counts[i] = 1
            stage, replica = int(r.arg), int(r.qual or 0)
            logger.warning(
                "chaos: stage-addressed kill of stage %d replica %d",
                stage, replica)
            from oobleck_tpu.utils import metrics

            metrics.flight_recorder().record(
                "chaos_injection", action="kill_stage", stage=stage,
                replica=replica)
            return stage, replica
        return None

    # -- churn directives (policy-plane faults) ----------------------------- #

    def flap_period(self, ip: str | None) -> float | None:
        """Seconds between connection flaps for this host, or None if no
        flap_host rule targets it. The agent owns the flap loop; this is
        read once at startup (flight-recorded on first read only)."""
        for r in self.rules:
            if r.action == "flap_host" and r.arg == ip:
                period = float(r.qual or 0)
                i = self.rules.index(r)
                if not self._counts.get(i):
                    self._counts[i] = 1
                    logger.warning(
                        "chaos: host %s will flap every %.2fs", ip, period)
                    from oobleck_tpu.utils import metrics

                    metrics.flight_recorder().record(
                        "chaos_injection", action="flap_host", ip=ip,
                        period=period)
                return period
        return None

    def kill_hosts_target(self) -> list[str] | None:
        """One-shot list of hosts to declare lost in the SAME step boundary
        (correlated failure), or None. Consuming, like kill_stage_target:
        dead hosts cannot die again."""
        for r in self.rules:
            if r.action != "kill_hosts":
                continue
            i = self.rules.index(r)
            if self._counts.get(i, 0):
                continue
            self._counts[i] = 1
            ips = [p for p in r.arg.split("+") if p]
            logger.warning("chaos: correlated kill of hosts %s", ips)
            from oobleck_tpu.utils import metrics

            metrics.flight_recorder().record(
                "chaos_injection", action="kill_hosts", ips=ips)
            return ips
        return None

    def preempt_notice(self, ip: str | None) -> tuple[float, float] | None:
        """One-shot (warn_seconds, startup_delay_seconds) if this host has a
        pending spot-preemption injection, else None. The agent sends the
        advance notice after the startup delay, then dies warn_seconds
        later — the window proactive drain + checkpoint flush must fit
        inside. Consuming."""
        for r in self.rules:
            if r.action != "preempt_notice" or not r.matches_ip(ip):
                continue
            i = self.rules.index(r)
            if self._counts.get(i, 0):
                continue
            self._counts[i] = 1
            warn, delay = float(r.arg), float(r.qual or 0)
            logger.warning(
                "chaos: preemption notice on %s in %.2fs, death %.2fs later",
                ip, delay, warn)
            from oobleck_tpu.utils import metrics

            metrics.flight_recorder().record(
                "chaos_injection", action="preempt_notice", ip=ip,
                warn_seconds=warn, delay_seconds=delay)
            return warn, delay
        return None

    # -- capacity arrivals (grow-plane faults) ------------------------------ #

    def join_targets(self) -> list[str] | None:
        """One-shot list of hosts ARRIVING at this step boundary, or None.

        The engine polls once per step; a join_host rule with ``@<delay>``
        fires on poll number delay+1 (deterministic down to the step).
        Several rules maturing at the same poll — or one join_hosts rule —
        return together: a correlated arrival the master-side batching
        window must fold into ONE grow incident. Consuming per rule."""
        arrived: list[str] = []
        for r in self.rules:
            if r.action not in ("join_host", "join_hosts"):
                continue
            i = self.rules.index(r)
            n = self._counts.get(i, 0)
            if n < 0:
                continue  # already fired
            delay = int(r.ip or 0)
            if n < delay:
                self._counts[i] = n + 1
                continue
            self._counts[i] = -1
            arrived.extend(p for p in r.arg.split("+") if p)
        if not arrived:
            return None
        logger.warning("chaos: hosts %s arriving (JOIN)", arrived)
        from oobleck_tpu.utils import metrics

        metrics.flight_recorder().record(
            "chaos_injection", action="join_host", ips=arrived)
        return arrived

    def spot_lifetime(self, ip: str | None) -> float | None:
        """Expected lifetime (seconds) of arriving spot host `ip`, or None
        when no spot_lifetime rule names it. NON-consuming: the policy
        scorer reads it per decision as the amortization horizon, and the
        engine reads it once more when admitting the host to arm the
        deferred synthetic loss."""
        for r in self.rules:
            if r.action == "spot_lifetime" and r.arg == ip:
                return float(r.qual or 0)
        return None

    # -- control-plane outage faults --------------------------------------- #

    def kill_master_after(self) -> tuple[float, float | None] | None:
        """One-shot (kill_after_s, restart_after_s|None) if a kill_master
        rule is pending, else None. The MASTER reads this at startup and
        schedules its own SIGKILL; restart_after_s is advisory — the
        master cannot restart itself, so the test harness reads the
        same rule (non-consumed, different process) to time the restart.
        Consuming within a process: a master only dies once."""
        for r in self.rules:
            if r.action != "kill_master":
                continue
            i = self.rules.index(r)
            if self._counts.get(i, 0):
                continue
            self._counts[i] = 1
            after = float(r.arg)
            restart = float(r.qual) if r.qual else None
            logger.warning(
                "chaos: master will SIGKILL itself in %.2fs%s", after,
                f" (harness restart advised after {restart:.2f}s)"
                if restart is not None else "")
            from oobleck_tpu.utils import metrics

            metrics.flight_recorder().record(
                "chaos_injection", action="kill_master",
                after_seconds=after, restart_seconds=restart)
            return after, restart
        return None

    def partition_master_secs(self, ip: str | None) -> float | None:
        """One-shot partition length (seconds) for agent `ip`, or None when
        no partition_master rule names it. The agent severs its master
        link and suppresses redial for that long — the masterless-mode
        fault where the master never died. Consuming."""
        for r in self.rules:
            if r.action != "partition_master" or r.arg != ip:
                continue
            i = self.rules.index(r)
            if self._counts.get(i, 0):
                continue
            self._counts[i] = 1
            return float(r.qual or 0)
        return None

    # -- gray failure (straggler fault) ------------------------------------- #

    def slow_factor(self, ip: str | None) -> float | None:
        """Per-step slowdown factor for host `ip` once its slow_host rule
        has activated, else None. The engine polls once per step; a rule
        with ``@<step>`` activates on poll number step+1 (deterministic,
        like join_targets). NON-consuming after activation — a gray-
        failing host stays slow until something drains it; the activation
        is flight-recorded once."""
        for r in self.rules:
            if r.action != "slow_host" or r.arg != ip:
                continue
            i = self.rules.index(r)
            n = self._counts.get(i, 0)
            if n >= 0:
                delay = int(r.ip or 0)
                if n < delay:
                    self._counts[i] = n + 1
                    return None
                self._counts[i] = -1  # active from here on
                factor = float(r.qual or 0)
                logger.warning(
                    "chaos: host %s now runs %.2fx slow (gray failure)",
                    ip, factor)
                from oobleck_tpu.utils import metrics

                metrics.flight_recorder().record(
                    "chaos_injection", action="slow_host", ip=ip,
                    factor=factor)
            return float(r.qual or 0)
        return None

    # -- serve traffic wave (pool-plane fault) ------------------------------ #

    def traffic_wave(self) -> tuple[float, float] | None:
        """(peak_rps, period_s) of the injected serve traffic wave once its
        rule has activated, else None. The load generator polls once per
        tick; a rule with ``@<poll>`` activates on poll number poll+1
        (deterministic, like slow_factor). NON-consuming after activation
        — the wave keeps oscillating until the run ends; the activation is
        flight-recorded once."""
        for r in self.rules:
            if r.action != "traffic_wave":
                continue
            i = self.rules.index(r)
            n = self._counts.get(i, 0)
            if n >= 0:
                delay = int(r.ip or 0)
                if n < delay:
                    self._counts[i] = n + 1
                    return None
                self._counts[i] = -1  # active from here on
                peak, period = float(r.arg), float(r.qual or 0)
                logger.warning(
                    "chaos: serve traffic wave active (peak %.1f rps, "
                    "period %.1fs)", peak, period)
                from oobleck_tpu.utils import metrics

                metrics.flight_recorder().record(
                    "chaos_injection", action="traffic_wave",
                    peak_rps=peak, period_s=period)
            return float(r.arg), float(r.qual or 0)
        return None

    # -- serving-replica faults (router-plane) ------------------------------ #

    def kill_replica_now(self, port: int) -> bool:
        """True exactly once, on the request whose ordinal a kill_replica
        rule for this port names (first request when no ``@<req>``): the
        replica's HTTP server dies mid-request — the in-flight connection
        aborts with no response and the port stops accepting, which is
        the failover the router must absorb. Call per /v1/generate
        request; counts requests per rule; consuming (a dead replica
        cannot die again)."""
        for r in self.rules:
            if r.action != "kill_replica" or int(r.arg) != int(port):
                continue
            i = self.rules.index(r)
            n = self._counts.get(i, 0)
            if n < 0:
                continue  # already fired
            n += 1
            ordinal = int(r.ip or 1)
            if n < ordinal:
                self._counts[i] = n
                continue
            self._counts[i] = -1
            logger.warning("chaos: killing replica :%d at request %d",
                           int(port), n)
            from oobleck_tpu.utils import metrics

            metrics.flight_recorder().record(
                "chaos_injection", action="kill_replica", port=int(port),
                request=n)
            return True
        return False

    def hang_replica_secs(self, port: int) -> float | None:
        """One-shot hang length (seconds) for the serving replica on
        `port`, or None. The replica's handler sleeps that long before
        answering — the alive-but-unresponsive replica a liveness probe
        must flag without a TCP disconnect ever firing. Consuming."""
        for r in self.rules:
            if r.action != "hang_replica" or int(r.arg) != int(port):
                continue
            i = self.rules.index(r)
            if self._counts.get(i, 0):
                continue
            self._counts[i] = 1
            secs = float(r.qual or 0)
            logger.warning("chaos: hanging replica :%d for %.2fs",
                           int(port), secs)
            from oobleck_tpu.utils import metrics

            metrics.flight_recorder().record(
                "chaos_injection", action="hang_replica", port=int(port),
                seconds=secs)
            return secs
        return None

    # -- speculative-decode faults (serve hot path) ------------------------- #

    def spec_misdraft_rate(self, request_ordinal: int = 1) -> float | None:
        """Probability each DRAFT token is replaced with a deliberately
        wrong one, once a spec_misdraft rule applies to this request —
        else None. ``@<req>`` restricts the fault to requests with
        admission ordinal >= req (default 1 = every request), so a run
        can serve clean traffic first and then misdraft. NON-consuming
        after activation — every subsequent draft stays poisoned (the
        rollback path must hold up under sustained rejection, not one
        bad step); the activation is flight-recorded once. Correctness
        must be unaffected: greedy acceptance discards the wrong tokens
        and rollback rewinds their KV, so the OUTPUT stays byte-identical
        — only acceptance-rate/goodput metrics should move."""
        for r in self.rules:
            if r.action != "spec_misdraft":
                continue
            if int(request_ordinal) < int(r.ip or 1):
                return None
            i = self.rules.index(r)
            rate = float(r.arg)
            if self._counts.get(i, 0) >= 0:
                self._counts[i] = -1  # active from here on
                logger.warning(
                    "chaos: misdrafting %.0f%% of speculative draft tokens "
                    "from request %d", rate * 100.0, int(request_ordinal))
                from oobleck_tpu.utils import metrics

                metrics.flight_recorder().record(
                    "chaos_injection", action="spec_misdraft", rate=rate,
                    request=int(request_ordinal))
            return rate
        return None

    # -- named barriers ---------------------------------------------------- #

    def barrier_delay(self, name: str, ip: str | None = None) -> float:
        """Seconds a matching delay_at rule injects at this barrier (the
        caller sleeps — slow-reload / slow-I/O fault). Counts events."""
        total = 0.0
        for r in self.rules:
            if r.action == "delay_at" and r.arg == name and r.matches_ip(ip):
                total += float(r.qual or 0)
        if total > 0:
            logger.warning("chaos: delaying %.3fs at barrier %s", total, name)
            from oobleck_tpu.utils import metrics

            metrics.flight_recorder().record(
                "chaos_injection", action="delay_at", barrier=name,
                seconds=total)
        return total

    def barrier(self, name: str, ip: str | None = None) -> None:
        """Hit a named barrier; a matching kill_at rule SIGKILLs the process
        (no cleanup, no atexit — the honest worker-crash fault). Matching
        delay_at rules sleep here before any kill check."""
        delay = self.barrier_delay(name, ip)
        if delay > 0:
            time.sleep(delay)
        for r in self.rules:
            if r.action != "kill_at" or r.arg != name or not r.matches_ip(ip):
                continue
            n = self._count(r)
            if r.nth is None or n == r.nth:
                logger.warning(
                    "chaos: killing worker at barrier %s (hit %d, pid %d)",
                    name, n, os.getpid(),
                )
                # Persist the victim's flight recorder while we still can:
                # SIGKILL leaves no other trace of the injection in the
                # postmortem artifacts.
                from oobleck_tpu.utils import metrics

                metrics.flight_recorder().record(
                    "chaos_injection", action="kill_at", barrier=name,
                    hit=n, ip=ip, pid=os.getpid())
                metrics.flight_recorder().dump(f"chaos_kill_at:{name}")
                logging.shutdown()
                os.kill(os.getpid(), signal.SIGKILL)
                time.sleep(60)  # SIGKILL delivery is async; never proceed


_instance: Chaos | None = None


def chaos() -> Chaos:
    """Process-global chaos config, parsed from OOBLECK_CHAOS on first use."""
    global _instance
    if _instance is None:
        _instance = Chaos()
    return _instance


def reset(spec: str | None = None) -> Chaos:
    """Re-parse (tests monkeypatch the env then call this)."""
    global _instance
    _instance = Chaos(spec)
    return _instance
