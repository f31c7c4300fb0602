"""Live per-mechanism signals the scorer consumes.

Each recovery mechanism becomes one ArmSignals record: its expected
recovery latency (measured history when the metrics plane has any,
documented priors otherwise — the source is carried so decisions are
honest about what they knew), its projected post-recovery throughput
retention, the work a checkpoint restore would replay, and feasibility
(a reroute around two correlated losses, or a restore with no durable
checkpoint, is not an option however cheap it looks).

Priors come in two flavors, and every arm records which one it used
(``prior_source``): the hardcoded PRIOR_LATENCY_S table below, or a
``learned_priors.json`` fitted from the incident corpus by
``oobleck_tpu.sim.priors`` and activated via ``$OOBLECK_POLICY_PRIORS``
(or an explicit ``priors_path``) — so a decision made from fitted priors
is distinguishable in forensics from one made from the shipped table.
"""

from __future__ import annotations

import json
import logging
import os
from dataclasses import dataclass, field

from oobleck_tpu.utils import metrics

logger = logging.getLogger("oobleck.policy")

# Latency priors (seconds) used until a mechanism has measured history.
# reroute/reinstantiate-warm come from a two-host CPU rig (~0.56 s / ~0.64 s,
# rounded up); reinstantiate-respawn and restore from the multiprocess
# recovery runs (~21 s respawn; restore adds durable read + re-instantiation
# on top). None was taken on a TPU: measured history replaces them.
PRIOR_LATENCY_S = {
    "reroute": 0.6,
    "reinstantiate": 0.7,          # warm in-place re-instantiation
    "reinstantiate_respawn": 21.0,  # multihost: respawn + re-init
    "restore": 25.0,
    # Grow-direction arms (JOIN incidents). absorb_spare only appends to
    # the spare pool (bookkeeping, no topology change); grow_dp is a warm
    # re-materialization at unchanged template size (one extra replica);
    # grow_reshape is a restore-across-reshape — durable read + larger-
    # template re-instantiation, priced like restore plus the re-match.
    "absorb_spare": 0.05,
    "grow_dp": 1.0,
    "grow_reshape": 26.0,
    # Slowdown-direction arms (SLOWDOWN incidents — a host alive but
    # persistently slow, PR 17). observe changes nothing (the cost is the
    # throughput the straggler keeps gating); drain/quarantine are a
    # proactive checkpoint-flush + reroute around a host that is still
    # able to flush cleanly — priced like a preemption drain, not like
    # recovering from a corpse.
    "observe": 0.0,
    "drain": 2.0,
    "quarantine": 2.0,
    # Pool-arbitration arms (cross-tenant borrow/reclaim incidents,
    # pool/arbiter.py). deny/hold change nothing (their cost is the SLO
    # debt the pressured tenant keeps paying); borrow_spare hands over
    # parked capacity (bookkeeping); borrow_drain preempts a training
    # host through the proactive drain + checkpoint flush (priced like
    # the slowdown drain plus serve-side attach); reclaim_grow returns
    # leased chips to training through the JOIN/grow path.
    "deny": 0.0,
    "borrow_spare": 0.1,
    "borrow_drain": 2.5,
    "hold": 0.0,
    "reclaim_grow": 1.2,
}
# Step-time prior when no measured step seconds are available yet (only
# used to price checkpoint staleness in lost-work seconds).
PRIOR_STEP_S = 1.0

# A drained straggler is readmitted once healthy; when its own MTBF is
# shorter than this horizon, the readmission is expected to cost another
# drain within it — the hazard that prices quarantine ahead of drain for
# a host that keeps failing (mirrors scorer.RISK_HORIZON_S, duplicated
# here because the scorer imports this module).
READMIT_HORIZON_S = 60.0

# Histogram families that hold measured recovery latencies by mechanism.
_LATENCY_HISTOGRAMS = (
    "oobleck_degrade_recovery_seconds",
    "oobleck_policy_measured_recovery_seconds",
)

# Path to a learned_priors.json fitted from the incident corpus (see
# oobleck_tpu/sim/priors.py); unset means the hardcoded table above.
ENV_PRIORS = "OOBLECK_POLICY_PRIORS"
# The priors-file format version this loader understands.
PRIORS_VERSION = 1

# (path, mtime) -> parsed latency table, so build_arms on the decision hot
# path never re-reads an unchanged file.
_priors_cache: dict = {"path": None, "mtime": None, "latency": None}


def learned_priors(path: str | None = None) -> tuple[dict, str] | None:
    """(latency_s table, "learned:<path>") from an explicit ``path`` or
    ``$OOBLECK_POLICY_PRIORS``; None when unset, unreadable, or of an
    unknown version (logged once per file change, never raised — a bad
    priors file must not take down the decision path)."""
    path = path or os.environ.get(ENV_PRIORS, "").strip() or None
    if not path:
        return None
    try:
        mtime = os.stat(path).st_mtime
    except OSError:
        return None
    if _priors_cache["path"] == path and _priors_cache["mtime"] == mtime:
        lat = _priors_cache["latency"]
        return (lat, f"learned:{path}") if lat else None
    _priors_cache.update(path=path, mtime=mtime, latency=None)
    try:
        with open(path) as f:
            rec = json.load(f)
    except (OSError, ValueError) as e:
        logger.warning("policy: cannot read priors file %s: %s", path, e)
        return None
    if not isinstance(rec, dict) or rec.get("version") != PRIORS_VERSION:
        logger.warning("policy: skipping priors file %s: unknown version %r",
                       path, rec.get("version") if isinstance(rec, dict)
                       else type(rec).__name__)
        return None
    latency = {k: float(v) for k, v in (rec.get("latency_s") or {}).items()
               if isinstance(v, (int, float)) and v > 0}
    if not latency:
        logger.warning("policy: priors file %s has no usable latency_s", path)
        return None
    _priors_cache["latency"] = latency
    return latency, f"learned:{path}"


def priors_provenance(path: str | None = None) -> dict:
    """Which priors the next decision would fall back to — surfaced in the
    /status policy block so fitted-priors deployments are visible."""
    lp = learned_priors(path)
    if lp is not None:
        return {"source": lp[1], "mechanisms": sorted(lp[0])}
    return {"source": "hardcoded", "mechanisms": sorted(PRIOR_LATENCY_S)}


@dataclass
class ArmSignals:
    """Everything the scorer needs to know about one recovery mechanism
    for one incident."""

    mechanism: str
    latency_s: float
    latency_source: str            # "measured" | "prior"
    retention: float               # projected throughput after recovery
    lost_work_s: float = 0.0       # replayed work (checkpoint restore)
    in_memory: bool = True         # state survives in RAM -> churn risk
    feasible: bool = True
    reason: str = ""               # why infeasible ("" when feasible)
    prior_source: str = ""         # "hardcoded" | "learned:<path>" | ""
    # Cross-tenant terms (pool arbitration; zero on single-tenant arms).
    # slo_debt_s rides arms that leave a pressured tenant's SLO unrelieved
    # (deny a borrow, reclaim under live pressure); preempt_cost_s rides
    # arms that take running capacity away from a tenant (borrow_drain).
    slo_debt_s: float = 0.0
    preempt_cost_s: float = 0.0

    def as_record(self) -> dict:
        return {
            "latency_s": round(self.latency_s, 6),
            "latency_source": self.latency_source,
            "prior_source": self.prior_source,
            "retention": round(self.retention, 6),
            "lost_work_s": round(self.lost_work_s, 6),
            "slo_debt_s": round(self.slo_debt_s, 6),
            "preempt_cost_s": round(self.preempt_cost_s, 6),
            "feasible": self.feasible,
            "reason": self.reason,
        }


def measured_latency(mechanism: str, registry=None) -> float | None:
    """Mean measured recovery latency for a mechanism across the metric
    families that observe it, or None with no history."""
    reg = registry or metrics.registry()
    total = count = 0.0
    for name in _LATENCY_HISTOGRAMS:
        # Reads families registered (literally) elsewhere; the loop
        # variable is what makes the name dynamic here.
        # oobleck: allow[OBL005] -- iterates the registered name list
        for s in reg.histogram(name, "").series():
            if s["labels"].get("mechanism") == mechanism and s["count"]:
                total += s["sum"]
                count += s["count"]
    return total / count if count else None


def _latency(mechanism: str, prior_key: str, overrides, registry,
             priors_path=None):
    """(seconds, latency_source, prior_source). Measurement always wins
    (EWMA override, then histogram history); the prior fallback prefers a
    corpus-fitted table over the hardcoded one and names which it used."""
    if overrides and mechanism in overrides:
        return float(overrides[mechanism]), "measured", ""
    m = measured_latency(mechanism, registry)
    if m is not None:
        return m, "measured", ""
    lp = learned_priors(priors_path)
    if lp is not None and prior_key in lp[0]:
        return lp[0][prior_key], "prior", lp[1]
    return PRIOR_LATENCY_S[prior_key], "prior", "hardcoded"


def build_arms(*,
               multihost: bool = False,
               warm_reinstantiate: bool | None = None,
               degrade_enabled: bool = True,
               correlated: bool = False,
               reroute_retention: float | None = None,
               reroute_feasible: bool = True,
               reroute_reason: str = "",
               survivor_frac: float = 1.0,
               staleness_steps: float | None = None,
               step_seconds: float | None = None,
               latency_overrides: dict[str, float] | None = None,
               registry=None,
               priors_path: str | None = None) -> dict[str, ArmSignals]:
    """Assemble the three arms for one incident.

    staleness_steps is None when there is no durable checkpoint (restore
    infeasible), else current_step - last_durable_step. reroute_retention
    is the degrade planner's replay-projected survivor throughput when a
    projection exists; survivor_frac ((n-lost)/n) is the fallback for it
    and the default for the other in-memory arm — re-instantiated
    templates run on the same survivors, so absent measurements the arms
    are not fabricated apart on retention.
    """
    if warm_reinstantiate is None:
        warm_reinstantiate = not multihost

    reroute = ArmSignals(
        mechanism="reroute",
        latency_s=0.0, latency_source="",
        retention=(reroute_retention if reroute_retention is not None
                   else survivor_frac),
    )
    reroute.latency_s, reroute.latency_source, reroute.prior_source = \
        _latency("reroute", "reroute", latency_overrides, registry,
                 priors_path)
    if not degrade_enabled:
        reroute.feasible, reroute.reason = False, "degrade_disabled"
    elif correlated:
        reroute.feasible, reroute.reason = False, "correlated_failure"
    elif not reroute_feasible:
        reroute.feasible, reroute.reason = False, (reroute_reason
                                                   or "reroute_infeasible")
    reinst = ArmSignals(
        mechanism="reinstantiate",
        latency_s=0.0, latency_source="",
        retention=survivor_frac,
    )
    reinst.latency_s, reinst.latency_source, reinst.prior_source = _latency(
        "reinstantiate",
        "reinstantiate" if warm_reinstantiate else "reinstantiate_respawn",
        latency_overrides, registry, priors_path)

    restore = ArmSignals(
        mechanism="restore",
        latency_s=0.0, latency_source="",
        retention=survivor_frac,
        in_memory=False,
    )
    restore.latency_s, restore.latency_source, restore.prior_source = \
        _latency("restore", "restore", latency_overrides, registry,
                 priors_path)
    if staleness_steps is None:
        restore.feasible, restore.reason = False, "no_durable_checkpoint"
    else:
        restore.lost_work_s = max(float(staleness_steps), 0.0) * (
            step_seconds if step_seconds else PRIOR_STEP_S)
    return {"reroute": reroute, "reinstantiate": reinst, "restore": restore}


def build_grow_arms(*,
                    joined_count: int,
                    current_hosts: int,
                    dp_feasible: bool = True,
                    dp_reason: str = "",
                    staleness_steps: float | None = None,
                    step_seconds: float | None = None,
                    latency_overrides: dict[str, float] | None = None,
                    registry=None,
                    priors_path: str | None = None) -> dict[str, ArmSignals]:
    """Assemble the three GROW arms for one JOIN incident.

    Retention is measured against the POST-grow throughput ceiling: the
    scorer's degraded term then prices the gain an arm forgoes by not
    absorbing the arrivals, with the same amortization horizon a shrink
    decision uses — except here the horizon is the arriving host's
    expected LIFETIME (a spot host that will vanish in 30 s cannot
    amortize a 26 s reshape, so absorb_spare wins; a long-lived arrival
    flips it). The in_memory flag keeps the churn hedge: grow_dp and
    grow_reshape commit live state onto the newcomer, so its early death
    schedules the next recovery; parking a spare risks nothing.

    ``dp_feasible`` is the planner's verdict on whether the arrivals can
    form a whole extra replica of an already-instantiated template size;
    ``staleness_steps`` prices grow_reshape's restore-across-reshape
    rollback (None = no durable checkpoint: the reshape falls back to a
    live-state re-instantiation, which replays nothing).
    """
    n, k = max(int(current_hosts), 0), max(int(joined_count), 0)
    kept = (n / (n + k)) if (n + k) else 1.0

    absorb = ArmSignals(
        mechanism="absorb_spare",
        latency_s=0.0, latency_source="",
        retention=kept,
        in_memory=False,
    )
    absorb.latency_s, absorb.latency_source, absorb.prior_source = _latency(
        "absorb_spare", "absorb_spare", latency_overrides, registry,
        priors_path)

    grow_dp = ArmSignals(
        mechanism="grow_dp",
        latency_s=0.0, latency_source="",
        retention=1.0,
    )
    grow_dp.latency_s, grow_dp.latency_source, grow_dp.prior_source = \
        _latency("grow_dp", "grow_dp", latency_overrides, registry,
                 priors_path)
    if not dp_feasible:
        grow_dp.feasible, grow_dp.reason = False, (dp_reason
                                                   or "no_template_fit")

    reshape = ArmSignals(
        mechanism="grow_reshape",
        latency_s=0.0, latency_source="",
        retention=1.0,
    )
    reshape.latency_s, reshape.latency_source, reshape.prior_source = \
        _latency("grow_reshape", "grow_reshape", latency_overrides,
                 registry, priors_path)
    if staleness_steps is not None:
        reshape.lost_work_s = max(float(staleness_steps), 0.0) * (
            step_seconds if step_seconds else PRIOR_STEP_S)
    return {"absorb_spare": absorb, "grow_dp": grow_dp,
            "grow_reshape": reshape}


def build_slowdown_arms(*,
                        slowdown_ratio: float,
                        survivor_frac: float,
                        host_mtbf_s: float | None = None,
                        host_failures: int = 0,
                        latency_overrides: dict[str, float] | None = None,
                        registry=None,
                        priors_path: str | None = None
                        ) -> dict[str, ArmSignals]:
    """Assemble the three SLOWDOWN arms for one gray-failure incident.

    A straggler gates the whole synchronous fleet, so *observe* retains
    ``1/slowdown_ratio`` of throughput — and keeps live state on a host
    whose degradation usually precedes death (``in_memory=True``: the
    scorer's churn term prices exactly that hazard, rising with the sick
    host's worsening MTBF — the drain-before-it-dies signal). *drain*
    flushes a checkpoint on the way out (``in_memory=False``: nothing is
    left at risk) and runs the survivors at full speed, paying
    ``survivor_frac`` retention for the lost capacity; a drained host
    with a short MTBF is expected to be readmitted and drained again
    within READMIT_HORIZON_S, priced as ``lost_work_s``. *quarantine* is
    drain plus barring readmission — feasible only for a host with
    observed failure history (quarantining a first-time straggler on
    telemetry alone would be acting on one signal)."""
    ratio = max(float(slowdown_ratio), 1.0)

    observe = ArmSignals(
        mechanism="observe",
        latency_s=0.0, latency_source="",
        retention=1.0 / ratio,
    )
    observe.latency_s, observe.latency_source, observe.prior_source = \
        _latency("observe", "observe", latency_overrides, registry,
                 priors_path)

    drain = ArmSignals(
        mechanism="drain",
        latency_s=0.0, latency_source="",
        retention=survivor_frac,
        in_memory=False,
    )
    drain.latency_s, drain.latency_source, drain.prior_source = _latency(
        "drain", "drain", latency_overrides, registry, priors_path)
    if host_mtbf_s is not None and host_mtbf_s <= READMIT_HORIZON_S:
        drain.lost_work_s = drain.latency_s

    quarantine = ArmSignals(
        mechanism="quarantine",
        latency_s=0.0, latency_source="",
        retention=survivor_frac,
        in_memory=False,
    )
    quarantine.latency_s, quarantine.latency_source, \
        quarantine.prior_source = _latency(
            "quarantine", "quarantine", latency_overrides, registry,
            priors_path)
    if host_failures < 1:
        quarantine.feasible, quarantine.reason = False, "no_failure_history"
    return {"observe": observe, "drain": drain, "quarantine": quarantine}


def build_borrow_arms(*,
                      chips: int,
                      train_hosts: int,
                      spare_hosts: int = 0,
                      min_train_hosts: int = 1,
                      slo_debt_s: float = 0.0,
                      drain_cost_s: float | None = None,
                      latency_overrides: dict[str, float] | None = None,
                      registry=None,
                      priors_path: str | None = None
                      ) -> dict[str, ArmSignals]:
    """Assemble the three BORROW arms for one cross-tenant pressure incident
    (a serve replica group asking the pool arbiter for `chips` hosts).

    The cross-tenant asymmetry lives in two terms: *deny* leaves training
    whole (retention 1.0) but the pressured tenant keeps paying its SLO
    debt — ``slo_debt_s`` is the requester's projected seconds of
    deadline-missed work over the amortization window, charged to every
    arm that does NOT relieve the pressure. *borrow_spare* relieves it
    from parked capacity (nobody pays); *borrow_drain* relieves it by
    preempting training hosts through the proven proactive-drain path —
    the training tenant pays ``preempt_cost_s`` (the drain + checkpoint
    flush, measured when history exists) plus degraded retention for the
    lease's remaining lifetime (the caller passes that lifetime as the
    scorer's ``mtbf_s`` so the amortization window IS the lease). deny is
    always feasible: the arbiter can always say no, and the requester
    sheds load through its own admission queue."""
    n, k = max(int(train_hosts), 0), max(int(chips), 1)
    survivor_frac = ((n - k) / n) if n else 0.0

    deny = ArmSignals(
        mechanism="deny",
        latency_s=0.0, latency_source="",
        retention=1.0,
        in_memory=False,
        slo_debt_s=max(float(slo_debt_s), 0.0),
    )
    deny.latency_s, deny.latency_source, deny.prior_source = _latency(
        "deny", "deny", latency_overrides, registry, priors_path)

    spare = ArmSignals(
        mechanism="borrow_spare",
        latency_s=0.0, latency_source="",
        retention=1.0,
        in_memory=False,
    )
    spare.latency_s, spare.latency_source, spare.prior_source = _latency(
        "borrow_spare", "borrow_spare", latency_overrides, registry,
        priors_path)
    if int(spare_hosts) < k:
        spare.feasible, spare.reason = False, "no_spare_capacity"

    drain = ArmSignals(
        mechanism="borrow_drain",
        latency_s=0.0, latency_source="",
        retention=survivor_frac,
        in_memory=False,
    )
    drain.latency_s, drain.latency_source, drain.prior_source = _latency(
        "borrow_drain", "borrow_drain", latency_overrides, registry,
        priors_path)
    drain.preempt_cost_s = (float(drain_cost_s) if drain_cost_s is not None
                            else drain.latency_s)
    if n - k < max(int(min_train_hosts), 0):
        drain.feasible, drain.reason = False, "train_floor"
    return {"deny": deny, "borrow_spare": spare, "borrow_drain": drain}


def build_reclaim_arms(*,
                       leased_hosts: int,
                       train_hosts: int,
                       slo_debt_s: float = 0.0,
                       lease_expired: bool = False,
                       latency_overrides: dict[str, float] | None = None,
                       registry=None,
                       priors_path: str | None = None
                       ) -> dict[str, ArmSignals]:
    """Assemble the two RECLAIM arms for one lease-end decision (off-peak
    sweep, early release, or expiry).

    *hold* keeps the lease with the borrower: training stays degraded
    (retention = its shrunken fraction, amortized over the remaining
    lease passed as ``mtbf_s``) but a borrower still under pressure pays
    nothing — infeasible once the lease has expired, since a lease that
    never ends is an allocation. *reclaim_grow* returns the chips to
    training through the JOIN/grow path; if the borrower's pressure has
    NOT passed, its ``slo_debt_s`` rides this arm (reclaiming re-exposes
    the borrower to the peak), which is what makes the arbiter hold
    through the peak and reclaim off-peak."""
    n, k = max(int(train_hosts), 0), max(int(leased_hosts), 1)
    degraded_frac = (n / (n + k)) if (n + k) else 1.0

    hold = ArmSignals(
        mechanism="hold",
        latency_s=0.0, latency_source="",
        retention=degraded_frac,
        in_memory=False,
    )
    hold.latency_s, hold.latency_source, hold.prior_source = _latency(
        "hold", "hold", latency_overrides, registry, priors_path)
    if lease_expired:
        hold.feasible, hold.reason = False, "lease_expired"

    reclaim = ArmSignals(
        mechanism="reclaim_grow",
        latency_s=0.0, latency_source="",
        retention=1.0,
        in_memory=False,
        slo_debt_s=max(float(slo_debt_s), 0.0),
    )
    reclaim.latency_s, reclaim.latency_source, reclaim.prior_source = \
        _latency("reclaim_grow", "reclaim_grow", latency_overrides,
                 registry, priors_path)
    return {"hold": hold, "reclaim_grow": reclaim}
