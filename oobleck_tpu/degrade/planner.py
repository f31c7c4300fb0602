"""Reroute planner: do the dead replica's microbatches fit the bubbles?

Given a feasible FailureReport, the planner decides HOW to reroute: which
survivor absorbs how many of the dead replica's microbatches, and what the
step-time cost is. Both questions run through the same machinery the
scheduler itself uses — replay_schedule() dependency replay over
calibrated per-(stage, chunk, direction) durations — so the planner's
makespan estimate and a test-side replay of the emitted schedule are one
computation, not two models that can drift (ISSUE 7 pins this down with
a replayed-bubble == planner-estimate assertion).

The fit intuition (ReCycle, arxiv 2405.14009): a 1F1B pipeline at M
microbatches idles (S-1)/(M+S-1) of its time; raising M to M+extra fills
that bubble with borrowed forwards before stretching the steady state, so
small reroutes are nearly free. The planner does not use the closed form —
it replays the actual rerouted streams with the pipeline's own measured
op durations, because calibrated fwd/bwd asymmetry moves the break-even
point.
"""

from __future__ import annotations

from dataclasses import dataclass, field

from oobleck_tpu.degrade.classify import FailureReport
from oobleck_tpu.execution.schedule import Op, replay_schedule


@dataclass(frozen=True)
class PipelineSpec:
    """What the planner needs to know about one pipeline: schedule shape
    plus calibrated op durations (pipe.last_op_times — (total_s, count)
    per (stage, chunk, 'f'|'b'|'cf'|'cb') — populated when sync_op_timing
    is on; 'cf'/'cb' are the cross-stage transfer times the same mode
    splits out of compute)."""

    num_stages: int
    num_microbatches: int
    virtual_stages: int = 1
    op_times: dict = field(default_factory=dict)
    # Measured fraction of cross-stage transfer time hidden under compute
    # (`parallel/overlap.comm_hidden_fraction`). 0.0 keeps the classic
    # fully-serialized projection; 1.0 projects comm as free.
    comm_hidden_fraction: float = 0.0

    def duration_fn(self):
        """instruction -> seconds from calibrated means; falls back to the
        classic fwd=1/bwd=2 cost model for uncalibrated (stage, chunk)
        units, scaled to the calibrated mean when any calibration exists
        so mixed dictionaries stay on one time base. When the calibration
        carries comm entries ('cf'/'cb'), each compute op is charged its
        EFFECTIVE comm — max(0, comm - hidden_fraction * compute) — so an
        overlap-enabled deployment's degraded projection doesn't double-
        count latency the schedule already hides."""
        from oobleck_tpu.parallel.overlap import effective_comm

        means: dict[tuple[int, int, str], float] = {}
        for (stage, chunk, kind), (total, count) in self.op_times.items():
            if count > 0:
                means[(stage, chunk, kind)] = total / count
        if means:
            fallback_f = sum(v for (_, _, k), v in means.items()
                             if k == "f") or None
            n_f = sum(1 for (_, _, k) in means if k == "f")
            base_f = (fallback_f / n_f) if fallback_f else 1.0
        else:
            base_f = 1.0

        def dur(inst):
            kind = "b" if inst.op is Op.BACKWARD else "f"
            mean = means.get((inst.stage, inst.chunk, kind))
            base = mean if mean is not None else (
                base_f * (2.0 if kind == "b" else 1.0))
            comm = means.get((inst.stage, inst.chunk, "c" + kind))
            if comm is not None:
                base += effective_comm(comm, base,
                                       self.comm_hidden_fraction)
            return base

        return dur


@dataclass
class ReroutePlan:
    """The planner's answer: per-survivor absorbed microbatches plus the
    projected cost of running degraded.

    `new_microbatches` is keyed by pipeline list index (same index space
    as FailureReport.dead/surviving). `makespan_before` includes the dead
    pipelines — pipelines run concurrently, so the pre-failure step time
    is the max over ALL replicas and the global batch is preserved either
    way; throughput retention is therefore makespan_before /
    makespan_after, and slowdown its inverse.
    """

    report: FailureReport
    new_microbatches: dict[int, int] = field(default_factory=dict)
    extra_microbatches: int = 0
    makespan_before: float = 0.0
    makespan_after: float = 0.0
    reason: str = ""

    @property
    def feasible(self) -> bool:
        return not self.reason

    @property
    def slowdown(self) -> float:
        if self.makespan_before <= 0:
            return float("inf")
        return self.makespan_after / self.makespan_before

    @property
    def throughput_retention(self) -> float:
        s = self.slowdown
        return 0.0 if s in (0.0, float("inf")) else min(1.0, 1.0 / s)

    def as_record(self) -> dict:
        rec = self.report.as_record()
        rec.update({
            "new_microbatches": {str(k): v
                                 for k, v in sorted(
                                     self.new_microbatches.items())},
            "extra_microbatches": self.extra_microbatches,
            "makespan_before_s": self.makespan_before,
            "makespan_after_s": self.makespan_after,
            "projected_slowdown": self.slowdown
            if self.makespan_before > 0 else None,
            "projected_retention": self.throughput_retention,
        })
        if self.reason:
            rec["reason"] = self.reason
        return rec


def plan_reroute(report: FailureReport, specs: list[PipelineSpec],
                 max_slowdown: float = 4.0) -> ReroutePlan:
    """Distribute dead replicas' microbatches over survivors and project
    the degraded step time.

    specs is indexed like the engine's pipeline list (the same index
    space as report.dead/report.surviving). Infeasibility reasons beyond
    the classifier's: "indivisible_extra" (an interleaved survivor can
    only grow in multiples of its S, and the remainder cannot be placed)
    and "exceeds_max_slowdown" (the work fits but the projected step-time
    blowup crosses max_slowdown — re-instantiation with a rebalanced plan
    is the better deal).
    """
    plan = ReroutePlan(report=report)
    if not report.feasible:
        plan.reason = report.reason
        return plan

    extra = sum(specs[i].num_microbatches for i in report.dead)
    plan.extra_microbatches = extra
    assigned = {i: 0 for i in report.surviving}
    # Interleaved survivors grow in quanta of S (validate_interleaving);
    # canonical survivors in quanta of 1.
    quantum = {
        i: specs[i].num_stages if specs[i].virtual_stages > 1 else 1
        for i in report.surviving
    }
    remaining = extra
    while remaining > 0:
        candidates = [i for i in report.surviving
                      if quantum[i] <= remaining]
        if not candidates:
            plan.reason = "indivisible_extra"
            return plan
        # Least-loaded first keeps the post-reroute makespan (max over
        # survivors) minimal for homogeneous replicas.
        i = min(candidates,
                key=lambda j: (specs[j].num_microbatches + assigned[j], j))
        assigned[i] += quantum[i]
        remaining -= quantum[i]

    plan.new_microbatches = {
        i: specs[i].num_microbatches + assigned[i]
        for i in report.surviving
    }

    # One replay per distinct schedule shape, not per replica: homogeneous
    # DP fleets (the sim runs this planner at 1024 replicas) share one
    # op_times dict, and replay_schedule is pure in (S, M, v, durations),
    # so the memo changes nothing but the wall clock. Scoped to this call:
    # no cross-call staleness when calibration moves between incidents.
    memo: dict = {}

    def makespan(spec: PipelineSpec, microbatches: int) -> float:
        key = (spec.num_stages, microbatches, spec.virtual_stages,
               id(spec.op_times), spec.comm_hidden_fraction)
        if key not in memo:
            memo[key] = replay_schedule(spec.num_stages, microbatches,
                                        spec.virtual_stages,
                                        spec.duration_fn())[0]
        return memo[key]

    # Pre-failure step time: max over ALL replicas (they run concurrently).
    plan.makespan_before = max(
        makespan(s, s.num_microbatches) for s in specs)
    plan.makespan_after = max(
        makespan(specs[i], plan.new_microbatches[i])
        for i in report.surviving)
    if plan.makespan_before > 0 and plan.slowdown > max_slowdown:
        plan.reason = "exceeds_max_slowdown"
    return plan
