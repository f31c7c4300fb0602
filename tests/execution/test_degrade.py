"""Degraded-mode execution plane (oobleck_tpu/degrade): emitter
invariants over every small drop-one-peer config, planner/classifier
tables, replayed-bubble == planner-estimate, and live engine reroute
parity — the post-reroute step must match a no-failure run given the
same data order, because rerouting only moves microbatches between
replicas, never changes the global batch or the gradient scale."""

import numpy as np
import pytest

import jax

from oobleck_tpu.degrade.classify import FailureReport, classify_failure
from oobleck_tpu.degrade.emitter import (
    dataflow_edges,
    emit_rerouted,
    validate_reroute,
)
from oobleck_tpu.degrade.planner import PipelineSpec, plan_reroute
from oobleck_tpu.execution.schedule import (
    all_instructions,
    replay_schedule,
    simulate_bubble,
)
from oobleck_tpu.utils import chaos as chaos_mod
from oobleck_tpu.utils import metrics

from tests.execution.test_engine import cache_env, make_engine  # noqa: F401


# --------------------------------------------------------------------- #
# emitter: structural invariants over every (S<=4, M<=8, v<=2) config
# --------------------------------------------------------------------- #

def _drop_one_peer_configs():
    """Every (S, base, extra, v) with base+extra <= 8 that a survivor can
    legally run: the full small-config space the ISSUE pins down, not just
    the equal-replica case (heterogeneous plans lend unequal extras)."""
    for S in (1, 2, 3, 4):
        for v in (1, 2):
            for base in range(1, 8):
                for extra in range(1, 8 - base + 1):
                    if v > 1 and (base + extra) % S != 0:
                        continue
                    yield S, base, extra, v


def test_emitter_invariants_all_small_configs():
    configs = list(_drop_one_peer_configs())
    assert len(configs) > 50  # the sweep must not silently collapse
    for S, base, extra, v in configs:
        sched = emit_rerouted(S, base, extra, v)
        validate_reroute(sched)  # fwd-before-bwd, send/recv, dataflow
        assert sched.num_microbatches == base + extra
        # every borrowed microbatch runs one fwd + one bwd per virtual
        # stage, somewhere in the survivor's streams
        assert len(sched.borrowed_units()) == extra * S * v * 2


def test_emitter_rejects_unrunnable_interleaving():
    # v=2 requires (base+extra) % S == 0: rerouting may not change v,
    # because a different chunk layout means a recompile.
    with pytest.raises(ValueError):
        emit_rerouted(2, 4, 1, virtual_stages=2)


def test_dataflow_edges_unchanged_by_reroute():
    for S, v, base, extra in ((2, 1, 4, 4), (4, 1, 4, 2), (2, 2, 4, 2)):
        sched = emit_rerouted(S, base, extra, v)
        assert dataflow_edges(sched.streams) == dataflow_edges(
            all_instructions(S, base, v))


# --------------------------------------------------------------------- #
# classifier: table-driven topology cases
# --------------------------------------------------------------------- #

def test_classifier_peer_available():
    # 4 single-host replicas, 2 chips each; losing host 1 kills replica 1
    ranks = [[0, 1], [2, 3], [4, 5], [6, 7]]
    rep = classify_failure(1, ranks, chips_per_host=2)
    assert rep.feasible
    assert rep.dead == [1] and rep.surviving == [0, 2, 3]
    assert rep.stranded_hosts == []
    assert rep.as_record()["reason"] == "peer_available"


def test_classifier_lost_host_runs_no_pipeline():
    rep = classify_failure(3, [[0, 1], [2, 3]], chips_per_host=2)
    assert not rep.feasible
    assert rep.reason == "lost_host_runs_no_pipeline"


def test_classifier_no_surviving_dp_peer():
    # one pipeline spanning both hosts: no replica survives the loss
    rep = classify_failure(0, [[0, 1, 2, 3]], chips_per_host=2)
    assert not rep.feasible
    assert rep.reason == "no_surviving_dp_peer"


def test_classifier_stranded_hosts():
    # replica 0 spans hosts 0+1; losing host 0 would leave host 1 idle
    rep = classify_failure(0, [[0, 1, 2, 3], [4, 5, 6, 7]],
                           chips_per_host=2)
    assert not rep.feasible
    assert rep.reason == "reroute_would_strand_hosts"
    assert rep.stranded_hosts == [1]
    assert rep.dead == [0] and rep.surviving == [1]


# --------------------------------------------------------------------- #
# planner: distribution, infeasibility reasons, replay consistency
# --------------------------------------------------------------------- #

def test_planner_least_loaded_distribution():
    report = FailureReport(lost_host=3, dead=[3], surviving=[0, 1, 2])
    specs = [PipelineSpec(2, 2)] * 4
    plan = plan_reroute(report, specs)
    assert plan.feasible
    assert plan.extra_microbatches == 2
    assert sorted(plan.new_microbatches.values()) == [2, 3, 3]
    assert sum(plan.new_microbatches.values()) == 8  # global batch kept
    assert 0.0 < plan.throughput_retention <= 1.0


def test_planner_indivisible_extra():
    # interleaved survivor can only absorb in quanta of S=2; extra=1 is
    # unplaceable
    report = FailureReport(lost_host=1, dead=[1], surviving=[0])
    specs = [PipelineSpec(2, 4, virtual_stages=2), PipelineSpec(1, 1)]
    plan = plan_reroute(report, specs)
    assert not plan.feasible
    assert plan.reason == "indivisible_extra"


def test_planner_exceeds_max_slowdown():
    report = FailureReport(lost_host=1, dead=[1], surviving=[0])
    specs = [PipelineSpec(2, 2), PipelineSpec(2, 2)]
    plan = plan_reroute(report, specs, max_slowdown=1.2)
    assert not plan.feasible
    assert plan.reason == "exceeds_max_slowdown"
    # the projection itself is still reported for the flight recorder
    assert plan.slowdown > 1.2
    rec = plan.as_record()
    assert rec["reason"] == "exceeds_max_slowdown"


def test_planner_propagates_classifier_reason():
    rep = classify_failure(0, [[0, 1, 2, 3]], chips_per_host=2)
    plan = plan_reroute(rep, [PipelineSpec(2, 4)])
    assert not plan.feasible
    assert plan.reason == "no_surviving_dp_peer"


def test_replayed_bubble_matches_planner_estimate():
    """Replaying the EMITTED streams through replay_schedule must land on
    exactly the planner's makespan projection — estimator and emitted
    schedule are one computation, so they cannot drift apart."""
    cases = [
        (2, 4, 1, {}),
        (4, 4, 1, {}),
        (2, 4, 2, {}),
        # calibrated, asymmetric per-stage durations (stage 1 slower)
        (2, 4, 1, {(0, 0, "f"): (1.0, 10), (1, 0, "f"): (3.0, 10),
                   (0, 0, "b"): (4.0, 10), (1, 0, "b"): (9.0, 10)}),
    ]
    for S, M, v, op_times in cases:
        spec = PipelineSpec(S, M, virtual_stages=v, op_times=op_times)
        report = FailureReport(lost_host=1, dead=[1], surviving=[0])
        plan = plan_reroute(report, [spec, spec])
        assert plan.feasible, (S, M, v)
        new_m = plan.new_microbatches[0]
        sched = emit_rerouted(S, M, new_m - M, v)
        makespan, busy = replay_schedule(
            S, new_m, v, spec.duration_fn(), streams=sched.streams)
        assert makespan == pytest.approx(plan.makespan_after, rel=1e-12)
        # and the bubble the engine would report for the rerouted shape is
        # the same number simulate_bubble computes for (S, new_m, v)
        assert 1.0 - busy / (S * makespan) == pytest.approx(
            simulate_bubble(S, new_m, v, spec.duration_fn()), rel=1e-12)


# --------------------------------------------------------------------- #
# chaos: stage-addressed kill directive
# --------------------------------------------------------------------- #

def test_chaos_kill_stage_parse_and_one_shot():
    rules = chaos_mod.parse_spec("kill_stage=1:0")
    assert rules[0].action == "kill_stage"
    assert rules[0].arg == "1" and rules[0].qual == "0"
    with pytest.raises(ValueError):
        chaos_mod.parse_spec("kill_stage=first")
    try:
        c = chaos_mod.reset("kill_stage=0:1")
        assert c.kill_stage_target() == (0, 1)
        assert c.kill_stage_target() is None  # a dead host cannot die again
    finally:
        chaos_mod.reset("")


# --------------------------------------------------------------------- #
# live engine: reroute fast path, parity, fallback, chaos hook
# --------------------------------------------------------------------- #

def _dp2_engine(devices, steps=8):
    """2 hosts x 2 chips: the smallest rig with a DP peer to reroute onto."""
    engine = make_engine(num_hosts=2, steps=steps, devices=devices[:4],
                         microbatch=2, global_mb=8)
    engine.initialize_distributed()
    engine.instantiate_pipelines(engine.args.job.global_num_microbatch)
    assert len(engine.pipelines) == 2, (
        "planner did not produce 2 DP replicas on the 2-host rig: "
        f"{engine.plan}")
    return engine


def _all_params(engine):
    out = {}
    for pipe in engine.pipelines:
        for li, p in pipe.params.items():
            out[li] = [np.asarray(x, np.float32) for x in jax.tree.leaves(p)]
    return out


def test_reroute_live_parity(cache_env, devices8):
    """Losing a DP peer and rerouting must be loss- and parameter-exact
    against a run that never failed: same data order, same gradient scale,
    same global batch — only the replica running the microbatches moved."""
    eng = _dp2_engine(devices8)
    ref = _dp2_engine(devices8)

    for _ in range(2):
        loss_eng = eng._train_step()
        loss_ref = ref._train_step()
        np.testing.assert_allclose(loss_eng, loss_ref, rtol=1e-6)

    eng.reconfigure("10.0.0.1")  # degrade enabled by default -> reroute

    # fast path engaged: same topology minus the dead replica, survivor
    # absorbed all microbatches, no re-plan artifacts
    assert eng.host_ips == ["10.0.0.0"]
    assert len(eng.pipelines) == 1
    assert eng.pipelines[0].num_microbatches == 4
    g = metrics.registry().gauge("oobleck_degrade_extra_microbatches", "")
    assert g.value() == 2.0

    # the next steps match the no-failure run: loss now and loss AFTER the
    # next update (the second step only matches if the first step's
    # gradients and optimizer update were identical)
    for _ in range(2):
        loss_eng = eng._train_step()
        loss_ref = ref._train_step()
        np.testing.assert_allclose(loss_eng, loss_ref, rtol=1e-5)

    # parameters track the reference run layer for layer
    got, want = _all_params(eng), _all_params(ref)
    assert got.keys() == want.keys()
    for li in got:
        for a, b in zip(got[li], want[li]):
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def test_infeasible_reroute_falls_back_with_decision(cache_env, devices8):
    """When the projected slowdown crosses the configured ceiling, the
    engine must fall back to template re-instantiation AND leave a
    DegradeDecision in the flight recorder carrying the reason."""
    eng = _dp2_engine(devices8, steps=4)
    eng.args.execution.degrade_max_slowdown = 1.01  # merge costs ~2x
    eng._train_step()
    # The projection replays each replica's schedule at the op durations
    # its last step recorded. A first step used to hold its own compiles,
    # on both replicas alike; one that finds its programs built records
    # enqueue times, a few of which can differ by the 2x this test is
    # about. Equal durations: what two replicas of one plan have.
    for pipe in eng.pipelines:
        pipe.last_op_times = {
            op: (0.01 * n, n) for op, (_, n) in pipe.last_op_times.items()}

    eng.reconfigure("10.0.0.1")

    assert eng.host_ips == ["10.0.0.0"]
    decisions = [e for e in metrics.flight_recorder().events()
                 if e.get("event") == "degrade_decision"]
    assert decisions, "fallback must still record a DegradeDecision"
    last = decisions[-1]
    assert last["mechanism"] == "reinstantiate"
    assert last["reason"] == "exceeds_max_slowdown"
    assert last["measured_recovery_s"] > 0
    # training continues on the re-instantiated plan
    assert np.isfinite(eng._train_step())


def test_chaos_kill_stage_resolves_to_replica_host(cache_env, devices8):
    """OOBLECK_CHAOS=kill_stage=<stage>:<replica> must resolve to the host
    owning that stage of that replica and drive the normal recovery path
    (which, with capacity available, is a reroute)."""
    eng = _dp2_engine(devices8, steps=4)
    eng._train_step()
    try:
        chaos_mod.reset("kill_stage=0:1")
        eng._maybe_chaos_kill_stage()
        assert [ip for ip, _, _ in eng._pending_lost] == ["10.0.0.1"]
        # In-process detection mints the incident trace right here.
        assert eng._pending_lost[0][1]["trace_id"]
        eng._maybe_reconfigure()
    finally:
        chaos_mod.reset("")
    assert eng.host_ips == ["10.0.0.0"]
    assert len(eng.pipelines) == 1
    assert eng.pipelines[0].num_microbatches == 4
    resolved = [e for e in metrics.flight_recorder().events()
                if e.get("event") == "chaos_kill_stage_resolved"]
    assert resolved and resolved[-1]["lost_ip"] == "10.0.0.1"
    assert np.isfinite(eng._train_step())


# --------------------------------------------------------------------- #
# comm-hidden-fraction in the degraded projection (parallel/overlap)
# --------------------------------------------------------------------- #

def test_duration_fn_charges_effective_comm():
    """Calibrations that carry 'cf'/'cb' comm entries charge each compute
    op its EFFECTIVE comm — max(0, comm - hf * compute) — so an
    overlap-enabled deployment's degraded projection doesn't double-count
    latency the schedule already hides."""
    from oobleck_tpu.execution.schedule import Instruction, Op

    op_times = {(0, 0, "f"): (10.0, 10), (0, 0, "cf"): (5.0, 10),
                (0, 0, "b"): (20.0, 10), (0, 0, "cb"): (5.0, 10)}
    f_inst = Instruction(Op.FORWARD, 0, 0)
    b_inst = Instruction(Op.BACKWARD, 0, 0)

    serial = PipelineSpec(1, 4, op_times=op_times).duration_fn()
    assert serial(f_inst) == pytest.approx(1.0 + 0.5)
    assert serial(b_inst) == pytest.approx(2.0 + 0.5)

    # hf=0.4: forward keeps 0.5 - 0.4*1.0 = 0.1 of its comm; backward's
    # larger compute window (2.0) hides all of it
    partial = PipelineSpec(1, 4, op_times=op_times,
                           comm_hidden_fraction=0.4).duration_fn()
    assert partial(f_inst) == pytest.approx(1.1)
    assert partial(b_inst) == pytest.approx(2.0)

    hidden = PipelineSpec(1, 4, op_times=op_times,
                          comm_hidden_fraction=1.0).duration_fn()
    assert hidden(f_inst) == pytest.approx(1.0)
    assert hidden(b_inst) == pytest.approx(2.0)


def test_planner_projection_discounts_hidden_comm():
    """Same calibration, different measured hidden fraction: the overlap-
    aware projection must land on a strictly smaller post-reroute
    makespan (and not be served from the hf=0 memo entry)."""
    op_times = {(s, 0, k): (v, 1) for s in (0, 1)
                for k, v in (("f", 1.0), ("b", 2.0),
                             ("cf", 0.8), ("cb", 0.8))}
    report = FailureReport(lost_host=1, dead=[1], surviving=[0])
    makespan = {}
    for hf in (0.0, 1.0):
        spec = PipelineSpec(2, 4, op_times=op_times,
                            comm_hidden_fraction=hf)
        plan = plan_reroute(report, [spec, spec])
        assert plan.feasible
        makespan[hf] = plan.makespan_after
    assert makespan[1.0] < makespan[0.0]
