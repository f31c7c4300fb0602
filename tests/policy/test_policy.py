"""Policy-plane unit tests: scorer monotonicity, quarantine hysteresis,
correlated infeasibility, forced modes, measured-latency feedback, and the
broadcast payload roundtrip. Everything runs on injectable clocks and a
fresh metrics registry — no sleeping, no global state leaks."""

from __future__ import annotations

import pytest

from oobleck_tpu.policy import (
    MECH_REINSTANTIATE, MECH_REROUTE, MECH_RESTORE, MODE_ADAPTIVE,
    HostHealthTracker, PolicyEngine, decision_from_payload)
from oobleck_tpu.policy.scorer import cheapest_feasible, score_arms
from oobleck_tpu.policy.signals import PRIOR_LATENCY_S, build_arms
from oobleck_tpu.utils import metrics


class FakeClock:
    def __init__(self, t: float = 1000.0):
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, s: float) -> None:
        self.t += s


@pytest.fixture(autouse=True)
def _fresh_registry(monkeypatch):
    """The suite shares one process: other modules' recovery histograms
    would otherwise leak measured latencies into these scoring tests."""
    monkeypatch.setattr(metrics, "_registry", metrics.Registry())


def _engine(mode=MODE_ADAPTIVE, **kw):
    return PolicyEngine(mode=mode, clock=FakeClock(), **kw)


# --------------------------------------------------------------------- #
# scorer


def test_first_failure_picks_reroute():
    # No failure history: risk 0, priors only — cheapest-latency wins,
    # which is the reroute-first behavior the fixed policy had.
    eng = _engine()
    d = eng.decide(["10.0.0.1"], staleness_steps=5.0)
    assert d.mechanism == MECH_REROUTE
    assert d.reason == "cheapest"
    assert d.mtbf_s is None
    assert d.costs[MECH_REROUTE] < d.costs[MECH_REINSTANTIATE]
    assert d.costs[MECH_REROUTE] < d.costs[MECH_RESTORE]


def test_scorer_monotone_in_mtbf():
    # At full retention, shrinking MTBF must never make an in-memory arm
    # CHEAPER: the churn hedge (risk * restore cost) grows as the fleet
    # gets sicker, while the restore arm itself is churn-free and stays
    # flat. (Below full retention the degraded-throughput term shrinks
    # with its amortization horizon, deliberately — that trade is covered
    # by the flip test below.)
    arms = build_arms(staleness_steps=10.0)
    prev_reroute = None
    restore_costs = []
    for mtbf in (600.0, 300.0, 60.0, 30.0, 5.0):
        scored = score_arms(arms, mtbf_s=mtbf)
        if prev_reroute is not None:
            assert scored[MECH_REROUTE].cost_s >= prev_reroute - 1e-9
        prev_reroute = scored[MECH_REROUTE].cost_s
        restore_costs.append(scored[MECH_RESTORE].cost_s)
    assert max(restore_costs) == pytest.approx(min(restore_costs))


def test_scorer_monotone_in_retention():
    # Worse projected survivor throughput raises the reroute cost.
    lo = score_arms(build_arms(staleness_steps=0.0, reroute_retention=0.5),
                    mtbf_s=100.0)
    hi = score_arms(build_arms(staleness_steps=0.0, reroute_retention=0.9),
                    mtbf_s=100.0)
    assert lo[MECH_REROUTE].cost_s > hi[MECH_REROUTE].cost_s


def test_churn_storm_flips_choice_to_restore_and_back():
    # A 5s-period flapper saturates risk: every in-memory recovery just
    # schedules the next incident, so restore-now (fresh checkpoint) wins.
    eng = _engine()
    for _ in range(4):
        eng.observe_failure("10.0.0.9", cause="flap")
        eng.health._clock.advance(5.0)
    d = eng.decide(["10.0.0.9"], staleness_steps=2.0, step_seconds=1.0)
    assert d.mtbf_s == pytest.approx(5.0)
    assert d.mechanism == MECH_RESTORE
    assert "10.0.0.9" in d.quarantined

    # Rising MTBF decays the hedge and flips back to the cheap arm.
    calm = _engine()
    calm.observe_failure("10.0.0.9")
    calm.health._clock.advance(3600.0)
    calm.observe_failure("10.0.0.9")
    d2 = calm.decide(["10.0.0.9"], staleness_steps=2.0, step_seconds=1.0)
    assert d2.mtbf_s == pytest.approx(3600.0)
    assert d2.mechanism == MECH_REROUTE


def test_cheapest_feasible_deterministic_ties():
    scored = score_arms(build_arms(staleness_steps=0.0), mtbf_s=None)
    for a in scored.values():
        a.cost_s = 1.0
    best = cheapest_feasible(scored)
    assert best.mechanism == MECH_REINSTANTIATE  # alphabetical tiebreak


# --------------------------------------------------------------------- #
# quarantine hysteresis


def test_quarantine_enters_on_repeat_and_lifts_after_quiet():
    clk = FakeClock()
    t = HostHealthTracker(clock=clk, default_window_s=10.0,
                          hysteresis_factor=2.0)
    t.record_failure("h")
    assert not t.is_quarantined("h")          # one failure = unlucky
    clk.advance(5.0)
    t.record_failure("h")                     # twice inside the window
    assert t.is_quarantined("h")
    assert t.mtbf("h") == pytest.approx(5.0)
    # Quick to quarantine, slow to forgive: quiet < 2x window keeps it out.
    clk.advance(9.0)
    assert t.is_quarantined("h")
    clk.advance(2.0)                          # 11s quiet >= 2 * mtbf(5)
    assert not t.is_quarantined("h")
    assert t.quarantined() == []


def test_quarantine_no_oscillation_for_fast_flapper():
    # A 2s-period flapper must stay quarantined across its whole flap
    # train — the hysteresis window re-arms on every new failure.
    clk = FakeClock()
    t = HostHealthTracker(clock=clk, default_window_s=300.0)
    t.record_failure("f")
    for _ in range(10):
        clk.advance(2.0)
        t.record_failure("f")
        assert t.is_quarantined("f")
    assert t.fleet_mtbf() == pytest.approx(2.0)


# --------------------------------------------------------------------- #
# feasibility gates


def test_correlated_failure_skips_reroute():
    eng = _engine()
    d = eng.decide(["10.0.0.1", "10.0.0.2"], staleness_steps=None)
    assert d.mechanism != MECH_REROUTE
    assert d.infeasible[MECH_REROUTE] == "correlated_failure"


def test_no_durable_checkpoint_blocks_restore():
    eng = _engine()
    d = eng.decide(["10.0.0.1"], staleness_steps=None)
    assert d.infeasible[MECH_RESTORE] == "no_durable_checkpoint"
    assert d.mechanism in (MECH_REROUTE, MECH_REINSTANTIATE)


def test_degrade_disabled_blocks_reroute():
    eng = _engine()
    d = eng.decide(["10.0.0.1"], degrade_enabled=False, staleness_steps=0.0)
    assert d.infeasible[MECH_REROUTE] == "degrade_disabled"
    assert d.mechanism != MECH_REROUTE


# --------------------------------------------------------------------- #
# forced modes (benchmark baselines)


def test_forced_mode_wins_when_feasible():
    eng = _engine(mode=MECH_RESTORE)
    d = eng.decide(["10.0.0.1"], staleness_steps=100.0)
    assert d.mechanism == MECH_RESTORE
    assert d.reason == "forced:restore"


def test_forced_mode_falls_back_when_infeasible():
    eng = _engine(mode=MECH_RESTORE)
    d = eng.decide(["10.0.0.1"], staleness_steps=None)
    assert d.mechanism == MECH_REINSTANTIATE
    assert d.reason.startswith("forced:restore:infeasible:")


def test_bad_mode_rejected_eagerly():
    with pytest.raises(ValueError):
        PolicyEngine(mode="yolo")


# --------------------------------------------------------------------- #
# measured feedback


def test_measured_latency_feeds_ewma_and_closes_loop():
    eng = _engine()
    eng.observe_measured(MECH_REROUTE, 0.2)
    d = eng.decide(["10.0.0.1"], staleness_steps=0.0)
    assert d.mechanism == MECH_REROUTE
    assert d.arms[MECH_REROUTE]["latency_source"] == "measured"
    assert d.arms[MECH_REROUTE]["latency_s"] == pytest.approx(0.2)
    # Feedback after the decision backfills projected-vs-measured.
    eng.observe_measured(MECH_REROUTE, 0.4)
    assert d.measured_recovery_s == pytest.approx(0.4)
    assert eng._ewma[MECH_REROUTE] == pytest.approx(0.3)  # EWMA alpha 0.5
    closed = [e for e in metrics.flight_recorder().events()
              if e["event"] == "policy_decision_measured"
              and e.get("trace_id") == d.trace_id]
    assert closed and closed[-1]["measured_recovery_s"] == pytest.approx(0.4)


def test_priors_used_until_history_exists():
    eng = _engine()
    d = eng.decide(["10.0.0.1"], staleness_steps=0.0)
    for m in (MECH_REROUTE, MECH_REINSTANTIATE, MECH_RESTORE):
        assert d.arms[m]["latency_source"] == "prior"
    assert d.arms[MECH_RESTORE]["latency_s"] == PRIOR_LATENCY_S["restore"]


# --------------------------------------------------------------------- #
# payload roundtrip + bookkeeping


def test_decision_payload_roundtrip():
    eng = _engine()
    d = eng.decide(["10.0.0.1"], staleness_steps=3.0, proactive=True)
    r = decision_from_payload(d.as_payload())
    assert r.mechanism == d.mechanism
    assert r.lost_ips == d.lost_ips
    assert r.proactive is True
    assert r.projected_cost_s == pytest.approx(d.projected_cost_s)
    assert r.trace_id == d.trace_id
    # Tolerant of legacy peers and future keys.
    assert decision_from_payload(None) is None
    assert decision_from_payload({"no": "mechanism"}) is None
    assert decision_from_payload(
        {"mechanism": "reroute", "future_field": 1}).mechanism == MECH_REROUTE


def test_every_decision_flight_recorded_with_costs():
    eng = _engine()
    d = eng.decide(["10.0.0.1"], staleness_steps=1.0)
    recs = [e for e in metrics.flight_recorder().events()
            if e["event"] == "policy_decision"
            and e.get("trace_id") == d.trace_id]
    assert len(recs) == 1
    assert set(recs[0]["costs"]) == {MECH_REROUTE, MECH_REINSTANTIATE,
                                     MECH_RESTORE}
    assert recs[0]["projected_cost_s"] == pytest.approx(d.projected_cost_s)


# --------------------------------------------------------------------- #
# grow direction (decide_grow)


def test_grow_scores_all_three_arms():
    from oobleck_tpu.policy import GROW_MODES, MECH_ABSORB

    eng = _engine()
    d = eng.decide_grow(["10.0.0.5"], current_hosts=4, staleness_steps=0.0)
    assert d.mechanism in GROW_MODES
    assert d.lost_ips == [] and d.joined_ips == ["10.0.0.5"]
    assert set(d.costs) == set(GROW_MODES)
    assert d.reason == "cheapest"
    # absorb's retention is measured against the POST-grow ceiling: the
    # foregone gain of parking 1 arrival next to 4 hosts is 4/5.
    assert d.arms[MECH_ABSORB]["retention"] == pytest.approx(4 / 5)


def test_short_spot_lifetime_flips_grow_to_absorb():
    """The amortization horizon is the arriving capacity's expected
    LIFETIME: a spot host that vanishes in seconds cannot amortize a
    reshape (or the churn risk of committing state to it), so absorb
    wins; a long-lived arrival flips the verdict to a real grow arm."""
    from oobleck_tpu.policy import GROW_MODES, MECH_ABSORB

    eng = _engine()
    ephemeral = eng.decide_grow(
        ["10.0.0.5"], current_hosts=4, staleness_steps=0.0,
        step_seconds=1.0, lifetime_hints={"10.0.0.5": 3.0})
    assert ephemeral.mechanism == MECH_ABSORB
    assert ephemeral.mtbf_s == pytest.approx(3.0)

    durable = eng.decide_grow(
        ["10.0.0.5"], current_hosts=4, staleness_steps=0.0,
        step_seconds=1.0, lifetime_hints={"10.0.0.5": 86400.0})
    assert durable.mechanism in set(GROW_MODES) - {MECH_ABSORB}
    assert durable.costs[durable.mechanism] < durable.costs[MECH_ABSORB]


def test_grow_lifetime_precedence_hint_then_own_mtbf_then_fleet():
    """lifetime_hints wins over the joiner's own failure history, which
    wins over the fleet MTBF (the joiner may be a flapper that left and
    came back, carrying its record)."""
    eng = _engine()
    # Fleet history: some OTHER host churns at 5 s.
    for _ in range(3):
        eng.observe_failure("10.0.0.1")
        eng.health._clock.advance(5.0)
    d = eng.decide_grow(["10.0.0.5"], current_hosts=4)
    assert d.mtbf_s == pytest.approx(5.0)  # fleet MTBF: joiner unknown

    # The joiner's own record beats the fleet's.
    eng.observe_failure("10.0.0.5")
    eng.health._clock.advance(120.0)
    eng.observe_failure("10.0.0.5")
    d = eng.decide_grow(["10.0.0.5"], current_hosts=4)
    assert d.mtbf_s == pytest.approx(120.0)

    # An explicit hint beats both.
    d = eng.decide_grow(["10.0.0.5"], current_hosts=4,
                        lifetime_hints={"10.0.0.5": 600.0})
    assert d.mtbf_s == pytest.approx(600.0)


def test_grow_dp_infeasibility_travels_with_reason():
    from oobleck_tpu.policy import MECH_GROW_DP

    eng = _engine()
    d = eng.decide_grow(["10.0.0.5"], current_hosts=4, dp_feasible=False,
                        dp_reason="arrivals(1)<smallest_template(2)")
    assert d.mechanism != MECH_GROW_DP
    assert d.infeasible[MECH_GROW_DP] == "arrivals(1)<smallest_template(2)"


def test_forced_grow_arm_wins_and_falls_back_to_absorb():
    from oobleck_tpu.policy import MECH_ABSORB, MECH_GROW_DP, \
        MECH_GROW_RESHAPE

    eng = _engine(mode=MECH_GROW_RESHAPE)
    d = eng.decide_grow(["10.0.0.5"], current_hosts=4, staleness_steps=50.0)
    assert d.mechanism == MECH_GROW_RESHAPE
    assert d.reason == "forced:grow_reshape"

    # An infeasible forced grow arm falls back to absorb_spare — the grow
    # direction's always-available mechanism.
    eng = _engine(mode=MECH_GROW_DP)
    d = eng.decide_grow(["10.0.0.5"], current_hosts=4, dp_feasible=False,
                        dp_reason="no_template_fit")
    assert d.mechanism == MECH_ABSORB
    assert d.reason == "forced:grow_dp:infeasible:no_template_fit"


def test_forced_modes_do_not_cross_directions():
    """A loss-direction forced mode consulted in the GROW direction (and
    vice versa) degrades to adaptive — a run forcing `restore` must not
    wedge the join path, and forcing `grow_dp` must not wedge recovery."""
    from oobleck_tpu.policy import GROW_MODES, MECH_GROW_DP

    eng = _engine(mode=MECH_RESTORE)
    d = eng.decide_grow(["10.0.0.5"], current_hosts=4, staleness_steps=0.0)
    assert d.mechanism in GROW_MODES
    assert d.reason == "cheapest"

    eng = _engine(mode=MECH_GROW_DP)
    d = eng.decide(["10.0.0.1"], staleness_steps=0.0)
    assert d.mechanism == MECH_REROUTE
    assert d.reason == "cheapest"


def test_grow_decision_payload_roundtrip_and_flight_record():
    from oobleck_tpu.policy import GROW_MODES

    eng = _engine()
    d = eng.decide_grow(["10.0.0.5", "10.0.0.6"], current_hosts=2,
                        staleness_steps=1.0)
    r = decision_from_payload(d.as_payload())
    assert r.mechanism == d.mechanism
    assert r.joined_ips == ["10.0.0.5", "10.0.0.6"]
    assert r.lost_ips == []
    assert set(r.costs) == set(GROW_MODES)
    recs = [e for e in metrics.flight_recorder().events()
            if e["event"] == "policy_decision"
            and e.get("trace_id") == d.trace_id]
    assert len(recs) == 1
    assert set(recs[0]["costs"]) == set(GROW_MODES)


def test_grow_measured_feedback_feeds_next_decision():
    """A measured grow latency (engine _observe_policy_measured) becomes
    the EWMA the NEXT grow decision scores with."""
    from oobleck_tpu.policy import MECH_GROW_DP

    eng = _engine()
    eng.observe_measured(MECH_GROW_DP, 0.08)
    d = eng.decide_grow(["10.0.0.5"], current_hosts=4, staleness_steps=0.0)
    assert d.arms[MECH_GROW_DP]["latency_source"] == "measured"
    assert d.arms[MECH_GROW_DP]["latency_s"] == pytest.approx(0.08)


def test_status_block_is_bounded():
    eng = _engine()
    for i in range(40):
        eng.decide([f"10.0.0.{i % 4}"], staleness_steps=0.0)
        eng.health._clock.advance(1.0)
    st = eng.status()
    assert st["mode"] == MODE_ADAPTIVE
    assert len(st["decisions"]) <= 16
    assert set(st) >= {"mode", "quarantined", "hosts", "decisions"}
