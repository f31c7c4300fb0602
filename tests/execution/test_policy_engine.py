"""Engine-side adaptive fault-tolerance policy: correlated chaos kill
batched into ONE incident (reroute ruled out), forced checkpoint-restore
recovery with honest step rollback, and the live-signal consult path an
in-process detection takes when no master decision rides the wire."""

import numpy as np
import pytest

from oobleck_tpu.policy import MECH_REINSTANTIATE, MECH_RESTORE, PolicyEngine
from oobleck_tpu.utils import chaos as chaos_mod
from oobleck_tpu.utils import metrics

from tests.execution.test_engine import cache_env, make_engine  # noqa: F401


def _live_engine(devices, num_hosts=4, steps=8, **kw):
    eng = make_engine(num_hosts=num_hosts, steps=steps, devices=devices,
                      **kw)
    eng.initialize_distributed()
    eng.instantiate_pipelines(eng.args.job.global_num_microbatch)
    return eng


def _flight(event):
    return [e for e in metrics.flight_recorder().events()
            if e.get("event") == event]


def test_chaos_kill_hosts_is_one_correlated_incident(cache_env, devices8):
    """kill_hosts=<ip1+ip2> must land as ONE incident covering the whole
    blast radius: the policy plane sees both losses, rules out rerouting
    (correlated_failure), and the engine re-plans once — not twice."""
    eng = _live_engine(devices8)
    eng._train_step()
    before = len(_flight("engine_reconfigured"))
    try:
        chaos_mod.reset("kill_hosts=10.0.0.1+10.0.0.3")
        eng._maybe_chaos_kill_hosts()
        assert sorted(ip for ip, _, _ in eng._pending_lost) == [
            "10.0.0.1", "10.0.0.3"]
        # Both pending entries carry the SAME minted incident trace.
        traces = {t["trace_id"] for _, t, _ in eng._pending_lost}
        assert len(traces) == 1
        eng._maybe_reconfigure()
    finally:
        chaos_mod.reset("")

    assert eng.host_ips == ["10.0.0.0", "10.0.0.2"]
    recs = _flight("engine_reconfigured")
    assert len(recs) == before + 1          # one re-plan, not two
    assert recs[-1]["correlated"] is True
    assert sorted(recs[-1]["lost_ips"]) == ["10.0.0.1", "10.0.0.3"]
    decisions = _flight("policy_decision")
    assert decisions, "in-process consult must flight-record its decision"
    last = decisions[-1]
    assert sorted(last["lost_ips"]) == ["10.0.0.1", "10.0.0.3"]
    assert last["infeasible"].get("reroute") == "correlated_failure"
    assert last["mechanism"] == MECH_REINSTANTIATE
    injections = [e for e in _flight("chaos_injection")
                  if e.get("action") == "kill_hosts"]
    assert injections and injections[-1]["ips"] == ["10.0.0.1", "10.0.0.3"]
    # Training survives the correlated loss on the re-planned topology.
    assert np.isfinite(eng._train_step())


def test_forced_restore_rolls_back_to_durable_step(cache_env, devices8,
                                                   tmp_path):
    """OOBLECK_POLICY=restore (benchmark baseline / churn-storm verdict):
    recovery must come from the durable plane — step honestly rolled back
    to the checkpoint, path=restore counted, engine_restored recorded with
    the rolled-back distance — and training must continue."""
    eng = _live_engine(devices8, num_hosts=2, microbatch=2, global_mb=8)
    eng.args.execution.checkpoint_dir = str(tmp_path / "ckpt")
    eng._train_step()
    eng.save_checkpoint(wait=True)
    saved_step = eng.step
    eng._train_step()
    eng._train_step()
    assert eng.step == saved_step + 2

    eng._policy = PolicyEngine(multihost=False, mode=MECH_RESTORE)
    eng.reconfigure("10.0.0.1")

    assert eng.host_ips == ["10.0.0.0"]
    assert eng.step == saved_step           # rolled back, not papered over
    restored = _flight("engine_restored")
    assert restored and restored[-1]["rolled_back_steps"] == 2
    assert restored[-1]["step"] == saved_step
    decisions = _flight("policy_decision")
    assert decisions[-1]["reason"] == "forced:restore"
    assert decisions[-1]["mechanism"] == MECH_RESTORE
    series = metrics.registry().counter(
        "oobleck_engine_reconfigurations_total", "").series()
    assert any(s["labels"].get("path") == "restore" and s["value"] >= 1
               for s in series)
    # The restore fed the policy plane's measured-latency loop.
    assert any(e["mechanism"] == MECH_RESTORE
               for e in _flight("policy_decision_measured"))
    assert np.isfinite(eng._train_step())


def test_restore_infeasible_without_checkpoint_falls_back(cache_env,
                                                          devices8):
    """A forced restore with NO durable checkpoint must not wedge: the
    scorer marks the arm infeasible and recovery re-instantiates."""
    eng = _live_engine(devices8, num_hosts=2, microbatch=2, global_mb=8)
    eng._train_step()
    eng._policy = PolicyEngine(multihost=False, mode=MECH_RESTORE)
    eng.reconfigure("10.0.0.1")
    assert eng.host_ips == ["10.0.0.0"]
    last = _flight("policy_decision")[-1]
    assert last["mechanism"] == MECH_REINSTANTIATE
    assert last["reason"].startswith("forced:restore:infeasible:")
    assert np.isfinite(eng._train_step())


@pytest.mark.parametrize("mode,want", [
    ("adaptive", [(None, ""), ("reinstantiate", "")]),
    ("reroute", [("reroute", "forced:reroute"),
                 ("reinstantiate", "forced:reroute:infeasible:")]),
    ("reinstantiate", [("reinstantiate", "forced:reinstantiate"),
                       ("reinstantiate", "forced:reinstantiate")]),
])
def test_churn_of_one_loss_then_a_correlated_pair(cache_env, devices8, mode,
                                                  want):
    """One host lost, then two at once, on four hosts: no single fixed
    mechanism fits both. The first is reroute territory, the second rules
    rerouting out (correlated_failure) and, with no checkpoint, leaves
    re-instantiation alone: a forced reroute has to fall back, live, with
    the reason on the record; under every mode the last host trains on."""
    eng = _live_engine(devices8)
    eng._policy = PolicyEngine(multihost=False, mode=mode)
    eng._train_step()
    got = []
    for lost in (["10.0.0.3"], ["10.0.0.1", "10.0.0.2"]):
        before = len(_flight("policy_decision"))
        for ip in lost:
            eng.request_reconfiguration(ip)
        eng._maybe_reconfigure()
        decisions = _flight("policy_decision")[before:]
        assert len(decisions) == 1            # one incident, one verdict
        assert sorted(decisions[0]["lost_ips"]) == lost
        got.append(decisions[0])
        assert np.isfinite(eng._train_step())
    for decision, (mechanism, reason) in zip(got, want):
        if mechanism is None:                 # the scorer's to choose
            assert decision["mechanism"] in ("reroute", "reinstantiate")
        else:
            assert decision["mechanism"] == mechanism
        assert decision["reason"].startswith(reason)
    assert "reroute" not in got[0]["infeasible"]
    assert got[1]["infeasible"]["reroute"] == "correlated_failure"
    assert eng.host_ips == ["10.0.0.0"]
