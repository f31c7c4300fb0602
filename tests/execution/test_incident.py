"""End-to-end incident forensics on a live engine: a chaos kill_stage
directive drives the normal recovery path through the real train loop, and
exactly ONE incident-<n>.json must be committed — with a phase breakdown
that agrees with the recovery-latency histogram the same run observed
(ISSUE acceptance: within 10%)."""

import glob
import json
import os

import numpy as np
import pytest

from oobleck_tpu.utils import chaos as chaos_mod
from oobleck_tpu.utils import metrics

from tests.execution.test_degrade import _dp2_engine
from tests.execution.test_engine import cache_env, make_engine  # noqa: F401


def _stage_sums(hist_name="oobleck_recovery_latency_seconds"):
    """{stage: sum_s} for the process-global recovery histogram."""
    out = {}
    for s in metrics.registry().histogram(hist_name, "").series():
        out[s["labels"].get("stage", "")] = s["sum"]
    return out


def test_chaos_kill_drives_exactly_one_incident(cache_env, devices8,
                                                tmp_path, monkeypatch):
    monkeypatch.setenv(metrics.ENV_METRICS_DIR, str(tmp_path))
    # As tests/elastic/conftest.py: the policy scores its arms from the
    # latency history in the PROCESS-GLOBAL registry, and a slow reroute
    # some engine module measured earlier on this worker would pick
    # another verb than the degrade path this test follows.
    metrics.registry().clear()
    before = _stage_sums()
    eng = _dp2_engine(devices8, steps=3)
    try:
        chaos_mod.reset("kill_stage=0:1")
        eng.train()  # kill fires at the first loop iteration
    finally:
        chaos_mod.reset("")

    # recovery happened: reroute onto the survivor
    assert eng.host_ips == ["10.0.0.0"]
    assert len(eng.pipelines) == 1

    # exactly one committed incident, however many steps followed
    paths = sorted(glob.glob(str(tmp_path / "incident-*.json")))
    assert [os.path.basename(p) for p in paths] == ["incident-0.json"]
    with open(paths[0]) as f:
        rec = json.load(f)

    assert rec["lost_ip"] == "10.0.0.1"
    assert rec["cause"] == "chaos_kill_stage"
    # the in-process chain: detect -> apply -> first post-recovery step
    for mark in ("detect", "apply_start", "apply_end", "first_step"):
        assert mark in rec["marks"], rec["marks"]
    assert rec["total_s"] > 0
    assert sum(rec["phases"].values()) == pytest.approx(
        rec["total_s"], abs=1e-5)

    # the spans on the incident's trace tell the same story
    names = {s["name"] for s in rec["spans"]}
    assert {"incident.detect", "engine.reconfigure",
            "incident.first_step"} <= names
    assert {"degrade.classify", "degrade.plan", "degrade.apply"} <= names
    assert all(s["trace_id"] == rec["trace_id"] for s in rec["spans"])
    # and the frozen metric families are the recovery/degrade planes only
    assert any(m["name"] == "oobleck_recovery_latency_seconds"
               for m in rec["metrics"])

    # ISSUE acceptance: the incident's phase sum agrees with what the
    # recovery-latency histogram observed for the same recovery (the
    # "degrade" apply + the first-step stages) within 10%.
    after = _stage_sums()
    observed = sum(after.get(stage, 0.0) - before.get(stage, 0.0)
                   for stage in ("degrade", "first_step"))
    assert observed > 0
    assert rec["total_s"] == pytest.approx(observed, rel=0.10)

    # train() dumped the span ring into the sink alongside the incident
    assert glob.glob(str(tmp_path / "spans-*.jsonl"))

    # and training kept going after the incident closed
    assert np.isfinite(eng._train_step())


def test_incident_digest_restaged_on_pipe_failure(monkeypatch):
    """A transient agent-pipe error must not drop the one-shot incident
    digest: it stays staged and rides the next successful push."""
    from types import SimpleNamespace

    from oobleck_tpu.execution.engine import OobleckEngine
    from oobleck_tpu.obs.goodput import GoodputLedger

    monkeypatch.delenv(metrics.ENV_METRICS_DIR, raising=False)
    sent = []

    class FlakyPipe:
        fail = True

        def send(self, msg):
            if self.fail:
                raise OSError("pipe hiccup")
            sent.append(msg)

    digest = {"trace_id": "t1", "lost_ip": "10.0.0.1"}
    eng = SimpleNamespace(step=5, _incident_record=dict(digest),
                          agent_pipe=FlakyPipe(),
                          _ledger=GoodputLedger(), _last_mfu=None)
    OobleckEngine._publish_metrics(eng)
    assert eng._incident_record == digest  # re-staged, not dropped
    eng.agent_pipe.fail = False
    OobleckEngine._publish_metrics(eng)
    assert eng._incident_record is None
    assert sent[-1]["snapshot"]["incident"] == digest
    # no pipe at all: consumed in one push (the JSONL sink owns it)
    eng2 = SimpleNamespace(step=0, _incident_record=dict(digest),
                           agent_pipe=None,
                           _ledger=GoodputLedger(), _last_mfu=None)
    OobleckEngine._publish_metrics(eng2)
    assert eng2._incident_record is None


def test_no_incident_committed_without_failure(cache_env, devices8,
                                               tmp_path, monkeypatch):
    """A clean run must never fabricate forensics."""
    monkeypatch.setenv(metrics.ENV_METRICS_DIR, str(tmp_path))
    eng = make_engine(num_hosts=1, steps=2, devices=devices8[:2],
                      microbatch=2, global_mb=4)
    eng.initialize_distributed()
    eng.instantiate_pipelines(eng.args.job.global_num_microbatch)
    eng.train()
    assert glob.glob(str(tmp_path / "incident-*.json")) == []
    assert eng._incident is None and eng._incident_record is None
